"""The system under test as a child process, and its admin API.

The broker is ``python -m rmqtt_tpu.broker --router xla --config <toml>`` at
its defaults; the toml sets only the listener and API ports. It is started
through ``launch_broker.py``, which runs that module unchanged and adds one
control thread (profiler trace on request, the device's peak memory).
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent  # the checkout
LAUNCHER = HERE / "launch_broker.py"


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Broker:
    def __init__(self, workdir: Path, env: dict, launcher: Path = LAUNCHER) -> None:
        self.port, self.api = free_port(), free_port()
        self.ctl = workdir / "ctl"
        self.ctl.mkdir()
        self.log = workdir / "broker.log"
        conf = workdir / "broker.toml"
        conf.write_text(
            f'[listener]\nhost = "127.0.0.1"\nport = {self.port}\n'
            f'[http_api]\nhost = "127.0.0.1"\nport = {self.api}\n')
        env = dict(env)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (str(ROOT), env.get("PYTHONPATH", "")) if p)
        self.t0 = time.perf_counter()
        with self.log.open("w") as out:
            self.proc = subprocess.Popen(
                [sys.executable, str(launcher), str(self.ctl),
                 "--router", "xla", "--config", str(conf)],
                cwd=workdir, env=env, stdout=out, stderr=subprocess.STDOUT)

    def log_tail(self, n: int = 3000) -> str:
        return self.log.read_text(errors="replace")[-n:]

    def get(self, path: str):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{self.api}{path}", timeout=60.0) as r:
            return json.load(r)

    def snapshot(self) -> dict:
        """The counter surfaces at one instant (as near as two GETs are)."""
        t = time.perf_counter()
        return {"t": t, "stats": self.get("/api/v1/stats")[0]["stats"],
                "device": self.get("/api/v1/device"),
                "metrics": self.get("/api/v1/metrics")["metrics"]}

    def wait_up(self, limit: float = 600.0) -> dict:
        """→ the broker's ``/api/v1/device`` body once the API answers."""
        while True:
            if self.proc.poll() is not None:
                raise SystemExit(f"benchmark: broker exited rc={self.proc.returncode} "
                                 f"at start:\n{self.log_tail()}")
            if time.perf_counter() - self.t0 > limit:
                raise SystemExit(f"benchmark: broker not up after {limit:.0f}s")
            try:
                return self.get("/api/v1/device")
            except (OSError, urllib.error.URLError):
                time.sleep(0.25)

    # ---- the control thread of launch_broker.py, spoken to through files
    def _await(self, name: str, limit: float) -> dict:
        path = self.ctl / name
        end = time.perf_counter() + limit
        while not path.exists():
            if time.perf_counter() > end or self.proc.poll() is not None:
                raise SystemExit(f"benchmark: broker control gave no {name}")
            time.sleep(0.05)
        out = json.loads(path.read_text())
        path.unlink()
        return out

    def trace_start(self) -> None:
        """Returns once the profiler is on."""
        (self.ctl / "trace.on").touch()
        self._await("trace.started", 120.0)

    def trace_stop(self) -> dict:
        """→ {dir, start, stop}: the trace directory and the instants
        (``perf_counter``) between which the profiler was on."""
        (self.ctl / "trace.on").unlink()
        return self._await("trace.done", 300.0)

    def memory_peak_bytes(self) -> int:
        (self.ctl / "mem.req").touch()
        return self._await("mem.json", 60.0)["memory_peak_bytes"]

    def compiling(self) -> bool:
        """Is a compile worker of the program alive? ``ops/hybrid.py`` has a
        match program it has not met compiled on a thread of its own
        (``rmqtt-compile``: started with the first such batch, gone when its
        list is empty) while the host mirror answers, and tracing shares the
        GIL with the event loop: a window that holds such a compile reads
        another broker. The counters say only that a compile has *ended*
        (``compile.traces``); that one is under way, only the thread does."""
        (self.ctl / "threads.req").touch()
        names = self._await("threads.json", 60.0)["names"]
        return any("compile" in n for n in names)

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)

"""--workers N: SO_REUSEPORT worker processes as a localhost broadcast
cluster (multi-core host data plane; reference scales via a multi-thread
tokio accept loop, `/root/reference/rmqtt/src/server.rs:229`)."""

import os
import socket
import subprocess
import sys
import time

import pytest


def _pkt(t, payload):
    return bytes([t, len(payload)]) + payload


def _connect(port, cid):
    s = socket.create_connection(("127.0.0.1", port), timeout=5)
    vh = b"\x00\x04MQTT\x04\x02\x00\x3c" + len(cid).to_bytes(2, "big") + cid
    s.sendall(_pkt(0x10, vh))
    assert s.recv(4)[0] == 0x20
    return s


def _round(subs, pubs, topic, wait):
    """Every publisher sends one message to ``topic``; → deliveries seen
    across all subscribers within ``wait`` seconds each."""
    for i, p in enumerate(pubs):
        p.sendall(_pkt(0x30, len(topic).to_bytes(2, "big") + topic + b"m%d" % i))
    got = 0
    for s in subs:
        buf = b""
        deadline = time.time() + wait
        while buf.count(topic) < len(pubs) and time.time() < deadline:
            try:
                buf += s.recv(4096)
            except socket.timeout:
                break
        got += buf.count(topic)
    return got


@pytest.mark.timeout(150)
def test_two_workers_share_port_and_deliver_across():
    port = 18861
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.getcwd(), env.get("PYTHONPATH", "")) if p
    )
    proc = subprocess.Popen(
        [sys.executable, "-m", "rmqtt_tpu.broker", "--port", str(port),
         "--workers", "2", "--cluster-port-base", str(port + 500)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        for _ in range(160):
            try:
                _connect(port, b"probe").close()
                break
            except OSError:
                time.sleep(0.25)
        else:
            pytest.fail("workers never came up")
        subs = []
        for i in range(16):
            s = _connect(port, b"s%d" % i)
            s.sendall(_pkt(0x82, b"\x00\x01\x00\x07sport/+\x00"))
            assert s.recv(5)[0] == 0x90
            s.settimeout(8)
            subs.append(s)
        pubs = [_connect(port, b"p%d" % i) for i in range(4)]
        # the workers peer up (connect + first heartbeats) some time after
        # their listeners open — seconds on a loaded box. Warm-up rounds
        # wait for that; the asserted round below runs once, unchanged.
        deadline = time.time() + 40
        k = 0
        while time.time() < deadline:
            k += 1
            if _round(subs, pubs, b"sport/w%03d" % k, wait=3) == len(subs) * len(pubs):
                break
        got = _round(subs, pubs, b"sport/news", wait=10)
        assert got == len(subs) * len(pubs), f"only {got} deliveries"
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()


@pytest.mark.timeout(120)
def test_workers_with_xla_router():
    """The full deployment combo: SO_REUSEPORT workers behind the fabric,
    the OWNER running the XlaRouter (adaptive hybrid + pipelined
    RoutingService) and holding the device; the other worker matches on it
    over the fabric, so cross-worker delivery proves the owner served."""
    port = 18871
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.getcwd(), env.get("PYTHONPATH", "")) if p
    )
    proc = subprocess.Popen(
        [sys.executable, "-m", "rmqtt_tpu.broker", "--port", str(port),
         "--workers", "2", "--fabric", "--router", "xla"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        for _ in range(240):
            try:
                _connect(port, b"probe").close()
                break
            except OSError:
                time.sleep(0.25)
        else:
            pytest.fail("xla workers never came up")
        time.sleep(1.5)
        subs = []
        for i in range(8):
            s = _connect(port, b"xs%d" % i)
            # pid 1, filter "xla/#", qos 0
            s.sendall(_pkt(0x82, b"\x00\x01" + b"\x00\x05xla/#" + b"\x00"))
            assert s.recv(5)[0] == 0x90
            s.settimeout(8)
            subs.append(s)
        pubs = [_connect(port, b"xp%d" % i) for i in range(4)]
        t = b"xla/t"
        for i, p in enumerate(pubs):
            p.sendall(_pkt(0x30, len(t).to_bytes(2, "big") + t + b"m%d" % i))
        got = 0
        for s in subs:
            buf = b""
            deadline = time.time() + 10
            while buf.count(t) < len(pubs) and time.time() < deadline:
                try:
                    buf += s.recv(4096)
                except socket.timeout:
                    break
            got += buf.count(t)
        assert got == len(subs) * len(pubs), f"only {got} xla deliveries"
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()


def test_workers_xla_without_fabric_refused_at_launch():
    """One process per chip: N peered workers would each open the device,
    so the supervisor refuses the combination and names the way out."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.getcwd(), env.get("PYTHONPATH", "")) if p
    )
    r = subprocess.run(
        [sys.executable, "-m", "rmqtt_tpu.broker", "--port", "18891",
         "--workers", "2", "--router", "xla"],
        env=env, capture_output=True, text=True, timeout=60,
    )
    assert r.returncode != 0
    assert "--fabric" in r.stderr


@pytest.mark.parametrize("worker_id,router_cls", [(1, "XlaRouter"),
                                                 (2, "NativeRouter")])
def test_fabric_xla_backend_in_owner_only(tmp_path, worker_id, router_cls):
    """``--workers N --fabric --router xla``: only the fabric owner builds
    the device router (the first backend touch); a non-owner gets the
    host-side native router for its FabricUnavailable degradation."""
    from rmqtt_tpu.broker.context import BrokerConfig, ServerContext

    ctx = ServerContext(BrokerConfig(
        router="xla", node_id=worker_id, fabric_enable=True,
        fabric_dir=str(tmp_path), fabric_worker_id=worker_id))
    assert type(ctx.router).__name__ == router_cls
    assert hasattr(ctx.router, "device_info") == (worker_id == 1)

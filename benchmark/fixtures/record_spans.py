#!/usr/bin/env python3
"""How ``trace_spans.xplane.pb`` was recorded (on one TPU v5e, PR 25).

    python3 benchmark/fixtures/record_spans.py <out dir>     # on the chip

A small stand-in for a traced broker, under the same profiler options as
``harness/launch_broker.py``: the main thread plays the event loop
(``rmqtt/loop.idle``, ``rmqtt/ingress.decode``, ``rmqtt/routing.match.side``,
``rmqtt/deliver.send`` spans, and stretches with no span at all), a second
thread plays an executor thread that serves device batches (the four
``rmqtt/matcher.*`` spans with ``batch=<seq>`` around a run of a jitted
program whose phases carry the named scopes ``scan`` / ``compact`` /
``resolve`` / ``sort`` / ``counts``), and a second jitted program carries no
scope. The device idles between the runs, so ``harness/host_spans.py`` has
gaps to attribute by every branch of its rule and device time to sum by
scope. What it must find is ``trace_spans.expected.json``, which
``handcheck_spans.py`` writes from a reading of its own.
"""

import json
import shutil
import sys
import threading
import time
from pathlib import Path


def record(out: Path) -> None:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.profiler import TraceAnnotation

    @jax.jit
    def match_fused_small(x):
        with jax.named_scope("scan"):
            words = jnp.cumsum(x @ x.T, axis=1)
        words = lax.optimization_barrier(words)
        with jax.named_scope("compact"):
            flat = words.ravel()
            idx = jnp.where(flat > 3.0, jnp.arange(flat.size), flat.size)
            packed = jnp.zeros((4096,), jnp.float32).at[idx % 8192].set(
                flat, mode="drop")
        with jax.named_scope("resolve"):
            fids = jnp.take(flat, (packed.astype(jnp.int32) * 7) % flat.size)
        with jax.named_scope("sort"):
            fids = lax.sort(fids)
        with jax.named_scope("counts"):
            return jnp.concatenate([fids, jnp.sum(words > 3.0, axis=1)
                                    .astype(jnp.float32)])

    @jax.jit
    def other_small(x):
        return jnp.tanh(x).sum()

    x = jnp.ones((256, 256), jnp.float32)
    jax.block_until_ready([match_fused_small(x), other_small(x)])  # compile outside

    go, done = threading.Event(), threading.Event()
    state = {"seq": 0, "stop": False}

    def executor() -> None:
        while True:
            go.wait()
            go.clear()
            if state["stop"]:
                return
            seq = state["seq"]
            with TraceAnnotation("rmqtt/matcher.encode", batch=seq, n=128):
                time.sleep(0.001)
            with TraceAnnotation("rmqtt/matcher.dispatch", batch=seq, n=128):
                y = match_fused_small(x)
            with TraceAnnotation("rmqtt/matcher.fetch", batch=seq, n=128):
                jax.block_until_ready(y)
                time.sleep(0.001)
            with TraceAnnotation("rmqtt/matcher.decode", batch=seq, n=128):
                time.sleep(0.001)
            done.set()

    worker = threading.Thread(target=executor, name="asyncio_0")
    worker.start()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    tmp = out / "trace_tmp"
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    for seq in range(1, 7):
        with TraceAnnotation("rmqtt/loop.idle"):
            time.sleep(0.003)
        with TraceAnnotation("rmqtt/ingress.decode", n=40):
            time.sleep(0.001)
        with TraceAnnotation("rmqtt/routing.match.side", batch=seq * 10, n=5):
            time.sleep(0.002)
        time.sleep(0.002)  # the loop at work with no span
        state["seq"] = seq
        go.set()  # a device batch, on the other thread, while ...
        with TraceAnnotation("rmqtt/deliver.send"):
            time.sleep(0.006)  # ... the loop delivers
        done.wait()
        done.clear()
        jax.block_until_ready(other_small(x))
        time.sleep(0.002)
    jax.profiler.stop_trace()
    state["stop"] = True
    go.set()
    worker.join()
    (pb,) = tmp.rglob("*.xplane.pb")
    shutil.copy(pb, out / "trace_spans.xplane.pb")
    shutil.rmtree(tmp)
    print(json.dumps({"recorded": str(out / "trace_spans.xplane.pb"),
                      "bytes": (out / "trace_spans.xplane.pb").stat().st_size,
                      "platform": jax.devices()[0].platform}))


if __name__ == "__main__":
    Path(sys.argv[1]).mkdir(parents=True, exist_ok=True)
    record(Path(sys.argv[1]))

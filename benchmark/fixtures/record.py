#!/usr/bin/env python3
"""How ``trace_small.xplane.pb`` was recorded (on one TPU v5e, PR 24).

    python3 benchmark/fixtures/record.py <out dir>     # on the chip

A few runs of two small jitted programs under the same profiler options as
``harness/launch_broker.py``, with idle gaps between them, so that the
reduction has modules to name, operations to unite and gaps to find. The
numbers the reduction must give on it are ``trace_small.expected.json``,
which ``handcheck.py`` writes from a reading of its own.
"""

import json
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent


def record(out: Path) -> None:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def match_small(x):
        return jnp.sort(x @ x.T, axis=-1)[:, :8]

    @jax.jit
    def other_small(x):
        return jnp.tanh(x).sum()

    x = jnp.ones((256, 256), jnp.float32)
    jax.block_until_ready([match_small(x), other_small(x)])  # compile outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    tmp = out / "trace_tmp"
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    for _ in range(6):
        jax.block_until_ready(match_small(x))
        time.sleep(0.01)
        jax.block_until_ready(other_small(x))
        time.sleep(0.02)
    jax.profiler.stop_trace()
    (pb,) = tmp.rglob("*.xplane.pb")
    shutil.copy(pb, out / "trace_small.xplane.pb")
    shutil.rmtree(tmp)
    print(json.dumps({"recorded": str(out / "trace_small.xplane.pb"),
                      "bytes": (out / "trace_small.xplane.pb").stat().st_size,
                      "platform": jax.devices()[0].platform}))


if __name__ == "__main__":
    Path(sys.argv[1]).mkdir(parents=True, exist_ok=True)
    record(Path(sys.argv[1]))

"""Share of the window's large batches (over ``hybrid_max`` topics) that the
host mirror answered because their device program was still being compiled off
the routing path (``hybrid_compiling_side`` over ``hybrid_large_batches`` on
``/api/v1/device``). 0 where no large batch was routed; absent where the broker
has no such counters."""

from _counters import backend

SPEC = {"layer": "hybrid ops/hybrid.py router/xla.py", "unit": "%",
        "source": "program_counter", "moves": "deliveries_per_s"}


def read(run: dict):
    side, large = backend(run, "hybrid_compiling_side"), backend(run, "hybrid_large_batches")
    if side is None or large is None:
        return None
    return 100.0 * (side[1][0] - side[0][0]) / max(1, large[1] - large[0])

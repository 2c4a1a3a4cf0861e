"""Share of the match programs' device time spent after the scan: the operations
under the named scopes ``compact`` + ``resolve`` + ``sort`` over the summed device
time of the ``jit_match_*`` modules in the same trace. Absent where the trace
holds no scope or no such module."""

from harness import host_spans

SPEC = {"layer": "kernels (jitted match programs)", "unit": "%",
        "source": "device_trace", "moves": "deliveries_per_s"}


def read(run: dict):
    red = host_spans.from_run(run)
    if not red or not red["match_s"] or not set(red["scope_s"]) - {host_spans.UNSCOPED}:
        return None
    tail = sum(red["scope_s"].get(k, 0.0) for k in ("compact", "resolve", "sort"))
    return 100.0 * tail / red["match_s"]

"""99th percentile, over the WINDOW's samples only, of the time a publish's
PUBACK / PUBREC was held for deliver-queue room (``fanout.hold``: the first
full queue its fan-out met → every queue it overfilled is back under its
limit, or gave its consumer up; ``broker/session.py``
``Session._enqueue_crowded``). From the deltas of the histogram's cumulative
log2 buckets: the upper edge of the bucket that holds the percentile, exact to
a factor of 2. 0 where no publish was held; absent without the buckets (a
program from before the hold)."""

from _stages import NBUCKETS, delta, p99_ms

SPEC = {"layer": "fan-out backpressure broker/shared.py session.py", "unit": "ms",
        "source": "program_span", "moves": "puback_p99_ms"}


def read(run: dict):
    counts = [delta(run, f"hist_fanout_hold_b{i:02d}") for i in range(NBUCKETS)]
    if any(c is None for c in counts):
        return None
    return p99_ms(run, "fanout.hold") if sum(counts) else 0.0

"""Window deltas of the broker's stage layer, shared by the readers here.

``/api/v1/stats`` carries, per served-path stage, the cumulative
``stage_<name>_count`` and ``stage_<name>_busy_ms_total`` (loop thread) and,
for three histograms, the 40 cumulative log2 bucket counts
``hist_<name>_b<i>`` (``rmqtt_tpu/broker/telemetry.py``). Only the
difference between two snapshots is a stretch of the window's (``_ends``).
A broker from before PR 25 has none of these keys: every helper then gives
None, and so does the reader.
"""

NBUCKETS = 40


def _ends(run: dict):
    """The two snapshots the deltas are taken between: those taken inside
    the traced span, just after the profiler started and just before it was
    stopped. (Stopping the profiler takes tens of seconds once the trace
    holds the program's spans, and the window's closing snapshot comes only
    after it: the broker idles through that stretch, which would dilute
    every share.) A run without a trace has only the window's two."""
    tr = run.get("trace")
    return (tr["before"], tr["after"]) if tr else (run["before"], run["after"])


def window_s(run: dict) -> float:
    """Seconds between the two snapshots the deltas are taken from."""
    before, after = _ends(run)
    return after["t"] - before["t"]


def delta(run: dict, key: str):
    before, after = _ends(run)
    a, b = after["stats"], before["stats"]
    return a[key] - b[key] if key in a and key in b else None


def busy_pct(run: dict, stages) -> float | None:
    """Loop-thread busy time of ``stages`` (dotted names) as a share of the
    window; None where the keys are missing or none of the stages ran."""
    ms = passes = 0
    for name in stages:
        key = "stage_" + name.replace(".", "_")
        d_ms, d_n = delta(run, key + "_busy_ms_total"), delta(run, key + "_count")
        if d_ms is None or d_n is None:
            return None
        ms, passes = ms + d_ms, passes + d_n
    return 100.0 * ms / (window_s(run) * 1e3) if passes else None


def p99_ms(run: dict, hist: str) -> float | None:
    """The 99th percentile of the samples ``hist`` took inside the window:
    the upper edge of the log2 bucket that holds it (``2**(i+1)`` ns), so
    exact to a factor of 2. None where the keys are missing or no sample."""
    key = "hist_" + hist.replace(".", "_") + "_b"
    counts = [delta(run, f"{key}{i:02d}") for i in range(NBUCKETS)]
    if any(c is None for c in counts) or not sum(counts):
        return None
    rank, acc = -(-99 * sum(counts) // 100), 0  # ceil
    for i, c in enumerate(counts):
        acc += c
        if acc >= rank:
            return (1 << (i + 1)) / 1e6


def matcher_ms(run: dict, stage: str) -> float | None:
    """Host milliseconds of the ``rmqtt/matcher.<stage>`` spans per run of
    a ``jit_match_*`` program, both counted in the same trace. None where
    the trace holds no span or no such run."""
    from harness import host_spans

    red = host_spans.from_run(run)
    if not red or not red["match_runs"] or "matcher." + stage not in red["spans"]:
        return None
    return red["spans"]["matcher." + stage][1] * 1e3 / red["match_runs"]

"""Loop-thread busy share of the window that the broker's own stats and
hot-key planes take: the ``stats.snapshot`` stage (``ServerContext.stats()``:
the history sampler's 5 s tick, every API scrape, the benchmark's own
snapshots) and the ``hotkeys.fold`` stage (``HotkeysService.drain()``: the
buffered publish, dispatch and delivery events folded into the sketches,
wherever it runs; a fold inside another stage is left out of that stage).
Absent on a broker without the two stages (a program from before them)."""

from _stages import busy_pct

SPEC = {"layer": "broker event loop (one Python thread)", "unit": "%",
        "source": "program_counter", "moves": "puback_p99_ms"}


def read(run: dict):
    return busy_pct(run, ("stats.snapshot", "hotkeys.fold"))

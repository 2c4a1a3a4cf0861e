"""Loop-thread busy share of the window in ``routing.plan`` + ``routing.match.side``
(the host trie mirror, for the batches the loop thread matched itself) +
``routing.resolve`` (futures set, cache fill). Executor-thread time of
``routing.match.side`` is kept apart by the program and not counted here.
Absent where the broker has no such counters or none of the stages ran."""

from _stages import busy_pct

SPEC = {"layer": "routing service broker/routing.py", "unit": "%",
        "source": "program_span", "moves": "deliveries_per_s"}


def read(run: dict):
    return busy_pct(run, ('routing.plan', 'routing.match.side', 'routing.resolve'))

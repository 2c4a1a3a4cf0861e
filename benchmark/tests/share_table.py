"""``share_table``: a small table with ``$share`` groups, for the tests here.

Imported by ``harness/generators.py`` where ``BENCHMARK_TEST_GENERATOR``
names this file, which only a test sets: no configuration can name it.

``filters()``: ``subscriptions`` rows, sorted. One in ten of a hundred is a
group ``$share/g/s/<k>`` of ``MEMBERS`` equal rows (so ``MEMBERS``
consecutive subscribers), four rows are the group ``$share/w/s/+`` that
every ``s/<k>`` topic also matches (its members may be members of some
``g`` group too), and the rest are plain exact rows ``p/<k>``.
``topic_stream()``: an ``s/<k>`` of a group or a ``p/<k>``, half and half,
so every publish has a receiver.
"""

from __future__ import annotations

import random

from harness.generators import register

MEMBERS = 4


@register("share_table")
class ShareTable:
    def __init__(self, seed: int, config: dict) -> None:
        self.n = config["subscriptions"]
        groups = self.n // (10 * MEMBERS)
        self.shared = [f"s/{k}" for k in range(groups)]
        self.plain = [f"p/{k}" for k in range(self.n - MEMBERS * (groups + 1))]

    def filters(self) -> list:
        rows = [f"$share/g/{f}" for f in self.shared for _ in range(MEMBERS)]
        rows += ["$share/w/s/+"] * MEMBERS + self.plain
        return sorted(rows)

    def topic_stream(self, stream_seed: int):
        rng = random.Random(stream_seed)
        while True:
            pool = self.shared if rng.random() < 0.5 else self.plain
            yield pool[rng.randrange(len(pool))]

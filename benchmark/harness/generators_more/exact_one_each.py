"""``exact_one_each``: upstream rmqtt's single-node benchmark table, one exact
topic a device (``docs/en_US/benchmark-testing.md:212-220`` as BASELINE.md
keeps it: 1,000,000 subscribers, 40 publishers; the topic shape is assumed,
the configuration's file says so).

``filters()``: ``subscriptions`` distinct two-level exact filters
``iot/<n>``, the device numbers drawn from the seed without replacement out
of 0..9,999,999, sorted. ``topic_stream()``: a subscribed topic drawn
uniformly, so every publish has exactly one subscriber and deliveries a
second are publishes a second, which is how upstream's 150K msg/s reads.
"""

from __future__ import annotations

import random

from harness.generators import register

DEVICE_NUMBERS = 10_000_000


@register("exact_one_each")
class ExactOneEach:
    def __init__(self, seed: int, config: dict) -> None:
        self.seed, self.n = seed, config["subscriptions"]
        self._filters = None

    def filters(self) -> list:
        if self._filters is None:
            numbers = random.Random(self.seed).sample(range(DEVICE_NUMBERS), self.n)
            self._filters = sorted(f"iot/{n}" for n in numbers)
        return self._filters

    def topic_stream(self, stream_seed: int):
        rng = random.Random(stream_seed)
        filters = self.filters()
        n = len(filters)
        while True:
            yield filters[rng.randrange(n)]

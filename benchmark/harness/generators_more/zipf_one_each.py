"""``exact_zipf``: ``exact_one_each``'s table under a Zipf-skewed publish
stream (BASELINE.json configs[3], "Zipf-skewed publish stream", read as
YCSB's core workload ``requestdistribution=zipfian``: the constant 0.99 of
its ``ZipfianGenerator``, and the scrambled variant that spreads the hot
items over the key space).

``filters()``: exactly ``exact_one_each``'s table for the seed (the class is
imported, not copied). ``topic_stream()``: rank r in 1..N (N the whole
table) drawn with probability r**-THETA / H(N, THETA), by inverse CDF over a
cumulative table built once; rank r is row ``scramble[r - 1]`` of the sorted
table, a permutation drawn from the TABLE's seed, so every publisher process
agrees on which topics are hot. Every topic it yields is subscribed.
"""

from __future__ import annotations

import numpy as np

from harness.generators import register
from harness.generators_more.exact_one_each import ExactOneEach

THETA = 0.99          # YCSB ZipfianGenerator.ZIPFIAN_CONSTANT
SCRAMBLE = 0x5C4A3B1E  # mixed into the table seed: the scramble's own stream
DRAW = 8192            # uniforms drawn at a time


@register("exact_zipf")
class ExactZipf(ExactOneEach):
    def __init__(self, seed: int, config: dict) -> None:
        super().__init__(seed, config)
        self._scramble = None

    def scramble(self) -> np.ndarray:
        """Row of the sorted table for each rank, hottest first."""
        if self._scramble is None:
            self._scramble = np.random.default_rng([self.seed, SCRAMBLE]).permutation(self.n)
        return self._scramble

    def hottest(self, k: int) -> list:
        """The topics of ranks 1..k."""
        filters = self.filters()
        return [filters[i] for i in self.scramble()[:k]]

    def topic_stream(self, stream_seed: int):
        filters, scramble = self.filters(), self.scramble()
        cdf = np.cumsum(np.arange(1, self.n + 1, dtype=np.float64) ** -THETA)
        cdf /= cdf[-1]
        rng = np.random.default_rng(stream_seed)
        while True:
            ranks = np.minimum(np.searchsorted(cdf, rng.random(DRAW), side="right"), self.n - 1)
            for row in scramble[ranks].tolist():
                yield filters[row]

"""Filter tables and publish streams, made from the seed.

A configuration names its generator by the ``generator`` key; ``REGISTRY``
maps that name to a class. A later PR adds a generator as a module under
``harness/generators_more/`` that calls ``register`` — every module there is
imported by ``load`` — and edits nothing here.

A generator is built as ``G(seed, config)`` and gives ``filters()`` (the
whole table, sorted, the same in every process that asks) and
``topic_stream(stream_seed)``: an endless iterator of publish topics.

``mixed_tree`` and ``single_plus`` are copies of ``bench.gen_mixed`` /
``bench._tree_topic`` / ``bench.gen_single_plus`` as the accepted tree has
them (PERF.md, Open questions, lists the originals).
"""

from __future__ import annotations

import importlib
import random
from pathlib import Path

REGISTRY: dict = {}


def register(name: str):
    def deco(cls):
        if name in REGISTRY:
            raise ValueError(f"generator {name!r} registered twice")
        REGISTRY[name] = cls
        return cls
    return deco


def load(name: str):
    more = Path(__file__).resolve().parent / "generators_more"
    if more.is_dir():
        for f in sorted(more.glob("*.py")):
            if f.stem != "__init__":
                importlib.import_module(f"harness.generators_more.{f.stem}")
    try:
        return REGISTRY[name]
    except KeyError:
        raise SystemExit(f"benchmark: no generator {name!r} "
                         f"(known: {sorted(REGISTRY)})") from None


VOCAB6 = [50, 80, 100, 150, 200, 400]  # per-level vocabulary of the 6-level tree


def _tree_topic(rng, depth=6):
    return "/".join(f"v{d}_{rng.randrange(VOCAB6[d])}" for d in range(depth))


@register("mixed_tree")
class MixedTree:
    """BASELINE.json configs[2]: mixed ``+``/``#`` filters over a 6-level
    topic tree; publish topics uniform over the tree's leaves."""

    def __init__(self, seed: int, config: dict) -> None:
        self.seed, self.n = seed, config["subscriptions"]

    def filters(self) -> list:
        rng = random.Random(self.seed)
        filters = set()
        while len(filters) < self.n:
            depth = rng.randint(2, 6)
            levels = [f"v{d}_{rng.randrange(VOCAB6[d])}" for d in range(depth)]
            r = rng.random()
            if r < 0.35:  # sprinkle +
                for _ in range(rng.randint(1, 2)):
                    levels[rng.randrange(depth)] = "+"
            if r >= 0.25 and r < 0.55:
                levels[-1] = "#"
            filters.add("/".join(levels))
        return sorted(filters)

    def topic_stream(self, stream_seed: int):
        rng = random.Random(stream_seed)
        while True:
            yield _tree_topic(rng)


@register("single_plus")
class SinglePlus:
    """BASELINE.json configs[1]: filters of depth 3-5 with one ``+`` each.
    The publish stream (assumed; BASELINE names none): draw a subscribed
    filter uniformly and fill its ``+`` from that level's vocabulary, so
    every publish has at least one subscriber."""

    def __init__(self, seed: int, config: dict) -> None:
        self.seed, self.n = seed, config["subscriptions"]
        self._filters = None

    def _vocab(self, d: int) -> int:
        return max(4, self.n >> (8 - d))

    def filters(self) -> list:
        if self._filters is None:
            rng = random.Random(self.seed)
            filters = set()
            while len(filters) < self.n:
                depth = rng.randint(3, 5)
                levels = [f"l{d}n{rng.randrange(self._vocab(d))}"
                          for d in range(depth)]
                levels[rng.randrange(depth)] = "+"
                filters.add("/".join(levels))
            self._filters = sorted(filters)
        return self._filters

    def topic_stream(self, stream_seed: int):
        rng = random.Random(stream_seed)
        filters = self.filters()
        while True:
            levels = filters[rng.randrange(len(filters))].split("/")
            d = levels.index("+")
            levels[d] = f"l{d}n{rng.randrange(self._vocab(d))}"
            yield "/".join(levels)

"""The plain reference in a process of its own.

Built beside the table load (a million inserts take some ten seconds of
one core), asked once after the window: for each publish topic, which
subscriber connections must receive it. Filter i of the sorted table
belongs to subscriber ``i % subscribers`` — the rule the fleet loads by.
"""

from __future__ import annotations

import multiprocessing as mp
import time
from array import array

import numpy as np

from harness import generators
from harness.trie import Trie


def _main(pipe, generator: str, seed: int, config: dict, subscribers: int) -> None:
    t0 = time.perf_counter()
    trie = Trie()
    for i, f in enumerate(generators.load(generator)(seed, config).filters()):
        trie.insert(f, i % subscribers)
    pipe.send({"build_s": time.perf_counter() - t0})
    while True:
        topics = pipe.recv()
        if topics is None:
            return
        counts, flat = array("l"), array("l")
        for t in topics:
            subs = sorted(set(trie.match(t)))
            counts.append(len(subs))
            flat.extend(subs)
        pipe.send((counts.tobytes(), flat.tobytes()))


class Reference:
    def __init__(self, generator: str, seed: int, config: dict, subscribers: int):
        ctx = mp.get_context("spawn")
        self.pipe, theirs = ctx.Pipe()
        self.proc = ctx.Process(target=_main, daemon=True, args=(
            theirs, generator, seed, config, subscribers))
        self.proc.start()
        theirs.close()
        self.build_s = None

    def expected(self, topics: list, limit: float = 600.0):
        """→ (counts, subscribers): for topic k, ``counts[k]`` subscriber
        indices, laid end to end in ``subscribers``."""
        if self.build_s is None:
            if not self.pipe.poll(limit):
                raise RuntimeError("the reference trie was not built in time")
            self.build_s = self.pipe.recv()["build_s"]
        self.pipe.send(topics)
        if not self.pipe.poll(limit):
            raise RuntimeError("the reference gave no answer in time")
        counts, flat = self.pipe.recv()
        return (np.frombuffer(counts, dtype=np.dtype("l")),
                np.frombuffer(flat, dtype=np.dtype("l")))

    def close(self) -> None:
        try:
            self.pipe.send(None)
        except OSError:
            pass
        self.proc.join(20.0)
        if self.proc.is_alive():
            self.proc.kill()
            self.proc.join(20.0)
        self.pipe.close()

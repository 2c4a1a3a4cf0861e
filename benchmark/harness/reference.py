"""The plain reference in a process of its own.

Built beside the table load (a million inserts take some ten seconds of
one core), asked once after the window: for each publish topic, which
subscriber connections must receive it. Filter i of the sorted table
belongs to subscriber ``i % subscribers`` — the rule the fleet loads by.

Shared subscriptions (MQTT 5.0 §4.8.2), written from the specification: a
row ``$share/{ShareName}/{filter}`` makes its owner a member of the group
(ShareName, filter), and ONE member of each group whose filter matches a
topic receives it. Equal rows are members of one group; ``$share/g/a/+``
and ``$share/g/a/#`` are two groups, as are ``$share/g/x`` and
``$share/h/x``. The filter after the ShareName is matched as any filter is,
so ``$share/g/#`` does not match ``$SYS/x``. A table with no ``$share`` row
is answered as before, at the same cost.

A broker keys a subscription by its owner and the filter it routes on, the
one after any ``$share/{ShareName}/``: an owner that holds ``x`` and
``$share/g/x``, or two groups of one filter, holds ONE subscription there,
and no comparison can judge what it should receive. Such a table is refused
in words before the window.
"""

from __future__ import annotations

import multiprocessing as mp
import time
from array import array

import numpy as np

from harness import generators, spec
from harness.trie import Trie

SHARE = "$share"


class Refused(ValueError):
    """A table the comparison cannot judge."""


def split_shared(row: str):
    """``$share/{ShareName}/{filter}`` → (ShareName, filter); any other row
    → (None, row). A ``$share`` row without a ShareName or a filter is no
    subscription a broker takes, so it is refused."""
    if row != SHARE and not row.startswith(SHARE + "/"):
        return None, row
    share, _, inner = row[len(SHARE) + 1:].partition("/")
    if not share or not inner:
        raise Refused(f"the row {row!r} is a $share filter without a ShareName "
                      "or a filter after it")
    return share, inner


class Table:
    """The reference's answer for one table: a trie of the plain rows and,
    where the table has ``$share`` rows, a trie of its groups' filters."""

    def __init__(self, filters: list, subscribers: int) -> None:
        self.trie = Trie()
        self.groups = None  # Trie of group filters → group index
        self.members = None  # (offsets, members): group g holds members[offsets[g]:offsets[g + 1]]
        if not any(f.startswith(SHARE) for f in filters):
            for i, f in enumerate(filters):
                self.trie.insert(f, i % subscribers)
            return
        held = {}  # (owner, filter routed on) → the first row that holds it
        index, owners = {}, []
        self.groups = Trie()
        for i, f in enumerate(filters):
            owner = i % subscribers
            share, inner = split_shared(f)
            first = held.setdefault((owner, inner), i)
            if first != i:
                raise Refused(
                    f"subscriber {owner} holds the rows {filters[first]!r} and {f!r}, both on "
                    f"the filter {inner!r}: a broker keeps one subscription of an "
                    "owner a filter, so no comparison can judge this table")
            if share is None:
                self.trie.insert(f, owner)
                continue
            g = index.get((share, inner))
            if g is None:
                g = index[share, inner] = len(owners)
                owners.append([])
                self.groups.insert(inner, g)
            owners[g].append(owner)
        sizes = np.array([len(o) for o in owners], dtype=np.dtype("l"))
        self.members = (np.concatenate(([0], np.cumsum(sizes))).astype(np.dtype("l")),
                        np.array([m for o in owners for m in o], dtype=np.dtype("l")))

    def answer(self, topics: list) -> tuple:
        """→ (counts, subscribers) of the plain rows and, where the table has
        groups, (group counts, groups) after them, each laid end to end."""
        counts, flat = array("l"), array("l")
        for t in topics:
            subs = sorted(set(self.trie.match(t)))
            counts.append(len(subs))
            flat.extend(subs)
        if self.groups is None:
            return counts, flat
        gcounts, gflat = array("l"), array("l")
        for t in topics:
            gids = sorted(set(self.groups.match(t)))
            gcounts.append(len(gids))
            gflat.extend(gids)
        return counts, flat, gcounts, gflat

    def expected(self, topics: list):
        """``Reference.expected`` of this table, in this process."""
        return as_expected([np.frombuffer(a, dtype=np.dtype("l")) for a in self.answer(topics)],
                           self.members)


def as_expected(parts: list, members):
    """→ (counts, subscribers), and a third item (group counts, groups,
    offsets, members) where the table has groups."""
    if members is None:
        return parts[0], parts[1]
    return parts[0], parts[1], (parts[2], parts[3], *members)


def _main(pipe, generator: str, seed: int, config: dict, subscribers: int) -> None:
    t0 = time.perf_counter()
    try:
        table = Table(generators.load(generator)(seed, config).filters(), subscribers)
    except Refused as e:
        pipe.send({"refused": str(e)})
        return
    members = None if table.members is None else tuple(a.tobytes() for a in table.members)
    pipe.send({"build_s": time.perf_counter() - t0, "members": members})
    while True:
        topics = pipe.recv()
        if topics is None:
            return
        pipe.send(tuple(a.tobytes() for a in table.answer(topics)))


class Reference:
    def __init__(self, generator: str, seed: int, config: dict, subscribers: int):
        ctx = mp.get_context("spawn")
        self.pipe, theirs = ctx.Pipe()
        self.proc = ctx.Process(target=_main, daemon=True, args=(
            theirs, generator, seed, config, subscribers))
        self.proc.start()
        theirs.close()
        self.build_s = None
        self.members = None

    def built(self, limit: float = 600.0) -> None:
        """Wait until the table is built; a table the comparison cannot
        judge ends the run here, in words."""
        if self.build_s is not None:
            return
        if not self.pipe.poll(limit):
            raise RuntimeError("the reference trie was not built in time")
        msg = self.pipe.recv()
        if "refused" in msg:
            spec.fail(f"the reference refuses the table: {msg['refused']}. "
                      "No result is printed.")
        self.build_s = msg["build_s"]
        if msg["members"] is not None:
            self.members = [np.frombuffer(b, dtype=np.dtype("l")) for b in msg["members"]]

    def expected(self, topics: list, limit: float = 600.0):
        """→ (counts, subscribers): for topic k, ``counts[k]`` subscriber
        indices, laid end to end in ``subscribers``. Where the table has
        ``$share`` groups, a third item (group counts, groups, offsets,
        members): topic k matches ``group counts[k]`` groups, laid end to end
        in ``groups``, and group g's members are
        ``members[offsets[g]:offsets[g + 1]]``."""
        self.built(limit)
        self.pipe.send(topics)
        if not self.pipe.poll(limit):
            raise RuntimeError("the reference gave no answer in time")
        parts = [np.frombuffer(b, dtype=np.dtype("l")) for b in self.pipe.recv()]
        return as_expected(parts, self.members)

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

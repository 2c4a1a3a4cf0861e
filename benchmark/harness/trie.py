"""The plain reference: a subscription trie with MQTT wildcard semantics.

Written for the benchmark from the MQTT 3.1.1 specification (section 4.7),
independent of the program's tries. ``+`` matches exactly one level, ``#``
the rest of the topic including the level before it (``a/#`` matches
``a``), and a topic whose first level starts with ``$`` is matched by no
filter whose first level is a wildcard.
"""

from __future__ import annotations


class Trie:
    def __init__(self) -> None:
        # node = [children: dict level -> node, values: list]
        self.root = [{}, []]

    def insert(self, topic_filter: str, value) -> None:
        node = self.root
        for level in topic_filter.split("/"):
            nxt = node[0].get(level)
            if nxt is None:
                nxt = node[0][level] = [{}, []]
            node = nxt
        node[1].append(value)

    def match(self, topic: str) -> list:
        """Values of every stored filter that matches ``topic`` (one entry
        per matching filter and value, so duplicates are possible)."""
        levels = topic.split("/")
        out: list = []
        n = len(levels)
        dollar = levels[0].startswith("$")

        def walk(node, i):
            children = node[0]
            if i == n:
                out.extend(node[1])
                h = children.get("#")
                if h is not None:
                    out.extend(h[1])
                return
            wild_ok = not (i == 0 and dollar)
            if wild_ok:
                h = children.get("#")
                if h is not None:
                    out.extend(h[1])
                p = children.get("+")
                if p is not None:
                    walk(p, i + 1)
            c = children.get(levels[i])
            if c is not None:
                walk(c, i + 1)

        walk(self.root, 0)
        return out

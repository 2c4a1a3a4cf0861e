"""``isa95_uns``: a Unified Namespace on the ISA-95 equipment hierarchy
(ANSI/ISA-95.00.01, IEC 62264-1: Enterprise → Site → Area → Work Center
(line) → Work Unit (cell) → equipment), one topic a tag of a device:
``<enterprise>/site<s>/area<a>/line<l>/cell<c>/dev<d>/<tag>``, seven levels.
The first three levels hold 1, 8 and 64 values: the namespace discriminates
only from the line down.

``filters()``: the four kinds of consumer a plant subscribes, sorted —

- ``…/line<l>/#`` for every line (line MES, dashboards);
- ``…/cell<c>/+/<tag>`` for every cell and tag (cell views of one tag);
- ``…/cell<c>/dev<d>/#`` for every device (device twins, edge apps);
- the exact tags of HMI screens and historians: the rest of the table,
  drawn from the seed without replacement out of every tag.

At the configuration's size (1,000,000 rows, 1,024 lines) that is 1,024 +
262,144 + 262,144 + 474,688 rows. A smaller table (the ``--cpu``
rehearsal's 4,000 rows) keeps the first ``n // ROWS_PER_LINE`` lines in
hierarchy order, with all four kinds of row, and its exact rows fill the
rest.

``topic_stream()``: a tag drawn uniformly from every tag of the table's
lines. Each publish reaches its line's, its cell-tag's and its device's
rows, and its own exact row where the table holds it (11.3 % of tags at
full size): about 3.11 deliveries a publish.
"""

from __future__ import annotations

import random

from harness.generators import register

ENTERPRISE = "acme"
# the configuration's hierarchy and tags; the defaults serve ``selftest.py``,
# which builds every registered generator from ``subscriptions`` alone
HIERARCHY = {"sites": 8, "areas_per_site": 8, "lines_per_area": 16,
             "cells_per_line": 16, "devices_per_cell": 16}
TAGS = ("temperature", "pressure", "vibration", "flow", "level", "speed",
        "current", "voltage", "power", "humidity", "torque", "position",
        "state", "count", "alarm", "runtime")
# rows a line of the table stands for: the full table's 1,000,000 rows over
# its 1,024 lines, rounded down
ROWS_PER_LINE = 976


@register("isa95_uns")
class Isa95Uns:
    def __init__(self, seed: int, config: dict) -> None:
        self.seed, self.n = seed, config["subscriptions"]
        h = {**HIERARCHY, **config.get("hierarchy", {})}
        self.tags = tuple(config.get("tags", TAGS))
        self.areas, self.lines_per_area = h["areas_per_site"], h["lines_per_area"]
        self.cells, self.devices = h["cells_per_line"], h["devices_per_cell"]
        all_lines = h["sites"] * self.areas * self.lines_per_area
        self.lines = min(all_lines, max(1, self.n // ROWS_PER_LINE))
        self.tags_per_line = self.cells * self.devices * len(self.tags)
        self.n_tags = self.lines * self.tags_per_line
        kinds = 1 + self.cells * len(self.tags) + self.cells * self.devices
        self.n_exact = self.n - self.lines * kinds
        if not 0 < self.n_exact <= self.n_tags:
            raise SystemExit(f"benchmark: isa95_uns cannot make {self.n} rows of "
                             f"{self.lines} lines ({self.lines * kinds} of them "
                             f"structural, {self.n_tags} tags)")
        self._filters = None

    def line(self, k: int) -> str:
        site, rest = divmod(k, self.areas * self.lines_per_area)
        area, line = divmod(rest, self.lines_per_area)
        return f"{ENTERPRISE}/site{site}/area{area}/line{line}"

    def tag_topic(self, g: int) -> str:
        """Tag ``g`` of the table's lines, in hierarchy order."""
        k, rest = divmod(g, self.tags_per_line)
        cell, rest = divmod(rest, self.devices * len(self.tags))
        dev, tag = divmod(rest, len(self.tags))
        return f"{self.line(k)}/cell{cell}/dev{dev}/{self.tags[tag]}"

    def filters(self) -> list:
        if self._filters is None:
            rows = []
            for k in range(self.lines):
                line = self.line(k)
                rows.append(line + "/#")
                for c in range(self.cells):
                    cell = f"{line}/cell{c}"
                    rows += [f"{cell}/+/{t}" for t in self.tags]
                    rows += [f"{cell}/dev{d}/#" for d in range(self.devices)]
            exact = random.Random(self.seed).sample(range(self.n_tags), self.n_exact)
            rows += map(self.tag_topic, exact)
            self._filters = sorted(rows)
        return self._filters

    def topic_stream(self, stream_seed: int):
        rng = random.Random(stream_seed)
        n = self.n_tags
        while True:
            yield self.tag_topic(rng.randrange(n))

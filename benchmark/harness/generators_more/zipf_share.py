"""``exact_zipf_share``: ``exact_zipf``'s table and stream with BASELINE.json
configs[3]'s shared subscriptions on it ("10M subs incl. ``$share/{group}/#``
shared subscriptions, Zipf-skewed publish stream"): ``shared_groups`` groups
``$share/g<k>/#`` of ``shared_members`` members each (MQTT 5.0 §4.8.2: one
member of each group receives every publish).

``filters()``: ``shared_members`` equal rows ``$share/g<k>/#`` for each k,
then ``exact_zipf``'s exact rows for the seed (``subscriptions`` less the
group rows): sorted, since ``$`` sorts before every exact row. So the group
rows are rows 0.. of the table and, by the owner rule, their members are
subscribers 0.. in group order. ``topic_stream()``: ``exact_zipf``'s stream
for the seed, the same ranks and the same scramble over the exact rows (the
class is subclassed, not copied; ``scramble()`` names the rows of this
table, past the group rows): no group row is ever a topic, and every topic
is matched by its one plain owner and by every group.
"""

from __future__ import annotations

from harness.generators import register
from harness.generators_more.zipf_one_each import ExactZipf

# the configuration's shared_groups and shared_members; the defaults serve
# ``selftest.py``, which builds every registered generator from
# ``subscriptions`` alone
GROUPS = 4
MEMBERS = 16


@register("exact_zipf_share")
class ExactZipfShare(ExactZipf):
    def __init__(self, seed: int, config: dict) -> None:
        self.groups = config.get("shared_groups", GROUPS)
        self.members = config.get("shared_members", MEMBERS)
        self.shared = self.groups * self.members
        super().__init__(seed, {**config, "subscriptions": config["subscriptions"] - self.shared})
        self._table = self._rows = None

    def filters(self) -> list:
        if self._table is None:
            groups = sorted(f"$share/g{k}/#" for k in range(self.groups)
                            for _ in range(self.members))
            self._table = groups + super().filters()
        return self._table

    def scramble(self):
        """``exact_zipf``'s permutation of the exact rows, as rows of this table."""
        if self._rows is None:
            self._rows = super().scramble() + self.shared
        return self._rows

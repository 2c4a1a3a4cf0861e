"""Window deltas of the broker's counters, shared by the readers here.

Cumulative values include the table load and the warm-up; only the
difference between the snapshots at the window's two ends is the window's.
"""


def stat(run: dict, key: str) -> float:
    return run["after"]["stats"][key] - run["before"]["stats"][key]


def served(before: dict, after: dict, backend: str, what: int) -> int:
    """``hybrid_served[backend]`` is [batches, topics]; ``what`` picks one."""
    a = after["device"]["backend"]["hybrid_served"][backend][what]
    return a - before["device"]["backend"]["hybrid_served"][backend][what]

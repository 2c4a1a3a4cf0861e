"""Window deltas of the broker's plain counters (``/api/v1/metrics``) and of
the hybrid's on ``/api/v1/device``, shared by the readers PR 27 added.

Taken between the same two snapshots as the stage deltas (``_stages._ends``:
inside the traced span where there is one). A counter the broker does not
have — a program from before the PR that brought it — gives None, and so
does the reader.
"""

from _stages import _ends


def metric(run: dict, key: str):
    """Delta of one ``/api/v1/metrics`` counter; None where it is missing."""
    before, after = _ends(run)
    a, b = after["metrics"], before["metrics"]
    return a[key] - b[key] if key in a and key in b else None


def dropped(run: dict) -> int:
    """Messages dropped between the snapshots, all reasons summed
    (``messages.dropped.<reason>``; a reason never met has no key yet)."""
    before, after = _ends(run)
    return sum(v - before["metrics"].get(k, 0)
               for k, v in after["metrics"].items()
               if k.startswith("messages.dropped."))


def backend(run: dict, key: str):
    """(before, after) of one ``backend`` entry of ``/api/v1/device``; None
    where it is missing."""
    before, after = _ends(run)
    a, b = after["device"]["backend"], before["device"]["backend"]
    return (b[key], a[key]) if key in a and key in b else None

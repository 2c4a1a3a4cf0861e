"""Median of send → first receipt over every delivered pair of the window, in
a saturated closed loop: by Little's law it is the fleet's size over the
rate, so it says nothing that ``deliveries_per_s`` does not. The end-to-end
name ``deliver_p50_ms`` is kept for open-loop cells, where the rate is
offered and the median is the broker's."""

import numpy as np

SPEC = {"layer": "wire + session, seen from the client", "unit": "ms",
        "source": "host_clock", "moves": "deliveries_per_s"}


def read(run: dict):
    ms = run["deliver_ms"]
    return float(np.percentile(ms, 50)) if ms.size else None

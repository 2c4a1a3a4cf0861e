"""The match programs' share of their HBM roofline, from the device trace.

Work: the topics the device served between the two counter snapshots taken
inside the traced slice, times the configuration's frozen
``hbm_bytes_per_topic``. Time: the summed device time of the XLA modules
whose names start with one of the configuration's ``match_programs``. The
snapshots lie inside the traced span, so the work is never counted higher
than what the trace timed. Absent where the device served no topic there.
"""

from _deltas import served
from harness import roofline

SPEC = {"layer": "kernels (jitted match programs)", "unit": "%",
        "source": "device_trace", "moves": "deliveries_per_s"}


def read(run: dict):
    tr, config = run["trace"], run["config"]
    if tr is None or not config.get("hbm_bytes_per_topic"):
        return None
    topics = served(tr["before"], tr["after"], "device", 1)
    seconds = sum(s for name, (_n, s) in tr["modules"].items()
                  if name.startswith(tuple(config["match_programs"])))
    if not topics or not seconds:
        return None
    return roofline.match_roofline_pct(topics, config["hbm_bytes_per_topic"],
                                       run["device"]["kind"], seconds)

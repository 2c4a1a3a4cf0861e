"""The words scan's share of its HBM roofline. Work: the topics the device served
between the two counter snapshots taken inside the traced span, times the
configuration's frozen ``hbm_bytes_per_topic`` (as ``kernel.match_roofline``
counts them: never more than the trace timed). Time: the device seconds of the
operations under the named scope ``scan``. Absent where the device served no
topic there or the trace holds no scope."""

from _deltas import served
from harness import host_spans, roofline

SPEC = {"layer": "kernels (jitted match programs)", "unit": "%",
        "source": "device_trace", "moves": "deliveries_per_s"}


def read(run: dict):
    tr, config = run["trace"], run["config"]
    red = host_spans.from_run(run)
    if not red or not config.get("hbm_bytes_per_topic"):
        return None
    topics = served(tr["before"], tr["after"], "device", 1)
    seconds = red["scope_s"].get("scan")
    if not topics or not seconds:
        return None
    return roofline.match_roofline_pct(topics, config["hbm_bytes_per_topic"],
                                       run["device"]["kind"], seconds)

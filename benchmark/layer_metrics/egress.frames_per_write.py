"""Frames a connection-write (``net.egress_frames`` over ``net.egress_flushes``):
how many encoded packets one coalesced flush of one connection carried; 1.0
where no two frames of a connection ever share a loop turn. Absent where the
broker has no such counters or flushed nothing."""

from _counters import metric

SPEC = {"layer": "deliver + egress broker/session.py egress.py", "unit": "frames/write",
        "source": "program_counter", "moves": "deliveries_per_s"}


def read(run: dict):
    frames, writes = metric(run, "net.egress_frames"), metric(run, "net.egress_flushes")
    return frames / writes if frames is not None and writes else None

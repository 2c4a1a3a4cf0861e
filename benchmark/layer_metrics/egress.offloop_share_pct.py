"""Share of the window's coalesced flushes (one vectored write of one
connection's frames) that the broker's native egress thread wrote, off the
event loop's thread (``net.egress_offloop_flushes`` over ``net.egress_flushes``).
The rest went through the asyncio transport on the loop thread: TLS, a
transport with bytes already buffered, a turn with a single dirty connection.
Absent where the broker has no such counter (a program from before PR 28) or
flushed nothing."""

from _counters import metric

SPEC = {"layer": "deliver + egress broker/session.py egress.py", "unit": "%",
        "source": "program_counter", "moves": "deliveries_per_s"}


def read(run: dict):
    off, flushes = metric(run, "net.egress_offloop_flushes"), metric(run, "net.egress_flushes")
    return 100.0 * off / flushes if off is not None and flushes else None

"""Share of the window's read chunks (one ``recv`` of one connection, handed
to its session) that the broker's native ingress thread read and framed, off
the event loop's thread (``net.ingress_offloop_reads`` over
``net.ingress_reads``). The rest came through the asyncio transport and its
StreamReader on the loop thread: TLS, WebSocket, what a StreamReader held
when a session took its reads over.
Absent where the broker has no such counter (a program from before PR 30) or
read nothing."""

from _counters import metric

SPEC = {"layer": "ingress codec + admission broker/session.py", "unit": "%",
        "source": "program_counter", "moves": "deliveries_per_s"}


def read(run: dict):
    off, reads = metric(run, "net.ingress_offloop_reads"), metric(run, "net.ingress_reads")
    return 100.0 * off / reads if off is not None and reads else None

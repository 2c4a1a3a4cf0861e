"""The broker broken on purpose, for the tests here.

    BENCHMARK_FAULT=drop|alter|reorder|share_all|share_none python faulty_broker.py <control dir> <broker args>

Started in place of ``harness/launch_broker.py``. ``drop`` and ``alter``
spoil every ``EVERY``-th non-empty match row where it is produced, in
``XlaRouter._expand`` — the one place all three routing paths (host mirror,
device, failover) hand their matches on:

- ``drop``: the row loses its last filter. The control: the configuration's
  delivery guarantee (every publish reaches every matching subscriber) is
  broken while every PUBACK still comes.
- ``alter``: the row is replaced by the row before it — an answer altered
  where it is produced: some subscribers get what is not theirs and others
  miss what is.

``reorder`` breaks the order of a connection's acks where they leave,
in ``SessionState._send_in_stage`` (the one exit of every publisher's
PUBACK, held or not): every ``EVERY``-th PUBACK is kept back until the same
connection's next one has left, so the younger publish is acked first
([MQTT-4.6.0-2] broken; every publish still delivered and acked). A kept
PUBACK that no other follows leaves by itself ``LONE_S`` later, in order:
the fault shows only where a fleet keeps more than one publish in flight.

``share_all`` and ``share_none`` break the choice of ``$share`` groups in
``Router.collapse``, where every routing path (a cache hit, a batch, a lone
match) collapses a match's groups to one member each ([MQTT 5.0 §4.8.2]):

- ``share_all``: every member of a matched group is kept;
- ``share_none``: the groups are dropped, only the plain subscribers are
  sent the publish.

Both are silent on a table without groups.
"""

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "harness"))

EVERY = 97
LONE_S = 0.2
PUBACK = 0x40


def install_reorder() -> None:
    import asyncio

    from rmqtt_tpu.broker.session import SessionState

    orig = SessionState._send_in_stage
    state = {"n": 0}
    kept = {}  # connection → the PUBACK frame kept back

    def lone(conn) -> None:
        frame = kept.pop(conn, None)
        if frame is not None:
            conn._write(frame)

    async def _send_in_stage(self, data, st, tok):
        if data[0] == PUBACK:
            older = kept.pop(self, None)
            if older is not None:
                data += older  # the younger publish's ack first
            else:
                state["n"] += 1
                if state["n"] % EVERY == 0:
                    kept[self] = data
                    asyncio.get_running_loop().call_later(LONE_S, lone, self)
                    return st.end(tok) if tok else 0
        return await orig(self, data, st, tok)

    SessionState._send_in_stage = _send_in_stage


def install_share(fault: str) -> None:
    from rmqtt_tpu.router.base import Router, SubRelation

    def collapse(self, raw):
        out, shared = raw
        if fault == "share_all":
            for (_group, tf), candidates in shared.items():
                for sid, opts, _online in candidates:
                    out.setdefault(sid.node_id, []).append(SubRelation(tf, sid, opts))
        return out

    Router.collapse = collapse


def install(fault: str) -> None:
    if fault == "reorder":
        return install_reorder()
    if fault in ("share_all", "share_none"):
        return install_share(fault)
    from rmqtt_tpu.router.xla import XlaRouter

    orig = XlaRouter._expand
    state = {"n": 0, "last": None}

    def _expand(self, items, fid_rows):
        rows = []
        for fids in fid_rows:
            if len(fids):
                state["n"] += 1
                if state["n"] % EVERY == 0:
                    fids = fids[:-1] if fault == "drop" else state["last"]
                else:
                    state["last"] = fids
            rows.append(fids)
        return orig(self, items, rows)

    XlaRouter._expand = _expand


if __name__ == "__main__":
    fault = os.environ["BENCHMARK_FAULT"]
    if fault not in ("drop", "alter", "reorder", "share_all", "share_none"):
        raise SystemExit(f"unknown BENCHMARK_FAULT {fault!r}")
    install(fault)
    import launch_broker

    launch_broker.main()

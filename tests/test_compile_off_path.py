"""No compile on the routing path (``ops/hybrid.py``, ``ops/partitioned.py``
``_pj``, ``broker/devprof.py`` ``compiles``).

With the native mirror present and the hybrid free to choose, a large batch
whose device program has no compiled shape yet is answered by the mirror, in
its usual time and equal to the plain reference, while the program compiles
on a thread of its own; the same shape then goes to the device; and the
``matcher.compile`` stage — compile time ON the routing path — records
nothing. With adaptivity off large batches stay pinned to the device, compile
and all.
"""

import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from rmqtt_tpu.broker.devprof import DEVPROF
from rmqtt_tpu.broker.telemetry import Telemetry
from rmqtt_tpu.router.base import Id, SubscriptionOptions

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "benchmark"))
from harness.generators import VOCAB6, MixedTree  # noqa: E402
from harness.trie import Trie  # noqa: E402

SLOW_S = 1.0  # what each never-seen program's "compile" is slowed by


def _topics(n: int, seed: int, firsts) -> list:
    rng = np.random.default_rng(seed)
    return ["/".join([f"v0_{rng.choice(firsts)}"] + [
        f"v{d}_{rng.integers(VOCAB6[d])}" for d in range(1, 6)])
        for _ in range(n)]


@pytest.fixture
def routed(monkeypatch):
    """→ (router, hybrid, telemetry, reference trie) over a ``mixed_tree``
    table with a few broad filters, the device profiler on as in a broker,
    and every never-seen program slowed by ``SLOW_S``."""
    from rmqtt_tpu.ops import partitioned as P
    from rmqtt_tpu.router.xla import XlaRouter

    prior = (DEVPROF.enabled, DEVPROF.telemetry)
    DEVPROF.reset()
    tele = Telemetry()
    DEVPROF.configure(enabled=True, telemetry=tele)
    router = XlaRouter()
    if not router._side_native:
        pytest.skip("no native host mirror: the hybrid cannot choose")
    router.telemetry = tele
    router.use_telemetry(tele)
    router.matcher.stage_timing = True  # as ServerContext switches it on
    # under v0_0 .. v0_3 a topic meets eight broad filters, elsewhere one
    filters = sorted(set(MixedTree(7, {"subscriptions": 3000}).filters()) | {"#"} | {
        "/".join([f"v0_{j}"] + ["+"] * d + [last])
        for j in range(4) for d, last in [(k, "#") for k in range(6)] + [(4, "+")]})
    ref = Trie()
    for i, f in enumerate(filters):
        router.add(f, Id(1, f"c{i % 8}"), SubscriptionOptions(qos=1))
        ref.insert(f, f)
    router.matcher._refresh()  # the table is resident, as after a load
    orig = P._pj

    def slowed_pj(kernel, fn, *args, **kwargs):
        def slow_fn(*a, **k):
            time.sleep(SLOW_S)
            return fn(*a, **k)

        key = DEVPROF.key_of(args, {k: v for k, v in kwargs.items()
                                    if k != "_key_extra"})
        fresh = not DEVPROF.seen(kernel, key)
        return orig(kernel, slow_fn if fresh else fn, *args, **kwargs)

    monkeypatch.setattr(P, "_pj", slowed_pj)
    # which thread asked the matcher to encode and dispatch, call by call
    submit = router.matcher.match_submit
    router.submits = []
    monkeypatch.setattr(
        router.matcher, "match_submit", lambda topics, *a, **k: (
            router.submits.append(threading.get_ident()), submit(topics, *a, **k))[1])
    yield router, router._hybrid, tele, ref
    hy = router._hybrid
    if hy._compiler is not None:
        hy._compiler.join(60)
    DEVPROF.reset()
    DEVPROF.configure(enabled=prior[0], telemetry=prior[1])


def _check(router, rows, topics, ref) -> None:
    for topic, fids in zip(topics, rows):
        got = sorted(router._fid_to_filter[f] for f in fids.tolist())
        assert got == sorted(ref.match(topic)), topic


def _wait_compiled(hybrid) -> None:
    end = time.monotonic() + 120
    while hybrid._compiler is not None and time.monotonic() < end:
        time.sleep(0.05)
    assert hybrid._compiler is None and not hybrid._to_compile


@pytest.mark.timeout(180)
@pytest.mark.parametrize("n", [100, 200])
def test_never_compiled_shape_is_served_by_the_mirror_then_by_the_device(routed, n):
    router, hybrid, tele, ref = routed
    topics = _topics(n, 1, [40, 41])  # about one match a topic: no regrow
    t0 = time.perf_counter()
    h = hybrid.match_submit(topics, True)
    took = time.perf_counter() - t0
    assert h[0] == "sync" and took < SLOW_S / 2  # nothing waited for a compile
    _check(router, hybrid.match_complete(h, True), topics, ref)
    assert hybrid.compiling_side == [1, n] and hybrid.served["device"] == [0, 0]
    # a second batch of the size being compiled does not even encode
    more = _topics(n, 2, [40, 41])
    me = threading.get_ident()
    assert router.submits.count(me) == 1
    h = hybrid.match_submit(more, True)
    assert h[0] == "sync" and router.submits.count(me) == 1
    _check(router, h[1], more, ref)
    assert hybrid.compiling_side == [2, 2 * n]
    _wait_compiled(hybrid)
    compiled = DEVPROF.traces
    assert compiled > 0
    # the same shape, compiled: the device serves it
    h = hybrid.match_submit(topics, True)
    assert h[0] == "device"
    _check(router, hybrid.match_complete(h, True), topics, ref)
    assert hybrid.served["device"] == [1, n] and DEVPROF.traces == compiled
    assert hybrid.compiling_side == [2, 2 * n] and hybrid.large_batches == 3
    st = tele.stage("matcher.compile")
    assert st.count == 0 and st.busy_ns == 0  # no compile on the path
    info = router.device_info()
    assert info["hybrid_compiling_side"] == [2, 2 * n]
    assert info["hybrid_large_batches"] == 3


@pytest.mark.timeout(180)
def test_regrown_slot_budget_is_compiled_off_the_path(routed):
    router, hybrid, tele, ref = routed
    sparse = _topics(128, 3, [40, 41])
    hybrid.match_submit(sparse, True)
    _wait_compiled(hybrid)
    # the same padded size with seven matches more a topic overflows the
    # slot budget the shape has now: the program with the regrown one is new
    dense = _topics(128, 4, [0, 1, 2, 3])
    budget = max(g for (padded, _nc), g in router.matcher._budgets.items()
                 if padded == 128)
    assert (sum(len(ref.match(t)) for t in sparse) <= budget
            < sum(len(ref.match(t)) for t in dense))
    h = hybrid.match_submit(dense, True)
    assert h[0] == "device"
    t0 = time.perf_counter()
    rows = hybrid.match_complete(h, True)
    assert time.perf_counter() - t0 < SLOW_S / 2
    _check(router, rows, dense, ref)
    assert hybrid.compiling_side == [2, 256] and hybrid.served["device"] == [0, 0]
    assert hybrid.last_backend == "side"
    _wait_compiled(hybrid)
    h = hybrid.match_submit(dense, True)
    assert h[0] == "device"
    _check(router, hybrid.match_complete(h, True), dense, ref)
    assert hybrid.served["device"] == [1, 128]
    st = tele.stage("matcher.compile")
    assert st.count == 0 and st.busy_ns == 0
    # the sections a handed-back compile cut short were all closed, and the
    # batches that ran only to compile were timed into none of them
    for name in ("encode", "dispatch", "fetch", "decode"):
        assert 0 < tele.stage("matcher." + name).busy_ns < SLOW_S / 2 * 1e9


@pytest.mark.timeout(180)
def test_with_adaptivity_off_the_device_is_pinned_and_compiles_in_place(routed):
    router, hybrid, tele, ref = routed
    hybrid.probe_every = 0  # RMQTT_HYBRID_ADAPT=0 / RMQTT_HYBRID_MAX=0
    topics = _topics(100, 5, [40, 41])
    h = hybrid.match_submit(topics, True)
    assert h[0] == "device"
    _check(router, hybrid.match_complete(h, True), topics, ref)
    assert hybrid.compiling_side == [0, 0] and hybrid._compiler is None
    st = tele.stage("matcher.compile")
    assert st.count > 0 and st.busy_ns >= SLOW_S * 1e9  # on the path, and timed


def test_a_shape_reaches_its_slot_budget_in_one_step_and_the_next_starts_there(routed):
    """Every budget is a program: a shape must not compile once per doubling
    as its batches fill up, nor every new shape start from 4 slots a topic."""
    m = routed[0].matcher
    assert m._budget_for(128, 32) == 512
    # 70 topics of a 128-bucket made 900 routes: a full bucket would make 1,646
    assert m._regrown(900, 70, 128) == 2048 and m._slots_per_topic == 16
    assert m._budget_for(256, 32) == 4096 and m._budget_for(128, 32) == 512
    # a lone topic padded to the floor says little: neither scaled nor learned
    assert m._regrown(300, 1, 8) == 512 and m._slots_per_topic == 16


@pytest.mark.timeout(180)
def test_the_workers_compile_is_its_own_stage_and_gauge(routed):
    """``matcher.compile_offpath`` times the worker's run on ``rmqtt-compile``;
    ``compiles_under_way`` on ``/api/v1/device`` is 1 while the worker lives;
    ``matcher.compile`` keeps timing only a compile on the routing path."""
    router, hybrid, tele, _ref = routed
    st = tele.stage("matcher.compile_offpath")
    assert router.device_info()["compiles_under_way"] == 0
    h = hybrid.match_submit(_topics(100, 5, [40, 41]), True)
    assert h[0] == "sync"
    assert router.device_info()["compiles_under_way"] == 1  # it sleeps SLOW_S
    _wait_compiled(hybrid)
    assert router.device_info()["compiles_under_way"] == 0
    assert st.count == 1 and st.busy_ns >= SLOW_S * 1e9
    flat = tele.stage_stats()
    assert flat["stage_matcher_compile_offpath_count"] == 1
    assert tele.stage("matcher.compile").count == 0

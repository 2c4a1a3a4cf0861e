"""Native C++ trie: build, bind, and differential-test against the oracle."""

import random

import pytest

from rmqtt_tpu.core.topic import filter_valid, match_filter

runtime = pytest.importorskip("rmqtt_tpu.runtime")
if not runtime.available():
    pytest.skip("no C++ toolchain available", allow_module_level=True)


def test_native_trie_basics():
    t = runtime.NativeTrie()
    assert t.add("a/+/c", 1)
    assert t.add("a/#", 2)
    assert not t.add("a/#", 2)  # dedup
    assert t.add("$SYS/#", 3)
    assert len(t) == 3
    assert sorted(t.match("a/b/c").tolist()) == [1, 2]
    assert t.match("a").tolist() == [2]  # parent '#'
    assert t.match("$SYS/x").tolist() == [3]  # $-isolation holds for 2
    assert t.match("zzz").tolist() == []
    assert t.remove("a/#", 2)
    assert not t.remove("a/#", 2)
    assert t.match("a").tolist() == []
    assert len(t) == 2


def test_native_differential():
    rng = random.Random(17)
    t = runtime.NativeTrie()
    fids = {}
    words = ["a", "b", "c", "", "+", "$s"]
    i = 0
    for _ in range(1500):
        n = rng.randint(1, 6)
        levels = [rng.choice(words) for _ in range(n)]
        if rng.random() < 0.35:
            levels[-1] = "#"
        f = "/".join(levels)
        if filter_valid(f) and f not in fids.values():
            t.add(f, i)
            fids[i] = f
            i += 1
    topics = [
        "/".join(rng.choice(["a", "b", "c", "d", "", "$s"]) for _ in range(rng.randint(1, 7)))
        for _ in range(400)
    ]
    rows = t.match_batch(topics)
    for topic, row in zip(topics, rows):
        expect = sorted(v for v, f in fids.items() if match_filter(f, topic))
        assert sorted(row.tolist()) == expect, topic
        assert sorted(t.match(topic).tolist()) == expect, topic


def test_native_router_agrees_with_default():
    from rmqtt_tpu.router import DefaultRouter, Id, SubscriptionOptions
    from rmqtt_tpu.router.native import NativeRouter

    rng = random.Random(9)
    d, n = DefaultRouter(), NativeRouter()
    subs = []
    for i in range(300):
        depth = rng.randint(1, 5)
        levels = [rng.choice(["a", "b", "c", "", "+"]) for _ in range(depth)]
        if rng.random() < 0.3:
            levels[-1] = "#"
        tf = "/".join(levels)
        if not filter_valid(tf):
            continue
        sid = Id(1, f"c{i % 40}")
        opts = SubscriptionOptions(qos=rng.randint(0, 2))
        subs.append((tf, sid))
        d.add(tf, sid, opts)
        n.add(tf, sid, opts)
    for tf, sid in rng.sample(subs, len(subs) // 3):
        assert d.remove(tf, sid) == n.remove(tf, sid)
    assert d.topics_count() == n.topics_count()

    def flat(m):
        return sorted((node, r.topic_filter, r.id.client_id) for node, v in m.items() for r in v)

    for _ in range(100):
        topic = "/".join(rng.choice(["a", "b", "c", "d", ""]) for _ in range(rng.randint(1, 6)))
        assert flat(d.matches(None, topic)) == flat(n.matches(None, topic)), topic


def test_large_matchset_regrow():
    t = runtime.NativeTrie()
    for i in range(5000):
        t.add("big/#", i)
    row = t.match("big/x")  # > default cap → retry path
    assert len(row) == 5000
    rows = t.match_batch(["big/x", "nope"], cap_per_topic=4)
    assert len(rows[0]) == 5000 and len(rows[1]) == 0


def test_native_codec_scan_matches_python_decoder():
    """Differential: random packet streams through the native-scan feed()
    vs the pure-Python decoder must produce identical packets, including
    split delivery and error positions."""
    import random

    from rmqtt_tpu.broker.codec import MqttCodec, codec as codec_mod, packets as pk
    from rmqtt_tpu.broker.codec.packets import SubOpts
    from rmqtt_tpu.broker.codec import props as P

    if codec_mod._native_lib() is None:
        import pytest

        pytest.skip("native runtime unavailable")
    rng = random.Random(3)

    def rand_packets(version):
        out = []
        for _ in range(60):
            kind = rng.randrange(6)
            if kind == 0:
                props = {}
                if version == pk.V5 and rng.random() < 0.5:
                    props = {P.CONTENT_TYPE: "t/x", P.USER_PROPERTY: [("a", "b")]}
                qos = rng.randrange(3)
                out.append(pk.Publish(
                    topic="/".join("lv%d" % rng.randrange(5) for _ in range(rng.randint(1, 6))),
                    payload=bytes(rng.randrange(256) for _ in range(rng.randrange(64))),
                    qos=qos, retain=rng.random() < 0.3, dup=qos > 0 and rng.random() < 0.2,
                    packet_id=rng.randrange(1, 65535) if qos else None,
                    properties=props,
                ))
            elif kind == 1:
                out.append(pk.Puback(rng.randrange(1, 65535)))
            elif kind == 2:
                out.append(pk.Subscribe(rng.randrange(1, 65535),
                                        [("a/+/b", SubOpts(qos=1))]))
            elif kind == 3:
                out.append(pk.Pingreq())
            elif kind == 4:
                out.append(pk.Suback(rng.randrange(1, 65535), [0, 1]))
            else:
                out.append(pk.Unsubscribe(rng.randrange(1, 65535), ["x/#"]))
        return out

    for version in (pk.V311, pk.V5):
        packets = rand_packets(version)
        enc = MqttCodec(version)
        stream = b"".join(enc.encode(p) for p in packets)
        fast = MqttCodec(version)
        slow = MqttCodec(version)
        got_fast, got_slow = [], []
        # feed in random chunks to exercise incomplete-frame resume
        pos = 0
        saved = codec_mod._native
        while pos < len(stream):
            # straddle the native crossover so BOTH paths stay covered
            n = rng.randint(1, codec_mod.NATIVE_MIN_BYTES * 5)
            chunk = stream[pos : pos + n]
            pos += n
            got_fast.extend(fast.feed(chunk))
            codec_mod._native = False  # force pure python
            try:
                got_slow.extend(slow.feed(chunk))
            finally:
                codec_mod._native = saved
        assert got_fast == got_slow
        assert len(got_fast) == len(packets)


def test_native_topic_validate_matches_python():
    import random

    from rmqtt_tpu import runtime as rt
    from rmqtt_tpu.core.topic import filter_valid, topic_valid

    if rt.load() is None:
        import pytest

        pytest.skip("native runtime unavailable")
    rng = random.Random(5)
    alphabet = ["a", "bb", "+", "#", "", "$sys", "x+y", "x#", "$share", "ünï"]
    cases = ["#", "+", "a/#", "#/a", "a/+/b", "$sys/a", "b/$sys", "", "/", "//", "a//b"]
    for _ in range(500):
        cases.append("/".join(rng.choice(alphabet) for _ in range(rng.randint(1, 5))))
    for t in cases:
        want_f = filter_valid(t)
        want_t = topic_valid(t)
        assert rt.topic_validate(t, is_filter=True) == want_f, ("filter", t)
        assert rt.topic_validate(t, is_filter=False) == want_t, ("topic", t)


@pytest.mark.parametrize("target,needs", [
    ("sancheck", ("libasan", "libubsan", "asan", "sanitize")),
    ("tsancheck", ("libtsan", "tsan", "sanitize")),
])
def test_runtime_sanitizers(target, needs):
    """The sanitizer passes over every native C ABI entry point (runtime/
    test_runtime.cc): ASan+UBSan (`make sancheck`: leaks, overflows, UB)
    and, since egress.cc and ingress.cc gave the library threads of its
    own, TSan (`make tsancheck`: races between the event loop's calls and
    the egress and the ingress thread).
    They fail the suite even though Python links the unsanitized .so."""
    import shutil
    import subprocess
    from pathlib import Path

    from rmqtt_tpu import runtime as rt

    # rt.available() already proves make + a working C++ compiler (whatever
    # $CXX is); checking for g++ literally would skip on clang-only hosts
    if shutil.which("make") is None or not rt.available():
        pytest.skip("no C++ toolchain")
    runtime_dir = Path(__file__).resolve().parent.parent / "runtime"
    build = subprocess.run(
        ["make", "-s", target + "_bin"], cwd=runtime_dir,
        capture_output=True, text=True, timeout=300,
    )
    if build.returncode != 0 and any(s in build.stderr for s in needs):
        pytest.skip("sanitizer runtime libraries unavailable")
    assert build.returncode == 0, f"{target} build failed:\n{build.stderr}"
    r = subprocess.run(
        [f"./{target}_bin"], cwd=runtime_dir,
        capture_output=True, text=True, timeout=300,
    )
    if target == "tsancheck" and "unexpected memory mapping" in r.stderr:
        pytest.skip("TSan cannot map its shadow here (ASLR entropy)")
    assert r.returncode == 0, f"{target} failed:\n{r.stdout}\n{r.stderr}"
    assert "runtime sanitizer checks passed" in r.stdout

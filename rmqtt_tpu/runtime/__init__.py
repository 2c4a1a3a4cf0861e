"""Native C++ runtime bindings (ctypes).

The reference's data plane is native (Rust); here the hot host-side
structures are C++ (``/root/repo/runtime``) bound via ctypes (no pybind11 in
this image). Currently: the topic-trie matcher (`runtime/topics.cc`) used as
(a) the fast host-side router backend (``NativeTrie`` →
``router.native.NativeRouter``) and (b) the honest CPU baseline in bench.py.

The shared library is built on demand with ``make`` and cached next to the
sources; environments without a toolchain fall back to the Python trie.
"""

from __future__ import annotations

import ctypes
import itertools
import logging
import os
import subprocess
from pathlib import Path
from typing import Collection, List, Optional, Sequence, Tuple

import numpy as np

log = logging.getLogger("rmqtt_tpu.runtime")

_RUNTIME_DIR = Path(__file__).resolve().parent.parent.parent / "runtime"
_LIB_PATH = _RUNTIME_DIR / "librmqtt_runtime.so"
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _build() -> bool:
    """``make`` the library under a per-process name in the same directory,
    then ``os.replace`` it into place: several processes racing the
    on-demand build (``--workers N``, test workers) each install a whole
    file, and none ever loads a half-written one."""
    tmp = f".build-{os.getpid()}-{_LIB_PATH.name}"
    try:
        subprocess.run(
            ["make", "-s", f"LIB={tmp}"], cwd=_RUNTIME_DIR, check=True,
            capture_output=True, timeout=120
        )
        os.replace(_RUNTIME_DIR / tmp, _LIB_PATH)
        return True
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired, OSError) as e:
        log.warning("native runtime build failed: %s%s", e,
                    (b"\n" + e.stderr).decode(errors="replace")
                    if getattr(e, "stderr", None) else "")
        return False
    finally:
        (_RUNTIME_DIR / tmp).unlink(missing_ok=True)


def load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _build_failed
    if _lib is not None:
        return _lib
    if _build_failed:
        return None
    srcs = [_RUNTIME_DIR / n for n in (
        "topics.cc", "encode.cc", "codec.cc", "egress.cc", "ingress.cc")]
    if not _LIB_PATH.exists() or any(
        s.exists() and s.stat().st_mtime > _LIB_PATH.stat().st_mtime for s in srcs
    ):
        if not _build():
            _build_failed = True
            return None
    lib = ctypes.CDLL(str(_LIB_PATH))
    lib.rt_trie_new.restype = ctypes.c_void_p
    lib.rt_trie_free.argtypes = [ctypes.c_void_p]
    lib.rt_trie_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
    lib.rt_trie_add.restype = ctypes.c_int
    lib.rt_trie_remove.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
    lib.rt_trie_remove.restype = ctypes.c_int
    lib.rt_trie_size.argtypes = [ctypes.c_void_p]
    lib.rt_trie_size.restype = ctypes.c_int64
    lib.rt_trie_match.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
    ]
    lib.rt_trie_match.restype = ctypes.c_int64
    lib.rt_trie_match_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
    ]
    lib.rt_trie_match_batch.restype = ctypes.c_int64
    lib.rt_enc_new.restype = ctypes.c_void_p
    lib.rt_enc_free.argtypes = [ctypes.c_void_p]
    lib.rt_enc_add_tokens.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_int32,
    ]
    lib.rt_enc_add_tokens.restype = ctypes.c_int64
    lib.rt_enc_parts_clear.argtypes = [ctypes.c_void_p]
    lib.rt_enc_parts_put.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int32,
    ]
    lib.rt_enc_parts_put.restype = ctypes.c_int64
    lib.rt_enc_encode.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.rt_enc_encode.restype = ctypes.c_int32
    lib.rt_match_decode_routes.argtypes = [
        ctypes.POINTER(ctypes.c_uint32), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
    ]
    lib.rt_match_decode_routes.restype = ctypes.c_int64
    lib.rt_codec_scan.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int32),
    ]
    lib.rt_codec_scan.restype = ctypes.c_int64
    if hasattr(lib, "rt_codec_encode_publish"):  # absent in stale .so builds
        lib.rt_codec_encode_publish.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
        ]
        lib.rt_codec_encode_publish.restype = ctypes.c_int64
    lib.rt_topic_validate.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_int32]
    lib.rt_topic_validate.restype = ctypes.c_int
    if hasattr(lib, "rt_egress_new"):  # absent in stale .so builds
        _egress_protos(lib)
    if hasattr(lib, "rt_ingress_new"):  # absent in stale .so builds
        _ingress_protos(lib)
    _lib = lib
    return lib


def _egress_protos(lib) -> None:
    lib.rt_egress_new.restype = ctypes.c_void_p
    lib.rt_egress_free.argtypes = [ctypes.c_void_p]
    lib.rt_egress_free.restype = None
    lib.rt_egress_eventfd.argtypes = [ctypes.c_void_p]
    lib.rt_egress_eventfd.restype = ctypes.c_int32
    lib.rt_egress_submit.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int64),
    ]
    lib.rt_egress_submit.restype = ctypes.c_int64
    lib.rt_egress_collect.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int64]
    lib.rt_egress_collect.restype = ctypes.c_int64
    lib.rt_egress_wait.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32]
    lib.rt_egress_wait.restype = ctypes.c_int32
    lib.rt_egress_stats.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64)]
    lib.rt_egress_stats.restype = None


_I64P = ctypes.POINTER(ctypes.c_int64)


def _ingress_protos(lib) -> None:
    lib.rt_ingress_new.restype = ctypes.c_void_p
    lib.rt_ingress_free.argtypes = [ctypes.c_void_p]
    lib.rt_ingress_free.restype = None
    lib.rt_ingress_eventfd.argtypes = [ctypes.c_void_p]
    lib.rt_ingress_eventfd.restype = ctypes.c_int32
    lib.rt_ingress_add.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64]
    lib.rt_ingress_add.restype = ctypes.c_int32
    lib.rt_ingress_remove.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.rt_ingress_remove.restype = None
    lib.rt_ingress_collect.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, _I64P, _I64P,
        ctypes.POINTER(_I64P), ctypes.POINTER(_I64P),
        ctypes.POINTER(ctypes.c_void_p), _I64P]
    lib.rt_ingress_collect.restype = ctypes.c_int64
    lib.rt_ingress_stats.argtypes = [ctypes.c_void_p, _I64P]
    lib.rt_ingress_stats.restype = None


CODEC_STRIDE = 10  # int64 slots per frame record (runtime/codec.cc)
_SCAN_CAP = 8192  # frames per scan call; feed loops on over-full buffers


def codec_scan(lib, buf: bytes, is_v5: bool, max_size: int):
    """→ (records, n, consumed, err, hit_cap): the ``n`` frames' records
    as one flat list of ``CODEC_STRIDE`` ints a frame."""
    cap = min(len(buf) // 2 + 1, _SCAN_CAP)
    meta = np.empty((cap, CODEC_STRIDE), dtype=np.int64)
    consumed = ctypes.c_int64(0)
    err = ctypes.c_int32(0)
    n = lib.rt_codec_scan(
        buf, len(buf), 1 if is_v5 else 0, max_size,
        meta.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), cap,
        ctypes.byref(consumed), ctypes.byref(err),
    )
    return meta[:n].ravel().tolist(), n, consumed.value, err.value, n == cap


def codec_encode_publish(lib, topic: bytes, payload: bytes, props: bytes,
                         qos: int, retain: bool, dup: bool,
                         packet_id: Optional[int]) -> Optional[bytes]:
    """Assemble one complete PUBLISH wire frame in C++ (codec.cc). `props`
    is the pre-encoded v5 properties blob (varint prefix + content; empty
    for v3). None when the .so predates the symbol (stale prebuilt build)
    — the caller falls back to the Python encoder."""
    if not hasattr(lib, "rt_codec_encode_publish"):
        return None
    cap = 7 + len(topic) + len(props) + len(payload) + (2 if qos else 0)
    out = (ctypes.c_uint8 * cap)()
    n = lib.rt_codec_encode_publish(
        topic, len(topic), payload, len(payload), props, len(props),
        qos, 1 if retain else 0, 1 if dup else 0,
        -1 if packet_id is None else packet_id, out, cap,
    )
    if n < 0:
        return None  # cap miscount — let the Python path handle it
    return bytes(out[:n])


def topic_validate(topic: str, is_filter: bool) -> Optional[bool]:
    """Native topic/filter validation; None if the runtime is unavailable."""
    lib = load()
    if lib is None:
        return None
    raw = topic.encode()
    return bool(lib.rt_topic_validate(raw, len(raw), 1 if is_filter else 0))


def available() -> bool:
    return load() is not None


class NativeTrie:
    """ctypes wrapper over the C++ trie (same semantics as core.trie.TopicTree)."""

    def __init__(self) -> None:
        lib = load()
        if lib is None:
            raise RuntimeError("native runtime unavailable (no C++ toolchain?)")
        self._lib = lib
        self._ptr = ctypes.c_void_p(lib.rt_trie_new())

    def __del__(self) -> None:
        ptr = getattr(self, "_ptr", None)
        if ptr:
            self._lib.rt_trie_free(ptr)
            self._ptr = None

    def add(self, topic_filter: str, value: int) -> bool:
        return bool(self._lib.rt_trie_add(self._ptr, topic_filter.encode(), value))

    def remove(self, topic_filter: str, value: int) -> bool:
        return bool(self._lib.rt_trie_remove(self._ptr, topic_filter.encode(), value))

    def __len__(self) -> int:
        return int(self._lib.rt_trie_size(self._ptr))

    def match(self, topic: str, cap: int = 4096) -> np.ndarray:
        buf = np.empty(cap, dtype=np.int64)
        n = self._lib.rt_trie_match(
            self._ptr, topic.encode(), buf.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), cap
        )
        if n > cap:  # rare: grow and retry
            return self.match(topic, cap=int(n))
        return buf[:n].copy()

    def match_batch(self, topics: Sequence[str], cap_per_topic: int = 64) -> List[np.ndarray]:
        blob = b"\x00".join(t.encode() for t in topics) + b"\x00"
        n = len(topics)
        counts = np.empty(n, dtype=np.int64)
        cap = max(1, cap_per_topic * n)
        while True:
            out = np.empty(cap, dtype=np.int64)
            total = self._lib.rt_trie_match_batch(
                self._ptr, blob, n,
                counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), cap,
            )
            if total <= cap:
                break
            cap = int(total)
        rows: List[np.ndarray] = []
        off = 0
        for j in range(n):
            c = int(counts[j])
            rows.append(out[off : off + c].copy())
            off += c
        return rows


class EgressThread:
    """ctypes wrapper over the library's one thread (runtime/egress.cc): it
    does the non-blocking socket writes of one job per event-loop turn
    without ever taking the GIL. Every method is the event loop's to call.

    ``submit`` / ``collect`` / ``stats`` are a copy and a mutex hold, so
    they go through a ``PyDLL`` handle and keep the GIL (a ``CDLL`` call
    drops and re-takes it, which on the saturated loop thread is the cost
    this thread exists to remove); ``wait`` and ``close`` can block and
    release it."""

    _COLLECT_CAP = 1024

    def __init__(self) -> None:
        lib = load()
        if lib is None or not hasattr(lib, "rt_egress_new"):
            raise RuntimeError("native runtime without egress.cc")
        self._lib = lib
        held = ctypes.PyDLL(str(_LIB_PATH))  # the same mapping, GIL kept
        _egress_protos(held)
        self._submit = held.rt_egress_submit
        self._collect = held.rt_egress_collect
        self._stats = held.rt_egress_stats
        ptr = lib.rt_egress_new()
        if not ptr:
            raise RuntimeError("rt_egress_new failed (eventfd / thread)")
        self._ptr = ctypes.c_void_p(ptr)
        self.eventfd = int(lib.rt_egress_eventfd(self._ptr))
        self._rows = (ctypes.c_int64 * (3 * self._COLLECT_CAP))()

    def close(self) -> None:
        """Sends what is queued, joins the thread, closes the eventfd."""
        ptr, self._ptr = self._ptr, None
        if ptr:
            self._lib.rt_egress_free(ptr)

    __del__ = close

    def submit(self, fds: Sequence[int], bufs: Sequence[bytes]) -> int:
        """One job: ``bufs[i]`` to ``fds[i]``, in this order; the bytes are
        copied before this returns. Each fd stays open, and out of any
        other job, until its completion is collected. → the ticket of the
        last entry (entry ``i``'s is ``ticket - n + i + 1``)."""
        n = len(fds)
        return self._submit(self._ptr, n, (ctypes.c_int32 * n)(*fds),
                            (ctypes.c_char_p * n)(*bufs),
                            (ctypes.c_int64 * n)(*map(len, bufs)))

    def collect(self) -> List[Tuple[int, int, int]]:
        """→ the (fd, bytes written, errno) posted since the last call."""
        out: List[Tuple[int, int, int]] = []
        cap = self._COLLECT_CAP
        while True:
            n = self._collect(self._ptr, self._rows, cap)
            flat = self._rows[:3 * n]
            out.extend(zip(flat[0::3], flat[1::3], flat[2::3]))
            if n < cap:
                return out

    def wait(self, ticket: int, timeout_ms: int) -> bool:
        """Block until the entry with ``ticket`` is posted."""
        return bool(self._lib.rt_egress_wait(self._ptr, ticket, timeout_ms))

    def stats(self) -> Tuple[int, int, int]:
        """→ (busy ns, sends, jobs) of the thread since it started."""
        out = (ctypes.c_int64 * 3)()
        self._stats(self._ptr, out)
        return out[0], out[1], out[2]


INGRESS_CHUNK = 7  # int64 slots per chunk record (runtime/ingress.cc)
# chunk flags (runtime/ingress.cc)
INGRESS_FRAMES, INGRESS_RAW, INGRESS_EOF, INGRESS_ERR, INGRESS_PAUSED = (
    1, 2, 4, 8, 16)
INGRESS_DATA = INGRESS_FRAMES | INGRESS_RAW  # a chunk that carries bytes


class IngressThread:
    """ctypes wrapper over the library's reading thread (runtime/ingress.cc):
    one epoll over every registered connection's socket, a non-blocking
    ``recv`` and the frame scan on readable, without ever taking the GIL.
    Every method is the event loop's to call.

    ``collect`` and ``stats`` are a swap and a few loads under a mutex the
    thread holds only while it appends, so they go through a ``PyDLL``
    handle and keep the GIL (as ``EgressThread`` does); ``add`` makes one
    ``epoll_ctl``, ``remove`` can wait for a read in flight and ``close``
    joins the thread: those release it."""

    def __init__(self) -> None:
        lib = load()
        if lib is None or not hasattr(lib, "rt_ingress_new"):
            raise RuntimeError("native runtime without ingress.cc")
        self._lib = lib
        held = ctypes.PyDLL(str(_LIB_PATH))  # the same mapping, GIL kept
        _ingress_protos(held)
        self._collect = held.rt_ingress_collect
        self._stats = held.rt_ingress_stats
        ptr = lib.rt_ingress_new()
        if not ptr:
            raise RuntimeError("rt_ingress_new failed (epoll / eventfd / thread)")
        self._ptr = ctypes.c_void_p(ptr)
        self.eventfd = int(lib.rt_ingress_eventfd(self._ptr))
        self._chunks, self._meta = _I64P(), _I64P()
        self._bytes = ctypes.c_void_p()
        self._counts = (ctypes.c_int64 * 3)()
        self._out = (ctypes.byref(self._chunks), ctypes.byref(self._meta),
                     ctypes.byref(self._bytes), self._counts)

    def close(self) -> None:
        """Joins the thread and closes its epoll and eventfd; the
        registered sockets stay the caller's."""
        ptr, self._ptr = self._ptr, None
        if ptr:
            self._lib.rt_ingress_free(ptr)

    __del__ = close

    def add(self, conn_id: int, fd: int, is_v5: bool, max_size: int,
            head: bytes = b"") -> None:
        """The thread reads ``fd`` from now on, as connection ``conn_id``
        (> 0, never reused). ``head``: bytes already read that are not a
        whole frame yet. ``fd`` stays open until ``remove`` has returned."""
        rc = self._lib.rt_ingress_add(self._ptr, conn_id, fd, 1 if is_v5 else 0,
                                      max_size, head, len(head))
        if rc:
            raise OSError(-rc, os.strerror(-rc))

    def remove(self, conn_id: int) -> None:
        """Once this returns the thread is out of any read of the
        connection's fd, for good."""
        self._lib.rt_ingress_remove(self._ptr, conn_id)

    def collect(self, acks: dict) -> Tuple[List[int], List[int], bytes]:
        """Hand in ``acks`` (connection id → bytes consumed since the last
        call) and take what the thread has posted: → (chunks, meta, blob),
        two flat int lists of ``INGRESS_CHUNK`` and ``CODEC_STRIDE`` slots a
        row (runtime/ingress.cc ``rt_ingress_collect``) and the bytes the
        rows' offsets index."""
        n = len(acks)
        if n:
            arr = ctypes.c_int64 * n
            ids, sizes = arr(*acks), arr(*acks.values())
        else:
            ids = sizes = None
        n = self._collect(self._ptr, n, ids, sizes, *self._out)
        if not n:
            return [], [], b""
        _, frames, size = self._counts
        return (self._chunks[:INGRESS_CHUNK * n],
                self._meta[:CODEC_STRIDE * frames],
                ctypes.string_at(self._bytes, size))

    def stats(self) -> Tuple[int, int, int, int]:
        """→ (busy ns, recvs, jobs, times the bound stopped a connection)
        of the thread since it started."""
        out = (ctypes.c_int64 * 4)()
        self._stats(self._ptr, out)
        return out[0], out[1], out[2], out[3]


def _i32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


class NativeEncoder:
    """ctypes wrapper over the C++ batched topic encoder (runtime/encode.cc).

    Owns the native mirrors of one ``PartitionedTable``'s token dictionary
    and partition-key → chunk-ids maps; the table pushes new tokens and the
    keys its mutations touched before each encode, in a fixed number of
    calls (see partitioned.py ``_sync_native``). No call here is per topic.
    """

    def __init__(self) -> None:
        lib = load()
        if lib is None:
            raise RuntimeError("native runtime unavailable (no C++ toolchain?)")
        self._lib = lib
        self._ptr = ctypes.c_void_p(lib.rt_enc_new())
        self.tokens_synced = 0  # count of TokenDict entries pushed so far
        self.parts_epoch = -1  # table.layout_epoch the partition mirror reflects

    def __del__(self) -> None:
        ptr = getattr(self, "_ptr", None)
        if ptr:
            self._lib.rt_enc_free(ptr)
            self._ptr = None

    def add_tokens(self, strs: Sequence[str], first_id: int) -> None:
        """Intern ``strs`` as ids ``first_id, first_id + 1, ...``."""
        blob = ("/".join(strs) + "/").encode()
        n = self._lib.rt_enc_add_tokens(self._ptr, blob, len(blob), first_id)
        if n != len(strs):  # a level holding '/' would shift every later id
            raise RuntimeError(f"native token mirror took {n} of {len(strs)} levels")

    def parts_clear(self) -> None:
        self._lib.rt_enc_parts_clear(self._ptr)

    def parts_put(self, keys: Collection[Tuple], lists: Collection[Collection[int]],
                  append: bool) -> None:
        """Install partition keys' chunk ids (``lists[i]`` for ``keys[i]``):
        replace each key's list (an empty one erases the key), or with
        ``append`` extend it. All iteration is C-level — a compaction
        install resyncs ~100K keys through here on the routing path."""
        n = len(keys)
        if not n:
            return
        blob = ("/".join(map("/".join, keys)) + "/").encode()
        counts = np.fromiter(map(len, lists), dtype=np.int32, count=n)
        chunks = np.fromiter(itertools.chain.from_iterable(lists), dtype=np.int32,
                             count=int(counts.sum()))
        rc = self._lib.rt_enc_parts_put(
            self._ptr, blob, len(blob), n, _i32p(counts), _i32p(chunks), int(append)
        )
        if rc != n:
            raise RuntimeError(f"native partition mirror refused key #{-rc - 1} of {n}")

    def encode(
        self,
        blob: bytes,
        n: int,
        max_levels: int,
        ttok: np.ndarray,
        tlen: np.ndarray,
        tdollar: np.ndarray,
        nc_cap: int,
        cand: np.ndarray,
        cand_counts: np.ndarray,
        group: np.ndarray,
    ) -> int:
        """Fills every output for the whole batch; returns the largest
        candidate count (> ``nc_cap``: rows truncated, grow and retry)."""
        return self._lib.rt_enc_encode(
            self._ptr, blob, n, max_levels,
            _i32p(ttok), _i32p(tlen),
            tdollar.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            nc_cap, _i32p(cand), _i32p(cand_counts), _i32p(group),
        )


def match_decode_routes(routes: np.ndarray, counts: np.ndarray,
                        chunk_ids: np.ndarray, b: int, wpc: int, chunk: int,
                        fid_map: np.ndarray):
    """Native route-level global compaction → flat per-topic-sorted fids;
    None if the runtime is unavailable. routes uint32, counts int64 (per
    PADDED topic), chunk_ids int32, fid_map int64, all C-contiguous. The
    route total is known up front (= counts.sum() = len(routes)), so
    unlike the word decoders there is no two-pass cap dance."""
    lib = load()
    if lib is None:
        return None
    bp, nc = chunk_ids.shape
    fid_map = np.ascontiguousarray(fid_map, dtype=np.int64)
    i32 = ctypes.POINTER(ctypes.c_int32)
    i64 = ctypes.POINTER(ctypes.c_int64)
    u32 = ctypes.POINTER(ctypes.c_uint32)
    n = int(routes.shape[0])
    out = np.empty(n, dtype=np.int64)
    total = lib.rt_match_decode_routes(
        routes.ctypes.data_as(u32), n, counts.ctypes.data_as(i64),
        chunk_ids.ctypes.data_as(i32), b, bp, nc, wpc, chunk,
        fid_map.ctypes.data_as(i64), out.ctypes.data_as(i64),
    )
    if total < 0:
        raise AssertionError(
            "rt_match_decode_routes hit an out-of-range route/fid/count — "
            "kernel/compaction bug"
        )
    return out

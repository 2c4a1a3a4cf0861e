// Off-loop socket reads: the library's second thread.
//
// The broker's event loop registers, after a session's handshake, a dup of
// the connection's plain-TCP socket (rt_ingress_add: the fd is the caller's
// to keep open until rt_ingress_remove returns). The thread waits in ONE
// epoll over all of them. On readable it does one non-blocking recv, runs
// the frame scan codec.cc already has (rt_codec_scan: frame boundaries,
// PUBLISH pre-parsed) over the connection's complete frames and appends
// them, bytes and records, to the batch every connection shares; a frame
// that is not whole yet stays in the connection's own buffer and later
// reads land behind it (no copy per read). The eventfd is signalled once
// per epoll round that posted anything, not once per connection. The loop
// takes the whole batch in one call (rt_ingress_collect) and builds the
// packets; EOF, a reset and any recv error are posted, not handled.
//
// Bound: a connection with kHigh bytes posted and not yet acknowledged as
// consumed (the `acks` of a later collect) leaves the epoll set until the
// loop has brought it under kLow again, so a flooding publisher meets TCP
// backpressure and memory a connection stays bounded, as it is behind an
// asyncio StreamReader. Where the scan refuses a frame (malformed,
// oversize) or meets a CONNECT, the connection's bytes are posted raw from
// there on: the Python codec judges them as it always has.
//
// Contract: add / remove / collect / stats / free come from one thread at a
// time (the event loop's); everything shared with the ingress thread is
// under `mu` or atomic (`make tsancheck`). The thread never takes the GIL.

#include <pthread.h>
#include <sys/epoll.h>
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <time.h>
#include <unistd.h>

#include <atomic>
#include <cerrno>
#include <condition_variable>
#include <cstring>
#include <memory>
#include <mutex>
#include <thread>
#include <unordered_map>
#include <vector>

#include "rmqtt_runtime.h"

namespace {

constexpr int64_t kHigh = 64 * 1024;  // posted, unconsumed: stop polling
constexpr int64_t kLow = 32 * 1024;   // ... until it is back under this
constexpr int64_t kRead = 64 * 1024;  // one recv takes at most this much
constexpr int kStride = 10;           // int64 slots a frame (rt_codec_scan)
constexpr int kChunk = 7;             // int64 slots a chunk (rt_ingress_collect)
constexpr int64_t kScanCap = 4096;    // frames a scan call

// chunk flags
constexpr int64_t F_FRAMES = 1;  // whole frames, scanned
constexpr int64_t F_RAW = 2;     // bytes the scan did not judge
constexpr int64_t F_EOF = 4;     // the peer closed its side
constexpr int64_t F_ERR = 8;     // recv failed: errno in the chunk
constexpr int64_t F_PAUSED = 16; // the bound stopped this connection

int64_t now_ns() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return static_cast<int64_t>(ts.tv_sec) * 1000000000 + ts.tv_nsec;
}

struct Conn {
  int64_t id;
  int fd;
  int32_t v5;
  int64_t max_size;
  std::vector<uint8_t> buf;  // bytes read and not posted: a frame's head
  int64_t pending = 0;       // posted and not acknowledged
  bool busy = false;         // the thread is inside recv / scan for it
  bool polled = false;       // in the epoll set
  bool paused = false;       // out of it by the bound
  bool ended = false;        // out of it by EOF / error
  bool raw = false;          // the scan gave up: post bytes as they come
};

struct Batch {
  std::vector<int64_t> chunks;  // kChunk a row
  std::vector<int64_t> meta;    // kStride a row
  std::vector<uint8_t> bytes;
  void clear() { chunks.clear(), meta.clear(), bytes.clear(); }
};

struct Ingress {
  std::mutex mu;
  std::condition_variable cv_idle;  // a connection left recv / scan
  std::unordered_map<int64_t, std::unique_ptr<Conn>> conns;
  Batch batch;      // what the thread has posted (under mu)
  Batch collected;  // what the last collect handed out (the caller's)
  int ep = -1, efd = -1, wake = -1;
  std::atomic<bool> stop{false};
  std::atomic<int64_t> busy_ns{0}, recvs{0}, njobs{0}, pauses{0};
  std::thread th;
  std::vector<uint8_t> scratch;  // the thread's: one recv
  std::vector<int64_t> rows;     // the thread's: one scan

  bool poll(Conn* c, bool on) {  // under mu
    if (c->polled == on) return true;
    epoll_event ev{};
    ev.events = EPOLLIN;
    ev.data.u64 = static_cast<uint64_t>(c->id);
    if (epoll_ctl(ep, on ? EPOLL_CTL_ADD : EPOLL_CTL_DEL, c->fd, &ev) < 0)
      return false;
    c->polled = on;
    return true;
  }

  // Appends one chunk to the batch (under mu).
  void post(Conn* c, int64_t flags, int64_t err, const uint8_t* p,
            int64_t len, const int64_t* frames, int64_t nframes) {
    const int64_t base = static_cast<int64_t>(batch.bytes.size());
    const int64_t row0 = static_cast<int64_t>(batch.meta.size()) / kStride;
    batch.bytes.insert(batch.bytes.end(), p, p + len);
    for (int64_t i = 0; i < nframes; i++) {
      const int64_t* m = frames + i * kStride;
      int64_t r[kStride];
      std::memcpy(r, m, sizeof r);
      r[1] += base;
      if ((m[0] >> 4) == 3) {  // PUBLISH: its spans too
        r[3] += base;
        if (m[6] >= 0) r[6] += base;
        r[8] += base;
      }
      batch.meta.insert(batch.meta.end(), r, r + kStride);
    }
    c->pending += len;
    if (c->pending >= kHigh && !c->paused && !c->ended) {
      poll(c, false);
      c->paused = true;
      flags |= F_PAUSED;
      pauses.fetch_add(1, std::memory_order_relaxed);
    }
    const int64_t row[kChunk] = {c->id, flags, err, base, len, row0, nframes};
    batch.chunks.insert(batch.chunks.end(), row, row + kChunk);
  }

  // One readable connection: recv, scan, post. → whether anything was posted.
  bool serve(int64_t id) {
    std::unique_lock<std::mutex> lk(mu);
    auto it = conns.find(id);
    if (it == conns.end()) return false;  // removed since the epoll round
    Conn* c = it->second.get();
    if (!c->polled) return false;  // paused / ended since
    c->busy = true;
    lk.unlock();

    // a frame's head waits in c->buf: read behind it; else into the scratch
    const size_t had = c->buf.size();
    uint8_t* dst;
    if (had) {
      c->buf.resize(had + kRead);
      dst = c->buf.data() + had;
    } else {
      dst = scratch.data();
    }
    ssize_t r;
    do {
      r = recv(c->fd, dst, kRead, MSG_DONTWAIT);
    } while (r < 0 && errno == EINTR);
    const int rerr = r < 0 ? errno : 0;
    if (had) c->buf.resize(had + (r > 0 ? static_cast<size_t>(r) : 0));
    recvs.fetch_add(1, std::memory_order_relaxed);

    bool posted = false;
    if (r < 0 && (rerr == EAGAIN || rerr == EWOULDBLOCK)) {
      lk.lock();  // a spurious wake-up: nothing to say
    } else if (r <= 0) {
      lk.lock();
      poll(c, false);
      c->ended = true;
      post(c, r == 0 ? F_EOF : F_ERR, rerr, nullptr, 0, nullptr, 0);
      posted = true;
    } else {
      const uint8_t* p = had ? c->buf.data() : scratch.data();
      int64_t len = had ? static_cast<int64_t>(c->buf.size()) : r;
      if (c->raw) {
        lk.lock();
        post(c, F_RAW, 0, p, len, nullptr, 0);
        posted = true;
        len = 0;
      } else {
        for (;;) {
          int64_t consumed = 0;
          int32_t err = 0;
          const int64_t n =
              rt_codec_scan(p, len, c->v5, c->max_size, rows.data(), kScanCap,
                            &consumed, &err);
          lk.lock();
          if (n > 0) {
            post(c, F_FRAMES, 0, p, consumed, rows.data(), n);
            posted = true;
          }
          p += consumed;
          len -= consumed;
          // the scan stops without an error before a whole CONNECT too
          if (err || (n < kScanCap && len >= 2 && (p[0] >> 4) == 1 &&
                      frame_complete(p, len))) {
            c->raw = true;
            post(c, F_RAW, 0, p, len, nullptr, 0);
            posted = true;
            len = 0;
            break;
          }
          if (n < kScanCap) break;
          lk.unlock();  // the scan filled its rows: go on behind them
        }
      }
      // what is left is a frame's head: keep it for the next read
      if (had) {
        c->buf.erase(c->buf.begin(), c->buf.end() - len);
        if (c->buf.empty() && c->buf.capacity() > static_cast<size_t>(kRead))
          std::vector<uint8_t>().swap(c->buf);
      } else if (len) {
        c->buf.assign(p, p + len);
      }
    }
    c->busy = false;
    lk.unlock();
    cv_idle.notify_all();
    return posted;
  }

  static bool frame_complete(const uint8_t* p, int64_t len) {
    int64_t mult = 1, blen = 0, i = 1;
    for (; i < len && i <= 4; i++) {
      blen += static_cast<int64_t>(p[i] & 0x7F) * mult;
      mult *= 128;
      if (!(p[i] & 0x80)) return len - (i + 1) >= blen;
    }
    return false;
  }

  void run() {
    pthread_setname_np(pthread_self(), "rmqtt-ingress");
    scratch.resize(static_cast<size_t>(kRead));
    rows.resize(static_cast<size_t>(kScanCap * kStride));
    epoll_event evs[256];
    while (!stop.load(std::memory_order_acquire)) {
      const int n = epoll_wait(ep, evs, 256, -1);
      if (n < 0) {
        if (errno == EINTR) continue;
        return;
      }
      const int64_t t0 = now_ns();
      bool posted = false;
      for (int i = 0; i < n; i++) {
        if (evs[i].data.u64 == 0) continue;  // the wake fd: stop is re-read
        posted |= serve(static_cast<int64_t>(evs[i].data.u64));
      }
      if (posted) {
        njobs.fetch_add(1, std::memory_order_relaxed);
        const uint64_t one = 1;
        if (write(efd, &one, sizeof one) < 0) {
          // EAGAIN: the counter is at its ceiling, so the loop is woken anyway
        }
      }
      busy_ns.fetch_add(now_ns() - t0, std::memory_order_relaxed);
    }
  }
};

}  // namespace

extern "C" {

void* rt_ingress_new() {
  auto* in = new Ingress();
  in->ep = epoll_create1(EPOLL_CLOEXEC);
  in->efd = eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
  in->wake = eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
  epoll_event ev{};
  ev.events = EPOLLIN;
  ev.data.u64 = 0;  // no connection has id 0
  bool ok = in->ep >= 0 && in->efd >= 0 && in->wake >= 0 &&
            epoll_ctl(in->ep, EPOLL_CTL_ADD, in->wake, &ev) == 0;
  if (ok) {
    try {
      in->th = std::thread([in] { in->run(); });
    } catch (...) {
      ok = false;
    }
  }
  if (!ok) {
    for (int fd : {in->ep, in->efd, in->wake})
      if (fd >= 0) close(fd);
    delete in;
    return nullptr;
  }
  return in;
}

// Joins the thread; the registered fds stay the caller's.
void rt_ingress_free(void* h) {
  auto* in = static_cast<Ingress*>(h);
  if (!in) return;
  in->stop.store(true, std::memory_order_release);
  const uint64_t one = 1;
  if (write(in->wake, &one, sizeof one) < 0) {
    // cannot fail on a fresh eventfd; the join below would hang if it did
  }
  in->th.join();
  close(in->ep), close(in->efd), close(in->wake);
  delete in;
}

// Readable whenever chunks were posted since the last collect.
int32_t rt_ingress_eventfd(void* h) { return static_cast<Ingress*>(h)->efd; }

// Registers connection `id` (> 0, never reused): the thread reads `fd` from
// now on. `head` are bytes the caller has read already that are not a whole
// frame yet; the thread's reads land behind them. → 0, or -errno.
int32_t rt_ingress_add(void* h, int64_t id, int32_t fd, int32_t is_v5,
                       int64_t max_size, const uint8_t* head,
                       int64_t head_len) {
  auto* in = static_cast<Ingress*>(h);
  auto c = std::make_unique<Conn>();
  c->id = id;
  c->fd = fd;
  c->v5 = is_v5;
  c->max_size = max_size;
  if (head_len > 0) c->buf.assign(head, head + head_len);
  std::lock_guard<std::mutex> g(in->mu);
  if (!in->poll(c.get(), true)) return -errno;
  in->conns[id] = std::move(c);
  return 0;
}

// Forgets connection `id`: once this returns the thread is not inside a
// read of its fd and never touches it again, so the caller may close it.
// Chunks already posted for it still come out of the next collect.
void rt_ingress_remove(void* h, int64_t id) {
  auto* in = static_cast<Ingress*>(h);
  std::unique_lock<std::mutex> lk(in->mu);
  auto it = in->conns.find(id);
  if (it == in->conns.end()) return;
  Conn* c = it->second.get();
  in->cv_idle.wait(lk, [&] { return !c->busy; });
  in->poll(c, false);
  in->conns.erase(it);
}

// First applies the caller's acknowledgements (ack_bytes[i] of connection
// ack_ids[i] are consumed: a connection the bound had stopped is polled
// again once it is under the low mark), then takes everything posted since
// the last call and clears the eventfd. The three arrays stay valid until
// the next collect:
//   chunks: 7 int64 a chunk — id, flags (1 frames, 2 raw bytes, 4 EOF,
//           8 recv error, 16 the bound stopped the connection after this
//           chunk), errno, byte offset, byte length, first frame, frames;
//           a connection's chunks are in the order its bytes came
//   meta:   10 int64 a frame, as rt_codec_scan writes them, offsets into
//           `bytes`
// counts[0..3) = chunks, frames, bytes. → chunks.
int64_t rt_ingress_collect(void* h, int64_t n_acks, const int64_t* ack_ids,
                           const int64_t* ack_bytes, const int64_t** chunks,
                           const int64_t** meta, const uint8_t** bytes,
                           int64_t* counts) {
  auto* in = static_cast<Ingress*>(h);
  uint64_t seen;
  // before the take: a chunk posted after it signals again
  if (read(in->efd, &seen, sizeof seen) < 0) {
    // EAGAIN: nothing signalled; chunks may still be there
  }
  in->collected.clear();
  {
    std::lock_guard<std::mutex> g(in->mu);
    for (int64_t i = 0; i < n_acks; i++) {
      auto it = in->conns.find(ack_ids[i]);
      if (it == in->conns.end()) continue;
      Conn* c = it->second.get();
      c->pending -= ack_bytes[i];
      if (c->paused && c->pending <= kLow) {
        c->paused = false;
        in->poll(c, true);
      }
    }
    std::swap(in->batch, in->collected);
  }
  const Batch& b = in->collected;
  *chunks = b.chunks.data();
  *meta = b.meta.data();
  *bytes = b.bytes.data();
  counts[0] = static_cast<int64_t>(b.chunks.size()) / kChunk;
  counts[1] = static_cast<int64_t>(b.meta.size()) / kStride;
  counts[2] = static_cast<int64_t>(b.bytes.size());
  return counts[0];
}

// out[0..4) = the thread's busy ns (wall time outside epoll_wait), recvs,
// jobs (epoll rounds that posted and signalled), times the bound stopped a
// connection.
void rt_ingress_stats(void* h, int64_t* out) {
  auto* in = static_cast<Ingress*>(h);
  out[0] = in->busy_ns.load(std::memory_order_relaxed);
  out[1] = in->recvs.load(std::memory_order_relaxed);
  out[2] = in->njobs.load(std::memory_order_relaxed);
  out[3] = in->pauses.load(std::memory_order_relaxed);
}

}  // extern "C"

// Off-loop socket writes: the library's one thread.
//
// The broker's event loop hands over, once per loop turn, every dirty
// plain-TCP connection's coalesced frames as ONE job (rt_egress_submit:
// the bytes are copied, so the job shares no memory with Python; the fds
// are the caller's to keep open until their completions are collected —
// the broker gives each connection a dup of its own for this). No system
// call is made on the caller's side but the wake of a sleeping thread.
// The thread does one non-blocking send per connection in job order —
// never a retry: what EAGAIN or a partial write leaves goes back to the
// loop, whose asyncio transport owns slow consumers — and posts
// (fd, bytes written, errno) per connection. The loop collects them when
// the eventfd wakes it, or at its next turn (rt_egress_collect), or waits
// for one connection's (rt_egress_wait: close, high-water flush).
//
// Contract: submit / collect / wait / stats / free come from one thread at
// a time (the event loop's); everything shared with the egress thread is
// under `mu` or atomic (`make tsancheck`).

#include <pthread.h>
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <time.h>
#include <unistd.h>

#include <atomic>
#include <cerrno>
#include <chrono>
#include <condition_variable>
#include <cstring>
#include <deque>
#include <memory>
#include <mutex>
#include <thread>
#include <vector>

#include "rmqtt_runtime.h"

namespace {

struct Entry {
  int32_t fd;
  int64_t off, len;
};

struct Job {
  std::vector<Entry> entries;
  std::unique_ptr<uint8_t[]> blob;
};

struct Done {
  int64_t fd, written, err;
};

int64_t now_ns() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return static_cast<int64_t>(ts.tv_sec) * 1000000000 + ts.tv_nsec;
}

struct Egress {
  std::mutex mu;
  std::condition_variable cv_job;   // a job was queued, or stop
  std::condition_variable cv_done;  // a completion was posted
  std::deque<Job> jobs;
  std::vector<Done> done;
  int64_t submitted = 0, posted = 0;  // entries, ever: a ticket is a count
  bool stop = false;
  int efd = -1;
  std::atomic<int64_t> busy_ns{0}, sends{0}, njobs{0};
  std::thread th;

  void run() {
    pthread_setname_np(pthread_self(), "rmqtt-egress");
    std::unique_lock<std::mutex> lk(mu);
    for (;;) {
      cv_job.wait(lk, [&] { return stop || !jobs.empty(); });
      if (jobs.empty()) return;  // stop, and nothing left to send
      Job job = std::move(jobs.front());
      jobs.pop_front();
      lk.unlock();
      const int64_t t0 = now_ns();
      for (const Entry& e : job.entries) {
        ssize_t w;
        do {
          w = send(e.fd, job.blob.get() + e.off, static_cast<size_t>(e.len),
                   MSG_DONTWAIT | MSG_NOSIGNAL);
        } while (w < 0 && errno == EINTR);
        const int err = w < 0 ? errno : 0;
        lk.lock();
        done.push_back({e.fd, w < 0 ? 0 : static_cast<int64_t>(w), err});
        posted++;
        lk.unlock();
        cv_done.notify_all();
      }
      sends.fetch_add(static_cast<int64_t>(job.entries.size()),
                      std::memory_order_relaxed);
      njobs.fetch_add(1, std::memory_order_relaxed);
      busy_ns.fetch_add(now_ns() - t0, std::memory_order_relaxed);
      const uint64_t one = 1;
      if (write(efd, &one, sizeof one) < 0) {
        // EAGAIN: the counter is at its ceiling, so the loop is woken anyway
      }
      lk.lock();
    }
  }
};

}  // namespace

extern "C" {

void* rt_egress_new() {
  auto* eg = new Egress();
  eg->efd = eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
  if (eg->efd < 0) {
    delete eg;
    return nullptr;
  }
  try {
    eg->th = std::thread([eg] { eg->run(); });
  } catch (...) {
    close(eg->efd);
    delete eg;
    return nullptr;
  }
  return eg;
}

// Sends what is still queued, then joins the thread.
void rt_egress_free(void* h) {
  auto* eg = static_cast<Egress*>(h);
  if (!eg) return;
  {
    std::lock_guard<std::mutex> g(eg->mu);
    eg->stop = true;
  }
  eg->cv_job.notify_all();
  eg->th.join();
  close(eg->efd);
  delete eg;
}

// Readable whenever a job has been finished since the last collect.
int32_t rt_egress_eventfd(void* h) { return static_cast<Egress*>(h)->efd; }

// One job: n connections, fds[i] gets bufs[i][0:lens[i]] in this order. An
// fd stays the caller's: open, and in no second job, until its completion
// is collected. → the ticket of the job's last entry (rt_egress_wait).
int64_t rt_egress_submit(void* h, int64_t n, const int32_t* fds,
                         const uint8_t* const* bufs, const int64_t* lens) {
  auto* eg = static_cast<Egress*>(h);
  int64_t total = 0;
  for (int64_t i = 0; i < n; i++) total += lens[i];
  Job job;
  job.blob.reset(new uint8_t[total > 0 ? total : 1]);
  job.entries.reserve(static_cast<size_t>(n));
  int64_t off = 0;
  for (int64_t i = 0; i < n; i++) {
    std::memcpy(job.blob.get() + off, bufs[i], static_cast<size_t>(lens[i]));
    job.entries.push_back({fds[i], off, lens[i]});
    off += lens[i];
  }
  int64_t ticket;
  {
    std::lock_guard<std::mutex> g(eg->mu);
    ticket = eg->submitted += n;
    if (n > 0) eg->jobs.push_back(std::move(job));
  }
  if (n > 0) eg->cv_job.notify_one();
  return ticket;
}

// Takes up to cap posted completions as rows of (fd, written, errno) and
// clears the eventfd; → rows written (== cap: call again).
int64_t rt_egress_collect(void* h, int64_t* out, int64_t cap) {
  auto* eg = static_cast<Egress*>(h);
  uint64_t seen;
  // before the take: a completion posted after it signals again
  if (read(eg->efd, &seen, sizeof seen) < 0) {
    // EAGAIN: nothing signalled; completions may still be there
  }
  std::lock_guard<std::mutex> g(eg->mu);
  const int64_t have = static_cast<int64_t>(eg->done.size());
  const int64_t n = have < cap ? have : cap;
  for (int64_t i = 0; i < n; i++) {
    out[3 * i] = eg->done[i].fd;
    out[3 * i + 1] = eg->done[i].written;
    out[3 * i + 2] = eg->done[i].err;
  }
  eg->done.erase(eg->done.begin(), eg->done.begin() + n);
  return n;
}

// Blocks until `ticket` entries have been posted: entry i of a job is
// posted once (its job's ticket - n + i + 1) have. → 1, or 0 at the timeout.
int32_t rt_egress_wait(void* h, int64_t ticket, int32_t timeout_ms) {
  auto* eg = static_cast<Egress*>(h);
  std::unique_lock<std::mutex> lk(eg->mu);
  return eg->cv_done.wait_for(lk, std::chrono::milliseconds(timeout_ms),
                              [&] { return eg->posted >= ticket; })
             ? 1
             : 0;
}

// out[0..3) = the thread's busy ns (wall time inside jobs), sends, jobs.
void rt_egress_stats(void* h, int64_t* out) {
  auto* eg = static_cast<Egress*>(h);
  out[0] = eg->busy_ns.load(std::memory_order_relaxed);
  out[1] = eg->sends.load(std::memory_order_relaxed);
  out[2] = eg->njobs.load(std::memory_order_relaxed);
}

}  // extern "C"

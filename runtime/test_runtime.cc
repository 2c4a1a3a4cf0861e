// Sanitizer test driver for the native runtime (topics.cc, encode.cc,
// codec.cc, egress.cc, ingress.cc). Built with -fsanitize=address,undefined by `make
// sancheck` and with -fsanitize=thread by `make tsancheck` (both run from
// tests/test_native.py): exercises every C ABI entry point with normal,
// boundary, and malformed inputs so leaks, overflows, UB and races are
// caught even though the Python test suite runs against the unsanitized
// library. egress.cc and ingress.cc each have a thread of their own
// (test_egress and test_ingress play the event loop against them); for the
// rest thread safety is external by contract, so TSan has nothing to see
// there.

#include <fcntl.h>
#include <poll.h>
#include <sys/socket.h>
#include <unistd.h>

#include <cerrno>

#include <cassert>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <map>
#include <string>
#include <thread>
#include <vector>

#include "rmqtt_runtime.h"

static void test_trie() {
  void* t = rt_trie_new();
  assert(rt_trie_add(t, "a/b/c", 1));
  assert(rt_trie_add(t, "a/+/c", 2));
  assert(rt_trie_add(t, "a/#", 3));
  assert(rt_trie_add(t, "#", 4));
  assert(rt_trie_add(t, "", 5));
  assert(rt_trie_size(t) == 5);
  int64_t out[16];
  int64_t n = rt_trie_match(t, "a/b/c", out, 16);
  assert(n == 4);
  n = rt_trie_match(t, "a/b/c", out, 1);  // overflow reporting: n > cap
  assert(n == 4);
  assert(rt_trie_remove(t, "a/+/c", 2));
  assert(!rt_trie_remove(t, "a/+/c", 2));
  // batch over a blob with empty + deep topics
  std::string blob;
  blob += "a/b/c";
  blob.push_back('\0');
  blob += "";
  blob.push_back('\0');
  blob += "x/y/z/w/v/u/t/s/r/q";
  blob.push_back('\0');
  int64_t counts[3];
  int64_t vals[64];
  int64_t total = rt_trie_match_batch(t, blob.data(), 3, counts, vals, 64);
  assert(total >= 0);
  rt_trie_free(t);
}

static void test_encoder() {
  void* e = rt_enc_new();
  // ids 10.. : "sensor", "", "a", "b"
  assert(rt_enc_add_tokens(e, "sensor//a/b/", 12, 10) == 4);
  // the partition maps of a hand-written table: each key's segments (the
  // Python tuple's kind and levels), every one followed by '/'
  std::string keys;
  std::vector<int32_t> counts, chunks;
  auto put = [&](const char* k, std::vector<int32_t> ids) {
    keys += k;
    counts.push_back(static_cast<int32_t>(ids.size()));
    chunks.insert(chunks.end(), ids.begin(), ids.end());
  };
  put("#/", {7});                // bare '#': every topic
  put("2/sensor/", {3});         // sensor/#
  put("4/sensor/+/b/", {5});     // sensor/+/b...
  put("4/+/a/+/", {9, 5});       // +/a/+...
  put("H3/sensor/a/", {2});      // sensor/a/#
  put("2E//b/", {4});            // '/b' exactly (empty first level)
  put("1/+/", {6});              // '+'
  put("4/a/a/a/", {8});          // never consulted below
  auto flush = [&](int32_t append) {
    int64_t rc = rt_enc_parts_put(e, keys.data(), static_cast<int64_t>(keys.size()),
                                  static_cast<int64_t>(counts.size()), counts.data(),
                                  chunks.data(), append);
    keys.clear();
    counts.clear();
    chunks.clear();
    return rc;
  };
  assert(flush(0) == 8);
  // shared chunks arrive in a second, appending call (7 repeats '#''s
  // chunk: first occurrence wins in the walk); an empty append is a no-op
  put("2/sensor/", {7});
  put("1/+/", {});
  assert(flush(1) == 2);
  put("9/x/", {1});
  assert(flush(0) == -1);  // unknown kind
  put("1/nosuch/", {1});
  assert(flush(0) == -1);  // unknown level
  put("#/", {7});
  put("4/a/a", {1});
  assert(flush(0) == -2);  // stream ends inside key 1
  put("1/a/a/", {1});
  assert(flush(0) == -2);  // bytes left over after the last key

  std::string blob;
  auto topic = [&](const char* t) {
    blob += t;
    blob.push_back('\0');
  };
  topic("sensor/a/b/c/d");       // 0: walk order: # 7 | 2/sensor 3 | H3 2 | 4/sensor/+/b 5 | 4/+/a/+ 9
  topic("unknown/levels/here");  // 1: only '#'
  topic("");                     // 2: one empty level: '#', 1/+
  topic("/b");                   // 3: '#', 2E//b
  topic("sensor/a/b/zzz");       // 4: same prefix as 0: same row, same group
  topic("$sys");                 // 5: unknown single level
  topic("sensor");               // 6: '#', 2/sensor, 1/+
  const int64_t n = 7;
  const int32_t lvl = 8, cap = 4;
  std::vector<int32_t> ttok(n * lvl), tlen(n), cand(n * cap, -1), cnt(n), grp(n);
  std::vector<uint8_t> dollar(n);
  int32_t mx = rt_enc_encode(e, blob.data(), n, lvl, ttok.data(), tlen.data(),
                             dollar.data(), cap, cand.data(), cnt.data(), grp.data());
  assert(mx == 5);  // topic 0 overflows cap 4: true count, row truncated
  assert(tlen[0] == 5 && cnt[0] == 5);
  assert(ttok[0] == 10 && ttok[1] == 12 && ttok[2] == 13 && ttok[3] == 3 && ttok[5] == 0);
  const int32_t want0[4] = {7, 3, 2, 5};
  assert(std::memcmp(&cand[0], want0, sizeof want0) == 0);
  assert(cnt[1] == 1 && cand[cap] == 7 && cand[cap + 1] == 0);
  assert(tlen[2] == 1 && cnt[2] == 2 && cand[2 * cap] == 7 && cand[2 * cap + 1] == 6);
  assert(tlen[3] == 2 && cnt[3] == 2 && cand[3 * cap] == 7 && cand[3 * cap + 1] == 4);
  assert(cnt[4] == 5 && std::memcmp(&cand[4 * cap], want0, sizeof want0) == 0);
  assert(grp[0] == 0 && grp[4] == 0 && grp[1] == 1 && grp[2] == 2 && grp[3] == 3);
  assert(dollar[5] == 1 && dollar[0] == 0 && cnt[5] == 2);
  assert(cnt[6] == 3 && cand[6 * cap] == 7 && cand[6 * cap + 1] == 3 && cand[6 * cap + 2] == 6);
  // retry with room: the whole row, in the table's order
  const int32_t cap2 = 8;
  std::vector<int32_t> cand2(n * cap2, -1);
  mx = rt_enc_encode(e, blob.data(), n, lvl, ttok.data(), tlen.data(), dollar.data(),
                     cap2, cand2.data(), cnt.data(), grp.data());
  const int32_t want0b[8] = {7, 3, 2, 5, 9, 0, 0, 0};
  assert(mx == 5 && std::memcmp(&cand2[0], want0b, sizeof want0b) == 0);
  // a count of 0 erases a key; the walk follows
  put("2/sensor/", {});
  assert(flush(0) == 1);
  mx = rt_enc_encode(e, blob.data(), n, lvl, ttok.data(), tlen.data(), dollar.data(),
                     cap2, cand2.data(), cnt.data(), grp.data());
  assert(mx == 4 && cand2[1] == 2 && cnt[6] == 2);
  // fewer levels than the key needs (max_levels 1) still walks 3 levels
  std::vector<int32_t> ttok1(n);
  mx = rt_enc_encode(e, blob.data(), n, 1, ttok1.data(), tlen.data(), dollar.data(),
                     cap2, cand2.data(), cnt.data(), grp.data());
  assert(mx == 4 && ttok1[0] == 10 && tlen[0] == 5);
  rt_enc_parts_clear(e);
  mx = rt_enc_encode(e, blob.data(), n, lvl, ttok.data(), tlen.data(), dollar.data(),
                     cap2, cand2.data(), cnt.data(), grp.data());
  assert(mx == 0 && cnt[0] == 0 && cand2[0] == 0);
  rt_enc_free(e);
}

static void test_match_decode_routes() {
  // route-level entries, b=2 (bp=3 with one padded topic), nc=2, wpc=4
  // (W=8), chunk=128
  // topic 0: word 0 bits 0,1 (chunk 1) + word 5 bit 31 (chunk 2, +32+31)
  // topic 1: word 1 bit 0 (chunk 2, +32)
  uint32_t routes[4] = {0 * 32 + 0, 0 * 32 + 1, 5 * 32 + 31, 1 * 32 + 0};
  int64_t counts[3] = {3, 1, 0};
  int32_t chunk_ids[6] = {1, 2, 2, 0, 0, 0};
  std::vector<int64_t> fid_map(3 * 128);
  for (size_t i = 0; i < fid_map.size(); ++i) fid_map[i] = 1000 + (int64_t)i;
  int64_t out[16];
  int64_t total = rt_match_decode_routes(routes, 4, counts, chunk_ids, 2, 3, 2,
                                         4, 128, fid_map.data(), out);
  assert(total == 4);
  assert(out[0] == 1000 + 128 && out[1] == 1000 + 129);
  assert(out[2] == 1000 + 2 * 128 + 32 + 31);
  assert(out[3] == 1000 + 2 * 128 + 32);
  // a padded topic with a nonzero count fails loudly (device bug)
  int64_t bad_counts[3] = {3, 0, 1};
  total = rt_match_decode_routes(routes, 4, bad_counts, chunk_ids, 2, 3, 2, 4,
                                 128, fid_map.data(), out);
  assert(total == -1);
  // counts overrunning the routes buffer fail loudly (caller bug)
  int64_t over_counts[3] = {3, 2, 0};
  total = rt_match_decode_routes(routes, 4, over_counts, chunk_ids, 2, 3, 2, 4,
                                 128, fid_map.data(), out);
  assert(total == -1);
  // a negative count fails loudly (would be UB in the sort)
  int64_t neg_counts[3] = {-1, 1, 0};
  total = rt_match_decode_routes(routes, 4, neg_counts, chunk_ids, 2, 3, 2, 4,
                                 128, fid_map.data(), out);
  assert(total == -1);
  // out-of-range route (widx >= W) fails loudly
  uint32_t bad_routes[1] = {8 * 32};
  int64_t one[3] = {1, 0, 0};
  total = rt_match_decode_routes(bad_routes, 1, one, chunk_ids, 2, 3, 2, 4,
                                 128, fid_map.data(), out);
  assert(total == -1);
  // cleared-row sentinel fails loudly
  fid_map[128] = -1;
  total = rt_match_decode_routes(routes, 4, counts, chunk_ids, 2, 3, 2, 4, 128,
                                 fid_map.data(), out);
  assert(total == -1);
}

static void test_codec() {
  // a CONNACK (2 bytes) + a v5 PUBLISH qos1 with empty props + trailing junk
  std::vector<uint8_t> buf = {
      0x20, 0x02, 0x00, 0x00,                    // CONNACK
      0x32, 0x0A, 0x00, 0x03, 'a', '/', 'b',     // PUBLISH qos1 topic a/b
      0x00, 0x07,                                // packet id 7
      0x00,                                      // props len 0
      'h', 'i',                                  // payload
  };
  int64_t meta[4 * 10];
  int64_t consumed = 0;
  int32_t err = 0;
  int64_t nf = rt_codec_scan(buf.data(), (int64_t)buf.size(), 1, 1 << 20, meta, 4,
                             &consumed, &err);
  assert(nf == 2 && err == 0 && consumed == (int64_t)buf.size());
  assert(meta[10] == 0x32);           // publish first byte
  assert(meta[10 + 5] == 7);          // packet id
  assert(meta[10 + 9] == 2);          // payload length
  // malformed: 5-byte remaining length
  std::vector<uint8_t> bad = {0x30, 0xFF, 0xFF, 0xFF, 0xFF, 0x7F};
  nf = rt_codec_scan(bad.data(), (int64_t)bad.size(), 0, 1 << 20, meta, 4,
                     &consumed, &err);
  assert(nf == 0 && err == 1);
  // truncated PUBLISH topic length
  std::vector<uint8_t> trunc = {0x30, 0x01, 0x00};
  nf = rt_codec_scan(trunc.data(), (int64_t)trunc.size(), 0, 1 << 20, meta, 4,
                     &consumed, &err);
  assert(err == 4);
  // encode_publish round-trip: assemble the same v5 qos1 frame the scan
  // above parsed and compare byte-for-byte
  uint8_t frame[64];
  const uint8_t props0[] = {0x00};  // v5 empty props (varint 0)
  int64_t fl = rt_codec_encode_publish(
      (const uint8_t*)"a/b", 3, (const uint8_t*)"hi", 2, props0, 1,
      /*qos=*/1, /*retain=*/0, /*dup=*/0, /*packet_id=*/7, frame, 64);
  assert(fl == 12);
  assert(std::memcmp(frame, buf.data() + 4, 12) == 0);
  // v3 qos0 retained (no packet id, no props), empty payload
  fl = rt_codec_encode_publish((const uint8_t*)"t", 1, nullptr, 0, nullptr,
                               0, 0, 1, 0, -1, frame, 64);
  assert(fl == 5 && frame[0] == 0x31 && frame[1] == 3);
  // multi-byte remaining-length varint (200-byte payload → rem = 203)
  std::vector<uint8_t> big(200, 0xAB);
  fl = rt_codec_encode_publish((const uint8_t*)"t", 1, big.data(), 200,
                               nullptr, 0, 0, 0, 0, -1, frame, 64);
  assert(fl == -1);  // cap too small: refused, nothing written
  std::vector<uint8_t> out2(256);
  fl = rt_codec_encode_publish((const uint8_t*)"t", 1, big.data(), 200,
                               nullptr, 0, 0, 0, 0, -1, out2.data(), 256);
  assert(fl == 206 && out2[1] == 0xCB && out2[2] == 0x01);  // varint 203
  // validation edge cases
  assert(rt_topic_validate((const uint8_t*)"a/b", 3, 0) == 1);
  assert(rt_topic_validate((const uint8_t*)"a/+", 3, 0) == 0);
  assert(rt_topic_validate((const uint8_t*)"#", 1, 1) == 1);
  assert(rt_topic_validate((const uint8_t*)"#/a", 3, 1) == 0);
  assert(rt_topic_validate((const uint8_t*)"/", 1, 1) == 1);
  assert(rt_topic_validate((const uint8_t*)"", 0, 1) == 0);
}

// One connection as the broker's loop sees it: a non-blocking stream
// socket it writes (ours[0]) and the peer's end (ours[1]).
static void make_pair(int sv[2], int sndbuf = 0) {
  assert(socketpair(AF_UNIX, SOCK_STREAM, 0, sv) == 0);
  assert(fcntl(sv[0], F_SETFL, O_NONBLOCK) == 0);
  if (sndbuf) {
    assert(setsockopt(sv[0], SOL_SOCKET, SO_SNDBUF, &sndbuf, sizeof sndbuf) == 0);
  }
}

static std::string read_n(int fd, size_t n) {
  std::string got(n, '\0');
  size_t have = 0;
  while (have < n) {
    ssize_t r = read(fd, got.data() + have, n - have);
    assert(r > 0);
    have += static_cast<size_t>(r);
  }
  return got;
}

// Collect until `want` completions came, waking on the eventfd like the
// loop's reader does.
static std::vector<int64_t> collect_n(void* eg, size_t want) {
  std::vector<int64_t> rows;
  int64_t buf[3 * 8];  // a small cap, so the "call again" arm runs
  while (rows.size() < 3 * want) {
    pollfd p{rt_egress_eventfd(eg), POLLIN, 0};
    assert(poll(&p, 1, 5000) >= 0);
    int64_t n;
    do {
      n = rt_egress_collect(eg, buf, 8);
      rows.insert(rows.end(), buf, buf + 3 * n);
    } while (n == 8);
  }
  return rows;
}

static void test_egress() {
  void* eg = rt_egress_new();
  assert(eg);
  assert(rt_egress_eventfd(eg) >= 0);
  // 1) 32 connections, 20 jobs: every byte arrives, per connection in order
  constexpr int N = 32, ROUNDS = 20;
  int sv[N][2];
  for (auto& p : sv) make_pair(p);
  std::vector<std::string> sent(N);
  for (int r = 0; r < ROUNDS; r++) {
    std::vector<std::string> data(N);
    int32_t fds[N];
    const uint8_t* bufs[N];
    int64_t lens[N];
    for (int i = 0; i < N; i++) {
      data[i] = "c" + std::to_string(i) + "r" + std::to_string(r) + "|" +
                std::string(static_cast<size_t>(r * 7 + i), 'x') + ";";
      fds[i] = sv[i][0];
      bufs[i] = reinterpret_cast<const uint8_t*>(data[i].data());
      lens[i] = static_cast<int64_t>(data[i].size());
      sent[i] += data[i];
    }
    const int64_t ticket = rt_egress_submit(eg, N, fds, bufs, lens);
    assert(ticket == static_cast<int64_t>(N) * (r + 1));
    data.clear();  // the job owns a copy: ours may go at once
    if (r % 3 == 0) assert(rt_egress_wait(eg, ticket - r % N, 5000) == 1);
    auto rows = collect_n(eg, N);
    for (size_t i = 0; i < N; i++) {
      assert(rows[3 * i] == sv[i][0]);  // completions in job order
      assert(rows[3 * i + 2] == 0);
    }
  }
  for (int i = 0; i < N; i++) assert(read_n(sv[i][1], sent[i].size()) == sent[i]);
  // 2) a peer that does not read, a small send buffer: a partial write (or
  // EAGAIN once full) is reported and NOT retried
  int slow[2];
  make_pair(slow, 4096);
  std::string big(1 << 20, 'z');
  int32_t fd1 = slow[0];
  const uint8_t* b1 = reinterpret_cast<const uint8_t*>(big.data());
  int64_t l1 = static_cast<int64_t>(big.size());
  rt_egress_submit(eg, 1, &fd1, &b1, &l1);
  auto rows = collect_n(eg, 1);
  assert(rows[0] == fd1 && rows[1] > 0 && rows[1] < l1 && rows[2] == 0);
  rt_egress_submit(eg, 1, &fd1, &b1, &l1);
  rows = collect_n(eg, 1);
  assert(rows[1] == 0 && (rows[2] == EAGAIN || rows[2] == EWOULDBLOCK));
  // 3) errors come back as errnos, one per connection, and the job goes on:
  // a peer that went away gives EPIPE (and no SIGPIPE), a closed fd EBADF
  int dead[2], gone[2];
  make_pair(dead);
  make_pair(gone);
  close(dead[1]);
  close(gone[0]), close(gone[1]);
  int32_t fds3[3] = {dead[0], gone[0], sv[1][0]};
  const uint8_t* b3[3] = {reinterpret_cast<const uint8_t*>("bye"),
                          reinterpret_cast<const uint8_t*>("bye"),
                          reinterpret_cast<const uint8_t*>("bye")};
  int64_t l3[3] = {3, 3, 3};
  const int64_t t3 = rt_egress_submit(eg, 3, fds3, b3, l3);
  assert(rt_egress_wait(eg, t3, 5000) == 1);
  assert(rt_egress_wait(eg, t3 - 2, 0) == 1);  // posted already: no wait
  rows = collect_n(eg, 3);
  assert(rows[0] == dead[0] && rows[1] == 0 && rows[2] == EPIPE);
  assert(rows[3] == gone[0] && rows[4] == 0 && rows[5] == EBADF);
  assert(rows[6] == sv[1][0] && rows[7] == 3 && rows[8] == 0);
  assert(read_n(sv[1][1], 3) == "bye");
  assert(rt_egress_wait(eg, t3 + 1, 10) == 0);  // never submitted: times out
  int64_t st[3];
  rt_egress_stats(eg, st);
  assert(st[0] > 0 && st[1] == N * ROUNDS + 5 && st[2] == ROUNDS + 3);
  // 4) free with a job still queued: it is sent before the thread ends
  int32_t fd4 = sv[0][0];
  rt_egress_submit(eg, 1, &fd4, b3, l3);
  rt_egress_free(eg);
  assert(read_n(sv[0][1], 3) == "bye");
  for (auto& p : sv) close(p[0]), close(p[1]);
  close(slow[0]), close(slow[1]), close(dead[0]);
  rt_egress_free(nullptr);
}

// ---- ingress.cc: the loop's side, as broker/ingress.py plays it
struct Chunk {
  int64_t flags, err, nframes;
  std::string bytes;               // the chunk's own bytes
  std::vector<int64_t> meta;       // rows, offsets rebased to `bytes`
};

// One collect (after waiting for the eventfd, at most `wait_ms`) with the
// given acks; appends every chunk to its connection's list.
static int64_t collect_in(void* in, std::map<int64_t, std::vector<Chunk>>& got,
                          int wait_ms,
                          const std::vector<int64_t>& ack_ids = {},
                          const std::vector<int64_t>& ack_bytes = {}) {
  pollfd p{rt_ingress_eventfd(in), POLLIN, 0};
  assert(poll(&p, 1, wait_ms) >= 0);
  const int64_t *chunks, *meta;
  const uint8_t* bytes;
  int64_t counts[3];
  const int64_t n = rt_ingress_collect(
      in, static_cast<int64_t>(ack_ids.size()), ack_ids.data(),
      ack_bytes.data(), &chunks, &meta, &bytes, counts);
  assert(n == counts[0]);
  for (int64_t i = 0; i < n; i++) {
    const int64_t* c = chunks + 7 * i;
    Chunk ch{c[1], c[2], c[6], std::string(), {}};
    assert(c[3] >= 0 && c[3] + c[4] <= counts[2]);
    ch.bytes.assign(reinterpret_cast<const char*>(bytes) + c[3],
                    static_cast<size_t>(c[4]));
    assert(c[5] + c[6] <= counts[1]);
    for (int64_t f = 0; f < c[6]; f++) {
      const int64_t* m = meta + 10 * (c[5] + f);
      int64_t r[10];
      std::memcpy(r, m, sizeof r);
      r[1] -= c[3];
      if ((m[0] >> 4) == 3) {
        r[3] -= c[3];
        if (m[6] >= 0) r[6] -= c[3];
        r[8] -= c[3];
      }
      assert(r[1] >= 0 && r[1] + r[2] <= c[4]);  // the body lies in the chunk
      ch.meta.insert(ch.meta.end(), r, r + 10);
    }
    got[c[0]].push_back(std::move(ch));
  }
  return n;
}

static void write_all(int fd, const std::string& s) {
  size_t off = 0;
  while (off < s.size()) {
    ssize_t w = write(fd, s.data() + off, s.size() - off);
    assert(w > 0);
    off += static_cast<size_t>(w);
  }
}

// A v3 QoS1 PUBLISH frame of topic "t/<i>" and the given payload.
static std::string publish_frame(int i, int pid, const std::string& payload) {
  const std::string topic = "t/" + std::to_string(i);
  uint8_t out[512];
  const int64_t n = rt_codec_encode_publish(
      reinterpret_cast<const uint8_t*>(topic.data()),
      static_cast<int64_t>(topic.size()),
      reinterpret_cast<const uint8_t*>(payload.data()),
      static_cast<int64_t>(payload.size()), nullptr, 0, 1, 0, 0, pid, out, 512);
  assert(n > 0);
  return std::string(reinterpret_cast<char*>(out), static_cast<size_t>(n));
}

static std::string all_bytes(const std::vector<Chunk>& v) {
  std::string s;
  for (const Chunk& c : v) s += c.bytes;
  return s;
}

static void test_ingress() {
  void* in = rt_ingress_new();
  assert(in);
  assert(rt_ingress_eventfd(in) >= 0);
  std::map<int64_t, std::vector<Chunk>> got;
  // 1) 16 connections, 10 rounds of a PUBLISH and a PUBACK each: every byte
  // comes out once, per connection in order, framed and pre-parsed
  constexpr int N = 16, ROUNDS = 10;
  int sv[N][2];
  for (auto& p : sv) make_pair(p);
  for (int i = 0; i < N; i++)
    assert(rt_ingress_add(in, i + 1, sv[i][0], 0, 1 << 20, nullptr, 0) == 0);
  std::vector<std::string> sent(N);
  const std::string puback = {0x40, 0x02, 0x00, 0x07};
  for (int r = 0; r < ROUNDS; r++) {
    for (int i = 0; i < N; i++) {
      const std::string d =
          publish_frame(i, r + 1, "p" + std::to_string(r)) + puback;
      write_all(sv[i][1], d);
      sent[i] += d;
    }
    std::vector<int64_t> ids, bytes;
    for (auto& [id, v] : got) {  // acknowledge what the last rounds brought
      ids.push_back(id);
      bytes.push_back(0);
    }
    collect_in(in, got, 5000, ids, bytes);
  }
  for (int tries = 0; tries < 200; tries++) {
    size_t have = 0;
    for (int i = 0; i < N; i++) have += all_bytes(got[i + 1]).size();
    size_t want = 0;
    for (auto& s : sent) want += s.size();
    if (have == want) break;
    collect_in(in, got, 100);
  }
  for (int i = 0; i < N; i++) {
    assert(all_bytes(got[i + 1]) == sent[i]);
    int64_t frames = 0;
    for (const Chunk& c : got[i + 1]) {
      assert(c.flags == 1 && c.err == 0);
      for (int64_t f = 0; f < c.nframes; f++) {
        const int64_t* m = c.meta.data() + 10 * f;
        if (m[0] == 0x32) {  // the PUBLISH: topic, id and payload spans
          const std::string topic = "t/" + std::to_string(i);
          assert(c.bytes.substr(static_cast<size_t>(m[3]),
                                static_cast<size_t>(m[4])) == topic);
          assert(m[5] >= 1 && m[5] <= ROUNDS && m[6] == -1);
          assert(c.bytes.substr(static_cast<size_t>(m[8]),
                                static_cast<size_t>(m[9])) ==
                 "p" + std::to_string(m[5] - 1));
        } else {
          assert(m[0] == 0x40 && m[2] == 2);
        }
      }
      frames += c.nframes;
    }
    assert(frames == 2 * ROUNDS);
  }
  // 2) a head the caller had read already, then a 30 KB frame in pieces:
  // nothing is posted until the frame is whole, then it comes in one chunk
  int big[2];
  make_pair(big);
  std::string frame = {static_cast<char>(0x82)};  // SUBSCRIBE, 30000-byte body
  frame += static_cast<char>(0x80 | (30000 & 0x7F));
  frame += static_cast<char>(0x80 | ((30000 >> 7) & 0x7F));
  frame += static_cast<char>(30000 >> 14);
  frame += std::string(30000, 's');
  assert(rt_ingress_add(in, 100, big[0], 1, 1 << 20,
                        reinterpret_cast<const uint8_t*>(frame.data()), 3) == 0);
  for (size_t off = 3; off < frame.size();) {
    const size_t n = std::min<size_t>(7001, frame.size() - off);
    write_all(big[1], frame.substr(off, n));
    off += n;
    collect_in(in, got, off < frame.size() ? 30 : 5000);
    if (off < frame.size()) assert(got.count(100) == 0);
  }
  while (got.count(100) == 0) collect_in(in, got, 100);
  assert(got[100].size() == 1 && got[100][0].bytes == frame);
  assert(got[100][0].nframes == 1 && got[100][0].meta[0] == 0x82 &&
         got[100][0].meta[1] == 4 && got[100][0].meta[2] == 30000);
  // 3) good frames, then one the scan refuses: the good ones come framed,
  // the rest raw, and everything later raw too (oversize, then a CONNECT)
  int bad[2], over[2], conn[2];
  make_pair(bad), make_pair(over), make_pair(conn);
  assert(rt_ingress_add(in, 101, bad[0], 0, 1 << 20, nullptr, 0) == 0);
  assert(rt_ingress_add(in, 102, over[0], 0, 64, nullptr, 0) == 0);
  assert(rt_ingress_add(in, 103, conn[0], 0, 1 << 20, nullptr, 0) == 0);
  const std::string malformed = {0x30, static_cast<char>(0xFF),
                                 static_cast<char>(0xFF), static_cast<char>(0xFF),
                                 static_cast<char>(0xFF), 0x01};
  write_all(bad[1], puback + malformed);
  const std::string oversize = {0x30, 0x41};  // body of 65 > 64
  write_all(over[1], puback + puback + oversize);
  const std::string connect = {0x10, 0x02, 0x00, 0x00};
  write_all(conn[1], puback + connect);
  while (all_bytes(got[101]).size() < 10 || all_bytes(got[102]).size() < 10 ||
         all_bytes(got[103]).size() < 8)
    collect_in(in, got, 100);
  for (int64_t id : {101, 102, 103}) {
    assert(got[id].size() == 2);
    assert(got[id][0].flags == 1 && got[id][0].nframes == (id == 102 ? 2 : 1));
    assert(got[id][1].flags == 2 && got[id][1].nframes == 0);
  }
  assert(got[101][1].bytes == malformed && got[102][1].bytes == oversize &&
         got[103][1].bytes == connect);
  write_all(bad[1], puback);
  while (got[101].size() < 3) collect_in(in, got, 100);
  assert(got[101][2].flags == 2 && got[101][2].bytes == puback);
  // 4) EOF in the middle of a frame, and a reset: posted, not handled
  int eof[2], rst[2];
  make_pair(eof), make_pair(rst);
  assert(rt_ingress_add(in, 104, eof[0], 0, 1 << 20, nullptr, 0) == 0);
  assert(rt_ingress_add(in, 105, rst[0], 0, 1 << 20, nullptr, 0) == 0);
  write_all(eof[1], puback + std::string("\x30\x10half", 6));
  close(eof[1]);
  assert(write(rst[0], "unread", 6) == 6);  // the peer closes over it: a reset
  close(rst[1]);
  while (got[104].empty() || got[104].back().flags != 4 || got[105].empty())
    collect_in(in, got, 100);
  assert(all_bytes(got[104]) == puback);
  assert(got[105].size() == 1 && got[105][0].flags == 8 &&
         got[105][0].err == ECONNRESET);
  // 5) the bound: a flood nobody acknowledges stops being read at 64 KB
  // (+ one read); an acknowledgement sets it going again
  int flood[2];
  make_pair(flood);
  assert(fcntl(flood[1], F_SETFL, O_NONBLOCK) == 0);
  assert(rt_ingress_add(in, 106, flood[0], 0, 1 << 20, nullptr, 0) == 0);
  std::string burst;
  for (int k = 0; k < 256; k++) burst += puback;  // 1 KB of whole frames
  size_t wrote = 0;
  auto pump = [&] {  // write until the socket buffer is full
    for (;;) {
      ssize_t w = write(flood[1], burst.data(), burst.size());
      if (w < 0) {
        assert(errno == EAGAIN || errno == EWOULDBLOCK);
        return;
      }
      wrote += static_cast<size_t>(w);
    }
  };
  int64_t st[4];
  size_t seen = 0;
  for (int rounds = 0; rounds < 100; rounds++) {
    pump();
    collect_in(in, got, 50);
    const size_t now = all_bytes(got[106]).size();
    if (now == seen && now >= 64 * 1024) break;
    seen = now;
  }
  assert(seen >= 64 * 1024 && seen < 64 * 1024 + 2 * 64 * 1024);
  assert(wrote > seen);  // the rest waits in the kernel: TCP backpressure
  bool paused = false;
  for (const Chunk& c : got[106]) paused |= (c.flags & 16) != 0;
  assert(paused);
  rt_ingress_stats(in, st);
  assert(st[3] >= 1);
  collect_in(in, got, 50);
  assert(all_bytes(got[106]).size() == seen);  // still stopped
  collect_in(in, got, 50, {106}, {static_cast<int64_t>(seen)});
  while (all_bytes(got[106]).size() == seen) collect_in(in, got, 100);
  // 6) remove while the peer floods: afterwards the fd is ours to close,
  // and a later collect may still bring what was posted before
  std::thread flooder([&] {
    for (int k = 0; k < 200; k++) {
      if (send(sv[0][1], puback.data(), puback.size(), MSG_NOSIGNAL) < 0) return;
    }
  });
  rt_ingress_remove(in, 1);
  close(sv[0][0]);
  flooder.join();
  rt_ingress_remove(in, 1);     // twice: nothing to do
  rt_ingress_remove(in, 4242);  // never added
  collect_in(in, got, 10);
  rt_ingress_stats(in, st);
  assert(st[0] > 0 && st[1] >= N && st[2] >= 1);  // rounds may coalesce
  // an fd that cannot be polled is refused at once
  assert(rt_ingress_add(in, 107, sv[0][0], 0, 1 << 20, nullptr, 0) == -EBADF);
  // 7) free with connections still registered: their fds stay ours
  rt_ingress_free(in);
  write_all(sv[1][1], puback);
  assert(read_n(sv[1][0], 4) == puback);
  close(sv[0][1]);
  for (int i = 1; i < N; i++) close(sv[i][0]), close(sv[i][1]);
  for (int* p : {big, bad, over, conn, flood}) close(p[0]), close(p[1]);
  close(eof[0]), close(rst[0]);
  rt_ingress_free(nullptr);
}

int main() {
  test_egress();
  test_ingress();
  test_trie();
  test_encoder();
  test_match_decode_routes();
  test_codec();
  std::puts("runtime sanitizer checks passed");
  return 0;
}

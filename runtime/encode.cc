// C++ batched publish-topic encoder for the partitioned automaton.
//
// Host-side encode (tokenize + candidate-chunk resolution) is the whole
// host cost of a device batch worth naming (PERF.md §5/§6, PR 26). This
// implements rmqtt_tpu/ops/partitioned.py::PartitionedTable.encode_topics
// natively for the WHOLE batch in one call: split levels, token-dict
// lookup, $-prefix flag, and the candidate-chunk walk itself — the encoder
// holds a mirror of the table's partition-key → chunk-ids maps
// (`_excl_chunks` / `_shared_chunks_of`) and of its split partitions
// (`_split_keys`), derives each topic's partition keys (`topic_partitions`:
// at most 15 three-level ones, and the token and '+' children of every
// split partition the topic descends through) and unions their chunks in
// `_candidates_for`'s order. Nothing comes back to Python per topic,
// whatever share of the batch has a never-seen prefix. The table keeps the
// mirror in step the way it keeps the tokens: mutations mark the keys they
// touched, the next encode pushes those keys' chunk lists in one or two
// rt_enc_parts_put calls and the new splits in one rt_enc_split_put call
// (a compaction install resyncs the chunk lists wholesale; a split is kept
// for good).
//
// Exposed as a C ABI for ctypes (no pybind11 in this image). Thread safety:
// external, same contract as topics.cc.

#include "rmqtt_runtime.h"
#include <cstdint>
#include <cstring>
#include <algorithm>
#include <string>
#include <string_view>
#include <initializer_list>
#include <unordered_map>
#include <unordered_set>
#include <vector>

namespace {

constexpr int32_t kUnkTok = 3;   // ops/encode.py UNK_TOK
constexpr int32_t kPadTok = 0;   // ops/encode.py PAD_TOK
constexpr int32_t kPlusTok = 1;  // ops/encode.py PLUS_TOK

// Heterogeneous hashing: lets find() take a string_view without
// materializing a std::string per level (the encode loop does one lookup
// per level per topic — heap allocs there dominated the first version).
struct SvHash {
  using is_transparent = void;
  size_t operator()(std::string_view s) const noexcept {
    return std::hash<std::string_view>{}(s);
  }
};
struct SvEq {
  using is_transparent = void;
  bool operator()(std::string_view a, std::string_view b) const noexcept { return a == b; }
};

inline uint64_t mix(uint64_t h, uint32_t v) {
  h ^= v;
  h *= 0x9E3779B97F4A7C15ull;
  return h ^ (h >> 29);
}
// The bytes of int32 words (a deep key, below): mixed a word
// at a time, which costs less than hashing their bytes.
struct WordsHash {
  using is_transparent = void;
  size_t operator()(std::string_view s) const noexcept {
    uint64_t h = s.size();
    for (size_t i = 0; i + sizeof(uint32_t) <= s.size(); i += sizeof(uint32_t)) {
      uint32_t v;
      std::memcpy(&v, s.data() + i, sizeof v);
      h = mix(h, v);
    }
    return static_cast<size_t>(h);
  }
};

// Heterogeneous unordered lookup (P1690) only ships in libstdc++ from GCC
// 11; on older toolchains fall back to one reusable thread_local buffer so
// the hot loop still never allocates per lookup.
#if defined(__cpp_lib_generic_unordered_lookup)
template <class Map>
auto sv_find(const Map& m, std::string_view k) {
  return m.find(k);
}
#else
template <class Map>
auto sv_find(const Map& m, std::string_view k) {
  static thread_local std::string buf;
  buf.assign(k.data(), k.size());
  return m.find(buf);
}
#endif

// partitioned.py partition_key kinds. A key is its kind, then the token id
// (or kPlusTok) of each level it names — the token-id image of the Python
// tuple ("4", "a", "+", "c") and the like. The deep kinds "4", "5", ...
// name 3, 4, ... levels and are stored as kDeep + levels; every other kind
// has a fixed number of levels. Every literal level of a stored key was
// interned when its filter row was written, so a topic level the
// dictionary does not know (kUnkTok) cannot name any partition.
enum Kind : int32_t { kHash = 0, k1 = 1, k2 = 2, k2E = 3, kH3 = 4, kBad = -1 };
constexpr int32_t kArity[] = {0, 1, 1, 2, 2};  // by Kind
constexpr int32_t kDeep = 16;                  // deep kind "N" = kDeep + N - 1
constexpr int32_t kMaxKeyLevels = 4096;        // a parse bound, far past any filter

using Key = std::vector<int32_t>;

// A key of at most three levels — every key of a table that never splits —
// is held fixed-width (unused levels 0), hashed and compared inline; a
// deeper one, which only a split makes, by the bytes of its words.
struct Key3 {
  int32_t kind, a, b, c;
  bool operator==(const Key3& o) const noexcept {
    return kind == o.kind && a == o.a && b == o.b && c == o.c;
  }
};
struct Key3Hash {
  size_t operator()(const Key3& k) const noexcept {
    uint64_t h = static_cast<uint32_t>(k.kind);
    for (int32_t v : {k.a, k.b, k.c}) h = mix(h, static_cast<uint32_t>(v));
    return static_cast<size_t>(h);
  }
};
constexpr size_t kKey3Words = 4;

Key3 key3(const int32_t* k, size_t n) {
  return {k[0], n > 1 ? k[1] : 0, n > 2 ? k[2] : 0, n > 3 ? k[3] : 0};
}
std::string_view words(const int32_t* k, size_t n) {
  return {reinterpret_cast<const char*>(k), n * sizeof(int32_t)};
}

// Next '/'-terminated segment of [*p, end): levels cannot hold a '/', so
// the token and partition-key streams delimit themselves with it whatever
// other bytes a level holds. False when no terminator is left.
bool next_segment(const char** p, const char* end, std::string_view* out) {
  const void* slash =
      *p < end ? std::memchr(*p, '/', static_cast<size_t>(end - *p)) : nullptr;
  if (!slash) return false;
  const char* q = static_cast<const char*>(slash);
  *out = std::string_view(*p, static_cast<size_t>(q - *p));
  *p = q + 1;
  return true;
}

// The stored kind of a key's first segment, or kBad.
int32_t kind_of(std::string_view s) {
  if (s == "#") return kHash;
  if (s == "1") return k1;
  if (s == "2") return k2;
  if (s == "2E") return k2E;
  if (s == "H3") return kH3;
  if (s.empty() || s.size() > 4 || s[0] == '0') return kBad;
  int32_t n = 0;
  for (char ch : s) {
    if (ch < '0' || ch > '9') return kBad;
    n = n * 10 + (ch - '0');
  }
  return n >= 4 && n - 1 <= kMaxKeyLevels ? kDeep + n - 1 : kBad;
}

int32_t arity_of(int32_t kind) { return kind >= kDeep ? kind - kDeep : kArity[kind]; }

struct Encoder {
  std::unordered_map<std::string, int32_t, SvHash, SvEq> tokens;
  // partition key -> chunk ids it occupies (exclusive, then shared)
  std::unordered_map<Key3, std::vector<int32_t>, Key3Hash> parts;
  std::unordered_map<std::string, std::vector<int32_t>, WordsHash, SvEq> deep_parts;
  // split deep partitions (their words): a topic that goes on past one
  // descends into its token and '+' children
  std::unordered_set<std::string, WordsHash, SvEq> splits;
  // topic levels a candidate row depends on: 3, or one past the longest
  // split key
  int32_t key_depth = 3;
  // per-batch memo: a topic's candidate row is a function of its first
  // <= key_depth key tokens and min(levels, key_depth) — the first topic
  // of the batch with a given prefix walks, later ones copy its row and
  // share its group id. Keyed {kl, tokens} up to three levels, by the
  // tokens' words (whose length says kl) past that
  std::unordered_map<Key3, int32_t, Key3Hash> seen_prefix;
  std::unordered_map<std::string, int32_t, WordsHash, SvEq> seen_deep;
  // chunk-id dedup stamps (partitions share boundary / shared chunks)
  std::vector<uint32_t> stamp;
  uint32_t stamp_gen = 0;
  // scratch: the key being walked, a topic's key tokens
  Key key, kt;

  // Key k[0..n)'s chunk ids, or null.
  const std::vector<int32_t>* find(const int32_t* k, size_t n) const {
    if (n <= kKey3Words) {
      auto it = parts.find(key3(k, n));
      return it == parts.end() ? nullptr : &it->second;
    }
    auto it = sv_find(deep_parts, words(k, n));
    return it == deep_parts.end() ? nullptr : &it->second;
  }
  std::vector<int32_t>& slot(const Key& k) {
    if (k.size() <= kKey3Words) return parts[key3(k.data(), k.size())];
    return deep_parts[std::string(words(k.data(), k.size()))];
  }
  void erase(const Key& k) {
    if (k.size() <= kKey3Words)
      parts.erase(key3(k.data(), k.size()));
    else
      deep_parts.erase(std::string(words(k.data(), k.size())));
  }

  int32_t key_token(std::string_view lev) const {
    if (lev == "+") return kPlusTok;
    auto it = sv_find(tokens, lev);
    return it == tokens.end() ? kUnkTok : it->second;
  }

  // Parse one key of a '/'-segmented stream into `out`: its kind, then
  // as many levels as the kind names. False on an unknown kind or level,
  // or a stream that ends inside the key.
  bool parse_key(const char** p, const char* end, Key* out) const {
    std::string_view seg;
    int32_t kind;
    if (!next_segment(p, end, &seg) || (kind = kind_of(seg)) == kBad) return false;
    out->assign(1, kind);
    for (int32_t l = 0, n = arity_of(kind); l < n; ++l) {
      int32_t tok;
      if (!next_segment(p, end, &seg) || (tok = key_token(seg)) == kUnkTok) return false;
      out->push_back(tok);
    }
    return true;
  }
};

// One topic's candidate walk: partitioned.py topic_partitions +
// _candidates_for.
struct Walk {
  Encoder* enc;
  const int32_t* t;  // the topic's first min(nlev, key_depth) key tokens
  int32_t nlev;      // min(levels, key_depth)
  int32_t* out;
  int32_t nc_cap;
  int32_t count = 0;

  // Union a key's chunks into the row: first occurrence wins, the TRUE
  // count runs on past nc_cap (the caller grows and retries).
  void add(const int32_t* k, size_t n) {
    for (size_t i = 1; i < n; ++i)
      if (k[i] == kUnkTok) return;
    const std::vector<int32_t>* cids = enc->find(k, n);
    if (!cids) return;
    for (int32_t cid : *cids) {
      uint32_t& st = enc->stamp[static_cast<size_t>(cid)];
      if (st == enc->stamp_gen) continue;
      st = enc->stamp_gen;
      if (count < nc_cap) out[count] = cid;
      ++count;
    }
  }
  // a key of a fixed kind, its words on the stack
  void flat(std::initializer_list<int32_t> k) { add(k.begin(), k.size()); }
  // partitioned.py _descend: the deep key, then — where it is split and
  // the topic goes on past it — its token child's and '+' child's walks.
  void descend() {
    Key& k = enc->key;
    add(k.data(), k.size());
    const int32_t d = static_cast<int32_t>(k.size()) - 1;  // levels it names
    if (nlev <= d || enc->splits.empty() ||
        sv_find(enc->splits, words(k.data(), k.size())) == enc->splits.end())
      return;
    ++k[0];
    k.push_back(t[d]);
    descend();
    k.back() = kPlusTok;
    descend();
    k.pop_back();
    --k[0];
  }
  void deep(int32_t a, int32_t b, int32_t c) {
    enc->key.assign({kDeep + 3, a, b, c});
    descend();
  }

  int32_t run() {
    if (++enc->stamp_gen == 0) {  // wrapped: stale stamps could alias
      std::fill(enc->stamp.begin(), enc->stamp.end(), 0u);
      enc->stamp_gen = 1;
    }
    const int32_t P = kPlusTok;
    flat({kHash});
    flat({k2, t[0]});
    flat({k2, P});
    if (nlev == 1) {
      flat({k1, t[0]});
      flat({k1, P});
      return count;
    }
    const int32_t pairs[4][2] = {{t[0], t[1]}, {t[0], P}, {P, t[1]}, {P, P}};
    for (const auto& p : pairs) flat({kH3, p[0], p[1]});
    if (nlev == 2) {
      for (const auto& p : pairs) flat({k2E, p[0], p[1]});
      return count;
    }
    for (const auto& p : pairs) {
      deep(p[0], p[1], t[2]);
      deep(p[0], p[1], P);
    }
    return count;
  }
};

}  // namespace

extern "C" {

void* rt_enc_new() { return new Encoder(); }

void rt_enc_free(void* h) { delete static_cast<Encoder*>(h); }

// Intern the level strings in `blob` — each followed by '/' — as ids first_id, first_id+1, ... (the table's TokenDict
// is append-only, so a sync is one contiguous run). Returns how many.
int64_t rt_enc_add_tokens(void* h, const char* blob, int64_t blob_len,
                          int32_t first_id) {
  auto* enc = static_cast<Encoder*>(h);
  const char* p = blob;
  std::string_view lev;
  int64_t n = 0;
  while (next_segment(&p, blob + blob_len, &lev))
    enc->tokens.emplace(std::string(lev), first_id + static_cast<int32_t>(n++));
  return n;
}

void rt_enc_parts_clear(void* h) {
  auto* enc = static_cast<Encoder*>(h);
  enc->parts.clear();
  enc->deep_parts.clear();
}

// Install n partition keys' chunk lists in one call. `keys` is the n keys'
// segments — the Python tuple's kind, then its levels ("4","a","+","c";
// "5","a","+","c","d"; "2E","","b"; "#") — each followed by '/'; the kind
// fixes how many levels follow. `counts[i]` chunk ids of key i follow
// one another in `chunks`, in the table's own order. With `append` 0 a
// key's list is replaced (an empty one erases the key); with 1 the ids
// are appended — the table pushes a key's exclusive chunks first, then its
// shared ones (`_candidates_for`'s order). Key levels are resolved through
// the token dictionary, so tokens must be synced first. Returns n, or
// -(i+1) when key i does not parse (unknown kind / level, or the stream
// ends early: a caller bug, fail loudly).
int64_t rt_enc_parts_put(void* h, const char* keys, int64_t keys_len, int64_t n,
                         const int32_t* counts, const int32_t* chunks,
                         int32_t append) {
  auto* enc = static_cast<Encoder*>(h);
  const char* p = keys;
  const char* const end = keys + keys_len;
  Key key;
  for (int64_t i = 0; i < n; ++i) {
    if (!enc->parse_key(&p, end, &key)) return -(i + 1);
    const int32_t cnt = counts[i];
    if (cnt == 0) {
      if (!append) enc->erase(key);
      continue;
    }
    auto& v = enc->slot(key);
    if (!append) v.clear();
    v.insert(v.end(), chunks, chunks + cnt);
    const int32_t mx = *std::max_element(chunks, chunks + cnt);
    chunks += cnt;
    if (static_cast<size_t>(mx) >= enc->stamp.size())
      enc->stamp.resize(static_cast<size_t>(mx) + 1, 0u);
  }
  return p == end ? n : -(n + 1);
}

// Mark n deep partition keys (segments as rt_enc_parts_put's) split, for
// good: a topic that goes on past such a key also walks its token and '+'
// children. Returns n, or -(i+1) when key i does not parse or is not of a
// deep kind.
int64_t rt_enc_split_put(void* h, const char* keys, int64_t keys_len, int64_t n) {
  auto* enc = static_cast<Encoder*>(h);
  const char* p = keys;
  const char* const end = keys + keys_len;
  Key key;
  for (int64_t i = 0; i < n; ++i) {
    if (!enc->parse_key(&p, end, &key) || key[0] < kDeep) return -(i + 1);
    enc->splits.emplace(words(key.data(), key.size()));
    enc->key_depth = std::max(enc->key_depth, static_cast<int32_t>(key.size()));
  }
  return p == end ? n : -(n + 1);
}

// Encode n '\0'-separated topics. Fills ttok [n, max_levels] (PAD beyond the
// topic's levels), tlen [n] (full level count), tdollar [n], cand
// [n, nc_cap] (each topic's candidate chunk ids, 0-padded), cand_counts [n]
// (the TRUE count, even when > nc_cap — caller grows nc_cap and retries)
// and group [n]: topics of this batch with equal prefix keys — hence equal
// candidate rows — share one id >= 0, letting the caller upload each
// distinct row once. Returns the batch's largest candidate count.
int32_t rt_enc_encode(void* h, const char* blob, int64_t n, int32_t max_levels,
                      int32_t* ttok, int32_t* tlen, uint8_t* tdollar, int32_t nc_cap,
                      int32_t* cand, int32_t* cand_counts, int32_t* group) {
  auto* enc = static_cast<Encoder*>(h);
  const auto& tokens = enc->tokens;
  auto& seen = enc->seen_prefix;
  seen.clear();
  enc->seen_deep.clear();
  const int32_t depth = enc->key_depth;
  const int32_t looked = std::max(max_levels, depth);
  Key& kt = enc->kt;  // key tokens of the first <= depth levels
  kt.assign(static_cast<size_t>(depth), 0);
  int32_t max_count = 0;
  const char* p = blob;
  for (int64_t j = 0; j < n; ++j) {
    const char* topic_start = p;
    int32_t* row = ttok + j * max_levels;
    int32_t nlev = 0;
    const char* lev_start = p;
    for (;; ++p) {
      if (*p == '/' || *p == '\0') {
        if (nlev < looked) {
          std::string_view lev(lev_start, static_cast<size_t>(p - lev_start));
          auto it = sv_find(tokens, lev);
          const int32_t tok = it == tokens.end() ? kUnkTok : it->second;
          if (nlev < max_levels) row[nlev] = tok;
          // a literal "+" level names the PLUS partitions, as the Python
          // walk's string equality has it (no valid publish carries one)
          if (nlev < depth) kt[nlev] = lev == "+" ? kPlusTok : tok;
        }
        ++nlev;
        if (*p == '\0') break;
        lev_start = p + 1;
      }
    }
    for (int32_t i = nlev; i < max_levels; ++i) row[i] = kPadTok;
    tlen[j] = nlev;
    tdollar[j] = topic_start[0] == '$' ? 1 : 0;
    const int32_t kl = nlev < depth ? nlev : depth;
    int32_t* out = cand + j * nc_cap;
    const auto jj = static_cast<int32_t>(j);
    int32_t first;  // the batch's first topic with this memo key
    if (kl <= 3) {
      first = seen.try_emplace(Key3{kl, kt[0], kl > 1 ? kt[1] : 0, kl > 2 ? kt[2] : 0}, jj)
                  .first->second;
    } else {
      first = enc->seen_deep.try_emplace(std::string(words(kt.data(), kl)), jj).first->second;
    }
    int32_t c;
    if (first == jj) {
      c = Walk{enc, kt.data(), kl, out, nc_cap}.run();
    } else {
      c = cand_counts[first];
      std::memcpy(out, cand + static_cast<int64_t>(first) * nc_cap,
                  static_cast<size_t>(c < nc_cap ? c : nc_cap) * sizeof(int32_t));
    }
    group[j] = first;
    for (int32_t i = c; i < nc_cap; ++i) out[i] = 0;
    cand_counts[j] = c;
    if (c > max_count) max_count = c;
    ++p;  // skip '\0'
  }
  return max_count;
}

}  // extern "C"

// Decode the ROUTE-level batch-global compaction (ops/partitioned.py
// compact_global_impl): one widx*32+bitpos entry per match, flat
// topic-major by the device's two-stage prefix sum; counts[bp] (per
// padded-topic route counts, fetched with the routes) reattributes the
// slots. For entry r of topic t the matched row is
// chunk_ids[t, (r>>5)/wpc]*chunk + ((r>>5)%wpc)*32 + (r&31), mapped
// through fid_map and sorted per topic. Writes nothing past b real
// topics — a nonzero count there is a device/compaction bug (padded
// topics encode tlen=-2 and can match nothing). Returns the total route
// count, or -1 on any out-of-range widx/fid/count.
int64_t rt_match_decode_routes(const uint32_t* routes, int64_t n,
                               const int64_t* counts,
                               const int32_t* chunk_ids, int64_t b,
                               int64_t bp, int64_t nc, int32_t wpc,
                               int32_t chunk, const int64_t* fid_map,
                               int64_t* out_fids) {
  const int64_t w_total = nc * wpc;
  for (int64_t t = b; t < bp; ++t)
    if (counts[t] != 0) return -1;  // padded topic matched: device bug
  int64_t off = 0;
  for (int64_t t = 0; t < b; ++t) {
    const int64_t c = counts[t];
    if (c == 0) continue;
    // counts must stay consistent with the fetched routes buffer (and
    // out_fids, allocated at n): a negative or overrunning count is a
    // device/caller bug and must fail loudly, not read heap garbage
    if (c < 0 || off + c > n) return -1;
    int64_t* span = out_fids + off;
    const int32_t* crow = chunk_ids + t * nc;
    const uint32_t* rs = routes + off;
    for (int64_t i = 0; i < c; ++i) {
      const uint32_t r = rs[i];
      const int64_t widx = r >> 5;
      if (widx >= w_total) return -1;  // route out of range: device bug
      const int64_t fid =
          fid_map[static_cast<int64_t>(crow[widx / wpc]) * chunk +
                  (widx % wpc) * 32 + (r & 31)];
      if (fid < 0 || fid >= (1LL << 32)) {
        // cleared-row sentinel (-1) or overflow: a kernel/compaction bug
        // must fail loudly (same contract as the numpy oracle), never
        // hand a bogus subscriber id to delivery
        return -1;
      }
      span[i] = fid;
    }
    std::sort(span, span + c);
    off += c;
  }
  return off;
}

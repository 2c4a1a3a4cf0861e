// C ABI of the native runtime — included by every implementation file AND
// the sanitizer test driver so a signature drift is a compile error (with
// extern "C" linkage a hand-redeclared prototype would still link and call
// with a mismatched ABI).
#pragma once

#include <cstdint>

extern "C" {

// topics.cc — topic-trie matcher
void* rt_trie_new();
void rt_trie_free(void* trie);
int rt_trie_add(void* trie, const char* topic_filter, int64_t value);
int rt_trie_remove(void* trie, const char* topic_filter, int64_t value);
int64_t rt_trie_size(void* trie);
int64_t rt_trie_match(void* trie, const char* topic, int64_t* out, int64_t cap);
int64_t rt_trie_match_batch(void* trie, const char* blob, int64_t n,
                            int64_t* counts, int64_t* out, int64_t cap);

// encode.cc — batched publish-topic encoder
void* rt_enc_new();
void rt_enc_free(void* enc);
int64_t rt_enc_add_tokens(void* enc, const char* blob, int64_t blob_len,
                          int32_t first_id);
void rt_enc_parts_clear(void* enc);
int64_t rt_enc_parts_put(void* enc, const char* keys, int64_t keys_len, int64_t n,
                         const int32_t* counts, const int32_t* chunks,
                         int32_t append);
int64_t rt_enc_split_put(void* enc, const char* keys, int64_t keys_len, int64_t n);
int32_t rt_enc_encode(void* enc, const char* blob, int64_t n, int32_t max_levels,
                      int32_t* ttok, int32_t* tlen, uint8_t* tdollar, int32_t nc_cap,
                      int32_t* cand, int32_t* cand_counts, int32_t* group);
int64_t rt_match_decode_routes(const uint32_t* routes, int64_t n,
                               const int64_t* counts,
                               const int32_t* chunk_ids, int64_t b,
                               int64_t bp, int64_t nc, int32_t wpc,
                               int32_t chunk, const int64_t* fid_map,
                               int64_t* out_fids);

// codec.cc — MQTT frame scanner + PUBLISH frame assembler + topic validation
int64_t rt_codec_scan(const uint8_t* buf, int64_t len, int32_t is_v5,
                      int64_t max_size, int64_t* meta, int64_t cap,
                      int64_t* consumed, int32_t* err);
int64_t rt_codec_encode_publish(const uint8_t* topic, int64_t topic_len,
                                const uint8_t* payload, int64_t payload_len,
                                const uint8_t* props, int64_t props_len,
                                int32_t qos, int32_t retain, int32_t dup,
                                int32_t packet_id, uint8_t* out, int64_t cap);
int rt_topic_validate(const uint8_t* s, int64_t len, int is_filter);

// egress.cc — off-loop socket writes (the library's one thread)
void* rt_egress_new();
void rt_egress_free(void* eg);
int32_t rt_egress_eventfd(void* eg);
int64_t rt_egress_submit(void* eg, int64_t n, const int32_t* fds,
                         const uint8_t* const* bufs, const int64_t* lens);
int64_t rt_egress_collect(void* eg, int64_t* out, int64_t cap);
int32_t rt_egress_wait(void* eg, int64_t ticket, int32_t timeout_ms);
void rt_egress_stats(void* eg, int64_t* out);

// ingress.cc — off-loop socket reads + frame scan (the library's second thread)
void* rt_ingress_new();
void rt_ingress_free(void* in);
int32_t rt_ingress_eventfd(void* in);
int32_t rt_ingress_add(void* in, int64_t id, int32_t fd, int32_t is_v5,
                       int64_t max_size, const uint8_t* head,
                       int64_t head_len);
void rt_ingress_remove(void* in, int64_t id);
int64_t rt_ingress_collect(void* in, int64_t n_acks, const int64_t* ack_ids,
                           const int64_t* ack_bytes, const int64_t** chunks,
                           const int64_t** meta, const uint8_t** bytes,
                           int64_t* counts);
void rt_ingress_stats(void* in, int64_t* out);

}  // extern "C"

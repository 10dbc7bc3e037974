// ThreadSanitizer soak for the native ingest/commit path.
//
// The Go reference's race-correctness strategy is running its whole test
// suite under `go test -race` (reference .circleci/config.yml:104-112).
// This driver is the equivalent gate for OUR native hot path: it links
// dogstatsd.cpp directly, spins up the same thread topology the Python
// runtime creates (multiple UDP readers calling vn_ingest_routed over
// shared shard contexts, SSF span readers on one shared span context, a
// flush thread draining every context, a telemetry thread reading the
// stats counters, an import thread upserting series), and runs them all
// concurrently under -fsanitize=thread. Any data race on the shard
// mutex discipline aborts the build (TSan exits non-zero).
//
// A second round drives the chunk commit (ingest_buffer's one lock hold
// a context for a whole buffer; the first round's lock instrumentation
// keeps the commit at one line a hold): readers hand 42-line buffers to
// vn_ingest_routed while a flush thread closes intervals under vn_lock
// (tally, plane detach, drains, vn_ctx_reset), so epochs turn over under
// the readers' feet and every accepted line must still be counted once.
//
// Built+run by `make -C native tsan` (tools/ci.sh runs it).

#include <atomic>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <string>
#include <thread>
#include <vector>

extern "C" {
void* vn_ctx_new(int hll_precision);
void vn_ctx_free(void* p);
int vn_ingest(void* p, const char* buf, int len);
int vn_ingest_routed(void** ctxps, int nctx, const char* buf, int len);
int vn_ingest_ssf_many(void* p, const char* buf, long long len,
                       const char* ind_name, int ind_len, const char* obj_name,
                       int obj_len, double uniq_rate, int* errors_out,
                       int* fallback_off, int* fallback_len, int fallback_cap,
                       int* nfall_out);
int vn_drain_histo(void* p, int32_t* rows, float* vals, float* wts, int cap);
int vn_drain_set(void* p, int32_t* rows, int32_t* idx, int8_t* rank, int cap);
int vn_drain_counter(void* p, int32_t* rows, double* contribs, int cap);
int vn_drain_gauge(void* p, int32_t* rows, double* vals, int cap);
int vn_drain_new_series(void* p, const int32_t** pools, const int32_t** rows,
                        const int32_t** sids, const int32_t** first_at,
                        const int32_t** first_kinds,
                        const int32_t** first_scopes, int* n_first,
                        const char** strs, long long* strs_len,
                        unsigned* generation);
int vn_drain_ssf_services(void* p, char* buf, int cap);
int vn_drain_other(void* p, char* buf, int cap);
int vn_upsert(void* p, const char* name, int name_len, int kind,
              const char* joined_tags, int tags_len, int scope_class);
long long vn_processed(void* p);
long long vn_errors(void* p);
int vn_pending_histo(void* p);
int vn_pending_set(void* p);
int vn_pending_counter(void* p);
int vn_pending_gauge(void* p);
int vn_lock_stats(void* p, long long out[5], long long* wait_out,
                  long long* hold_out, int cap);
void vn_lock(void* p);
void vn_unlock(void* p);
void vn_ctx_reset(void* p);
void vn_commit_counters(void* p, long long* out);
void vn_set_stage_depth(void* p, int depth);
void* vn_stage_detach(void* p, float** vals, float** wts, int32_t** counts,
                      int32_t* rows_out, int32_t* depth_out);
void vn_stage_free(void* plane);
long long vn_stage_total(void* p);
}

namespace {

constexpr int kShards = 4;
constexpr int kReaders = 4;
constexpr int kPacketsPerReader = 39996;  // divisible by the 6-case rotation
constexpr int kSsfThreads = 2;
constexpr int kSsfBatches = 200;
constexpr int kSpansPerBatch = 64;

std::atomic<bool> done{false};
std::atomic<long long> sent_ok{0}, sent_bad{0}, sent_evt{0};

void put_varint(std::string* out, uint64_t v) {
  while (v >= 0x80) {
    out->push_back(static_cast<char>((v & 0x7f) | 0x80));
    v >>= 7;
  }
  out->push_back(static_cast<char>(v));
}

// Minimal wire-format SSFSpan (proto/ssf.proto fields: trace_id=2 id=3
// start=5 end=6 service=8 indicator=12 name=13), framed [u32 LE len].
std::string make_ssf_batch(int seed) {
  std::string out;
  for (int i = 0; i < kSpansPerBatch; ++i) {
    std::string span;
    put_varint(&span, (2 << 3) | 0);  // trace_id
    put_varint(&span, 1000 + seed);
    put_varint(&span, (3 << 3) | 0);  // id
    put_varint(&span, 1 + i);
    put_varint(&span, (5 << 3) | 0);  // start_timestamp
    put_varint(&span, 1700000000000000000ull + i);
    put_varint(&span, (6 << 3) | 0);  // end_timestamp
    put_varint(&span, 1700000000000000000ull + i + 5000000);
    const char* svc = (i % 2) ? "svc-a" : "svc-b";
    put_varint(&span, (8 << 3) | 2);  // service
    put_varint(&span, std::strlen(svc));
    span += svc;
    put_varint(&span, (12 << 3) | 0);  // indicator
    put_varint(&span, 1);
    put_varint(&span, (13 << 3) | 2);  // name
    put_varint(&span, 2);
    span += "op";
    uint32_t len = static_cast<uint32_t>(span.size());
    char hdr[4];
    std::memcpy(hdr, &len, 4);
    out.append(hdr, 4);
    out += span;
  }
  return out;
}

int make_line(char* line, size_t cap, int i, int tid);

void reader_thread(std::vector<void*>* ctxs, int tid) {
  char line[128];
  for (int i = 0; i < kPacketsPerReader; ++i) {
    int kind = i % 6;
    int n = make_line(line, sizeof line, i, tid);
    int rc = vn_ingest_routed(ctxs->data(), kShards, line, n);
    if (kind == 5)
      sent_evt.fetch_add(1, std::memory_order_relaxed);
    else if (rc > 0)
      sent_ok.fetch_add(rc, std::memory_order_relaxed);
    else
      sent_bad.fetch_add(1, std::memory_order_relaxed);
  }
}

// Line i of reader tid: the six cases in rotation.
int make_line(char* line, size_t cap, int i, int tid) {
  int n;
  switch (i % 6) {
    case 0:
      n = std::snprintf(line, cap, "soak.timer%d:%d|ms|#t:%d", i % 64,
                        i % 1000, tid);
      break;
    case 1:
      n = std::snprintf(line, cap, "soak.count:%d|c|@0.5", i % 7);
      break;
    case 2:
      n = std::snprintf(line, cap, "soak.gauge%d:%d|g", tid, i);
      break;
    case 3:
      n = std::snprintf(line, cap, "soak.set:user%d|s", i % 997);
      break;
    case 4:  // malformed: exercises the error path under contention
      n = std::snprintf(line, cap, "soak.bad:%d|q", i);
      break;
    default:  // event: races the other_lines append in vn_ingest_routed
              // against the drain thread's vn_drain_other boundary cut
      n = std::snprintf(line, cap, "_e{9,2}:soaktitle|hi|#t:%d", tid);
      break;
  }
  return n;
}

// Round two: the same lines, 42 to a buffer (a datagram's worth, and
// whole turns of the 6-case rotation).
constexpr int kLinesPerChunk = 42;

void chunk_reader_thread(std::vector<void*>* ctxs, int tid) {
  char line[128];
  std::string buf;
  for (int i = 0; i < kPacketsPerReader; i += kLinesPerChunk) {
    buf.clear();
    for (int k = i; k < i + kLinesPerChunk && k < kPacketsPerReader; ++k) {
      buf.append(line, make_line(line, sizeof line, k, tid));
      buf.push_back('\n');
    }
    int rc = vn_ingest_routed(ctxs->data(), kShards, buf.data(),
                              static_cast<int>(buf.size()));
    sent_ok.fetch_add(rc, std::memory_order_relaxed);
  }
}

// Round two's flush: close every context's interval under its lock, as
// Server._flush_begin does, and keep the closed intervals' tallies.
void flush_thread(std::vector<void*>* ctxs, long long* processed,
                  long long* errors) {
  constexpr int kCap = 8192;
  std::vector<int32_t> rows(kCap), idx(kCap);
  std::vector<float> vals(kCap), wts(kCap);
  std::vector<double> dvals(kCap);
  std::vector<int8_t> rank(kCap);
  std::vector<char> namebuf(kCap * 64);
  bool last = false;
  while (!last) {
    last = done.load(std::memory_order_acquire);  // one pass after the end
    for (void* c : *ctxs) {
      vn_lock(c);
      *processed += vn_processed(c);
      *errors += vn_errors(c);
      float *sv, *sw;
      int32_t* scnt;
      int32_t srows, sdepth;
      void* plane = vn_stage_detach(c, &sv, &sw, &scnt, &srows, &sdepth);
      while (vn_drain_histo(c, rows.data(), vals.data(), wts.data(), kCap)) {}
      while (vn_drain_set(c, rows.data(), idx.data(), rank.data(), kCap)) {}
      while (vn_drain_counter(c, rows.data(), dvals.data(), kCap)) {}
      while (vn_drain_gauge(c, rows.data(), dvals.data(), kCap)) {}
      const int32_t *np, *nr, *ns, *fa, *fk, *fs;
      const char* strs;
      int n_first = 0;
      long long strs_len = 0;
      unsigned gen = 0;
      vn_drain_new_series(c, &np, &nr, &ns, &fa, &fk, &fs, &n_first, &strs,
                          &strs_len, &gen);
      vn_drain_other(c, namebuf.data(), static_cast<int>(namebuf.size()));
      vn_ctx_reset(c);
      vn_unlock(c);
      if (plane != nullptr) {
        // the uploader reads the handed-off plane outside the lock
        volatile float probe = sv[0] + sw[0] + static_cast<float>(scnt[0]);
        (void)probe;
        vn_stage_free(plane);
      }
    }
  }
}

void ssf_thread(void* ctx, int tid) {
  std::string batch = make_ssf_batch(tid);
  for (int i = 0; i < kSsfBatches; ++i) {
    int errs = 0, nfall = 0;
    vn_ingest_ssf_many(ctx, batch.data(),
                       static_cast<long long>(batch.size()), "ind", 3, "obj",
                       3, 0.0, &errs, nullptr, nullptr, 0, &nfall);
  }
}

// The flush loop: drain every pool of every context while readers are
// still committing — the exact overlap the two-phase flush runs.
void drain_thread(std::vector<void*>* all_ctxs) {
  constexpr int kCap = 8192;
  std::vector<int32_t> rows(kCap), idx(kCap);
  std::vector<float> vals(kCap), wts(kCap);
  std::vector<double> dvals(kCap);
  std::vector<int8_t> rank(kCap);
  std::vector<char> namebuf(kCap * 64);
  long long detaches = 0;
  while (!done.load(std::memory_order_acquire)) {
    for (void* c : *all_ctxs) {
      // periodic staged-plane detach races the readers' staging stores
      // (the per-flush handoff under the ctx mutex)
      float *sv, *sw;
      int32_t* scnt;
      int32_t srows, sdepth;
      void* plane = vn_stage_detach(c, &sv, &sw, &scnt, &srows, &sdepth);
      if (plane != nullptr) {
        // read the handed-off memory like the uploader does
        volatile float probe = sv[0] + sw[0] + (float)scnt[0];
        (void)probe;
        ++detaches;
        vn_stage_free(plane);
      }
      vn_drain_histo(c, rows.data(), vals.data(), wts.data(), kCap);
      vn_drain_set(c, rows.data(), idx.data(), rank.data(), kCap);
      vn_drain_counter(c, rows.data(), dvals.data(), kCap);
      vn_drain_gauge(c, rows.data(), dvals.data(), kCap);
      {
        // read the handed-out queue like the Python drain's copy does
        const int32_t *np, *nr, *ns, *fa, *fk, *fs;
        const char* strs;
        int n_first = 0;
        long long strs_len = 0;
        unsigned gen = 0;
        int n = vn_drain_new_series(c, &np, &nr, &ns, &fa, &fk, &fs,
                                    &n_first, &strs, &strs_len, &gen);
        volatile long long probe = gen;
        for (int i = 0; i < n; ++i) probe += np[i] + nr[i] + ns[i];
        for (int i = 0; i < n_first; ++i) probe += fa[i] + fk[i] + fs[i];
        for (long long i = 0; i < strs_len; ++i) probe += strs[i];
        (void)probe;
      }
      vn_drain_ssf_services(c, namebuf.data(),
                            static_cast<int>(namebuf.size()));
      vn_drain_other(c, namebuf.data(), static_cast<int>(namebuf.size()));
    }
  }
}

// Self-telemetry: reads the counters the scopedstatsd reporter polls.
void stats_thread(std::vector<void*>* all_ctxs) {
  long long out[6];
  while (!done.load(std::memory_order_acquire)) {
    for (void* c : *all_ctxs) {
      (void)vn_processed(c);
      (void)vn_errors(c);
      (void)vn_pending_histo(c);
      (void)vn_pending_set(c);
      (void)vn_pending_counter(c);
      (void)vn_pending_gauge(c);
      (void)vn_lock_stats(c, out, nullptr, nullptr, 0);
      vn_commit_counters(c, out);
    }
  }
}

// The import path: registers series directly, racing the parser's own
// directory upserts on the same contexts.
void upsert_thread(std::vector<void*>* ctxs) {
  char name[64];
  for (int i = 0; i < 20000; ++i) {
    int n = std::snprintf(name, sizeof name, "import.series%d", i % 512);
    vn_upsert((*ctxs)[i % kShards], name, n, i % 4, "env:prod", 8, 0);
  }
}

}  // namespace

int main() {
  std::vector<void*> shard_ctxs;
  for (int i = 0; i < kShards; ++i) {
    void* c = vn_ctx_new(12);
    // small depth so both the staging store AND the full-row spill path
    // run under the sanitizer
    vn_set_stage_depth(c, 8);
    shard_ctxs.push_back(c);
  }
  void* ssf_ctx = vn_ctx_new(12);
  std::vector<void*> all_ctxs = shard_ctxs;
  all_ctxs.push_back(ssf_ctx);

  std::vector<std::thread> threads;
  threads.emplace_back(drain_thread, &all_ctxs);
  threads.emplace_back(stats_thread, &all_ctxs);
  threads.emplace_back(upsert_thread, &shard_ctxs);
  for (int t = 0; t < kReaders; ++t)
    threads.emplace_back(reader_thread, &shard_ctxs, t);
  for (int t = 0; t < kSsfThreads; ++t)
    threads.emplace_back(ssf_thread, ssf_ctx, t);

  for (size_t i = 2; i < threads.size(); ++i) threads[i].join();
  done.store(true, std::memory_order_release);
  threads[0].join();
  threads[1].join();

  // conservation: every accepted datagram was counted exactly once
  long long processed = 0, errors = 0;
  for (void* c : shard_ctxs) {
    processed += vn_processed(c);
    errors += vn_errors(c);
  }
  long long want_ok = sent_ok.load(), want_bad = sent_bad.load();
  long long want_bad_expect = (long long)kReaders * (kPacketsPerReader / 6);
  std::printf("tsan_soak: processed=%lld errors=%lld sent_ok=%lld "
              "sent_bad=%lld events=%lld\n",
              processed, errors, want_ok, want_bad, sent_evt.load());
  bool ok = processed == want_ok && errors == want_bad &&
            want_bad == want_bad_expect;

  // round two: the chunk commit under a flush that turns epochs over
  done.store(false, std::memory_order_release);
  sent_ok.store(0);
  long long batches_before = 0, lines_before = 0;
  long long counters[6];
  for (void* c : shard_ctxs) {
    vn_commit_counters(c, counters);
    batches_before += counters[3];
    lines_before += counters[4];
  }
  long long chunk_processed = -processed, chunk_errors = -errors;
  threads.clear();
  threads.emplace_back(flush_thread, &shard_ctxs, &chunk_processed,
                       &chunk_errors);
  threads.emplace_back(stats_thread, &all_ctxs);
  threads.emplace_back(upsert_thread, &shard_ctxs);
  for (int t = 0; t < kReaders; ++t)
    threads.emplace_back(chunk_reader_thread, &shard_ctxs, t);
  for (size_t i = 2; i < threads.size(); ++i) threads[i].join();
  done.store(true, std::memory_order_release);
  threads[0].join();
  threads[1].join();
  // the lock's record is one entry a lock hold of the commit, both
  // rounds, whoever else took the lock meanwhile
  long long batches = -batches_before, batch_lines = -lines_before;
  long long timed_holds = 0;
  for (void* c : shard_ctxs) {
    vn_commit_counters(c, counters);
    batches += counters[3];
    batch_lines += counters[4];
    timed_holds -= counters[3];
    long long lock_totals[5];
    (void)vn_lock_stats(c, lock_totals, nullptr, nullptr, 0);
    timed_holds += lock_totals[0];
  }
  std::printf("tsan_soak: chunk round processed=%lld errors=%lld "
              "sent_ok=%lld batches=%lld\n",
              chunk_processed, chunk_errors, sent_ok.load(), batches);
  ok = ok && chunk_processed == sent_ok.load() &&
       chunk_errors == want_bad_expect && batch_lines == chunk_processed &&
       batches < chunk_processed / 4 && timed_holds == 0;

  for (void* c : all_ctxs) vn_ctx_free(c);
  if (!ok) {
    std::fprintf(stderr, "tsan_soak: conservation FAILED\n");
    return 1;
  }
  std::puts("tsan_soak: OK");
  return 0;
}

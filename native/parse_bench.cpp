// Per-core DogStatsD parse+stage throughput microbench.
//
// VERDICT r4 item 4a: the 50M samples/s/chip north star is host-parse
// bound, and the round-4 artifacts only ever *extrapolated* per-core
// parse throughput from end-to-end runs. This bench measures it
// directly, phase by phase, with cycles/line (rdtsc):
//
//   parse    parse_line only (tokenize + value + tag normalize + digest)
//   commit   handle_line (parse + directory upsert + stage/SoA commit)
//   datagram vn_ingest over 25-line datagrams (the wire-facing API the
//            C++ readers call — includes line splitting)
//   cell     the benchmark cell's interval (bench/configs/local-timers.json
//            under bench/traffic/steady.json, bench/stream.py's formats):
//            --timers N timer series (262,144; the first 4,096 hot x 256
//            samples, the rest x 2 and tagged), 65,536 counters and
//            65,536 gauges x 2, 1,024 sets, one seeded shuffle, 64 KiB
//            chunks through the stream reader's entry point
//            (stream_feed), the SoA and series queues drained a hundred
//            times an interval and the plane detached, freed and the
//            context reset at its end, as the flush does. Thread CPU
//            time of the feeds alone (the reader's share) and of the
//            drains and the reset (the flush path's). The first interval
//            creates every series; the others are the steady state.
//
// The corpus mirrors the production mix the overload soak blasts
// (timers with tags + sample rate, counters, gauges, HLL sets) plus a
// no-tag fast-path variant. Single-threaded by design: multiply by the
// deployment's reader-core budget (tools/bench_parse_percore.py runs
// the multi-process SO_REUSEPORT scaling harness where cores exist).
//
// Output: one JSON line on stdout.
//
// Build/run: make -C native parse_bench && ./native/parse_bench
//   [lines per phase] [--timers N] [--intervals K] [--seed S]
//   [--cell 0|1] [--only-cell]

#include "dogstatsd.cpp"

#include <time.h>
#include <x86intrin.h>

#include <chrono>
#include <cstdio>
#include <random>

namespace {

double now_s() {
  return std::chrono::duration<double>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

std::vector<std::string> build_corpus(int n) {
  std::vector<std::string> lines;
  lines.reserve(n);
  char buf[256];
  for (int i = 0; i < n; ++i) {
    int series = i % 800;
    switch (i % 10) {
      case 0: case 1: case 2: case 3:  // 40% tagged timers
        std::snprintf(buf, sizeof buf,
                      "svc.req.latency.%d:%d.%02d|ms|@0.5|#env:prod,"
                      "region:us-east-1,service:api%d",
                      series, i % 300, i % 100, series % 16);
        break;
      case 4: case 5:  // 20% counters
        std::snprintf(buf, sizeof buf,
                      "svc.req.count.%d:%d|c|#env:prod,service:api%d",
                      series, 1 + i % 5, series % 16);
        break;
      case 6:  // 10% gauges
        std::snprintf(buf, sizeof buf, "svc.queue.depth.%d:%d|g|#env:prod",
                      series, i % 10000);
        break;
      case 7:  // 10% sets
        std::snprintf(buf, sizeof buf, "svc.users.%d:user%d|s|#env:prod",
                      series, i % 65536);
        break;
      default:  // 20% untagged timers (fast path)
        std::snprintf(buf, sizeof buf, "svc.db.time.%d:%d.%d|ms", series,
                      i % 200, i % 10);
        break;
    }
    lines.emplace_back(buf);
  }
  return lines;
}

// parse + commit of one line into a context the caller owns
bool handle_line(Ctx* ctx, std::string_view line) {
  thread_local Scratch sc;
  Parsed p;
  if (!parse_line(&sc, line, &p)) return false;
  return commit_metric(ctx, p, sc.joined, series_key_hash(p, sc.joined));
}

double thread_cpu_s() {
  timespec ts;
  clock_gettime(CLOCK_THREAD_CPUTIME_ID, &ts);
  return ts.tv_sec + ts.tv_nsec * 1e-9;
}

// bench/generators/fleet.py set_cardinalities
std::vector<int> set_cardinalities(int n_sets, int max_members) {
  const int n_mid = n_sets >= 64 ? 16 : 2, n_large = n_sets >= 64 ? 8 : 1;
  std::vector<int> out;
  auto geom = [&out](double lo, double hi, int n) {
    for (int i = 0; i < n; ++i)
      out.push_back(static_cast<int>(std::lround(
          n == 1 ? lo : lo * std::pow(hi / lo, i / double(n - 1)))));
  };
  geom(1, 100, n_sets - n_mid - n_large);
  geom(128, std::min(4096, max_members), n_mid);
  geom(std::max(1, max_members / 10), max_members, n_large);
  return out;
}

// One interval of local-timers.steady as 64 KiB chunks, each ending on a
// line boundary (bench/stream.py chunk_lines).
struct CellStream {
  std::string bytes;
  std::vector<size_t> chunk_at;  // chunk k = bytes[chunk_at[k], chunk_at[k+1])
  long long lines = 0;
};

CellStream build_cell_stream(int timers, uint64_t seed) {
  constexpr int kHot = 4096, kHotSamples = 256, kColdSamples = 2;
  constexpr int kCounters = 65536, kGauges = 65536, kSets = 1024;
  constexpr int kSetMax = 1500, kTagFrom = 4096;
  constexpr size_t kChunk = 64 << 10;
  std::mt19937_64 rng(seed);
  std::string blob;
  std::vector<uint32_t> at;  // line i = blob[at[i], at[i+1])
  char buf[96];
  auto push = [&](int n) {
    at.push_back(static_cast<uint32_t>(blob.size()));
    blob.append(buf, static_cast<size_t>(n));
  };
  std::normal_distribution<double> lognorm(3.0, 1.0);
  for (int i = 0; i < std::min(kHot, timers); ++i)
    for (int k = 0; k < kHotSamples; ++k) {
      double v = std::clamp(std::round(std::exp(lognorm(rng)) * 4.0), 1.0,
                            400000.0) / 4.0;
      push(i >= kTagFrom
               ? std::snprintf(buf, sizeof buf, "cs.t.%d:%.2f|ms|#shard:%d", i,
                               v, i & 63)
               : std::snprintf(buf, sizeof buf, "cs.t.%d:%.2f|ms", i, v));
    }
  for (int i = kHot; i < timers; ++i)
    for (int k = 0; k < kColdSamples; ++k)
      push(std::snprintf(buf, sizeof buf, "cs.t.%d:%.2f|ms|#shard:%d", i,
                         (4 + rng() % 399996) / 4.0, i & 63));
  for (int i = 0; i < kCounters; ++i)
    for (int k = 0; k < 2; ++k)
      push(std::snprintf(buf, sizeof buf, "cs.c.%d:%d|c", i,
                         static_cast<int>(1 + rng() % 999)));
  for (int i = 0; i < kGauges; ++i)
    for (int k = 0; k < 2; ++k)
      push(std::snprintf(buf, sizeof buf, "cs.g.%d:%.2f|g", i,
                         (rng() % (1 << 20)) / 4.0));
  std::vector<int> cards = set_cardinalities(kSets, kSetMax);
  for (int i = 0; i < kSets; ++i)
    for (int m = 0; m < cards[i]; ++m)
      push(std::snprintf(buf, sizeof buf, "cs.s.%d:u%d-%d|s", i, i, m));
  at.push_back(static_cast<uint32_t>(blob.size()));

  std::vector<uint32_t> order(at.size() - 1);
  for (uint32_t i = 0; i < order.size(); ++i) order[i] = i;
  std::shuffle(order.begin(), order.end(), rng);

  CellStream cs;
  cs.lines = static_cast<long long>(order.size());
  cs.bytes.reserve(blob.size() + order.size());
  cs.chunk_at.push_back(0);
  for (uint32_t i : order) {
    size_t len = at[i + 1] - at[i];
    if (cs.bytes.size() > cs.chunk_at.back() &&
        cs.bytes.size() - cs.chunk_at.back() + len + 1 > kChunk)
      cs.chunk_at.push_back(cs.bytes.size());
    cs.bytes.append(blob, at[i], len);
    cs.bytes.push_back('\n');
  }
  cs.chunk_at.push_back(cs.bytes.size());
  return cs;
}

double median(std::vector<double> v) {
  std::sort(v.begin(), v.end());
  return v[v.size() / 2];
}

// -- phase 4: the benchmark cell's interval through the stream reader ------
void cell_phase(int timers, int intervals, uint64_t seed, std::string* json) {
  CellStream cs = build_cell_stream(timers, seed);
  const size_t n_chunks = cs.chunk_at.size() - 1;
  Ctx* ctx = static_cast<Ctx*>(vn_ctx_new(14));
  vn_set_stage_depth(ctx, 64);
  StreamReader reader;
  reader.max_len = 4096;
  reader.ctxs.push_back(ctx);
  StreamCarry carry;

  constexpr int kCap = 1 << 22;
  std::vector<int32_t> rows(kCap), idx(kCap);
  std::vector<float> vals(kCap), wts(kCap);
  std::vector<double> dvals(kCap);
  std::vector<int8_t> rank(kCap);
  auto drain = [&] {
    vn_drain_histo(ctx, rows.data(), vals.data(), wts.data(), kCap);
    vn_drain_set(ctx, rows.data(), idx.data(), rank.data(), kCap);
    vn_drain_counter(ctx, rows.data(), dvals.data(), kCap);
    vn_drain_gauge(ctx, rows.data(), dvals.data(), kCap);
    const int32_t *qp, *qr, *qs, *fa, *fk, *fs;
    const char* strs;
    int n_first = 0;
    long long strs_len = 0;
    unsigned gen = 0;
    vn_drain_new_series(ctx, &qp, &qr, &qs, &fa, &fk, &fs, &n_first, &strs,
                        &strs_len, &gen);
  };
  const size_t drain_every = std::max<size_t>(1, n_chunks / 100);
  std::vector<double> feed_s, flush_s;
  long long processed = 0, plane_rows = 0;
  for (int it = 0; it < intervals; ++it) {
    double feed = 0, flush = 0;
    for (size_t k = 0; k < n_chunks; ++k) {
      double t0 = thread_cpu_s();
      stream_feed(&reader, &carry, cs.bytes.data() + cs.chunk_at[k],
                  cs.chunk_at[k + 1] - cs.chunk_at[k]);
      double t1 = thread_cpu_s();
      feed += t1 - t0;
      if ((k + 1) % drain_every == 0) {
        drain();
        flush += thread_cpu_s() - t1;
      }
    }
    double t0 = thread_cpu_s();
    processed += vn_processed(ctx);
    float *sv, *sw;
    int32_t* scnt;
    int32_t srows = 0, sdepth = 0;
    void* plane = vn_stage_detach(ctx, &sv, &sw, &scnt, &srows, &sdepth);
    plane_rows = srows;
    drain();
    vn_ctx_reset(ctx);
    if (plane != nullptr) vn_stage_free(plane);
    flush += thread_cpu_s() - t0;
    feed_s.push_back(feed);
    flush_s.push_back(flush);
  }
  vn_ctx_free(ctx);

  std::vector<double> warm_feed(feed_s.begin() + (intervals > 1), feed_s.end());
  std::vector<double> warm_flush(flush_s.begin() + (intervals > 1),
                                 flush_s.end());
  char out[640];
  std::snprintf(
      out, sizeof out,
      ", \"cell_timers\": %d, \"cell_lines\": %lld, \"cell_chunks\": %zu, "
      "\"cell_intervals\": %d, \"cell_processed\": %lld, "
      "\"cell_plane_rows\": %lld, "
      "\"cell_first_interval_s\": %.4f, \"cell_reader_s_min\": %.4f, "
      "\"cell_reader_s_median\": %.4f, \"cell_reader_s_max\": %.4f, "
      "\"cell_reader_ns_per_line\": %.1f, "
      "\"cell_flush_side_s_median\": %.4f",
      timers, cs.lines, n_chunks, intervals, processed, plane_rows, feed_s[0],
      *std::min_element(warm_feed.begin(), warm_feed.end()), median(warm_feed),
      *std::max_element(warm_feed.begin(), warm_feed.end()),
      median(warm_feed) / cs.lines * 1e9, median(warm_flush));
  *json += out;
}

}  // namespace

int main(int argc, char** argv) {
  int corpus_n = 4000;
  long long target_lines = 8'000'000;
  int cell_timers = 262144, cell_intervals = 7;
  uint64_t cell_seed = 30;
  bool cell = true, only_cell = false;
  for (int i = 1; i < argc; ++i) {
    std::string_view a = argv[i];
    auto value = [&]() -> long long {
      return i + 1 < argc ? std::atoll(argv[++i]) : 0;
    };
    if (a == "--timers") cell_timers = static_cast<int>(value());
    else if (a == "--intervals") cell_intervals = static_cast<int>(value());
    else if (a == "--seed") cell_seed = static_cast<uint64_t>(value());
    else if (a == "--cell") cell = value() != 0;
    else if (a == "--only-cell") only_cell = true;
    else target_lines = std::atoll(argv[i]);
  }
  std::string cell_json;
  if (only_cell) {
    cell_phase(cell_timers, cell_intervals, cell_seed, &cell_json);
    std::printf("{%s}\n", cell_json.c_str() + 2);
    return 0;
  }

  auto lines = build_corpus(corpus_n);
  size_t total_bytes = 0;
  for (auto& l : lines) total_bytes += l.size();

  // -- phase 1: parse only ------------------------------------------------
  Scratch sc;
  Parsed p;
  long long parsed = 0;
  double sink = 0;  // defeat dead-code elimination
  double t0 = now_s();
  uint64_t c0 = __rdtsc();
  for (long long it = 0; parsed < target_lines; ++it) {
    const std::string& line = lines[it % corpus_n];
    if (parse_line(&sc, line, &p)) sink += p.value + p.digest;
    ++parsed;
  }
  uint64_t parse_cycles = __rdtsc() - c0;
  double parse_s = now_s() - t0;

  // -- phase 2: parse + commit (directory upsert + stage/SoA) -------------
  void* ctx = vn_ctx_new(14);
  vn_set_stage_depth(ctx, 64);
  long long committed = 0;
  t0 = now_s();
  c0 = __rdtsc();
  for (long long it = 0; committed < target_lines; ++it) {
    const std::string& line = lines[it % corpus_n];
    handle_line(static_cast<Ctx*>(ctx), line);
    ++committed;
    if ((it + 1) % 2'000'000 == 0) {
      // periodic drain keeps the SoA/stage memory bounded like the
      // runtime's pump does, at a realistic cadence
      vn_ctx_reset(ctx);
    }
  }
  uint64_t commit_cycles = __rdtsc() - c0;
  double commit_s = now_s() - t0;
  vn_ctx_free(ctx);

  // -- phase 3: full datagram API (vn_ingest, 25 lines/datagram) ----------
  std::vector<std::string> datagrams;
  {
    std::string d;
    for (int i = 0; i < corpus_n; ++i) {
      d += lines[i];
      if ((i + 1) % 25 == 0) {
        datagrams.push_back(d);
        d.clear();
      } else {
        d.push_back('\n');
      }
    }
    if (!d.empty()) datagrams.push_back(d);
  }
  ctx = vn_ctx_new(14);
  vn_set_stage_depth(ctx, 64);
  long long dg_lines = 0;
  t0 = now_s();
  c0 = __rdtsc();
  for (long long it = 0; dg_lines < target_lines; ++it) {
    const std::string& d = datagrams[it % datagrams.size()];
    vn_ingest(ctx, d.data(), static_cast<int>(d.size()));
    dg_lines += 25;
    if ((it + 1) % 80'000 == 0) vn_ctx_reset(ctx);
  }
  uint64_t dg_cycles = __rdtsc() - c0;
  double dg_s = now_s() - t0;
  vn_ctx_free(ctx);

  if (cell) cell_phase(cell_timers, cell_intervals, cell_seed, &cell_json);

  double avg_line = static_cast<double>(total_bytes) / corpus_n;
  std::printf(
      "{\"parse_lines_per_s\": %.0f, \"parse_cycles_per_line\": %.0f, "
      "\"commit_lines_per_s\": %.0f, \"commit_cycles_per_line\": %.0f, "
      "\"datagram_lines_per_s\": %.0f, \"datagram_cycles_per_line\": %.0f, "
      "\"avg_line_bytes\": %.1f, \"lines_timed\": %lld, \"sink\": %.3g%s}\n",
      parsed / parse_s, static_cast<double>(parse_cycles) / parsed,
      committed / commit_s, static_cast<double>(commit_cycles) / committed,
      dg_lines / dg_s, static_cast<double>(dg_cycles) / dg_lines, avg_line,
      target_lines, sink, cell_json.c_str());
  return 0;
}

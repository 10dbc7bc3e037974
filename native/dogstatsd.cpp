// Native ingest hot path: DogStatsD parsing, tag normalization, series
// directory, and SoA batch building.
//
// The reference's per-packet CPU hotspot is its zero-allocation Go parser +
// map upsert (samplers/parser.go:298-423, worker.go:108-177, SURVEY.md
// §3.2). Here the whole host-side ingest path is one C++ translation unit:
// a packet buffer goes in; dense (row, value, weight) SoA arrays come out,
// ready to be shipped to the device. Row assignment (the series directory)
// lives in an open-addressing hash table keyed by the same 32-bit FNV-1a
// identity digest the Python parser computes, so both front ends agree.
//
// Events (_e{) and service checks (_sc) are rare control-plane traffic and
// are handed back to Python verbatim.
//
// Exposed as a C ABI for ctypes (no pybind11 in this environment).

#include <emmintrin.h>  // SSE2 delimiter masks (MaskFinder)
#include <errno.h>
#include <fcntl.h>
#include <sys/socket.h>
#include <sys/time.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <cctype>
#include <charconv>
#include <chrono>
#include <cmath>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <memory>
#include <mutex>
#include <new>
#include <string>
#include <string_view>
#include <thread>
#include <type_traits>
#include <unordered_map>
#include <vector>

namespace {

inline int64_t now_ns() {
  return std::chrono::duration_cast<std::chrono::nanoseconds>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

constexpr uint32_t kFnv32Offset = 2166136261u;
constexpr uint32_t kFnv32Prime = 16777619u;
constexpr uint64_t kFnv64Offset = 0xcbf29ce484222325ull;
constexpr uint64_t kFnv64Prime = 0x100000001b3ull;

inline uint32_t fnv1a32(std::string_view s, uint32_t h = kFnv32Offset) {
  for (unsigned char c : s) {
    h ^= c;
    h *= kFnv32Prime;
  }
  return h;
}

inline uint64_t fnv1a64_continue(std::string_view s, uint64_t h) {
  for (unsigned char c : s) {
    h ^= c;
    h *= kFnv64Prime;
  }
  return h;
}

inline uint64_t fnv1a64(std::string_view s) {
  return fnv1a64_continue(s, kFnv64Offset);
}

inline uint64_t fmix64(uint64_t h) {
  h ^= h >> 33;
  h *= 0xff51afd7ed558ccdull;
  h ^= h >> 33;
  h *= 0xc4ceb9fe1a85ec53ull;
  h ^= h >> 33;
  return h;
}

// MetroHash64 — the Go fleet's set-element hash (vendored
// axiomhq/hyperloglog hashes with metro64 seed=1337; see
// utils/hashing.py metro_hash64 for the Python twin and the interop
// rationale). Enabled per-context via vn_ctx_set_metro.
inline uint64_t rotr64(uint64_t v, int k) { return (v >> k) | (v << (64 - k)); }

inline uint64_t load_le(const char* p, int n) {
  uint64_t v = 0;
  std::memcpy(&v, p, n);  // little-endian hosts only (x86/ARM LE)
  return v;
}

uint64_t metro_hash64(std::string_view s, uint64_t seed) {
  constexpr uint64_t k0 = 0xD6D018F5, k1 = 0xA2AA033B, k2 = 0x62992FC1,
                     k3 = 0x30BC5B29;
  const char* p = s.data();
  size_t n = s.size();
  uint64_t h = (seed + k2) * k0;
  if (n >= 32) {
    uint64_t v0 = h, v1 = h, v2 = h, v3 = h;
    while (n >= 32) {
      v0 += load_le(p, 8) * k0; v0 = rotr64(v0, 29) + v2;
      v1 += load_le(p + 8, 8) * k1; v1 = rotr64(v1, 29) + v3;
      v2 += load_le(p + 16, 8) * k2; v2 = rotr64(v2, 29) + v0;
      v3 += load_le(p + 24, 8) * k3; v3 = rotr64(v3, 29) + v1;
      p += 32;
      n -= 32;
    }
    v2 ^= rotr64((v0 + v3) * k0 + v1, 37) * k1;
    v3 ^= rotr64((v1 + v2) * k1 + v0, 37) * k0;
    v0 ^= rotr64((v0 + v2) * k0 + v3, 37) * k1;
    v1 ^= rotr64((v1 + v3) * k1 + v2, 37) * k0;
    h += v0 ^ v1;
  }
  if (n >= 16) {
    uint64_t v0 = h + load_le(p, 8) * k2; v0 = rotr64(v0, 29) * k3;
    uint64_t v1 = h + load_le(p + 8, 8) * k2; v1 = rotr64(v1, 29) * k3;
    v0 ^= rotr64(v0 * k0, 21) + v1;
    v1 ^= rotr64(v1 * k3, 21) + v0;
    h += v1;
    p += 16;
    n -= 16;
  }
  if (n >= 8) {
    h += load_le(p, 8) * k3;
    h ^= rotr64(h, 55) * k1;
    p += 8;
    n -= 8;
  }
  if (n >= 4) {
    h += load_le(p, 4) * k3;
    h ^= rotr64(h, 26) * k1;
    p += 4;
    n -= 4;
  }
  if (n >= 2) {
    h += load_le(p, 2) * k3;
    h ^= rotr64(h, 48) * k1;
    p += 2;
    n -= 2;
  }
  if (n >= 1) {
    h += static_cast<unsigned char>(*p) * k3;
    h ^= rotr64(h, 37) * k1;
  }
  h ^= rotr64(h, 28);
  h *= k0;
  h ^= rotr64(h, 29);
  return h;
}

// Strict float parse matching the Python/Go rules: full consumption, no
// whitespace or underscores, finite. Fast path decodes the overwhelmingly
// common statsd shapes ([-]digits[.digits], ≤15 significant digits)
// without the std::string/strtod detour (~2x parser speedup on tagged
// lines); everything else (exponents, inf/nan/hex — mostly rejects)
// falls back to the strict strtod check.
bool parse_value_slow(std::string_view s, double* out) {
  for (char c : s) {
    if (c == '_' || std::isspace(static_cast<unsigned char>(c))) return false;
    // strtod accepts C hex floats ("0x1f"); the Python parser rejects
    // them all, and Go's ParseFloat rejects the p-less form ("0x1f")
    // while accepting "0x1p3" — a form no statsd client emits, so
    // rejecting every hex literal keeps the two in-repo parsers exact
    if (c == 'x' || c == 'X') return false;
  }
  std::string buf(s);
  char* end = nullptr;
  double v = std::strtod(buf.c_str(), &end);
  if (end != buf.c_str() + buf.size()) return false;
  if (!std::isfinite(v)) return false;
  *out = v;
  return true;
}

bool parse_value(std::string_view s, double* out) {
  if (s.empty()) return false;
  const char* p = s.data();
  const char* end = p + s.size();
  bool neg = false;
  if (*p == '-') {
    neg = true;
    ++p;
  }
  uint64_t mant = 0;
  int digits = 0, frac = 0;
  bool seen_dot = false, seen_digit = false;
  for (; p < end; ++p) {
    char c = *p;
    if (c >= '0' && c <= '9') {
      seen_digit = true;
      if (++digits > 15) return parse_value_slow(s, out);
      mant = mant * 10 + static_cast<uint64_t>(c - '0');
      if (seen_dot) ++frac;
    } else if (c == '.' && !seen_dot) {
      seen_dot = true;
    } else {
      return parse_value_slow(s, out);  // exponent/inf/garbage
    }
  }
  if (!seen_digit) return parse_value_slow(s, out);
  static const double kPow10[16] = {
      1e0, 1e1, 1e2, 1e3, 1e4, 1e5, 1e6, 1e7,
      1e8, 1e9, 1e10, 1e11, 1e12, 1e13, 1e14, 1e15};
  double v = static_cast<double>(mant) / kPow10[frac];
  *out = neg ? -v : v;
  return true;
}

enum MetricKind : int32_t {
  KIND_COUNTER = 0,
  KIND_GAUGE = 1,
  KIND_HISTOGRAM = 2,
  KIND_TIMER = 3,
  KIND_SET = 4,
};

enum ScopeClass : int32_t {
  SCOPE_MIXED = 0,
  SCOPE_LOCAL = 1,
  SCOPE_GLOBAL = 2,
};

const char* kind_type_string(MetricKind k) {
  switch (k) {
    case KIND_COUNTER: return "counter";
    case KIND_GAUGE: return "gauge";
    case KIND_HISTOGRAM: return "histogram";
    case KIND_TIMER: return "timer";
    case KIND_SET: return "set";
  }
  return "";
}

// scope label per WorkerMetrics.Upsert routing (worker.go:108-177)
ScopeClass classify(MetricKind kind, int scope /*0 mixed,1 local,2 global*/) {
  switch (kind) {
    case KIND_COUNTER:
    case KIND_GAUGE:
      return scope == 2 ? SCOPE_GLOBAL : SCOPE_MIXED;
    case KIND_HISTOGRAM:
    case KIND_TIMER:
      if (scope == 1) return SCOPE_LOCAL;
      if (scope == 2) return SCOPE_GLOBAL;
      return SCOPE_MIXED;
    case KIND_SET:
      return scope == 1 ? SCOPE_LOCAL : SCOPE_MIXED;
  }
  return SCOPE_MIXED;
}

// Series created this interval and not yet handed to Python
// (vn_drain_new_series), as parallel arrays: every record is three
// integers, and only a series whose strings Python does not hold yet
// (a first-seen `sid`, see Ctx::interned) also carries its kind, scope
// class and a "name \x1f joined_tags \x1e" record in `strs`.
struct NewSeriesQueue {
  std::vector<int32_t> pools;  // 0 histo, 1 set, 2 counter, 3 gauge
  std::vector<int32_t> rows;
  std::vector<int32_t> sids;
  std::vector<int32_t> first_at;  // positions of the first-seen records
  std::vector<int32_t> first_kinds;
  std::vector<int32_t> first_scopes;
  std::string strs;

  size_t size() const { return rows.size(); }
  void clear() {
    pools.clear();
    rows.clear();
    sids.clear();
    first_at.clear();
    first_kinds.clear();
    first_scopes.clear();
    strs.clear();
  }

  // Reorder the records so that each pool's are together (pool 0 first),
  // in the order they were queued: rows are handed out per pool, so a
  // pool's records then carry consecutive rows. first_at follows.
  void group_by_pool() {
    const size_t n = size();
    size_t start[5] = {0, 0, 0, 0, 0};
    for (int32_t pool : pools) ++start[pool + 1];
    for (int p = 0; p < 4; ++p)
      if (start[p + 1] == n) return;  // one pool: grouped as it stands
    for (int p = 0; p < 4; ++p) start[p + 1] += start[p];
    std::vector<int32_t> at(n), by_pool(n), by_row(n), by_sid(n);
    for (size_t i = 0; i < n; ++i) {
      const size_t to = start[pools[i]]++;
      at[i] = static_cast<int32_t>(to);
      by_pool[to] = pools[i];
      by_row[to] = rows[i];
      by_sid[to] = sids[i];
    }
    pools.swap(by_pool);
    rows.swap(by_row);
    sids.swap(by_sid);
    for (int32_t& first : first_at) first = at[first];
  }
};

// Open-addressing directory, one per context for the context's lifetime:
// identity = (kind-type string, scope class, name, joined tags), hashed
// with dir_key_hash below -> a dense, append-only series id (`sid`).
// The slot also carries the series' row of the interval it was last
// written in and that interval's epoch (Ctx::epoch): a row is good only
// while the two epochs agree, so a flush forgets every row by bumping
// one integer and the table, its arena and its capacity stay.
struct Directory {
  struct alignas(32) Slot {  // two to a cache line, never astride one
    uint64_t key_hash = 0;
    uint32_t key_off = 0;
    uint32_t key_len = 0;
    int32_t sid = -1;  // -1: empty
    int32_t row = 0;
    uint32_t epoch = 0;  // Ctx::epoch starts at 1: a new slot is stale
  };
  static constexpr size_t kMinSlots = size_t{1} << 12;
  std::vector<Slot> slots;
  std::string arena;
  size_t used = 0;

  Directory() : slots(kMinSlots) {}

  // Forget every series; sized for `expect` of them (what the caller
  // saw live), not for what the dropped table had grown to.
  void reset(size_t expect) {
    size_t n = kMinSlots;
    while (expect * 4 >= n * 3) n *= 2;
    slots.assign(n, Slot{});
    arena.clear();
    used = 0;
  }

  void grow() {
    std::vector<Slot> old;
    old.swap(slots);
    slots.assign(old.size() * 2, Slot{});
    for (const Slot& s : old) {
      if (s.sid >= 0) {
        size_t mask = slots.size() - 1;
        size_t i = s.key_hash & mask;
        while (slots[i].sid >= 0) i = (i + 1) & mask;
        slots[i] = s;
      }
    }
  }

  // The series' slot, inserted (sid = used, *inserted set) when the
  // table has not seen it. Identity is passed as PARTS — compared
  // piecewise against the arena and appended with the canonical
  // `name \x1f type \x1f joined \x1f cls` layout only on a miss, so the
  // per-line hot path never builds a key string (round-5 parse bench:
  // the key build + byte-serial fnv1a64 full-key pass were ~25% of
  // commit cost). The pointer is good until the next insert.
  Slot* find_or_insert(uint64_t key_hash, std::string_view name,
                       std::string_view type_str, std::string_view joined,
                       char cls_char, bool* inserted) {
    if (used * 4 >= slots.size() * 3) grow();
    size_t mask = slots.size() - 1;
    const size_t nn = name.size(), nt = type_str.size(), nj = joined.size();
    const size_t want = nn + nt + nj + 4;
    size_t i = key_hash & mask;
    while (slots[i].sid >= 0) {
      if (slots[i].key_hash == key_hash && slots[i].key_len == want) {
        const char* k = arena.data() + slots[i].key_off;
        if (std::memcmp(k, name.data(), nn) == 0 && k[nn] == '\x1f' &&
            std::memcmp(k + nn + 1, type_str.data(), nt) == 0 &&
            k[nn + 1 + nt] == '\x1f' &&
            std::memcmp(k + nn + 2 + nt, joined.data(), nj) == 0 &&
            k[want - 2] == '\x1f' && k[want - 1] == cls_char) {
          *inserted = false;
          return &slots[i];
        }
      }
      i = (i + 1) & mask;
    }
    slots[i].key_hash = key_hash;
    slots[i].sid = static_cast<int32_t>(used);
    slots[i].key_off = static_cast<uint32_t>(arena.size());
    slots[i].key_len = static_cast<uint32_t>(want);
    arena.append(name);
    arena.push_back('\x1f');
    arena.append(type_str);
    arena.push_back('\x1f');
    arena.append(joined);
    arena.push_back('\x1f');
    arena.push_back(cls_char);
    ++used;
    *inserted = true;
    return &slots[i];
  }
};

// Directory key hash from the identity PARTS — no key-string build.
// metro64 (8 bytes/step) replaces the old byte-serial fnv1a64 pass over
// the built key on the per-line hot path. Purely internal (the
// hash is never serialized), but every producer must agree — ingest
// commit, vn_upsert, vn_upsert_many — since the directory dedupes by
// this hash + piecewise compare.
inline uint64_t dir_key_hash(uint32_t digest, std::string_view name,
                             std::string_view type_str,
                             std::string_view joined, int cls) {
  uint64_t h = metro_hash64(name, 0x56454E55ull);  // "VENU"
  uint64_t hj = metro_hash64(joined, 0x544147ull);  // "TAG"
  h ^= (hj << 17) | (hj >> 47);
  h ^= (static_cast<uint64_t>(digest) << 32) ^
       (static_cast<uint64_t>(type_str.size()) << 8) ^
       static_cast<uint64_t>(cls);
  return fmix64(h);
}

struct Ctx {
  int hll_precision = 14;
  bool set_hash_metro = false;

  // Guards every mutation; taken by all exported entry points so readers
  // calling vn_ingest_routed can commit into any shard while the Python
  // flush path drains another. Parsing never holds it (thread-local
  // scratch), so it only covers the short directory-upsert + SoA append.
  // Recursive so the flush path can hold it across its whole multi-call
  // drain→sync→reset sequence (vn_lock/vn_unlock) — otherwise a routed
  // commit slipping between the last drain and the reset would be
  // destroyed with the old epoch.
  std::recursive_mutex mu;

  // Rows are per interval, in first-seen order; `epoch` names the
  // interval (vn_ctx_reset increments it) and `interned` stamps each
  // series' row with it.
  uint32_t epoch = 1;
  int32_t next_histo_row = 0;
  int32_t next_set_row = 0;
  int32_t next_counter_row = 0;
  int32_t next_gauge_row = 0;

  // Raw-sample staging plane (round-4 staged ingest): histo/timer
  // samples land here at parse time and Python detaches the whole plane
  // once per flush (vn_stage_detach) — zero per-batch Python work. Rows
  // whose staging is full spill into the h_* SoA batch below, which
  // Python drains mid-interval and folds directly (hot rows keep the
  // gathered per-batch fold cheap). Heap-allocated so detach is a
  // pointer handoff: Python wraps the planes' memory as numpy, uploads,
  // then vn_stage_free()s the plane, which wipes it and puts it back on
  // its context's shelf: in a steady state two planes of the size the
  // interval needs take turns, and the reader neither allocates nor
  // meets a page for the first time. A new plane comes from calloc
  // (zeroed lazily by the kernel at the sizes that matter), a used one
  // is wiped slot by slot: either way every slot a row has not filled
  // reads zero (slot validity is gated on wts > 0).
  struct PlaneShelf;
  struct StagePlane {
    int32_t rows = 0;   // allocated rows (pow2)
    int32_t depth = 0;  // slots per row (B)
    long long total = 0;  // staged samples since allocation
    // true while every staged weight is exactly 1.0 (unsampled metrics,
    // the overwhelmingly common case): the consumer can then skip the
    // weights plane entirely and rebuild it on device from `count` —
    // halving the host->device upload at flush
    bool unit_wts = true;
    float* vals = nullptr;     // [rows * depth]
    float* wts = nullptr;      // [rows * depth]
    int32_t* count = nullptr;  // [rows]
    // micro-fold watermark: slots [drained[r], count[r]) are staged but
    // not yet copied out by vn_stage_drain_delta. `count` itself is
    // never rewound by a drain — the per-epoch depth cap (and hence the
    // spill partitioning) is identical whether or not micro-folds ran.
    std::vector<int32_t> drained;  // [rows], lazily sized
    long long drained_total = 0;
    std::shared_ptr<PlaneShelf> shelf;  // where vn_stage_free puts it back

    StagePlane() = default;
    StagePlane(const StagePlane&) = delete;
    StagePlane& operator=(const StagePlane&) = delete;
    ~StagePlane() {
      std::free(vals);
      std::free(wts);
      std::free(count);
    }

    // Row-major [rows, depth]: existing rows keep their offsets, the
    // rows past them are zero.
    void grow_to(int32_t nr) {
      auto regrow = [this, nr](auto** arr, size_t per_row) {
        using T = std::remove_reference_t<decltype(**arr)>;
        T* fresh = static_cast<T*>(std::calloc(nr * per_row, sizeof(T)));
        if (fresh == nullptr) throw std::bad_alloc();
        if (rows > 0) std::memcpy(fresh, *arr, rows * per_row * sizeof(T));
        std::free(*arr);
        *arr = fresh;
      };
      regrow(&vals, depth);
      regrow(&wts, depth);
      regrow(&count, 1);
      rows = nr;
    }

    // Back to what a new plane of these rows holds: only the slots the
    // counts say were written are touched.
    void wipe() {
      for (int32_t r = 0; r < rows; ++r) {
        const size_t filled = static_cast<size_t>(count[r]);
        if (filled == 0) continue;
        const size_t at = static_cast<size_t>(r) * depth;
        std::memset(vals + at, 0, filled * sizeof(float));
        std::memset(wts + at, 0, filled * sizeof(float));
        count[r] = 0;
      }
      std::fill(drained.begin(), drained.end(), 0);
      total = drained_total = 0;
      unit_wts = true;
    }
  };
  // A context's spare plane. It has a lock of its own because a plane
  // comes back from the flush's thread with no context lock held, and
  // shared ownership because a detached plane may outlive its context.
  struct PlaneShelf {
    std::mutex mu;
    StagePlane* spare = nullptr;
    // the pow2 that held the histo rows of the interval before
    // (note_stage_rows): a new plane starts there, so a steady interval
    // never grows its plane, and a plane of another size is not kept
    int32_t want_rows = 0;
    bool open = true;  // false once the context is gone (vn_ctx_free)
  };
  int stage_depth = 0;  // 0 = staging disabled (legacy SoA only)
  StagePlane* stage = nullptr;
  std::shared_ptr<PlaneShelf> shelf = std::make_shared<PlaneShelf>();

  // pending SoA batches
  std::vector<int32_t> h_rows;
  std::vector<float> h_vals;
  std::vector<float> h_wts;
  std::vector<int32_t> c_rows;
  std::vector<double> c_contribs;
  std::vector<int32_t> g_rows;
  std::vector<double> g_vals;
  // built lazily the first time g_rows hits the spill cap: gauges are
  // last-write-wins, so a capped batch must UPDATE a row's pending
  // entry in place rather than shed the newest value (a shed gauge
  // would flush an actively wrong early-interval value). Cleared on
  // drain/reset; rows absent from the capped batch still shed+count.
  std::unordered_map<int32_t, size_t> g_last;
  std::vector<int32_t> s_rows;
  std::vector<int32_t> s_idx;
  std::vector<int8_t> s_rank;

  // The series directory, for the context's lifetime: (kind, scope
  // class, name, joined tags) -> sid, dense and append-only, plus the
  // series' row of the current interval (series_row). A series that
  // re-registers in a later interval is queued for Python as integers
  // and its strings cross once in its lifetime. sid_handed[sid] is set
  // once a drain has handed the strings over. Past intern_cap entries
  // the table is dropped at the next reset (the queue is empty there,
  // so no drain mixes two generations) and learnt again; the drain
  // reports intern_generation so Python drops its side.
  Directory interned;
  std::vector<uint8_t> sid_handed;
  uint32_t intern_generation = 0;
  size_t intern_cap = 4000000;
  NewSeriesQueue new_series;
  // the last drain's records: vn_drain_new_series swaps the queue in
  // here and hands out pointers, valid until the next drain
  NewSeriesQueue drained_series;
  std::string other_lines;  // events/_sc handed back to Python, \n-joined

  long long processed = 0;
  long long errors = 0;
  long long overload_dropped = 0;  // samples shed at the SoA spill caps
  size_t spill_cap = size_t{1} << 22;  // entries per pending SoA batch

  // What the reader threads homed on this context did with their time
  // (vn_reader_ns): nanoseconds inside recv, and nanoseconds outside it
  // (parse + commit + lock wait). Two clock reads per recv of up to a
  // chunk, nothing per line; lifetime totals, never reset, so a reader
  // of them takes differences and a reader thread that has exited (one
  // per TCP connection) leaves its share behind.
  std::atomic<long long> rd_recv_ns{0};
  std::atomic<long long> rd_busy_ns{0};

  // What the commit path met (vn_commit_counters), lifetime totals like
  // the two above, written under `mu`. A committed sample or an upsert
  // is one of: dir_hits (the series has a row of this interval),
  // dir_restamped (known series, first write of the interval: a row is
  // stamped and queued as integers), dir_first_seen (inserted, strings
  // queued). commit_batches counts lock holds of ingest_buffer and
  // commit_lines the metric lines committed inside them; plane_grows
  // the staging plane's reallocations. A histogram or timer sample that
  // was committed went into the staging plane (histo_staged) or, its
  // row's slots full, into the SoA batch for the spill fold
  // (histo_spilled); a shed sample is neither.
  long long dir_hits = 0;
  long long dir_restamped = 0;
  long long dir_first_seen = 0;
  long long commit_batches = 0;
  long long commit_lines = 0;
  long long plane_grows = 0;
  long long histo_staged = 0;
  long long histo_spilled = 0;

  // The commit lock as the committers meet it (vn_lock_stats), written
  // under `mu`, always on: one record a lock hold of ingest_buffer (a
  // try_lock and three clock reads a chunk or a datagram, nothing a
  // line), so lk_acquisitions moves with commit_batches. Wait is the
  // blocked acquire (0 where try_lock took it), hold is commit_lines.
  // Lifetime totals unless vn_lock_stats_reset is called; the rings
  // keep the most recent waits/holds for true percentiles.
  long long lk_acquisitions = 0;
  long long lk_contended = 0;
  long long lk_wait_ns_total = 0;
  long long lk_hold_ns_total = 0;
  static constexpr int kLockRing = 4096;
  int64_t lk_ring_n = 0;  // total samples ever (ring index = n % kLockRing)
  int64_t lk_wait_ring[kLockRing] = {0};
  int64_t lk_hold_ring[kLockRing] = {0};

  // SSF span ingest stats (native span→metric fast path). Service names
  // come from untrusted payloads — keyed by hash map so per-span cost
  // stays O(1) under high service cardinality.
  long long ssf_spans = 0;
  long long ssf_invalid = 0;
  std::unordered_map<std::string, long long> ssf_services;
  std::string ssf_services_out;  // drained lines awaiting pickup
  // raw SSF payloads the native reader could not ingest (STATUS samples
  // aboard -> Python path). Bounded; overflow counts into ssf_invalid.
  std::vector<std::string> ssf_fallback;
  size_t ssf_fallback_bytes = 0;
  static constexpr size_t kSsfFallbackCap = 1 << 22;
  uint64_t uniq_rng = 0x9E3779B97F4A7C15ull;

  // scratch reused across lines (SSF extraction builds `joined` itself;
  // DogStatsD tag parsing uses the thread-local Scratch instead)
  std::string joined;
};

// The series' row of this interval, from one probe of the lifetime
// directory: a slot stamped with this epoch has it; a known series
// written for the first time this interval takes the pool's next row and
// is queued for vn_drain_new_series as three integers; a series the
// table has never seen is inserted and also queues its kind, scope
// class and strings. `next_row` is the pool's counter (Ctx::next_*_row),
// key_hash is dir_key_hash over the same parts. Caller holds ctx->mu.
int32_t series_row(Ctx* ctx, int32_t pool, int32_t* next_row, int32_t kind,
                   int32_t scope_class, uint64_t key_hash,
                   std::string_view name, std::string_view type_str,
                   std::string_view joined) {
  bool inserted = false;
  Directory::Slot* slot = ctx->interned.find_or_insert(
      key_hash, name, type_str, joined, static_cast<char>('0' + scope_class),
      &inserted);
  if (slot->epoch == ctx->epoch) {
    ++ctx->dir_hits;
    return slot->row;
  }
  const int32_t row = (*next_row)++;
  const int32_t sid = slot->sid;
  slot->row = row;
  slot->epoch = ctx->epoch;
  if (inserted) {
    ctx->sid_handed.push_back(0);
    ++ctx->dir_first_seen;
  } else {
    ++ctx->dir_restamped;
  }
  NewSeriesQueue& q = ctx->new_series;
  if (!ctx->sid_handed[sid]) {
    q.first_at.push_back(static_cast<int32_t>(q.rows.size()));
    q.first_kinds.push_back(kind);
    q.first_scopes.push_back(scope_class);
    // the record is framed with the \x1e/\x1f unit separators; no
    // legitimate name/tag contains them, but wire input is untrusted —
    // substitute so framing can't break
    auto append_clean = [&q](std::string_view part, char end) {
      for (char ch : part)
        q.strs.push_back(ch == '\x1e' || ch == '\x1f' ? '_' : ch);
      q.strs.push_back(end);
    };
    append_clean(name, '\x1f');
    append_clean(joined, '\x1e');
  }
  q.pools.push_back(pool);
  q.rows.push_back(row);
  q.sids.push_back(sid);
  return row;
}

bool route_metric(Ctx* ctx, std::string_view name, MetricKind kind,
                  double value, std::string_view set_value,
                  double sample_rate, int scope);

// Parse-phase scratch, one per reader thread: parsing (tag sort/join —
// the expensive part of a line) runs with no lock held; only the commit
// into the target shard takes that shard's mutex.
struct Scratch {
  std::vector<std::string_view> tags;
  std::string joined;
};

struct Parsed {
  std::string_view name;
  MetricKind kind = KIND_COUNTER;
  double value = 0;
  std::string_view set_value;
  double sample_rate = 1.0;
  int scope = 0;
  uint32_t digest = 0;  // worker-routing digest (fnv1a32 of identity)
};

// The directory key of a parsed line: it spans identity + scope class
// (the same MetricKey can legally live in two scope maps); hashed from
// parts, no key build, no ctx access.
inline uint64_t series_key_hash(const Parsed& p, std::string_view joined) {
  return dir_key_hash(p.digest, p.name, kind_type_string(p.kind), joined,
                      classify(p.kind, p.scope));
}

bool commit_metric(Ctx* ctx, const Parsed& p, std::string_view joined,
                   uint64_t key_hash);

// Delimiter finders: one tokenizer body (parse_line_impl), two ways to
// locate delimiters. MaskFinder covers lines ≤64 bytes (the production
// norm — avg ~50B) with ONE SSE2 sweep building '|' ':' ',' bitmasks,
// replacing ~5 memchr calls' worth of per-call overhead; ScalarFinder
// is the memchr path for longer lines. Both must locate identically —
// the shared body is what guarantees the accept/reject sets match
// (pinned by tools/fuzz_differential.py's dogstatsd target).
struct ScalarFinder {
  std::string_view line;
  size_t first_colon() const { return line.find(':'); }
  size_t next_pipe(size_t from) const { return line.find('|', from); }
  size_t next_comma(size_t from, size_t limit) const {
    size_t c = line.find(',', from);
    return (c == std::string_view::npos || c >= limit)
               ? std::string_view::npos
               : c;
  }
};

struct MaskFinder {
  uint64_t pipe = 0, colon = 0, comma = 0;

  explicit MaskFinder(std::string_view line) {
    const char* p = line.data();
    const size_t n = line.size();
    const __m128i vp = _mm_set1_epi8('|');
    const __m128i vc = _mm_set1_epi8(':');
    const __m128i vm = _mm_set1_epi8(',');
    size_t i = 0;
    for (; i + 16 <= n; i += 16) {
      __m128i x = _mm_loadu_si128(
          reinterpret_cast<const __m128i*>(p + i));
      pipe |= static_cast<uint64_t>(static_cast<uint16_t>(
                  _mm_movemask_epi8(_mm_cmpeq_epi8(x, vp))))
              << i;
      colon |= static_cast<uint64_t>(static_cast<uint16_t>(
                   _mm_movemask_epi8(_mm_cmpeq_epi8(x, vc))))
               << i;
      comma |= static_cast<uint64_t>(static_cast<uint16_t>(
                   _mm_movemask_epi8(_mm_cmpeq_epi8(x, vm))))
               << i;
    }
    for (; i < n; ++i) {  // tail (never reads past the buffer)
      const char c = p[i];
      if (c == '|') pipe |= 1ull << i;
      else if (c == ':') colon |= 1ull << i;
      else if (c == ',') comma |= 1ull << i;
    }
  }

  static size_t from_mask(uint64_t m) {
    return m ? static_cast<size_t>(__builtin_ctzll(m))
             : std::string_view::npos;
  }
  size_t first_colon() const { return from_mask(colon); }
  size_t next_pipe(size_t from) const {
    // from <= 64 always (one past a delimiter in a ≤64B line)
    return from_mask(from >= 64 ? 0 : pipe & (~0ull << from));
  }
  size_t next_comma(size_t from, size_t limit) const {
    uint64_t m = from >= 64 ? 0 : comma & (~0ull << from);
    if (limit < 64) m &= (1ull << limit) - 1;
    return from_mask(m);
  }
};

template <class Finder>
inline bool parse_line_impl(const Finder& f, Scratch* sc,
                            std::string_view line, Parsed* out) {
  size_t colon = f.first_colon();
  if (colon == std::string_view::npos || colon == 0) return false;
  std::string_view name = line.substr(0, colon);
  // the reference tokenizes by splitting on '|' FIRST (pipeSplitter,
  // samplers/parser.go:298-325): the first pipe chunk must be the full
  // name:value, so a '|' before the first ':' means the first chunk
  // has no colon — reject like the reference and the Python parser do
  // (round-4 differential fuzz, tools/fuzz_differential.py). One scan:
  // the global first '|' past the colon IS pipe1.
  size_t pipe1 = f.next_pipe(0);
  if (pipe1 == std::string_view::npos || pipe1 < colon) return false;
  std::string_view value_chunk = line.substr(colon + 1, pipe1 - colon - 1);
  size_t pipe2 = f.next_pipe(pipe1 + 1);
  std::string_view type_chunk =
      line.substr(pipe1 + 1, (pipe2 == std::string_view::npos
                                  ? line.size()
                                  : pipe2) - pipe1 - 1);
  if (type_chunk.empty()) return false;

  MetricKind kind;
  switch (type_chunk[0]) {
    case 'c': kind = KIND_COUNTER; break;
    case 'g': kind = KIND_GAUGE; break;
    case 'd':
    case 'h': kind = KIND_HISTOGRAM; break;
    case 'm': kind = KIND_TIMER; break;
    case 's': kind = KIND_SET; break;
    default: return false;
  }

  double value = 0;
  std::string_view set_value;
  if (kind == KIND_SET) {
    set_value = value_chunk;
  } else {
    if (!parse_value(value_chunk, &value)) return false;
  }

  double sample_rate = 1.0;
  bool found_rate = false, found_tags = false;
  int scope = 0;
  sc->tags.clear();
  sc->joined.clear();

  size_t pos = pipe2;
  while (pos != std::string_view::npos) {
    size_t next = f.next_pipe(pos + 1);
    size_t chunk_end = next == std::string_view::npos ? line.size() : next;
    std::string_view chunk = line.substr(pos + 1, chunk_end - pos - 1);
    if (chunk.empty()) return false;
    if (chunk[0] == '@') {
      if (found_rate) return false;
      if (!parse_value(chunk.substr(1), &sample_rate)) return false;
      if (!(sample_rate > 0 && sample_rate <= 1)) return false;
      found_rate = true;
    } else if (chunk[0] == '#') {
      if (found_tags) return false;
      found_tags = true;
      size_t tstart = pos + 2;  // one past '#'
      while (true) {
        size_t comma = f.next_comma(tstart, chunk_end);
        size_t e = comma == std::string_view::npos ? chunk_end : comma;
        sc->tags.push_back(line.substr(tstart, e - tstart));
        if (comma == std::string_view::npos) break;
        tstart = comma + 1;
      }
      std::sort(sc->tags.begin(), sc->tags.end());
      // first magic scope tag (prefix match) is consumed
      // (samplers/parser.go:394-408)
      for (size_t i = 0; i < sc->tags.size(); ++i) {
        constexpr std::string_view kLocal = "veneurlocalonly";
        constexpr std::string_view kGlobal = "veneurglobalonly";
        if (sc->tags[i].substr(0, kLocal.size()) == kLocal) {
          scope = 1;
          sc->tags.erase(sc->tags.begin() + i);
          break;
        }
        if (sc->tags[i].substr(0, kGlobal.size()) == kGlobal) {
          scope = 2;
          sc->tags.erase(sc->tags.begin() + i);
          break;
        }
      }
      for (size_t i = 0; i < sc->tags.size(); ++i) {
        if (i) sc->joined.push_back(',');
        sc->joined.append(sc->tags[i]);
      }
    } else {
      return false;
    }
    pos = next;
  }

  out->name = name;
  out->kind = kind;
  out->value = value;
  out->set_value = set_value;
  out->sample_rate = sample_rate;
  out->scope = scope;
  // identity digest: fnv1a32 over name, type, joined tags (parse-time
  // digest, samplers/parser.go:325-420); doubles as the shard router
  uint32_t digest = fnv1a32(name);
  digest = fnv1a32(kind_type_string(kind), digest);
  digest = fnv1a32(sc->joined, digest);
  out->digest = digest;
  return true;
}

// Parse one metric line into `out` (tags normalized into sc->joined);
// returns false on parse error. No ctx access — safe concurrently.
bool parse_line(Scratch* sc, std::string_view line, Parsed* out) {
  if (line.size() <= 64) {
    return parse_line_impl(MaskFinder(line), sc, line, out);
  }
  return parse_line_impl(ScalarFinder{line}, sc, line, out);
}

// Route one parsed/converted sample into the pools. Expects ctx->joined to
// hold the sorted, magic-stripped tag string. Used by the SSF span
// extraction below (which builds ctx->joined itself); the DogStatsD text
// path goes parse_line → commit_metric.
bool route_metric(Ctx* ctx, std::string_view name, MetricKind kind,
                  double value, std::string_view set_value,
                  double sample_rate, int scope) {
  Parsed p;
  p.name = name;
  p.kind = kind;
  p.value = value;
  p.set_value = set_value;
  p.sample_rate = sample_rate;
  p.scope = scope;
  uint32_t digest = fnv1a32(name);
  digest = fnv1a32(kind_type_string(kind), digest);
  digest = fnv1a32(ctx->joined, digest);
  p.digest = digest;
  return commit_metric(ctx, p, ctx->joined, series_key_hash(p, ctx->joined));
}

constexpr int32_t kMinStageRows = 4096;

// The closing interval's plane needed this many rows (the pow2 its
// growth by doubling ends on): the next plane starts there.
void note_stage_rows(Ctx* ctx) {
  int32_t nr = kMinStageRows;
  while (nr < ctx->next_histo_row) nr *= 2;
  std::lock_guard<std::mutex> g(ctx->shelf->mu);
  ctx->shelf->want_rows = nr;
}

// A plane nobody reads any more: wiped and kept as its context's spare
// if it has the size the context wants next, else freed. Takes no
// context lock.
void shelve_plane(Ctx::StagePlane* sp) {
  std::shared_ptr<Ctx::PlaneShelf> shelf = sp->shelf;
  {
    std::lock_guard<std::mutex> g(shelf->mu);
    if (!shelf->open || shelf->spare != nullptr ||
        sp->rows != shelf->want_rows) {
      delete sp;
      return;
    }
  }
  sp->wipe();  // (the plane is nobody's here: no lock needed)
  std::lock_guard<std::mutex> g(shelf->mu);
  if (shelf->open && shelf->spare == nullptr) {
    shelf->spare = sp;
  } else {
    delete sp;
  }
}

// The plane of a new interval: the spare if it fits, else a new one (its
// arrays come with its first row).
Ctx::StagePlane* take_plane(Ctx* ctx) {
  Ctx::PlaneShelf& shelf = *ctx->shelf;
  {
    std::lock_guard<std::mutex> g(shelf.mu);
    Ctx::StagePlane* sp = shelf.spare;
    shelf.spare = nullptr;
    if (sp != nullptr && sp->depth == ctx->stage_depth) return sp;
    delete sp;
  }
  Ctx::StagePlane* sp = new Ctx::StagePlane();
  sp->depth = ctx->stage_depth;
  sp->shelf = ctx->shelf;
  return sp;
}

// Store one histo/timer sample into the staging plane. Returns false if
// staging is disabled or the row's slots are full (caller spills to the
// SoA batch). Caller holds the ctx mutex.
bool stage_histo_sample(Ctx* ctx, int32_t row, double value,
                        double sample_rate) {
  if (ctx->stage_depth <= 0) return false;
  Ctx::StagePlane* sp = ctx->stage;
  if (sp == nullptr) sp = ctx->stage = take_plane(ctx);
  if (row >= sp->rows) {
    int32_t nr = sp->rows;
    if (nr == 0) {
      std::lock_guard<std::mutex> g(ctx->shelf->mu);
      nr = std::max(kMinStageRows, ctx->shelf->want_rows);
    }
    while (nr <= row) nr *= 2;
    if (sp->rows > 0) ++ctx->plane_grows;
    sp->grow_to(nr);
  }
  int32_t& c = sp->count[row];
  if (c >= sp->depth) return false;
  size_t at = static_cast<size_t>(row) * sp->depth + c;
  float w = static_cast<float>(1.0 / sample_rate);
  sp->vals[at] = static_cast<float>(value);
  sp->wts[at] = w;
  if (w != 1.0f) sp->unit_wts = false;
  ++c;
  ++sp->total;
  return true;
}

// Commit one parsed metric into a shard's directory + SoA buffers;
// key_hash is series_key_hash(p, joined). Caller holds ctx->mu (or owns
// the ctx exclusively).
bool commit_metric(Ctx* ctx, const Parsed& p, std::string_view joined,
                   uint64_t key_hash) {
  std::string_view name = p.name;
  MetricKind kind = p.kind;
  double value = p.value;
  std::string_view set_value = p.set_value;
  double sample_rate = p.sample_rate;
  const char* type_str = kind_type_string(kind);
  ScopeClass cls = classify(kind, p.scope);
  // Overload shedding: the pending SoA batches are normally drained
  // every ~100ms (Server's native pump / strided ingest checks), but a
  // host whose aggregate throughput is below the offered load can't
  // drain them at arrival rate, and an unbounded vector here is an OOM
  // waiting for a traffic spike (observed: multi-GB RSS in an overload
  // soak). Beyond the cap the SAMPLE is dropped and counted
  // (overload_dropped -> veneur.ingest.overload_dropped_total); the
  // series registration above the drop still happens, so cardinality
  // bookkeeping stays exact. Mirrors the reference's bounded worker
  // channels, where the kernel socket buffer sheds the excess
  // (worker.go:31-48 PacketChan; drop-don't-block per README backpressure).
  const size_t kSpillCap = ctx->spill_cap;
  switch (kind) {
    case KIND_HISTOGRAM:
    case KIND_TIMER: {
      int32_t row = series_row(ctx, 0, &ctx->next_histo_row, kind, cls,
                               key_hash, name, type_str, joined);
      if (stage_histo_sample(ctx, row, value, sample_rate)) {
        ++ctx->histo_staged;
      } else if (ctx->h_rows.size() < kSpillCap) {
        // staging disabled, or this row's plane slots are full: spill
        // into the SoA batch for the direct per-batch device fold
        ctx->h_rows.push_back(row);
        ctx->h_vals.push_back(static_cast<float>(value));
        ctx->h_wts.push_back(static_cast<float>(1.0 / sample_rate));
        ++ctx->histo_spilled;
      } else {
        ++ctx->overload_dropped;
      }
      break;
    }
    case KIND_SET: {
      int32_t row = series_row(ctx, 1, &ctx->next_set_row, kind, cls,
                               key_hash, name, type_str, joined);
      uint64_t h = ctx->set_hash_metro ? metro_hash64(set_value, 1337)
                                       : fmix64(fnv1a64(set_value));
      int p = ctx->hll_precision;
      uint32_t idx = static_cast<uint32_t>(h >> (64 - p));
      uint64_t w = h << p;
      int rank = w == 0 ? (64 - p + 1) : (__builtin_clzll(w) + 1);
      if (rank > 64 - p + 1) rank = 64 - p + 1;
      if (ctx->s_rows.size() < kSpillCap) {
        ctx->s_rows.push_back(row);
        ctx->s_idx.push_back(static_cast<int32_t>(idx));
        ctx->s_rank.push_back(static_cast<int8_t>(rank));
      } else {
        ++ctx->overload_dropped;
      }
      break;
    }
    case KIND_COUNTER: {
      int32_t row = series_row(ctx, 2, &ctx->next_counter_row, kind, cls,
                               key_hash, name, type_str, joined);
      if (ctx->c_rows.size() < kSpillCap) {
        // Go semantics: int64(sample) * int64(1/rate)
        ctx->c_rows.push_back(row);
        ctx->c_contribs.push_back(
            static_cast<double>(static_cast<long long>(value) *
                                static_cast<long long>(1.0 / sample_rate)));
      } else {
        ++ctx->overload_dropped;
      }
      break;
    }
    case KIND_GAUGE: {
      int32_t row = series_row(ctx, 3, &ctx->next_gauge_row, kind, cls,
                               key_hash, name, type_str, joined);
      if (ctx->g_rows.size() < kSpillCap) {
        ctx->g_rows.push_back(row);
        ctx->g_vals.push_back(value);
      } else {
        if (ctx->g_last.empty()) {
          // overload onset: index the batch once (last occurrence wins)
          for (size_t i = 0; i < ctx->g_rows.size(); ++i)
            ctx->g_last[ctx->g_rows[i]] = i;
        }
        auto it = ctx->g_last.find(row);
        if (it != ctx->g_last.end()) {
          ctx->g_vals[it->second] = value;  // last write wins, in place
        } else {
          ++ctx->overload_dropped;
        }
      }
      break;
    }
  }
  return true;
}

// The Python-side upsert (vn_upsert, vn_upsert_many): the series' row of
// this interval by the same probe a parsed line takes, no sample.
// Caller holds ctx->mu.
int32_t upsert_series(Ctx* ctx, std::string_view name, int32_t kind,
                      std::string_view joined, int32_t scope_class) {
  MetricKind k = static_cast<MetricKind>(kind);
  const char* type_str = kind_type_string(k);
  uint32_t digest = fnv1a32(name);
  digest = fnv1a32(type_str, digest);
  digest = fnv1a32(joined, digest);
  uint64_t key_hash = dir_key_hash(digest, name, type_str, joined, scope_class);
  int32_t* next = nullptr;
  int32_t pool = 0;
  switch (k) {
    case KIND_HISTOGRAM:
    case KIND_TIMER:
      next = &ctx->next_histo_row;
      pool = 0;
      break;
    case KIND_SET:
      next = &ctx->next_set_row;
      pool = 1;
      break;
    case KIND_COUNTER:
      next = &ctx->next_counter_row;
      pool = 2;
      break;
    case KIND_GAUGE:
      next = &ctx->next_gauge_row;
      pool = 3;
      break;
  }
  return series_row(ctx, pool, next, kind, scope_class, key_hash, name,
                    type_str, joined);
}

// ---------------------------------------------------------------------------
// SSF span ingest: protobuf wire decode + span→metric extraction.
//
// Replaces the Python path (protocol/ssf_wire.parse_ssf +
// core/spans.MetricExtractionSink) for the hot case — spans carrying
// counter/gauge/histogram/set samples and indicator timers (reference
// sinks/ssfmetrics/metrics.go:66-141, samplers/parser.go:103-208). The
// decoder is a minimal hand-rolled proto3 reader over proto/ssf.proto
// (field numbers follow the public SSF spec, ssf/sample.proto), reading
// string fields as zero-copy views into the datagram. STATUS samples are
// control-plane traffic; spans carrying them return -1 so the caller can
// take the Python path.

struct TagPair {
  std::string_view k, v;
};

struct SampleView {
  int metric = 0;  // SSFSample.Metric enum
  std::string_view name;
  float value = 0;
  std::string_view message;
  int status = 0;
  float sample_rate = 1.0f;
  int scope = 0;  // SSFSample.Scope enum
  std::vector<TagPair> tags;
};

struct SpanView {
  int64_t trace_id = 0, id = 0, parent_id = 0;
  int64_t start_ts = 0, end_ts = 0;
  bool error = false, indicator = false;
  std::string_view service, name;
  std::vector<TagPair> tags;
  std::vector<SampleView> samples;
  bool has_status = false;
};

// proto3 `string` fields must be valid UTF-8; the stock protobuf
// decoders (Python, Go) reject violations, so the fast path must too
// or corrupted packets would diverge between the two pipelines.
bool valid_utf8(std::string_view s) {
  size_t i = 0, n = s.size();
  while (i < n) {
    uint8_t c = static_cast<uint8_t>(s[i]);
    if (c < 0x80) { i++; continue; }
    int len;
    uint32_t cp, min_cp;
    if ((c >> 5) == 0x6) { len = 2; cp = c & 0x1F; min_cp = 0x80; }
    else if ((c >> 4) == 0xE) { len = 3; cp = c & 0x0F; min_cp = 0x800; }
    else if ((c >> 3) == 0x1E) { len = 4; cp = c & 0x07; min_cp = 0x10000; }
    else return false;
    if (i + static_cast<size_t>(len) > n) return false;
    for (int j = 1; j < len; j++) {
      uint8_t cc = static_cast<uint8_t>(s[i + j]);
      if ((cc >> 6) != 0x2) return false;
      cp = (cp << 6) | (cc & 0x3F);
    }
    if (cp < min_cp || cp > 0x10FFFF) return false;
    if (cp >= 0xD800 && cp <= 0xDFFF) return false;  // surrogate range
    i += len;
  }
  return true;
}

struct ProtoReader {
  const uint8_t* p;
  const uint8_t* end;
  bool ok = true;

  uint64_t varint() {
    uint64_t v = 0;
    int shift = 0;
    while (p < end && shift < 64) {
      uint8_t b = *p++;
      // overflow in the 10th byte (bits past 2^64) is malformed wire;
      // see WireCursor::varint
      if (shift == 63 && (b & 0xfe)) {
        ok = false;
        return 0;
      }
      v |= static_cast<uint64_t>(b & 0x7f) << shift;
      if (!(b & 0x80)) return v;
      shift += 7;
    }
    ok = false;
    return 0;
  }

  // a TAG varint: canonical wire caps tags at 5 bytes (uint32), as
  // upstream protobuf parsers enforce. The reference's gogo-generated
  // Unmarshal is looser (≤10 bytes, truncating) — deliberate
  // spec-over-reference divergence, see PARITY.md "Deliberate
  // wire-strictness divergences"
  uint64_t tag_varint() {
    uint64_t v = 0;
    int shift = 0;
    while (p < end && shift < 35) {
      uint8_t b = *p++;
      if (shift == 28 && (b & 0xF0)) {  // bits past 2^32 or a 6th byte
        ok = false;
        return 0;
      }
      v |= static_cast<uint64_t>(b & 0x7f) << shift;
      if (!(b & 0x80)) return v;
      shift += 7;
    }
    ok = false;
    return 0;
  }

  std::string_view bytes() {
    uint64_t n = varint();
    if (!ok || n > static_cast<uint64_t>(end - p)) {
      ok = false;
      return {};
    }
    std::string_view s(reinterpret_cast<const char*>(p),
                       static_cast<size_t>(n));
    p += n;
    return s;
  }

  // a `string`-typed field: length-delimited AND valid UTF-8
  std::string_view str() {
    std::string_view s = bytes();
    if (ok && !valid_utf8(s)) ok = false;
    return s;
  }

  float fixed32f() {
    if (end - p < 4) {
      ok = false;
      return 0;
    }
    float f;
    std::memcpy(&f, p, 4);
    p += 4;
    return f;
  }

  void skip(int wire_type) {
    switch (wire_type) {
      case 0: varint(); break;
      case 1: p += 8; if (p > end) ok = false; break;
      case 2: bytes(); break;
      case 5: p += 4; if (p > end) ok = false; break;
      default: ok = false;
    }
  }
};

// A known field whose declared wire type doesn't match the schema is a
// corrupt/incompatible packet: reject it (the Python protobuf parser
// raises; silently consuming with the wrong reader would desync the
// stream and ingest garbage into the series directory).
#define VN_EXPECT_WT(want) \
  if (wt != (want)) return false

// map<string,string> entry: {1: key, 2: value}
bool decode_tag_entry(std::string_view buf, TagPair* out) {
  ProtoReader r{reinterpret_cast<const uint8_t*>(buf.data()),
                reinterpret_cast<const uint8_t*>(buf.data() + buf.size())};
  while (r.ok && r.p < r.end) {
    uint64_t tag = r.tag_varint();
    if (!r.ok) return false;
    // field number 0 is forbidden (tag_varint already bounds the tag
    // itself at uint32, i.e. field <= 2^29-1)
    if ((tag >> 3) == 0) return false;
    int field = static_cast<int>(tag >> 3), wt = static_cast<int>(tag & 7);
    if (field == 1) {
      VN_EXPECT_WT(2);
      out->k = r.str();
    } else if (field == 2) {
      VN_EXPECT_WT(2);
      out->v = r.str();
    } else {
      r.skip(wt);
    }
  }
  return r.ok;
}

bool decode_sample(std::string_view buf, SampleView* s) {
  ProtoReader r{reinterpret_cast<const uint8_t*>(buf.data()),
                reinterpret_cast<const uint8_t*>(buf.data() + buf.size())};
  while (r.ok && r.p < r.end) {
    uint64_t tag = r.tag_varint();
    if (!r.ok) return false;
    // field number 0 is forbidden (tag_varint already bounds the tag
    // itself at uint32, i.e. field <= 2^29-1)
    if ((tag >> 3) == 0) return false;
    int field = static_cast<int>(tag >> 3), wt = static_cast<int>(tag & 7);
    switch (field) {
      case 1: VN_EXPECT_WT(0); s->metric = static_cast<int>(r.varint());
        break;
      case 2: VN_EXPECT_WT(2); s->name = r.str(); break;
      case 3: VN_EXPECT_WT(5); s->value = r.fixed32f(); break;
      case 5: VN_EXPECT_WT(2); s->message = r.str(); break;
      case 6: VN_EXPECT_WT(0); s->status = static_cast<int>(r.varint());
        break;
      case 7: VN_EXPECT_WT(5); s->sample_rate = r.fixed32f(); break;
      case 8: {
        VN_EXPECT_WT(2);
        TagPair t;
        if (!decode_tag_entry(r.bytes(), &t)) return false;
        s->tags.push_back(t);
        break;
      }
      // unit (field 9) is unused here but is a proto3 string: its bytes
      // must still be valid UTF-8 or the stock decoders reject the span
      case 9: VN_EXPECT_WT(2); r.str(); break;
      case 10: VN_EXPECT_WT(0); s->scope = static_cast<int>(r.varint());
        break;
      default: r.skip(wt);
    }
  }
  if (s->sample_rate == 0) s->sample_rate = 1.0f;  // wire normalization
  return r.ok;
}

bool decode_span(std::string_view buf, SpanView* sp) {
  ProtoReader r{reinterpret_cast<const uint8_t*>(buf.data()),
                reinterpret_cast<const uint8_t*>(buf.data() + buf.size())};
  while (r.ok && r.p < r.end) {
    uint64_t tag = r.tag_varint();
    if (!r.ok) return false;
    // field number 0 is forbidden (tag_varint already bounds the tag
    // itself at uint32, i.e. field <= 2^29-1)
    if ((tag >> 3) == 0) return false;
    int field = static_cast<int>(tag >> 3), wt = static_cast<int>(tag & 7);
    switch (field) {
      case 2: VN_EXPECT_WT(0);
        sp->trace_id = static_cast<int64_t>(r.varint());
        break;
      case 3: VN_EXPECT_WT(0); sp->id = static_cast<int64_t>(r.varint());
        break;
      case 4: VN_EXPECT_WT(0);
        sp->parent_id = static_cast<int64_t>(r.varint());
        break;
      case 5: VN_EXPECT_WT(0);
        sp->start_ts = static_cast<int64_t>(r.varint());
        break;
      case 6: VN_EXPECT_WT(0);
        sp->end_ts = static_cast<int64_t>(r.varint());
        break;
      case 7: VN_EXPECT_WT(0); sp->error = r.varint() != 0; break;
      case 8: VN_EXPECT_WT(2); sp->service = r.str(); break;
      case 10: {
        VN_EXPECT_WT(2);
        SampleView s;
        if (!decode_sample(r.bytes(), &s)) return false;
        if (s.metric == 4) sp->has_status = true;
        sp->samples.push_back(std::move(s));
        break;
      }
      case 11: {
        VN_EXPECT_WT(2);
        TagPair t;
        if (!decode_tag_entry(r.bytes(), &t)) return false;
        sp->tags.push_back(t);
        break;
      }
      case 12: VN_EXPECT_WT(0); sp->indicator = r.varint() != 0; break;
      case 13: VN_EXPECT_WT(2); sp->name = r.str(); break;
      default: r.skip(wt);
    }
  }
  if (!r.ok) return false;
  // wire normalization: empty span name falls back to the "name" tag
  if (sp->name.empty()) {
    for (size_t i = 0; i < sp->tags.size(); ++i) {
      if (sp->tags[i].k == "name") {
        sp->name = sp->tags[i].v;
        sp->tags.erase(sp->tags.begin() + i);
        break;
      }
    }
  }
  return true;
}

// "k1:v1" < "k2:v2" without materializing the joined strings. Bytes
// compare UNSIGNED — matching Python's code-point sort and
// std::string_view's char_traits compare — or non-ASCII tags would order
// differently per ingest path and split one series into two digests.
bool tagpair_less(const TagPair& a, const TagPair& b) {
  size_t na = a.k.size() + 1 + a.v.size();
  size_t nb = b.k.size() + 1 + b.v.size();
  size_t n = na < nb ? na : nb;
  for (size_t i = 0; i < n; ++i) {
    unsigned char ca = static_cast<unsigned char>(
        i < a.k.size() ? a.k[i]
        : (i == a.k.size() ? ':' : a.v[i - a.k.size() - 1]));
    unsigned char cb = static_cast<unsigned char>(
        i < b.k.size() ? b.k[i]
        : (i == b.k.size() ? ':' : b.v[i - b.k.size() - 1]));
    if (ca != cb) return ca < cb;
  }
  return na < nb;
}

// Build ctx->joined from tag pairs, consuming magic scope keys (exact-key
// match in wire order — parse_metric_ssf semantics, parser.go:276-287).
void build_joined(Ctx* ctx, std::vector<TagPair>& pairs, int* scope) {
  for (size_t i = 0; i < pairs.size();) {
    if (pairs[i].k == "veneurlocalonly") {
      *scope = 1;
      pairs.erase(pairs.begin() + i);
    } else if (pairs[i].k == "veneurglobalonly") {
      *scope = 2;
      pairs.erase(pairs.begin() + i);
    } else {
      ++i;
    }
  }
  std::sort(pairs.begin(), pairs.end(), tagpair_less);
  ctx->joined.clear();
  for (size_t i = 0; i < pairs.size(); ++i) {
    if (i) ctx->joined.push_back(',');
    ctx->joined.append(pairs[i].k);
    ctx->joined.push_back(':');
    ctx->joined.append(pairs[i].v);
  }
}

bool ingest_sample(Ctx* ctx, SampleView& s) {
  if (s.name.empty()) return false;
  MetricKind kind;
  std::string_view set_value;
  double value = 0;
  switch (s.metric) {
    case 0: kind = KIND_COUNTER; value = s.value; break;
    case 1: kind = KIND_GAUGE; value = s.value; break;
    case 2: kind = KIND_HISTOGRAM; value = s.value; break;
    case 3: kind = KIND_SET; set_value = s.message; break;
    default: return false;  // STATUS handled by the Python path
  }
  int scope = 0;
  if (s.scope == 1) scope = 1;
  else if (s.scope == 2) scope = 2;
  build_joined(ctx, s.tags, &scope);
  return route_metric(ctx, s.name, kind, value, set_value,
                      s.sample_rate, scope);
}

// xorshift64* for uniqueness sampling — statistical, parity not required
// (the Python path uses random.random(), ssf/samples.go RandomlySample).
// State lives per-Ctx (no shared mutable global → no cross-context race).
inline double uniform01(uint64_t* state) {
  uint64_t x = *state;
  x ^= x >> 12;
  x ^= x << 25;
  x ^= x >> 27;
  *state = x;
  return static_cast<double>((x * 0x2545F4914F6CDD1Dull) >> 11) /
         static_cast<double>(1ull << 53);
}

void bump_service_count(Ctx* ctx, std::string_view service) {
  if (service.empty()) service = "unknown";
  // service names are untrusted payload bytes: bound the length (so one
  // huge name can't wedge the line-framed drain) and replace the drain
  // framing bytes themselves
  if (service.size() > 256) service = service.substr(0, 256);
  std::string key(service);
  for (char& c : key) {
    if (c == '\t' || c == '\n') c = '_';
  }
  ++ctx->ssf_services[std::move(key)];
}

// returns 1 ok, 0 decode error, -1 span carries STATUS samples (take the
// Python path; nothing was ingested)
int ingest_ssf_span(Ctx* ctx, std::string_view buf,
                    std::string_view indicator_name,
                    std::string_view objective_name, double uniq_rate) {
  SpanView sp;
  if (!decode_span(buf, &sp)) return 0;
  if (sp.has_status) return -1;

  for (SampleView& s : sp.samples) {
    if (!ingest_sample(ctx, s)) ++ctx->ssf_invalid;
  }

  bool valid_trace = sp.id != 0 && sp.trace_id != 0 && sp.start_ts != 0 &&
                     sp.end_ts != 0 && !sp.name.empty();
  if (sp.indicator && valid_trace) {
    double duration_ns = static_cast<double>(sp.end_ts - sp.start_ts);
    const std::string_view error_sv = sp.error ? "true" : "false";
    if (!indicator_name.empty()) {
      std::vector<TagPair> tags{{"service", sp.service}, {"error", error_sv}};
      int scope = 0;
      build_joined(ctx, tags, &scope);
      route_metric(ctx, indicator_name, KIND_HISTOGRAM, duration_ns, {},
                   1.0, scope);
    }
    if (!objective_name.empty()) {
      std::string_view objective = sp.name;
      for (const TagPair& t : sp.tags) {
        if (t.k == "ssf_objective" && !t.v.empty()) objective = t.v;
      }
      std::vector<TagPair> tags{{"service", sp.service},
                                {"objective", objective},
                                {"error", error_sv}};
      int scope = 2;  // veneurglobalonly
      build_joined(ctx, tags, &scope);
      route_metric(ctx, objective_name, KIND_HISTOGRAM, duration_ns, {},
                   1.0, scope);
    }
  }

  if (uniq_rate > 0 && !sp.service.empty() &&
      (uniq_rate >= 1.0 || uniform01(&ctx->uniq_rng) < uniq_rate)) {
    std::vector<TagPair> tags{
        {"indicator", sp.indicator ? "true" : "false"},
        {"service", sp.service},
        {"root_span", sp.id == sp.trace_id ? "true" : "false"}};
    int scope = 0;
    build_joined(ctx, tags, &scope);
    route_metric(ctx, "ssf.names_unique", KIND_SET, 0.0, sp.name, 1.0,
                 scope);
  }

  ++ctx->ssf_spans;
  bump_service_count(ctx, sp.service);
  return 1;
}

}  // namespace

extern "C" {

// Build stamp: the Makefile injects the sha256 prefix of this source
// file, so tests can detect a stale committed .so (one that no longer
// matches dogstatsd.cpp) instead of silently testing old code.
#ifndef VN_SOURCE_HASH
#define VN_SOURCE_HASH "unstamped"
#endif
const char* vn_source_hash() { return VN_SOURCE_HASH; }

void* vn_ctx_new(int hll_precision) {
  Ctx* ctx = new Ctx();
  ctx->hll_precision = hll_precision;
  return ctx;
}

void vn_ctx_free(void* p) {
  Ctx* ctx = static_cast<Ctx*>(p);
  {
    std::lock_guard<std::mutex> g(ctx->shelf->mu);
    ctx->shelf->open = false;  // planes still out are freed, not shelved
    delete ctx->shelf->spare;
    ctx->shelf->spare = nullptr;
  }
  delete ctx->stage;
  delete ctx;
}

// Enable the raw-sample staging plane with B slots per histogram row
// (0 disables; takes effect for subsequent samples).
void vn_set_stage_depth(void* p, int depth) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> ctx_guard(ctx->mu);
  ctx->stage_depth = depth;
}

// Detach the staging plane for flush: hands ownership of the [rows,
// depth] vals/wts planes and the per-row counts to the caller and
// installs a fresh (lazily allocated) plane for the next epoch. Returns
// an opaque handle to free with vn_stage_free AFTER the caller is done
// with the pointers, or NULL when nothing is staged.
void* vn_stage_detach(void* p, float** vals, float** wts, int32_t** counts,
                      int32_t* rows_out, int32_t* depth_out) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> ctx_guard(ctx->mu);
  Ctx::StagePlane* sp = ctx->stage;
  if (sp == nullptr || sp->total == 0) return nullptr;
  ctx->stage = nullptr;
  note_stage_rows(ctx);
  *vals = sp->vals;
  *wts = sp->wts;
  *counts = sp->count;
  *rows_out = sp->rows;
  *depth_out = sp->depth;
  return sp;
}

// Whether every weight in a detached plane is exactly 1.0 (see
// StagePlane.unit_wts). Takes the DETACHED handle, not the ctx.
int vn_stage_unit_wts(void* plane) {
  return static_cast<Ctx::StagePlane*>(plane)->unit_wts ? 1 : 0;
}

void vn_stage_free(void* plane) {
  shelve_plane(static_cast<Ctx::StagePlane*>(plane));
}

// Staged-sample count (telemetry / drain-threshold checks).
long long vn_stage_total(void* p) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> ctx_guard(ctx->mu);
  return ctx->stage == nullptr ? 0 : ctx->stage->total;
}

// Staged samples not yet copied out by vn_stage_drain_delta
// (micro-fold due-threshold checks).
long long vn_stage_pending(void* p) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> ctx_guard(ctx->mu);
  Ctx::StagePlane* sp = ctx->stage;
  return sp == nullptr ? 0 : sp->total - sp->drained_total;
}

// Copy up to `cap` not-yet-drained staged samples into the caller's COO
// buffers as (row, absolute slot, val, wt) and advance the per-row
// drained watermark. `count` is untouched: the depth cap — and hence
// which samples spill to the SoA batch — is identical to a run with no
// micro-folds, which is what makes micro==batch bit-identity hold.
// Returns the number of entries written.
int64_t vn_stage_drain_delta(void* p, int32_t* rows, int32_t* slots,
                             float* vals, float* wts, int64_t cap) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> ctx_guard(ctx->mu);
  Ctx::StagePlane* sp = ctx->stage;
  if (sp == nullptr || sp->total == sp->drained_total || cap <= 0) return 0;
  if (static_cast<int32_t>(sp->drained.size()) < sp->rows)
    sp->drained.resize(sp->rows, 0);
  int64_t n = 0;
  for (int32_t r = 0; r < sp->rows && n < cap; ++r) {
    int32_t d = sp->drained[r];
    const int32_t c = sp->count[r];
    if (d >= c) continue;
    const size_t base = static_cast<size_t>(r) * sp->depth;
    for (; d < c && n < cap; ++d, ++n) {
      rows[n] = r;
      slots[n] = d;
      vals[n] = sp->vals[base + d];
      wts[n] = sp->wts[base + d];
    }
    sp->drained_total += d - sp->drained[r];
    sp->drained[r] = d;
  }
  return n;
}

// Switch the set-element hash to metro64(seed=1337) for Go-fleet interop
// (must match every other inserter of the same set series).
void vn_ctx_set_metro(void* p, int enable) {
  static_cast<Ctx*>(p)->set_hash_metro = enable != 0;
}

// Hold the context lock across a multi-call sequence (the mutex is
// recursive, so the individual exports still work while held). The flush
// path wraps its drain→sync→reset in this so no routed commit can land
// between the last drain and the reset and be destroyed with the epoch.
// ctypes releases the GIL, so blocking here cannot deadlock Python.
void vn_lock(void* p) { static_cast<Ctx*>(p)->mu.lock(); }
void vn_unlock(void* p) { static_cast<Ctx*>(p)->mu.unlock(); }

uint64_t vn_metro_hash64(const char* data, int len, uint64_t seed) {
  return metro_hash64(std::string_view(data, static_cast<size_t>(len)), seed);
}

// ---------------------------------------------------------------------------
// Forward-batch wire encoder.
//
// Emits the histogram/timer rows of a flush snapshot as protobuf wire
// bytes of veneurtpu.MetricBatch (proto/veneur_tpu.proto) — the Python
// protobuf path costs ~5us per row building Metric messages, which at
// 1M forwarded series is seconds per flush. The wire format here is
// hand-encoded (as the framework's gob and Kafka codecs are) and
// decodes with the stock generated classes; proto3 default-skipping is
// matched (zero doubles / enum 0 omitted, empty centroids omitted).
//
// Field numbers (veneur_tpu.proto):
//   MetricBatch.metrics = 1 (LEN)
//   Metric: name=1 LEN, tags=2 LEN, kind=3 VARINT, scope=4 VARINT,
//           digest=7 LEN
//   DigestValue: centroids=1 LEN, min=2 F64, max=3 F64,
//                reciprocal_sum=4 F64, compression=5 F64
//   Centroids: means=1 packed f32, weights=2 packed f32

namespace {

inline int varint_size(uint64_t v) {
  int n = 1;
  while (v >= 0x80) {
    v >>= 7;
    ++n;
  }
  return n;
}

inline void put_varint(std::string* out, uint64_t v) {
  while (v >= 0x80) {
    out->push_back(static_cast<char>((v & 0x7F) | 0x80));
    v >>= 7;
  }
  out->push_back(static_cast<char>(v));
}

inline void put_f64(std::string* out, int field, double v) {
  if (v == 0.0) return;  // proto3 default skip
  out->push_back(static_cast<char>((field << 3) | 1));  // wire type 1
  uint64_t bits;
  std::memcpy(&bits, &v, 8);
  for (int i = 0; i < 8; ++i)
    out->push_back(static_cast<char>((bits >> (8 * i)) & 0xFF));
}

inline int f64_field_size(double v) { return v == 0.0 ? 0 : 9; }

}  // namespace

// meta_blob: per emitted row "name \x1f tag \x1f tag ...", rows joined
// with \x1e — one record per row where emit[row] != 0, in row order.
// The bytes are returned via a thread-local buffer: valid until the
// calling thread's next call, no ctx state touched (the flush thread
// encodes while readers keep committing). Returns the byte length, or
// -1 on malformed meta.
long long vn_encode_histo_batch(
    const char* meta_blob, long long meta_len,
    const signed char* kinds, const signed char* scopes,
    const unsigned char* emit, const float* means, const float* weights,
    int rows, int cap, const double* dmin, const double* dmax,
    const double* drecip, double compression, const char** out_ptr) {
  thread_local std::string buf;
  std::string& out = buf;
  out.clear();
  // rough reserve: 8 bytes/centroid + 64/row metadata
  out.reserve(static_cast<size_t>(rows) * 96);

  std::string_view meta(meta_blob, static_cast<size_t>(meta_len));
  size_t mpos = 0;
  const int comp_size = f64_field_size(compression);
  std::vector<std::string_view> tags;
  for (int r = 0; r < rows; ++r) {
    if (!emit[r]) continue;
    if (mpos > meta.size()) return -1;
    size_t rec_end = meta.find('\x1e', mpos);
    if (rec_end == std::string_view::npos) rec_end = meta.size();
    std::string_view rec = meta.substr(mpos, rec_end - mpos);
    mpos = rec_end + 1;

    // split rec into name + tags
    size_t nend = rec.find('\x1f');
    std::string_view name =
        nend == std::string_view::npos ? rec : rec.substr(0, nend);
    tags.clear();
    if (nend != std::string_view::npos) {
      std::string_view rest = rec.substr(nend + 1);
      for (;;) {
        size_t tend = rest.find('\x1f');
        if (tend == std::string_view::npos) {
          tags.push_back(rest);
          break;
        }
        tags.push_back(rest.substr(0, tend));
        rest = rest.substr(tend + 1);
      }
    }

    // count nonzero centroids
    const float* wrow = weights + static_cast<size_t>(r) * cap;
    const float* mrow = means + static_cast<size_t>(r) * cap;
    int n = 0;
    for (int j = 0; j < cap; ++j)
      if (wrow[j] > 0.0f) ++n;

    // --- sizes, innermost out ---
    int centroids_size = 0;
    if (n > 0) {
      int packed = 4 * n;
      centroids_size = 2 * (1 + varint_size(packed) + packed);
    }
    int digest_size = 0;
    if (centroids_size > 0)
      digest_size += 1 + varint_size(centroids_size) + centroids_size;
    digest_size += f64_field_size(dmin[r]) + f64_field_size(dmax[r]) +
                   f64_field_size(drecip[r]) + comp_size;

    int metric_size = 0;
    if (!name.empty())
      metric_size += 1 + varint_size(name.size()) + (int)name.size();
    for (std::string_view tag : tags)
      metric_size += 1 + varint_size(tag.size()) + (int)tag.size();
    if (kinds[r] != 0) metric_size += 1 + varint_size((uint64_t)kinds[r]);
    if (scopes[r] != 0) metric_size += 1 + varint_size((uint64_t)scopes[r]);
    metric_size += 1 + varint_size(digest_size) + digest_size;

    // --- emit ---
    out.push_back('\x0a');  // MetricBatch.metrics, field 1 LEN
    put_varint(&out, metric_size);
    if (!name.empty()) {
      out.push_back('\x0a');  // name field 1
      put_varint(&out, name.size());
      out.append(name);
    }
    for (std::string_view tag : tags) {
      out.push_back('\x12');  // tags field 2
      put_varint(&out, tag.size());
      out.append(tag);
    }
    if (kinds[r] != 0) {
      out.push_back('\x18');  // kind field 3
      put_varint(&out, (uint64_t)kinds[r]);
    }
    if (scopes[r] != 0) {
      out.push_back('\x20');  // scope field 4
      put_varint(&out, (uint64_t)scopes[r]);
    }
    out.push_back('\x3a');  // digest field 7
    put_varint(&out, digest_size);
    if (centroids_size > 0) {
      out.push_back('\x0a');  // centroids field 1
      put_varint(&out, centroids_size);
      int packed = 4 * n;
      out.push_back('\x0a');  // means field 1, packed
      put_varint(&out, packed);
      for (int j = 0; j < cap; ++j) {
        if (wrow[j] > 0.0f) {
          uint32_t bits;
          std::memcpy(&bits, &mrow[j], 4);
          out.push_back(static_cast<char>(bits & 0xFF));
          out.push_back(static_cast<char>((bits >> 8) & 0xFF));
          out.push_back(static_cast<char>((bits >> 16) & 0xFF));
          out.push_back(static_cast<char>((bits >> 24) & 0xFF));
        }
      }
      out.push_back('\x12');  // weights field 2, packed
      put_varint(&out, packed);
      for (int j = 0; j < cap; ++j) {
        if (wrow[j] > 0.0f) {
          uint32_t bits;
          std::memcpy(&bits, &wrow[j], 4);
          out.push_back(static_cast<char>(bits & 0xFF));
          out.push_back(static_cast<char>((bits >> 8) & 0xFF));
          out.push_back(static_cast<char>((bits >> 16) & 0xFF));
          out.push_back(static_cast<char>((bits >> 24) & 0xFF));
        }
      }
    }
    put_f64(&out, 2, dmin[r]);
    put_f64(&out, 3, dmax[r]);
    put_f64(&out, 4, drecip[r]);
    put_f64(&out, 5, compression);
  }
  *out_ptr = out.data();
  return static_cast<long long>(out.size());
}

void vn_ctx_reset(void* p) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> ctx_guard(ctx->mu);
  // every row stamped before now is stale: the directory itself stays
  if (++ctx->epoch == 0) {  // 2^32 resets: unstamp, start over
    for (Directory::Slot& slot : ctx->interned.slots) slot.epoch = 0;
    ctx->epoch = 1;
  }
  const size_t live_series =
      static_cast<size_t>(ctx->next_histo_row) + ctx->next_set_row +
      ctx->next_counter_row + ctx->next_gauge_row;
  note_stage_rows(ctx);
  ctx->next_histo_row = ctx->next_set_row = 0;
  ctx->next_counter_row = ctx->next_gauge_row = 0;
  // drop the staging plane wholesale: rows re-register next epoch and
  // the next plane comes zeroed (slot validity is gated on wts > 0, so
  // stale values must never survive a reset)
  if (ctx->stage != nullptr) shelve_plane(ctx->stage);
  ctx->stage = nullptr;
  ctx->h_rows.clear();
  ctx->h_vals.clear();
  ctx->h_wts.clear();
  ctx->c_rows.clear();
  ctx->c_contribs.clear();
  ctx->g_rows.clear();
  ctx->g_vals.clear();
  ctx->g_last.clear();
  ctx->s_rows.clear();
  ctx->s_idx.clear();
  ctx->s_rank.clear();
  // a first-seen record dropped here was never handed over, so its
  // series queues its strings again when it comes back
  ctx->new_series.clear();
  if (ctx->interned.used >= ctx->intern_cap ||
      ctx->interned.arena.size() >= (size_t{1} << 30)) {
    ctx->interned.reset(live_series);
    ctx->sid_handed.clear();
    ++ctx->intern_generation;
  }
  ctx->other_lines.clear();
  ctx->processed = 0;
  ctx->errors = 0;
  ctx->overload_dropped = 0;
  ctx->ssf_spans = 0;
  ctx->ssf_invalid = 0;
  ctx->ssf_services.clear();
  ctx->ssf_services_out.clear();
  ctx->ssf_fallback.clear();
  ctx->ssf_fallback_bytes = 0;
}

}  // extern "C"

namespace {

// One parsed line of a buffer, waiting for its commit. The views of `p`
// point into the caller's buffer, the joined tags lie in Batch::tags.
struct BatchLine {
  Parsed p;
  uint64_t key_hash = 0;
  uint32_t tags_at = 0;
  uint32_t tags_len = 0;
  uint32_t target = 0;   // index of the context that owns the series
  int32_t staged_row = -1;  // the probe stage's finding, for the next one
};

// A buffer's lines between their parse (no lock) and their commit (one
// lock hold a target context); one per calling thread, kept for its
// capacity.
struct Batch {
  std::vector<BatchLine> lines;  // the first n_lines are this buffer's
  size_t n_lines = 0;
  std::string tags;
  std::vector<std::string_view> others;  // events and service checks
  std::vector<uint32_t> order;  // line indices bucketed by target, in order
  std::vector<uint32_t> bucket_end;
  long long errors = 0;
};

// How many lines ahead of the commit each stage of commit_lines runs. A
// line's commit walks a chain of dependent misses on a stream shuffled
// over more series than any cache holds (slot -> key bytes and count[row]
// -> the row's next vals/wts slot); each stage issues the loads the
// next one will read, so the misses of a dozen lines overlap instead of
// queueing. Constants, not options: the chain is the code's, and the
// batch is however many lines the caller was handed. While the directory
// is small enough to sit in the cache (32,768 slots are 1 MiB, 24,576
// series at the most) there are no misses to overlap and the stages
// would cost a tenth of a line each for nothing, so they wait until it
// has outgrown that.
constexpr size_t kSlotAhead = 24;
constexpr size_t kKeyAhead = 12;
constexpr size_t kSampleAhead = 6;
constexpr size_t kCacheResidentSlots = size_t{1} << 15;

inline bool stages_samples(MetricKind k) {
  return k == KIND_HISTOGRAM || k == KIND_TIMER;
}

inline void prefetch_slot(const Ctx* ctx, const BatchLine& ln) {
  const std::vector<Directory::Slot>& slots = ctx->interned.slots;
  __builtin_prefetch(&slots[ln.key_hash & (slots.size() - 1)]);
}

// Reads the slot prefetch_slot asked for; asks for the key bytes the
// commit will compare and, for a timer that has its row already, the
// row's staged count. Only hints: the commit probes again.
inline void prefetch_key(const Ctx* ctx, BatchLine* ln) {
  const Directory& dir = ctx->interned;
  const size_t mask = dir.slots.size() - 1;
  size_t i = ln->key_hash & mask;
  ln->staged_row = -1;
  for (int probes = 0; probes < 4; ++probes, i = (i + 1) & mask) {
    const Directory::Slot& slot = dir.slots[i];
    if (slot.sid < 0) return;
    if (slot.key_hash != ln->key_hash) continue;
    const char* key = dir.arena.data() + slot.key_off;
    __builtin_prefetch(key);
    __builtin_prefetch(key + slot.key_len - 1);
    const Ctx::StagePlane* sp = ctx->stage;
    if (slot.epoch == ctx->epoch && sp != nullptr && slot.row < sp->rows &&
        stages_samples(ln->p.kind)) {
      ln->staged_row = slot.row;
      __builtin_prefetch(&sp->count[slot.row]);
    }
    return;
  }
}

// Reads the count prefetch_key asked for; asks for the slot the sample
// will be written to.
inline void prefetch_sample(const Ctx* ctx, const BatchLine& ln) {
  const Ctx::StagePlane* sp = ctx->stage;
  if (ln.staged_row < 0 || sp == nullptr || ln.staged_row >= sp->rows) return;
  const int32_t c = sp->count[ln.staged_row];
  if (c >= sp->depth) return;
  const size_t at = static_cast<size_t>(ln.staged_row) * sp->depth + c;
  __builtin_prefetch(&sp->vals[at], 1);
  __builtin_prefetch(&sp->wts[at], 1);
}

inline void commit_line(Ctx* ctx, const Batch& b, const BatchLine& ln) {
  commit_metric(ctx, ln.p, std::string_view(b.tags).substr(ln.tags_at,
                                                           ln.tags_len),
                ln.key_hash);
}

// Commit lines order[0..n) of the batch (or lines 0..n when `order` is
// null) into ctx, in that order. Caller holds ctx->mu.
void commit_lines(Ctx* ctx, Batch* b, const uint32_t* order, size_t n) {
  auto line = [b, order](size_t k) -> BatchLine& {
    return b->lines[order != nullptr ? order[k] : k];
  };
  if (ctx->interned.slots.size() <= kCacheResidentSlots) {
    for (size_t k = 0; k < n; ++k) commit_line(ctx, *b, line(k));
  } else {
    // step i: stage one looks at line i, the commit at line i - kSlotAhead
    // (size_t wraps below zero, so one compare bounds both ends)
    for (size_t i = 0; i < n + kSlotAhead; ++i) {
      if (i < n) prefetch_slot(ctx, line(i));
      if (i - (kSlotAhead - kKeyAhead) < n)
        prefetch_key(ctx, &line(i - (kSlotAhead - kKeyAhead)));
      if (i - (kSlotAhead - kSampleAhead) < n)
        prefetch_sample(ctx, line(i - (kSlotAhead - kSampleAhead)));
      if (i - kSlotAhead < n) commit_line(ctx, *b, line(i - kSlotAhead));
    }
  }
  ctx->processed += static_cast<long long>(n);
  ++ctx->commit_batches;
  ctx->commit_lines += static_cast<long long>(n);
}

// Ingest a buffer of newline-separated lines: every entry point that
// takes text (vn_ingest, vn_ingest_home, the datagram and stream
// readers) ends here, with whatever it was handed: one line, a datagram
// of forty, a 64 KiB chunk of two thousand. All lines are parsed first,
// with no lock held (thread-local scratch; tag sort/join is the
// expensive part of a line); then each target context (digest % nctx —
// the native twin of the reference's contention-free Digest%N worker
// routing, server.go:1028-1039) is locked once (the hold is timed:
// Ctx::lk_acquisitions) and takes its lines in buffer order, which is
// what a gauge's last write and first-seen rows need, since a series
// always maps to one context. Events/service checks
// and parse errors land on the caller's home shard so one noisy event
// stream can't serialize every reader behind shard 0. A line longer than
// max_line is a parse error. Returns the metric lines accepted;
// *lines_out (if given) gets the non-empty lines within max_line.
int ingest_buffer(Ctx* const* ctxs, int nctx, std::string_view data, int home,
                  size_t max_line, long long* lines_out) {
  thread_local Scratch sc;
  thread_local Batch batch;
  Batch& b = batch;
  b.n_lines = 0;
  b.tags.clear();
  b.others.clear();
  b.errors = 0;
  long long seen = 0;
  while (!data.empty()) {
    size_t nl = data.find('\n');
    std::string_view line =
        nl == std::string_view::npos ? data : data.substr(0, nl);
    data = nl == std::string_view::npos ? std::string_view()
                                        : data.substr(nl + 1);
    if (line.empty()) continue;
    if (line.size() > max_line) {
      ++b.errors;
      continue;
    }
    ++seen;
    if (line.substr(0, 3) == "_e{" || line.substr(0, 3) == "_sc") {
      b.others.push_back(line);
      continue;
    }
    // (a slot is written in full by a parse that succeeds, so the
    // slots of the last buffer are used again as they are)
    if (b.n_lines == b.lines.size()) b.lines.emplace_back();
    BatchLine& ln = b.lines[b.n_lines];
    if (!parse_line(&sc, line, &ln.p)) {
      ++b.errors;
      continue;
    }
    ++b.n_lines;
    ln.key_hash = series_key_hash(ln.p, sc.joined);
    ln.tags_at = static_cast<uint32_t>(b.tags.size());
    ln.tags_len = static_cast<uint32_t>(sc.joined.size());
    b.tags.append(sc.joined);
    ln.target = nctx > 1 ? ln.p.digest % static_cast<uint32_t>(nctx) : 0;
  }
  if (lines_out != nullptr) *lines_out = seen;

  // bucket the lines by target, keeping their order (a counting sort);
  // with one context the lines are their own order
  const size_t n = b.n_lines;
  const uint32_t* order = nullptr;
  b.bucket_end.assign(static_cast<size_t>(nctx), 0);
  if (nctx > 1) {
    for (size_t i = 0; i < n; ++i) ++b.bucket_end[b.lines[i].target];
    uint32_t at = 0;
    for (uint32_t& e : b.bucket_end) {
      uint32_t count = e;
      e = at;  // the bucket's start, advanced to its end below
      at += count;
    }
    b.order.resize(n);
    for (uint32_t i = 0; i < n; ++i)
      b.order[b.bucket_end[b.lines[i].target]++] = i;
    order = b.order.data();
  } else {
    b.bucket_end[0] = static_cast<uint32_t>(n);
  }

  const bool home_work = b.errors > 0 || !b.others.empty();
  uint32_t begin = 0;
  for (int t = 0; t < nctx; ++t) {
    const uint32_t end = b.bucket_end[t];
    const bool is_home = t == home && home_work;
    Ctx* target = ctxs[t];
    std::unique_lock<std::recursive_mutex> hold(target->mu, std::defer_lock);
    if (end > begin) {
      const int64_t t0 = now_ns();
      const bool contended = !hold.try_lock();
      if (contended) hold.lock();
      const int64_t t1 = now_ns();
      commit_lines(target, &b, order != nullptr ? order + begin : nullptr,
                   end - begin);
      const int64_t t2 = now_ns();
      const int64_t wait = contended ? t1 - t0 : 0;
      ++target->lk_acquisitions;
      if (contended) ++target->lk_contended;
      target->lk_wait_ns_total += wait;
      target->lk_hold_ns_total += t2 - t1;
      const int64_t slot = target->lk_ring_n % Ctx::kLockRing;
      target->lk_wait_ring[slot] = wait;
      target->lk_hold_ring[slot] = t2 - t1;
      ++target->lk_ring_n;
    }
    if (is_home) {
      if (!hold.owns_lock()) hold.lock();
      target->errors += b.errors;
      for (std::string_view other : b.others) {
        target->other_lines.append(other);
        target->other_lines.push_back('\n');
      }
    }
    begin = end;
  }
  return static_cast<int>(n);
}

}  // namespace

extern "C" {

// Ingest a datagram (possibly multiple newline-separated lines).
// Returns the number of metric lines accepted.
int vn_ingest(void* p, const char* buf, int len) {
  Ctx* ctx = static_cast<Ctx*>(p);
  return ingest_buffer(&ctx, 1, std::string_view(buf, static_cast<size_t>(len)),
                       0, std::string_view::npos, nullptr);
}

// Sharded ingest (ingest_buffer): multiple SO_REUSEPORT readers call
// this concurrently; ctypes drops the GIL, so parsing genuinely
// parallelizes. With nctx == 1 and home == 0 this is the shared-nothing
// per-reader commit path: every line commits into the caller's own ctx
// under a mutex nobody else touches on the line path.
int vn_ingest_home(void** ctxps, int nctx, const char* buf, int len,
                   int home) {
  return ingest_buffer(reinterpret_cast<Ctx**>(ctxps), nctx,
                       std::string_view(buf, static_cast<size_t>(len)), home,
                       std::string_view::npos, nullptr);
}

int vn_ingest_routed(void** ctxps, int nctx, const char* buf, int len) {
  return vn_ingest_home(ctxps, nctx, buf, len, 0);
}

// ---------------------------------------------------------------------------
// Native UDP reader: a C++ thread owning the recv loop — datagram to
// staged sample with no Python (and no GIL) anywhere on the path. The
// Python reference loop is Server._read_metric_socket (the reference's
// ReadMetricSocket, server.go:1123); this replaces it when
// tpu_native_readers is on. Stop leaves the fd OPEN so queued datagrams
// survive an fd-handoff restart, mirroring the quiesce semantics.

namespace {

struct Reader {
  std::thread th;
  std::atomic<bool> stop{false};
  std::atomic<long long> packets{0};
  int fd = -1;
  int max_len = 0;
  int home = 0;  // shard receiving this reader's events/errors
  std::vector<Ctx*> ctxs;
};

// One recv with the reader's clock around it: the time since the last
// recv returned is busy, the time inside this one is recv.
inline ssize_t timed_recv(Ctx* home, int64_t* t_back, int fd, char* buf,
                          size_t len) {
  int64_t t0 = now_ns();
  ssize_t n = recv(fd, buf, len, 0);
  int64_t t1 = now_ns();
  home->rd_busy_ns.fetch_add(t0 - *t_back, std::memory_order_relaxed);
  home->rd_recv_ns.fetch_add(t1 - t0, std::memory_order_relaxed);
  *t_back = t1;
  return n;
}

void reader_loop(Reader* r) {
  std::vector<char> buf(static_cast<size_t>(r->max_len) + 1);
  int64_t t_back = now_ns();
  while (!r->stop.load(std::memory_order_acquire)) {
    ssize_t n = timed_recv(r->ctxs[r->home], &t_back, r->fd, buf.data(),
                           buf.size());
    if (n < 0) {
      if (errno == EAGAIN || errno == EWOULDBLOCK || errno == EINTR)
        continue;  // SO_RCVTIMEO tick: poll the stop flag
      break;  // fd closed under us (shutdown)
    }
    if (n > r->max_len) {
      std::lock_guard<std::recursive_mutex> g(r->ctxs[r->home]->mu);
      ++r->ctxs[r->home]->errors;
    } else {
      ingest_buffer(r->ctxs.data(), static_cast<int>(r->ctxs.size()),
                    std::string_view(buf.data(), static_cast<size_t>(n)),
                    r->home, std::string_view::npos, nullptr);
    }
    // counted once it is in: who reads N packets finds N committed
    r->packets.fetch_add(1, std::memory_order_release);
  }
}

// SSF datagram reader: one unframed span per datagram, decoded +
// span->metric extracted in C++. Spans carrying STATUS samples buffer
// raw for the Python fallback (drained by the pump / epoch close).
struct SsfReader {
  std::thread th;
  std::atomic<bool> stop{false};
  std::atomic<long long> packets{0};
  int fd = -1;
  int max_len = 0;
  Ctx* ctx = nullptr;
  std::string ind, obj;
  double uniq_rate = 0.0;
};

void ssf_reader_loop(SsfReader* r) {
  std::vector<char> buf(static_cast<size_t>(r->max_len) + 1);
  while (!r->stop.load(std::memory_order_acquire)) {
    ssize_t n = recv(r->fd, buf.data(), buf.size(), 0);
    if (n < 0) {
      if (errno == EAGAIN || errno == EWOULDBLOCK || errno == EINTR)
        continue;
      break;
    }
    r->packets.fetch_add(1, std::memory_order_relaxed);
    std::lock_guard<std::recursive_mutex> g(r->ctx->mu);
    if (n == 0 || n > r->max_len) {
      ++r->ctx->errors;
      continue;
    }
    int rc = ingest_ssf_span(r->ctx, std::string_view(buf.data(), n),
                             r->ind, r->obj, r->uniq_rate);
    if (rc == 1) {
      // one accepted span = one processed unit, matching the Python
      // path's worker.ingest_ssf_packet accounting
      ++r->ctx->processed;
    } else if (rc == 0) {
      ++r->ctx->errors;
    } else if (rc == -1) {
      Ctx* c = r->ctx;
      if (c->ssf_fallback_bytes + n > Ctx::kSsfFallbackCap) {
        ++c->ssf_invalid;  // fallback buffer full: drop, visibly
      } else {
        c->ssf_fallback.emplace_back(buf.data(), n);
        c->ssf_fallback_bytes += n;
      }
    }
  }
}

}  // namespace

// Start a reader thread on an already-bound datagram fd. The fd is
// switched to blocking with a 500ms SO_RCVTIMEO so the stop flag is
// polled; ownership of the fd stays with the caller. Returns NULL if
// the timeout cannot be applied — a reader whose recv never times out
// could not be stopped, and would hang shutdown/handoff in join().
// home selects the shard that absorbs this reader's events/service
// checks and parse errors (vn_reader_start pins it to 0 for ABI
// compatibility). A reader given nctx == 1 owns its ctx outright: the
// shared-nothing per-reader commit shape.
void* vn_reader_start2(void** ctxps, int nctx, int fd, int max_len,
                       int home) {
  if (home < 0 || home >= nctx) return nullptr;
  int fl = fcntl(fd, F_GETFL);
  if (fl < 0) return nullptr;
  if ((fl & O_NONBLOCK) && fcntl(fd, F_SETFL, fl & ~O_NONBLOCK) < 0)
    return nullptr;
  struct timeval tv;
  tv.tv_sec = 0;
  tv.tv_usec = 500000;
  if (setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof tv) != 0)
    return nullptr;
  Reader* r = new Reader();
  r->fd = fd;
  r->max_len = max_len;
  r->home = home;
  for (int i = 0; i < nctx; ++i)
    r->ctxs.push_back(static_cast<Ctx*>(ctxps[i]));
  r->th = std::thread(reader_loop, r);
  return r;
}

void* vn_reader_start(void** ctxps, int nctx, int fd, int max_len) {
  return vn_reader_start2(ctxps, nctx, fd, max_len, 0);
}

long long vn_reader_packets(void* p) {
  return static_cast<Reader*>(p)->packets.load(std::memory_order_acquire);
}

// Stop and join the reader, then free it. Does NOT close the fd.
// Returns the FINAL packet count, read after the join — the thread
// keeps ingesting for up to one SO_RCVTIMEO tick after the stop flag
// is set, and a count snapshotted before the join would lose those.
long long vn_reader_stop(void* p) {
  Reader* r = static_cast<Reader*>(p);
  r->stop.store(true, std::memory_order_release);
  if (r->th.joinable()) r->th.join();
  long long final_count = r->packets.load(std::memory_order_relaxed);
  delete r;
  return final_count;
}

// Line-delimited TCP stream reader: one C++ thread per plain (non-TLS)
// statsd connection. Reassembles newline-split lines across reads and
// routes them like the datagram readers; an overlong partial line is
// dropped (counted) and the reader skips to the next newline. The
// reader OWNS the fd and closes it on exit — the Python side dup()s the
// accepted socket before handing it over.
void* vn_stream_reader_start(void** ctxps, int nctx, int fd, int max_len);
long long vn_stream_reader_stop(void* p);

namespace {

struct StreamReader {
  std::thread th;
  std::atomic<bool> stop{false};
  std::atomic<bool> finished{false};  // loop exited (peer closed/error)
  std::atomic<long long> lines{0};
  int fd = -1;
  int max_len = 0;
  int home = 0;  // shard receiving this reader's events/errors
  std::vector<Ctx*> ctxs;
};

// What a stream reader carries from one recv to the next: the partial
// last line, and whether it is inside an overlong line it dropped.
struct StreamCarry {
  std::string buf;
  bool skipping = false;  // inside an overlong line, waiting for \n
};

// One recv's bytes: every complete line goes to ingest_buffer at once,
// the partial last line is kept for the next recv.
void stream_feed(StreamReader* r, StreamCarry* st, const char* data,
                 size_t n) {
  std::string& buf = st->buf;
  const bool carried = !buf.empty();
  if (carried) {  // (else the lines are taken where recv put them)
    buf.append(data, n);
    data = buf.data();
    n = buf.size();
  }
  const char* last = static_cast<const char*>(memrchr(data, '\n', n));
  if (st->skipping && last == nullptr) return;  // still inside that line
  size_t done = 0;  // bytes up to and including the last newline
  if (last != nullptr) {
    done = static_cast<size_t>(last - data) + 1;
    size_t start = 0;
    if (st->skipping) {  // the tail of the overlong line dropped before
      start = static_cast<const char*>(memchr(data, '\n', n)) - data + 1;
      st->skipping = false;
    }
    long long lines = 0;
    ingest_buffer(r->ctxs.data(), static_cast<int>(r->ctxs.size()),
                  std::string_view(data + start, done - start), r->home,
                  static_cast<size_t>(r->max_len), &lines);
    r->lines.fetch_add(lines, std::memory_order_relaxed);
  }
  if (carried) {
    buf.erase(0, done);
  } else {
    buf.assign(data + done, n - done);
  }
  if (!st->skipping && buf.size() > static_cast<size_t>(r->max_len)) {
    // partial line already too long: drop it now (bounded memory;
    // the Python path buffers unboundedly here)
    std::lock_guard<std::recursive_mutex> g(r->ctxs[r->home]->mu);
    ++r->ctxs[r->home]->errors;
    buf.clear();
    st->skipping = true;
  }
}

void stream_reader_loop(StreamReader* r) {
  std::vector<char> chunk(64 << 10);
  StreamCarry carry;
  int64_t t_back = now_ns();
  while (!r->stop.load(std::memory_order_acquire)) {
    ssize_t n = timed_recv(r->ctxs[r->home], &t_back, r->fd, chunk.data(),
                           chunk.size());
    if (n < 0) {
      if (errno == EAGAIN || errno == EWOULDBLOCK || errno == EINTR)
        continue;  // SO_RCVTIMEO tick: poll the stop flag
      break;
    }
    if (n == 0) break;  // peer closed
    stream_feed(r, &carry, chunk.data(), static_cast<size_t>(n));
  }
  close(r->fd);
  r->finished.store(true, std::memory_order_release);
}

}  // namespace

// True once the reader's loop exited (peer closed / error): the handle
// should be reaped with vn_stream_reader_stop — an unjoined dead thread
// pins its stack for the process lifetime.
int vn_stream_reader_done(void* p) {
  return static_cast<StreamReader*>(p)->finished.load(
             std::memory_order_acquire)
             ? 1
             : 0;
}

void* vn_stream_reader_start2(void** ctxps, int nctx, int fd, int max_len,
                              int home) {
  if (home < 0 || home >= nctx) return nullptr;
  int fl = fcntl(fd, F_GETFL);
  if (fl < 0) return nullptr;
  if ((fl & O_NONBLOCK) && fcntl(fd, F_SETFL, fl & ~O_NONBLOCK) < 0)
    return nullptr;
  struct timeval tv;
  tv.tv_sec = 0;
  tv.tv_usec = 500000;
  if (setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof tv) != 0)
    return nullptr;
  StreamReader* r = new StreamReader();
  r->fd = fd;
  r->max_len = max_len;
  r->home = home;
  for (int i = 0; i < nctx; ++i)
    r->ctxs.push_back(static_cast<Ctx*>(ctxps[i]));
  r->th = std::thread(stream_reader_loop, r);
  return r;
}

void* vn_stream_reader_start(void** ctxps, int nctx, int fd, int max_len) {
  return vn_stream_reader_start2(ctxps, nctx, fd, max_len, 0);
}

// Join and free; returns lines ingested. The reader closes its fd.
long long vn_stream_reader_stop(void* p) {
  StreamReader* r = static_cast<StreamReader*>(p);
  r->stop.store(true, std::memory_order_release);
  if (r->th.joinable()) r->th.join();
  long long total = r->lines.load(std::memory_order_relaxed);
  delete r;
  return total;
}

// SSF variant of vn_reader_start: one unframed span per datagram on the
// fd, decoded and extracted in C++; STATUS spans buffer for the Python
// fallback (vn_drain_ssf_fallback). Same stop/timeout contract.
void* vn_ssf_reader_start(void* ctxp, int fd, int max_len,
                          const char* ind, int ind_len, const char* obj,
                          int obj_len, double uniq_rate) {
  int fl = fcntl(fd, F_GETFL);
  if (fl < 0) return nullptr;
  if ((fl & O_NONBLOCK) && fcntl(fd, F_SETFL, fl & ~O_NONBLOCK) < 0)
    return nullptr;
  struct timeval tv;
  tv.tv_sec = 0;
  tv.tv_usec = 500000;
  if (setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof tv) != 0)
    return nullptr;
  SsfReader* r = new SsfReader();
  r->fd = fd;
  r->max_len = max_len;
  r->ctx = static_cast<Ctx*>(ctxp);
  r->ind.assign(ind, static_cast<size_t>(ind_len));
  r->obj.assign(obj, static_cast<size_t>(obj_len));
  r->uniq_rate = uniq_rate;
  r->th = std::thread(ssf_reader_loop, r);
  return r;
}

long long vn_ssf_reader_stop(void* p) {
  SsfReader* r = static_cast<SsfReader*>(p);
  r->stop.store(true, std::memory_order_release);
  if (r->th.joinable()) r->th.join();
  long long final_count = r->packets.load(std::memory_order_relaxed);
  delete r;
  return final_count;
}

// Drain buffered Python-fallback SSF payloads as [u32 LE len][bytes]
// frames. Only whole frames are written; leftovers stay buffered.
int vn_drain_ssf_fallback(void* p, char* buf, int cap) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> ctx_guard(ctx->mu);
  int written = 0;
  size_t taken = 0;
  for (const std::string& pkt : ctx->ssf_fallback) {
    size_t need = 4 + pkt.size();
    if (cap < 0 || static_cast<size_t>(cap) - written < need) break;
    uint32_t len32 = static_cast<uint32_t>(pkt.size());
    std::memcpy(buf + written, &len32, 4);
    std::memcpy(buf + written + 4, pkt.data(), pkt.size());
    written += static_cast<int>(need);
    ++taken;
  }
  if (taken) {
    for (size_t i = 0; i < taken; ++i)
      ctx->ssf_fallback_bytes -= ctx->ssf_fallback[i].size();
    ctx->ssf_fallback.erase(ctx->ssf_fallback.begin(),
                            ctx->ssf_fallback.begin() + taken);
  }
  return written;
}

// Totals: [acquisitions, contended, wait_ns_total, hold_ns_total,
// ring_samples]. Ring samples (most recent min(ring_samples, 4096)
// waits/holds, ns) land in wait_out/hold_out when non-null; returns the
// number of ring entries written.
int vn_lock_stats(void* p, long long out[5], long long* wait_out,
                  long long* hold_out, int cap) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> g(ctx->mu);
  out[0] = ctx->lk_acquisitions;
  out[1] = ctx->lk_contended;
  out[2] = ctx->lk_wait_ns_total;
  out[3] = ctx->lk_hold_ns_total;
  int n = static_cast<int>(
      std::min<int64_t>(ctx->lk_ring_n, Ctx::kLockRing));
  out[4] = n;
  int wrote = 0;
  if (wait_out != nullptr && hold_out != nullptr) {
    wrote = std::min(n, cap);
    for (int i = 0; i < wrote; ++i) {
      wait_out[i] = ctx->lk_wait_ring[i];
      hold_out[i] = ctx->lk_hold_ring[i];
    }
  }
  return wrote;
}

void vn_lock_stats_reset(void* p) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> g(ctx->mu);
  ctx->lk_acquisitions = 0;
  ctx->lk_contended = 0;
  ctx->lk_wait_ns_total = 0;
  ctx->lk_hold_ns_total = 0;
  ctx->lk_ring_n = 0;
}

static int locked_size(void* p, const std::vector<int32_t> Ctx::* field) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> g(ctx->mu);
  return static_cast<int>((ctx->*field).size());
}

static int locked_i32(void* p, int32_t Ctx::* field) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> g(ctx->mu);
  return ctx->*field;
}

int vn_pending_histo(void* p) { return locked_size(p, &Ctx::h_rows); }
int vn_pending_set(void* p) { return locked_size(p, &Ctx::s_rows); }
int vn_pending_counter(void* p) { return locked_size(p, &Ctx::c_rows); }
int vn_pending_gauge(void* p) { return locked_size(p, &Ctx::g_rows); }
int vn_num_histo_rows(void* p) { return locked_i32(p, &Ctx::next_histo_row); }
int vn_num_set_rows(void* p) { return locked_i32(p, &Ctx::next_set_row); }
int vn_num_counter_rows(void* p) {
  return locked_i32(p, &Ctx::next_counter_row);
}
int vn_num_gauge_rows(void* p) { return locked_i32(p, &Ctx::next_gauge_row); }
long long vn_processed(void* p) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> g(ctx->mu);
  return ctx->processed;
}
long long vn_errors(void* p) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> g(ctx->mu);
  return ctx->errors;
}

long long vn_overload_dropped(void* p) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> g(ctx->mu);
  return ctx->overload_dropped;
}

// out[0] = ns the readers homed here spent inside recv, out[1] = ns
// outside it; lifetime, lock-free.
void vn_reader_ns(void* p, long long* out) {
  Ctx* ctx = static_cast<Ctx*>(p);
  out[0] = ctx->rd_recv_ns.load(std::memory_order_relaxed);
  out[1] = ctx->rd_busy_ns.load(std::memory_order_relaxed);
}

// What the commit path met, lifetime (Ctx::dir_hits and the seven after
// it, in that order): out[0..7] = dir_hits, dir_restamped,
// dir_first_seen, commit_batches, commit_lines, plane_grows,
// histo_staged, histo_spilled.
void vn_commit_counters(void* p, long long* out) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> g(ctx->mu);
  out[0] = ctx->dir_hits;
  out[1] = ctx->dir_restamped;
  out[2] = ctx->dir_first_seen;
  out[3] = ctx->commit_batches;
  out[4] = ctx->commit_lines;
  out[5] = ctx->plane_grows;
  out[6] = ctx->histo_staged;
  out[7] = ctx->histo_spilled;
}

void vn_set_spill_cap(void* p, long long cap) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> g(ctx->mu);
  if (cap > 0) ctx->spill_cap = static_cast<size_t>(cap);
  // A raised cap lets g_rows resume push_back, so the onset-built
  // last-write index no longer covers the batch tail; clear it so the
  // next overload onset rebuilds it over the full batch (a stale entry
  // would update an older-positioned duplicate, losing LWW at drain).
  ctx->g_last.clear();
}

int vn_drain_histo(void* p, int32_t* rows, float* vals, float* wts, int cap) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> ctx_guard(ctx->mu);
  int n = std::min<int>(cap, static_cast<int>(ctx->h_rows.size()));
  std::memcpy(rows, ctx->h_rows.data(), n * sizeof(int32_t));
  std::memcpy(vals, ctx->h_vals.data(), n * sizeof(float));
  std::memcpy(wts, ctx->h_wts.data(), n * sizeof(float));
  ctx->h_rows.erase(ctx->h_rows.begin(), ctx->h_rows.begin() + n);
  ctx->h_vals.erase(ctx->h_vals.begin(), ctx->h_vals.begin() + n);
  ctx->h_wts.erase(ctx->h_wts.begin(), ctx->h_wts.begin() + n);
  return n;
}

int vn_drain_set(void* p, int32_t* rows, int32_t* idx, int8_t* rank,
                 int cap) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> ctx_guard(ctx->mu);
  int n = std::min<int>(cap, static_cast<int>(ctx->s_rows.size()));
  std::memcpy(rows, ctx->s_rows.data(), n * sizeof(int32_t));
  std::memcpy(idx, ctx->s_idx.data(), n * sizeof(int32_t));
  std::memcpy(rank, ctx->s_rank.data(), n * sizeof(int8_t));
  ctx->s_rows.erase(ctx->s_rows.begin(), ctx->s_rows.begin() + n);
  ctx->s_idx.erase(ctx->s_idx.begin(), ctx->s_idx.begin() + n);
  ctx->s_rank.erase(ctx->s_rank.begin(), ctx->s_rank.begin() + n);
  return n;
}

int vn_drain_counter(void* p, int32_t* rows, double* contribs, int cap) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> ctx_guard(ctx->mu);
  int n = std::min<int>(cap, static_cast<int>(ctx->c_rows.size()));
  std::memcpy(rows, ctx->c_rows.data(), n * sizeof(int32_t));
  std::memcpy(contribs, ctx->c_contribs.data(), n * sizeof(double));
  ctx->c_rows.erase(ctx->c_rows.begin(), ctx->c_rows.begin() + n);
  ctx->c_contribs.erase(ctx->c_contribs.begin(),
                        ctx->c_contribs.begin() + n);
  return n;
}

int vn_drain_gauge(void* p, int32_t* rows, double* vals, int cap) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> ctx_guard(ctx->mu);
  int n = std::min<int>(cap, static_cast<int>(ctx->g_rows.size()));
  std::memcpy(rows, ctx->g_rows.data(), n * sizeof(int32_t));
  std::memcpy(vals, ctx->g_vals.data(), n * sizeof(double));
  ctx->g_rows.erase(ctx->g_rows.begin(), ctx->g_rows.begin() + n);
  ctx->g_vals.erase(ctx->g_vals.begin(), ctx->g_vals.begin() + n);
  ctx->g_last.clear();  // indices into the batch are invalid after erase
  return n;
}

// Cheap emptiness probe so Python-side upsert loops (the global tier
// imports one series at a time) can skip the buffer-allocating drain
// when nothing is pending.
int vn_pending_new_series(void* p) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> ctx_guard(ctx->mu);
  return static_cast<int>(ctx->new_series.size());
}

// Drain every pending new-series record in one call. The queue is
// swapped out whole and the caller is handed pointers into it:
// pools/rows/sids for all n records, and for the *n_first of them whose
// strings Python has not been given yet, their positions in those
// arrays, kinds, scope classes and packed "name\x1fjoined_tags\x1e"
// records. The records come grouped by pool (0, 1, 2, 3), each pool's in
// the order its rows were handed out, so a pool's series are one slice
// with consecutive rows and the caller sorts nothing.
// The pointers stay valid until the next drain of this context; one
// thread drains a context at a time (NativeIngest.drain_new_series
// sees to that; this lock is held for the swap and the grouping only).
// *generation changes when the intern table was dropped: every sid the
// caller holds is then void.
int vn_drain_new_series(void* p, const int32_t** pools, const int32_t** rows,
                        const int32_t** sids, const int32_t** first_at,
                        const int32_t** first_kinds,
                        const int32_t** first_scopes, int* n_first,
                        const char** strs, long long* strs_len,
                        unsigned* generation) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> ctx_guard(ctx->mu);
  NewSeriesQueue& q = ctx->drained_series;
  q.clear();
  std::swap(q, ctx->new_series);
  for (int32_t at : q.first_at) ctx->sid_handed[q.sids[at]] = 1;
  q.group_by_pool();
  *pools = q.pools.data();
  *rows = q.rows.data();
  *sids = q.sids.data();
  *first_at = q.first_at.data();
  *first_kinds = q.first_kinds.data();
  *first_scopes = q.first_scopes.data();
  *n_first = static_cast<int>(q.first_at.size());
  *strs = q.strs.data();
  *strs_len = static_cast<long long>(q.strs.size());
  *generation = ctx->intern_generation;
  return static_cast<int>(q.size());
}

// Entries the intern table holds before a reset drops it (4,000,000;
// the tests lower it to reach the bound).
void vn_set_intern_cap(void* p, long long cap) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> ctx_guard(ctx->mu);
  ctx->intern_cap = cap > 0 ? static_cast<size_t>(cap) : 1;
}

// Directory upsert for the Python-side ingest paths (SSF-derived metrics,
// imports): returns the row id, assigning a new one when the series is
// unseen this epoch. kind: MetricKind; scope_class: ScopeClass. The new
// series is recorded for vn_drain_new_series like any parsed one.
int vn_upsert(void* p, const char* name, int name_len, int kind,
              const char* joined_tags, int tags_len, int scope_class) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> ctx_guard(ctx->mu);
  return upsert_series(
      ctx, std::string_view(name, static_cast<size_t>(name_len)), kind,
      std::string_view(joined_tags, static_cast<size_t>(tags_len)),
      scope_class);
}

// ---------------------------------------------------------------------------
// Forward-batch wire decoder + batched directory upsert: the import
// side of the native forward path. A global veneur receiving 1M
// forwarded digests spent ~50s/flush building Python protobuf objects
// and upserting per metric; the decoder parses the MetricBatch wire
// into SoA buffers (one C call), and vn_upsert_many assigns directory
// rows for a whole chunk under one lock hold.

namespace {

struct Decoded {
  std::string meta;  // per metric: name \x1f joined_tags, recs \x1e-joined
  std::vector<uint8_t> kinds;       // pb MetricKind enum (== native kinds)
  std::vector<uint8_t> scopes;      // pb Scope enum (== ScopeClass)
  std::vector<uint8_t> value_kind;  // 0 none, 1 counter, 2 gauge,
                                    // 3 digest, 4 hll
  std::vector<uint32_t> digests;    // worker-routing digest
  std::vector<double> scalars;      // counter/gauge value
  std::vector<double> dmin, dmax, drecip, compression;
  std::vector<long long> cent_off;  // [n+1]
  std::vector<float> cent_means, cent_weights;
  std::vector<long long> hll_off;  // [n+1]
  std::string hll_bytes;
  std::vector<int32_t> hll_precision;
  // byte range of each metric's length-prefixed record in the source
  // buffer (tag byte through body end): lets a proxy ring-split a batch
  // by slicing the original bytes, no re-encode (protobuf repeated
  // records concatenate)
  std::vector<long long> rec_off, rec_len;
  // consistent-ring key hash: fmix64(fnv1a64(name + type + joined)) —
  // the proxy's per-metric placement hash, computed here so the Python
  // tier never hashes per metric (distributed/ring.owners_for_hashes)
  std::vector<uint64_t> ring_hash;

  void clear() {
    meta.clear();
    kinds.clear();
    scopes.clear();
    value_kind.clear();
    digests.clear();
    scalars.clear();
    dmin.clear();
    dmax.clear();
    drecip.clear();
    compression.clear();
    cent_off.assign(1, 0);
    cent_means.clear();
    cent_weights.clear();
    hll_off.assign(1, 0);
    hll_bytes.clear();
    hll_precision.clear();
    rec_off.clear();
    rec_len.clear();
    ring_hash.clear();
  }
};

struct WireCursor {
  const uint8_t* p;
  const uint8_t* end;

  bool varint(uint64_t* out) {
    uint64_t v = 0;
    int shift = 0;
    while (p < end && shift < 64) {
      uint8_t b = *p++;
      // 10th byte holds bits 63..69 of which only bit 63 exists in a
      // uint64: any higher bit (or a continuation bit demanding an
      // 11th byte) is an overflow every spec parser rejects — silently
      // truncating here made the decoder accept what peers refuse
      if (shift == 63 && (b & 0xFE)) return false;
      v |= static_cast<uint64_t>(b & 0x7F) << shift;
      if (!(b & 0x80)) {
        *out = v;
        return true;
      }
      shift += 7;
    }
    return false;
  }

  // TAG varints cap at 5 bytes (uint32 wire grammar); see
  // ProtoReader::tag_varint
  bool tag_varint(uint64_t* out) {
    uint64_t v = 0;
    int shift = 0;
    while (p < end && shift < 35) {
      uint8_t b = *p++;
      if (shift == 28 && (b & 0xF0)) return false;
      v |= static_cast<uint64_t>(b & 0x7F) << shift;
      if (!(b & 0x80)) {
        *out = v;
        return true;
      }
      shift += 7;
    }
    return false;
  }

  bool skip(uint32_t wire_type) {
    uint64_t tmp;
    switch (wire_type) {
      case 0:
        return varint(&tmp);
      case 1:
        if (end - p < 8) return false;
        p += 8;
        return true;
      case 2: {
        if (!varint(&tmp) || tmp > static_cast<uint64_t>(end - p))
          return false;
        p += tmp;
        return true;
      }
      case 5:
        if (end - p < 4) return false;
        p += 4;
        return true;
      default:
        return false;  // groups unsupported
    }
  }

  bool len_view(std::string_view* out) {
    uint64_t n;
    if (!varint(&n) || n > static_cast<uint64_t>(end - p)) return false;
    *out = std::string_view(reinterpret_cast<const char*>(p),
                            static_cast<size_t>(n));
    p += n;
    return true;
  }

  bool f64(double* out) {
    if (end - p < 8) return false;
    std::memcpy(out, p, 8);
    p += 8;
    return true;
  }
};

bool decode_packed_floats(std::string_view payload, std::vector<float>* out) {
  if (payload.size() % 4 != 0) return false;
  size_t n = payload.size() / 4;
  size_t base = out->size();
  out->resize(base + n);
  std::memcpy(out->data() + base, payload.data(), payload.size());
  return true;
}

bool decode_centroids(std::string_view body, std::vector<float>* means,
                      std::vector<float>* weights) {
  WireCursor c{reinterpret_cast<const uint8_t*>(body.data()),
               reinterpret_cast<const uint8_t*>(body.data() + body.size())};
  while (c.p < c.end) {
    uint64_t tag;
    if (!c.tag_varint(&tag)) return false;
    uint32_t field = static_cast<uint32_t>(tag >> 3);
    uint32_t wt = static_cast<uint32_t>(tag & 7);
    if (field == 0) return false;  // protobuf forbids field number 0
    if (field == 1 || field == 2) {
      std::vector<float>* dst = field == 1 ? means : weights;
      if (wt == 2) {  // packed
        std::string_view payload;
        if (!c.len_view(&payload) || !decode_packed_floats(payload, dst))
          return false;
      } else if (wt == 5) {  // unpacked single
        if (c.end - c.p < 4) return false;
        float v;
        std::memcpy(&v, c.p, 4);
        c.p += 4;
        dst->push_back(v);
      } else {
        return false;
      }
    } else if (!c.skip(wt)) {
      return false;
    }
  }
  return true;
}

void sanitize_seps(std::string* s) {
  for (char& ch : *s)
    if (ch == '\x1e' || ch == '\x1f') ch = '_';
}

// protobuf rejects `string` fields that aren't valid UTF-8; the native
// decoder must agree (strictness parity with the Python fallback —
// pinned by the decoder fuzz test)
// one Metric submessage → appended SoA entry; false on malformed
bool decode_metric(std::string_view body, Decoded* d) {
  WireCursor c{reinterpret_cast<const uint8_t*>(body.data()),
               reinterpret_cast<const uint8_t*>(body.data() + body.size())};
  std::string name;
  std::string joined;
  uint64_t kind = 0, scope = 0;
  uint8_t vkind = 0;
  double scalar = 0, mn = 0, mx = 0, rc = 0, comp = 0;
  size_t cent_means_base = d->cent_means.size();
  size_t cent_w_base = d->cent_weights.size();
  int32_t precision = 0;
  while (c.p < c.end) {
    uint64_t tag;
    if (!c.tag_varint(&tag)) return false;
    uint32_t field = static_cast<uint32_t>(tag >> 3);
    uint32_t wt = static_cast<uint32_t>(tag & 7);
    if (field == 0) return false;  // protobuf forbids field number 0
    switch (field) {
      case 1: {  // name (proto3 string: must be valid UTF-8)
        std::string_view v;
        if (wt != 2 || !c.len_view(&v) || !valid_utf8(v)) return false;
        name.assign(v);
        break;
      }
      case 2: {  // tags (repeated proto3 string)
        std::string_view v;
        if (wt != 2 || !c.len_view(&v) || !valid_utf8(v)) return false;
        if (!joined.empty()) joined.push_back(',');
        joined.append(v);
        break;
      }
      case 3:
        if (wt != 0 || !c.varint(&kind)) return false;
        break;
      case 4:
        if (wt != 0 || !c.varint(&scope)) return false;
        break;
      case 5: {  // counter { sfixed64 value = 1 }
        std::string_view v;
        if (wt != 2 || !c.len_view(&v)) return false;
        vkind = 1;
        WireCursor ic{reinterpret_cast<const uint8_t*>(v.data()),
                      reinterpret_cast<const uint8_t*>(v.data() + v.size())};
        while (ic.p < ic.end) {
          uint64_t it;
          if (!ic.tag_varint(&it)) return false;
          if ((it >> 3) == 0) return false;
          if ((it >> 3) == 1 && (it & 7) == 1) {
            int64_t sv;
            if (ic.end - ic.p < 8) return false;
            std::memcpy(&sv, ic.p, 8);
            ic.p += 8;
            scalar = static_cast<double>(sv);
          } else if (!ic.skip(static_cast<uint32_t>(it & 7))) {
            return false;
          }
        }
        break;
      }
      case 6: {  // gauge { double value = 1 }
        std::string_view v;
        if (wt != 2 || !c.len_view(&v)) return false;
        vkind = 2;
        WireCursor ic{reinterpret_cast<const uint8_t*>(v.data()),
                      reinterpret_cast<const uint8_t*>(v.data() + v.size())};
        while (ic.p < ic.end) {
          uint64_t it;
          if (!ic.tag_varint(&it)) return false;
          if ((it >> 3) == 0) return false;
          if ((it >> 3) == 1 && (it & 7) == 1) {
            if (!ic.f64(&scalar)) return false;
          } else if (!ic.skip(static_cast<uint32_t>(it & 7))) {
            return false;
          }
        }
        break;
      }
      case 7: {  // digest
        std::string_view v;
        if (wt != 2 || !c.len_view(&v)) return false;
        vkind = 3;
        WireCursor ic{reinterpret_cast<const uint8_t*>(v.data()),
                      reinterpret_cast<const uint8_t*>(v.data() + v.size())};
        while (ic.p < ic.end) {
          uint64_t it;
          if (!ic.tag_varint(&it)) return false;
          if ((it >> 3) == 0) return false;
          uint32_t f = static_cast<uint32_t>(it >> 3);
          uint32_t w = static_cast<uint32_t>(it & 7);
          if (f == 1 && w == 2) {
            std::string_view cb;
            if (!ic.len_view(&cb) ||
                !decode_centroids(cb, &d->cent_means, &d->cent_weights))
              return false;
          } else if (f >= 2 && f <= 5 && w == 1) {
            double dv;
            if (!ic.f64(&dv)) return false;
            if (f == 2) mn = dv;
            else if (f == 3) mx = dv;
            else if (f == 4) rc = dv;
            else comp = dv;
          } else if (!ic.skip(w)) {
            return false;
          }
        }
        break;
      }
      case 8: {  // hll
        std::string_view v;
        if (wt != 2 || !c.len_view(&v)) return false;
        vkind = 4;
        WireCursor ic{reinterpret_cast<const uint8_t*>(v.data()),
                      reinterpret_cast<const uint8_t*>(v.data() + v.size())};
        while (ic.p < ic.end) {
          uint64_t it;
          if (!ic.tag_varint(&it)) return false;
          if ((it >> 3) == 0) return false;
          uint32_t f = static_cast<uint32_t>(it >> 3);
          uint32_t w = static_cast<uint32_t>(it & 7);
          if (f == 1 && w == 2) {
            std::string_view rb;
            if (!ic.len_view(&rb)) return false;
            d->hll_bytes.append(rb);
          } else if (f == 2 && w == 0) {
            uint64_t pv;
            if (!ic.varint(&pv)) return false;
            precision = static_cast<int32_t>(pv);
          } else if (!ic.skip(w)) {
            return false;
          }
        }
        break;
      }
      default:
        if (!c.skip(wt)) return false;
    }
  }
  if (kind > 4 || scope > 2) return false;
  // centroid means/weights must pair up
  if (d->cent_means.size() - cent_means_base !=
      d->cent_weights.size() - cent_w_base)
    return false;
  sanitize_seps(&name);
  sanitize_seps(&joined);
  const char* type_str = kind_type_string(static_cast<MetricKind>(kind));
  uint32_t digest = fnv1a32(name);
  digest = fnv1a32(type_str, digest);
  digest = fnv1a32(joined, digest);
  uint64_t rh = fnv1a64_continue(name, kFnv64Offset);
  rh = fnv1a64_continue(type_str, rh);
  rh = fmix64(fnv1a64_continue(joined, rh));
  d->ring_hash.push_back(rh);

  if (!d->meta.empty()) d->meta.push_back('\x1e');
  d->meta.append(name);
  d->meta.push_back('\x1f');
  d->meta.append(joined);
  d->kinds.push_back(static_cast<uint8_t>(kind));
  d->scopes.push_back(static_cast<uint8_t>(scope));
  d->value_kind.push_back(vkind);
  d->digests.push_back(digest);
  d->scalars.push_back(scalar);
  d->dmin.push_back(mn);
  d->dmax.push_back(mx);
  d->drecip.push_back(rc);
  d->compression.push_back(comp);
  d->cent_off.push_back(static_cast<long long>(d->cent_means.size()));
  d->hll_off.push_back(static_cast<long long>(d->hll_bytes.size()));
  d->hll_precision.push_back(precision);
  return true;
}

thread_local Decoded g_decoded;

}  // namespace

// Decode a serialized veneurtpu.MetricBatch into SoA views. The views
// live in thread-local storage: valid until the calling thread's next
// decode. Returns the metric count, or -1 on malformed input.
long long vn_decode_metric_batch(
    const char* buf, long long len, const char** meta,
    long long* meta_len, const uint8_t** kinds, const uint8_t** scopes,
    const uint8_t** value_kind, const uint32_t** digests,
    const double** scalars, const double** dmin, const double** dmax,
    const double** drecip, const double** compression,
    const long long** cent_off, const float** cent_means,
    const float** cent_weights, const long long** hll_off,
    const char** hll_bytes, const int32_t** hll_precision,
    const long long** rec_off, const long long** rec_len,
    const uint64_t** ring_hash) {
  Decoded& d = g_decoded;
  d.clear();
  WireCursor c{reinterpret_cast<const uint8_t*>(buf),
               reinterpret_cast<const uint8_t*>(buf + len)};
  const uint8_t* base = reinterpret_cast<const uint8_t*>(buf);
  while (c.p < c.end) {
    const uint8_t* tag_start = c.p;
    uint64_t tag;
    if (!c.tag_varint(&tag)) return -1;
    uint32_t field = static_cast<uint32_t>(tag >> 3);
    uint32_t wt = static_cast<uint32_t>(tag & 7);
    if (field == 0) return -1;  // protobuf forbids field number 0
    if (field == 1 && wt == 2) {
      std::string_view body;
      if (!c.len_view(&body) || !decode_metric(body, &d)) return -1;
      d.rec_off.push_back(static_cast<long long>(tag_start - base));
      d.rec_len.push_back(static_cast<long long>(c.p - tag_start));
    } else if (!c.skip(wt)) {
      return -1;
    }
  }
  *meta = d.meta.data();
  *meta_len = static_cast<long long>(d.meta.size());
  *kinds = d.kinds.data();
  *scopes = d.scopes.data();
  *value_kind = d.value_kind.data();
  *digests = d.digests.data();
  *scalars = d.scalars.data();
  *dmin = d.dmin.data();
  *dmax = d.dmax.data();
  *drecip = d.drecip.data();
  *compression = d.compression.data();
  *cent_off = d.cent_off.data();
  *cent_means = d.cent_means.data();
  *cent_weights = d.cent_weights.data();
  *hll_off = d.hll_off.data();
  *hll_bytes = d.hll_bytes.data();
  *hll_precision = d.hll_precision.data();
  *rec_off = d.rec_off.data();
  *rec_len = d.rec_len.data();
  *ring_hash = d.ring_hash.data();
  return static_cast<long long>(d.kinds.size());
}

// Batch directory upsert: one lock hold for a whole import chunk.
// meta is the \x1e/\x1f-framed record blob (one record per metric, in
// order); sel[i] != 0 selects the metrics owned by this context's
// worker; out_rows[i] = assigned row, or -1 where unselected/invalid.
// Returns the number of selected upserts.
long long vn_upsert_many(void* p, const char* meta, long long meta_len,
                         const uint8_t* kinds, const uint8_t* scopes,
                         const uint8_t* sel, long long n,
                         int32_t* out_rows) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> ctx_guard(ctx->mu);
  std::string_view blob(meta, static_cast<size_t>(meta_len));
  size_t mpos = 0;
  long long done = 0;
  for (long long i = 0; i < n; ++i) {
    size_t rec_end = blob.find('\x1e', mpos);
    if (rec_end == std::string_view::npos) rec_end = blob.size();
    std::string_view rec = blob.substr(mpos, rec_end - mpos);
    mpos = rec_end + 1;
    if (!sel[i]) {
      out_rows[i] = -1;
      continue;
    }
    size_t nend = rec.find('\x1f');
    std::string_view name =
        nend == std::string_view::npos ? rec : rec.substr(0, nend);
    std::string_view joined =
        nend == std::string_view::npos ? std::string_view()
                                       : rec.substr(nend + 1);
    out_rows[i] = upsert_series(ctx, name, kinds[i], joined, scopes[i]);
    ++done;
  }
  return done;
}

// ---------------------------------------------------------------------------
// Columnar emit serializers (vn_encode_datadog_series, the statsd line
// emitters, vn_encode_signalfx_body, exposition text, deflate) live in
// emit.cpp — the emit tier of the library (built into the same .so).

// SSF span fast path. Returns 1 ok, 0 decode error, -1 fallback needed
// (span carries STATUS samples; nothing was ingested).
int vn_ingest_ssf(void* p, const char* buf, int len, const char* ind_name,
                  int ind_len, const char* obj_name, int obj_len,
                  double uniq_rate) {
  return ingest_ssf_span(
      static_cast<Ctx*>(p), std::string_view(buf, len),
      std::string_view(ind_name, ind_len), std::string_view(obj_name, obj_len),
      uniq_rate);
}

// Batched SSF ingest: buf holds frames of [u32 LE length][span bytes].
// Returns the number of spans ingested; decode errors are counted in
// *errors_out, spans needing the Python fallback are APPENDED to
// fallback_off/fallback_len (caller-provided arrays of capacity
// fallback_cap; pass 0 to count-as-error instead) as offsets into buf,
// with the appended count written to *nfall_out.
int vn_ingest_ssf_many(void* p, const char* buf, long long len,
                       const char* ind_name, int ind_len,
                       const char* obj_name, int obj_len, double uniq_rate,
                       int* errors_out, int* fallback_off,
                       int* fallback_len, int fallback_cap,
                       int* nfall_out) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> ctx_guard(ctx->mu);
  std::string_view ind(ind_name, ind_len), obj(obj_name, obj_len);
  long long pos = 0;
  int ok = 0, errs = 0, nfall = 0;
  while (pos + 4 <= len) {
    uint32_t flen;
    std::memcpy(&flen, buf + pos, 4);
    pos += 4;
    if (flen > static_cast<uint64_t>(len - pos)) {
      ++errs;
      break;
    }
    if (flen == 0) {
      // empty datagram: proto3 decodes it as an all-default span, which
      // would count as processed; match the single-packet path's
      // empty-packet parse error (server.py handle_trace_packet)
      ++errs;
      continue;
    }
    int rc = ingest_ssf_span(ctx, std::string_view(buf + pos, flen), ind,
                             obj, uniq_rate);
    if (rc == 1) {
      ++ok;
    } else if (rc == 0) {
      ++errs;
    } else if (nfall < fallback_cap) {
      fallback_off[nfall] = static_cast<int>(pos);
      fallback_len[nfall] = static_cast<int>(flen);
      ++nfall;
    } else {
      ++errs;  // fallback list full; count as error rather than drop silently
    }
    pos += flen;
  }
  *errors_out = errs;
  *nfall_out = nfall;
  return ok;
}

long long vn_ssf_spans(void* p) { return static_cast<Ctx*>(p)->ssf_spans; }
long long vn_ssf_invalid(void* p) {
  return static_cast<Ctx*>(p)->ssf_invalid;
}

// Drain the per-service span counters as "service\tcount\n" lines.
// Output beyond cap stays buffered for the next call (like
// vn_drain_other) — truncating after clearing would lose counts and
// could hand Python a cut mid-line.
//
// CAP CONTRACT: cap must be >= one full line (service names are
// truncated to 256 bytes at ingest, so 256 + 1 tab + 20 digit count +
// newline = 278; callers must pass cap >= 512). With a smaller cap a
// line that doesn't fit returns 0 while data stays buffered, and a
// `while n > 0` drain loop would stall until the next flush.
int vn_drain_ssf_services(void* p, char* buf, int cap) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> ctx_guard(ctx->mu);
  for (const auto& e : ctx->ssf_services) {
    ctx->ssf_services_out.append(e.first);
    ctx->ssf_services_out.push_back('\t');
    ctx->ssf_services_out.append(std::to_string(e.second));
    ctx->ssf_services_out.push_back('\n');
  }
  ctx->ssf_services.clear();
  // cut on a line boundary so the consumer never sees a partial record
  // (cap clamped: a negative cap must not become a huge memcpy size)
  size_t n = cap < 0 ? 0
                     : std::min(static_cast<size_t>(cap),
                                ctx->ssf_services_out.size());
  while (n > 0 && ctx->ssf_services_out[n - 1] != '\n') --n;
  std::memcpy(buf, ctx->ssf_services_out.data(), n);
  ctx->ssf_services_out.erase(0, n);
  return static_cast<int>(n);
}

// Drain the buffered event/service-check lines (newline separated).
// Cuts on a line boundary like vn_drain_ssf_services so a full buffer
// never severs a record across two drains.
//
// CAP CONTRACT: cap should be >= metric_max_length + 1 (events are
// length-capped at ingest); an oversize first record is dropped whole,
// counted in vn_errors, and the drain continues with the records
// behind it — so a `while n > 0` loop never stalls on one bad record.
int vn_drain_other(void* p, char* buf, int cap) {
  Ctx* ctx = static_cast<Ctx*>(p);
  std::lock_guard<std::recursive_mutex> ctx_guard(ctx->mu);
  size_t n;
  for (;;) {
    n = cap < 0 ? 0
                : std::min(static_cast<size_t>(cap), ctx->other_lines.size());
    while (n > 0 && ctx->other_lines[n - 1] != '\n') --n;
    if (n == 0 && cap > 0 && !ctx->other_lines.empty()) {
      // degenerate: first record alone exceeds the caller's buffer — drop
      // it whole (counted as an error so the loss is observable) and
      // retry, so complete records queued behind it still drain this call
      // rather than emitting a severed fragment the consumer would
      // misparse as two records
      size_t nl = ctx->other_lines.find('\n');
      ctx->other_lines.erase(
          0, nl == std::string::npos ? ctx->other_lines.size() : nl + 1);
      ++ctx->errors;
      continue;
    }
    break;
  }
  std::memcpy(buf, ctx->other_lines.data(), n);
  ctx->other_lines.erase(0, n);
  return static_cast<int>(n);
}

}  // extern "C"

"""ctypes binding for the native ingest pipeline (native/dogstatsd.cpp).

Builds the shared library on first use if the toolchain is available;
callers fall back to the pure-Python parser when it isn't.
"""

from __future__ import annotations

import bisect
import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from dataclasses import dataclass
from typing import Optional

import numpy as np

log = logging.getLogger("veneur_tpu.native")

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libveneur_native.so")

_LOADGEN_PATH = os.path.join(_NATIVE_DIR, "libveneur_loadgen.so")

# what the Makefile stamps each library with (SRC_HASH, LG_SRC_HASH):
# the first 16 hex digits of the sha256 of these sources, concatenated
_LIB_SOURCES = ("dogstatsd.cpp", "emit.cpp", "forward_codec.cpp")
_LOADGEN_SOURCES = ("loadgen.cpp",)

# path -> the library as this process loaded it, or None where there is
# none or it was refused: decided once, so a refusal warns once
_loaded: dict = {}
_load_lock = threading.Lock()


def _build() -> None:
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR],
                       capture_output=True, check=True, timeout=120)
    except subprocess.CalledProcessError as e:
        # the libraries are not committed (-march=native): a failed build
        # on a fresh checkout means the Python parser, and must be seen
        log.warning("native build failed (%s): %s", e,
                    e.stderr.decode("utf-8", "replace")[-2000:])
    except (OSError, subprocess.TimeoutExpired) as e:
        log.warning("native build unavailable: %s", e)


def _sources_stamp(sources: tuple) -> Optional[str]:
    """The stamp a build of this tree carries; None where the sources
    are not on disk beside the library."""
    h = hashlib.sha256()
    try:
        for name in sources:
            with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
                h.update(f.read())
    except OSError:
        return None
    return h.hexdigest()[:16]


def _load(path: str, stamp_symbol: str, sources: tuple,
          bind) -> Optional[ctypes.CDLL]:
    """The library at `path` with every symbol bound, if it is this
    tree's build: it exports everything `bind` names and its stamp is
    the hash of `sources`. Any other library is refused whole, with one
    warning, and the caller runs as if there were none: a build that
    failed beside an older library must not change what runs, symbol
    by symbol, in silence."""
    with _load_lock:
        if path in _loaded:
            return _loaded[path]
        # make is dependency-checked, so this is a no-op when the .so is
        # current and a rebuild when a source changed underneath it
        _build()
        lib = None
        if os.path.exists(path):
            want = _sources_stamp(sources)
            found, missing = "none", ""
            try:
                lib = ctypes.CDLL(path)
                stamp = getattr(lib, stamp_symbol)
                stamp.restype = ctypes.c_char_p
                stamp.argtypes = []
                found = stamp().decode()
                bind(lib)
            except (OSError, AttributeError) as e:
                missing = f" ({e})"
            if missing or (want is not None and found != want):
                log.warning(
                    "%s is not this tree's build: stamp %s, the sources' "
                    "is %s%s; running without it", path, found, want,
                    missing)
                lib = None
        _loaded[path] = lib
        return lib


def load_library() -> Optional[ctypes.CDLL]:
    return _load(_LIB_PATH, "vn_source_hash", _LIB_SOURCES, _bind_library)


def _bind_library(lib) -> None:
    c = ctypes
    lib.vn_encode_histo_batch.restype = c.c_longlong
    lib.vn_encode_histo_batch.argtypes = [
        c.c_char_p, c.c_longlong,
        c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
        c.c_void_p, c.c_int, c.c_int,
        c.c_void_p, c.c_void_p, c.c_void_p, c.c_double,
        c.POINTER(c.c_char_p)]
    P = c.POINTER
    lib.vn_decode_metric_batch.restype = c.c_longlong
    lib.vn_decode_metric_batch.argtypes = [
        c.c_char_p, c.c_longlong,
        P(c.c_char_p), P(c.c_longlong),          # meta
        P(c.c_void_p), P(c.c_void_p),            # kinds, scopes
        P(c.c_void_p), P(c.c_void_p),            # value_kind, digests
        P(c.c_void_p),                           # scalars
        P(c.c_void_p), P(c.c_void_p), P(c.c_void_p),  # dmin/max/rec
        P(c.c_void_p),                           # compression
        P(c.c_void_p), P(c.c_void_p), P(c.c_void_p),  # centroids
        P(c.c_void_p), P(c.c_char_p), P(c.c_void_p),  # hll
        P(c.c_void_p), P(c.c_void_p),  # record byte ranges
        P(c.c_void_p)]  # ring hashes
    lib.vn_upsert_many.restype = c.c_longlong
    lib.vn_upsert_many.argtypes = [
        c.c_void_p, c.c_char_p, c.c_longlong,
        c.c_void_p, c.c_void_p, c.c_void_p, c.c_longlong,
        c.c_void_p]
    lib.vn_encode_datadog_series.restype = c.c_longlong
    lib.vn_encode_datadog_series.argtypes = [
        c.c_char_p, c.c_longlong, c.c_longlong,       # meta
        c.c_char_p, c.c_longlong,                     # suffixes
        c.c_void_p, c.c_int,                          # types, nfam
        c.c_void_p, c.c_void_p,                       # values, masks
        c.c_longlong, c.c_double,                     # ts, interval
        c.c_char_p, c.c_longlong,                     # hostname
        c.c_char_p, c.c_longlong,                     # common tags
        c.c_char_p, c.c_longlong,                     # excl keys
        c.c_char_p, c.c_longlong,                     # excl prefixes
        c.c_char_p, c.c_longlong,                     # drop prefixes
        c.c_longlong,                                 # max_per_body
        c.POINTER(c.c_void_p), c.POINTER(c.c_char_p),
        c.POINTER(c.c_longlong), c.POINTER(c.c_longlong)]
    lib.vn_encode_signalfx_body.restype = c.c_longlong
    lib.vn_encode_signalfx_body.argtypes = [
        c.c_char_p, c.c_longlong, c.c_longlong,
        c.c_char_p, c.c_longlong,
        c.c_void_p, c.c_int, c.c_void_p, c.c_void_p,
        c.c_longlong,
        c.c_char_p, c.c_longlong, c.c_char_p, c.c_longlong,
        c.c_char_p, c.c_longlong, c.c_char_p, c.c_longlong,
        c.c_char_p, c.c_longlong,
        c.POINTER(c.c_char_p), c.POINTER(c.c_longlong)]
    lib.vn_encode_prometheus_lines.restype = c.c_longlong
    lib.vn_encode_prometheus_lines.argtypes = [
        c.c_char_p, c.c_longlong, c.c_longlong,
        c.c_char_p, c.c_longlong,
        c.c_void_p, c.c_int, c.c_void_p, c.c_void_p,
        c.c_char_p, c.c_longlong,
        c.POINTER(c.c_char_p), c.POINTER(c.c_longlong)]
    # emit tier (native/emit.cpp): forward lines, exposition
    # text, and the GIL-free deflate pass
    lib.vn_encode_forward_lines.restype = c.c_longlong
    lib.vn_encode_forward_lines.argtypes = (
        lib.vn_encode_prometheus_lines.argtypes)
    lib.vn_encode_prometheus_exposition.restype = c.c_longlong
    lib.vn_encode_prometheus_exposition.argtypes = (
        lib.vn_encode_prometheus_lines.argtypes)
    lib.vn_deflate.restype = c.c_longlong
    lib.vn_deflate.argtypes = [
        c.c_char_p, c.c_longlong,
        c.POINTER(c.c_char_p), c.POINTER(c.c_longlong)]
    lib.vn_deflate_chunks.restype = c.c_longlong
    lib.vn_deflate_chunks.argtypes = [
        c.c_char_p, c.c_void_p, c.c_longlong,
        c.POINTER(c.c_void_p), c.POINTER(c.c_char_p),
        c.POINTER(c.c_longlong)]
    # archive tier (native/emit.cpp): VMB1 columnar sections
    lib.vn_encode_archive_section.restype = c.c_longlong
    lib.vn_encode_archive_section.argtypes = [
        c.c_char_p, c.c_longlong, c.c_longlong,
        c.c_char_p, c.c_longlong,
        c.c_void_p, c.c_int, c.c_void_p, c.c_void_p,
        c.POINTER(c.c_char_p), c.POINTER(c.c_longlong)]
    # forward frame codec (native/forward_codec.cpp): VSF1
    # stream frames/acks + the VDE1 dedup envelope header
    lib.vn_stream_frame_encode.restype = c.c_longlong
    lib.vn_stream_frame_encode.argtypes = [
        c.c_ulonglong, c.c_char_p, c.c_longlong,
        c.POINTER(c.c_char_p), c.POINTER(c.c_longlong)]
    lib.vn_stream_frame_decode.restype = c.c_longlong
    lib.vn_stream_frame_decode.argtypes = [
        c.c_char_p, c.c_longlong, c.POINTER(c.c_ulonglong)]
    lib.vn_stream_ack_encode.restype = c.c_longlong
    lib.vn_stream_ack_encode.argtypes = [
        c.c_ulonglong, c.c_int, c.c_char_p]
    lib.vn_stream_ack_decode.restype = c.c_longlong
    lib.vn_stream_ack_decode.argtypes = [
        c.c_char_p, c.c_longlong, c.POINTER(c.c_ulonglong)]
    lib.vn_dedup_header_encode.restype = c.c_longlong
    lib.vn_dedup_header_encode.argtypes = [
        c.c_char_p, c.c_longlong, c.c_longlong, c.c_longlong,
        c.POINTER(c.c_char_p), c.POINTER(c.c_longlong)]
    lib.vn_dedup_header_parse.restype = c.c_longlong
    lib.vn_dedup_header_parse.argtypes = [
        c.c_char_p, c.c_longlong,
        c.POINTER(c.c_char_p), c.POINTER(c.c_longlong),
        c.POINTER(c.c_longlong), c.POINTER(c.c_longlong)]
    lib.vn_lock_stats.restype = c.c_int
    lib.vn_lock_stats.argtypes = [
        c.c_void_p, c.POINTER(c.c_longlong),
        c.POINTER(c.c_longlong), c.POINTER(c.c_longlong), c.c_int]
    lib.vn_lock_stats_reset.argtypes = [c.c_void_p]
    lib.vn_ctx_new.restype = c.c_void_p
    lib.vn_ctx_new.argtypes = [c.c_int]
    lib.vn_ctx_free.argtypes = [c.c_void_p]
    lib.vn_ctx_reset.argtypes = [c.c_void_p]
    lib.vn_ingest.restype = c.c_int
    lib.vn_ingest.argtypes = [c.c_void_p, c.c_char_p, c.c_int]
    for name in ("vn_pending_histo", "vn_pending_set",
                 "vn_pending_counter", "vn_pending_gauge",
                 "vn_num_histo_rows", "vn_num_set_rows",
                 "vn_num_counter_rows", "vn_num_gauge_rows"):
        fn = getattr(lib, name)
        fn.restype = c.c_int
        fn.argtypes = [c.c_void_p]
    for name in ("vn_processed", "vn_errors"):
        fn = getattr(lib, name)
        fn.restype = c.c_longlong
        fn.argtypes = [c.c_void_p]
    lib.vn_overload_dropped.restype = c.c_longlong
    lib.vn_overload_dropped.argtypes = [c.c_void_p]
    lib.vn_set_spill_cap.restype = None
    lib.vn_set_spill_cap.argtypes = [c.c_void_p, c.c_longlong]
    lib.vn_reader_ns.restype = None
    lib.vn_reader_ns.argtypes = [
        c.c_void_p, c.POINTER(c.c_longlong)]
    lib.vn_commit_counters.restype = None
    lib.vn_commit_counters.argtypes = [
        c.c_void_p, c.POINTER(c.c_longlong)]
    lib.vn_drain_histo.restype = c.c_int
    lib.vn_drain_histo.argtypes = [
        c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_int]
    lib.vn_drain_set.restype = c.c_int
    lib.vn_drain_set.argtypes = [
        c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_int]
    lib.vn_drain_counter.restype = c.c_int
    lib.vn_drain_counter.argtypes = [
        c.c_void_p, c.c_void_p, c.c_void_p, c.c_int]
    lib.vn_drain_gauge.restype = c.c_int
    lib.vn_drain_gauge.argtypes = [
        c.c_void_p, c.c_void_p, c.c_void_p, c.c_int]
    lib.vn_drain_new_series.restype = c.c_int
    lib.vn_drain_new_series.argtypes = (
        [c.c_void_p] + [c.POINTER(c.c_void_p)] * 6
        + [c.POINTER(c.c_int), c.POINTER(c.c_void_p),
           c.POINTER(c.c_longlong), c.POINTER(c.c_uint)])
    lib.vn_set_intern_cap.restype = None
    lib.vn_set_intern_cap.argtypes = [c.c_void_p, c.c_longlong]
    lib.vn_pending_new_series.restype = c.c_int
    lib.vn_pending_new_series.argtypes = [c.c_void_p]
    lib.vn_drain_other.restype = c.c_int
    lib.vn_drain_other.argtypes = [c.c_void_p, c.c_char_p, c.c_int]
    lib.vn_upsert.restype = c.c_int
    lib.vn_upsert.argtypes = [
        c.c_void_p, c.c_char_p, c.c_int, c.c_int, c.c_char_p, c.c_int,
        c.c_int]
    lib.vn_ingest_ssf.restype = c.c_int
    lib.vn_ingest_ssf.argtypes = [
        c.c_void_p, c.c_char_p, c.c_int, c.c_char_p, c.c_int,
        c.c_char_p, c.c_int, c.c_double]
    lib.vn_ssf_spans.restype = c.c_longlong
    lib.vn_ssf_spans.argtypes = [c.c_void_p]
    lib.vn_ssf_invalid.restype = c.c_longlong
    lib.vn_ssf_invalid.argtypes = [c.c_void_p]
    lib.vn_drain_ssf_services.restype = c.c_int
    lib.vn_drain_ssf_services.argtypes = [c.c_void_p, c.c_char_p, c.c_int]
    lib.vn_ctx_set_metro.argtypes = [c.c_void_p, c.c_int]
    lib.vn_metro_hash64.restype = c.c_uint64
    lib.vn_metro_hash64.argtypes = [c.c_char_p, c.c_int, c.c_uint64]
    lib.vn_ingest_routed.restype = c.c_int
    lib.vn_ingest_routed.argtypes = [
        c.POINTER(c.c_void_p), c.c_int, c.c_char_p, c.c_int]
    lib.vn_lock.argtypes = [c.c_void_p]
    lib.vn_unlock.argtypes = [c.c_void_p]
    lib.vn_ingest_ssf_many.restype = c.c_int
    lib.vn_ingest_ssf_many.argtypes = [
        c.c_void_p, c.c_char_p, c.c_longlong, c.c_char_p, c.c_int,
        c.c_char_p, c.c_int, c.c_double, c.POINTER(c.c_int),
        c.c_void_p, c.c_void_p, c.c_int, c.POINTER(c.c_int)]
    lib.vn_set_stage_depth.argtypes = [c.c_void_p, c.c_int]
    lib.vn_stage_detach.restype = c.c_void_p
    lib.vn_stage_detach.argtypes = [
        c.c_void_p, c.POINTER(c.POINTER(c.c_float)),
        c.POINTER(c.POINTER(c.c_float)),
        c.POINTER(c.POINTER(c.c_int32)),
        c.POINTER(c.c_int32), c.POINTER(c.c_int32)]
    lib.vn_stage_free.argtypes = [c.c_void_p]
    lib.vn_stage_total.restype = c.c_longlong
    lib.vn_stage_total.argtypes = [c.c_void_p]
    lib.vn_stage_pending.restype = c.c_longlong
    lib.vn_stage_pending.argtypes = [c.c_void_p]
    lib.vn_stage_drain_delta.restype = c.c_int64
    lib.vn_stage_drain_delta.argtypes = [
        c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
        c.c_void_p, c.c_int64]
    lib.vn_stage_unit_wts.restype = c.c_int
    lib.vn_stage_unit_wts.argtypes = [c.c_void_p]
    lib.vn_reader_start.restype = c.c_void_p
    lib.vn_reader_start.argtypes = [
        c.POINTER(c.c_void_p), c.c_int, c.c_int, c.c_int]
    lib.vn_reader_packets.restype = c.c_longlong
    lib.vn_reader_packets.argtypes = [c.c_void_p]
    lib.vn_reader_stop.restype = c.c_longlong
    lib.vn_reader_stop.argtypes = [c.c_void_p]
    lib.vn_stream_reader_start.restype = c.c_void_p
    lib.vn_stream_reader_start.argtypes = [
        c.POINTER(c.c_void_p), c.c_int, c.c_int, c.c_int]
    lib.vn_stream_reader_stop.restype = c.c_longlong
    lib.vn_stream_reader_stop.argtypes = [c.c_void_p]
    lib.vn_stream_reader_done.restype = c.c_int
    lib.vn_stream_reader_done.argtypes = [c.c_void_p]
    lib.vn_ssf_reader_start.restype = c.c_void_p
    lib.vn_ssf_reader_start.argtypes = [
        c.c_void_p, c.c_int, c.c_int, c.c_char_p, c.c_int,
        c.c_char_p, c.c_int, c.c_double]
    lib.vn_ssf_reader_stop.restype = c.c_longlong
    lib.vn_ssf_reader_stop.argtypes = [c.c_void_p]
    lib.vn_drain_ssf_fallback.restype = c.c_int
    lib.vn_drain_ssf_fallback.argtypes = [
        c.c_void_p, c.c_char_p, c.c_int]
    # reader-shard API: home-aware routed ingest (events/errors
    # land on the caller's own shard) and reader constructors
    # that take a home shard
    lib.vn_ingest_home.restype = c.c_int
    lib.vn_ingest_home.argtypes = [
        c.POINTER(c.c_void_p), c.c_int, c.c_char_p, c.c_int,
        c.c_int]
    lib.vn_reader_start2.restype = c.c_void_p
    lib.vn_reader_start2.argtypes = [
        c.POINTER(c.c_void_p), c.c_int, c.c_int, c.c_int, c.c_int]
    lib.vn_stream_reader_start2.restype = c.c_void_p
    lib.vn_stream_reader_start2.argtypes = [
        c.POINTER(c.c_void_p), c.c_int, c.c_int, c.c_int, c.c_int]


def _ptr(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.c_void_p)


_NO_INTS = np.zeros(0, np.int32)


@dataclass
class NewSeriesBatch:
    """One drain of a context's new-series queue
    (NativeIngest.drain_new_series). Record i is the series that took
    row ``rows[i]`` of pool ``pools[i]`` (0 histo, 1 set, 2 counter, 3
    gauge) this interval; ``sids[i]`` names it for the context's
    lifetime. The records come grouped by pool, pool 0 first, and a
    pool's rows are consecutive: ``pool_slices()`` says where each
    pool's records lie. The records at positions ``first_at`` are the
    ones whose strings this context hands over for the first time:
    their MetricKind ints, scope classes, names and joined tags ride
    along, in the same order. ``generation`` changes when the context
    dropped its table: every sid learnt under another generation is
    void."""

    generation: int
    pools: np.ndarray
    rows: np.ndarray
    sids: np.ndarray
    first_at: np.ndarray
    first_kinds: np.ndarray
    first_scopes: np.ndarray
    first_names: list
    first_tags: list

    def __len__(self) -> int:
        return len(self.rows)

    def pool_slices(self) -> list:
        """[(pool, start, stop)] of the pools that have records, by
        bisection of ``pools`` (sorted): a few scalar reads, so that an
        adoption under the ingest lock sorts and compares nothing."""
        out, start, n = [], 0, len(self.pools)
        while start < n:
            pool = int(self.pools[start])
            stop = bisect.bisect_right(self.pools, pool, start, n)
            out.append((pool, start, stop))
            start = stop
        return out

    def first_records(self) -> list:
        """The first-seen records as (pool, row, kind, scope_class,
        name, joined_tags) tuples: every record of a context that was
        never reset (tests, tools/fuzz_differential.py)."""
        at = self.first_at
        return list(zip(self.pools[at].tolist(), self.rows[at].tolist(),
                        self.first_kinds.tolist(),
                        self.first_scopes.tolist(),
                        self.first_names, self.first_tags))


def _lock_stats(lib, ctx, samples: bool = True) -> dict:
    """A context's commit-lock record (Ctx::lk_*, always on): one entry
    a lock hold of the chunk commit. ``samples=False`` reads the four
    totals alone and leaves the rings where they are."""
    totals = (ctypes.c_longlong * 5)()
    out = {}
    if samples:
        wait = (ctypes.c_longlong * 4096)()
        hold = (ctypes.c_longlong * 4096)()
        n = lib.vn_lock_stats(ctx, totals, wait, hold, 4096)
        out["wait_ns_samples"] = wait[:n]
        out["hold_ns_samples"] = hold[:n]
    else:
        lib.vn_lock_stats(ctx, totals, None, None, 0)
    out.update(acquisitions=int(totals[0]), contended=int(totals[1]),
               wait_ns_total=int(totals[2]), hold_ns_total=int(totals[3]))
    return out


class NativeIngest:
    """One epoch-scoped native parser+directory context."""

    def __init__(self, hll_precision: int = 14,
                 set_hash: str = "fnv") -> None:
        lib = load_library()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._ctx = lib.vn_ctx_new(hll_precision)
        if set_hash == "metro":
            lib.vn_ctx_set_metro(self._ctx, 1)
        # drain_new_series out-parameters, allocated once: the import
        # path drains per upsert
        c = ctypes
        ptrs = [c.c_void_p() for _ in range(6)]
        outs = (c.c_int(0), c.c_void_p(), c.c_longlong(0), c.c_uint(0))
        self._ns_out = (ptrs, *outs,
                        [c.byref(o) for o in (*ptrs, *outs)])
        self._ns_lock = threading.Lock()

    def __del__(self):
        if getattr(self, "_ctx", None):
            self._lib.vn_ctx_free(self._ctx)
            self._ctx = None

    def reset(self) -> None:
        self._lib.vn_ctx_reset(self._ctx)

    def lock(self) -> None:
        """Hold the context's (recursive) lock across a multi-call
        sequence, excluding routed commits from other threads."""
        self._lib.vn_lock(self._ctx)

    def unlock(self) -> None:
        self._lib.vn_unlock(self._ctx)

    def ingest(self, datagram: bytes) -> int:
        return self._lib.vn_ingest(self._ctx, datagram, len(datagram))

    # shared-nothing reader-shard path --------------------------------------

    def _self_arr(self):
        arr = getattr(self, "_self_arr_c", None)
        if arr is None:
            arr = self._self_arr_c = (ctypes.c_void_p * 1)(self._ctx)
        return arr

    def ingest_owned(self, datagram: bytes) -> int:
        """Shared-nothing ingest: parse lock-free, commit every line into
        THIS context under its own (uncontended on the reader-shard path)
        mutex — the in-process twin of an owned C++ reader thread.
        Events/service checks and parse errors stay on this context too."""
        return self._lib.vn_ingest_home(
            self._self_arr(), 1, datagram, len(datagram), 0)

    def start_owned_reader(self, fd: int, max_len: int):
        """Spawn a C++ reader thread committing exclusively into this
        context (the shared-nothing per-reader shape; same fd/stop
        contract as NativeRouter.start_reader)."""
        h = self._lib.vn_reader_start2(self._self_arr(), 1, fd, max_len, 0)
        if not h:
            raise RuntimeError("vn_reader_start2 failed")
        return h

    def lock_stats(self, samples: bool = True) -> dict:
        """This context's commit-mutex contention record (same shape as
        NativeRouter.lock_stats)."""
        return _lock_stats(self._lib, self._ctx, samples)

    def reset_lock_stats(self) -> None:
        self._lib.vn_lock_stats_reset(self._ctx)

    # pending counts ---------------------------------------------------------

    @property
    def pending_histo(self) -> int:
        return self._lib.vn_pending_histo(self._ctx)

    @property
    def pending_set(self) -> int:
        return self._lib.vn_pending_set(self._ctx)

    @property
    def pending_counter(self) -> int:
        return self._lib.vn_pending_counter(self._ctx)

    @property
    def pending_gauge(self) -> int:
        return self._lib.vn_pending_gauge(self._ctx)

    @property
    def processed(self) -> int:
        return self._lib.vn_processed(self._ctx)

    @property
    def errors(self) -> int:
        return self._lib.vn_errors(self._ctx)

    @property
    def overload_dropped(self) -> int:
        """Samples shed at the pending-batch spill caps (overload)."""
        return int(self._lib.vn_overload_dropped(self._ctx))

    def reader_ns(self) -> tuple:
        """(ns inside recv, ns outside it) of the C++ reader threads
        homed on this context: lifetime totals, never reset."""
        out = (ctypes.c_longlong * 2)()
        self._lib.vn_reader_ns(self._ctx, out)
        return int(out[0]), int(out[1])

    COMMIT_COUNTERS = ("dir_hits", "dir_restamped", "dir_first_seen",
                       "commit_batches", "commit_lines", "plane_grows",
                       "histo_staged", "histo_spilled")

    def commit_counters(self) -> dict:
        """What this context's commit path met, lifetime totals: a
        committed sample or an upsert found its series with a row of
        this interval (dir_hits), known but not yet written this
        interval (dir_restamped) or never seen (dir_first_seen);
        commit_batches lock holds of the chunk commit took commit_lines
        lines; plane_grows reallocations of the staging plane; a
        committed histogram or timer sample went into the staging plane
        (histo_staged) or past its depth to the spill fold
        (histo_spilled)."""
        out = (ctypes.c_longlong * len(self.COMMIT_COUNTERS))()
        self._lib.vn_commit_counters(self._ctx, out)
        return dict(zip(self.COMMIT_COUNTERS, map(int, out)))

    def set_spill_cap(self, cap: int) -> None:
        """Entries per pending SoA batch before samples shed (tests /
        memory-constrained deployments; default 2^22)."""
        self._lib.vn_set_spill_cap(self._ctx, int(cap))

    def num_rows(self) -> tuple[int, int, int, int]:
        """(histo, set, counter, gauge) row counts."""
        return (self._lib.vn_num_histo_rows(self._ctx),
                self._lib.vn_num_set_rows(self._ctx),
                self._lib.vn_num_counter_rows(self._ctx),
                self._lib.vn_num_gauge_rows(self._ctx))

    # staging plane ----------------------------------------------------------

    def set_stage_depth(self, depth: int) -> None:
        """Enable the C++ raw-sample staging plane with B slots per
        histogram row (0 disables). Staged samples bypass the per-batch
        SoA drain entirely; detach_stage() pulls the whole plane at
        flush."""
        self._lib.vn_set_stage_depth(self._ctx, depth)

    @property
    def stage_total(self) -> int:
        return int(self._lib.vn_stage_total(self._ctx))

    @property
    def stage_pending(self) -> int:
        """Staged samples not yet copied out by drain_stage_delta
        (micro-fold due checks)."""
        return int(self._lib.vn_stage_pending(self._ctx))

    def drain_stage_delta(self, cap: int):
        """Copy up to `cap` not-yet-drained staged samples out as COO
        (rows, slots, vals, wts) with ABSOLUTE slot positions, advancing
        the plane's per-row drained watermark. The plane's counts are
        untouched, so the per-epoch depth cap (and the spill
        partitioning) is identical to a run with no micro-folds."""
        rows = np.empty(cap, np.int32)
        slots = np.empty(cap, np.int32)
        vals = np.empty(cap, np.float32)
        wts = np.empty(cap, np.float32)
        n = self._lib.vn_stage_drain_delta(
            self._ctx, _ptr(rows), _ptr(slots), _ptr(vals), _ptr(wts), cap)
        return rows[:n], slots[:n], vals[:n], wts[:n]

    def detach_stage(self):
        """Detach the staged plane: returns (vals[rows, depth],
        wts[rows, depth], counts[rows], unit_wts, free) — the numpy
        arrays alias C++ memory owned by the detached plane; call free()
        only after the data has been uploaded/copied. None when nothing
        is staged. unit_wts=True means every weight is exactly 1.0, so
        the consumer can rebuild the weights plane on device from
        `counts` instead of uploading it. A fresh zeroed plane takes
        over for subsequent samples."""
        c = ctypes
        pv = c.POINTER(c.c_float)()
        pw = c.POINTER(c.c_float)()
        pc = c.POINTER(c.c_int32)()
        rows = c.c_int32()
        depth = c.c_int32()
        handle = self._lib.vn_stage_detach(
            self._ctx, c.byref(pv), c.byref(pw), c.byref(pc),
            c.byref(rows), c.byref(depth))
        if not handle:
            return None
        r, d = rows.value, depth.value
        vals = np.ctypeslib.as_array(pv, shape=(r, d))
        wts = np.ctypeslib.as_array(pw, shape=(r, d))
        counts = np.ctypeslib.as_array(pc, shape=(r,))
        unit = bool(self._lib.vn_stage_unit_wts(handle))
        lib = self._lib

        def free(_h=handle, _lib=lib):
            _lib.vn_stage_free(_h)

        return vals, wts, counts, unit, free

    # drains -----------------------------------------------------------------

    def drain_histo(self, cap: int):
        rows = np.empty(cap, np.int32)
        vals = np.empty(cap, np.float32)
        wts = np.empty(cap, np.float32)
        n = self._lib.vn_drain_histo(
            self._ctx, _ptr(rows), _ptr(vals), _ptr(wts), cap)
        return rows[:n], vals[:n], wts[:n]

    def drain_set(self, cap: int):
        rows = np.empty(cap, np.int32)
        idx = np.empty(cap, np.int32)
        rank = np.empty(cap, np.int8)
        n = self._lib.vn_drain_set(
            self._ctx, _ptr(rows), _ptr(idx), _ptr(rank), cap)
        return rows[:n], idx[:n], rank[:n]

    def drain_counter(self, cap: int):
        rows = np.empty(cap, np.int32)
        contribs = np.empty(cap, np.float64)
        n = self._lib.vn_drain_counter(
            self._ctx, _ptr(rows), _ptr(contribs), cap)
        return rows[:n], contribs[:n]

    def drain_gauge(self, cap: int):
        rows = np.empty(cap, np.int32)
        vals = np.empty(cap, np.float64)
        n = self._lib.vn_drain_gauge(self._ctx, _ptr(rows), _ptr(vals), cap)
        return rows[:n], vals[:n]

    @property
    def pending_new_series(self) -> int:
        """Count of undrained new-series records (cheap C call; the
        per-upsert sync skips the drain entirely when 0)."""
        return self._lib.vn_pending_new_series(self._ctx)

    def drain_new_series(self) -> "NewSeriesBatch":
        """Every series created since the last drain, in one call: the
        whole pending queue as int32 arrays, grouped by pool, and
        strings only for the series this context has not handed over
        before (first-seen `sid`s; a series keeps its sid across
        reset())."""
        c = ctypes
        ptrs, n_first, strs, strs_len, generation, args = self._ns_out
        # the pointers are good until this context's next drain, and the
        # out-parameters are shared: one drainer at a time, kept out by a
        # lock of Python's. The context's own lock is held inside the
        # call only, so a reader never waits for a thread that holds it
        # while waiting for the interpreter, and the drain gives the
        # interpreter up once, not three times
        with self._ns_lock:
            n = self._lib.vn_drain_new_series(self._ctx, *args)
            nf = n_first.value
            # (string_at + frombuffer: a read-only copy, at a tenth of
            # np.ctypeslib's cost for the short drains of the upsert path)
            pools, rows, sids, first_at, first_kinds, first_scopes = (
                np.frombuffer(c.string_at(ptr, 4 * count), np.int32)
                if count else _NO_INTS
                for ptr, count in zip(ptrs, (n, n, n, nf, nf, nf)))
            packed = c.string_at(strs, strs_len.value) if nf else b""
            gen = generation.value
        names: list[str] = []
        tags: list[str] = []
        if nf:
            # one decode of the whole buffer: the separators are ASCII,
            # so a byte that does not decode never swallows one
            for rec in packed.decode("utf-8", "replace").split("\x1e")[:nf]:
                name, _, joined = rec.partition("\x1f")
                names.append(name)
                tags.append(joined)
        return NewSeriesBatch(gen, pools, rows, sids, first_at,
                              first_kinds, first_scopes, names, tags)

    def set_intern_cap(self, cap: int) -> None:
        """Bound on the lifetime series table (4,000,000; past it the
        next reset() drops the table and bumps the generation the drain
        reports). Only the tests move it."""
        self._lib.vn_set_intern_cap(self._ctx, int(cap))

    KIND_BY_TYPE = {"counter": 0, "gauge": 1, "histogram": 2, "timer": 3,
                    "set": 4}
    TYPE_BY_KIND = {v: k for k, v in KIND_BY_TYPE.items()}

    def upsert(self, name: str, mtype: str, joined_tags: str,
               scope_class: int) -> int:
        """Directory upsert for Python-side ingest (shares row space with
        parsed traffic).

        The native new-series drain protocol frames records with the
        \\x1e/\\x1f unit separators, so those control bytes cannot travel
        through it verbatim — they are replaced with '_' here (no
        legitimate metric name or tag contains ASCII unit separators;
        series identity is preserved up to that substitution)."""
        if "\x1e" in name or "\x1f" in name:
            name = name.replace("\x1e", "_").replace("\x1f", "_")
        if "\x1e" in joined_tags or "\x1f" in joined_tags:
            joined_tags = joined_tags.replace(
                "\x1e", "_").replace("\x1f", "_")
        nb = name.encode("utf-8")
        tb = joined_tags.encode("utf-8")
        return self._lib.vn_upsert(
            self._ctx, nb, len(nb), self.KIND_BY_TYPE[mtype], tb, len(tb),
            scope_class)

    def ingest_ssf(self, packet: bytes, indicator_name: bytes = b"",
                   objective_name: bytes = b"",
                   uniqueness_rate: float = 0.0) -> int:
        """Native SSF span fast path: decode + span→metric extraction.
        Returns 1 on success, 0 on decode error, -1 when the span carries
        STATUS samples (caller must take the Python path)."""
        return self._lib.vn_ingest_ssf(
            self._ctx, packet, len(packet),
            indicator_name, len(indicator_name),
            objective_name, len(objective_name),
            float(uniqueness_rate))

    def ingest_ssf_many(self, packets: list[bytes],
                        indicator_name: bytes = b"",
                        objective_name: bytes = b"",
                        uniqueness_rate: float = 0.0
                        ) -> tuple[int, int, list[bytes]]:
        """Batched SSF ingest: one C call for many spans (amortizes the
        per-call ctypes overhead, ~1/3 of the per-span cost). Returns
        (accepted, decode_errors, fallback_packets) where
        fallback_packets carry STATUS samples and need the Python path."""
        if not packets:
            return 0, 0, []
        buf = b"".join(
            len(pkt).to_bytes(4, "little") + pkt for pkt in packets)
        errors = ctypes.c_int(0)
        nfall = ctypes.c_int(0)
        cap = len(packets)
        fb_off = np.empty(cap, np.int32)
        fb_len = np.empty(cap, np.int32)
        ok = self._lib.vn_ingest_ssf_many(
            self._ctx, buf, len(buf),
            indicator_name, len(indicator_name),
            objective_name, len(objective_name),
            float(uniqueness_rate), ctypes.byref(errors),
            _ptr(fb_off), _ptr(fb_len), cap, ctypes.byref(nfall))
        fallbacks = [
            buf[fb_off[i]:fb_off[i] + fb_len[i]]
            for i in range(int(nfall.value))
        ]
        return int(ok), int(errors.value), fallbacks

    @property
    def ssf_spans(self) -> int:
        return self._lib.vn_ssf_spans(self._ctx)

    @property
    def ssf_invalid(self) -> int:
        return self._lib.vn_ssf_invalid(self._ctx)

    def drain_ssf_services(self) -> dict[str, int]:
        # cap contract (see vn_drain_ssf_services): must hold at least one
        # full "service\tcount\n" line (<= 278 bytes) or the drain loop
        # below would exit with counts stuck buffered until next flush
        cap = 1 << 18
        buf = ctypes.create_string_buffer(cap)
        out: dict[str, int] = {}
        while True:
            n = self._lib.vn_drain_ssf_services(self._ctx, buf, cap)
            if n <= 0:
                break
            for line in buf.raw[:n].split(b"\n"):
                if not line:
                    continue
                # rpartition: the count is the field after the LAST tab,
                # so a malformed line can't turn into a bad int() (the C++
                # side also sanitizes framing bytes out of service names)
                svc, sep, cnt = line.rpartition(b"\t")
                if not sep or not cnt.isdigit():
                    log.warning("malformed ssf service-count line %r", line)
                    continue
                svc_s = svc.decode("utf-8", "replace")
                out[svc_s] = out.get(svc_s, 0) + int(cnt)
        return out

    def _drain_buf(self) -> ctypes.Array:
        """Per-thread 1 MiB drain scratch: the native pump polls
        drain_other/drain_ssf_fallback 10x/s per context, and a fresh
        zero-filled ctypes buffer per call was ~20 MiB/s of allocation
        churn at idle. Thread-local rather than lock-guarded: the C++
        side already serializes each buffer cut on the ctx mutex, and a
        Python lock here would invert against callers that drain while
        HOLDING the ctx lock (the flush epoch close) versus callers that
        take it inside the drain call (reader-thread event drains)."""
        tl = getattr(self, "_drain_tl", None)
        if tl is None:
            tl = self._drain_tl = threading.local()
        buf = getattr(tl, "buf", None)
        if buf is None:
            buf = tl.buf = ctypes.create_string_buffer(1 << 20)
        return buf

    def drain_ssf_fallback(self) -> list[bytes]:
        """Raw SSF payloads the native reader handed back for the Python
        path (STATUS samples aboard), as whole packets."""
        buf = self._drain_buf()
        cap = len(buf)
        out = []
        while True:
            n = self._lib.vn_drain_ssf_fallback(self._ctx, buf, cap)
            if n == 0:
                break
            raw = buf.raw[:n]
            pos = 0
            while pos + 4 <= n:
                ln = int.from_bytes(raw[pos:pos + 4], "little")
                out.append(raw[pos + 4:pos + 4 + ln])
                pos += 4 + ln
        return out

    def drain_other(self) -> list[bytes]:
        buf = self._drain_buf()
        cap = len(buf)
        out = []
        while True:
            # chunks are cut on line boundaries (so n < cap does NOT
            # mean drained); loop until the buffer reports empty
            n = self._lib.vn_drain_other(self._ctx, buf, cap)
            if n == 0:
                break
            out.extend(ln for ln in buf.raw[:n].split(b"\n") if ln)
        return out


def available() -> bool:
    return load_library() is not None


def emit_available() -> bool:
    """True when the native emit tier (native/emit.cpp) is loadable and
    not masked out. VENEUR_EMIT_NATIVE=0 forces the Python formatters —
    the CI parity lane and the bench --emit-native axis flip this
    without touching the .so on disk."""
    if os.environ.get("VENEUR_EMIT_NATIVE", "").lower() in (
            "0", "false", "off", "no"):
        return False
    return load_library() is not None


def codec_available() -> bool:
    """True when the native forward frame codec
    (native/forward_codec.cpp) is loadable and not masked out.
    VENEUR_CODEC_NATIVE=0 forces the pinned Python codec — the CI
    parity lane and fuzz_differential flip this without touching the
    .so on disk (same contract as VENEUR_EMIT_NATIVE)."""
    if os.environ.get("VENEUR_CODEC_NATIVE", "").lower() in (
            "0", "false", "off", "no"):
        return False
    return load_library() is not None


def _blob_arg(blob) -> tuple:
    """(c_char_p-compatible arg, length) for a meta blob that may be a
    bytes object or a pool's live bytearray arena (zero-copy: the arena
    is frozen after the epoch swap, so a borrowed pointer is safe for
    the duration of the call)."""
    if isinstance(blob, bytearray):
        n = len(blob)
        if n == 0:
            return b"", 0
        arr = (ctypes.c_char * n).from_buffer(blob)
        return ctypes.cast(arr, ctypes.c_char_p), n
    return blob, len(blob)


def encode_histo_batch(meta_blob: bytes, kinds: np.ndarray,
                       scopes: np.ndarray, emit: np.ndarray,
                       means: np.ndarray, weights: np.ndarray,
                       dmin: np.ndarray, dmax: np.ndarray,
                       drecip: np.ndarray,
                       compression: float) -> Optional[bytes]:
    """Histogram rows -> veneurtpu.MetricBatch wire bytes at C++ speed
    (see native/dogstatsd.cpp vn_encode_histo_batch). Returns None when
    the native library is unavailable."""
    lib = load_library()
    if lib is None:
        return None
    rows, cap = means.shape
    means = np.ascontiguousarray(means, np.float32)
    weights = np.ascontiguousarray(weights, np.float32)
    kinds = np.ascontiguousarray(kinds, np.int8)
    scopes = np.ascontiguousarray(scopes, np.int8)
    emit = np.ascontiguousarray(emit, np.uint8)
    dmin = np.ascontiguousarray(dmin, np.float64)
    dmax = np.ascontiguousarray(dmax, np.float64)
    drecip = np.ascontiguousarray(drecip, np.float64)
    out_ptr = ctypes.c_char_p()
    n = lib.vn_encode_histo_batch(
        meta_blob, len(meta_blob), _ptr(kinds), _ptr(scopes), _ptr(emit),
        _ptr(means), _ptr(weights), rows, cap, _ptr(dmin), _ptr(dmax),
        _ptr(drecip), ctypes.c_double(compression),
        ctypes.byref(out_ptr))
    if n < 0:
        return None
    return ctypes.string_at(out_ptr, n)


class DecodedBatch:
    """SoA view of one decoded MetricBatch (copies out of the C++
    thread-local buffers, so the object outlives further decodes)."""

    __slots__ = ("n", "meta", "kinds", "scopes", "value_kind", "digests",
                 "scalars", "dmin", "dmax", "drecip", "compression",
                 "cent_off", "cent_means", "cent_weights", "hll_off",
                 "hll_bytes", "hll_precision", "rec_off", "rec_len",
                 "ring_hash")


def _copy_arr(ptr: "ctypes.c_void_p", count: int, dtype) -> np.ndarray:
    if count == 0 or not ptr.value:
        return np.zeros(0, dtype)
    ctype = np.ctypeslib.as_ctypes_type(dtype)
    view = np.ctypeslib.as_array(
        ctypes.cast(ptr, ctypes.POINTER(ctype)), shape=(count,))
    return view.copy()


def decode_metric_batch(blob: bytes) -> Optional[DecodedBatch]:
    """Parse serialized veneurtpu.MetricBatch wire bytes into SoA arrays
    via the C++ decoder (native/dogstatsd.cpp vn_decode_metric_batch).
    Returns None when there is no library or the input is
    malformed (callers fall back to the Python protobuf path)."""
    lib = load_library()
    if lib is None:
        return None
    c = ctypes
    meta = c.c_char_p()
    meta_len = c.c_longlong()
    (kinds, scopes, value_kind, digests, scalars, dmin, dmax, drecip,
     compression, cent_off, cent_means, cent_weights,
     hll_off, hll_precision, rec_off, rec_len, ring_hash) = [
        c.c_void_p() for _ in range(17)]
    hll_bytes = c.c_char_p()
    n = lib.vn_decode_metric_batch(
        blob, len(blob), c.byref(meta), c.byref(meta_len),
        c.byref(kinds), c.byref(scopes), c.byref(value_kind),
        c.byref(digests), c.byref(scalars), c.byref(dmin), c.byref(dmax),
        c.byref(drecip), c.byref(compression), c.byref(cent_off),
        c.byref(cent_means), c.byref(cent_weights), c.byref(hll_off),
        c.byref(hll_bytes), c.byref(hll_precision), c.byref(rec_off),
        c.byref(rec_len), c.byref(ring_hash))
    if n < 0:
        return None
    d = DecodedBatch()
    d.n = n
    d.meta = ctypes.string_at(meta, meta_len.value) if meta_len.value \
        else b""
    d.kinds = _copy_arr(kinds, n, np.uint8)
    d.scopes = _copy_arr(scopes, n, np.uint8)
    d.value_kind = _copy_arr(value_kind, n, np.uint8)
    d.digests = _copy_arr(digests, n, np.uint32)
    d.scalars = _copy_arr(scalars, n, np.float64)
    d.dmin = _copy_arr(dmin, n, np.float64)
    d.dmax = _copy_arr(dmax, n, np.float64)
    d.drecip = _copy_arr(drecip, n, np.float64)
    d.compression = _copy_arr(compression, n, np.float64)
    d.cent_off = _copy_arr(cent_off, n + 1, np.int64)
    ncent = int(d.cent_off[-1]) if n else 0
    d.cent_means = _copy_arr(cent_means, ncent, np.float32)
    d.cent_weights = _copy_arr(cent_weights, ncent, np.float32)
    d.hll_off = _copy_arr(hll_off, n + 1, np.int64)
    nhll = int(d.hll_off[-1]) if n else 0
    d.hll_bytes = ctypes.string_at(hll_bytes, nhll) if nhll else b""
    d.hll_precision = _copy_arr(hll_precision, n, np.int32)
    d.rec_off = _copy_arr(rec_off, n, np.int64)
    d.rec_len = _copy_arr(rec_len, n, np.int64)
    d.ring_hash = _copy_arr(ring_hash, n, np.uint64)
    return d


def upsert_many(ctx: "NativeIngest", meta: bytes, kinds: np.ndarray,
                scopes: np.ndarray, sel: np.ndarray) -> np.ndarray:
    """Batch directory upsert under one native lock hold. Returns row
    ids (i32[n], -1 where unselected)."""
    lib = ctx._lib
    n = len(kinds)
    out = np.empty(n, np.int32)
    kinds = np.ascontiguousarray(kinds, np.uint8)
    scopes = np.ascontiguousarray(scopes, np.uint8)
    sel = np.ascontiguousarray(sel, np.uint8)
    lib.vn_upsert_many(ctx._ctx, meta, len(meta), _ptr(kinds),
                       _ptr(scopes), _ptr(sel), n, _ptr(out))
    return out


def encode_datadog_series(meta_blob: bytes, nrows: int,
                          suffixes: list[str], family_types: np.ndarray,
                          values: np.ndarray, masks: np.ndarray,
                          ts: int, interval: float, hostname: str,
                          common_tags_json: bytes,
                          excluded_keys: list[str],
                          excluded_prefixes: list[str],
                          drop_prefixes: list[str],
                          max_per_body: int,
                          compress: bool = False
                          ) -> "Optional[tuple[list[bytes], int]]":
    """Chunked Datadog {"series": [...]} bodies straight from columnar
    arrays (native/emit.cpp vn_encode_datadog_series). Returns
    (bodies, emitted_count), or None when there is no library.
    compress=True deflates every chunk natively before it is
    copied out (vn_deflate_chunks; byte-identical to zlib.compress),
    so only compressed bytes cross back into Python."""
    lib = load_library()
    if lib is None:
        return None
    c = ctypes
    values = np.ascontiguousarray(values, np.float64)
    masks = np.ascontiguousarray(masks, np.uint8)
    family_types = np.ascontiguousarray(family_types, np.int8)
    suffix_blob = "\x1f".join(suffixes).encode("utf-8")
    ek = "\x1f".join(excluded_keys).encode("utf-8")
    ep = "\x1f".join(excluded_prefixes).encode("utf-8")
    dp = "\x1f".join(drop_prefixes).encode("utf-8")
    host = hostname.encode("utf-8")
    meta_arg, meta_len = _blob_arg(meta_blob)
    chunk_off = c.c_void_p()
    out = c.c_char_p()
    out_len = c.c_longlong()
    entries = c.c_longlong()
    n_chunks = lib.vn_encode_datadog_series(
        meta_arg, meta_len, nrows, suffix_blob, len(suffix_blob),
        _ptr(family_types), len(suffixes), _ptr(values), _ptr(masks),
        ts, float(interval), host, len(host), common_tags_json,
        len(common_tags_json), ek, len(ek), ep, len(ep), dp, len(dp),
        max_per_body, c.byref(chunk_off), c.byref(out),
        c.byref(out_len), c.byref(entries))
    if n_chunks < 0:
        return None
    if compress and n_chunks:
        # chain the deflate pass on the still-live thread-local body
        # buffer (same thread; the deflate output lives in its own
        # buffers) — one more GIL-free call, zero Python-side copies of
        # the uncompressed bodies
        zoff = c.c_void_p()
        zout = c.c_char_p()
        zlen = c.c_longlong()
        zn = lib.vn_deflate_chunks(out, chunk_off, n_chunks,
                                   c.byref(zoff), c.byref(zout),
                                   c.byref(zlen))
        if zn < 0:
            return None
        chunk_off, out, out_len = zoff, zout, zlen
    offs = _copy_arr(chunk_off, n_chunks + 1, np.int64).tolist()
    whole = ctypes.string_at(out, out_len.value)
    return ([whole[offs[i]:offs[i + 1]] for i in range(n_chunks)],
            int(entries.value))


def encode_signalfx_body(meta_blob: bytes, nrows: int,
                         suffixes: list[str], family_types: np.ndarray,
                         values: np.ndarray, masks: np.ndarray,
                         ts_ms: int, hostname_tag: str, hostname: str,
                         name_drops: list[str], tag_drops: list[str],
                         excluded_keys: list[str]
                         ) -> "Optional[tuple[bytes, int]]":
    """One SignalFx {"counter":[...],"gauge":[...]} body from columnar
    arrays; (body, emitted_count), or None when unavailable."""
    lib = load_library()
    if lib is None:
        return None
    c = ctypes
    values = np.ascontiguousarray(values, np.float64)
    masks = np.ascontiguousarray(masks, np.uint8)
    family_types = np.ascontiguousarray(family_types, np.int8)
    sb = "\x1f".join(suffixes).encode("utf-8")
    nd = "\x1f".join(name_drops).encode("utf-8")
    td_ = "\x1f".join(tag_drops).encode("utf-8")
    ek = "\x1f".join(excluded_keys).encode("utf-8")
    ht = hostname_tag.encode("utf-8")
    hv = hostname.encode("utf-8")
    meta_arg, meta_len = _blob_arg(meta_blob)
    out = c.c_char_p()
    out_len = c.c_longlong()
    n = lib.vn_encode_signalfx_body(
        meta_arg, meta_len, nrows, sb, len(sb),
        _ptr(family_types), len(suffixes), _ptr(values), _ptr(masks),
        ts_ms, ht, len(ht), hv, len(hv), nd, len(nd), td_, len(td_),
        ek, len(ek), c.byref(out), c.byref(out_len))
    if n < 0:
        return None
    return ctypes.string_at(out, out_len.value), int(n)


def _encode_lines(symbol: str, meta_blob, nrows: int,
                  suffixes: list[str], family_types: np.ndarray,
                  values: np.ndarray, masks: np.ndarray,
                  excluded_keys: list[str]
                  ) -> "Optional[tuple[bytes, int]]":
    """Shared wrapper for the line-oriented emitters (statsd lines,
    forward lines, exposition text): one newline-joined buffer plus the
    emitted count; None when there is no library."""
    lib = load_library()
    if lib is None:
        return None
    c = ctypes
    values = np.ascontiguousarray(values, np.float64)
    masks = np.ascontiguousarray(masks, np.uint8)
    family_types = np.ascontiguousarray(family_types, np.int8)
    suffix_blob = "\x1f".join(suffixes).encode("utf-8")
    ek = "\x1f".join(excluded_keys).encode("utf-8")
    meta_arg, meta_len = _blob_arg(meta_blob)
    out = c.c_char_p()
    out_len = c.c_longlong()
    n = getattr(lib, symbol)(
        meta_arg, meta_len, nrows, suffix_blob, len(suffix_blob),
        _ptr(family_types), len(suffixes), _ptr(values), _ptr(masks),
        ek, len(ek), c.byref(out), c.byref(out_len))
    if n < 0:
        return None
    return ctypes.string_at(out, out_len.value), int(n)


def encode_archive_section(meta_blob, nrows: int,
                           suffixes: list[str],
                           family_types: np.ndarray,
                           values: np.ndarray, masks: np.ndarray
                           ) -> "Optional[bytes]":
    """One VMB1 columnar section body (archive/wire.py) straight from an
    EmitGroupPlan's buffers, GIL-free; byte-identical to the Python
    encoder. None when there is no library."""
    lib = load_library()
    if lib is None:
        return None
    c = ctypes
    values = np.ascontiguousarray(values, np.float64)
    masks = np.ascontiguousarray(masks, np.uint8)
    family_types = np.ascontiguousarray(family_types, np.int8)
    suffix_blob = "\x1f".join(suffixes).encode("utf-8")
    meta_arg, meta_len = _blob_arg(meta_blob)
    out = c.c_char_p()
    out_len = c.c_longlong()
    n = lib.vn_encode_archive_section(
        meta_arg, meta_len, nrows, suffix_blob, len(suffix_blob),
        _ptr(family_types), len(suffixes), _ptr(values), _ptr(masks),
        c.byref(out), c.byref(out_len))
    if n < 0:
        return None
    return ctypes.string_at(out, out_len.value)


def encode_prometheus_lines(meta_blob, nrows: int,
                            suffixes: list[str],
                            family_types: np.ndarray,
                            values: np.ndarray, masks: np.ndarray,
                            excluded_keys: list[str]
                            ) -> "Optional[tuple[bytes, int]]":
    """statsd repeater lines from columnar arrays (one newline-joined
    buffer + line count); None when there is no library."""
    return _encode_lines("vn_encode_prometheus_lines", meta_blob, nrows,
                         suffixes, family_types, values, masks,
                         excluded_keys)


def encode_forward_lines(meta_blob, nrows: int, suffixes: list[str],
                         family_types: np.ndarray, values: np.ndarray,
                         masks: np.ndarray, excluded_keys: list[str]
                         ) -> "Optional[tuple[bytes, int]]":
    """Verbatim DogStatsD forward lines (no sanitization) from columnar
    arrays; same contract as encode_prometheus_lines."""
    return _encode_lines("vn_encode_forward_lines", meta_blob, nrows,
                         suffixes, family_types, values, masks,
                         excluded_keys)


def encode_prometheus_exposition(meta_blob, nrows: int,
                                 suffixes: list[str],
                                 family_types: np.ndarray,
                                 values: np.ndarray, masks: np.ndarray,
                                 excluded_keys: list[str]
                                 ) -> "Optional[tuple[bytes, int]]":
    """Prometheus exposition text (`name{k="v"} value` samples, the
    pushgateway body) from columnar arrays; (text, sample_count)."""
    return _encode_lines("vn_encode_prometheus_exposition", meta_blob,
                         nrows, suffixes, family_types, values, masks,
                         excluded_keys)


def deflate(data: bytes) -> Optional[bytes]:
    """zlib deflate with the GIL released (native/emit.cpp vn_deflate);
    byte-identical to zlib.compress(data) — both drive the system zlib
    at default level. None when there is no library."""
    lib = load_library()
    if lib is None:
        return None
    c = ctypes
    out = c.c_char_p()
    out_len = c.c_longlong()
    if lib.vn_deflate(data, len(data), c.byref(out),
                      c.byref(out_len)) < 0:
        return None
    return ctypes.string_at(out, out_len.value)


def stream_frame_encode(seq: int, body: bytes) -> Optional[bytes]:
    """VSF1 frame (magic + u64 LE seq + body) with the GIL released;
    byte-identical to codec.encode_stream_frame_py. None -> caller
    falls back to the Python reference (library or symbol missing,
    seq outside u64)."""
    lib = load_library()
    if lib is None:
        return None
    if not 0 <= seq < 1 << 64:
        return None  # Python raises OverflowError; keep that path
    c = ctypes
    out = c.c_char_p()
    out_len = c.c_longlong()
    if lib.vn_stream_frame_encode(seq, body, len(body), c.byref(out),
                                  c.byref(out_len)) != 0:
        return None
    return ctypes.string_at(out, out_len.value)


def stream_frame_decode(blob: bytes) -> "Optional[tuple[int, bytes]]":
    """(seq, body) for a VSF1 frame; None on a non-frame blob (caller
    raises the pinned ValueError) or a missing library — callers
    distinguish the two with codec_available()."""
    lib = load_library()
    if lib is None:
        return None
    c = ctypes
    seq = c.c_ulonglong()
    off = lib.vn_stream_frame_decode(blob, len(blob), c.byref(seq))
    if off < 0:
        return None
    return seq.value, blob[off:]


def stream_ack_encode(seq: int, status: int) -> Optional[bytes]:
    """9 ack bytes (u64 LE seq + u8 status); None -> Python fallback."""
    lib = load_library()
    if lib is None:
        return None
    if not 0 <= seq < 1 << 64 or not 0 <= status <= 0xFF:
        return None  # Python raises Overflow/ValueError; keep that path
    buf = ctypes.create_string_buffer(9)
    lib.vn_stream_ack_encode(seq, status, buf)
    return buf.raw[:9]


def stream_ack_decode(blob: bytes) -> "Optional[tuple[int, int]]":
    """(seq, status) for a 9-byte ack; None on a non-ack blob or a
    missing library."""
    lib = load_library()
    if lib is None:
        return None
    c = ctypes
    seq = c.c_ulonglong()
    status = lib.vn_stream_ack_decode(blob, len(blob), c.byref(seq))
    if status < 0:
        return None
    return seq.value, status


def dedup_header_encode(sender: bytes, dedup_id: int,
                        count: int) -> Optional[bytes]:
    """VDE1 envelope prefix (magic + u16 LE len + canonical JSON
    header) for a UTF-8 sender; the caller appends the body. None ->
    Python fallback (ints outside i64, malformed UTF-8); ValueError
    for the pinned too-large header."""
    lib = load_library()
    if lib is None:
        return None
    if not (-(1 << 63) <= dedup_id < 1 << 63
            and -(1 << 63) <= count < 1 << 63):
        return None
    c = ctypes
    out = c.c_char_p()
    out_len = c.c_longlong()
    rc = lib.vn_dedup_header_encode(sender, len(sender), dedup_id,
                                    count, c.byref(out),
                                    c.byref(out_len))
    if rc == -2:
        raise ValueError("dedup header too large")
    if rc != 0:
        return None
    return ctypes.string_at(out, out_len.value)


def dedup_header_parse(hdr: bytes) -> "Optional[tuple[str, int, int]]":
    """(sender, id, count) for a canonical VDE1 JSON header; None when
    the header isn't canonical (caller falls back to json.loads for
    the exact Python semantics) or the library is missing."""
    lib = load_library()
    if lib is None:
        return None
    c = ctypes
    sender = c.c_char_p()
    sender_len = c.c_longlong()
    id_out = c.c_longlong()
    count_out = c.c_longlong()
    rc = lib.vn_dedup_header_parse(hdr, len(hdr), c.byref(sender),
                                   c.byref(sender_len), c.byref(id_out),
                                   c.byref(count_out))
    if rc != 0:
        return None
    return (ctypes.string_at(sender, sender_len.value).decode("utf-8"),
            id_out.value, count_out.value)


def source_hash() -> str:
    """Build stamp of the loaded library (sha256 prefix of
    dogstatsd.cpp + emit.cpp + forward_codec.cpp concatenated at build
    time); '' when no library is loadable."""
    lib = load_library()
    return lib.vn_source_hash().decode() if lib is not None else ""


class NativeRouter:
    """Sharded ingest over several workers' native contexts: lines are
    parsed lock-free in C++ and committed to shard digest % N under that
    shard's own mutex (native twin of the reference's Digest%N routing,
    server.go:1028-1039). One router is shared by all reader threads —
    ctypes releases the GIL, so readers parse in parallel."""

    def __init__(self, contexts: list["NativeIngest"]) -> None:
        if not contexts:
            raise ValueError("router needs at least one context")
        self._lib = contexts[0]._lib
        self._contexts = contexts  # keep alive
        self._arr = (ctypes.c_void_p * len(contexts))(
            *[c._ctx for c in contexts])
        self._n = len(contexts)

    def ingest(self, datagram: bytes) -> int:
        return self._lib.vn_ingest_routed(
            self._arr, self._n, datagram, len(datagram))

    # native reader threads (C++ recv loop; no Python on the path) -----------

    def start_reader(self, fd: int, max_len: int, home: int = 0):
        """Spawn a C++ reader thread on an already-bound datagram fd.
        The fd stays owned by the caller (keep the Python socket object
        alive); stop_reader() joins without closing it, preserving
        fd-handoff semantics. `home` picks the shard that absorbs this
        reader's events/service checks and parse errors (spreading the
        funnel across workers)."""
        h = self._lib.vn_reader_start2(
            self._arr, self._n, fd, max_len, home % self._n)
        if not h:
            raise RuntimeError("vn_reader_start failed")
        return h

    def reader_packets(self, handle) -> int:
        return int(self._lib.vn_reader_packets(handle))

    def stop_reader(self, handle) -> int:
        """Join the reader and return its FINAL packet count (the thread
        keeps ingesting up to one recv-timeout tick after the stop flag;
        a pre-join snapshot would undercount)."""
        return int(self._lib.vn_reader_stop(handle))

    def start_stream_reader(self, fd: int, max_len: int, home: int = 0):
        """Spawn a C++ line-stream reader for a plain TCP connection.
        The reader OWNS fd (pass a dup) and closes it on exit; reap
        finished readers with stream_reader_done + stop_stream_reader.
        `home` routes this connection's events/errors like
        start_reader's."""
        h = self._lib.vn_stream_reader_start2(
            self._arr, self._n, fd, max_len, home % self._n)
        if not h:
            raise RuntimeError("vn_stream_reader_start failed")
        return h

    def stream_reader_done(self, handle) -> bool:
        return bool(self._lib.vn_stream_reader_done(handle))

    def stop_stream_reader(self, handle) -> int:
        return int(self._lib.vn_stream_reader_stop(handle))

    def start_ssf_reader(self, ctx_owner: "NativeIngest", fd: int,
                         max_len: int, indicator: bytes, objective: bytes,
                         uniq_rate: float):
        """Spawn a C++ SSF datagram reader committing into ctx_owner's
        context (single-shard: the native SSF path requires one worker)."""
        h = self._lib.vn_ssf_reader_start(
            ctx_owner._ctx, fd, max_len, indicator, len(indicator),
            objective, len(objective), uniq_rate)
        if not h:
            raise RuntimeError("vn_ssf_reader_start failed")
        return h

    def stop_ssf_reader(self, handle) -> int:
        return int(self._lib.vn_ssf_reader_stop(handle))

    def lock_stats(self, shard: int) -> dict:
        """Contention record for one shard's mutex: totals plus the most
        recent (up to 4096) wait/hold samples in ns."""
        return _lock_stats(self._lib, self._contexts[shard]._ctx)

    def reset_lock_stats(self) -> None:
        for c in self._contexts:
            self._lib.vn_lock_stats_reset(c._ctx)


# --------------------------------------------------------------------------
# loadgen: wire-rate traffic generation / capture / replay
# (native/loadgen.cpp — separate .so so the load harness can be absent
# without touching the ingest library)


def load_loadgen_library() -> Optional[ctypes.CDLL]:
    return _load(_LOADGEN_PATH, "vn_lg_source_hash", _LOADGEN_SOURCES,
                 _bind_loadgen)


def _bind_loadgen(lib) -> None:
    c = ctypes
    lib.vn_lg_ring_new.restype = c.c_void_p
    lib.vn_lg_ring_free.argtypes = [c.c_void_p]
    lib.vn_lg_ring_count.restype = c.c_longlong
    lib.vn_lg_ring_count.argtypes = [c.c_void_p]
    lib.vn_lg_ring_total_lines.restype = c.c_longlong
    lib.vn_lg_ring_total_lines.argtypes = [c.c_void_p]
    lib.vn_lg_ring_total_bytes.restype = c.c_longlong
    lib.vn_lg_ring_total_bytes.argtypes = [c.c_void_p]
    lib.vn_lg_ring_hash.restype = c.c_uint64
    lib.vn_lg_ring_hash.argtypes = [c.c_void_p]
    lib.vn_lg_ring_datagram.restype = c.c_longlong
    lib.vn_lg_ring_datagram.argtypes = [
        c.c_void_p, c.c_longlong, c.POINTER(c.c_char_p)]
    lib.vn_lg_ring_append.restype = c.c_longlong
    lib.vn_lg_ring_append.argtypes = [
        c.c_void_p, c.c_char_p, c.c_longlong, c.c_int]
    lib.vn_lg_ring_synth.restype = c.c_longlong
    lib.vn_lg_ring_synth.argtypes = [
        c.c_void_p, c.c_uint64, c.c_longlong, c.c_double,
        c.POINTER(c.c_double), c.c_int, c.c_longlong,
        c.c_char_p, c.c_int, c.c_int, c.c_longlong,
        c.c_longlong, c.c_double, c.c_double, c.c_longlong]
    lib.vn_lg_ring_serialize.restype = c.c_longlong
    lib.vn_lg_ring_serialize.argtypes = [
        c.c_void_p, c.POINTER(c.c_char_p)]
    lib.vn_lg_ring_load.restype = c.c_longlong
    lib.vn_lg_ring_load.argtypes = [c.c_void_p, c.c_char_p,
                                    c.c_longlong]
    lib.vn_lg_send_start.restype = c.c_void_p
    lib.vn_lg_send_start.argtypes = [
        c.c_void_p, c.c_int, c.c_double, c.c_longlong, c.c_int]
    for name in ("vn_lg_send_lines", "vn_lg_send_packets",
                 "vn_lg_send_errors", "vn_lg_send_resyncs",
                 "vn_lg_send_stop"):
        fn = getattr(lib, name)
        fn.restype = c.c_longlong
        fn.argtypes = [c.c_void_p]
    lib.vn_lg_send_done.restype = c.c_int
    lib.vn_lg_send_done.argtypes = [c.c_void_p]
    lib.vn_lg_send_free.restype = None
    lib.vn_lg_send_free.argtypes = [c.c_void_p]
    lib.vn_lg_capture_start.restype = c.c_void_p
    lib.vn_lg_capture_start.argtypes = [c.c_int, c.c_int, c.c_longlong]
    for name in ("vn_lg_capture_packets", "vn_lg_capture_truncated",
                 "vn_lg_capture_stop"):
        fn = getattr(lib, name)
        fn.restype = c.c_longlong
        fn.argtypes = [c.c_void_p]
    lib.vn_lg_capture_detach_ring.restype = c.c_void_p
    lib.vn_lg_capture_detach_ring.argtypes = [c.c_void_p]
    lib.vn_lg_capture_free.argtypes = [c.c_void_p]


def loadgen_available() -> bool:
    return load_loadgen_library() is not None


def loadgen_source_hash() -> str:
    lib = load_loadgen_library()
    return lib.vn_lg_source_hash().decode() if lib is not None else ""


# fixed metric-type order for the synth type-mix weights
LOADGEN_TYPES = ("c", "g", "ms", "h", "s")


class LoadgenRing:
    """Pre-built datagram sequence: synthesize from a workload spec,
    append externally-built payloads (SSF), or load a captured blob.
    Immutable once handed to a sender."""

    def __init__(self) -> None:
        lib = load_loadgen_library()
        if lib is None:
            raise RuntimeError("loadgen library unavailable")
        self._lib = lib
        self._ring = lib.vn_lg_ring_new()

    def __del__(self):
        if getattr(self, "_ring", None):
            self._lib.vn_lg_ring_free(self._ring)
            self._ring = None

    def __len__(self) -> int:
        return int(self._lib.vn_lg_ring_count(self._ring))

    @property
    def total_lines(self) -> int:
        return int(self._lib.vn_lg_ring_total_lines(self._ring))

    @property
    def total_bytes(self) -> int:
        return int(self._lib.vn_lg_ring_total_bytes(self._ring))

    @property
    def content_hash(self) -> int:
        """fnv1a64 over (length, bytes) pairs — the bit-exactness
        witness for capture→replay round trips."""
        return int(self._lib.vn_lg_ring_hash(self._ring))

    def datagram(self, i: int) -> bytes:
        out = ctypes.c_char_p()
        n = self._lib.vn_lg_ring_datagram(self._ring, i,
                                          ctypes.byref(out))
        if n < 0:
            raise IndexError(i)
        return ctypes.string_at(out, n)

    def datagrams(self) -> list[bytes]:
        return [self.datagram(i) for i in range(len(self))]

    def append(self, payload: bytes, lines: int = 1) -> None:
        """Append one externally-built datagram (SSF spans are built in
        Python once at setup; only the send loop is per-packet)."""
        if self._lib.vn_lg_ring_append(self._ring, payload, len(payload),
                                       lines) < 0:
            raise ValueError("bad payload")

    def synth(self, seed: int, n_keys: int, zipf_s: float,
              type_mix: "list[float]", n_tags: int, tag_card: int,
              prefix: bytes, dgram_target: int, n_lines: int,
              tenant_count: int = 1, tenant_abusive_frac: float = 0.0,
              tenant_zipf_s: float = 0.0,
              tenant_churn_keys: int = 0) -> int:
        """Build ~n_lines of DogStatsD traffic. type_mix is 5 weights
        in LOADGEN_TYPES order. tenant_count > 1 stamps a trailing
        tenant:tN tag per line (the last tenant is the abusive one);
        1 is byte-identical single-tenant output. Returns the
        datagram count."""
        mix = (ctypes.c_double * len(LOADGEN_TYPES))(*type_mix)
        n = self._lib.vn_lg_ring_synth(
            self._ring, seed, n_keys, float(zipf_s), mix, n_tags,
            tag_card, prefix, len(prefix), dgram_target, n_lines,
            int(tenant_count), float(tenant_abusive_frac),
            float(tenant_zipf_s), int(tenant_churn_keys))
        if n < 0:
            raise ValueError("invalid workload spec for synth")
        return int(n)

    def serialize(self) -> bytes:
        out = ctypes.c_char_p()
        n = self._lib.vn_lg_ring_serialize(self._ring, ctypes.byref(out))
        return ctypes.string_at(out, n)

    def load(self, blob: bytes) -> int:
        n = self._lib.vn_lg_ring_load(self._ring, blob, len(blob))
        if n < 0:
            raise ValueError("malformed ring blob")
        return int(n)


class LoadgenSender:
    """Paced C++ send thread cycling a ring over a connected socket.
    The caller owns the socket and the ring; both must outlive the
    sender (stop() joins the thread)."""

    def __init__(self, ring: LoadgenRing, fd: int, lines_per_s: float,
                 max_lines: int = 0, stream: bool = False) -> None:
        self._lib = ring._lib
        self._ring = ring  # keep alive
        self._h = self._lib.vn_lg_send_start(
            ring._ring, fd, float(lines_per_s), int(max_lines),
            1 if stream else 0)
        if not self._h:
            raise RuntimeError("vn_lg_send_start failed (empty ring?)")

    @property
    def sent_lines(self) -> int:
        return int(self._lib.vn_lg_send_lines(self._h))

    @property
    def sent_packets(self) -> int:
        return int(self._lib.vn_lg_send_packets(self._h))

    @property
    def send_errors(self) -> int:
        return int(self._lib.vn_lg_send_errors(self._h))

    @property
    def resyncs(self) -> int:
        return int(self._lib.vn_lg_send_resyncs(self._h))

    @property
    def done(self) -> bool:
        return bool(self._lib.vn_lg_send_done(self._h))

    def stop(self) -> float:
        """Join the send thread (idempotent); the final counters stay
        readable afterwards. Returns the loop's elapsed seconds."""
        if not self._h:
            return 0.0
        return self._lib.vn_lg_send_stop(self._h) / 1e9

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._h = None
            self._lib.vn_lg_send_free(h)


class LoadgenCapture:
    """C++ capture thread recording datagrams off a bound socket for
    bit-exact replay. The caller owns the fd (kept blocking with a
    100ms receive timeout, like the ingest readers)."""

    def __init__(self, fd: int, max_len: int = 65536,
                 max_packets: int = 0) -> None:
        lib = load_loadgen_library()
        if lib is None:
            raise RuntimeError("loadgen library unavailable")
        self._lib = lib
        self._h = lib.vn_lg_capture_start(fd, max_len, max_packets)
        if not self._h:
            raise RuntimeError("vn_lg_capture_start failed")
        self._stopped = False

    @property
    def packets(self) -> int:
        return int(self._lib.vn_lg_capture_packets(self._h))

    @property
    def truncated(self) -> int:
        return int(self._lib.vn_lg_capture_truncated(self._h))

    def stop(self) -> int:
        if not self._stopped:
            self._lib.vn_lg_capture_stop(self._h)
            self._stopped = True
        return self.packets

    def detach_ring(self) -> LoadgenRing:
        """Move the captured datagrams into a fresh ring (stop first)."""
        self.stop()
        handle = self._lib.vn_lg_capture_detach_ring(self._h)
        if not handle:
            raise RuntimeError("capture detach failed")
        ring = LoadgenRing.__new__(LoadgenRing)
        ring._lib = self._lib
        ring._ring = handle
        return ring

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.vn_lg_capture_free(self._h)
            self._h = None

"""Device fault domain: guarded execution, breaker, and the dispatch seam.

Every device entry point on the worker's hot path — batch fold,
micro-fold scatter, spill fold, staged-plane fold, flush extract, set
insert, import merge, pool growth, ad-hoc query eval — goes through
``DeviceGuard.call``, which:

1. routes the actual invocation through the module-level ``dispatch``
   seam (the ONE chokepoint seeded fault injection monkeypatches —
   utils/faults.DeviceFaultPlan);
2. classifies any device-side exception into the ``device.fault.*``
   taxonomy (oom / compile / lost / other) and counts it;
3. retries ONCE when the call site declared itself retry-safe (no
   donated operands — retrying a donating jit call would replay against
   invalidated buffers);
4. trips a per-worker breaker after ``streak_limit`` CONSECUTIVE
   failures, after which the worker quarantines its device path and
   fails over to the host engine (ops/host_engine.py) — see
   core/worker.DeviceWorker._quarantine_live;
5. while quarantined, gates re-admission behind a probe
   (compile+fold+extract of a tiny pool, run by the worker once per
   ``probe_interval_s`` — the half-open breaker pattern the health gate
   (PR 14) and delivery manager (PR 5) already use).

Python-level errors (TypeError, ValueError, assertion failures in host
code) are NOT device faults: ``classify`` returns None for them and
``call`` re-raises untouched — a code bug must stay loud, not trip a
failover that masks it.

Escape hatch: ``VENEUR_DEVICE_GUARD=0`` (or config device_guard: false)
constructs the guard disabled — ``call`` invokes the function directly,
no seam, no classification, no breaker — restoring the exact pre-guard
behavior for bisection.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from typing import Callable, Optional

log = logging.getLogger("veneur_tpu.ops.device_guard")

FAULT_KINDS = ("oom", "compile", "lost", "other")

#: default consecutive-failure streak that trips the breaker
DEFAULT_STREAK_LIMIT = 3
#: default seconds between re-admission probes while quarantined
DEFAULT_PROBE_INTERVAL_S = 30.0


def guard_enabled_default() -> bool:
    """Process-wide escape hatch (checked at worker construction)."""
    return os.environ.get("VENEUR_DEVICE_GUARD", "1") not in ("0", "false")


class DeviceFaultError(RuntimeError):
    """A classified device failure, raised by DeviceGuard.call after
    counting (and after the retry, when one was allowed). Carries the
    taxonomy kind and the original exception."""

    def __init__(self, kind: str, op: str, original: BaseException):
        super().__init__(f"device fault [{kind}] in {op}: {original}")
        self.kind = kind
        self.op = op
        self.original = original


# message markers per kind, matched against the exception text. XLA's
# runtime errors carry gRPC-style status prefixes (RESOURCE_EXHAUSTED,
# UNAVAILABLE, ...); PJRT OOMs say "Out of memory"; Mosaic/XLA compile
# failures name the compiler. Matched in this order — an OOM message
# that also mentions compilation is still an OOM.
_OOM_MARKS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
              "Resource exhausted", "Allocation failure", "OOM")
_COMPILE_MARKS = ("Mosaic", "compilation", "Compilation", "compile",
                  "lowering", "XLA translation")
_LOST_MARKS = ("UNAVAILABLE", "FAILED_PRECONDITION", "DATA_LOSS",
               "device lost", "Device lost", "ABORTED", "INTERNAL",
               "device is in an invalid state", "halted")
# exception class names (anywhere in the MRO) that mark a device-side
# runtime error; matched by name so no jaxlib import is needed here
_XLA_CLASS_NAMES = {"XlaRuntimeError", "JaxRuntimeError"}


def classify(exc: BaseException) -> Optional[str]:
    """Map an exception to a fault kind, or None for "not a device
    error — re-raise untouched"."""
    if isinstance(exc, DeviceFaultError):
        return exc.kind
    # injected faults (utils/faults.DeviceFaultPlan) tag themselves so
    # the taxonomy works without faking jaxlib exception classes
    kind = getattr(exc, "device_fault_kind", None)
    if kind is not None:
        return kind if kind in FAULT_KINDS else "other"
    names = {c.__name__ for c in type(exc).__mro__}
    if not (names & _XLA_CLASS_NAMES):
        return None
    msg = str(exc)
    if any(m in msg for m in _OOM_MARKS):
        return "oom"
    if any(m in msg for m in _COMPILE_MARKS):
        return "compile"
    if any(m in msg for m in _LOST_MARKS):
        return "lost"
    return "other"


def dispatch(op: str, fn: Callable, *args, **kwargs):
    """The device dispatch seam — every guarded call funnels through
    this trivial function so seeded fault injection has exactly one
    surface to monkeypatch (utils/faults.install_device_faults). `op`
    names the call site (fold/spill/staged/micro/extract/sets/import/
    grow/probe/query) for per-kind fault scripting."""
    return fn(*args, **kwargs)


#: ops whose dispatch span carries ``bytes`` (the fold and the extract:
#: the two a roofline share is asked of)
_BYTES_OPS = frozenset(("staged", "extract"))


def _nbytes(tree) -> int:
    """Bytes of every array in a tuple/list nest (non-arrays count 0)."""
    if isinstance(tree, (tuple, list)):
        return sum(_nbytes(x) for x in tree)
    return int(getattr(tree, "nbytes", 0))


def guard_span(guard, name: str, **attrs):
    """A span of the guard's record; a no-op (yielding None) where there
    is no guard or no record (the mirror and the set store are also
    built without one)."""
    rec = getattr(guard, "rec", None)
    if rec is None:
        return contextlib.nullcontext()
    return rec.span(name, **attrs)


def wait_span(guard, name: str):
    """A ``wait: true`` span around a call that blocks on the device."""
    return guard_span(guard, name, wait=True)


class DeviceGuard:
    """Per-worker breaker over the guarded device path."""

    def __init__(self, streak_limit: int = DEFAULT_STREAK_LIMIT,
                 probe_interval_s: float = DEFAULT_PROBE_INTERVAL_S,
                 enabled: bool = True,
                 clock: Callable[[], float] = time.monotonic):
        self.enabled = enabled
        self.streak_limit = max(1, int(streak_limit))
        self.probe_interval_s = float(probe_interval_s)
        self._clock = clock
        self._lock = threading.Lock()
        self._streak = 0
        self._quarantined = False
        self._trip_reason: Optional[str] = None
        self._last_probe_t: Optional[float] = None
        self._counters: dict[str, int] = {}
        # last classified fault, for the governor's panic verdict
        self.last_fault: Optional[str] = None
        # the owning worker's span recorder (core/flightrec.py): every
        # guarded dispatch is a span taken here, at the seam
        self.rec = None

    # -- state reads ------------------------------------------------------

    @property
    def quarantined(self) -> bool:
        return self._quarantined

    @property
    def trip_reason(self) -> Optional[str]:
        return self._trip_reason

    def counters(self) -> dict[str, int]:
        with self._lock:
            return dict(self._counters)

    def _bump(self, key: str, n: int = 1) -> None:
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + n

    def bump(self, key: str, n: int = 1) -> None:
        """Public counter hook for guard-adjacent events that happen
        outside call() — e.g. the HBM valve's grow-OOM degradation."""
        self._bump(key, n)

    # -- the guarded call -------------------------------------------------

    def call(self, op: str, fn: Callable, *args, retryable: bool = False,
             attrs: Optional[dict] = None, **kwargs):
        """Run one device operation under the guard. ``attrs`` go onto
        the ``dispatch`` span beside ``op``.

        retryable=True only at call sites whose operands are NOT donated
        (extract, set inserts, query evals, allocation pre-flights): a
        transient fault there retries once against the same still-valid
        inputs. Donating folds must not retry — their inputs may already
        be invalidated — so their faults surface immediately and the
        worker replays the retained HOST inputs through the fallback
        engine instead (the no-epoch-lost contract).
        """
        rec = self.rec
        if rec is None:
            return self._call(op, fn, args, retryable, kwargs)
        with rec.span("dispatch", op=op, **(attrs or {})):
            out = self._call(op, fn, args, retryable, kwargs)
            if op in _BYTES_OPS:
                # operands read plus results written: the least HBM
                # traffic the program can have
                rec.add("bytes", _nbytes(args) + _nbytes(out))
            return out

    def _call(self, op: str, fn: Callable, args: tuple, retryable: bool,
              kwargs: dict):
        if not self.enabled:
            return fn(*args, **kwargs)
        try:
            out = dispatch(op, fn, *args, **kwargs)
        except Exception as exc:
            kind = classify(exc)
            if kind is None:
                raise
            self._note_fault(op, kind)
            if retryable and not self._quarantined:
                self._bump("device.fault.retries")
                try:
                    out = dispatch(op, fn, *args, **kwargs)
                except Exception as exc2:
                    kind2 = classify(exc2)
                    if kind2 is None:
                        raise
                    self._note_fault(op, kind2)
                    raise DeviceFaultError(kind2, op, exc2) from exc2
                self._bump("device.fault.retry_success")
                self._note_success()
                return out
            raise DeviceFaultError(kind, op, exc) from exc
        self._note_success()
        return out

    def _note_fault(self, op: str, kind: str) -> None:
        with self._lock:
            self._counters[f"device.fault.{kind}"] = (
                self._counters.get(f"device.fault.{kind}", 0) + 1)
            self.last_fault = f"{kind}:{op}"
            self._streak += 1
            tripped = (not self._quarantined
                       and self._streak >= self.streak_limit)
            if tripped:
                self._quarantined = True
                self._trip_reason = (
                    f"{self._streak} consecutive device faults,"
                    f" last [{kind}] in {op}")
                self._counters["device.guard.trips"] = (
                    self._counters.get("device.guard.trips", 0) + 1)
                # first probe waits a full interval — the device just
                # proved itself unhealthy
                self._last_probe_t = self._clock()
        if tripped:
            log.error("device breaker OPEN: %s — failing over to host"
                      " engine", self._trip_reason)

    def _note_success(self) -> None:
        # lock-free fast path: this runs after EVERY successful device
        # dispatch, so the healthy path must not pay a lock round trip.
        # The unlocked read is safe — _streak only matters as "nonzero
        # after a fault", and faults serialize through _note_fault's
        # locked section before the next success can observe them.
        if self._streak:
            with self._lock:
                self._streak = 0

    # -- explicit breaker control ----------------------------------------

    def trip(self, reason: str) -> None:
        """Force the breaker open (used when a single fault is already
        proof the device path can't continue, e.g. OOM on pool growth
        after the pre-flight — waiting out a streak would just fault
        the same grow N more times)."""
        with self._lock:
            if self._quarantined:
                return
            self._quarantined = True
            self._trip_reason = reason
            self._counters["device.guard.trips"] = (
                self._counters.get("device.guard.trips", 0) + 1)
            self._last_probe_t = self._clock()
        log.error("device breaker OPEN: %s — failing over to host engine",
                  reason)

    def probe_due(self, now: Optional[float] = None) -> bool:
        """Half-open check: quarantined and a probe interval has passed
        since the trip / last failed probe."""
        with self._lock:
            if not self._quarantined:
                return False
            now = self._clock() if now is None else now
            return (self._last_probe_t is None
                    or now - self._last_probe_t >= self.probe_interval_s)

    def note_probe(self, ok: bool) -> None:
        with self._lock:
            self._counters["device.guard.probes"] = (
                self._counters.get("device.guard.probes", 0) + 1)
            if not ok:
                self._counters["device.guard.probe_failures"] = (
                    self._counters.get("device.guard.probe_failures", 0) + 1)
                self._last_probe_t = self._clock()

    def readmit(self) -> None:
        with self._lock:
            if not self._quarantined:
                return
            self._quarantined = False
            self._trip_reason = None
            self._streak = 0
            self._last_probe_t = None
            self._counters["device.guard.readmissions"] = (
                self._counters.get("device.guard.readmissions", 0) + 1)
        log.warning("device breaker CLOSED: probe succeeded, device path"
                    " re-admitted")

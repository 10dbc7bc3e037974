"""Flush-deadline health subsystem.

The reference makes the flush deadline existential: a flush that
outlives `flush_watchdog_missed_flushes` intervals kills the process
(server.go:948-990). That contract is only honest on hardware that can
extract the whole pool inside the interval — a CPU-only deployment at
1M series measured 320s of extraction against a 10s budget. This
package replaces hope with governance:

- governor.FlushDeadlineGovernor — slices the flush extraction into
  bounded sub-interval chunks (config `flush_chunk_target_ms`) and
  publishes per-chunk progress, so an overlong flush degrades to
  longer-but-bounded instead of unbounded.
- policy — the documented watchdog-vs-shedding contract: an overdue
  flush whose chunks keep completing defers the watchdog panic; a
  stalled chunk does not.
- ledger.TransferLedger — per-flush host<->device byte accounting at
  the two transfer boundaries (compacted staged upload, packed
  extraction readback), pinned by a regression test so the O(samples)
  transfer diet cannot silently regress to O(series x depth).
"""

from veneur_tpu.health.governor import ChunkRun, FlushDeadlineGovernor
from veneur_tpu.health.ledger import TransferLedger
from veneur_tpu.health.policy import stall_window_s, watchdog_should_defer

__all__ = [
    "ChunkRun",
    "FlushDeadlineGovernor",
    "TransferLedger",
    "stall_window_s",
    "watchdog_should_defer",
]

"""Compile counting + HBM snapshots.

An unexpected XLA recompile mid-run is one of the most expensive silent
failures a jit-based trainer has: a shape or layout that drifts after
warmup stalls every step behind a minutes-long compile, and nothing in the
default logs says so (the ZeRO-1 gate work in round 7 found exactly this
class of problem — warm-cache runs that LOOKED fine). CompileWatch hangs a
listener on jax.monitoring's compile-duration events and keeps counts +
cumulative durations; after `mark_steady()` every further compile fires the
warn callback loudly.

HBM tracking: `hbm_snapshot()` polls `device.memory_stats()` (PJRT exposes
bytes_in_use / peak_bytes_in_use on TPU; CPU returns None) — creep between
snapshots is the "this run will OOM at step 40k" early warning.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional

# jax fires these through jax.monitoring.record_event_duration_secs (the
# names live in jax._src.dispatch; matched by substring so a path shuffle
# in a future jax degrades to "no events seen", never an ImportError).
# The event wraps compile-or-load: it fires for a persistent-cache hit too,
# with the (short) retrieval time as its duration.
_COMPILE_EVENT_SUBSTRINGS = ("backend_compile",)
# plain event (jax._src.compiler): one per executable loaded from the
# persistent compilation cache instead of compiled
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileWatch:
    """Counts XLA compiles via jax.monitoring; loud after warmup.

    install() registers the listeners (idempotent); uninstall() detaches
    them.
    """

    def __init__(self, warn: Optional[Callable[[str], None]] = None,
                 registry=None):
        self._warn = warn
        self._installed = False
        self._steady = False
        self._lock = threading.Lock()
        self.compiles = 0
        self.compile_secs = 0.0
        self.compiles_after_steady = 0
        self.cache_hits = 0
        self.durations: List[float] = []
        # registry publication (telemetry/registry.py): compiles tick live
        # so a /metrics scrape sees a recompile storm as it happens
        self._compiles_total = self._compile_secs_total = None
        if registry is not None:
            self._compiles_total = registry.counter(
                "bert_xla_compiles_total", "XLA backend compiles")
            self._compile_secs_total = registry.counter(
                "bert_xla_compile_seconds_total",
                "cumulative XLA compile time (s)")

    # -- listener lifecycle -------------------------------------------------

    def install(self) -> "CompileWatch":
        import jax.monitoring

        if not self._installed:
            jax.monitoring.register_event_duration_secs_listener(
                self._on_duration)
            jax.monitoring.register_event_listener(self._on_event)
            self._installed = True
        return self

    def uninstall(self) -> None:
        import jax.monitoring

        if self._installed:
            jax.monitoring.unregister_event_duration_listener(
                self._on_duration)
            jax.monitoring.unregister_event_listener(self._on_event)
            self._installed = False

    def _on_event(self, event: str, **kw) -> None:
        if event == _CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def _on_duration(self, event: str, duration_secs: float, **kw) -> None:
        if not any(s in event for s in _COMPILE_EVENT_SUBSTRINGS):
            return
        with self._lock:
            self.compiles += 1
            self.compile_secs += duration_secs
            self.durations.append(duration_secs)
            steady = self._steady
            if steady:
                self.compiles_after_steady += 1
        if self._compiles_total is not None:
            self._compiles_total.inc()
            self._compile_secs_total.inc(duration_secs)
        if steady and self._warn is not None:
            self._warn(
                f"RECOMPILE after warmup: compile #{self.compiles} took "
                f"{duration_secs:.2f}s — a shape/layout/donation drift is "
                "stalling the step pipeline (jax.log_compiles=True to see "
                "which program)")

    # -- policy -------------------------------------------------------------

    def mark_steady(self) -> None:
        """Call once warmup compiles are done (first logged interval);
        compiles after this point warn. Idempotent."""
        self._steady = True

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {
                "compiles": self.compiles,
                "compile_secs": round(self.compile_secs, 3),
                "recompiles_after_warmup": self.compiles_after_steady,
                "compile_cache_hits": self.cache_hits,
            }


def hbm_snapshot(devices=None) -> Dict[str, float]:
    """Max over local devices of PJRT memory_stats; {} where the backend
    exposes none (CPU). Bytes, not GiB — the consumer formats."""
    import jax

    if devices is None:
        devices = jax.local_devices()
    peak, in_use, limit = [], [], []
    for d in devices:
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            pass
        if not stats:
            continue
        if "peak_bytes_in_use" in stats:
            peak.append(stats["peak_bytes_in_use"])
        if "bytes_in_use" in stats:
            in_use.append(stats["bytes_in_use"])
        if "bytes_limit" in stats:
            limit.append(stats["bytes_limit"])
    out: Dict[str, float] = {}
    if peak:
        out["hbm_peak_bytes"] = max(peak)
    if in_use:
        out["hbm_bytes_in_use"] = max(in_use)
    if limit:
        out["hbm_bytes_limit"] = max(limit)
    return out

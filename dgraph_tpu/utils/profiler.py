"""Wall-clock sampling profiler — the flight recorder's attribution
tool for the GIL-bound residual the perf captures keep hitting.

A capture walks `sys._current_frames()` at DGRAPH_TPU_PROFILE_HZ for a
bounded window and folds every sampled stack into flamegraph-compatible
folded-stack lines (``root;child;leaf count``) — feed the output
straight to flamegraph.pl / speedscope. The sampler thread exists ONLY
for the duration of a capture, so the armed-but-idle cost is exactly
zero: no thread, no timer, no allocation.

Two triggers:

* on demand — ``/debug/profile?seconds=N`` (start_debug_http) blocks
  its handler thread for the window and returns the folded text;
* automatic — `AUTO.check()` rides the metrics-history tick and fires
  a capture when the 300s query SLO burn rate exceeds
  DGRAPH_TPU_PROFILE_BURN (cooldown DGRAPH_TPU_PROFILE_COOLDOWN_S);
  the folded output is retained for ``/debug/profile?last=1`` and the
  debug bundle, so the evidence of a burn exists even when nobody was
  watching.

Sampling is observation-only: frames are read, never mutated, and no
query-path code changes behavior based on an active capture. METRICS
is never called while a profiler lock is held (lock-order
discipline).
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Dict, Optional

from dgraph_tpu.utils.observe import METRICS

# stack frames deeper than this fold into their 64-frame prefix
_MAX_DEPTH = 64


class SamplingProfiler:
    """One capture at a time (concurrent requests serialize on the
    busy flag — two interleaved samplers would halve each other's
    effective rate and double the overhead). The lock guards ONLY the
    flag flips, so the sampling loop never sleeps under a lock."""

    def __init__(self):
        self._lock = threading.Lock()
        self._busy = False

    @staticmethod
    def _frame_label(f) -> str:
        code = f.f_code
        return (
            f"{code.co_name} "
            f"({os.path.basename(code.co_filename)}:{f.f_lineno})"
        )

    def profile(self, seconds: float, hz: Optional[int] = None) -> str:
        """Sample every thread but the sampler for `seconds`; returns
        folded-stack lines sorted by sample count (descending)."""
        from dgraph_tpu.x import config

        rate = int(hz) if hz else int(config.get("PROFILE_HZ"))
        interval = 1.0 / max(1, rate)
        me = threading.get_ident()
        counts: Dict[str, int] = {}
        nsamples = 0
        while True:
            with self._lock:
                if not self._busy:
                    self._busy = True
                    break
            time.sleep(0.01)  # another capture is draining
        METRICS.set_gauge("profiler_active", 1.0)
        try:
            deadline = time.monotonic() + max(0.0, float(seconds))
            while time.monotonic() < deadline:
                t0 = time.monotonic()
                for tid, frame in sys._current_frames().items():
                    if tid == me:
                        continue
                    stack = []
                    f = frame
                    while f is not None and len(stack) < _MAX_DEPTH:
                        stack.append(self._frame_label(f))
                        f = f.f_back
                    stack.reverse()
                    key = ";".join(stack)
                    counts[key] = counts.get(key, 0) + 1
                    nsamples += 1
                time.sleep(
                    max(0.0, interval - (time.monotonic() - t0))
                )
        finally:
            with self._lock:
                self._busy = False
            METRICS.set_gauge("profiler_active", 0.0)
        METRICS.inc("profiler_samples_total", nsamples)
        lines = [
            f"{k} {v}"
            for k, v in sorted(counts.items(), key=lambda kv: -kv[1])
        ]
        return "\n".join(lines) + ("\n" if lines else "")


class AutoProfiler:
    """Sustained-burn trigger: `check()` (called once per metrics-
    history tick) fires a background capture when the 300s query burn
    rate exceeds DGRAPH_TPU_PROFILE_BURN, at most once per cooldown.
    The capture runs off-tick in its own daemon thread so the history
    sampler never blocks for the profile window."""

    def __init__(self):
        self._lock = threading.Lock()
        self._last_folded: Optional[str] = None
        self._last_info: Optional[dict] = None
        self._last_trigger: Optional[float] = None
        self._running = False

    def last(self) -> Optional[str]:
        """Folded stacks of the most recent auto-capture, or None."""
        with self._lock:
            return self._last_folded

    def last_info(self) -> Optional[dict]:
        """{ts, seconds, burn} of the most recent auto-capture."""
        with self._lock:
            return dict(self._last_info) if self._last_info else None

    @staticmethod
    def _query_burn_300s() -> Optional[float]:
        from dgraph_tpu.utils.observe import _SLO_TRACKED

        slo = _SLO_TRACKED.get("query_latency_seconds")
        if slo is None:
            return None
        w = slo.report()["windows"].get("300s") or {}
        if not w.get("total"):
            return None
        return w.get("burn_rate")

    def check(self) -> bool:
        """Returns True when a capture was triggered this call."""
        from dgraph_tpu.x import config

        if not bool(config.get("PROFILE_AUTO")):
            return False
        burn = self._query_burn_300s()
        if burn is None or burn <= float(config.get("PROFILE_BURN")):
            return False
        now = time.monotonic()
        cooldown = float(config.get("PROFILE_COOLDOWN_S"))
        with self._lock:
            if self._running:
                return False
            if (
                self._last_trigger is not None
                and now - self._last_trigger < cooldown
            ):
                return False
            self._running = True
            self._last_trigger = now
        METRICS.inc("profiler_auto_triggers_total")
        threading.Thread(
            target=self._capture,
            args=(float(config.get("PROFILE_AUTO_S")), burn),
            daemon=True,
            name="auto-profiler",
        ).start()
        return True

    def _capture(self, seconds: float, burn: float) -> None:
        try:
            folded = PROFILER.profile(seconds)
        except Exception:
            folded = ""
        with self._lock:
            self._last_folded = folded or None
            self._last_info = {
                "ts": time.time(),
                "seconds": seconds,
                "burn": burn,
            }
            self._running = False


PROFILER = SamplingProfiler()
AUTO = AutoProfiler()

"""Device-plane observability — the cost and memory of every compiled
program, and live device-memory telemetry (docs/OBSERVABILITY.md "Device
plane").

The host-side obs plane (trace.py/metrics.py) sees every framework span and
RPC hop but is blind below the jit boundary. The reference's
``src/profiler`` keeps per-op device stats and an ``aggregate_stats``
memory table (TBV, SURVEY.md §5.1); our XLA mapping gets the same facts from
the compiler itself:

- **The cost registry** (:func:`record`, :func:`cost_of`, :func:`costs`):
  ``progcache.build`` — the one place a program is built — reads
  ``compiled.cost_analysis()`` (flops, bytes accessed) and
  ``compiled.memory_analysis()`` (argument/output/temp/generated-code
  bytes) into its site's ``compile_log`` entry and, with telemetry on,
  files the record here under the program key's (site, label): the
  ``device.*`` metrics and a ``device.compile`` instant event (the
  top-programs table in ``tools/trace_report.py``). Registry, entry and
  persistent cache derive identity through ``progcache.program_key``, so a
  cached program and its cost record can never disagree.
- **Live-memory telemetry** (:func:`sample`): a sampled ``device.live_bytes``
  gauge (device ``memory_stats()`` where the backend reports it, the
  ``jax.live_arrays()`` sum elsewhere), exported as a Perfetto counter
  track in the chrome trace and as a Prometheus gauge via the existing
  TELEMETRY plane, with a steady-state :class:`LeakDetector` that flags
  monotonic growth (a retained-array leak) and stays quiet over a
  steady-state fit.

MFU is not kept here: it is the benchmark's ``train_mfu_pct``
(``benchmark/flops.py`` over the trace, peaks in ``benchmark/peaks.json``).
Everything here is zero-cost when telemetry is off (``obs.enable()`` /
``MXNET_OBS=1``).
"""
from __future__ import annotations

import os
import threading
from typing import Dict, Optional, Tuple

from . import metrics as _metrics
from . import trace as _trace

__all__ = ["record", "cost_of", "costs", "live_bytes", "sample",
           "LeakDetector", "monitor", "reset"]

# (site, label) → cost record. Sites: "update" (fused engine), "serve",
# "decode", "executor", "cachedop". Bounded by program count (itself
# bounded by the engines' cache-key accounting).
_COSTS: Dict[Tuple[str, str], dict] = {}
_lock = threading.Lock()


def record(site: str, label: str, cost: dict) -> None:
    """File a program's cost record: the (site,label) registry, the
    ``device.*`` metrics mirror, and a ``device.compile`` instant event
    (the trace-side row ``tools/trace_report.py`` tabulates)."""
    with _lock:
        _COSTS[(site, str(label))] = cost
    if _trace._ENABLED:
        reg = _metrics.registry
        reg.counter("device.compile.count").inc()
        reg.counter("device.compile.flops_total").inc(cost.get("flops", 0))
        reg.counter("device.compile.bytes_total").inc(
            cost.get("bytes_accessed", 0))
        reg.gauge(f"device.{site}.flops").set(cost.get("flops", 0))
        reg.gauge(f"device.{site}.peak_hbm_bytes").set(
            cost.get("peak_hbm_bytes", 0))
        peak = reg.gauge("device.peak_hbm_bytes")
        if cost.get("peak_hbm_bytes", 0) > peak.value:
            peak.set(cost["peak_hbm_bytes"])
        _trace.tracer.event("device.compile", site=site, label=str(label),
                            **{k: cost.get(k, 0)
                               for k in ("flops", "bytes_accessed",
                                         "peak_hbm_bytes")})


def cost_of(site: str, label: str) -> Optional[dict]:
    return _COSTS.get((site, str(label)))


def costs() -> Dict[Tuple[str, str], dict]:
    """Snapshot of every recorded program cost (tests, reports)."""
    with _lock:
        return dict(_COSTS)


# ---------------------------------------------------------------------------
# live device memory + leak detection
# ---------------------------------------------------------------------------

def live_bytes() -> int:
    """Current device-resident bytes: the backend allocator's
    ``bytes_in_use`` where reported (TPU/GPU), else the ``jax.live_arrays``
    sum (CPU — the PJRT CPU client reports no memory_stats)."""
    import jax

    total, found = 0, False
    for d in jax.devices():
        try:
            ms = d.memory_stats()
        except Exception:  # lint-ok: stats are optional per backend
            ms = None
        if ms and ms.get("bytes_in_use") is not None:
            total += int(ms["bytes_in_use"])
            found = True
    if found:
        return total
    return int(sum(a.nbytes for a in jax.live_arrays()))


class LeakDetector:
    """Steady-state leak detector over sampled live-bytes.

    A training loop's device footprint is a step function: big at compile
    (temp buffers, donated swaps), then FLAT — parameters update in place.
    Monotonic growth across steady-state steps means something retains
    arrays per step (the classic "append outputs to a list" leak). The
    detector drops ``warmup`` samples (compile/warmup allocations look
    exactly like a leak), then fits a least-squares slope over a sliding
    ``window``; it fires when the slope exceeds ``threshold_bytes_per_step``
    AND the window actually rose end-to-end (slope alone can be a single
    spike's artifact). After firing it re-arms only after a full fresh
    window, so a real leak logs once per window, not once per step.
    """

    def __init__(self, window: int = 10, warmup: int = 3,
                 threshold_bytes_per_step: float = 1 << 20):
        self.window = int(window)
        self.warmup = int(warmup)
        self.threshold = float(threshold_bytes_per_step)
        self._samples: list = []
        self._seen = 0
        self._cooldown = 0
        self.findings: list = []

    def observe(self, nbytes: int) -> Optional[dict]:
        """Feed one sample; returns a finding dict when a leak is flagged
        (and records it in ``findings``), else None."""
        self._seen += 1
        if self._seen <= self.warmup:
            return None
        self._samples.append(float(nbytes))
        if len(self._samples) > self.window:
            self._samples.pop(0)
        if self._cooldown > 0:
            self._cooldown -= 1
            return None
        n = len(self._samples)
        if n < self.window:
            return None
        # least-squares slope over x = 0..n-1
        xs = range(n)
        mean_x = (n - 1) / 2.0
        mean_y = sum(self._samples) / n
        sxx = sum((x - mean_x) ** 2 for x in xs)
        sxy = sum((x - mean_x) * (y - mean_y)
                  for x, y in zip(xs, self._samples))
        slope = sxy / sxx if sxx else 0.0
        grew = self._samples[-1] - self._samples[0]
        if slope > self.threshold and grew > self.threshold * (n - 1) / 2:
            finding = {"slope_bytes_per_step": round(slope, 1),
                       "window": n,
                       "grew_bytes": round(grew, 1),
                       "live_bytes": int(self._samples[-1])}
            self.findings.append(finding)
            self._cooldown = self.window
            return finding
        return None

    def reset(self) -> None:
        self._samples.clear()
        self._seen = 0
        self._cooldown = 0
        self.findings.clear()


# the process-global monitor fed by sample(); threshold tuned for real
# leaks (a retained activation is MBs/step), override via env for tests
monitor = LeakDetector(
    window=int(os.environ.get("MXNET_DEVICE_LEAK_WINDOW", "10")),
    threshold_bytes_per_step=float(
        os.environ.get("MXNET_DEVICE_LEAK_BYTES_PER_STEP", str(1 << 20))))


def sample(**attrs) -> Optional[int]:
    """Sample live device bytes into the ``device.live_bytes`` gauge, the
    chrome-trace counter track, and the leak detector. The per-batch call
    sites (Module.fit loop, serve execute) gate on the obs flag via this
    function — one flag check when telemetry is off.

    ``MXNET_OBS_MEMORY=0`` disables sampling even with telemetry on (the
    ``jax.live_arrays`` walk is O(live buffers) on CPU)."""
    if not _trace._ENABLED:
        return None
    if os.environ.get("MXNET_OBS_MEMORY", "").lower() in ("0", "false",
                                                          "no", "off"):
        return None
    n = live_bytes()
    _metrics.registry.gauge("device.live_bytes").set(n)
    _trace.tracer.counter("device.live_bytes", n)
    finding = monitor.observe(n)
    if finding is not None:
        _metrics.registry.counter("device.leak_suspected").inc()
        _trace.tracer.event("device.leak_suspected", **dict(finding, **attrs))
    return n


def reset() -> None:
    """Drop recorded program costs and the leak monitor's state (tests; a
    fresh run starts empty)."""
    with _lock:
        _COSTS.clear()
    monitor.reset()

"""Device-plane observability — XLA cost & memory accounting, MFU/roofline
attribution, and live device-memory telemetry (docs/OBSERVABILITY.md
"Device plane").

The host-side obs plane (trace.py/metrics.py) sees every framework span and
RPC hop but is blind below the jit boundary: no compiled program reported
its FLOPs, bytes, or HBM footprint, so an MFU number could only be
re-measured, never *attributed*. The reference's ``src/profiler`` keeps
per-op device stats and an ``aggregate_stats`` memory table (TBV, SURVEY.md
§5.1); our XLA mapping gets the same facts from the compiler itself:

- **Cost accounting** (:func:`capture`): every compiled-program choke point
  (``optimizer/fused.py``, ``serve/engine.py``, the Executor jit sites,
  CachedOp, ``parallel.ShardedTrainer``) lowers its program through the AOT
  path when capture is active, reads ``compiled.cost_analysis()`` (flops,
  bytes accessed) + ``compiled.memory_analysis()`` (argument/output/temp/
  generated-code bytes), folds the numbers into its own ``compile_log``
  entry, and keeps the *same* compiled executable for execution — one
  compile, measured and run. Records mirror into ``device.*`` metrics and a
  ``device.compile`` instant event (the top-programs table in
  ``tools/trace_report.py``). The (site, label) → cost registry here is
  the program-identity/cost store the persistent AOT program cache
  (``mxnet_tpu/progcache.py``) keys off — both derive identity through
  ``progcache.program_key``, so a cached program and its cost record can
  never disagree.
- **MFU/roofline attribution** (:func:`attribute`): folding an execute
  span's wall duration with its program's cost record gives analytic MFU
  (``flops / dt / peak``) and a roofline class — compute-bound when the
  program's operational intensity (FLOP/byte) clears the machine balance
  point (peak FLOPs / peak bandwidth), bandwidth-bound otherwise — per
  phase (forward/backward/update/serve.execute). :func:`set_peak` pins
  another denominator. This is the compiler's FLOP count over host wall
  time, NOT the ledger's ``train_mfu_pct`` (``benchmark/flops.py``).
- **Live-memory telemetry** (:func:`sample`): a sampled ``device.live_bytes``
  gauge (device ``memory_stats()`` where the backend reports it, the
  ``jax.live_arrays()`` sum elsewhere), exported as a Perfetto counter
  track in the chrome trace and as a Prometheus gauge via the existing
  TELEMETRY plane, with a steady-state :class:`LeakDetector` that flags
  monotonic growth (a retained-array leak) and stays quiet over a
  steady-state fit.

Activation follows the obs contract — zero-cost when off: capture runs
when telemetry is enabled (``obs.enable()`` / ``MXNET_OBS=1``) or when
``MXNET_DEVICE_COST=1`` forces it (program costs captured without
paying span overhead); ``MXNET_DEVICE_COST=0`` forces it off even
with telemetry on (the escape hatch if an exotic backend rejects AOT
lowering).
"""
from __future__ import annotations

import os
import threading
from typing import Dict, Optional, Tuple

from . import metrics as _metrics
from . import trace as _trace

__all__ = ["active", "capture", "analyze_compiled", "record", "cost_of",
           "costs", "attribute", "annotate_span", "roofline_class",
           "set_peak", "get_peak", "nominal_peak", "DEVICE_PEAKS",
           "live_bytes", "sample", "LeakDetector",
           "monitor", "reset"]

# ---------------------------------------------------------------------------
# activation
# ---------------------------------------------------------------------------


def active() -> bool:
    """Should compile sites capture device cost? ``MXNET_DEVICE_COST``
    forces (1) or vetoes (0); default follows the one obs flag."""
    env = os.environ.get("MXNET_DEVICE_COST", "").lower()
    if env in ("1", "true", "yes", "on"):
        return True
    if env in ("0", "false", "no", "off"):
        return False
    return _trace._ENABLED


# ---------------------------------------------------------------------------
# peaks (the MFU denominator and the roofline ceiling)
# ---------------------------------------------------------------------------

# Published single-chip peaks keyed by jax's ``device_kind``: (dense bf16
# TFLOP/s, HBM GB/s); the benchmark keeps its own in benchmark/peaks.json
# (ROADMAP D13). A TPU kind that is not listed raises: a default would put some
# other chip's ceiling under this one's numbers.
DEVICE_PEAKS = {
    "TPU v5 lite": (197.0, 819.0),  # Google Cloud documentation, "TPU v5e"
}
# The CPU backend has no published peak; a fixed placeholder keeps the
# attribution internally consistent there (never absolute, never a device
# metric).
_CPU_PLACEHOLDER_PEAK = (0.2, 20.0)
_peak_override: list = [None, None]        # [tflops, gbps]


def set_peak(tflops: Optional[float] = None, gbps: Optional[float] = None):
    """Pin the peak compute rate (TFLOP/s) and/or memory bandwidth (GB/s)
    used by MFU/roofline math, e.g. a measured matmul peak."""
    if tflops is not None:
        _peak_override[0] = float(tflops)
    if gbps is not None:
        _peak_override[1] = float(gbps)


def nominal_peak() -> Tuple[float, float]:
    """The published (TFLOP/s, GB/s) of the device this process runs on."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return _CPU_PLACEHOLDER_PEAK
    if dev.device_kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peak for device kind {dev.device_kind!r}: add "
            "it, with its source, to obs.device.DEVICE_PEAKS")
    return DEVICE_PEAKS[dev.device_kind]


def get_peak() -> Tuple[float, float]:
    """(peak_tflops, peak_gbps): explicit ``set_peak`` wins, then the
    ``MXNET_DEVICE_PEAK_TFLOPS``/``_GBPS`` env, then the device's published
    peak (:func:`nominal_peak`)."""
    tflops, gbps = _peak_override
    if tflops is None:
        env = os.environ.get("MXNET_DEVICE_PEAK_TFLOPS")
        tflops = float(env) if env else None
    if gbps is None:
        env = os.environ.get("MXNET_DEVICE_PEAK_GBPS")
        gbps = float(env) if env else None
    if tflops is None or gbps is None:
        dt, db = nominal_peak()
        tflops = dt if tflops is None else tflops
        gbps = db if gbps is None else gbps
    return tflops, gbps


# ---------------------------------------------------------------------------
# cost capture
# ---------------------------------------------------------------------------

# (site, label) → cost record. Sites: "update" (fused engine), "serve",
# "executor", "cachedop", "train_step". The registry the attribution path
# and cost_of() read back; bounded by program count (itself bounded by the
# engines' cache-key accounting).
_COSTS: Dict[Tuple[str, str], dict] = {}
_lock = threading.Lock()

# cost-record field order is the compile_log/report schema; keep stable
COST_FIELDS = ("flops", "bytes_accessed", "argument_bytes", "output_bytes",
               "temp_bytes", "generated_code_bytes", "alias_bytes",
               "peak_hbm_bytes")


def analyze_compiled(compiled) -> dict:
    """Extract the cost/memory record from a ``jax.stages.Compiled``.
    Missing analyses (backend-dependent) just leave fields at 0 — the
    record is always structurally complete."""
    cost: dict = {k: 0 for k in COST_FIELDS}
    try:
        ca = compiled.cost_analysis()
        if ca:
            cost["flops"] = int(ca.get("flops", 0) or 0)
            cost["bytes_accessed"] = int(ca.get("bytes accessed", 0) or 0)
    except Exception:  # lint-ok: cost analysis is best-effort by contract
        pass
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            arg = int(getattr(ma, "argument_size_in_bytes", 0))
            out = int(getattr(ma, "output_size_in_bytes", 0))
            tmp = int(getattr(ma, "temp_size_in_bytes", 0))
            code = int(getattr(ma, "generated_code_size_in_bytes", 0))
            alias = int(getattr(ma, "alias_size_in_bytes", 0))
            cost.update(argument_bytes=arg, output_bytes=out, temp_bytes=tmp,
                        generated_code_bytes=code, alias_bytes=alias,
                        # donated buffers alias an argument into an output;
                        # counting both would double the footprint
                        peak_hbm_bytes=max(arg + out + tmp + code - alias, 0))
    except Exception:  # lint-ok: memory analysis is best-effort by contract
        pass
    return cost


def capture(jitted, args: tuple, site: str = None, label: str = None,
            kwargs=None, key=None):
    """AOT-compile ``jitted`` (a ``jax.jit`` wrapper) for the given example
    ``args`` and return ``(compiled, cost)``.

    The caller keeps ``compiled`` as its executable for this signature —
    ONE compile serves both accounting and execution (no double-compile
    tax). On any failure (exotic backend, lowering restriction) returns
    ``(None, None)`` and the caller stays on its ``jax.jit`` path —
    capture must never break dispatch.

    ``key`` takes a :class:`~mxnet_tpu.progcache.ProgramKey` — the ONE
    shared program-identity derivation (``progcache.program_key``): the
    registry files under its (site, label) and the cost record carries
    its digest, so the device plane, ``compile_log`` entries, and the
    persistent program cache can never key the same program differently.
    """
    if key is not None:
        site, label = key.site, key.label
    try:
        lowered = jitted.lower(*args, **(kwargs or {}))
        compiled = lowered.compile()
    except Exception:  # lint-ok: fall back to the jit path, never raise
        return None, None
    cost = analyze_compiled(compiled)
    if key is not None:
        cost = dict(cost, program_key=key.digest)
    record(site, label, cost)
    return compiled, cost


def adopt_cached_cost(key, meta: dict) -> dict:
    """Cost salvage for a persistent program-cache hit
    (``mxnet_tpu/progcache.py``): the writer's compile-time cost analysis
    rides the cache entry's metadata, so the registry/MFU attribution work
    on hits without re-analyzing. Filters ``meta`` down to
    :data:`COST_FIELDS` and — when the device plane records — files it
    under the entry's shared ProgramKey. Returns the cost dict, ``{}``
    when the writer captured none (callers skip an all-zero record)."""
    cost = {k: meta[k] for k in COST_FIELDS if k in meta}
    if not any(cost.values()):
        return {}
    if active():
        record(key.site, key.label, dict(cost, program_key=key.digest))
    return cost


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
# MFU + roofline attribution
# ---------------------------------------------------------------------------

def roofline_class(cost: Optional[dict], peak_tflops: Optional[float] = None,
                   peak_gbps: Optional[float] = None) -> Optional[dict]:
    """Classify a program against the roofline: its operational intensity
    (FLOP per byte of HBM traffic) vs the machine balance point
    (peak FLOPs / peak bandwidth). Returns None when the record can't
    support the math (zero flops or bytes)."""
    if not cost:
        return None
    flops = cost.get("flops") or 0
    byt = cost.get("bytes_accessed") or 0
    if flops <= 0 or byt <= 0:
        return None
    pt, pb = get_peak()
    if peak_tflops is not None:
        pt = peak_tflops
    if peak_gbps is not None:
        pb = peak_gbps
    intensity = flops / byt
    balance = (pt * 1e12) / (pb * 1e9)
    return {"intensity_flop_per_byte": round(intensity, 3),
            "machine_balance_flop_per_byte": round(balance, 3),
            "bound": "compute" if intensity >= balance else "bandwidth"}


def attribute(phase: str, seconds: float, cost: Optional[dict],
              peak_tflops: Optional[float] = None,
              peak_gbps: Optional[float] = None) -> dict:
    """Fold one program execution (wall ``seconds``) with its cost record:
    returns span attrs ``{analytic_mfu, achieved_tflops, roofline}`` and
    feeds the ``device.mfu.<phase>`` histogram. Phases: forward / backward
    / update / serve.execute (docs/OBSERVABILITY.md). Empty dict when
    there's nothing to attribute — callers splat it into span attrs."""
    if not cost or seconds <= 0:
        return {}
    flops = cost.get("flops") or 0
    if flops <= 0:
        return {}
    pt, pb = get_peak()
    if peak_tflops is not None:
        pt = peak_tflops
    if peak_gbps is not None:
        pb = peak_gbps
    achieved = flops / seconds / 1e12
    mfu = achieved / pt if pt > 0 else 0.0
    rl = roofline_class(cost, pt, pb)
    attrs = {"analytic_mfu": round(mfu, 6),
             "achieved_tflops": round(achieved, 6)}
    if rl:
        attrs["roofline"] = rl["bound"]
    if _trace._ENABLED:
        # MFU is a ratio — fine-grained low buckets, not the latency ladder
        _metrics.registry.histogram(
            f"device.mfu.{phase}",
            buckets=(0.01, 0.02, 0.05, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7,
                     0.8, 0.9, 1.0)).observe(mfu)
        _metrics.registry.gauge(f"device.{phase}.analytic_mfu").set(
            round(mfu, 6))
    return attrs


def annotate_span(span, phase: str, seconds: float,
                  cost: Optional[dict]) -> dict:
    """``attribute`` + fold the attrs into a live span (before its
    ``__exit__`` records it). No-op on the shared no-op span."""
    attrs = attribute(phase, seconds, cost)
    if attrs:
        span.set(**attrs)
    return attrs


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
    """Drop recorded program costs, peaks, and the leak monitor's state
    (tests; a fresh run starts empty)."""
    with _lock:
        _COSTS.clear()
    _peak_override[0] = _peak_override[1] = None
    monitor.reset()

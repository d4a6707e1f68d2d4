"""``mx.profiler`` over jax.profiler.

Reference: ``src/profiler/`` + ``python/mxnet/profiler.py`` (TBV —
SURVEY.md §5.1). The reference hooks the engine and dumps chrome-trace
JSON; here XLA's profiler produces an XPlane/perfetto trace (viewable in
TensorBoard/Perfetto, superset of the chrome-trace view). Per-op
attribution inside jitted programs comes from ``named_scope`` annotations
(``mx.profiler.scope``).
"""
from __future__ import annotations

import contextlib
import os
import warnings

import jax

__all__ = ["set_config", "set_state", "dump", "dumps", "pause", "resume",
           "scope", "Profiler", "DispatchCounts", "count_dispatches",
           "count_dispatch", "counting_dispatches"]

_config = {"filename": "profile.json", "profile_all": False, "aggregate_stats": False}
_state = {"running": False, "dir": None}

# ---------------------------------------------------------------------------
# Aggregate per-op statistics (reference MXAggregateProfileStatsPrint /
# src/profiler/aggregate_stats.cc — TBV). The engine hook becomes a timing
# wrapper at the eager dispatch choke point (ndarray.invoke): active only
# while the profiler runs with aggregate_stats=True, because accurate
# per-op timing must block on the async dispatch (NaiveEngine-style).
# ---------------------------------------------------------------------------

_agg: dict = {}


def aggregate_active() -> bool:
    return _state["running"] and bool(_config.get("aggregate_stats"))


# ---------------------------------------------------------------------------
# Dispatch counting — the honest "how many compiled device programs did this
# step execute" metric behind the perf tests (``pytest -m perf``).
# Hook points: ndarray.invoke (each eager op is one compiled execution),
# the fused update engine, Executor forward/backward, CachedOp calls, and
# NDArray.asnumpy (device→host transfers).  Works on any backend, CPU
# included — it counts dispatches, not device time.
#
# Storage is the obs metrics registry (``dispatch.*`` counters): every
# count_dispatch() call feeds the registry, and a count_dispatches() region
# is a before/after delta over those counters.  ONE choke point feeds both
# the region view and the global metrics, so the two cannot drift
# (docs/OBSERVABILITY.md).  Counting activates when a region is open OR
# when obs telemetry is enabled; otherwise the call-site guard
# (counting_dispatches()) keeps the hot path a no-op, exactly as before.
# ---------------------------------------------------------------------------

from . import obs as _obs

_DISPATCH_KINDS = ("compiled", "eager_ops", "h2d", "d2h")


class DispatchCounts:
    """Counters for one measured region."""

    __slots__ = ("compiled", "eager_ops", "h2d", "d2h")

    def __init__(self):
        self.compiled = 0   # jit-compiled program executions (engine/executor)
        self.eager_ops = 0  # eager op dispatches (each is a compiled program)
        self.h2d = 0        # host→device transfers
        self.d2h = 0        # device→host transfers (asnumpy/asscalar)

    @property
    def total_compiled(self):
        return self.compiled + self.eager_ops

    def as_dict(self):
        return {"compiled_calls": self.compiled, "eager_ops": self.eager_ops,
                "total_compiled": self.total_compiled,
                "h2d_transfers": self.h2d, "d2h_transfers": self.d2h}

    def __repr__(self):
        return f"DispatchCounts({self.as_dict()})"


_open_regions = 0  # count_dispatches() nesting depth


def counting_dispatches() -> bool:
    """Call-site guard: True while a count_dispatches() region is open or
    obs telemetry is enabled (the registry then accumulates globally)."""
    return _open_regions > 0 or _obs.enabled()


def count_dispatch(kind: str, n: int = 1) -> None:
    _obs.metrics.registry.counter("dispatch." + kind).inc(n)


def _dispatch_totals() -> dict:
    reg = _obs.metrics.registry
    return {k: reg.counter("dispatch." + k).value for k in _DISPATCH_KINDS}


@contextlib.contextmanager
def count_dispatches():
    """Count compiled executions / transfers in a region::

        with profiler.count_dispatches() as c:
            trainer.step(batch_size)
        assert c.total_compiled <= 2

    The yielded counts are finalized when the region exits (they are a
    delta over the registry's ``dispatch.*`` counters).
    """
    global _open_regions
    c = DispatchCounts()
    before = _dispatch_totals()
    _open_regions += 1
    try:
        yield c
    finally:
        _open_regions -= 1
        after = _dispatch_totals()
        for k in _DISPATCH_KINDS:
            setattr(c, k, after[k] - before[k])


def record_op(name: str, seconds: float) -> None:
    ent = _agg.get(name)
    if ent is None:
        _agg[name] = [1, seconds, seconds, seconds]
    else:
        ent[0] += 1
        ent[1] += seconds
        ent[2] = min(ent[2], seconds)
        ent[3] = max(ent[3], seconds)


def reset_stats() -> None:
    _agg.clear()


def set_config(**kwargs):
    """profile_{all,symbolic,imperative,memory,api}=..., filename=... —
    reference kwargs accepted; XLA traces everything on the device timeline."""
    _config.update(kwargs)


def set_state(state="stop", profile_process="worker"):
    """Start/stop the XLA trace. Idempotent both ways: a second "run" (or
    a "run" racing a trace some other code started directly through
    ``jax.profiler``) must never surface JAX's deep "trace already
    started" RuntimeError to a training loop — we adopt the active trace
    instead. Start/stop land as tagged obs events so profiler windows are
    visible inside the span timeline (docs/OBSERVABILITY.md)."""
    if state in ("run", 1):
        if _state["running"]:
            return  # double start: the window is already open
        logdir = _config.get("filename", "profile.json")
        trace_dir = logdir if os.path.isdir(logdir) else \
            (os.path.splitext(logdir)[0] + "_trace")
        os.makedirs(trace_dir, exist_ok=True)
        try:
            jax.profiler.start_trace(trace_dir)
        except RuntimeError as e:
            # adopt ONLY the double-start case; any other RuntimeError is
            # a genuine failure the caller must see (masking it would
            # report a phantom profile window)
            if "already" not in str(e).lower():
                raise
            warnings.warn(f"jax profiler already tracing ({e}); adopting "
                          "the active trace window", stacklevel=2)
        _state.update(running=True, dir=trace_dir)
        _obs.event("profiler.start_trace", dir=trace_dir)
    elif state in ("stop", 0):
        if not _state["running"]:
            return  # double stop: nothing open
        try:
            jax.profiler.stop_trace()
        except RuntimeError as e:  # jax's trace died under us — still ours
            warnings.warn(f"jax profiler stop: {e}", stacklevel=2)
        _state["running"] = False
        _obs.event("profiler.stop_trace", dir=_state.get("dir"))
    else:
        raise ValueError(f"invalid profiler state {state!r}")


def pause(profile_process="worker"):
    if _state["running"]:
        jax.profiler.stop_trace()
        _state["running"] = False


resume = None  # set below


def _resume(profile_process="worker"):
    set_state("run")


resume = _resume


def dump(finished=True, profile_process="worker"):
    """Finish tracing; the trace directory holds the XPlane/perfetto dump."""
    set_state("stop")
    return _state.get("dir")


def dumps(reset=False, format="table", sort_by="total", ascending=False):
    """Aggregate per-op stats table (reference `profiler.dumps()` /
    MXAggregateProfileStatsPrint analog) + the trace dir pointer."""
    lines = [f"profiler trace dir: {_state.get('dir')}"]
    if _agg:
        key_idx = {"total": 1, "count": 0, "min": 2, "max": 3,
                   "avg": None}.get(sort_by, 1)
        items = list(_agg.items())
        if key_idx is None:
            items.sort(key=lambda kv: kv[1][1] / kv[1][0], reverse=not ascending)
        else:
            items.sort(key=lambda kv: kv[1][key_idx], reverse=not ascending)
        lines.append("")
        lines.append("Profile Statistics (eager op dispatch):")
        lines.append(f"{'Name':<32}{'Count':>8}{'Total(ms)':>12}"
                     f"{'Min(ms)':>10}{'Max(ms)':>10}{'Avg(ms)':>10}")
        for name, (cnt, tot, mn, mx) in items:
            lines.append(f"{name:<32}{cnt:>8}{tot * 1e3:>12.3f}"
                         f"{mn * 1e3:>10.3f}{mx * 1e3:>10.3f}"
                         f"{tot / cnt * 1e3:>10.3f}")
    if reset:
        reset_stats()
    return "\n".join(lines)


@contextlib.contextmanager
def scope(name: str):
    """Named sub-scope for per-op attribution inside jit (reference profiler
    scopes / operator names in the engine timeline)."""
    with jax.named_scope(name):
        yield


class Profiler:
    """Context manager: profile a region."""

    def __init__(self, filename="profile", **kwargs):
        set_config(filename=filename, **kwargs)

    def __enter__(self):
        set_state("run")
        return self

    def __exit__(self, *a):
        dump()

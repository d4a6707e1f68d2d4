"""``mxnet_tpu.obs`` — unified runtime telemetry (docs/OBSERVABILITY.md).

Two surfaces, one switch:

- :mod:`~mxnet_tpu.obs.trace` — span tracer. ``obs.trace.span("phase")``
  context managers build a framework-level timeline (per-batch step phases,
  RPCs, checkpoint commits, chaos injections) exportable to chrome-trace
  JSON (Perfetto) or a JSONL stream.
- :mod:`~mxnet_tpu.obs.metrics` — metrics registry. Named counters, gauges,
  and fixed-bucket histograms; ``obs.metrics.dump()`` prints the table,
  ``snapshot()`` returns it as data. The profiler's dispatch counters live
  here too (``dispatch.*``), so ``profiler.count_dispatches()`` and the obs
  layer can never disagree.

The whole layer is **off by default and zero-cost when off**: one module
flag guards every entry point; ``span()`` returns a shared no-op, the
convenience helpers (``inc``/``observe``/``set_gauge``) return immediately.
Turn it on with ``MXNET_OBS=1`` in the environment or ``obs.enable()`` in
code; ``MXNET_OBS_JSONL=<path>`` additionally streams events to a file.

Typical session::

    import mxnet_tpu as mx
    mx.obs.enable()
    module.fit(train_iter, num_epoch=2, checkpoint="ckpts")
    mx.obs.export("trace.json")          # spans + metrics, one file
    print(mx.obs.metrics.dump())         # the metrics table
    # then: python tools/trace_report.py trace.json
"""
from __future__ import annotations

import os
from typing import Optional

from . import context, metrics, trace
# NOTE: the package attribute `obs.export` is the (pre-existing)
# chrome-trace export FUNCTION below; the export MODULE (prometheus text
# + multi-process merge) is reachable as `obs.export_mod` or via its full
# dotted path: `from mxnet_tpu.obs.export import to_prometheus` (python
# resolves that through sys.modules, not the shadowed attribute)
from . import export as export_mod
from . import tail  # tail-based trace retention (verdict at root close)
from . import profile  # continuous sampling profiler
from . import blackbox  # crash flight recorder
from . import slo  # SLO monitor over merged telemetry
from . import device  # device plane: program cost registry, live memory
from . import health  # training-health plane: numerics sentinel + rollback
from . import fleetstats  # training-fleet plane: step attribution, stragglers

__all__ = ["trace", "metrics", "context", "export_mod", "tail", "profile",
           "blackbox", "slo", "device", "health", "fleetstats", "enable",
           "disable", "enabled", "span", "event", "inc", "observe",
           "set_gauge", "export", "reset", "telemetry_part"]

# re-exported hot-path helpers (obs.span is obs.trace.span)
span = trace.span
event = trace.event


def enabled() -> bool:
    """True when telemetry is recording (the one module flag)."""
    return trace._ENABLED


def enable(jsonl: Optional[str] = None) -> None:
    """Turn telemetry on. ``jsonl`` additionally streams every completed
    span/event to that path (appended, flushed per event — survives
    SIGKILL, tail-able on headless workers). A literal ``%p`` in the path
    expands to this process's pid — how a fleet of ProcReplicas sharing
    one ``MXNET_OBS_JSONL`` template each get their own evidence file."""
    trace._ENABLED = True
    if jsonl:
        trace.stream_to(jsonl.replace("%p", str(os.getpid())))


def disable() -> None:
    """Turn telemetry off (the no-op fast path) and close any JSONL
    stream (after appending a final metrics-snapshot record to it).
    Recorded events and metrics are kept until :func:`reset`."""
    was_streaming = trace.tracer._stream is not None
    trace._ENABLED = False
    if was_streaming:
        trace.tracer.stream_metrics(metrics.snapshot())
    trace.stream_to(None)


def reset() -> None:
    """Clear the span ring buffer, drop every metric, and empty the
    device-plane cost registry / leak-monitor state (plus the tail
    plane's pending buffer + exemplars when tail mode is on)."""
    trace.reset()
    metrics.reset()
    device.reset()
    tail.reset()
    fleetstats.reset()


# -- self-gating convenience helpers for instrumentation call sites --------
# One call, one flag check: `obs.inc("kvstore.rpc.retries")` costs a single
# boolean test when telemetry is off.

def inc(name: str, n: int = 1) -> None:
    if trace._ENABLED:
        metrics.registry.counter(name).inc(n)


def observe(name: str, value: float) -> None:
    if trace._ENABLED:
        metrics.registry.histogram(name).observe(value)


def set_gauge(name: str, value: float) -> None:
    if trace._ENABLED:
        metrics.registry.gauge(name).set(value)


def export(path: str) -> str:
    """Write the chrome-trace JSON (spans + instant events + a metrics
    snapshot in ``otherData``) to ``path``. Load it in Perfetto, or feed it
    to ``tools/trace_report.py`` for a terminal breakdown."""
    return trace.export_chrome_trace(path, metrics=metrics.snapshot())


def telemetry_part(drain: bool = True, role: Optional[str] = None) -> dict:
    """This process's contribution to a fleet-wide telemetry collection:
    the drained span ring (or a copy with ``drain=False``) with the count
    of records the full ring has pushed out since the last ``reset()``
    (``dropped``), the metrics snapshot, and the clock anchor that lets
    collectors merge many
    processes onto one timeline (obs/export.py ``merge_chrome_parts``).
    This is what a server returns over ``OP_TELEMETRY``."""
    if drain:
        spans = trace.tracer.drain()
    else:
        spans = [trace.tracer._event_dict(r) for r in trace.tracer.events()]
    part = {"pid": os.getpid(), "role": role,
            "wall_epoch": trace.tracer.wall_epoch,
            "sample_rate": context.sample_rate(),
            "spans": spans, "dropped": trace.tracer.dropped,
            "metrics": metrics.snapshot()}
    if tail.enabled():
        # bucket→trace_id exemplars + buffer state ride the part, so one
        # collection carries the exposition's exemplar links and the
        # fleet report can show pending/retained/dropped per member
        part["exemplars"] = tail.exemplars_snapshot()
        part["tail"] = tail.stats()
    return part


# environment switches: MXNET_OBS=1 enables at import, MXNET_OBS_JSONL
# names the stream file (implies enable)
_env = os.environ.get("MXNET_OBS", "").lower()
_jsonl = os.environ.get("MXNET_OBS_JSONL")
if _jsonl or _env not in ("", "0", "false", "no", "off"):
    enable(jsonl=_jsonl)

# the black-box plane's switches (docs/OBSERVABILITY.md): tail retention,
# continuous profiler, flight recorder — each independent, all inherited
# by ProcReplica children so a fleet observes (and crash-records) as one
if os.environ.get("MXNET_OBS_TAIL", "").lower() not in (
        "", "0", "false", "no", "off"):
    tail.enable()
if os.environ.get("MXNET_OBS_PROF", "").lower() not in (
        "", "0", "false", "no", "off"):
    profile.start()
if os.environ.get("MXNET_OBS_BLACKBOX", "").lower() not in (
        "", "0", "false", "no", "off") \
        or os.environ.get("MXNET_OBS_BLACKBOX_DIR"):
    blackbox.enable()

"""From a profiler trace (``.xplane.pb``) to numbers: device busy time as
the union of the device's operation intervals, the idle gaps and what the
host was doing in them, time by operation name, and the summed time of the
events whose name matches a pattern.

``python3 benchmark/reduce_trace.py <file.xplane.pb>`` prints the planes,
lines and top names of a trace: look at one by hand before writing a
pattern against it.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import sys
import time
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"             # one event per operation run on the core
HOST_PLANE = "/host:CPU"
MARK = "bench."                  # the benchmark's own host annotations
MOSAIC = "tpu_custom_call"       # what a compiled Pallas kernel lowers to


class profile:
    """``with profile(dir) as p:`` traces the block with jax's profiler
    (Python call tracing off: it slows the host it measures). ``p.path`` is
    the trace file, ``p.seconds`` the length of the traced block."""

    def __init__(self, directory):
        self.dir = os.path.join(directory, "trace")

    def __enter__(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        import jax

        self.seconds = time.monotonic() - self._t0
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        self.path = found[0] if found else None
        return False


def mark(name):
    """A host span on the profiler's clock, named ``bench.<name>``."""
    import jax

    return jax.profiler.TraceAnnotation(MARK + name)


def _events(plane, line_name=None):
    for line in plane.lines:
        if line_name is None or line.name == line_name:
            for e in line.events:
                yield e.name, e.start_ns, e.start_ns + e.duration_ns


def short_name(event_name):
    """An operation's event is named by its whole HLO line. Keep the
    instruction's name without ``%`` and its numeric suffix, so that the 24
    layers' instances of one fusion add up, and mark a Mosaic kernel:
    ``%transpose_jvp___.36 = ... custom_call_target="tpu_custom_call"`` is
    ``mosaic:transpose_jvp___``."""
    name = re.sub(r"\.\d+$", "", event_name.split(" = ")[0].lstrip("%"))
    return "mosaic:" + name if MOSAIC in event_name else name


def union(intervals):
    """Merged, sorted [start, end] intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _host_marks(planes):
    return sorted((s, e, name) for plane in planes if plane.name == HOST_PLANE
                  for name, s, e in _events(plane) if name.startswith(MARK))


def _what_host_did(marks, start, end):
    """The benchmark's host span that covers most of [start, end]."""
    best, best_overlap = "unmarked", 0
    for s, e, name in marks:
        overlap = min(e, end) - max(s, start)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def reduce(path, chips=1, window_s=None):
    """The trace as numbers. ``busy_s`` is the mean over the first ``chips``
    device planes of the union of their operation intervals; ``window_s``
    the traced window (the span from the first to the last device operation
    unless given); ``by_name`` the seconds by operation name summed over
    those planes (see ``short_name``); ``device_ops`` its largest entries;
    ``idle_gaps`` the longest gaps of the first device, named by the host
    span under them. None where the trace holds no TPU plane."""
    import jax

    planes = list(jax.profiler.ProfileData.from_file(path).planes)
    devices = sorted((p for p in planes if DEVICE_PLANE.match(p.name)),
                     key=lambda p: p.name)[:chips]
    if not devices:     # a CPU rehearsal: nothing to read
        return None
    by_name = defaultdict(float)
    busy, first, last, gaps = [], None, None, []
    marks = _host_marks(planes)
    for i, plane in enumerate(devices):
        ops = list(_events(plane, OPS_LINE))
        for name, s, e in ops:
            by_name[short_name(name)] += (e - s) / 1e9
        merged = union((s, e) for _, s, e in ops)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        if merged:
            first = merged[0][0] if first is None else min(first, merged[0][0])
            last = merged[-1][1] if last is None else max(last, merged[-1][1])
        if i == 0:
            for (_, e0), (s1, _) in zip(merged, merged[1:]):
                gaps.append((s1 - e0, e0, s1))
    if window_s is None:
        window_s = (last - first) / 1e9
    by_gap = defaultdict(float)
    for length, s, e in sorted(gaps, reverse=True)[:200]:
        by_gap[_what_host_did(marks, s, e)] += length / 1e9
    top = lambda d: [[k, v] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])]
    return {"busy_s": sum(busy) / len(busy), "window_s": window_s,
            "by_name": dict(by_name), "device_ops": top(by_name),
            "idle_gaps": top(by_gap)}


def matching(by_name, pattern):
    """Summed seconds of the operations whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(v for k, v in by_name.items() if rx.search(k))


def dump(path, top=25):
    import jax

    for plane in jax.profiler.ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            total = defaultdict(float)
            count = 0
            for e in line.events:
                total[e.name] += e.duration_ns / 1e6
                count += 1
            print(f"  LINE {line.name!r}: {count} events")
            for name, ms in sorted(total.items(), key=lambda kv: -kv[1])[:top]:
                print(f"    {ms:10.3f} ms  {name[:140]}")


if __name__ == "__main__":
    dump(sys.argv[1])

"""From a profiler trace (``.xplane.pb``) to numbers: device busy time as
the union of the device's operation intervals, the idle gaps and what the
host was doing in them, time by operation name, and the summed time of the
events whose name matches a pattern. The traced window is a host span on
the trace's own clock (``profile`` marks it), and every device interval is
cut to it before anything is summed: ``busy_in`` is the one place.

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
WINDOW = MARK + "window"         # ``profile``'s own: the traced window
MOSAIC = "tpu_custom_call"       # what a compiled Pallas kernel lowers to


class profile:
    """``with profile(dir) as p:`` traces the block with jax's profiler
    (Python call tracing off: it slows the host it measures). ``p.path`` is
    the trace file, ``p.seconds`` the length of the traced block by the
    host's clock. The block is also a ``bench.window`` span in the trace,
    opened once the profiler runs and closed before it is stopped: what the
    device ran while the profiler started and stopped lies outside it."""

    def __init__(self, directory):
        self.dir = os.path.join(directory, "trace")

    def __enter__(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._window = jax.profiler.TraceAnnotation(WINDOW)
        self._window.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        import jax

        self.seconds = time.monotonic() - self._t0
        self._window.__exit__(*exc)
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


def host_marks(planes):
    """The benchmark's own host spans, sorted (start, end, name)."""
    return sorted((s, e, name) for plane in planes if plane.name == HOST_PLANE
                  for name, s, e in _events(plane) if name.startswith(MARK))


def window_of(marks):
    """(start, end) of ``profile``'s ``bench.window`` span among the host's
    marks, in the trace's nanoseconds; None in a trace recorded without it."""
    return next(((s, e) for s, e, name in marks if name == WINDOW), None)


def busy_in(plane, window=None):
    """A device plane's operations cut to ``window`` (None: as they lie):
    the (name, start, end) events that reach into it, their merged busy
    intervals, and the busy nanoseconds cut away before and after it."""
    ops = list(_events(plane, OPS_LINE))
    merged = union((s, e) for _, s, e in ops)
    if window is None:
        return ops, merged, 0, 0
    w0, w1 = window
    before = sum(min(e, w0) - s for s, e in merged if s < w0)
    after = sum(e - max(s, w1) for s, e in merged if e > w1)
    ops = [(name, max(s, w0), min(e, w1)) for name, s, e in ops
           if s < w1 and e > w0]
    merged = [[max(s, w0), min(e, w1)] for s, e in merged if s < w1 and e > w0]
    return ops, merged, before, after


def idle_in(merged, window=None):
    """The [start, end] gaps of a device's merged busy intervals: all of
    ``window`` that they leave, or without one the gaps between them."""
    if window is not None:
        merged = [[window[0]] * 2] + merged + [[window[1]] * 2]
    return [[e0, s1] for (_, e0), (s1, _) in zip(merged, merged[1:])
            if s1 > e0]


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
    the traced window; ``by_name`` the seconds by operation name summed over
    those planes (see ``short_name``); ``device_ops`` its largest entries;
    ``idle_gaps`` the longest gaps of the first device, named by the host
    span under them. Where the trace holds ``profile``'s ``bench.window``
    span, all of these are taken inside it (``busy_in``) and ``window_s`` is
    its length, so that ``busy_s`` cannot pass it; ``clipped_s`` is then the
    busy seconds cut away [before, after] it, a chip's mean, and
    ``host_clock_s`` the ``window_s`` given. In a trace without the span
    ``clipped_s`` is None and ``window_s`` the one given, or else the span
    from the first to the last device operation. None where the trace holds
    no TPU plane."""
    import jax

    planes = list(jax.profiler.ProfileData.from_file(path).planes)
    devices = sorted((p for p in planes if DEVICE_PLANE.match(p.name)),
                     key=lambda p: p.name)[:chips]
    if not devices:     # a CPU rehearsal: nothing to read
        return None
    by_name = defaultdict(float)
    busy, first, last, gaps, clipped = [], None, None, [], [0.0, 0.0]
    marks = host_marks(planes)
    window = window_of(marks)
    marks = [m for m in marks if m[2] != WINDOW]    # it names no gap
    for i, plane in enumerate(devices):
        ops, merged, before, after = busy_in(plane, window)
        for name, s, e in ops:
            by_name[short_name(name)] += (e - s) / 1e9
        busy.append(sum(e - s for s, e in merged) / 1e9)
        clipped[0] += before / 1e9 / len(devices)
        clipped[1] += after / 1e9 / len(devices)
        if merged:
            first = merged[0][0] if first is None else min(first, merged[0][0])
            last = merged[-1][1] if last is None else max(last, merged[-1][1])
        if i == 0:
            gaps = [(e - s, s, e) for s, e in idle_in(merged, window)]
    host_clock_s = window_s
    if window is not None:
        window_s = (window[1] - window[0]) / 1e9
    elif window_s is None:
        window_s = (last - first) / 1e9
    by_gap = defaultdict(float)
    for length, s, e in sorted(gaps, reverse=True)[:200]:
        by_gap[_what_host_did(marks, s, e)] += length / 1e9
    top = lambda d: [[k, v] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])]
    return {"busy_s": sum(busy) / len(busy), "window_s": window_s,
            "by_name": dict(by_name), "device_ops": top(by_name),
            "idle_gaps": top(by_gap), "host_clock_s": host_clock_s,
            "clipped_s": clipped if window is not None else None}


def last_line(trace, log=print):
    """What the last line of a traced run on a device carries of its reduced
    trace: (``busy_s``, ``window_s``, ``breakdown``). The contract has
    0 < ``busy_s`` <= ``window_s``: a run with no trace, or with a pair
    outside that, ends here with a message and a non-zero code, and prints
    no line that its reader could only call malformed."""
    if not trace:
        sys.exit("a traced run on a device, and no trace to reduce: the "
                 "profiler left no .xplane.pb, or it holds no TPU plane")
    busy, window, cut = trace["busy_s"], trace["window_s"], trace["clipped_s"]
    log(f"trace: busy {busy:.6f} s of a {window:.6f} s window (host clock "
        f"{trace['host_clock_s']}); " + (
            f"no {WINDOW} span in it, nothing clipped" if cut is None else
            f"device time clipped {cut[0] * 1e3:.3f} ms before the {WINDOW} "
            f"span, {cut[1] * 1e3:.3f} ms after"))
    if not 0 < busy <= window:
        sys.exit(f"device.busy_s {busy!r} is not above 0 and at most "
                 f"device.window_s {window!r}: no line printed")
    return busy, window, {"device_ops": trace["device_ops"][:10],
                          "idle_gaps": trace["idle_gaps"][:10]}


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

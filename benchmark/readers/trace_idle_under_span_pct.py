"""100 x the seconds in which the first device ran no operation while the
host was under a span named ``args.under`` and under none named
``args.outside`` / the traced window, from the run's own ``.xplane.pb``.

The device's idle time is what the union of its ``XLA Ops`` intervals
leaves of the traced window: the same intervals, cut to the same
``bench.window`` span, that ``reduce_trace.reduce`` sums and divides by (in
a trace without that span, the gaps between its first and its last
operation). The host's spans are the ``/host:CPU`` events whose name starts
with ``args.prefix`` (the program's live spans, bridged to the profiler's
clock). Beside the number the reader prints where all of the idle time
went: one line per span name with the idle seconds under it, the innermost
span winning, and the remainder as ``unmarked``.

The observations hold the reduced trace, not its path. The runner's
``reduce_trace.profile`` leaves the file under
``benchmark/.out/<cell>/trace/plugins/profile/*/`` and clears that
directory before each run; the cell is the process's ``--workload``.
None where the runner reduced no trace, there is no such file, no device
or host plane in it, or no ``under`` span on the host plane (a program
that lacks the bridge).
"""
import argparse
import glob
import os
from collections import defaultdict

from benchmark import reduce_trace

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def trace_file():
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    cell = ap.parse_known_args()[0].workload
    if not cell:
        return None
    found = glob.glob(os.path.join(HERE, ".out", cell, "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return found[0] if found else None


def load(path, prefix):
    """(the first device's idle gaps, the host's spans): sorted [start, end]
    and (start, end, name), in the trace's nanoseconds. None without a
    device plane that ran something or without a host plane."""
    import jax

    planes = list(jax.profiler.ProfileData.from_file(path).planes)
    devices = sorted((p for p in planes
                      if reduce_trace.DEVICE_PLANE.match(p.name)),
                     key=lambda p: p.name)
    hosts = [p for p in planes if p.name == reduce_trace.HOST_PLANE]
    if not devices or not hosts:
        return None
    window = reduce_trace.window_of(reduce_trace.host_marks(planes))
    _, busy, _, _ = reduce_trace.busy_in(devices[0], window)
    if not busy:
        return None
    gaps = reduce_trace.idle_in(busy, window)
    spans = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for plane in hosts for line in plane.lines
                   for e in line.events if e.name.startswith(prefix))
    return gaps, spans


def segments(spans):
    """The host's time cut at every span's start and end: (start, end,
    names of the spans that cover the piece, the innermost last), for the
    pieces that some span covers. Innermost is the span that started last
    (of two that start together, the one that ends first)."""
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        covering = sorted((s, -e, name) for s, e, name in spans
                          if s <= a and b <= e)
        if covering:
            out.append((a, b, [name for _, _, name in covering]))
    return out


def idle_by_piece(gaps, pieces):
    """For each piece of ``segments``, the nanoseconds of ``gaps`` inside
    it. Both are sorted and disjoint: one pass over each."""
    out, i = [], 0
    for a, b, _ in pieces:
        while i < len(gaps) and gaps[i][1] <= a:
            i += 1
        j, idle = i, 0
        while j < len(gaps) and gaps[j][0] < b:
            idle += min(b, gaps[j][1]) - max(a, gaps[j][0])
            j += 1
        out.append(idle)
    return out


def attribute(gaps, spans, under, outside):
    """(idle seconds by innermost span name with the rest as ``unmarked``,
    idle seconds under ``under`` and under no ``outside``, idle seconds)."""
    pieces = segments(spans)
    by_name, chosen = defaultdict(float), 0
    for (_, _, names), idle in zip(pieces, idle_by_piece(gaps, pieces)):
        by_name[names[-1]] += idle / 1e9
        if under in names and outside not in names:
            chosen += idle
    total = sum(e - s for s, e in gaps) / 1e9
    by_name["unmarked"] = total - sum(by_name.values())
    return dict(by_name), chosen / 1e9, total


def read(obs, args):
    window = (obs.get("trace") or {}).get("window_s")
    path = trace_file()
    loaded = load(path, args["prefix"]) if window and path else None
    if not loaded:
        return None
    gaps, spans = loaded
    if not any(name == args["under"] for _, _, name in spans):
        return None
    by_name, chosen, total = attribute(gaps, spans, args["under"],
                                       args["outside"])
    for name, seconds in sorted(by_name.items(), key=lambda kv: -kv[1]):
        print(f"device idle under {name}: {seconds:.6f} s "
              f"({100 * seconds / total if total else 0.0:.1f} % of "
              f"{total:.6f} s idle)", flush=True)
    return 100.0 * chosen / window

"""Record the trace that ``test_reduce_trace.py``'s window cases read, on the
chip: ``python3 benchmark/tests/record_window_trace.py <out dir>``. A thread
keeps the device fed from before ``reduce_trace.profile`` is entered until
after it is left, so the device runs while the profiler starts and while it
stops: its operations overhang the ``bench.window`` span on both sides, and
their plain union is longer than the window. Beside the trace, what the
reduction has to give, worked out here the slow way: every operation cut to
the span by hand, time cut at every edge and each piece looked up against
every operation, names added up in a plain loop."""
import json
import os
import shutil
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import reduce_trace  # noqa: E402


def covered(intervals, lo, hi):
    """Nanoseconds of [lo, hi] that some interval covers."""
    edges = sorted({lo, hi} | {t for s, e in intervals for t in (s, e)
                               if lo < t < hi})
    return sum(b - a for a, b in zip(edges, edges[1:])
               if any(s <= a and b <= e for s, e in intervals))


def record(out, loop, a):
    """One trace with the device fed throughout, and what it has to give."""
    stop = threading.Event()

    def feed():
        while not stop.is_set():
            loop(a)     # not waited for: the queue stays full

    feeder = threading.Thread(target=feed)
    feeder.start()
    time.sleep(0.2)
    with reduce_trace.profile(out) as prof:
        time.sleep(0.03)
    stop.set()
    feeder.join()
    loop(a).block_until_ready()

    planes = list(jax.profiler.ProfileData.from_file(prof.path).planes)
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for p in planes if p.name == "/device:TPU:0"
              for line in p.lines if line.name == "XLA Ops"
              for e in line.events]
    (w0, w1), = [(e.start_ns, e.start_ns + e.duration_ns)
                 for p in planes if p.name == "/host:CPU"
                 for line in p.lines for e in line.events
                 if e.name == "bench.window"]
    spans = [(s, e) for _, s, e in events]
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    by_name, cut_by_name = {}, {}
    for name, s, e in events:
        key = reduce_trace.short_name(name)
        inside = max(0, min(e, w1) - max(s, w0))
        by_name[key] = by_name.get(key, 0.0) + inside / 1e9
        cut_by_name[key] = cut_by_name.get(key, 0.0) + (e - s - inside) / 1e9
    return prof.path, {
        "events": len(events), "window_s": (w1 - w0) / 1e9,
        "host_clock_s": prof.seconds,
        "busy_s": covered(spans, w0, w1) / 1e9,
        "busy_unclipped_s": covered(spans, lo, hi) / 1e9,
        "clipped_before_s": covered(spans, min(lo, w0), w0) / 1e9,
        "clipped_after_s": covered(spans, w1, max(hi, w1)) / 1e9,
        "by_name": by_name, "cut_by_name": cut_by_name,
        "device": jax.devices()[0].device_kind}


def main(out):
    a = jnp.full((2048, 2048), 1e-4, jnp.bfloat16)
    # one program of a millisecond or two: a ``while`` with its body's
    # operations nested in it, as a scanned prefill has them
    loop = jax.jit(lambda x: jax.lax.fori_loop(
        0, 16, lambda i, y: jnp.tanh(y @ y), x).sum())
    loop(a).block_until_ready()
    for attempt in range(8):    # until the file holds the fault
        path, want = record(out, loop, a)
        print(f"attempt {attempt}:", {k: v for k, v in want.items()
                                      if not k.endswith("by_name")})
        if (want["busy_unclipped_s"] > want["window_s"]
                and min(want["clipped_before_s"], want["clipped_after_s"]) > 0):
            break
    else:
        sys.exit("the device's operations never overhung the window on both "
                 "sides by more than it idled inside: nothing written")
    shutil.copy(path, os.path.join(out, "window.xplane.pb"))
    with open(os.path.join(out, "window.expected.json"), "w") as f:
        json.dump(want, f, indent=1)
    print(json.dumps(want, indent=1))
    print(os.path.getsize(os.path.join(out, "window.xplane.pb")), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])

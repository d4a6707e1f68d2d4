"""Record the small trace that ``test_span_readers.py`` reads, on the chip:
``python3 benchmark/tests/record_span_trace.py <out dir>``. Two turns of a
make-believe scheduler under the program's span names (``decode.turn`` and
its phases, as ``jax.profiler.TraceAnnotation``s), with a pause between
them under no span, and beside the trace what the reader has to give,
worked out here the slow way: time cut at every edge of every event, each
piece looked up against every device operation and every host span."""
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import reduce_trace  # noqa: E402

span = jax.profiler.TraceAnnotation


def main(out):
    a = jnp.ones((1024, 1024), jnp.float32)
    mm = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    add = jax.jit(lambda x: (x + 1.0).sum())
    jax.device_get(mm(a)), jax.device_get(add(a))
    with reduce_trace.profile(out) as prof:
        with span("decode.turn"):
            with span("decode.admit"):
                time.sleep(0.001)
            with span("decode.execute"):
                with span("decode.dispatch"):
                    y = mm(a)
                with span("decode.device_get"):
                    jax.device_get(y)
            with span("decode.build"):
                time.sleep(0.003)
            with span("decode.execute"):
                with span("decode.dispatch"):
                    y = add(mm(a))
                with span("decode.device_get"):
                    jax.device_get(y)
            with span("decode.distribute"):
                time.sleep(0.002)
        time.sleep(0.004)
        with span("decode.turn"):
            with span("decode.execute"):
                with span("decode.dispatch"):
                    y = add(a)
                with span("decode.device_get"):
                    jax.device_get(y)
    shutil.copy(prof.path, os.path.join(out, "spans.xplane.pb"))

    planes = list(jax.profiler.ProfileData.from_file(prof.path).planes)
    ops = [(e.start_ns, e.start_ns + e.duration_ns)
           for p in planes if p.name == "/device:TPU:0"
           for line in p.lines if line.name == "XLA Ops"
           for e in line.events]
    host = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for p in planes if p.name == "/host:CPU" for line in p.lines
            for e in line.events if e.name.startswith("decode.")]
    lo, hi = min(s for s, _ in ops), max(e for _, e in ops)
    edges = sorted({t for s, e in ops for t in (s, e)}
                   | {t for s, e, _ in host for t in (s, e) if lo < t < hi})
    by_name, chosen, idle = {}, 0, 0
    for p, q in zip(edges, edges[1:]):
        if any(s <= p and q <= e for s, e in ops):
            continue    # the device ran something
        idle += q - p
        over = [(s, e, n) for s, e, n in host if s <= p and q <= e]
        names = [n for _, _, n in over]
        innermost = "unmarked"
        for s, e, n in over:    # started last; of those, ended first
            if innermost == "unmarked" or (s, -e) > best:
                innermost, best = n, (s, -e)
        by_name[innermost] = by_name.get(innermost, 0.0) + (q - p) / 1e9
        if "decode.turn" in names and "decode.execute" not in names:
            chosen += q - p
    counts = {}
    for _, _, n in host:
        counts[n] = counts.get(n, 0) + 1
    with open(os.path.join(out, "spans.expected.json"), "w") as f:
        json.dump({"ops": len(ops), "host_spans": counts,
                   "span_s": (hi - lo) / 1e9, "idle_s": idle / 1e9,
                   "idle_by_innermost_span": by_name,
                   "idle_under_turn_outside_execute_s": chosen / 1e9,
                   "device": jax.devices()[0].device_kind}, f, indent=1)
    print(open(os.path.join(out, "spans.expected.json")).read())
    print(os.path.getsize(os.path.join(out, "spans.xplane.pb")), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])

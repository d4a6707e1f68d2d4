"""Record the small trace that ``test_reduce_trace.py`` reads, on the chip:
``python3 benchmark/tests/record_trace.py <out dir>``. Two jitted programs,
a marked pause between them, and beside the trace the numbers that the
reduction has to give, worked out here the slow way (every nanosecond
interval compared with every other, names added up in a plain loop)."""
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


def main(out):
    a = jnp.ones((512, 512), jnp.float32)
    mm = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    add = jax.jit(lambda x: (x + 1.0).sum())
    mm(a).block_until_ready(), add(a).block_until_ready()
    with reduce_trace.profile(out) as prof:
        with reduce_trace.mark("test.first"):
            mm(a).block_until_ready()
        with reduce_trace.mark("test.pause"):
            time.sleep(0.02)
        with reduce_trace.mark("test.second"):
            add(mm(a)).block_until_ready()
    shutil.copy(prof.path, os.path.join(out, "small.xplane.pb"))

    plane = next(p for p in jax.profiler.ProfileData.from_file(prof.path).planes
                 if p.name == "/device:TPU:0")
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for line in plane.lines if line.name == "XLA Ops"
              for e in line.events]
    lo = min(s for _, s, _ in events)
    hi = max(e for _, _, e in events)
    edges = sorted({t for _, s, e in events for t in (s, e)})
    busy = sum(b - a_ for a_, b in zip(edges, edges[1:])
               if any(s <= a_ and b <= e for _, s, e in events))
    by_name = {}
    for name, s, e in events:
        key = reduce_trace.short_name(name)
        by_name[key] = by_name.get(key, 0.0) + (e - s) / 1e9
    with open(os.path.join(out, "small.expected.json"), "w") as f:
        json.dump({"events": len(events), "busy_s": busy / 1e9,
                   "span_s": (hi - lo) / 1e9, "by_name": by_name,
                   "device": jax.devices()[0].device_kind}, f, indent=1)
    print(open(os.path.join(out, "small.expected.json")).read())
    print(os.path.getsize(os.path.join(out, "small.xplane.pb")), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])

"""A kernel's share of its roofline, as ``kernel_roofline_pct``, with the
module that counts the work named in ``args.module`` (a module of
``benchmark``, beside ``flops.py``): the least time the chip could take for
the work / the summed device time of the trace events matching
``args.pattern``. The ``args.work`` function returns ``{piece: (FLOPs,
bytes)}``; each piece's least time is the larger of its FLOPs / peak FLOP/s
and its bytes / peak bytes/s, and the pieces add up (a piece is bound by
itself: decode and prefill sit on different sides of the roofline). None
where there is no trace, no matching event, or nothing was observed to
count."""
import importlib

from benchmark import flops, reduce_trace


def read(obs, args):
    trace = obs.get("trace")
    if not trace:
        return None
    seconds = reduce_trace.matching(trace["by_name"], args["pattern"])
    if seconds <= 0:
        return None
    module = importlib.import_module(f"benchmark.{args['module']}")
    try:
        pieces = getattr(module, args["work"])(obs["model"], obs)
    except KeyError:      # the runner observed none of what this work counts
        return None
    peak = flops.peaks(obs["device_kind"])
    least = 0.0
    for name, (work_flops, work_bytes) in pieces.items():
        bounds = {"compute": work_flops / peak["bf16_flops_per_s"],
                  "memory": work_bytes / peak["hbm_bytes_per_s"]}
        bound = max(bounds, key=bounds.get)
        least += bounds[bound]
        print(f"roofline {args['pattern']} [{name}]: {bound}-bound, least "
              f"{bounds[bound] * 1e3:.3f} ms (compute "
              f"{bounds['compute'] * 1e3:.3f}, memory "
              f"{bounds['memory'] * 1e3:.3f})", flush=True)
    if least <= 0:
        return None
    print(f"roofline {args['pattern']}: least {least * 1e3:.3f} ms against "
          f"{seconds * 1e3:.3f} ms measured", flush=True)
    return 100.0 * least / seconds

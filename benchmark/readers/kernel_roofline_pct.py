"""A kernel's share of its roofline: the least time the chip could take for
the work (the larger of FLOPs / peak FLOP/s and bytes / peak bytes/s, from
``benchmark/flops.py``'s ``args.work`` function over what the runner
observed) / the summed device time of the trace events matching
``args.pattern``, both per ``args.per`` (an observed count of steps or
calls in the traced window)."""
from benchmark import flops, reduce_trace


def read(obs, args):
    trace = obs.get("trace")
    if not trace:
        return None
    seconds = reduce_trace.matching(trace["by_name"], args["pattern"])
    if seconds <= 0:
        return None
    work_flops, work_bytes = getattr(flops, args["work"])(obs["model"], obs)
    peak = flops.peaks(obs["device_kind"])
    bounds = {"compute": work_flops / peak["bf16_flops_per_s"],
              "memory": work_bytes / peak["hbm_bytes_per_s"]}
    bound = max(bounds, key=bounds.get)
    per = obs[args["per"]]
    print(f"roofline {args['pattern']}: {bound}-bound, least "
          f"{bounds[bound] * 1e3:.3f} ms against {seconds / per * 1e3:.3f} ms "
          f"measured per {args['per']}", flush=True)
    return 100.0 * bounds[bound] / (seconds / per)

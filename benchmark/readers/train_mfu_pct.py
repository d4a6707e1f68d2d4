"""Model FLOP/s utilization of a training step: the FLOPs the forward and
backward passes need per token (``benchmark/flops.py``, no recomputation)
x tokens a step / the median blocked step time of the same run / (chips x
the published bf16 peak)."""
import statistics

from benchmark import flops


def read(obs, args):
    steps = obs.get("blocked_step_ms")
    if not steps:
        return None
    per_token = getattr(flops, args["flops"])(obs["model"], obs["seq"])
    rate = per_token * obs["tokens_per_step"] / (statistics.median(steps) / 1e3)
    peak = flops.peaks(obs["device_kind"])["bf16_flops_per_s"]
    return 100.0 * rate / (obs["chips"] * peak)

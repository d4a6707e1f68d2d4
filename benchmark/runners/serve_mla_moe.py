"""Runner ``serve_mla_moe``: ``runners/serve.py``'s closed loop — the same
callers, the same ``ServeClient.generate`` -> ``ServeServer`` ->
``DecodeScheduler`` -> ``DecodeEngine`` — around a model of the ``mla_moe``
kind (``mxnet_tpu.models.mla_moe``: latent attention through a latent page
pool, routed and shared experts), whose weights the program makes on the
device from the seed and the reference (``reference_mla_moe.py``) makes again
for itself, a layer at a time.

After the window the callers finish what they hold, the server stops, the
engine AND the model's weights are freed, and a seeded sample of the
finished requests (the longest among them) goes through the reference layer
by layer, all sampled sequences through one layer's float32 weights at a
time. The numbers compared are ``serve.py``'s — the gap by which a served
token's reference logit lies below the reference's best at its position —
at its widest and at its 99th percentile (``describe``). Also held: the
scheduler's ``moe.dropped`` total is 0.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import reduce_trace, reference_mla_moe as reference, traffic
from benchmark.runners.serve import (TRACE_FOR_S, TRACE_FROM_S, TRACE_WINDOW_S,
                                     Callers, failed, percentile, sample)

PROFILE_MARK = "bench.profile"   # an obs.trace event at each end of the profile

def one_order(requests):
    """The same requests' lengths and ids, in ONE order for every seed.

    ``traffic.serve_requests`` gives every seed the same multiset of prompt
    lengths and of output lengths, paired and ordered by the seed. Here a
    request costs from 0.1 s (a 256-token prompt, 64 tokens out) to over 1 s
    of the engine, and a 40 s window sees about 85 of the 128: which 85 would
    move tokens/s by several per cent from seed to seed, more than the
    metric's bound. So the sorted prompt lengths are paired with the sorted
    output lengths by one fixed stride and taken in another (both coprime
    with the count, so that any run of consecutive requests spans short and
    long of both), and the seed's ids are cut to those lengths: every seed
    offers the same work in the same order, with other tokens."""
    n = len(requests)
    ids = np.concatenate([r["prompt"] for r in requests])
    plen = sorted(len(r["prompt"]) for r in requests)
    olen = sorted(r["max_new_tokens"] for r in requests)
    assert n % 2 == 0 and n % 3 and n % 5 and n % 7 and n % 11
    order = [(plen[(i * 75) % n], olen[(i * 75 * 49) % n]) for i in range(n)]
    cuts = np.cumsum([p for p, _ in order])[:-1]
    return [{"prompt": p, "max_new_tokens": o}
            for p, (_, o) in zip(np.split(ids, cuts), order)]


def serve(run, seconds):
    """Build the model and the engine from the seed, serve ``ramp_s`` and
    then the window, let the callers finish, stop the server and free the
    engine and the weights. Returns what the window left behind."""
    import gc

    import jax

    from mxnet_tpu import obs
    from mxnet_tpu.models.mla_moe import MLAMoEDecodeModel
    from mxnet_tpu.serve import DecodeEngine, DecodeScheduler, ServeServer

    model, sv = run.model, run.workload["serve"]
    requests = one_order(traffic.serve_requests(run.traffic, model, run.seed))
    lm = MLAMoEDecodeModel(model, seed=run.seed)
    run.log("weights made on the device")
    slots = sv["slots"]
    engine = DecodeEngine(
        lm, slots=slots, page_size=sv["page_size"],
        prompt_buckets=sv["prompt_buckets"],
        num_pages=slots * (model["max_length"] // sv["page_size"]) + 1)
    run.log(f"engine built: {slots} slots, {engine.num_pages} pages of "
            f"{engine.page_size} x {engine.cache_row_bytes} B, buckets "
            f"{engine.buckets}")
    engine.warmup()
    run.log(f"warm-up done: {engine.stats()['num_programs']} programs; step "
            f"program {engine.stats()['step_program']}")
    sched = DecodeScheduler(engine, max_queue=4 * slots,
                            default_timeout=sv["stream_timeout_s"])
    server = ServeServer(engine=None, decode=sched, port=0)
    server.start()
    callers = Callers(server.port, requests, sv["clients"],
                      sv["stream_timeout_s"])
    out = {"observations": {
        "slots": slots, "model": model, "one": 1,
        "moe_groups": model["experts_held"] * (model["num_layers"]
                                               - model["first_dense"]),
        "device_kind": run.devices[0].device_kind}}
    try:
        callers.start()
        time.sleep(sv["ramp_s"])
        built = run.open_window()
        t0 = run.window_start
        if run.trace:
            obs.enable()
            time.sleep(TRACE_FROM_S)
            with reduce_trace.profile(run.scratch) as prof:
                obs.trace.event(PROFILE_MARK)
                p0 = time.monotonic()
                time.sleep(TRACE_FOR_S)
                p1 = time.monotonic()
                obs.trace.event(PROFILE_MARK)
            time.sleep(max(0.0, TRACE_WINDOW_S - (time.monotonic() - t0)))
            out["observations"]["spans"] = obs.trace.drain()
            obs.disable()
            out["profiled"] = (p0, p1, prof)
        else:
            time.sleep(seconds)
        t1 = time.monotonic()
        out["programs_in_window"] = run.programs_built - built
        drained = callers.finish(sv["stream_timeout_s"])
    finally:
        server.stop()
    stats = engine.stats()
    out["sound"] = (drained and stats["pool"]["used"] == 0
                    and stats["num_programs"] == len(engine.buckets) + 1)
    run.log(f"server stopped: callers drained {drained}; pages held "
            f"{stats['pool']['used']}; {stats['num_programs']} programs for "
            f"{len(engine.buckets)} buckets + 1 step; shed "
            f"{sched.stats()['shed_by_reason']}")
    out.update(records=callers.records, t0=t0, t1=t1,
               counted=sched.stats()["counted"])
    # 13 GB of weights and pool have to be gone before the reference makes
    # its own: deleted outright, whoever may still refer to the engine
    for array in jax.tree_util.tree_leaves(lm.params) + [engine.kv]:
        array.delete()
    del server, sched, callers, engine, lm
    gc.collect()
    jax.clear_caches()   # a loaded program keeps its scratch reserved
    return out


def profiled_spans(spans):
    """The spans that began between the two ``PROFILE_MARK`` events."""
    marks = sorted(s["ts"] for s in spans if s["name"] == PROFILE_MARK)
    if len(marks) != 2:
        return []
    return [s for s in spans if marks[0] <= s["ts"] < marks[1]]


def observe_moe(observations):
    """What the grouped product and the flash forward had to do while the
    profiler ran."""
    inside = profiled_spans(observations["spans"])
    calls = {"decode.step": [], "decode.prefill": []}
    for s in inside:
        a = s.get("args", {})
        if s["name"] in calls and "moe.held" in a:
            calls[s["name"]].append((a["moe.held"], a["moe.touched"]))
    observations["moe_decode"] = calls["decode.step"]
    observations["moe_prefill"] = calls["decode.prefill"]
    observations["prefill_buckets"] = [
        s["args"]["bucket"] for s in inside if s["name"] == "decode.prefill"]


def reference_gaps(run, picked, control=None):
    """{"sound": the served tokens' gaps below the reference's best, all
    positions of ``picked`` in one array} and, with ``control`` (a precision
    of the reference), {"control_<precision>": the gaps of the tokens that
    precision puts first}, judged by the same float32 logits."""
    logits = reference.served_logits(run.model, run.seed, picked, log=run.log)
    out = {"sound": np.concatenate([
        reference.gaps_below_best(lg, r["tokens"])
        for lg, r in zip(logits, picked)])}
    if control:
        lower = reference.served_logits(run.model, run.seed, picked, control,
                                        log=run.log)
        out["control_" + control] = np.concatenate([
            reference.gaps_below_best(lg, np.asarray(lo).argmax(axis=1))
            for lg, lo in zip(logits, lower)])
    return out


def describe(gaps) -> dict:
    """The numbers ``correct`` compares — the widest gap (a single token far
    from the reference: a page or a position gone wrong) and the 99th
    percentile of all gaps (the precision of the whole computation: an
    expert choice that flips on a near tie moves single tokens by up to ~1,
    so the widest gap alone sits only 2x under the fp8 control's; the 99th
    percentile sits 4x under) — and how many tokens are not the
    reference's first."""
    flipped = gaps[gaps > 0]
    return {"logit_gap": float(gaps.max()),
            "logit_gap_p99": float(np.quantile(gaps, 0.99)),
            "flips": len(flipped), "tokens": len(gaps),
            "p50_of_flips": float(np.median(flipped)) if len(flipped) else 0.0}


def within(numbers: dict, limits: dict) -> bool:
    return all(np.isfinite(numbers[name]) and numbers[name] <= limit
               for name, limit in limits.items())


def run(run):
    out = serve(run, run.seconds)
    records, t0, t1, observations = (out["records"], out["t0"], out["t1"],
                                     out["observations"])
    started = [r for r in records if t0 <= r["called"] < t1]
    finished = [r for r in started if not failed(r)]
    for r in started:
        if failed(r):
            run.log(f"failed request {r['index']}: {r['error']}, "
                    f"{len(r['tokens'])}/{r['asked']} tokens")
    tokens_in_window = sum(1 for r in records for t in r["times"]
                           if t0 <= t < t1)
    ttft = [(r["times"][0] - r["called"]) * 1e3 for r in started if r["times"]]
    gaps = [(b - a) * 1e3 for r in records
            for a, b in zip(r["times"], r["times"][1:]) if t0 <= b < t1]
    run.log(f"window {t1 - t0:.3f}s: {len(started)} requests started "
            f"({len(started) - len(finished)} failed), {tokens_in_window} "
            f"tokens received, {len(ttft)} first-token samples, {len(gaps)} "
            f"gap samples")
    metrics = {"serve_tokens_per_s": tokens_in_window / (t1 - t0),
               "itl_p95_ms": percentile(gaps, 0.95)}
    observations["ttft_ms"] = ttft
    run.log("ttft ms p50 %.1f p95 %.1f max %.1f | itl ms p50 %.1f p95 %.1f "
            "max %.1f" % (percentile(ttft, 0.5), percentile(ttft, 0.95),
                          max(ttft), percentile(gaps, 0.5),
                          metrics["itl_p95_ms"], max(gaps)))
    counted = out["counted"]      # over the whole life of the scheduler
    dropped = counted.get("moe.dropped", -1)
    run.log("moe: %d pairs, %.2f %% on held experts, most on one expert %d, "
            "dropped %d" % (counted.get("moe.assignments", 0),
                            100.0 * counted.get("moe.held", 0)
                            / max(counted.get("moe.assignments", 0), 1),
                            counted.get("moe.load_max", 0), dropped))
    if run.trace:
        # what the latent kernel had to read while the profiler ran: every
        # token a decode step produced saw its whole context
        p0, p1, prof = out["profiled"]
        seen = [(len(r["prompt"]) + i) for r in records
                for i, t in enumerate(r["times"]) if i >= 1 and p0 <= t < p1]
        observations["decode_tokens"] = len(seen)
        observations["decode_live_token_steps"] = int(sum(seen))
        observations["trace"] = reduce_trace.reduce(prof.path, 1,
                                                    prof.seconds)
        observe_moe(observations)
        run.log(f"{len(observations['moe_decode'])} steps and "
                f"{len(observations['moe_prefill'])} prefills under the "
                f"profiler")

    # the program's state is freed: now the reference
    run.program_done()
    t = time.monotonic()
    picked = sample(run, finished)
    sound = describe(reference_gaps(run, picked)["sound"])
    run.reference_s += time.monotonic() - t
    holds = within(sound, run.workload["limits"])
    run.log("correct: " + ", ".join(
        f"{name} {sound[name]:.6g} limit {limit:.6g}"
        for name, limit in run.workload["limits"].items())
        + f" {'ok' if holds else 'FAIL'} ({sound['tokens']} served tokens "
        f"of {len(picked)} requests, {sound['flips']} not the reference's "
        f"first, their median gap {sound['p50_of_flips']:.3g}; moe.dropped "
        f"{dropped}; reference {time.monotonic() - t:.2f}s)")
    return {"correct": holds and out["sound"] and dropped == 0,
            "attempted": len(started),
            "failed": len(started) - len(finished), "metrics": metrics,
            "programs_in_window": out["programs_in_window"],
            "observations": observations}


def control(run):
    """The readings the limit is set from: one short window at the cell's
    own load, then over the sampled requests the sound reading (the served
    tokens) and the control's (the tokens that the reference with fp8
    matmul operands, one step below the configuration's bfloat16, puts
    first). The control has to read above one of the limits at least, the
    sound reading below every one."""
    out = serve(run, run.seconds)
    picked = sample(run, [r for r in out["records"] if not failed(r)])
    gaps = reference_gaps(run, picked, run.workload["control"]["precision"])
    return {name: describe(g) for name, g in gaps.items()}

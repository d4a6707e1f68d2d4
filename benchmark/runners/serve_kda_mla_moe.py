"""Runner ``serve_kda_mla_moe``: ``runners/serve_swa_moe.py``'s closed loop — the
callers in a process of their own (``ChildCallers``), the same
``ServeClient.generate`` -> ``ServeServer`` -> ``DecodeScheduler`` ->
``DecodeEngine``, the same window, profile marks, observations and numbers
compared — around a model of the ``kda_mla_moe`` kind
(``mxnet_tpu.models.kda_mla_moe``: Kimi-delta-attention layers whose state
and convolution tails lie per slot beside latent-attention layers in the
page pool, group-limited sigmoid-routed experts with a shared one), whose
weights the program makes on the device from the seed and the reference
(``reference_kda_mla_moe.py``) makes again for itself, a layer at a time.

That runner names its model and its reference, so this is a file of its own
(the window logic's sixth copy: PERF.md section 7, D9); what does not name
them is imported from there and from the runners it imports from. What is
this cell's own: the pool's size is the cell's (``serve.num_pages``: the most
the closed loop holds, with room — the state, not the pool, is what a slot
costs here, and a request shed for pages fails the run); the state is freed
with the pool before the reference runs; and the tokens the one-token delta
kernel ran for while the profiler ran are read off the spans the program
counted them on (``kda.tokens``). The requests go in
``runners/serve_mla_moe.py``'s ``one_order``: the traffic block's lengths in
ONE order for every seed, with the seed's ids.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import reduce_trace, reference_kda_mla_moe as reference, traffic
from benchmark.runners.serve import (TRACE_FOR_S, TRACE_FROM_S, TRACE_WINDOW_S,
                                     failed, percentile)
from benchmark.runners.serve_gdn_moe import long_enough
from benchmark.runners.serve_mla_moe import (PROFILE_MARK, describe,
                                             observe_moe, one_order,
                                             profiled_spans, within)
from benchmark.runners.serve_ssm_moe import ChildCallers, covering_sample


def serve(run, seconds):
    """Build the model and the engine from the seed, serve ``ramp_s`` and
    then the window, let the callers finish, stop the server and free the
    engine, its state and the weights. Returns what the window left
    behind."""
    import gc

    import jax

    from mxnet_tpu import obs
    from mxnet_tpu.models.kda_mla_moe import KDAMLAMoEDecodeModel
    from mxnet_tpu.serve import DecodeEngine, DecodeScheduler, ServeServer

    model, sv = run.model, run.workload["serve"]
    requests = one_order(traffic.serve_requests(run.traffic, model, run.seed))
    lm = KDAMLAMoEDecodeModel(model, seed=run.seed)
    run.log("weights made on the device")
    slots = sv["slots"]
    engine = DecodeEngine(lm, slots=slots, page_size=sv["page_size"],
                          prompt_buckets=sv["prompt_buckets"],
                          num_pages=sv["num_pages"])
    stats = engine.stats()
    run.log(f"engine built: {slots} slots, {engine.num_pages} pages of "
            f"{engine.page_size} x {engine.cache_row_bytes} B in "
            f"{stats['paged_layers']} paged layers, {stats['state_bytes']} B "
            f"of state a slot {stats['state']}, pieces of "
            f"{stats['prefill_piece']} up to {stats['max_prompt']}, row tiles "
            f"{stats['moe_row_tile']}")
    engine.warmup()
    run.log(f"warm-up done: {engine.stats()['num_programs']} programs; step "
            f"program {engine.stats()['step_program']}")
    sched = DecodeScheduler(engine, max_queue=4 * slots,
                            default_timeout=sv["stream_timeout_s"])
    server = ServeServer(engine=None, decode=sched, port=0)
    server.start()
    callers = ChildCallers(server.port, requests, sv["clients"],
                           sv["stream_timeout_s"])
    out = {"observations": {
        "slots": slots, "model": model, "one": 1,
        "moe_groups": model["experts_held"] * len(lm.expert_layers),
        "device_kind": run.devices[0].device_kind}}
    try:
        callers.start()
        time.sleep(sv["ramp_s"])
        built = run.open_window()
        t0, pieces = run.window_start, sched.stats()["prefill_pieces"]
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
        # every piece stalls all the streams for its length: tokens/s of a
        # window follows how many it caught (PERF.md section 2)
        out["pieces_in_window"] = sched.stats()["prefill_pieces"] - pieces
        out["programs_in_window"] = run.programs_built - built
        drained = callers.finish(sv["stream_timeout_s"])
    finally:
        server.stop()
    stats, shed = engine.stats(), sched.stats()["shed_by_reason"]
    out["sound"] = (drained and stats["pool"]["used"] == 0
                    and not any(shed.values())
                    and stats["num_programs"] == len(engine.buckets) + 1)
    run.log(f"server stopped: callers drained {drained}; pages held "
            f"{stats['pool']['used']} (most at once "
            f"{stats['pool'].get('peak_used')} of {engine.num_pages - 1}); "
            f"{stats['num_programs']} programs for {len(engine.buckets)} "
            f"piece + 1 step; {sched.stats()['prefill_pieces']} pieces for "
            f"{sched.stats()['admitted']} prompts; launched ahead "
            f"{sched.stats()['launched_ahead_share']:.4f}; shed {shed}")
    out.update(records=callers.records, t0=t0, t1=t1,
               counted=sched.stats()["counted"])
    # 11 GB of weights, state and pool have to be gone before the reference
    # makes its own: deleted outright, whoever may still refer to the engine
    for array in (jax.tree_util.tree_leaves(lm.params) + [engine.kv]
                  + list(engine.state.values())):
        array.delete()
    del server, sched, callers, engine, lm
    gc.collect()
    jax.clear_caches()   # a loaded program keeps its scratch reserved
    return out


def observe_kda(observations):
    """The (token, KDA layer) pairs the one-token delta kernel ran for while
    the profiler ran, as the program counted them on its ``decode.step``
    spans, and the steps' live tokens from the same spans."""
    steps = [s["args"] for s in profiled_spans(observations["spans"])
             if s["name"] == "decode.step" and "kda.tokens" in s.get("args", {})]
    observations["kda_decode_tokens"] = sum(a["kda.tokens"] for a in steps)
    observations["decode_tokens"] = sum(a["active"] for a in steps)


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
            f"({len(started) - len(finished)} failed), "
            f"{out['pieces_in_window']} pieces, {tokens_in_window} "
            f"tokens received, {len(ttft)} first-token samples, {len(gaps)} "
            f"gap samples")
    metrics = {"serve_tokens_per_s": tokens_in_window / (t1 - t0),
               "itl_p95_ms": percentile(gaps, 0.95),
               "itl_p99_ms": percentile(gaps, 0.99)}
    observations["ttft_ms"], observations["itl_ms"] = ttft, gaps
    run.log("ttft ms p50 %.1f p95 %.1f max %.1f | itl ms p50 %.1f p90 %.1f "
            "p95 %.1f p97 %.1f p99 %.1f max %.1f | share of gaps over twice "
            "the median %.4f | tokens/s %.1f" % (
                percentile(ttft, 0.5), percentile(ttft, 0.95),
                max(ttft, default=0.0),
                percentile(gaps, 0.5), percentile(gaps, 0.9),
                metrics["itl_p95_ms"], percentile(gaps, 0.97),
                metrics["itl_p99_ms"], max(gaps),
                np.mean(np.asarray(gaps) > 2 * percentile(gaps, 0.5)),
                metrics["serve_tokens_per_s"]))
    counted = out["counted"]      # over the whole life of the scheduler
    dropped = counted.get("moe.dropped", -1)
    pairs = max(counted.get("moe.assignments", 0), 1)
    run.log("moe: %d pairs, %.2f %% on held experts (%d rows run for them, "
            "%d touched experts), most on one expert %d, dropped %d | "
            "tokens that kept a held group %d, (token, KDA layer) pairs "
            "%d" % (
                counted.get("moe.assignments", 0),
                100.0 * counted.get("moe.held", 0) / pairs,
                counted.get("moe.rows_run", 0), counted.get("moe.touched", 0),
                counted.get("moe.load_max", 0), dropped,
                counted.get("moe.group_hit", 0),
                counted.get("kda.tokens", 0)))
    if run.trace:
        p0, p1, prof = out["profiled"]
        seen = [(len(r["prompt"]) + i) for r in records
                for i, t in enumerate(r["times"]) if i >= 1 and p0 <= t < p1]
        observations["decode_live_token_steps"] = int(sum(seen))
        observations["trace"] = reduce_trace.reduce(prof.path, 1,
                                                    prof.seconds)
        observe_moe(observations)
        observe_kda(observations)
        run.log(f"{len(observations['moe_decode'])} steps and "
                f"{len(observations['moe_prefill'])} pieces (of "
                f"{sorted(set(observations['prefill_buckets']))}) under the "
                f"profiler; {observations['decode_tokens']} step tokens, "
                f"{observations['kda_decode_tokens']} (token, KDA layer) "
                f"pairs, {observations['decode_live_token_steps']} latent "
                f"rows a paged layer")

    # the program's state is freed: now the reference
    run.program_done()
    t = time.monotonic()
    picked = covering_sample(run, finished)
    covered = long_enough(run, picked)
    sound = describe(reference_gaps(run, picked)["sound"])
    run.reference_s += time.monotonic() - t
    holds = within(sound, run.workload["limits"])
    run.log("correct: " + ", ".join(
        f"{name} {sound[name]:.6g} limit {limit:.6g}"
        for name, limit in run.workload["limits"].items())
        + f" {'ok' if holds else 'FAIL'} (widest gap {sound['logit_gap']:.6g}; "
        f"{sound['tokens']} served tokens "
        f"of {len(picked)} requests, prompts "
        f"{[len(r['prompt']) for r in picked]}, long enough: {covered}; "
        f"{sound['flips']} not the reference's first, their median gap "
        f"{sound['p50_of_flips']:.3g}; moe.dropped {dropped}; reference "
        f"{time.monotonic() - t:.2f}s)")
    return {"correct": holds and covered and out["sound"] and dropped == 0,
            "attempted": len(started),
            "failed": len(started) - len(finished), "metrics": metrics,
            "programs_in_window": out["programs_in_window"],
            "observations": observations}


def control(run):
    """The readings the limits are set from: one short window at the cell's
    own load, then over the sampled requests the sound reading (the served
    tokens) and the control's (the tokens that the reference with fp8
    matmul operands, one step below the configuration's bfloat16, puts
    first). The control has to read above one of the limits at least, the
    sound reading below every one."""
    out = serve(run, run.seconds)
    picked = covering_sample(run, [r for r in out["records"]
                                   if not failed(r)])
    gaps = reference_gaps(run, picked, run.workload["control"]["precision"])
    return {name: describe(g) for name, g in gaps.items()}

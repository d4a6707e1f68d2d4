"""Runner ``serve_ssm_moe``: ``runners/serve_gdn_moe.py``'s closed loop — the
same callers, the same ``ServeClient.generate`` -> ``ServeServer`` ->
``DecodeScheduler`` -> ``DecodeEngine``, the same window, profile marks,
observations and numbers compared — around a model of the ``ssm_moe`` kind
(``mxnet_tpu.models.ssm_moe``: Mamba-2 state-space layers that keep a
fixed-size state per slot beside the page pool, grouped-KV attention through
the pool, routed and shared experts of two products), whose weights the
program makes on the device from the seed and the reference
(``reference_ssm_moe.py``) makes again for itself, a layer at a time.

That runner names its model and its reference, so this is a file of its own;
what does not name them is imported from there and from
``runners/serve_mla_moe.py``. The callers are ``runners/serve.py``'s
``Callers``, one thread a slot (128 here), started in a process of their own
(:class:`ChildCallers`): in the server's process their threads share its
interpreter lock, and at this cell's 4,900 tokens a second the device then
idled a quarter of a traced window behind the scheduler's turn (25.4 %
against 0.1-2.7 %, 3,437 tokens/s against 4,668-4,742; my chip runs, PR 40).
The cell holds ``serve_tokens_per_s`` (its 99th gap spread 1.4 and 2.3 %
over two sets of six seeds, too near half of that metric's bound: it is
printed in each run's ``itl ms`` line and held by no one), so a traced run
gives the scheduler's and the device's idle readers what they take (the
spans, ``slots``, the reduced trace).
"""
from __future__ import annotations

import os
import pickle
import subprocess
import sys
import time

import numpy as np

from benchmark import reduce_trace, reference_ssm_moe as reference, traffic
from benchmark.runners.serve import (TRACE_FOR_S, TRACE_FROM_S, TRACE_WINDOW_S,
                                     Callers, failed, percentile, sample)
from benchmark.runners.serve_gdn_moe import long_enough
from benchmark.runners.serve_mla_moe import (PROFILE_MARK, describe,
                                             observe_moe, within)


class ChildCallers:
    """``runners/serve.py``'s ``Callers`` — the same threads, the same
    ``ServeClient.generate`` over the same wire, the same records — in a
    process of their own: 128 callers taking thousands of tokens a second
    share one interpreter lock with the scheduler when they live in the
    server's process, and no deployment's clients do (module docstring: what
    the device's idle share read both ways). The child is this module run as
    a script
    (``JAX_PLATFORMS=cpu``: it opens sockets and never a device); its clock
    is the parent's (``time.monotonic`` is the machine's). The records
    come back when the callers have finished."""

    def __init__(self, port, requests, n, rpc_timeout):
        self._args = (port, requests, n, rpc_timeout)
        self.records, self._child = [], None

    def start(self):
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        self._child = subprocess.Popen(
            [sys.executable, "-m", "benchmark.runners.serve_ssm_moe"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, cwd=root,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        pickle.dump(self._args, self._child.stdin)
        self._child.stdin.flush()
        if self._child.stdout.readline() != b"started\n":
            raise RuntimeError("the callers' process did not start")

    def finish(self, timeout):
        """No new requests; wait ``timeout`` seconds for the ones in
        flight and a minute for the records: a child that has died or
        hangs ends the run with an error, not at the driver's limit."""
        try:
            out, _ = self._child.communicate(b"%r\n" % float(timeout),
                                             timeout=timeout + 60)
        except subprocess.TimeoutExpired:
            self._child.kill()
            self._child.communicate()
            raise RuntimeError("the callers' process did not finish")
        if self._child.returncode or not out:
            raise RuntimeError("the callers' process ended without its "
                               f"records (exit {self._child.returncode})")
        drained, self.records = pickle.loads(out)
        return drained


def _callers_process():
    """The child's side of :class:`ChildCallers`."""
    callers = Callers(*pickle.load(sys.stdin.buffer))
    callers.start()
    sys.stdout.buffer.write(b"started\n")
    sys.stdout.buffer.flush()
    drained = callers.finish(float(sys.stdin.buffer.readline()))
    pickle.dump((drained, callers.records), sys.stdout.buffer)
    sys.stdout.buffer.flush()


def serve(run, seconds):
    """Build the model and the engine from the seed, serve ``ramp_s`` and
    then the window, let the callers finish, stop the server and free the
    engine, its state and the weights. Returns what the window left
    behind."""
    import gc

    import jax

    from mxnet_tpu import obs
    from mxnet_tpu.models.ssm_moe import SSMMoEDecodeModel
    from mxnet_tpu.serve import DecodeEngine, DecodeScheduler, ServeServer

    model, sv = run.model, run.workload["serve"]
    requests = traffic.serve_requests(run.traffic, model, run.seed)
    lm = SSMMoEDecodeModel(model, seed=run.seed)
    run.log("weights made on the device")
    slots = sv["slots"]
    engine = DecodeEngine(
        lm, slots=slots, page_size=sv["page_size"],
        prompt_buckets=sv["prompt_buckets"],
        num_pages=slots * (model["max_length"] // sv["page_size"]) + 1)
    stats = engine.stats()
    run.log(f"engine built: {slots} slots, {engine.num_pages} pages of "
            f"{engine.page_size} x {engine.cache_row_bytes} B in "
            f"{stats['paged_layers']} paged layers, {stats['state_bytes']} B "
            f"of state a slot {stats['state']}, buckets {engine.buckets}")
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
            f"{len(engine.buckets)} buckets + 1 step; launched ahead "
            f"{sched.stats()['launched_ahead_share']:.4f}; shed "
            f"{sched.stats()['shed_by_reason']}")
    out.update(records=callers.records, t0=t0, t1=t1,
               counted=sched.stats()["counted"])
    # 10 GB of weights, pool and state have to be gone before the reference
    # makes its own: deleted outright, whoever may still refer to the engine
    for array in (jax.tree_util.tree_leaves(lm.params) + [engine.kv]
                  + list(engine.state.values())):
        array.delete()
    del server, sched, callers, engine, lm
    gc.collect()
    jax.clear_caches()   # a loaded program keeps its scratch reserved
    return out


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


def covering_sample(run, finished):
    """``runners/serve.py``'s seeded sample (the longest request among
    them), made to hold a request of the cell's ``check_needs``: a fifth of
    this cell's prompts are that long, so a sample of six lacks one in a run
    in four. Its last place then goes to the longest finished request that
    fits, if there is one (``correct`` still asks: ``long_enough``)."""
    picked = sample(run, finished)
    if not long_enough(run, picked):
        fits = [r for r in finished if long_enough(run, [r])]
        if fits:
            picked[-1] = max(fits, key=lambda r: len(r["prompt"])
                             + len(r["tokens"]))
    return picked


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
               "itl_p95_ms": percentile(gaps, 0.95),
               "itl_p99_ms": percentile(gaps, 0.99)}
    observations["ttft_ms"], observations["itl_ms"] = ttft, gaps
    run.log("ttft ms p50 %.1f p95 %.1f max %.1f | itl ms p50 %.1f p90 %.1f "
            "p95 %.1f p97 %.1f p99 %.1f max %.1f | share of gaps over twice "
            "the median %.4f | tokens/s %.1f" % (
                percentile(ttft, 0.5), percentile(ttft, 0.95), max(ttft),
                percentile(gaps, 0.5), percentile(gaps, 0.9),
                metrics["itl_p95_ms"], percentile(gaps, 0.97),
                metrics["itl_p99_ms"], max(gaps),
                np.mean(np.asarray(gaps) > 2 * percentile(gaps, 0.5)),
                metrics["serve_tokens_per_s"]))
    counted = out["counted"]      # over the whole life of the scheduler
    dropped = counted.get("moe.dropped", -1)
    run.log("moe: %d pairs, %.2f %% on held experts, most on one expert %d, "
            "dropped %d" % (counted.get("moe.assignments", 0),
                            100.0 * counted.get("moe.held", 0)
                            / max(counted.get("moe.assignments", 0), 1),
                            counted.get("moe.load_max", 0), dropped))
    if run.trace:
        # what the kernels had to read while the profiler ran: every token a
        # decode step produced saw its whole context and its slot's state
        p0, p1, prof = out["profiled"]
        seen = [(len(r["prompt"]) + i) for r in records
                for i, t in enumerate(r["times"]) if i >= 1 and p0 <= t < p1]
        observations["decode_tokens"] = len(seen)
        observations["decode_live_token_steps"] = int(sum(seen))
        observations["trace"] = reduce_trace.reduce(prof.path, 1,
                                                    prof.seconds)
        observe_moe(observations)
        run.log(f"{len(observations['moe_decode'])} steps and "
                f"{len(observations['moe_prefill'])} prefills (buckets "
                f"{observations['prefill_buckets']}) under the profiler")

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


if __name__ == "__main__":
    _callers_process()

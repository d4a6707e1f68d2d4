"""Runner ``serve``: generation through the whole served path in one process:
``ServeClient.generate`` in threads -> ``ServeServer`` on a local port ->
``DecodeScheduler`` -> ``DecodeEngine`` (the pattern of ``chip_smoke.py``;
the client timing loop is copied from ``tools/serve_bench.py``).

Closed loop: ``clients`` callers each send their next request when the last
one ends. The callers run for ``ramp_s`` before the window opens, so that
the window sees the steady state and not sixteen prompts arriving at once.
After the window the callers finish what they hold, the server stops, the
engine is freed, and the plain reference runs once over a seeded sample of
the finished requests (the longest among them): the number compared is the
widest gap by which a served token's logit lies below the reference's best
at its position.
"""
from __future__ import annotations

import itertools
import threading
import time

import numpy as np

from benchmark import reduce_trace, reference, traffic

TRACE_WINDOW_S = 8.0    # a traced run's window: spans over all of it,
TRACE_FROM_S = 2.0      # the profiler from here
TRACE_FOR_S = 3.0       # for this long


def percentile(values, q):
    """The q-quantile by rank (no interpolation): the value a share q of
    the samples does not exceed."""
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, int(np.ceil(q * len(ordered))) - 1)]


def engine_params(weights: dict, model: dict) -> dict:
    """The seeded weights in the layout ``DecodeEngine(cfg, params=...)``
    documents (``models.transformer.decode_params``)."""
    flat = reference.per_leaf(weights)
    layers = [{k: flat[f"layer{i}.{k}"] for k, _, _ in
               reference.LAYER_SHAPES} for i in range(model["num_layers"])]
    return {"embed": flat["embed"], "pos": flat["pos"],
            "final_g": flat["head_ln_g"], "final_b": flat["head_ln_b"],
            "dec_w": flat["head_w"], "dec_b": flat["head_b"],
            "layers": layers}


class Callers:
    """``n`` closed-loop callers over one list of requests, taken in order
    through a shared counter. Every request leaves a record: when it was
    called, when each token came, the tokens, how it ended."""

    def __init__(self, port, requests, n, rpc_timeout):
        self.port, self.requests, self.rpc_timeout = port, requests, rpc_timeout
        self.records, self.lock = [], threading.Lock()
        self._next = itertools.count()
        self._stop = threading.Event()
        self.threads = [threading.Thread(target=self._call, daemon=True)
                        for _ in range(n)]

    def start(self):
        for t in self.threads:
            t.start()

    def finish(self, timeout):
        """No new requests; wait for the ones in flight."""
        self._stop.set()
        deadline = time.monotonic() + timeout
        for t in self.threads:
            t.join(max(0.0, deadline - time.monotonic()))
        return not any(t.is_alive() for t in self.threads)

    def _call(self):
        from mxnet_tpu import serve

        with serve.ServeClient("127.0.0.1", self.port) as client:
            while not self._stop.is_set():
                with self.lock:
                    index = next(self._next)
                req = self.requests[index % len(self.requests)]
                rec = {"index": index, "prompt": req["prompt"],
                       "asked": req["max_new_tokens"], "tokens": [],
                       "times": [], "called": time.monotonic(), "error": None}
                try:
                    for tok in client.generate(
                            req["prompt"],
                            max_new_tokens=req["max_new_tokens"],
                            rpc_timeout=self.rpc_timeout):
                        rec["times"].append(time.monotonic())
                        rec["tokens"].append(int(tok))
                except serve.ServeError as e:   # shed, deadline, broken stream
                    rec["error"] = f"{type(e).__name__}: {e}"
                rec["ended"] = time.monotonic()
                with self.lock:
                    self.records.append(rec)


def failed(rec) -> bool:
    return rec["error"] is not None or len(rec["tokens"]) != rec["asked"]


def widest_gap(model, weights, requests, precision=None, log=None):
    """The widest gap, over the served tokens of ``requests``, by which a
    token's reference logit lies below the reference's best at its position.
    With ``precision`` the token judged is not the served one but the one
    that precision puts first: the control's reading."""
    widest, flips, n_tokens = 0.0, 0, 0
    for rec in requests:
        judge = None
        if precision is not None:
            _, judge = reference.served_gaps(model, weights, rec["prompt"],
                                             rec["tokens"], precision)
        gaps, _ = reference.served_gaps(model, weights, rec["prompt"],
                                        rec["tokens"], judge=judge)
        widest = max(widest, float(gaps.max()))
        flips += int((gaps > 0).sum())
        n_tokens += len(rec["tokens"])
    return widest, flips, n_tokens


def sample(run, finished):
    """A seeded sample of the finished requests, the longest among them."""
    rng = np.random.default_rng(np.random.SeedSequence([run.seed, 2]))
    n = min(run.workload["serve"]["check_requests"], len(finished))
    picked = [finished[i] for i in rng.choice(len(finished), n, replace=False)]
    longest = max(finished, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    if not any(r is longest for r in picked):
        picked[0] = longest
    return picked


def serve(run, seconds):
    """Build the engine from the seeded weights, serve ``ramp_s`` and then
    the window, let the callers finish, stop the server and free the
    engine. Returns what the window left behind."""
    import gc

    from mxnet_tpu import obs
    from mxnet_tpu.serve import DecodeEngine, DecodeScheduler, ServeServer

    model, sv = run.model, run.workload["serve"]
    requests = traffic.serve_requests(run.traffic, model, run.seed)
    weights = reference.make_weights(model, run.seed)
    cfg = {"vocab": model["vocab_size"], "units": model["units"],
           "heads": model["num_heads"],
           "head_dim": model["units"] // model["num_heads"],
           "layers": model["num_layers"], "max_length": model["max_length"]}
    slots = sv["slots"]
    engine = DecodeEngine(
        cfg, params=engine_params(weights, model), slots=slots,
        page_size=sv["page_size"], prompt_buckets=sv["prompt_buckets"],
        num_pages=slots * (model["max_length"] // sv["page_size"]) + 1)
    del weights
    run.log(f"engine built: {slots} slots, {engine.num_pages} pages of "
            f"{engine.page_size}, buckets {engine.buckets}")
    engine.warmup()
    run.log(f"warm-up done: {engine.stats()['num_programs']} programs")
    sched = DecodeScheduler(engine, max_queue=4 * slots,
                            default_timeout=sv["stream_timeout_s"])
    server = ServeServer(engine=None, decode=sched, port=0)
    server.start()
    callers = Callers(server.port, requests, sv["clients"],
                      sv["stream_timeout_s"])
    out = {"observations": {"slots": slots, "model": model, "one": 1,
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
                p0 = time.monotonic()
                time.sleep(TRACE_FOR_S)
                p1 = time.monotonic()
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
    out.update(records=callers.records, t0=t0, t1=t1)
    del server, sched, callers, engine
    gc.collect()
    return out


def run(run):
    model = run.model
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
    run.log("ttft ms p50 %.1f p95 %.1f max %.1f | itl ms p50 %.1f p95 %.1f "
            "max %.1f" % (percentile(ttft, 0.5), percentile(ttft, 0.95),
                          max(ttft), percentile(gaps, 0.5),
                          metrics["itl_p95_ms"], max(gaps)))
    eighths = np.histogram([t for r in records for t in r["times"]], bins=8,
                           range=(t0, t1))[0] * 8 / (t1 - t0)
    run.log("itl ms mean %.3f p90 %.2f p97 %.2f p99 %.2f | tokens/s by "
            "eighth of the window: %s" % (
                sum(gaps) / len(gaps), percentile(gaps, 0.9),
                percentile(gaps, 0.97), metrics["itl_p99_ms"],
                " ".join("%.0f" % rate for rate in eighths)))
    stalls = sorted((b - t0, b - a) for r in records
                    for a, b in zip(r["times"], r["times"][1:])
                    if t0 <= b < t1 and b - a > 0.5)
    if stalls:   # a gap of many steps: say when, and on how many streams
        run.log(f"{len(stalls)} token gaps over 0.5 s, at (window s, gap s): "
                + " ".join(f"({at:.2f},{gap:.2f})" for at, gap in stalls[:24]))
    if run.trace:
        # what the paged kernel had to read while the profiler ran: every
        # token a decode step produced saw its whole context
        p0, p1, prof = out["profiled"]
        seen = [(len(r["prompt"]) + i) for r in records
                for i, t in enumerate(r["times"]) if i >= 1 and p0 <= t < p1]
        observations["decode_tokens"] = len(seen)
        observations["decode_live_token_steps"] = int(sum(seen))
        observations["trace"] = reduce_trace.reduce(prof.path, 1,
                                                    prof.seconds)

    # the program's state is freed: now the reference
    run.program_done()
    t = time.monotonic()
    limit = run.workload["limits"]["logit_gap"]
    picked = sample(run, finished)
    weights = reference.make_weights(model, run.seed)
    widest, flips, n_tokens = widest_gap(model, weights, picked)
    del weights
    run.reference_s += time.monotonic() - t
    holds = bool(np.isfinite(widest)) and widest <= limit
    run.log(f"correct: logit_gap {widest:.6g} limit {limit:.6g} "
            f"{'ok' if holds else 'FAIL'} ({n_tokens} served tokens of "
            f"{len(picked)} requests, {flips} not the reference's first; "
            f"reference {time.monotonic() - t:.2f}s)")
    return {"correct": holds and out["sound"], "attempted": len(started),
            "failed": len(started) - len(finished), "metrics": metrics,
            "programs_in_window": out["programs_in_window"],
            "observations": observations}


def control(run):
    """The readings a limit is set from: one short window at the cell's own
    load, then over every finished request the sound reading (the served
    tokens) and the controls' (the tokens that the reference computed in a
    lower precision puts first)."""
    out = serve(run, run.seconds)
    finished = [r for r in out["records"] if not failed(r)]
    weights = reference.make_weights(run.model, run.seed)
    numbers = {}
    for name, precision in (("sound", None), ("control_high", "high"),
                            ("control_bf16", "bf16")):
        widest, flips, n = widest_gap(run.model, weights, finished, precision)
        numbers[name] = {"logit_gap": widest, "flips": flips, "tokens": n}
    return numbers

"""Load generator for the mxnet_tpu serving endpoint (docs/SERVING.md).

Two drive modes against an in-process endpoint (or ``--connect host:port``
for an external one):

- **closed-loop** (``--mode closed``): N client threads, each sending the
  next request the moment the previous reply lands. Measures the
  throughput ceiling and the latency the system settles at under maximum
  sustainable pressure.
- **open-loop** (``--mode open``): requests arrive on a Poisson process at
  ``--qps`` offered load, regardless of completions — the honest way to
  measure tail latency under a traffic model (closed-loop self-throttles
  and hides queueing collapse). Sheds (429s / deadline misses) are counted,
  not retried: under overload, shedding IS the designed behavior.

Reports p50/p95/p99 latency, achieved throughput vs offered load, shed
rate, and the engine's compiled-program count (the bucketing bound), as a
table and one JSON line (``--json``). ``bench.py`` imports ``run_bench``
for the ``serve_qps`` / ``serve_p99_ms`` headline gains.

**Scale mode** (``--scale``): closed-loop qps through dp∈{1,2,4}
tensor-parallel replica groups on mesh slices (one FleetServer front over
``ReplicaPool.sharded``) — the ROADMAP item 1 near-linear-scaling number,
reported as ``scaling_dp4``.

**Ramp mode** (``--ramp``): open-loop offered load climbs ``--qps-lo`` →
``--qps-hi`` while the SLO Autoscaler (``serve/autoscale.py``) watches
windowed error-budget burn + queue depth + occupancy and grows the fleet
from one replica group toward ``--groups``. Reports every scale event with
its timestamp and reason, shed/error counts, and per-third latency
windows — measured autoscale-out, not a claim.

**Chaos mode** (``--chaos``, ``make chaos-serve``): the same open-loop
Poisson load is driven through a supervised replica fleet
(``serve/fleet.py``: pool + failover router + one socket front), one
replica is hard-killed a third of the way in, and the pool restarts it.
The report buckets every request into before / during / after windows
around the kill→recovery interval and prints error rate and p50/p99 per
window — degradation under replica death is a measured number, not a
claim.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return float("nan")
    idx = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[idx]


def _build_model(model: str, classes: int = 10):
    """Return (symbol, arg_params, aux_params, feature_shape)."""
    import mxnet_tpu as mx
    from mxnet_tpu import symbol as sym

    rng = np.random.RandomState(0)
    if model == "mlp":
        data = sym.Variable("data")
        net = sym.FullyConnected(data, num_hidden=64, name="fc1")
        net = sym.Activation(net, act_type="relu", name="relu1")
        net = sym.FullyConnected(net, num_hidden=classes, name="fc2")
        net = sym.softmax(net, name="prob")
        arg = {"fc1_weight": rng.randn(64, 32).astype(np.float32) * 0.1,
               "fc1_bias": np.zeros(64, np.float32),
               "fc2_weight": rng.randn(classes, 64).astype(np.float32) * 0.1,
               "fc2_bias": np.zeros(classes, np.float32)}
        return net, arg, {}, (32,)
    # model-zoo CNN traced to a symbol
    from mxnet_tpu.gluon.model_zoo import get_model
    from mxnet_tpu import nd

    mx.random.seed(0)
    img = int(os.environ.get("SERVE_BENCH_IMAGE_SIZE", 32))
    zoo = get_model(model, classes=classes, thumbnail=True)
    zoo.initialize()
    zoo(nd.array(rng.rand(1, 3, img, img).astype(np.float32)))  # shapes
    traced = zoo(sym.Variable("data"))
    net = sym.softmax(traced, name="prob")
    # split by the traced graph's own arg/aux view (shared helper)
    from mxnet_tpu.serve import _split_arg_aux

    all_params = {p.name: p.data() for p in zoo._iter_params()}
    arg, aux = _split_arg_aux(all_params, net)
    return net, arg, aux, (3, img, img)


def run_bench(model="mlp", mode="closed", duration=5.0, clients=4, qps=200.0,
              max_batch_size=8, max_linger_ms=2.0, deadline_ms=None,
              request_rows=1, connect=None, warmup=True):
    """Drive the endpoint; returns the result dict (see module doc)."""
    from mxnet_tpu import serve

    srv = None
    feat = None
    if connect:
        host, _, port = connect.partition(":")
        addr = (host, int(port))
        engine = None
        feat_env = os.environ.get("SERVE_BENCH_FEATURE", "32")
        feat = tuple(int(d) for d in feat_env.split(",") if d)
    else:
        net, arg, aux, feat = _build_model(model)
        engine = serve.InferenceEngine(net, arg, aux,
                                       max_batch_size=max_batch_size,
                                       lint="off")
        if warmup:
            engine.warmup(feat)  # compiles never pollute latency numbers
        srv = serve.ServeServer(engine, port=0, max_linger_ms=max_linger_ms)
        srv.start()
        addr = ("127.0.0.1", srv.port)

    rng = np.random.RandomState(1)
    payload = rng.rand(request_rows, *feat).astype(np.float32)
    lat_lock = threading.Lock()
    latencies: list = []
    shed = [0]
    errors = [0]
    stop_at = [0.0]

    def one_request(cli):
        t0 = time.perf_counter()
        try:
            cli.infer(payload, deadline_ms=deadline_ms)
        except (serve.RequestRejected, serve.DeadlineExceeded):
            with lat_lock:
                shed[0] += 1
            return
        except serve.ServeError:
            with lat_lock:
                errors[0] += 1
            return
        dt = time.perf_counter() - t0
        with lat_lock:
            latencies.append(dt)

    t_start = time.perf_counter()
    stop_at[0] = t_start + duration
    if mode == "closed":
        def closed_worker():
            cli = serve.ServeClient(*addr)
            while time.perf_counter() < stop_at[0]:
                one_request(cli)
            cli.close()

        threads = [threading.Thread(target=closed_worker)
                   for _ in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        offered = None
    elif mode == "open":
        # Poisson arrivals: a dispatcher sleeps exponential gaps and hands
        # each request to a pooled connection — arrivals NEVER wait on
        # completions (that would quietly turn the experiment closed-loop
        # and hide queueing collapse), so the pool grows on exhaustion
        pool = [serve.ServeClient(*addr) for _ in range(max(clients, 8))]
        free = list(range(len(pool)))
        free_lock = threading.Lock()
        inflight = []
        n_sent = 0

        def fire(idx):
            one_request(pool[idx])
            with free_lock:
                free.append(idx)

        while time.perf_counter() < stop_at[0]:
            gap = rng.exponential(1.0 / qps)
            time.sleep(gap)
            with free_lock:
                if free:
                    idx = free.pop()
                else:  # all connections busy: open another, don't stall
                    pool.append(serve.ServeClient(*addr))
                    idx = len(pool) - 1
            th = threading.Thread(target=fire, args=(idx,))
            th.start()
            inflight.append(th)
            n_sent += 1
        for th in inflight:
            th.join(timeout=30)
        for cli in pool:
            cli.close()
        offered = n_sent / (time.perf_counter() - t_start)
    else:
        raise ValueError(f"unknown mode {mode!r}")
    wall = time.perf_counter() - t_start

    lat = sorted(latencies)
    n_ok = len(lat)
    out = {
        "model": model, "mode": mode, "clients": clients,
        "request_rows": request_rows, "duration_s": round(wall, 2),
        "completed": n_ok, "shed": shed[0], "errors": errors[0],
        "qps": round(n_ok * request_rows / wall, 2),
        "p50_ms": round(_percentile(lat, 0.50) * 1e3, 3) if lat else None,
        "p95_ms": round(_percentile(lat, 0.95) * 1e3, 3) if lat else None,
        "p99_ms": round(_percentile(lat, 0.99) * 1e3, 3) if lat else None,
        "max_ms": round(lat[-1] * 1e3, 3) if lat else None,
    }
    if offered is not None:
        out["offered_qps"] = round(offered * request_rows, 2)
        out["shed_rate"] = round(shed[0] / max(shed[0] + n_ok, 1), 4)
    if engine is not None:
        out["compiled_programs"] = engine.num_programs
        out["buckets"] = list(engine.buckets)
    if srv is not None:
        srv.stop()
    return out


def run_cold_bench(model="mlp", max_batch_size=8, timeout=180.0,
                   keep_artifact=None):
    """Cold-start-to-ready A/B (docs/PERFORMANCE.md "Program cache and
    cold start"): spawn a fresh ProcReplica against an empty persistent
    program cache (cold — every bucket pays an XLA compile at warmup),
    SIGKILL it, then spawn another against the now-populated cache (warm —
    every bucket deserializes). ``cold_start_to_ready_s`` is wall time
    from process spawn to the readiness probe answering OK, measured by
    the parent — the number a fleet autoscaler actually waits on.

    The gate is on the deterministic quantity: the warm replica must
    perform ZERO fresh XLA compilations (every compile_log entry a
    ``cache_hit``, strictly fewer compiles than cold). Wall times are
    reported honestly — on a small host the jax import dominates tiny
    models, so the time win tracks model size (``host_cores`` noted)."""
    import shutil
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu import serve
    from mxnet_tpu.model import save_checkpoint

    # a temp dir on purpose, unlike every other cache in the checkout: the
    # cold leg is DEFINED by an empty program cache, so it cannot live at
    # progcache.default_dir()'s fixed path, where an earlier run's entries
    # would make it warm
    tmp = keep_artifact or tempfile.mkdtemp(prefix="mxnet-coldstart-")
    created = keep_artifact is None
    try:
        net, arg, aux, feat = _build_model(model)
        prefix = os.path.join(tmp, "model")
        save_checkpoint(
            prefix, 0, net,
            {k: mx.nd.array(np.asarray(v)) for k, v in arg.items()},
            {k: mx.nd.array(np.asarray(v)) for k, v in aux.items()})
        cache_dir = os.path.join(tmp, "progcache")
        shape_arg = ",".join(str(d) for d in feat)
        # replicas run on the REAL backend topology: the test harness's
        # --xla_force_host_platform_device_count emulation changes XLA:CPU
        # codegen so bucket kernels hash-collide across programs, and the
        # JIT's process-wide kernel dedup then yields executables that are
        # not self-contained — progcache refuses those exports (correctly),
        # which would make this A/B measure the emulation, not the cache
        xla_flags = " ".join(
            tok for tok in os.environ.get("XLA_FLAGS", "").split()
            if not tok.startswith("--xla_force_host_platform_device_count"))
        legs = {}
        for leg in ("cold", "warm"):
            # an inherited MXNET_PROGCACHE=0 veto would silently disable
            # the explicit cache dir and mis-diagnose as key instability —
            # this A/B's whole point is the armed cache, so override it
            rep = serve.ProcReplica(
                prefix,
                args=["--epoch", "0", "--warmup-shape", shape_arg,
                      "--max-batch-size", str(max_batch_size)],
                env={"XLA_FLAGS": xla_flags, "MXNET_PROGCACHE": "1"},
                progcache_dir=cache_dir)
            rep.idx = 0
            t0 = time.perf_counter()
            cli = None
            graceful = False
            try:
                addr = rep.start()
                cli = serve.ServeClient(*addr, timeout=5.0)
                ready = False
                deadline = time.perf_counter() + timeout
                while time.perf_counter() < deadline:
                    try:
                        if cli.ready():
                            ready = True
                            break
                    except Exception:  # noqa: BLE001 — still booting
                        pass
                    if not rep.alive():
                        break
                    time.sleep(0.05)
                t_ready = time.perf_counter() - t0
                if not ready:
                    raise RuntimeError(
                        f"{leg} replica never became ready in {timeout}s")
                eng = cli.stats().get("engine", {})
                legs[leg] = {
                    "start_to_ready_s": round(t_ready, 3),
                    "compiles": int(eng.get("compiles", 0)),
                    "cache_hits": int(eng.get("cache_hits", 0)),
                    "progcache": eng.get("progcache"),
                }
                # the cold leg always exits by SIGKILL (the chaos story:
                # no graceful cache flush); warm stops gracefully unless
                # something above raised — bench.py keeps running after a
                # raise, so the finally must never leak the child
                graceful = leg == "warm"
            finally:
                if cli is not None:
                    try:
                        cli.close()
                    except Exception:  # noqa: BLE001 — already torn down
                        pass
                if not graceful:
                    rep.kill()
                rep.stop()  # reap
        cold, warm = legs["cold"], legs["warm"]
        cold_fresh = cold["compiles"] - cold["cache_hits"]
        warm_fresh = warm["compiles"] - warm["cache_hits"]
        ok = (warm_fresh == 0 and cold_fresh > 0
              and warm["cache_hits"] == warm["compiles"] > 0
              and warm["compiles"] <= cold["compiles"])
        return {
            "model": model,
            "max_batch_size": max_batch_size,
            "cold_start_to_ready_s": warm["start_to_ready_s"],
            "cold_s": cold["start_to_ready_s"],
            "warm_s": warm["start_to_ready_s"],
            "speedup": round(cold["start_to_ready_s"]
                             / max(warm["start_to_ready_s"], 1e-9), 3),
            "warm_wall_win": warm["start_to_ready_s"]
            < cold["start_to_ready_s"],
            "compiles_cold": cold["compiles"],
            "compiles_warm": warm["compiles"],
            "fresh_compiles_cold": cold_fresh,
            "fresh_compiles_warm": warm_fresh,
            "cache_hits_warm": warm["cache_hits"],
            "host_cores": os.cpu_count(),
            "note": "start-to-ready includes interpreter+jax import; the "
                    "wall win scales with model compile cost, the compile "
                    "counts are the deterministic gate",
            "ok": ok,
        }
    finally:
        if created:
            shutil.rmtree(tmp, ignore_errors=True)


def run_decode_bench(duration=4.0, clients=6, slots=4, page_size=8,
                     num_pages=64, max_new_tokens=24, churn=True):
    """Autoregressive decode bench (docs/SERVING.md "Autoregressive
    decode"): a tiny transformer LM behind the paged-KV two-program
    engine and the streaming wire, driven by ``clients`` concurrent
    streams with mid-run churn (periodic early hang-ups and one hopeless
    deadline lane) so join/leave and page reclaim are part of the
    measured path, not a separate test.

    Headline numbers: ``decode_tokens_per_s`` (fleet token throughput)
    and ``decode_p99_per_token_ms`` (client-observed inter-token gap —
    the streaming UX tail, excluding the first token which carries queue
    wait + prefill and is reported separately as ``ttft_ms_p50``). The
    compiled-program bound and the zero-residual-pages check ride along
    as canaries: a retrace or a page leak fails the run, it doesn't just
    skew it."""
    from mxnet_tpu import nd, serve
    from mxnet_tpu.models.transformer import transformer_lm
    from mxnet_tpu.serve.decode import DecodeEngine, DecodeScheduler

    lm = transformer_lm(vocab_size=257, units=64, hidden_size=128,
                        num_layers=2, num_heads=4, max_length=128,
                        dropout=0.0)
    lm.initialize()
    lm(nd.zeros((1, 8)))
    eng = DecodeEngine(lm, slots=slots, page_size=page_size,
                       num_pages=num_pages)
    eng.warmup()  # compiles never pollute token-gap numbers
    sched = DecodeScheduler(eng, max_new_tokens=max_new_tokens)
    srv = serve.ServeServer(engine=None, decode=sched, port=0)
    srv.start()

    lock = threading.Lock()
    gaps: list = []          # inter-token gaps, first token excluded
    ttfts: list = []         # submit -> first token
    tokens = [0]
    completed = [0]
    cancelled = [0]
    shed = [0]
    errors = [0]
    stop_at = time.perf_counter() + duration

    def worker(wid):
        rng = np.random.RandomState(100 + wid)
        cli = serve.ServeClient("127.0.0.1", srv.port)
        my_gaps, my_ttfts = [], []
        rounds = 0
        try:
            while time.perf_counter() < stop_at:
                rounds += 1
                n = int(rng.randint(3, 33))
                prompt = rng.randint(1, 250, size=n).astype(np.int32)
                mode = "normal"
                if churn and wid == 0 and rounds % 3 == 2:
                    mode = "cancel"
                elif churn and wid == 1 and rounds % 5 == 3:
                    mode = "deadline"
                try:
                    if mode == "cancel":
                        gen = cli.generate(prompt,
                                           max_new_tokens=max_new_tokens)
                        next(gen)
                        next(gen)
                        gen.close()  # hang-up: server reclaims the pages
                        with lock:
                            cancelled[0] += 1
                            tokens[0] += 2
                        continue
                    dl = 1.0 if mode == "deadline" else None
                    t_sent = time.perf_counter()
                    t_prev = t_sent
                    got = 0
                    for _tok in cli.generate(prompt,
                                             max_new_tokens=max_new_tokens,
                                             deadline_ms=dl):
                        now = time.perf_counter()
                        if got == 0:
                            my_ttfts.append(now - t_sent)
                        else:
                            my_gaps.append(now - t_prev)
                        t_prev = now
                        got += 1
                    with lock:
                        completed[0] += 1
                        tokens[0] += got
                except (serve.DeadlineExceeded, serve.RequestRejected,
                        serve.Draining):
                    with lock:
                        shed[0] += 1
                except serve.ServeError:
                    with lock:
                        errors[0] += 1
        finally:
            cli.close()
            with lock:
                gaps.extend(my_gaps)
                ttfts.extend(my_ttfts)

    t_start = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=duration + 60)
    wall = time.perf_counter() - t_start
    st = sched.stats()
    srv.stop()
    sigs = {repr(e["sig"]) for e in eng.compile_log}
    gaps.sort()
    ttfts.sort()
    return {
        "duration_s": round(wall, 2), "clients": clients, "slots": slots,
        "page_size": page_size, "num_pages": num_pages,
        "max_new_tokens": max_new_tokens,
        "streams_completed": completed[0],
        "streams_cancelled": cancelled[0],
        "shed": shed[0], "errors": errors[0],
        "tokens_out": tokens[0],
        "decode_tokens_per_s": round(tokens[0] / wall, 2),
        "ttft_ms_p50": (round(_percentile(ttfts, 0.50) * 1e3, 3)
                        if ttfts else None),
        "decode_p50_per_token_ms": (round(_percentile(gaps, 0.50) * 1e3, 3)
                                    if gaps else None),
        "decode_p99_per_token_ms": (round(_percentile(gaps, 0.99) * 1e3, 3)
                                    if gaps else None),
        "occupancy": round(st["occupancy"], 3),
        "scheduler_steps": st["steps"],
        "compiled_programs": len(eng.compile_log),
        "buckets": list(eng.buckets),
        "program_bound_ok": len(sigs) == len(eng.buckets) + 1,
        "pages_leaked": eng.pool.used(),
    }


def _serve_rules(model):
    """Tensor-parallel sharding specs for the bench models: the mlp gets
    the classic Megatron split (fc1 row-parallel, fc2 column-parallel —
    one all-reduce at the output); zoo models serve replicated-params
    (still mesh-placed, still correct — TP specs are a model property)."""
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu.parallel.sharding import ShardingRules

    if model == "mlp":
        return ShardingRules([("fc1_weight|fc1_bias", P("tp")),
                              ("fc2_weight", P(None, "tp"))])
    return ShardingRules()


def _sharded_fleet(model, mesh, *, start=None, max_batch_size=8,
                   max_linger_ms=1.0, probe_interval=0.15):
    """(pool, router, front, feat): data-parallel replica groups on the
    mesh's dp slices, each serving a tensor-parallel engine, behind one
    FleetServer front."""
    from mxnet_tpu import serve
    from mxnet_tpu.serve.fleet import FleetServer, ReplicaPool, Router

    net, arg, aux, feat = _build_model(model)
    rules = _serve_rules(model)

    def make_server(submesh):
        engine = serve.InferenceEngine(net, arg, aux,
                                       max_batch_size=max_batch_size,
                                       lint="off", mesh=submesh, rules=rules)
        engine.warmup(feat)
        srv = serve.ServeServer(engine, port=0,
                                max_linger_ms=max_linger_ms)
        srv.start()
        return srv

    pool = ReplicaPool.sharded(make_server, mesh=mesh, start=start,
                               probe_interval=probe_interval,
                               backoff_base=0.1, backoff_cap=1.0)
    pool.start()
    router = Router(pool)
    front = FleetServer(router, port=0)
    front.start()
    return pool, router, front, feat


def _closed_drive(addr, payload, clients, duration, deadline_ms=None):
    """Closed-loop drive against an already-running endpoint; returns
    (sorted latencies, shed, errors, wall_seconds)."""
    from mxnet_tpu import serve

    lock = threading.Lock()
    lats: list = []
    shed = [0]
    errors = [0]
    t_start = time.perf_counter()
    stop_at = t_start + duration

    def worker():
        cli = serve.ServeClient(*addr)
        while time.perf_counter() < stop_at:
            t0 = time.perf_counter()
            try:
                cli.infer(payload, deadline_ms=deadline_ms)
            except (serve.RequestRejected, serve.DeadlineExceeded):
                with lock:
                    shed[0] += 1
                continue
            except serve.ServeError:
                with lock:
                    errors[0] += 1
                continue
            with lock:
                lats.append(time.perf_counter() - t0)
        cli.close()

    threads = [threading.Thread(target=worker) for _ in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sorted(lats), shed[0], errors[0], time.perf_counter() - t_start


def run_scale_bench(model="mlp", groups_list=(1, 2, 4), tp=2, duration=4.0,
                    clients=16, max_batch_size=8, request_rows=4,
                    max_linger_ms=1.0):
    """serve_qps vs data-parallel replica-group count on the local device
    mesh — the ROADMAP item 1 headline: serve throughput must scale with
    the mesh, not with hand-tuning. For each ``groups`` a ``dp×tp`` mesh
    is sliced into tensor-parallel replica groups (one engine per slice,
    params shard-resident), closed-loop load runs through one FleetServer
    front, and the report carries qps per group count plus the
    ``scaling_dp4`` ratio (dp4 qps over single-group qps)."""
    import jax

    from mxnet_tpu import parallel as par

    rng = np.random.RandomState(1)
    results = {}
    feat = None
    ndev = par.local_device_count()
    for groups in groups_list:
        need = int(groups) * int(tp)
        if need > ndev:
            results[str(groups)] = {"skipped": f"needs {need} devices, "
                                               f"have {ndev}"}
            continue
        mesh = par.make_mesh({"dp": int(groups), "tp": int(tp)},
                             devices=jax.devices()[:need])
        pool, router, front, feat = _sharded_fleet(
            model, mesh, max_batch_size=max_batch_size,
            max_linger_ms=max_linger_ms)
        try:
            payload = rng.rand(request_rows, *feat).astype(np.float32)
            lat, shed, errors, wall = _closed_drive(
                ("127.0.0.1", front.port), payload, clients, duration)
            results[str(groups)] = {
                "qps": round(len(lat) * request_rows / wall, 2),
                "p50_ms": round(_percentile(lat, 0.50) * 1e3, 3)
                if lat else None,
                "p99_ms": round(_percentile(lat, 0.99) * 1e3, 3)
                if lat else None,
                "completed": len(lat), "shed": shed, "errors": errors,
                "ready_replicas": len(pool.ready_members()),
            }
        finally:
            front.stop()
            pool.stop()
    host_cores = len(os.sched_getaffinity(0)) \
        if hasattr(os, "sched_getaffinity") else (os.cpu_count() or 1)
    out = {"mode": "scale", "model": model, "tp": tp, "clients": clients,
           "duration_s": duration, "request_rows": request_rows,
           "max_batch_size": max_batch_size, "host_cores": host_cores,
           "groups": results}
    base = results.get(str(groups_list[0]), {}).get("qps")
    for g in groups_list[1:]:
        q = results.get(str(g), {}).get("qps")
        if base and q:
            out[f"scaling_dp{g}"] = round(q / base, 2)
    max_g = max(int(g) for g in groups_list)
    if host_cores < max_g:
        # virtual CPU devices SHARE the host's cores: a single replica's
        # XLA matmuls already use them all, so compute-bound scaling is
        # capped at host_cores× regardless of replica groups — the
        # near-linear check needs >= groups physical cores (or real chips)
        out["note"] = (f"host has {host_cores} cores for {max_g} replica "
                       f"groups; compute-bound scaling caps at "
                       f"~{host_cores}x — run on >= {max_g} cores or real "
                       "devices for the near-linear check")
    return out


def run_ramp_bench(model="mlp", duration=14.0, qps_lo=30.0, qps_hi=450.0,
                   groups=4, tp=2, start_replicas=1, max_batch_size=8,
                   max_linger_ms=4.0, deadline_ms=2000.0, interval=0.4,
                   request_rows=4):
    """Open-loop load RAMP against an autoscaled sharded fleet: offered
    qps climbs linearly lo→hi over the run while the Autoscaler watches
    windowed burn + queue depth + occupancy and grows the pool from
    ``start_replicas`` toward ``groups``. The report is the measured
    proof the ISSUE asks for: scale-out events (with timestamps and
    reasons), shed/error counts, and per-third latency windows —
    autoscaling under a ramp must shed nothing."""
    import jax

    from mxnet_tpu import parallel as par, serve
    from mxnet_tpu.serve.autoscale import Autoscaler, AutoscalePolicy

    need = int(groups) * int(tp)
    mesh = par.make_mesh({"dp": int(groups), "tp": int(tp)},
                         devices=jax.devices()[:need])
    pool, router, front, feat = _sharded_fleet(
        model, mesh, start=start_replicas, max_batch_size=max_batch_size,
        max_linger_ms=max_linger_ms)
    policy = AutoscalePolicy(min_replicas=start_replicas,
                             max_replicas=groups,
                             queue_out=max(2.0, max_batch_size / 2),
                             occupancy_out=0.85, burn_out=1.0,
                             hysteresis=4, cooldown_s=2.0,
                             scale_in_cooldown_s=10.0)
    scaler = Autoscaler(pool, router, policy=policy,
                        interval=interval).start()

    rng = np.random.RandomState(1)
    payload = rng.rand(request_rows, *feat).astype(np.float32)
    addr = ("127.0.0.1", front.port)
    lock = threading.Lock()
    records: list = []  # (t_sent, outcome, latency)
    pool_clients = [serve.ServeClient(*addr) for _ in range(8)]
    free = list(range(len(pool_clients)))

    def fire(idx, t_sent):
        t0 = time.perf_counter()
        try:
            pool_clients[idx].infer(payload, deadline_ms=deadline_ms)
            outcome = "ok"
        except (serve.RequestRejected, serve.Draining):
            outcome = "shed"
        except serve.DeadlineExceeded:
            outcome = "deadline"
        except serve.ServeError:
            outcome = "error"
        with lock:
            records.append((t_sent, outcome, time.perf_counter() - t0))
            free.append(idx)

    t_mono0 = time.monotonic()  # scaler events are monotonic-stamped
    t_start = time.perf_counter()
    inflight = []
    ready_timeline = [(0.0, len(pool.ready_members()))]
    while time.perf_counter() < t_start + duration:
        t = time.perf_counter() - t_start
        qps = qps_lo + (qps_hi - qps_lo) * min(t / duration, 1.0)
        time.sleep(rng.exponential(1.0 / qps))
        r = len(pool.ready_members())
        if r != ready_timeline[-1][1]:
            ready_timeline.append((round(t, 2), r))
        with lock:
            if free:
                idx = free.pop()
            else:
                pool_clients.append(serve.ServeClient(*addr))
                idx = len(pool_clients) - 1
        th = threading.Thread(target=fire,
                              args=(idx, time.perf_counter() - t_start))
        th.start()
        inflight.append(th)
    for th in inflight:
        th.join(timeout=30)
    scaler.stop()
    events = [{"t_s": round(e["t"] - t_mono0, 2), "action": e["action"],
               "reason": e["reason"], "ready": e["ready"]}
              for e in scaler.events]
    fleet_stats = router.stats()
    front.stop()
    pool.stop()
    for cli in pool_clients:
        cli.close()

    def window(name, lo, hi):
        rows = [r for r in records if lo <= r[0] < hi]
        lat = sorted(r[2] for r in rows if r[1] == "ok")
        return {"window": name, "sent": len(rows), "ok": len(lat),
                "shed": sum(1 for r in rows if r[1] in ("shed", "deadline")),
                "errors": sum(1 for r in rows if r[1] == "error"),
                "p50_ms": round(_percentile(lat, 0.5) * 1e3, 2)
                if lat else None,
                "p99_ms": round(_percentile(lat, 0.99) * 1e3, 2)
                if lat else None}

    third = duration / 3.0
    shed_total = sum(1 for r in records if r[1] in ("shed", "deadline"))
    return {
        "mode": "ramp", "model": model, "tp": tp, "groups": groups,
        "start_replicas": start_replicas, "duration_s": duration,
        "qps_lo": qps_lo, "qps_hi": qps_hi, "deadline_ms": deadline_ms,
        "sent": len(records),
        "ok": sum(1 for r in records if r[1] == "ok"),
        "shed": shed_total,
        "errors": sum(1 for r in records if r[1] == "error"),
        "scale_out_events": sum(1 for e in events
                                if e["action"] == "scale_out"),
        "scale_in_events": sum(1 for e in events
                               if e["action"] == "scale_in"),
        "events": events,
        "ready_timeline": ready_timeline,
        "final_generation": pool.generation,
        "failovers": fleet_stats["failovers"],
        "windows": [window("ramp_lo", 0.0, third),
                    window("ramp_mid", third, 2 * third),
                    window("ramp_hi", 2 * third, duration + 1e9)],
    }


def run_obs_overhead(model="mlp", duration=4.0, sample=0.1, clients=4,
                     max_batch_size=8, request_rows=1, threshold_pct=5.0):
    """Measure what tracing COSTS, instead of assuming it's free: the same
    closed-loop bench twice through the full engine→batcher→socket stack —
    telemetry off, then on with head-based sampling at ``sample`` — and
    report the qps delta as ``obs_overhead_pct``. This is the number that
    justifies leaving tracing on under load (docs/OBSERVABILITY.md), and
    ``bench.py`` records + gates it (< ``threshold_pct`` at sample 0.1 on
    the resnet18 serve path)."""
    from mxnet_tpu import obs

    # the caller may be mid-run with live telemetry (bench.py streaming
    # JSONL): snapshot flag/rate/stream, and only wipe what THIS harness
    # recorded when telemetry was off to begin with
    was_on = obs.enabled()
    prev_rate = obs.context.sample_rate()
    prev_stream = obs.trace.tracer.stream_path
    obs.disable()
    try:
        off = run_bench(model=model, mode="closed", duration=duration,
                        clients=clients, max_batch_size=max_batch_size,
                        request_rows=request_rows)
        obs.context.set_sample_rate(sample)
        obs.enable()
        on = run_bench(model=model, mode="closed", duration=duration,
                       clients=clients, max_batch_size=max_batch_size,
                       request_rows=request_rows)
    finally:
        obs.disable()
        obs.context.set_sample_rate(prev_rate)
        if was_on:
            obs.enable(jsonl=prev_stream)  # resume the caller's stream
        else:
            obs.reset()  # telemetry was off: leave no residue
    qps_off, qps_on = off["qps"], on["qps"]
    pct = 100.0 * (qps_off - qps_on) / qps_off if qps_off else 0.0
    return {"model": model, "sample_rate": sample,
            "duration_s": duration, "clients": clients,
            "qps_off": qps_off, "qps_on": qps_on,
            "p99_ms_off": off["p99_ms"], "p99_ms_on": on["p99_ms"],
            "obs_overhead_pct": round(pct, 2),
            "threshold_pct": threshold_pct,
            "ok": bool(pct < threshold_pct)}


def run_wire_hop(model="mlp", duration=4.0, clients=4, max_batch_size=8,
                 request_rows=1):
    """The measured wire-hop baseline for the zero-copy rewrite
    (docs/ANALYSIS.md "Data-plane lint", ROADMAP item 4): a closed-loop
    serve run with the MXNET_COPYTRACK twin counting at the wire/batcher/
    device choke points. Reports the p50 client latency with the mean
    per-request execute time subtracted (``hop_ms_p50`` — queueing +
    framing + copies + syncs, the part a zero-copy rewrite can attack)
    plus bytes-copied / serialize-calls / host-syncs per request. This is
    the committed denominator a later rewrite must beat by >=2x."""
    from mxnet_tpu import copytrack, obs

    # same snapshot/restore discipline as run_obs_overhead: telemetry is
    # needed for serve.execute_seconds, but the caller's stream survives
    was_on = obs.enabled()
    prev_rate = obs.context.sample_rate()
    prev_stream = obs.trace.tracer.stream_path
    track_was_on = copytrack.enabled()
    obs.disable()
    try:
        obs.context.set_sample_rate(0.0)  # spans off; metrics are enough
        obs.enable()
        copytrack.enable()
        copytrack.reset()
        before = obs.metrics.snapshot()["histograms"].get(
            "serve.execute_seconds", {})
        res = run_bench(model=model, mode="closed", duration=duration,
                        clients=clients, max_batch_size=max_batch_size,
                        request_rows=request_rows)
        after = obs.metrics.snapshot()["histograms"].get(
            "serve.execute_seconds", {})
        track = copytrack.snapshot()
    finally:
        if not track_was_on:
            copytrack.disable()
        obs.disable()
        obs.context.set_sample_rate(prev_rate)
        if was_on:
            obs.enable(jsonl=prev_stream)
        else:
            obs.reset()
    n = max(res["completed"], 1)
    exec_s = after.get("sum", 0.0) - before.get("sum", 0.0)
    exec_ms_per_req = 1e3 * exec_s / n
    p50 = res["p50_ms"] or 0.0
    sync_sites = track.get("hotpath.sync_sites", {})
    return {
        "model": model, "duration_s": duration, "clients": clients,
        "request_rows": request_rows, "completed": res["completed"],
        "qps": res["qps"], "p50_ms": p50, "p99_ms": res["p99_ms"],
        "execute_ms_per_request": round(exec_ms_per_req, 3),
        "hop_ms_p50": round(max(p50 - exec_ms_per_req, 0.0), 3),
        "bytes_copied_per_request":
            round(track.get("wire.bytes_copied", 0) / n, 1),
        "serialize_calls_per_request":
            round(track.get("wire.serialize_calls", 0) / n, 3),
        "host_syncs_per_request":
            round(track.get("hotpath.host_syncs", 0) / n, 3),
        "sync_sites": dict(sorted(sync_sites.items(),
                                  key=lambda kv: -kv[1])[:8]),
        "bytes_copied_total": track.get("wire.bytes_copied", 0),
    }


def run_prof_overhead(model="mlp", duration=4.0, hz=None, clients=4,
                      max_batch_size=8, request_rows=1, threshold_pct=5.0,
                      segments=5):
    """What the BLACK-BOX plane costs, measured (docs/OBSERVABILITY.md
    "Tail sampling"/"Continuous profiling"): closed-loop qps through the
    full engine→batcher→socket stack in THREE interleaved configurations
    against one endpoint —

    - ``off``: no telemetry at all;
    - ``plain``: the PR-7 span/metrics plane recording every request
      durably (sample rate 1.0) — what "observe everything" already cost
      before this plane existed;
    - ``on``: telemetry + tail-mode buffering (every request's spans
      into the pending buffer, retention verdict at root close) + the
      continuous profiler at ``hz`` (``MXNET_OBS_PROF_HZ``, default 67).

    ``prof_overhead_pct`` — the gated number — is the plain→on delta:
    what tail buffering + 67 Hz profiling ADD on top of recording
    telemetry, mirroring how the PR 7/9 overhead legs each gate their
    own plane's increment (the off→plain recording cost is PR 7's,
    gated by ``--obs-overhead`` at its deployed sample rate; it is
    reported here as ``record_overhead_pct`` for reference).
    ``bench.py`` records + gates it under ``threshold_pct``: "record
    everything, keep the interesting" only earns its place if the
    keep-or-drop machinery is near-free on top of the recording.

    Each configuration's ``segments`` segments interleave round-robin
    and the best of each side is compared — the elastic_bench
    methodology: host-load drift over a multi-second run otherwise lands
    on whichever side happened to run last and swamps a small delta."""
    from mxnet_tpu import obs, serve

    net, arg, aux, feat = _build_model(model)
    engine = serve.InferenceEngine(net, arg, aux,
                                   max_batch_size=max_batch_size,
                                   lint="off")
    engine.warmup(feat)
    srv = serve.ServeServer(engine, port=0, max_linger_ms=2.0)
    srv.start()
    addr = ("127.0.0.1", srv.port)
    rng = np.random.RandomState(1)
    payload = rng.rand(request_rows, *feat).astype(np.float32)

    def segment(seg_s: float) -> float:
        """Drive `clients` closed-loop threads for seg_s; return qps."""
        done = [0] * clients
        stop_at = time.perf_counter() + seg_s

        def worker(i):
            cli = serve.ServeClient(*addr)
            n = 0
            while time.perf_counter() < stop_at:
                cli.infer(payload)
                n += 1
            done[i] = n
            cli.close()

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return sum(done) / (time.perf_counter() - t0)

    was_on = obs.enabled()
    prev_rate = obs.context.sample_rate()
    prev_stream = obs.trace.tracer.stream_path
    tail_was_on = obs.tail.enabled()
    prev_tail_buf = obs.tail.buffer()  # the CALLER's buffer + policy
    prof_was_on = obs.profile.enabled()
    prev_prof_hz = obs.profile.profiler.hz if prof_was_on else None
    if prof_was_on:
        # a caller-owned profiler sampling through the off/plain
        # segments would charge its cost to the wrong side
        obs.profile.stop()
    seg_s = duration / max(segments, 1)
    qps_off: list = []
    qps_plain: list = []
    qps_on: list = []
    prof_samples = 0
    prof_stacks = 0
    prof_hz = float(hz) if hz else None

    def cfg_off():
        obs.tail.disable() if obs.tail.enabled() else None
        obs.disable()

    def cfg_plain():
        if obs.tail.enabled():
            obs.tail.disable()
        obs.context.set_sample_rate(1.0)
        obs.enable()

    tail_buf = None

    def cfg_on():
        nonlocal tail_buf
        obs.enable()
        # re-attach the SAME buffer across segments so retain/drop
        # counters accumulate (enable() would mint a fresh one)
        if tail_buf is None:
            tail_buf = obs.tail.enable()
        else:
            obs.tail.set_buffer(tail_buf)
        return obs.profile.start(hz=hz)

    try:
        # warm all three paths once (connections, code paths, allocator)
        cfg_off()
        segment(min(seg_s, 1.0))
        cfg_plain()
        segment(min(seg_s, 1.0))
        p = cfg_on()
        segment(min(seg_s, 1.0))
        obs.profile.stop()
        for _ in range(max(segments, 1)):
            cfg_off()
            qps_off.append(segment(seg_s))
            cfg_plain()
            qps_plain.append(segment(seg_s))
            prof = cfg_on()
            qps_on.append(segment(seg_s))
            st = prof.stats()
            obs.profile.stop()
            prof_samples += st["samples"]
            prof_stacks = max(prof_stacks, st["distinct_stacks"])
            prof_hz = st["hz"]
        tail_stats = (tail_buf.stats() if tail_buf is not None else {})
    finally:
        obs.profile.stop()
        if prof_was_on:
            # the caller ran a continuous profiler before the bench (e.g.
            # MXNET_OBS_PROF=1): restart one at their rate so post-bench
            # flight-recorder bundles keep their profiler slice
            obs.profile.start(hz=prev_prof_hz)
        if tail_was_on:
            # the bench swapped its own buffer in (cfg_on) — hand the
            # caller's original back, retained log and policy intact
            obs.tail.set_buffer(prev_tail_buf)
        elif obs.tail.enabled():
            obs.tail.disable()
        obs.disable()
        obs.context.set_sample_rate(prev_rate)
        if was_on:
            obs.enable(jsonl=prev_stream)  # resume the caller's stream
        else:
            obs.reset()  # telemetry was off: leave no residue
        srv.stop()
    best_off, best_plain, best_on = max(qps_off), max(qps_plain), max(qps_on)
    pct = 100.0 * (best_plain - best_on) / best_plain if best_plain else 0.0
    rec_pct = 100.0 * (best_off - best_plain) / best_off if best_off else 0.0
    return {"model": model, "profiler_hz": prof_hz,
            "duration_s": duration, "clients": clients,
            "segments": len(qps_off),
            "qps_off": round(best_off, 2),
            "qps_plain": round(best_plain, 2),
            "qps_on": round(best_on, 2),
            "qps_off_segments": [round(q, 1) for q in qps_off],
            "qps_plain_segments": [round(q, 1) for q in qps_plain],
            "qps_on_segments": [round(q, 1) for q in qps_on],
            "prof_samples": prof_samples,
            "prof_distinct_stacks": prof_stacks,
            "tail_retained": tail_stats.get("retained", 0),
            "tail_dropped": tail_stats.get("dropped", 0),
            "record_overhead_pct": round(rec_pct, 2),
            "prof_overhead_pct": round(pct, 2),
            "threshold_pct": threshold_pct,
            "ok": bool(pct < threshold_pct)}


def run_chaos_bench(model="mlp", duration=12.0, qps=120.0, replicas=3,
                    max_batch_size=8, max_linger_ms=2.0, deadline_ms=500.0,
                    request_rows=1, hedge_ms=None, kill_replica=0):
    """Availability under replica death, measured: open-loop Poisson load
    through a FleetServer front over ``replicas`` supervised in-process
    replicas; at duration/3 one replica is hard-killed (crash-equivalent:
    its sockets sever mid-work); the pool restarts it with backoff. Every
    request is timestamped and bucketed into before / during (kill →
    readiness recovered) / after windows. Returns the result dict."""
    from mxnet_tpu import serve
    from mxnet_tpu.serve.fleet import FleetServer, ReplicaPool, Router

    net, arg, aux, feat = _build_model(model)

    def factory():
        engine = serve.InferenceEngine(net, arg, aux,
                                       max_batch_size=max_batch_size,
                                       lint="off")
        engine.warmup(feat)
        srv = serve.ServeServer(engine, port=0,
                                max_linger_ms=max_linger_ms)
        srv.start()
        return srv

    pool = ReplicaPool.local(factory, replicas, probe_interval=0.15,
                             backoff_base=0.1, backoff_cap=1.0)
    pool.start()
    router = Router(pool, hedge_ms=hedge_ms, breaker_cooldown=0.3)
    front = FleetServer(router, port=0)
    front.start()
    addr = ("127.0.0.1", front.port)

    rng = np.random.RandomState(1)
    payload = rng.rand(request_rows, *feat).astype(np.float32)
    lock = threading.Lock()
    records = []  # (t_sent, outcome, latency)
    pool_clients = [serve.ServeClient(*addr) for _ in range(8)]
    free = list(range(len(pool_clients)))

    def fire(idx, t_sent):
        t0 = time.perf_counter()
        try:
            pool_clients[idx].infer(payload, deadline_ms=deadline_ms)
            outcome = "ok"
        except (serve.RequestRejected, serve.Draining):
            outcome = "shed"
        except serve.DeadlineExceeded:
            outcome = "deadline"
        except serve.ServeError:
            outcome = "error"
        with lock:
            records.append((t_sent, outcome, time.perf_counter() - t0))
            free.append(idx)

    t_start = time.perf_counter()
    kill_at = t_start + duration / 3.0
    t_kill = [None]
    t_recovered = [None]
    killed = [False]
    dipped = [False]  # readiness must visibly drop before "recovered"
    inflight = []
    while time.perf_counter() < t_start + duration:
        now = time.perf_counter()
        if not killed[0] and now >= kill_at:
            pool.kill(kill_replica)
            t_kill[0] = now
            killed[0] = True
        if killed[0] and t_recovered[0] is None:
            ready = len(pool.ready_members())
            if ready < replicas:
                dipped[0] = True
            elif dipped[0]:
                t_recovered[0] = now
        time.sleep(rng.exponential(1.0 / qps))
        with lock:
            if free:
                idx = free.pop()
            else:
                pool_clients.append(serve.ServeClient(*addr))
                free_idx = len(pool_clients) - 1
                idx = free_idx
        th = threading.Thread(target=fire,
                              args=(idx, time.perf_counter() - t_start))
        th.start()
        inflight.append(th)
    for th in inflight:
        th.join(timeout=30)
    if killed[0] and t_recovered[0] is None and dipped[0] \
            and len(pool.ready_members()) >= replicas:
        t_recovered[0] = time.perf_counter()
    fleet_stats = router.stats()
    front.stop()
    pool.stop()
    for cli in pool_clients:
        cli.close()

    kill_off = (t_kill[0] - t_start) if t_kill[0] else None
    rec_off = (t_recovered[0] - t_start) if t_recovered[0] else None

    def window(name, lo, hi):
        rows = [r for r in records if lo <= r[0] < hi]
        lat = sorted(r[2] for r in rows if r[1] == "ok")
        n = len(rows)
        bad = sum(1 for r in rows if r[1] == "error")
        shed = sum(1 for r in rows if r[1] in ("shed", "deadline"))
        return {"window": name, "sent": n, "ok": len(lat), "shed": shed,
                "errors": bad,
                "error_rate": round(bad / n, 4) if n else None,
                "p50_ms": round(_percentile(lat, 0.5) * 1e3, 2) if lat
                else None,
                "p99_ms": round(_percentile(lat, 0.99) * 1e3, 2) if lat
                else None}

    end = duration + 1e9
    out = {
        "mode": "chaos", "model": model, "replicas": replicas,
        "offered_qps": qps, "duration_s": duration,
        "deadline_ms": deadline_ms, "hedge_ms": hedge_ms,
        "kill_at_s": round(kill_off, 2) if kill_off else None,
        "recovered_at_s": round(rec_off, 2) if rec_off else None,
        "recovery_s": round(rec_off - kill_off, 2)
        if (kill_off and rec_off) else None,
        "windows": [window("before", 0.0, kill_off or end),
                    window("during", kill_off or end, rec_off or end),
                    window("after", rec_off or end, end)],
        "failovers": fleet_stats["failovers"],
        "breaker_trips": fleet_stats["breaker_trips"],
        "hedges": fleet_stats["hedges"],
        "restarts": sum(r["restarts"]
                        for r in fleet_stats["replicas"].values()),
        "lost": sum(1 for r in records if r[1] == "error"),
    }
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="closed/open-loop load generator for mxnet_tpu.serve")
    ap.add_argument("--model", default="mlp",
                    help="mlp or a model-zoo name (e.g. resnet18_v1)")
    ap.add_argument("--mode", default="both",
                    choices=("closed", "open", "both"))
    ap.add_argument("--duration", type=float, default=5.0)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--qps", type=float, default=200.0,
                    help="offered load for open-loop mode")
    ap.add_argument("--deadline-ms", type=float, default=None)
    ap.add_argument("--request-rows", type=int, default=1)
    ap.add_argument("--max-batch-size", type=int, default=8)
    ap.add_argument("--max-linger-ms", type=float, default=2.0)
    ap.add_argument("--connect", default=None,
                    help="host:port of an external endpoint (skips the "
                         "in-process server)")
    ap.add_argument("--json", action="store_true",
                    help="one JSON line per mode instead of the table")
    ap.add_argument("--chaos", action="store_true",
                    help="fleet availability bench: open-loop load over a "
                         "supervised replica fleet, hard-kill one replica "
                         "mid-run, report error rate + p99 before/during/"
                         "after (always prints JSON)")
    ap.add_argument("--replicas", type=int, default=3,
                    help="fleet size for --chaos")
    ap.add_argument("--hedge-ms", type=float, default=None,
                    help="fleet tail-latency hedge threshold for --chaos")
    ap.add_argument("--obs-overhead", action="store_true",
                    help="measure tracing overhead: closed-loop qps with "
                         "telemetry off vs on at --sample (always prints "
                         "JSON; warns when over the 5%% budget)")
    ap.add_argument("--sample", type=float, default=0.1,
                    help="head-sampling rate for --obs-overhead")
    ap.add_argument("--wire-hop", action="store_true",
                    help="closed-loop serve run with the MXNET_COPYTRACK "
                         "twin on: p50 hop cost (execute subtracted) + "
                         "bytes-copied/serialize-calls/host-syncs per "
                         "request — the zero-copy rewrite's baseline")
    ap.add_argument("--prof-overhead", action="store_true",
                    help="measure the black-box plane's overhead: "
                         "closed-loop qps with everything off vs tail-mode "
                         "buffering + the continuous profiler at --hz "
                         "(always prints JSON; warns over the 5%% budget)")
    ap.add_argument("--hz", type=float, default=None,
                    help="profiler sampling rate for --prof-overhead "
                         "(default MXNET_OBS_PROF_HZ or 67)")
    ap.add_argument("--cold", action="store_true",
                    help="cold-start A/B: spawn a ProcReplica with an "
                         "empty vs warmed persistent program cache and "
                         "report cold_start_to_ready_s both ways (always "
                         "prints JSON; exits 1 when the warm leg performed "
                         "any fresh XLA compile — the key-stability gate)")
    ap.add_argument("--decode", action="store_true",
                    help="autoregressive decode bench: concurrent token "
                         "streams with churn through the paged-KV engine "
                         "and the streaming wire; reports tokens/s + "
                         "per-token p99 (always prints JSON; exits 1 on "
                         "a program-bound break or a page leak)")
    ap.add_argument("--scale", action="store_true",
                    help="mesh-scaling bench: closed-loop qps through "
                         "tensor-parallel replica groups on dp 1/2/4 mesh "
                         "slices (always prints JSON)")
    ap.add_argument("--ramp", action="store_true",
                    help="open-loop load ramp against an SLO-autoscaled "
                         "sharded fleet: offered qps climbs --qps-lo → "
                         "--qps-hi over --duration; reports scale-out "
                         "events + shed count (always prints JSON)")
    ap.add_argument("--tp", type=int, default=2,
                    help="devices per tensor-parallel replica group for "
                         "--scale/--ramp")
    ap.add_argument("--groups", type=int, default=4,
                    help="max data-parallel replica groups for --ramp")
    ap.add_argument("--qps-lo", type=float, default=30.0)
    ap.add_argument("--qps-hi", type=float, default=450.0)
    args = ap.parse_args(argv)

    if not args.connect:
        # building an in-process engine touches the device; a backend that
        # hangs must cost one watchdog budget + one parseable artifact
        from mxnet_tpu import platform as mxplatform

        mxplatform.devices_or_exit(what="tools/serve_bench.py")

    if args.obs_overhead:
        if args.connect:
            # the overhead harness toggles THIS process's telemetry around
            # an in-process stack; it cannot flip a remote endpoint's —
            # a localhost number labeled as the remote's would be a lie
            ap.error("--obs-overhead measures an in-process stack and "
                     "cannot target --connect")
        res = run_obs_overhead(model=args.model, duration=args.duration,
                               sample=args.sample, clients=args.clients,
                               max_batch_size=args.max_batch_size,
                               request_rows=args.request_rows)
        print(json.dumps(res, indent=1))
        if not res["ok"]:
            print(f"WARNING: obs_overhead_pct={res['obs_overhead_pct']} "
                  f"exceeds the {res['threshold_pct']}% budget at "
                  f"sample={args.sample}", file=sys.stderr)
        return 0

    if args.wire_hop:
        if args.connect:
            ap.error("--wire-hop instruments an in-process stack and "
                     "cannot target --connect")
        res = run_wire_hop(model=args.model, duration=args.duration,
                           clients=args.clients,
                           max_batch_size=args.max_batch_size,
                           request_rows=args.request_rows)
        print(json.dumps(res, indent=1))
        print(f"wire hop: p50 {res['hop_ms_p50']} ms "
              f"(client p50 {res['p50_ms']} ms - execute "
              f"{res['execute_ms_per_request']} ms), "
              f"{res['bytes_copied_per_request']} B copied, "
              f"{res['serialize_calls_per_request']} serialize calls, "
              f"{res['host_syncs_per_request']} host syncs per request",
              file=sys.stderr)
        return 0

    if args.prof_overhead:
        if args.connect:
            ap.error("--prof-overhead measures an in-process stack and "
                     "cannot target --connect")
        res = run_prof_overhead(model=args.model, duration=args.duration,
                                hz=args.hz, clients=args.clients,
                                max_batch_size=args.max_batch_size,
                                request_rows=args.request_rows)
        print(json.dumps(res, indent=1))
        if not res["ok"]:
            print(f"WARNING: prof_overhead_pct={res['prof_overhead_pct']} "
                  f"exceeds the {res['threshold_pct']}% budget at "
                  f"{res['profiler_hz']} Hz", file=sys.stderr)
        return 0

    if args.cold:
        res = run_cold_bench(model=args.model,
                             max_batch_size=args.max_batch_size)
        print(json.dumps(res, indent=1))
        if not res["ok"]:
            print("WARNING: warm start performed "
                  f"{res['fresh_compiles_warm']} fresh XLA compile(s) "
                  f"(cold: {res['fresh_compiles_cold']}) — program-cache "
                  "keys are unstable across processes", file=sys.stderr)
            return 1
        return 0

    if args.decode:
        if args.connect:
            ap.error("--decode builds an in-process decode stack and "
                     "cannot target --connect")
        res = run_decode_bench(duration=args.duration,
                               clients=args.clients)
        print(json.dumps(res, indent=1))
        print(f"decode: {res['decode_tokens_per_s']} tok/s, "
              f"per-token p50 {res['decode_p50_per_token_ms']} ms / "
              f"p99 {res['decode_p99_per_token_ms']} ms, ttft p50 "
              f"{res['ttft_ms_p50']} ms, occupancy {res['occupancy']}, "
              f"{res['compiled_programs']} programs for "
              f"{len(res['buckets'])} buckets", file=sys.stderr)
        if not res["program_bound_ok"] or res["pages_leaked"]:
            print("WARNING: decode invariant broke — "
                  f"program_bound_ok={res['program_bound_ok']} "
                  f"pages_leaked={res['pages_leaked']}", file=sys.stderr)
            return 1
        return 0

    if args.scale:
        res = run_scale_bench(model=args.model, tp=args.tp,
                              duration=args.duration,
                              clients=max(args.clients, 16),
                              max_batch_size=args.max_batch_size)
        print(json.dumps(res, indent=1))
        return 0

    if args.ramp:
        res = run_ramp_bench(model=args.model,
                             duration=max(args.duration, 10.0),
                             qps_lo=args.qps_lo, qps_hi=args.qps_hi,
                             groups=args.groups, tp=args.tp,
                             max_batch_size=args.max_batch_size,
                             deadline_ms=args.deadline_ms or 2000.0)
        print(json.dumps(res, indent=1))
        return 0

    if args.chaos:
        res = run_chaos_bench(model=args.model, duration=args.duration,
                              qps=args.qps, replicas=args.replicas,
                              max_batch_size=args.max_batch_size,
                              max_linger_ms=args.max_linger_ms,
                              deadline_ms=args.deadline_ms or 500.0,
                              request_rows=args.request_rows,
                              hedge_ms=args.hedge_ms)
        print(json.dumps(res, indent=1))
        return 0

    modes = ("closed", "open") if args.mode == "both" else (args.mode,)
    results = []
    for mode in modes:
        res = run_bench(model=args.model, mode=mode, duration=args.duration,
                        clients=args.clients, qps=args.qps,
                        max_batch_size=args.max_batch_size,
                        max_linger_ms=args.max_linger_ms,
                        deadline_ms=args.deadline_ms,
                        request_rows=args.request_rows,
                        connect=args.connect)
        results.append(res)
        if args.json:
            print(json.dumps(res))
    if not args.json:
        cols = ("qps", "offered_qps", "p50_ms", "p95_ms", "p99_ms",
                "max_ms", "completed", "shed", "errors",
                "compiled_programs")
        print(f"{'metric':<18}" + "".join(f"{m:>14}" for m in modes))
        for c in cols:
            vals = [r.get(c, "-") for r in results]
            if all(v in ("-", None) for v in vals):
                continue
            print(f"{c:<18}" + "".join(
                f"{('-' if v is None else v):>14}" for v in vals))
    return 0


if __name__ == "__main__":
    rc = main()
    # skip interpreter teardown: after 2+ in-process engine/server builds
    # the PJRT CPU client's worker threads can std::terminate the exit
    # (pre-existing, timing-dependent; everything is printed and flushed
    # by now) — a measurement CLI must not turn a clean run into rc=134
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc or 0)

"""Headline benchmarks on one chip. Prints exactly ONE JSON line.

Primary metric (stable across rounds): ResNet-50 v1 fp32 train throughput vs
the recalled reference V100 number (BASELINE.md — LOW CONFIDENCE/TBV, mount
still empty round 2). The ``extra`` object carries the rest of the matrix:

- ``resnet50_bf16_ips``      — same step with bf16 compute (AMP policy)
- ``resnet50_piped_ips``     — fp32 step fed by the REAL input pipeline
                               (JPEG RecordIO → native C++ decoder → device)
- ``bert_base_*``            — BERT-base bf16 train step: seq/sec, model
                               TFLOP/s, and MFU against (a) the sustained
                               matmul peak *measured on this chip* by a
                               256-deep chained-matmul jit (one sync, so
                               dispatch latency amortizes out) and (b)
                               nominal v5e bf16 peak (197 TFLOPS).
                               BASELINE.json's second target (≥40% MFU)
                               reads (a); both are reported and must not
                               contradict ``model_tflops``.

Every step runs as ONE donated XLA program via parallel.ShardedTrainer on a
1-device mesh — the same code path that scales to dp×tp×sp meshes.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from mxnet_tpu.obs.device import DEVICE_PEAKS

BASELINE_IMG_PER_SEC_PER_GPU = 385.0
NOMINAL_V5E_BF16_TFLOPS, NOMINAL_V5E_HBM_GBPS = DEVICE_PEAKS["TPU v5 lite"]


class _SkipLeg(Exception):
    """Raised inside a leg's try block when --extras deselects it."""


class _device_cost_capture:
    """Force obs.device program-cost capture (MXNET_DEVICE_COST=1) for a
    leg without enabling span telemetry — the XLA cost analysis rides the
    one step compile, zero per-step overhead. Restores the prior setting."""

    def __enter__(self):
        self._prev = os.environ.get("MXNET_DEVICE_COST")
        os.environ["MXNET_DEVICE_COST"] = "1"

    def __exit__(self, *exc):
        if self._prev is None:
            os.environ.pop("MXNET_DEVICE_COST", None)
        else:
            os.environ["MXNET_DEVICE_COST"] = self._prev


def _attach_step_cost(leg: dict, trainer, sec: float) -> None:
    """Fold the trainer's captured step-program cost record into a bench
    leg: the XLA-counted FLOP rate ("analytic") beside the hand-model
    rate, plus the raw cost fields the dossier/report can audit."""
    cost = getattr(trainer, "step_cost", None)
    if not cost or not cost.get("flops"):
        return
    leg["device_cost"] = {k: cost.get(k, 0) for k in
                          ("flops", "bytes_accessed", "peak_hbm_bytes")}
    # 4 significant digits, not fixed decimals — a CPU smoke run's
    # micro-TFLOP rate must not round to a falsy 0.0
    leg["analytic_tflops"] = float(f"{cost['flops'] / sec / 1e12:.4g}")


def _annotate_analytic(leg: dict, peak_tflops: float) -> None:
    """extra.*_analytic_mfu / extra.*_roofline: analytic MFU against the
    same measured-peak denominator as the measured MFU it sits next to,
    and the roofline class (compute- vs bandwidth-bound) of the step
    program — the attribution ROADMAP item 3's open MFU questions need."""
    from mxnet_tpu.obs import device as obs_device

    cost = leg.get("device_cost")
    at = leg.get("analytic_tflops")
    if not cost or not at or not peak_tflops:
        return
    leg["analytic_mfu"] = float(f"{at / peak_tflops:.4g}")
    rl = obs_device.roofline_class(cost, peak_tflops=peak_tflops,
                                   peak_gbps=NOMINAL_V5E_HBM_GBPS)
    if rl:
        leg["roofline"] = rl["bound"]
        leg["intensity_flop_per_byte"] = rl["intensity_flop_per_byte"]

# Round-2's 802 img/s fp32 was measured on a silently-wrong program: a
# deferred-shape capture bug froze every BatchNorm gamma/beta/stat as an XLA
# constant (fixed in commit 3b0fc89), letting the compiler fold BN into the
# convs. With BN actually training the step costs more; what it costs on
# the current code and chip is not measured (PERF.md).


def _steps_cfg(platform):
    batch = int(os.environ.get("BENCH_BATCH", 64 if platform == "tpu" else 8))
    size = int(os.environ.get("BENCH_IMAGE_SIZE",
                              224 if platform == "tpu" else 64))
    # 30 steps per sync amortize the fixed cost of the sync itself
    steps = int(os.environ.get("BENCH_STEPS", 30 if platform == "tpu" else 2))
    warmup = int(os.environ.get("BENCH_WARMUP", 5 if platform == "tpu" else 1))
    return batch, size, steps, warmup


def _n_runs(platform):
    return int(os.environ.get("BENCH_RUNS", 3 if platform == "tpu" else 1))


def _loadavg():
    try:
        with open("/proc/loadavg") as f:
            return float(f.read().split()[0])
    except (OSError, ValueError, IndexError):
        return -1.0


def _resnet_trainer(mesh, compute_dtype=None, preprocess=None):
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon.model_zoo import get_model

    mx.random.seed(0)
    net = get_model("resnet50_v1", classes=1000)
    net.initialize()
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    return net, loss_fn, par.ShardedTrainer(
        net, loss_fn, mesh, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4},
        compute_dtype=compute_dtype, preprocess=preprocess)


def _time_steps(trainer, batches, steps, warmup, n_runs=1):
    """batches: callable i -> (x, y). Returns (best secs/step, spread).

    Each run dispatches `steps` steps and host-syncs once. n_runs repeats
    defend the number against host contention: the BEST run is the
    least-contended one, and spread = (worst-best)/best is reported so a
    reader can see how noisy the host was.
    """
    last = None
    for i in range(warmup):
        last = trainer.step(*batches(i))
    float(last.asnumpy())  # host fetch of the loss: waits for the step
    times = []
    for _ in range(max(n_runs, 1)):
        t0 = time.perf_counter()
        for i in range(steps):
            last = trainer.step(*batches(i))
        final = float(last.asnumpy())
        times.append((time.perf_counter() - t0) / steps)
    assert np.isfinite(final), f"non-finite loss {final}"
    best = min(times)
    spread = (max(times) - best) / best
    return best, spread


def bench_resnet(platform, compute_dtype=None):
    import jax

    from mxnet_tpu import nd
    from mxnet_tpu import parallel as par

    batch, size, steps, warmup = _steps_cfg(platform)
    mesh = par.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    net, loss_fn, trainer = _resnet_trainer(mesh, compute_dtype)
    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(batch, 3, size, size).astype(np.float32))
    y = nd.array(rng.randint(0, 1000, batch).astype(np.int32))
    net(x)  # resolve deferred shapes
    sec, spread = _time_steps(trainer, lambda i: (x, y), steps, warmup,
                              n_runs=_n_runs(platform))
    return batch / sec, spread


def _make_rec_dataset(path, n=256, size=256):
    """Synthetic JPEG RecordIO set (tools/im2rec.py wire format)."""
    from mxnet_tpu.io.recordio import MXIndexedRecordIO, pack_img, IRHeader

    rec = MXIndexedRecordIO(path + ".idx", path + ".rec", "w")
    rng = np.random.RandomState(0)
    for i in range(n):
        img = rng.randint(0, 255, (size, size, 3), np.uint8)
        s = pack_img(IRHeader(0, float(i % 1000), i, 0), img, quality=80,
                     img_fmt=".jpg")
        rec.write_idx(i, s)
    rec.close()


def bench_resnet_piped(platform, compute_dtype=None):
    """ResNet step fed by the real pipeline, assembled the TPU-first way:
    native JPEG decode → raw uint8 over the host→device link (4x smaller) →
    normalize fused into the jitted step → PrefetchingIter overlaps the whole
    host side with device compute. Returns ips + a time breakdown."""
    import tempfile

    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu import parallel as par

    batch, size, steps, warmup = _steps_cfg(platform)
    n_img = max(batch * (steps + warmup + 2), 128)
    tmp = tempfile.mkdtemp(prefix="mxtpu_bench_")
    path = os.path.join(tmp, "synth")
    _make_rec_dataset(path, n=n_img, size=max(size, 128))

    mesh = par.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    raw = mx.io.ImageRecordIter(
        path_imgrec=path + ".rec", data_shape=(3, size, size),
        batch_size=batch, shuffle=False, rand_crop=True, rand_mirror=True,
        resize=max(size, 128), preprocess_threads=2, dtype="uint8",
        mean_r=123.68, mean_g=116.78, mean_b=103.94,
        std_r=58.4, std_g=57.12, std_b=57.38)
    mean = jnp.asarray(raw.mean)
    std = jnp.asarray(raw.std)

    def preprocess(x):
        if x.dtype == jnp.uint8:  # labels pass through untouched
            return (x.astype(jnp.float32) - mean) / std
        return x

    net, loss_fn, trainer = _resnet_trainer(mesh, compute_dtype=compute_dtype,
                                            preprocess=preprocess)
    native = raw._native is not None

    # --- host-floor probe: what can this host even deliver? ---
    # (a) serial rate of the iterator alone (decode+augment+upload — the
    #     upload is inseparable without bypassing the iterator), (b)
    #     host→device bandwidth for distinct uint8 batches. These probes
    #     record the conditions the piped number was taken under, so the
    #     piped number is falsifiable.
    t0 = time.perf_counter()
    probe_batches = 0
    for bb in raw:
        probe_batches += 1
        if probe_batches >= 5:
            break
    host_ms = (time.perf_counter() - t0) / max(probe_batches, 1) * 1000
    raw.reset()
    # upload bandwidth via SLOPE (k=2 vs k=6 uploads, one tiny fetch each):
    # the fixed dispatch+sync cost cancels in the difference. DISTINCT
    # random batches, so nothing on the way can dedupe or compress them
    rng_w = np.random.RandomState(1)
    wires = [rng_w.randint(0, 255, (batch, 3, size, size), np.uint8)
             for _ in range(6)]
    dev = jax.devices()[0]

    def put_k(k):
        t0 = time.perf_counter()
        bufs = [jax.device_put(wires[i], dev) for i in range(k)]
        np.asarray(jax.device_get(bufs[-1].ravel()[:1]))
        return time.perf_counter() - t0

    put_k(2)  # warm
    wire_ms = max(put_k(6) - put_k(2), 1e-4) / 4 * 1000

    it = mx.io.PrefetchingIter(raw, prefetch=3)

    def next_batch():
        nonlocal it
        try:
            bb = next(it)
        except StopIteration:
            it.reset()
            bb = next(it)
        # f32 labels go straight in: pick() casts in-jit; an eager astype
        # here would cost a full dispatch round-trip per batch
        return bb.data[0], bb.label[0]

    last = None
    try:
        for _ in range(warmup):
            last = trainer.step(*next_batch())
        float(last.asnumpy())
        runs = []
        for _ in range(max(_n_runs(platform), 1)):
            t_data = t_disp = 0.0
            t0_all = time.perf_counter()
            for _ in range(steps):
                t0 = time.perf_counter()
                x, y = next_batch()
                t_data += time.perf_counter() - t0
                t0 = time.perf_counter()
                last = trainer.step(x, y)
                t_disp += time.perf_counter() - t0
            final = float(last.asnumpy())
            runs.append(((time.perf_counter() - t0_all) / steps,
                         t_data / steps, t_disp / steps))
    finally:
        # leftover prefetch workers would keep decoding and contend with
        # the next bench section (they skewed round-4's first capture)
        it.close()
    assert np.isfinite(final), f"non-finite piped loss {final}"
    dt, t_data, t_disp = min(runs)
    spread = (max(r[0] for r in runs) - dt) / dt
    # optimistic ceiling: the 2-worker prefetcher can at best halve the
    # serial iterator time (decode+upload overlapped pairwise); measured
    # ips should sit at or below this
    host_floor_ips = batch / (max(host_ms / 2, wire_ms / 2) / 1000)
    out = {
        "ips": round(batch / dt, 2),
        "ms_per_batch": round(dt * 1000, 1),
        "data_wait_ms": round(t_data * 1000, 1),
        "step_dispatch_ms": round(t_disp * 1000, 1),
        "n_runs": len(runs),
        "spread": round(spread, 3),
        "host_iter_serial_ms_per_batch": round(host_ms, 1),
        "wire_transfer_ms_per_batch": round(wire_ms, 1),
        "host_floor_ips": round(host_floor_ips, 1),
        "native_decode": native,
        "wire_dtype": "uint8",
    }
    return out


def _measure_matmul_peak(n1=64, n2=256):
    """Sustained bf16 matmul rate via SLOPE timing: two dependent-chain jits
    of depth n1/n2, one host-fetch sync each — the fixed dispatch+sync cost
    cancels in the difference, so the number is compute-bound."""
    import jax
    import jax.numpy as jnp

    m = 4096
    a = jax.random.normal(jax.random.PRNGKey(0), (m, m), jnp.bfloat16)

    def total(iters):
        @jax.jit
        def chain(x):
            def body(c, _):
                # explicit single-pass precision: the package global is
                # "highest", and the probe must measure the same MXU mode
                # the bf16 model path uses
                return jax.lax.dot(c, a,
                                   precision=jax.lax.Precision.DEFAULT), None
            y, _ = jax.lax.scan(body, x, None, length=iters)
            return y

        r = chain(a)
        float(np.asarray(jax.device_get(r[0, 0])))  # compile + warm + sync
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            r = chain(a)
            float(np.asarray(jax.device_get(r[0, 0])))
            best = min(best, time.perf_counter() - t0)
        return best

    for _ in range(3):
        dt = total(n2) - total(n1)
        if dt > 0:
            return 2 * m ** 3 * (n2 - n1) / dt / 1e12
    # contention spike made the slope non-positive three times — report
    # the probe as failed rather than an absurd number
    return float("nan")


def _bert_train_flops(n_layers, units, hidden, vocab, seq, batch):
    """Per-step training FLOPs (fwd 1× + bwd 2×) from the matmul inventory."""
    per_tok_layer = 2 * (4 * units * units + 2 * units * hidden)  # qkv+proj+ffn
    attn = 2 * 2 * seq * seq * units  # scores + weighted sum, per layer/batch
    fwd = (n_layers * (per_tok_layer * seq * batch + attn * batch)
           + 2 * 2 * seq * batch * units * vocab)  # mlm head + embed decode
    return 3 * fwd


def bench_bert(platform):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import bert_base, bert_sharding_rules

    seq = int(os.environ.get("BENCH_BERT_SEQ", 128))
    batch = int(os.environ.get("BENCH_BERT_BATCH",
                               64 if platform == "tpu" else 2))
    # 20+ steps per sync amortize the fixed cost of the sync itself
    steps = int(os.environ.get("BENCH_BERT_STEPS",
                               24 if platform == "tpu" else 2))
    warmup = 3 if platform == "tpu" else 1

    mx.random.seed(0)
    vocab = 30522
    net = bert_base(vocab_size=vocab, max_length=seq, dropout=0.0)
    net.initialize()
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = par.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = par.ShardedTrainer(net, loss_fn, mesh,
                                 rules=bert_sharding_rules(),
                                 optimizer="adam",
                                 optimizer_params={"learning_rate": 1e-4},
                                 compute_dtype="bfloat16")
    rng = np.random.RandomState(0)
    x = nd.array(rng.randint(0, vocab, (batch, seq)).astype(np.int32))
    net(x)
    with _device_cost_capture():
        sec, spread = _time_steps(trainer, lambda i: (x, x), steps, warmup,
                                  n_runs=_n_runs(platform))
    flops = _bert_train_flops(12, 768, 3072, vocab, seq, batch)
    out = {
        "seq_per_sec": round(batch / sec, 2),
        "tokens_per_sec": round(batch * seq / sec, 1),
        "model_tflops": round(flops / sec / 1e12, 3),
        "seq_len": seq,
        "batch": batch,
        "n_runs": _n_runs(platform),
        "spread": round(spread, 3),
    }
    _attach_step_cost(out, trainer, sec)
    return out


def _lm_train_flops(n_layers, units, hidden, vocab, seq, batch):
    """Causal-LM per-step training FLOPs: the attention term is halved vs
    bidirectional (the flash kernel skips fully-masked key blocks)."""
    per_tok_layer = 2 * (4 * units * units + 2 * units * hidden)
    attn = 2 * 2 * seq * seq * units // 2
    fwd = (n_layers * (per_tok_layer * seq * batch + attn * batch)
           + 2 * seq * batch * units * vocab)  # lm head
    return 3 * fwd


def bench_serve(platform):
    """Serving trajectory (docs/SERVING.md): closed-loop load through the
    full engine→batcher→socket stack on this chip. Headline gains:
    ``serve_qps`` (throughput ceiling) and ``serve_p99_ms`` (tail latency
    at that pressure), plus the compiled-program count as a regression
    canary on the bucketing bound."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import serve_bench

    model = os.environ.get("BENCH_SERVE_MODEL",
                           "resnet18_v1" if platform == "tpu" else "mlp")
    duration = float(os.environ.get("BENCH_SERVE_DURATION",
                                    8 if platform == "tpu" else 4))
    res = serve_bench.run_bench(
        model=model, mode="closed", duration=duration,
        clients=int(os.environ.get("BENCH_SERVE_CLIENTS", 4)),
        max_batch_size=int(os.environ.get("BENCH_SERVE_BATCH", 8)))
    return {"model": model,
            "serve_qps": res["qps"],
            "serve_p50_ms": res["p50_ms"],
            "serve_p99_ms": res["p99_ms"],
            "shed": res["shed"], "errors": res["errors"],
            "compiled_programs": res.get("compiled_programs"),
            "buckets": res.get("buckets")}


def bench_decode(platform):
    """Autoregressive decode trajectory (docs/SERVING.md "Autoregressive
    decode"): concurrent token streams with churn (early hang-ups, a
    hopeless-deadline lane) through the paged-KV two-program engine and
    the streaming wire. Headline gains: ``decode_tokens_per_s`` and
    ``decode_p99_per_token_ms`` (client-observed inter-token tail); the
    compiled-program bound and zero residual pages are asserted, so a
    retrace or page leak fails the leg instead of skewing it."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import serve_bench

    duration = float(os.environ.get("BENCH_DECODE_DURATION",
                                    8 if platform == "tpu" else 4))
    res = serve_bench.run_decode_bench(
        duration=duration,
        clients=int(os.environ.get("BENCH_DECODE_CLIENTS", 6)))
    assert res["program_bound_ok"], (
        f"{res['compiled_programs']} decode programs for "
        f"{len(res['buckets'])} buckets — the two-program bound broke")
    assert res["pages_leaked"] == 0, (
        f"{res['pages_leaked']} KV pages leaked after the drive")
    return res


def bench_cold_start(platform):
    """Replica cold start, cold vs warmed persistent program cache
    (docs/PERFORMANCE.md "Program cache and cold start"): two ProcReplica
    spawns against the same cache dir — the first compiles every bucket,
    the second deserializes them. ``cold_start_to_ready_s`` (the warm
    number) is the trajectory gain; the compile counts are the
    deterministic key-stability gate (`make coldstart` asserts them)."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import serve_bench

    model = os.environ.get("BENCH_COLD_MODEL",
                           "resnet18_v1" if platform == "tpu" else "mlp")
    res = serve_bench.run_cold_bench(
        model=model,
        max_batch_size=int(os.environ.get("BENCH_SERVE_BATCH", 8)))
    assert res["ok"], (
        f"warm start performed {res['fresh_compiles_warm']} fresh XLA "
        f"compile(s) (cold: {res['fresh_compiles_cold']}) — program-cache "
        "keys are unstable across processes")
    return res


def bench_serve_scale(platform):
    """Mesh-sharded serving scaling (docs/SERVING.md "Mesh-sharded serving
    and elastic autoscaling"): closed-loop serve_qps through dp∈{1,2,4}
    tensor-parallel replica groups on mesh slices behind one FleetServer
    front — the ROADMAP item 1 headline: serve throughput must scale with
    the mesh. On a CPU host the virtual devices share the physical cores,
    so the report carries ``host_cores`` + a note when the near-linear
    check cannot bind (compute caps at host_cores×)."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import serve_bench

    duration = float(os.environ.get("BENCH_SERVE_SCALE_DURATION",
                                    4 if platform == "tpu" else 3))
    res = serve_bench.run_scale_bench(
        model=os.environ.get("BENCH_SERVE_SCALE_MODEL", "mlp"),
        duration=duration,
        tp=int(os.environ.get("BENCH_SERVE_SCALE_TP", 2)))
    return res


def bench_serve_ramp(platform):
    """Autoscale under a load ramp (docs/SERVING.md): open-loop offered
    qps climbs while the SLO autoscaler grows the sharded fleet; the
    trajectory metric is scale_out_events with shed==0 — measured
    elasticity, the serving twin of extra.elastic_recovery_s."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import serve_bench

    duration = float(os.environ.get("BENCH_SERVE_RAMP_DURATION", 14))
    res = serve_bench.run_ramp_bench(
        model=os.environ.get("BENCH_SERVE_SCALE_MODEL", "mlp"),
        duration=duration)
    res.pop("ready_timeline", None)  # keep the artifact compact
    return res


def bench_obs_overhead(platform):
    """Tracing overhead on the serve path (docs/OBSERVABILITY.md): the
    serve bench twice — telemetry off vs on at head-sampling 0.1 — and the
    qps delta as ``obs_overhead_pct``, asserted under the 5% budget. The
    number that justifies leaving distributed tracing on in production."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import serve_bench

    model = os.environ.get("BENCH_SERVE_MODEL",
                           "resnet18_v1" if platform == "tpu" else "mlp")
    duration = float(os.environ.get("BENCH_OBS_DURATION",
                                    6 if platform == "tpu" else 3))
    sample = float(os.environ.get("BENCH_OBS_SAMPLE", 0.1))
    res = serve_bench.run_obs_overhead(model=model, duration=duration,
                                       sample=sample)
    assert res["ok"], (
        f"obs_overhead_pct={res['obs_overhead_pct']} >= "
        f"{res['threshold_pct']}% at sample={sample} — tracing is too "
        f"expensive to leave on (qps {res['qps_off']} -> {res['qps_on']})")
    return res


def bench_prof_overhead(platform):
    """Black-box-plane overhead (docs/OBSERVABILITY.md "Tail sampling" /
    "Continuous profiling"): interleaved off/on serve segments against
    one endpoint (best of each side, the elastic-bench methodology) —
    everything off vs tail-mode trace buffering (every request records
    pending, verdict at root close) + the 67 Hz continuous profiler —
    and the qps delta as ``prof_overhead_pct``, asserted under the 5%
    budget. The number that justifies recording EVERY request and
    keeping only the interesting."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import serve_bench

    model = os.environ.get("BENCH_SERVE_MODEL",
                           "resnet18_v1" if platform == "tpu" else "mlp")
    duration = float(os.environ.get("BENCH_PROF_DURATION",
                                    6 if platform == "tpu" else 5))
    res = serve_bench.run_prof_overhead(model=model, duration=duration)
    assert res["ok"], (
        f"prof_overhead_pct={res['prof_overhead_pct']} >= "
        f"{res['threshold_pct']}% at {res['profiler_hz']} Hz — the "
        f"black-box plane is too expensive to leave on "
        f"(qps {res['qps_plain']} -> {res['qps_on']})")
    return res


def bench_wire_hop(platform):
    """Per-request wire-hop cost on the serve path (docs/ANALYSIS.md
    "Data-plane lint"): a closed-loop serve run with the MXNET_COPYTRACK
    twin counting at the wire/batcher/device choke points — p50 client
    latency minus mean per-request execute time (``hop_ms_p50``), plus
    bytes-copied / serialize-calls / host-syncs per request. Records
    today's hop cost as the committed denominator ROADMAP item 4's
    zero-copy rewrite must beat by >=2x."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import serve_bench

    model = os.environ.get("BENCH_SERVE_MODEL",
                           "resnet18_v1" if platform == "tpu" else "mlp")
    duration = float(os.environ.get("BENCH_WIRE_HOP_DURATION",
                                    6 if platform == "tpu" else 3))
    return serve_bench.run_wire_hop(model=model, duration=duration)


def bench_health_overhead(platform):
    """Cost of the training-health plane (docs/OBSERVABILITY.md "Training
    health"): the same train-step loop with the divergence sentinel off vs
    attached at the default sampling period (stats variant only on sampled
    steps), asserted under the 5% budget — the number that justifies
    leaving the sentinel on for every production fit."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import health_bench

    steps = int(os.environ.get("BENCH_HEALTH_STEPS",
                               120 if platform == "tpu" else 60))
    res = health_bench.run_health_overhead(steps=steps)
    assert res["ok"], (
        f"health_overhead_pct={res['health_overhead_pct']} >= "
        f"{res['threshold_pct']}% at every={res['every']} — the sentinel "
        f"is too expensive to leave on (ips {res['ips_off']} -> "
        f"{res['ips_on']})")
    return res


def bench_elastic(platform):
    """Elastic-training plane (docs/ROBUSTNESS.md "Elastic training"):
    worker-death recovery time and rejoin-to-training latency, plus the
    membership plane's idle cost on PS RPC throughput (interleaved
    off-vs-on segments, best-of each side), gated under the same 5%
    budget as the obs/health overhead legs — heartbeats must cost nothing
    when nothing is failing."""
    del platform  # host-side plane: same measurement on any backend
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import elastic_bench

    res = elastic_bench.run_elastic_bench(
        workers=int(os.environ.get("BENCH_ELASTIC_WORKERS", 3)),
        ops=int(os.environ.get("BENCH_ELASTIC_OPS", 200)))
    assert res["ok"], (
        f"elastic plane out of budget: overhead "
        f"{res['elastic_overhead_pct']}% (gate {res['threshold_pct']}%), "
        f"recovery {res['elastic_recovery_s']}s, "
        f"rejoin {res['rejoin_to_training_s']}s")
    return res


def bench_train_obs(platform):
    """Training-fleet telemetry plane (docs/OBSERVABILITY.md
    "Training-fleet telemetry"): the fit-loop step accounting's marginal
    cost — span tracing on in BOTH configurations, fleet plane vetoed vs
    on, interleaved best-of (the PR-13 methodology) — gated under the
    same 5% budget as every other always-on plane; plus the straggler
    leg's measured detection latency (windows) and step-time skew with
    one slowed worker."""
    del platform  # host-side plane: same measurement on any backend
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import elastic_bench

    res = elastic_bench.run_train_obs_overhead(
        steps=int(os.environ.get("BENCH_TRAIN_OBS_STEPS", 250)))
    assert res["ok"], (
        f"train_obs_overhead_pct={res['train_obs_overhead_pct']} >= "
        f"{res['threshold_pct']}% — the fleet step accounting is too "
        f"expensive to leave on (ips {res['ips_off']} -> "
        f"{res['ips_on']})")
    res["straggler"] = elastic_bench.run_straggler_bench()
    return res


def bench_async(platform):
    """Bounded-staleness async plane (docs/ROBUSTNESS.md "Asynchronous
    training"): the same straggler-shaped fleet under lockstep allreduce
    vs the committed-clock gated-pull wire. The trajectory number is
    ``async_step_decoupling`` — the slowest rank's median step time over
    the fleet median — ~1.0 under sync (the straggler taxes every rank)
    and >=2x under async (only the straggler pays)."""
    del platform  # host-side plane: same measurement on any backend
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import elastic_bench

    res = elastic_bench.run_async_bench(
        workers=int(os.environ.get("BENCH_ASYNC_WORKERS", 3)))
    assert res["ok"], (
        f"async wire failed to decouple the fleet from its straggler: "
        f"async_step_decoupling={res['async_step_decoupling']} "
        f"(want >=2.0) vs sync {res['sync_step_decoupling']} (want ~1)")
    return res


def bench_update_engine_dispatches():
    """Compiled executions per optimizer step (tools/profile_step.py
    counters): the fused engine must stay at 1 program regardless of the
    parameter count; the eager column is the per-param dispatch cost it
    replaced."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import profile_step

    res = profile_step.profile_model("resnet18_v1", batch_size=1,
                                     image_size=32, optimizer="sgd",
                                     eager=True, warmup=2)
    return {"n_params": res["n_params"],
            "fused": res["update"]["total_compiled"],
            "eager": res["update_eager"]["total_compiled"]}


def bench_lm_long(platform):
    """TransformerLM at seq 2048 bf16 — the config where the Pallas flash
    kernel is the difference between fitting the S×S scores in HBM or not.
    Runs the same step with impl=flash and impl=plain to justify the
    _FLASH_MIN_SEQ dispatch policy empirically."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import bert_sharding_rules, transformer_lm

    seq = int(os.environ.get("BENCH_LM_SEQ", 2048))
    batch = int(os.environ.get("BENCH_LM_BATCH", 4 if platform == "tpu" else 1))
    steps = int(os.environ.get("BENCH_LM_STEPS", 16 if platform == "tpu" else 2))
    warmup = 3 if platform == "tpu" else 1
    vocab = 32000
    layers, units, hidden = (12, 768, 3072) if platform == "tpu" else (2, 64, 128)

    out = {"seq_len": seq, "batch": batch}
    rng = np.random.RandomState(0)
    x = rng.randint(0, vocab, (batch, seq)).astype(np.int32)
    flops = _lm_train_flops(layers, units, hidden, vocab, seq, batch)
    impls = tuple(os.environ.get("BENCH_LM_IMPLS", "flash,plain").split(","))
    for impl in impls:
        os.environ["MXNET_ATTENTION_IMPL"] = impl
        try:
            mx.random.seed(0)
            net = transformer_lm(vocab_size=vocab, max_length=seq,
                                 num_layers=layers, units=units,
                                 hidden_size=hidden, dropout=0.0)
            net.initialize()
            loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
            mesh = par.make_mesh({"dp": 1}, devices=jax.devices()[:1])
            trainer = par.ShardedTrainer(
                net, loss_fn, mesh, rules=bert_sharding_rules(),
                optimizer="adam",
                optimizer_params={"learning_rate": 1e-4},
                compute_dtype="bfloat16",
                remat=os.environ.get("BENCH_LM_REMAT") == "1",
                grad_accum=int(os.environ.get("BENCH_LM_ACCUM", 1)))
            xd = nd.array(x)
            net(xd)
            with _device_cost_capture():
                sec, spread = _time_steps(trainer, lambda i: (xd, xd), steps,
                                          warmup, n_runs=_n_runs(platform))
            out[impl] = {"tokens_per_sec": round(batch * seq / sec, 1),
                         "model_tflops": round(flops / sec / 1e12, 3),
                         "spread": round(spread, 3)}
            _attach_step_cost(out[impl], trainer, sec)
        except Exception as e:
            out[f"{impl}_error"] = f"{type(e).__name__}: {e}"[:200]
        finally:
            os.environ.pop("MXNET_ATTENTION_IMPL", None)
    if "flash" in out and "plain" in out:
        out["flash_speedup"] = round(out["flash"]["tokens_per_sec"]
                                     / out["plain"]["tokens_per_sec"], 3)
    return out


def main():
    from mxnet_tpu import platform as mxplatform

    # --extras LEG[,LEG...]: run only the named legs (e.g. `bench.py
    # --extras wire_hop` grabs a fresh hop-cost baseline without paying
    # for the full training trajectory). Everything else self-reports as
    # skipped so the one-line artifact keeps its shape.
    only = None
    argv = sys.argv[1:]
    if "--extras" in argv:
        i = argv.index("--extras")
        names = argv[i + 1] if i + 1 < len(argv) else ""
        only = {n.strip() for n in names.replace(",", " ").split()
                if n.strip()}

    # jax.devices() can block forever without raising (a chip held by
    # another process is one way). The platform watchdog
    # (mxnet_tpu/platform.py) turns that hang — or a real init raise,
    # reported distinctly so it is never triaged as a hang — into one
    # parseable JSON line instead of a capture timeout.
    # BENCH_DEVICE_TIMEOUT (legacy knob) wins when set; otherwise the
    # platform default applies — which honors MXNET_PLATFORM_TIMEOUT, so
    # the repo-wide bounded-exit contract isn't silently overridden here
    bench_to = os.environ.get("BENCH_DEVICE_TIMEOUT")
    try:
        devs = mxplatform.devices(
            timeout=float(bench_to) if bench_to else None)
    except mxplatform.PlatformUnavailable as e:
        print(json.dumps({
            "metric": "resnet50_v1 fp32 train throughput (batch=64, "
                      "224x224, 1 tpu chip)",
            "value": None,
            "unit": "images/sec",
            "vs_baseline": None,
            "error": f"device enumeration: {e.kind}: {e.detail}"[:300],
            "platform_error": e.artifact(driver="bench.py"),
        }))
        sys.exit(1)

    platform = devs[0].platform
    device_kind = devs[0].device_kind

    # Optional legs self-skip past this wall-clock budget so a cold compile
    # cache can never time the whole bench out of the driver's capture.
    t_start = time.perf_counter()
    budget_s = float(os.environ.get("BENCH_TIME_BUDGET", 2100))
    def over_budget(section):
        if time.perf_counter() - t_start > budget_s:
            extra[f"{section}_skipped"] = "time budget exceeded"
            return True
        return False

    def skip_leg(section):
        if only is not None and section not in only:
            extra[f"{section}_skipped"] = "not selected by --extras"
            return True
        return over_budget(section)

    load0 = _loadavg()
    extra = {"device_kind": device_kind,
             "n_runs": _n_runs(platform),
             "loadavg_start": load0}
    ips = None
    if not skip_leg("resnet50_fp32"):
        ips, fp32_spread = bench_resnet(platform)
        extra["fp32_spread"] = round(fp32_spread, 3)
    if not skip_leg("resnet50_bf16"):
        try:
            bf16_ips, bf16_spread = bench_resnet(platform,
                                                 compute_dtype="bfloat16")
            extra["resnet50_bf16_ips"] = round(bf16_ips, 2)
            extra["resnet50_bf16_spread"] = round(bf16_spread, 3)
        except Exception as e:  # never lose the primary metric
            extra["resnet50_bf16_error"] = f"{type(e).__name__}: {e}"[:200]
    if platform == "tpu" and os.environ.get("BENCH_FP32_HIGH", "1") != "0" \
            and not skip_leg("resnet50_fp32_high"):
        # fp32 storage with 3-pass bf16 matmul emulation (~1e-6 rel err) —
        # the TF32-class mode modern GPU "fp32" baselines actually run;
        # the primary metric above stays true-fp32 (HIGHEST, 6-pass)
        import jax as _j

        try:
            _j.config.update("jax_default_matmul_precision", "high")
            high_ips, high_spread = bench_resnet(platform)
            extra["resnet50_fp32_high_ips"] = round(high_ips, 2)
            extra["resnet50_fp32_high_spread"] = round(high_spread, 3)
        except Exception as e:
            extra["resnet50_fp32_high_error"] = f"{type(e).__name__}: {e}"[:200]
        finally:
            _j.config.update("jax_default_matmul_precision",
                             os.environ.get("MXNET_MATMUL_PRECISION",
                                            "highest"))
    if not skip_leg("resnet50_piped"):
        try:
            piped = bench_resnet_piped(platform)
            extra["resnet50_piped_ips"] = piped.pop("ips")
            extra["resnet50_piped_breakdown"] = piped
        except Exception as e:
            extra["resnet50_piped_error"] = f"{type(e).__name__}: {e}"[:200]
    if not skip_leg("resnet50_piped_bf16"):
        try:
            # full breakdown, not just the scalar (VERDICT r4 weak #1: the
            # r4 bf16 number was physically odd and shipped with no defense)
            piped_bf = bench_resnet_piped(platform, compute_dtype="bfloat16")
            extra["resnet50_piped_bf16_ips"] = piped_bf.pop("ips")
            extra["resnet50_piped_bf16_breakdown"] = piped_bf
        except Exception as e:
            extra["resnet50_piped_bf16_error"] = f"{type(e).__name__}: {e}"[:200]
    # the measured-peak denominator, shared with the lm legs — probed in
    # its own guard so a bert-leg failure can't strip the LM analytic-MFU
    # columns of a successfully measured peak
    peak_eff = None
    peak = float("nan")
    want_mfu = only is None or bool(
        {"bert_base_bf16", "lm_seq2048", "lm_seq4096"} & only)
    if want_mfu:
        try:
            peak = _measure_matmul_peak()
        except Exception as e:
            extra["matmul_probe_error"] = f"{type(e).__name__}: {e}"[:200]
    if np.isfinite(peak):
        peak_eff = min(peak, NOMINAL_V5E_BF16_TFLOPS)
    try:
        if skip_leg("bert_base_bf16"):
            raise _SkipLeg
        bert = bench_bert(platform)
        # chip throughput drifts run-to-run (~±20% observed); a sustained
        # model rate is itself a lower bound on peak, so the MFU denominator
        # is max(probe, model math) — the ratio can never self-contradict
        # (>1). The probe stays reported under its own (honest) name.
        if np.isfinite(peak):
            bert["matmul_probe_tflops"] = round(peak, 2)
        else:  # probe failed under contention — say so, don't fake a number
            bert["matmul_probe_tflops"] = None
            bert["matmul_probe_failed"] = True
            peak = bert["model_tflops"]
        # slope noise can read above physics (270 observed once vs the 197
        # nominal); a probe above nominal is noise, not a faster chip
        peak = min(peak, NOMINAL_V5E_BF16_TFLOPS)
        peak_eff = max(peak, bert["model_tflops"])
        bert["effective_peak_tflops"] = round(peak_eff, 2)
        bert["mfu_vs_measured_peak"] = round(
            bert["model_tflops"] / peak_eff, 4)
        bert["mfu_vs_nominal_v5e"] = round(
            bert["model_tflops"] / NOMINAL_V5E_BF16_TFLOPS, 4)
        # device-plane attribution (obs/device.py): the XLA-counted FLOP
        # rate as analytic_mfu + the step program's roofline class, same
        # measured-peak denominator as mfu_vs_measured_peak beside it
        from mxnet_tpu.obs import device as obs_device

        obs_device.set_peak(tflops=peak_eff, gbps=NOMINAL_V5E_HBM_GBPS)
        _annotate_analytic(bert, peak_eff)
        extra["bert_base_bf16"] = bert
    except _SkipLeg:
        pass
    except Exception as e:
        extra["bert_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        if skip_leg("lm_seq2048"):
            raise _SkipLeg
        lm = bench_lm_long(platform)
        for _impl in ("flash", "plain"):
            if isinstance(lm.get(_impl), dict) and peak_eff:
                _annotate_analytic(lm[_impl], peak_eff)
        extra["lm_seq2048_bf16"] = lm
    except _SkipLeg:
        pass
    except Exception as e:
        extra["lm_seq2048_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        if skip_leg("update_engine"):
            raise _SkipLeg
        # dispatch-overhead guarantee (docs/PERFORMANCE.md): compiled device
        # programs per Trainer.step update phase, fused engine vs eager loop
        extra["update_engine_dispatches_per_step"] = \
            bench_update_engine_dispatches()
    except _SkipLeg:
        pass
    except Exception as e:
        extra["update_engine_error"] = f"{type(e).__name__}: {e}"[:200]
    if not skip_leg("serve"):
        try:
            # the inference half (docs/SERVING.md): closed-loop qps + tail
            # latency through engine→batcher→socket, so BENCH_*.json
            # captures the serving trajectory alongside training
            extra["serve"] = bench_serve(platform)
        except Exception as e:
            extra["serve_error"] = f"{type(e).__name__}: {e}"[:200]
    if not skip_leg("decode"):
        try:
            # the autoregressive half of serving (docs/SERVING.md
            # "Autoregressive decode"): concurrent token streams with
            # churn through the paged-KV engine + streaming wire —
            # decode_tokens_per_s / decode_p99_per_token_ms are the
            # trajectory numbers next to serve_qps
            extra["decode"] = bench_decode(platform)
        except Exception as e:
            extra["decode_error"] = f"{type(e).__name__}: {e}"[:200]
    if not skip_leg("cold_start"):
        try:
            # persistent AOT program cache (docs/PERFORMANCE.md "Program
            # cache and cold start"): replica spawn-to-ready, cold vs
            # warmed cache — cold_start_to_ready_s is the first-class
            # trajectory metric next to serve_qps (a fleet autoscaler
            # waits on exactly this number)
            extra["cold_start"] = bench_cold_start(platform)
        except Exception as e:
            extra["cold_start_error"] = f"{type(e).__name__}: {e}"[:200]
    if not skip_leg("serve_scale"):
        try:
            # serve throughput vs data-parallel replica groups on mesh
            # slices + measured autoscale-out under a load ramp
            # (docs/SERVING.md "Mesh-sharded serving") — ROADMAP item 1's
            # two headline numbers: scaling_dp4 and scale_out_events@shed=0
            extra["serve_scale"] = bench_serve_scale(platform)
        except Exception as e:
            extra["serve_scale_error"] = f"{type(e).__name__}: {e}"[:200]
    if not skip_leg("serve_ramp"):
        try:
            extra["serve_ramp"] = bench_serve_ramp(platform)
        except Exception as e:
            extra["serve_ramp_error"] = f"{type(e).__name__}: {e}"[:200]
    if not skip_leg("obs_overhead"):
        try:
            # tracing must be cheap enough to stay ON under load — measure
            # it, don't assume it (docs/OBSERVABILITY.md): same serve path,
            # telemetry off vs on at head-sampling 0.1, <5% qps cost gated
            extra["obs_overhead"] = bench_obs_overhead(platform)
        except Exception as e:
            extra["obs_overhead_error"] = f"{type(e).__name__}: {e}"[:200]
    if not skip_leg("prof_overhead"):
        try:
            # the black-box plane (tail retention + continuous profiler)
            # must be cheap enough to stay always-on: same serve path,
            # everything off vs tail buffering + 67 Hz sampling, <5% gated
            extra["prof_overhead"] = bench_prof_overhead(platform)
        except Exception as e:
            extra["prof_overhead_error"] = f"{type(e).__name__}: {e}"[:200]
    if not skip_leg("health_overhead"):
        try:
            # the divergence sentinel must be cheap enough to leave ON for
            # every production fit (docs/OBSERVABILITY.md "Training
            # health"): off-vs-on train-step throughput at the default
            # sampling period, <5% gated
            extra["health_overhead"] = bench_health_overhead(platform)
        except Exception as e:
            extra["health_overhead_error"] = f"{type(e).__name__}: {e}"[:200]
    if not skip_leg("wire_hop"):
        try:
            # per-request wire-hop cost with the MXNET_COPYTRACK twin on
            # (docs/ANALYSIS.md "Data-plane lint"): p50 latency minus
            # execute + bytes-copied/serialize-calls/host-syncs per
            # request — the denominator the zero-copy rewrite (ROADMAP
            # item 4) must beat by >=2x
            extra["wire_hop"] = bench_wire_hop(platform)
        except Exception as e:
            extra["wire_hop_error"] = f"{type(e).__name__}: {e}"[:200]
    if not skip_leg("elastic"):
        try:
            # elastic training must be free when nothing fails: membership
            # overhead <5% gated, plus measured death-recovery and
            # rejoin-to-training times (docs/ROBUSTNESS.md "Elastic
            # training"); extra.elastic.elastic_recovery_s is the
            # trajectory number alongside serve's chaos metrics
            extra["elastic"] = bench_elastic(platform)
            extra["elastic_recovery_s"] = \
                extra["elastic"]["elastic_recovery_s"]
        except Exception as e:
            extra["elastic_error"] = f"{type(e).__name__}: {e}"[:200]
    if not skip_leg("async"):
        try:
            # bounded-staleness async training must actually decouple the
            # fleet from its slowest rank (docs/ROBUSTNESS.md
            # "Asynchronous training"): sync lockstep vs the gated-pull
            # wire under one slowed rank; extra.async_step_decoupling is
            # the trajectory number (>=2x gated in the leg itself)
            extra["async"] = bench_async(platform)
            extra["async_step_decoupling"] = \
                extra["async"]["async_step_decoupling"]
        except Exception as e:
            extra["async_error"] = f"{type(e).__name__}: {e}"[:200]
    if not skip_leg("train_obs"):
        try:
            # the training-fleet step accounting must be cheap enough to
            # leave on for every production fit: spans on both sides,
            # fleet plane off vs on, <5% gated; the straggler leg reports
            # detection latency in windows + step-time skew
            extra["train_obs"] = bench_train_obs(platform)
        except Exception as e:
            extra["train_obs_error"] = f"{type(e).__name__}: {e}"[:200]
    if platform == "tpu" and os.environ.get("BENCH_LM_LONG4K", "1") != "0" \
            and not skip_leg("lm_seq4096"):
        # the long-context scaling point: seq 4096, flash only (plain's
        # S×S scores are ~3.2 GB f32 — the config flash exists for).
        # Attempt batch 2 first, then batch 2 via grad_accum=2
        # (micro-batch-1 program, one update — same effective batch), then
        # plain batch 1; which of them the installed compiler takes is not
        # measured yet.
        try:
            os.environ["BENCH_LM_SEQ"] = "4096"
            os.environ["BENCH_LM_STEPS"] = "10"
            os.environ["BENCH_LM_IMPLS"] = "flash"
            for b_, acc_ in [("2", "1"), ("2", "2"), ("1", "1")]:
                os.environ["BENCH_LM_BATCH"] = b_
                os.environ["BENCH_LM_ACCUM"] = acc_
                res = bench_lm_long(platform)
                if "flash" in res:
                    res["grad_accum"] = int(acc_)
                    if peak_eff:
                        _annotate_analytic(res["flash"], peak_eff)
                    extra["lm_seq4096_bf16"] = res
                    break
                extra[f"lm_seq4096_attempt_b{b_}_acc{acc_}_error"] = \
                    res.get("flash_error", "unknown")[:160]
            else:
                extra["lm_seq4096_error"] = "all batch/accum attempts failed"
        except Exception as e:
            extra["lm_seq4096_error"] = f"{type(e).__name__}: {e}"[:200]
        finally:
            for k in ("BENCH_LM_SEQ", "BENCH_LM_BATCH", "BENCH_LM_STEPS",
                      "BENCH_LM_IMPLS", "BENCH_LM_ACCUM"):
                os.environ.pop(k, None)

    # Explicit per-leg outcome summary (VERDICT r4 weak #8: a silently
    # skipped leg must not read as a silently missing column). Derived from
    # the result keys each leg writes — one map, no per-site bookkeeping.
    leg_result_key = {
        "resnet50_fp32": "fp32_spread",
        "resnet50_bf16": "resnet50_bf16_ips",
        "resnet50_fp32_high": "resnet50_fp32_high_ips",
        "resnet50_piped": "resnet50_piped_ips",
        "resnet50_piped_bf16": "resnet50_piped_bf16_ips",
        "bert_base_bf16": "bert_base_bf16",
        "lm_seq2048": "lm_seq2048_bf16",
        "lm_seq4096": "lm_seq4096_bf16",
        "serve": "serve",
        "decode": "decode",
        "cold_start": "cold_start",
        "serve_scale": "serve_scale",
        "serve_ramp": "serve_ramp",
        "obs_overhead": "obs_overhead",
        "prof_overhead": "prof_overhead",
        "health_overhead": "health_overhead",
        "wire_hop": "wire_hop",
        "elastic": "elastic",
        "async": "async",
        "train_obs": "train_obs",
    }
    leg_error_key = {"bert_base_bf16": "bert_error"}  # irregular names
    extra["legs_run"] = [l for l, k in leg_result_key.items() if k in extra]
    extra["legs_skipped"] = [l for l, k in leg_result_key.items()
                             if k not in extra]
    for leg in extra["legs_skipped"]:  # gated-off legs get an explicit why
        has_reason = (f"{leg}_skipped" in extra or f"{leg}_error" in extra
                      or leg_error_key.get(leg, "") in extra)
        if not has_reason:
            extra[f"{leg}_skipped"] = "disabled (env/platform gate)"
    extra["loadavg_end"] = _loadavg()
    extra["bench_wall_s"] = round(time.perf_counter() - t_start, 1)
    # 1-core VM: loadavg much above 1 means something else was competing
    # with the bench dispatch thread — numbers are then lower bounds
    if max(load0, extra["loadavg_end"]) > 1.5:
        extra["host_contended"] = True

    print(json.dumps({
        "metric": f"resnet50_v1 fp32 train throughput (batch="
                  f"{_steps_cfg(platform)[0]}, "
                  f"{_steps_cfg(platform)[1]}x{_steps_cfg(platform)[1]}, "
                  f"1 {platform} chip)",
        "value": round(ips, 2) if ips is not None else None,
        "unit": "images/sec",
        "vs_baseline": (round(ips / BASELINE_IMG_PER_SEC_PER_GPU, 4)
                        if ips is not None else None),
        "extra": extra,
    }))


if __name__ == "__main__":
    main()

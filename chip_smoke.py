"""Does the system still start on the chip?  ``python chip_smoke.py``

One process drives the two hot paths through the entry points a user calls,
at the full width of ``models.transformer_lm()`` (vocab 32000, units 768,
hidden 3072, 12 layers, 12 heads), with random weights made from a seed:

- *device*    what jax sees, the versions, where the compile cache is;
- *kernels*   the Pallas kernels compiled by Mosaic (not interpreted) and
              compared on the chip with the repo's XLA references at
              production shapes;
- *train*     ``parallel.ShardedTrainer`` on a one-device mesh, 4 x 2048
              tokens, bf16 compute, default attention dispatch, 5 steps;
- *serve*     ``DecodeEngine`` -> ``DecodeScheduler`` -> ``ServeServer``,
              concurrent ``ServeClient.generate()`` streams checked token
              for token against the dense reference run on the chip;
- *latent*    a small latent-attention + routed-experts model
              (``models.mla_moe``, the cache row at its published 576
              bfloat16 values in 640 columns) through ``DecodeEngine``: the paged latent
              kernel against the XLA gather on the chip, the step program's
              temporaries under one layer's latents, nothing dropped;
- *state*     the gated-delta model (``models.gdn_moe``) through
              ``DecodeEngine``, state beside pages: a prompt in pieces against
              the prompt whole, and its kernels against XLA — ``gqa_decode``,
              the flash forward, ``gdn_decode``, and ``gdn_prefill`` at a
              2,048-position piece of 32 heads of 128 x 128 against the
              token-by-token recurrence and the XLA chunked form (error and
              time a layer of both printed);
- *ssm*       the Mamba-2 + relu^2-experts model (``models.ssm_moe``) at its
              published widths, four layers, 128 slots, through
              ``DecodeEngine``, in memory filled with NaN beforehand (the
              stored experts' zeros are zeros), with ONE slot live (the other
              127 on the kernel's scratch block): its state after the prefill,
              then after each step every slot's state and tail against the
              same step with the one-token update in XLA, then against 304
              tokens prefilled; ``ssm_decode`` against XLA on random states of
              64 x 64 x 128 float32; the step's temporaries under one layer's
              share of pool plus state;
- *scmoe*     the shortcut-connected double-layer latent model
              (``models.mla_scmoe``: head geometry as published, a query
              latent, identity experts behind the softmax router) at 128
              slots through ``DecodeEngine``, in memory filled with NaN
              beforehand: a 300-token prefill and four steps, the pool a layer
              a SUB-layer, ``moe.zero`` counted, nothing dropped, and every
              served token within 0.05 of the first choice of the plain
              reference's float32 full forward
              (``benchmark/reference_mla_scmoe.py``, run on the chip);
- *swa*       the window + global model (``models.swa_moe``: 64 heads of
              192 over 8 and 4 cached heads, values 128, a window of 128 as a
              ring in per-slot state beside a 2-layer pool) through
              ``DecodeEngine``, in memory filled with NaN beforehand: a prompt
              of 2,500 in pieces of 1,024 against ``prefill`` whole (pool rows,
              rings, first token), then 72 steps across a ring's wrap with
              every served token within 0.1 of the first choice of the plain
              reference's float32 full forward
              (``benchmark/reference_swa_moe.py``), and ``swa_decode`` and
              ``gqa_decode_dv`` against their XLA twins over what was written;
- *kda*       the delta rule with a decay per key channel (``ops/kda.py``) at
              the published 32 heads of 128 x 128: ``kda_decode`` through
              Mosaic and its XLA twin against the token-by-token recurrence
              at 2, 32 and 128 slots (dead slots and the other layer
              untouched, NaN-filled memory first), the kernel's time a layer
              at 128 slots, and 200 tokens through the chunked form with a
              whole chunk of log decays at the -5 bound;
- *experts*   ``ops.moe.held_experts`` at the three expert cells' prompt
              and decode-step geometries against a plain masked loop in
              bfloat16 on the chip: the error, nothing dropped, the row tile
              and the slot the rows were laid out in, the rows the grouped
              products were handed over the rows held (what the slots cost
              in padding) and the time of a call; and the same function on float32 operands at
              2, 4 and 32 slots (few rows a step: PERF.md section 7), its
              error printed and not held;
- *multichip* with >= 4 chips: the same trainer on dp2 x tp2, then on
              dp2 x sp2 at seq 4096 (ring attention, the Pallas kernel
              inside shard_map). Otherwise reported ``not_run``.

It takes no arguments and reads no variables of its own. It exits non-zero
unless jax's backend is ``tpu``; no phase's failure is caught, so the first
one ends the run with a traceback. A passing run ends with two lines:
``summary {...}`` (per-phase status and set-up seconds, the attention path
each phase ran, the compile cache, ``"claim": null``), then, last, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`` as
jax reports the device. It measures set-up time per phase and no rate (the
experts phase prints what a call took, for the reader): what the program
costs on the chip is the benchmark's to say.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import threading
import time

import numpy as np

VOCAB = 32000          # transformer_lm()'s default width; depth is its 12 too
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 2048, 5
SLOTS, PAGE = 8, 16
BUCKETS = [128, 512]
PROMPT_LENS = [5, 100, 200, 400]   # one bucket-128 pair, one bucket-512 pair
NEW_TOKENS = 32
_MOSAIC = "tpu_custom_call"        # what a compiled Pallas kernel lowers to

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = [0]   # programs built (compiled or read from the cache) so far


def _count_compile(event, _secs, **_kw):
    if event == _COMPILE_EVENT:
        _compiles[0] += 1


class _Phase:
    """Prints ``<name> PASS <seconds>s`` when its block ends without an
    exception, and records both for the summary. Never swallows one."""

    results: dict = {}

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.t0 = time.monotonic()
        print(f"== {self.name}", flush=True)
        return self

    def __exit__(self, exc_type, exc, tb):
        secs = round(time.monotonic() - self.t0, 1)
        if exc_type is None:
            _Phase.results[self.name] = {"status": "PASS", "seconds": secs}
            print(f"== {self.name} PASS {secs}s", flush=True)
        else:
            print(f"== {self.name} FAIL {secs}s", flush=True)
        return False


def _require(ok, message):
    """A check that holds under ``python -O`` too, unlike ``assert``."""
    if not ok:
        raise AssertionError(message)


def _rel_err(got, ref):
    """max|got - ref| / max|ref| in f32 — one number per comparison."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def _check_close(what, got, ref, tol):
    _require(np.all(np.isfinite(np.asarray(got, np.float32))),
             f"{what}: non-finite values")
    err = _rel_err(got, ref)
    print(f"   {what}: rel err {err:.2e} (tol {tol:.0e})", flush=True)
    _require(err <= tol, f"{what}: rel err {err:.3e} > {tol:.0e}")


def _check_mostly_close(what, got, ref, tol, but=0.01):
    """:func:`_check_close` over all values but the worst ``but`` of them.
    Two programs that cut one computation differently round differently, and
    where a near tie of the router then falls the other way one token's
    values move as far as values go: a fault (a state not carried, a
    position off by one) moves them all."""
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    _require(np.all(np.isfinite(got)), f"{what}: non-finite values")
    diff = np.abs(got - ref)
    scale = max(np.max(np.abs(ref)), 1e-30)
    err = float(np.quantile(diff, 1.0 - but) / scale)
    print(f"   {what}: rel err {err:.2e} over {100 * (1 - but):.0f} % of the "
          f"values (tol {tol:.0e}), {float(diff.max() / scale):.2e} over all",
          flush=True)
    _require(err <= tol, f"{what}: rel err {err:.3e} > {tol:.0e}")


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def phase_device():
    import jax
    import jaxlib
    import libtpu

    import mxnet_tpu  # noqa: F401  (places the compile cache)

    with _Phase("device"):
        backend = jax.default_backend()
        _require(backend == "tpu",
                 f"jax backend is {backend!r}, not 'tpu': this smoke only "
                 "means something on the chip")
        devs = jax.devices()
        info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs)}
        cache_dir = jax.config.jax_compilation_cache_dir
        entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
        cache = {"dir": cache_dir, "from_env":
                 bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
                 "entries_at_start": entries, "warm": entries > 0}
        print(f"   {info['platform']} / {info['kind']} x {info['count']}; "
              f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
              f"libtpu {libtpu.__version__}", flush=True)
        print(f"   compile cache: {cache_dir} "
              f"({'JAX_COMPILATION_CACHE_DIR' if cache['from_env'] else 'checkout default'}), "
              f"{entries} entries at start", flush=True)
    return info, cache


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _require_mosaic(jitted, *args):
    _require(_MOSAIC in jitted.lower(*args).as_text(),
             "kernel lowered without the Mosaic custom call")


def _attention_paths(config, batch, seq):
    """(flash_packed, flash, plain): the ``attention.impl.*`` counts of one
    trace of the forward of a benchmark train cell's model (built as
    ``benchmark/configs/<config>.json`` says) at (batch, seq): one count an
    attention layer, by the path ``MultiHeadAttention`` took."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import models, obs
    from mxnet_tpu.parallel.functional import functionalize

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmark", "configs", config + ".json")) as f:
        model = json.load(f)["model"]
    net = getattr(models, model["constructor"])(
        **{k: model[k] for k in model["constructor_args"]})
    net.initialize()
    _, apply = functionalize(net)
    params = {p.name: jax.ShapeDtypeStruct(p.shape, jnp.bfloat16)
              for p in net._iter_params()}
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    was_on = obs.enabled()
    obs.enable()
    try:
        before = dict(obs.metrics.snapshot()["counters"])
        jax.eval_shape(lambda p, *ins: apply(p, *ins)[0], params,
                       *((tokens,) * (2 if model["kind"] == "mlm" else 1)))
        after = obs.metrics.snapshot()["counters"]
    finally:
        if not was_on:
            obs.disable()
    return tuple(after.get("attention.impl." + impl, 0)
                 - before.get("attention.impl." + impl, 0)
                 for impl in ("flash_packed", "flash", "plain"))


def phase_kernels():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention import plain_attention
    from mxnet_tpu.ops.flash_attention import (_decode_attention_xla,
                                               _join_heads,
                                               decode_page_group,
                                               flash_attention,
                                               flash_attention_packed,
                                               flash_decode_attention,
                                               flash_schedule)

    with _Phase("kernels"):
        # flash forward + gradients at gpt2m-train-s1024's own attention
        # shape and at the train phase's, the schedule the kernels take from
        # the shapes, and what the kernels alone cost a call
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        # the packed entry's errors at the cell's shape are held to the
        # band the (B, H, S, D) entry measured there (PERF.md §6, PR 47),
        # at the train phase's to PR 27's
        for (b, h, s, d), packed_tols in (
                ((4, 16, 1024, 64), (3e-3, 4e-3, 5.2e-3, 3e-3)),
                ((TRAIN_BATCH, 12, TRAIN_SEQ, 64),
                 (3e-3, 5.2e-3, 4e-3, 3.3e-3))):
            q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.bfloat16)
                       for kk in keys[:3])
            w = jax.random.normal(keys[3], (b, h, s, d), jnp.float32)

            def loss(attn, q, k, v):
                out = attn(q, k, v, causal=True)
                return jnp.sum(out.astype(jnp.float32) * w), out

            forward = jax.jit(functools.partial(flash_attention, causal=True))
            flash = jax.jit(jax.value_and_grad(
                functools.partial(loss, flash_attention), argnums=(0, 1, 2),
                has_aux=True))
            # the reference sees the same bf16 values, widened: its own
            # rounding would otherwise be as large as the error under test
            ref = jax.jit(jax.value_and_grad(
                functools.partial(loss, plain_attention), argnums=(0, 1, 2),
                has_aux=True))
            _require_mosaic(flash, q, k, v)
            (_, out), grads = flash(q, k, v)
            (_, out_ref), grads_ref = ref(*(x.astype(jnp.float32)
                                            for x in (q, k, v)))
            shape = f"({b},{h},{s},{d}) bf16 causal"
            print(f"   flash schedule {shape}: "
                  f"{flash_schedule(s, d, True, b, h)}", flush=True)
            _check_close(f"flash fwd  {shape}", out, out_ref, 2e-2)
            for name, g, gr in zip(("dq", "dk", "dv"), grads, grads_ref):
                _check_close(f"flash bwd {name}", g, gr, 4e-2)
            # the packed entry over the SAME values laid out as the fused
            # projection writes them, (B, S, 3·H·D): no transpose around it
            qkv = jnp.concatenate([_join_heads(x) for x in (q, k, v)], -1)
            w_packed = _join_heads(w)

            def packed_loss(qkv):
                out = flash_attention_packed(qkv, h, causal=True)
                return jnp.sum(out.astype(jnp.float32) * w_packed), out

            packed = jax.jit(jax.value_and_grad(packed_loss, has_aux=True))
            packed_forward = jax.jit(functools.partial(
                flash_attention_packed, heads=h, causal=True))
            _require_mosaic(packed, qkv)
            (_, out), grad = packed(qkv)
            for name, g, gr, tol in zip(
                    ("fwd", "dq", "dk", "dv"),
                    (out,) + tuple(jnp.split(grad, 3, axis=-1)),
                    [_join_heads(x) for x in (out_ref,) + grads_ref],
                    packed_tols):
                _check_close(f"flash packed {name} ({b},{s},3x{h * d})", g,
                             gr, tol)
            took = []
            for fn, args in ((forward, (q, k, v)), (flash, (q, k, v)),
                             (packed_forward, (qkv,)), (packed, (qkv,))):
                jax.block_until_ready(fn(*args))
                t0 = time.monotonic()
                for _ in range(20):
                    last = fn(*args)
                jax.block_until_ready(last)
                took.append((time.monotonic() - t0) * 50)
            print(f"   flash alone {shape}: forward {took[0]:.3f} ms, forward "
                  f"+ backward {took[1]:.3f} ms a call; packed {took[2]:.3f}, "
                  f"{took[3]:.3f} (host clock, 20 calls)", flush=True)
        # which entry the benchmark's two train programs take, a layer
        for config, batch, seq, want in (
                ("gpt2-medium", 4, 1024, (24, 0, 0)),
                ("bert-large", 32, 128, (0, 0, 24))):
            got = _attention_paths(config, batch, seq)
            print(f"   attention layers of {config}'s train program at "
                  f"({batch}, {seq}): {got[0]} flash_packed, {got[1]} flash, "
                  f"{got[2]} plain", flush=True)
            _require(got == want, f"{config}: attention paths {got}, "
                     f"expected {want}")
        # longcat-omni's longest prefill runs the forward at 192 / 128
        print("   flash schedule (1,64,1536,192) bf16 causal: "
              f"{flash_schedule(1536, 192, True)}", flush=True)
        h, d = 12, 64    # the serve phase's heads

        # paged decode, every layer in the one pool, K and V side by side,
        # ragged lengths, one inner layer: the serve phase's geometry in
        # both dtypes, then gpt2m-serve-closed's own (16 slots, a table of
        # 64 pages of 16, the 24-layer float32 pool)
        ragged = [0, 1, 15, 16, 17, 1000, 2047, 2048]
        cell = [0, 1, 15, 16, 17, 127, 128, 129, 248, 255, 256, 500, 768,
                1000, 1023, 1024]
        for n_seq, max_pages, layers, heads, lengths, dtype, tol in (
                (SLOTS, 2048 // PAGE, 3, h, ragged, jnp.float32, 1e-4),
                # 16 heads: the kernel copies a 16-bit pool's pages out
                # only where the heads fill whole packed tiles
                (SLOTS, 2048 // PAGE, 3, 16, ragged, jnp.bfloat16, 2e-2),
                (16, 1024 // PAGE, 24, 16, cell, jnp.float32, 1e-4)):
            layer = layers // 2
            n_pages = n_seq * max_pages + 1
            lengths = np.array(lengths, np.int32)
            rng = np.random.RandomState(0)
            table = (rng.permutation(n_pages - 1) + 1).astype(np.int32) \
                .reshape(n_seq, max_pages)
            live = lengths > 0   # a length-0 row is an idle slot: garbage out
            decode = jax.jit(flash_decode_attention, static_argnums=2)
            decode_ref = jax.jit(
                lambda q, pool, *a: _decode_attention_xla(
                    q, pool, layer, *a, 1.0 / np.sqrt(d)))
            kq, kp = jax.random.split(jax.random.PRNGKey(1), 2)
            qd = jax.random.normal(kq, (n_seq, heads, d), dtype)
            pool = jax.random.normal(
                kp, (n_pages, layers, PAGE, heads, 2 * d), dtype)
            group = decode_page_group(pool.shape, max_pages)
            _require(group > 1, f"one page a grid step: page group {group}")
            _require_mosaic(decode, qd, pool, layer, table, lengths)
            got = np.asarray(decode(qd, pool, layer, table, lengths),
                             np.float32)
            want = np.asarray(decode_ref(qd, pool, table, lengths),
                              np.float32)
            _require(np.all(np.isfinite(got)), "decode kernel: non-finite")
            _check_close(f"paged decode ({n_seq},{heads},64) x {max_pages} "
                         f"pages in groups of {group}, {layers}-layer pool "
                         f"{jnp.dtype(dtype).name}", got[live], want[live],
                         tol)
            del pool


# ---------------------------------------------------------------------------
# train (one chip, and the multichip meshes)
# ---------------------------------------------------------------------------

def _on_tpu(tree):
    import jax

    return all(dev.platform == "tpu"
               for leaf in jax.tree_util.tree_leaves(tree)
               for dev in leaf.devices())


def run_trainer(mesh_axes, devices, seq, steps):
    """``transformer_lm()`` at full width under ShardedTrainer on the given
    mesh: ``steps`` steps on one fixed batch. Returns (trainer, losses,
    attention path) after the invariants every mesh shares."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import bert_sharding_rules, transformer_lm

    mx.random.seed(0)
    net = transformer_lm(max_length=seq)
    net.initialize()
    trainer = par.ShardedTrainer(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
        par.make_mesh(mesh_axes, devices=devices),
        rules=bert_sharding_rules(), optimizer="adam",
        optimizer_params={"learning_rate": 1e-4}, compute_dtype="bfloat16")
    tokens = np.random.RandomState(0).randint(
        0, VOCAB, (TRAIN_BATCH, seq + 1)).astype(np.int32)
    x, y = nd.array(tokens[:, :-1]), nd.array(tokens[:, 1:])

    losses = []
    compiles_after_first = None
    for _ in range(steps):
        losses.append(float(trainer.step(x, y).asnumpy()))
        if compiles_after_first is None:
            compiles_after_first = _compiles[0]
    trainer.block_until_ready()
    print(f"   mesh {mesh_axes} seq {seq}: loss "
          + " ".join(f"{l:.4f}" for l in losses), flush=True)
    _require(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    _require(trainer._step_fn._cache_size() == 1,
             f"{trainer._step_fn._cache_size()} step programs, expected 1")
    _require(_compiles[0] == compiles_after_first,
             f"{_compiles[0] - compiles_after_first} program(s) built "
             "after step 1")
    _require(_on_tpu(trainer.param_vals) and _on_tpu(trainer.opt_state),
             "a parameter or optimizer slot is not on a TPU device")

    # which attention ran: read it off the step program itself
    with par.mesh_scope(trainer.mesh):
        text = trainer._step_fn.lower(
            trainer.param_vals, trainer.opt_state, jnp.float32(1e-4),
            jnp.float32(1), x._data, y._data).as_text()
    kernel = "flash" if _MOSAIC in text else "plain"
    path = f"ring+{kernel}" if mesh_axes.get("sp", 1) > 1 else kernel
    print(f"   attention: {path}", flush=True)
    return trainer, losses, path


def phase_train():
    import jax

    with _Phase("train"):
        _trainer, losses, path = run_trainer(
            {"dp": 1}, jax.devices()[:1], TRAIN_SEQ, TRAIN_STEPS)
        _require(losses[-1] < losses[0],
                 f"loss did not fall over {TRAIN_STEPS} steps: {losses}")
        _require(path == "flash",
                 f"auto dispatch ran {path} attention at seq {TRAIN_SEQ}")
    return losses, path


def phase_multichip(one_chip_loss):
    """dp2 x tp2, then dp2 x sp2 at seq 4096, on the first four chips."""
    import jax

    devs = jax.devices()
    if len(devs) < 4:
        _Phase.results["multichip"] = {"status": "not_run",
                                       "devices": len(devs)}
        print(f"== multichip not_run ({len(devs)} device(s), needs 4)",
              flush=True)
        return {}
    paths = {}
    with _Phase("multichip"):
        trainer, losses, paths["dp2xtp2"] = run_trainer(
            {"dp": 2, "tp": 2}, devs[:4], TRAIN_SEQ, 2)
        holders = {d for v in trainer.param_vals.values()
                   for d in v.devices()}
        _require(len(holders) == 4,
                 f"shards on {len(holders)} devices, not 4")
        name = next(n for n in trainer.param_vals if n.endswith("qkv_weight"))
        whole = trainer.param_vals[name]
        shard = whole.addressable_shards[0].data
        _require(shard.nbytes * 2 == whole.nbytes,
                 f"{name}: {shard.nbytes} bytes per device of "
                 f"{whole.nbytes} — not halved over tp")
        print(f"   {name}: {whole.nbytes} bytes, {shard.nbytes} per device "
              f"on {len(holders)} devices", flush=True)
        _require(abs(losses[0] - one_chip_loss) <= 1e-2 * abs(one_chip_loss),
                 f"step-1 loss {losses[0]} vs one chip {one_chip_loss}")

        _t, _l, paths["dp2xsp2"] = run_trainer(
            {"dp": 2, "sp": 2}, devs[:4], 4096, 2)
        _require(paths["dp2xsp2"] == "ring+flash", paths["dp2xsp2"])
    return paths


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def dense_reference(cfg, params, prompts, new_tokens):
    """Greedy tokens from ``lm_prefill`` + ``lm_decode_step`` over a dense
    KV cache — no pages, no buckets, no scheduler — for all prompts as one
    batch, on the same device and in the same f32 as the engine."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models.transformer import lm_decode_step, lm_prefill

    n, longest = len(prompts), max(len(p) for p in prompts)
    lens = np.array([len(p) for p in prompts], np.int32)
    padded = np.zeros((n, longest), np.int32)
    for i, p in enumerate(prompts):
        padded[i, :len(p)] = p
    params = jax.tree_util.tree_map(jnp.asarray, params)

    @jax.jit
    def prefill(params, tokens):
        logits, k, v = lm_prefill(cfg, params, tokens)
        pad = ((0, 0), (0, 0), (0, new_tokens), (0, 0), (0, 0))
        first = jnp.argmax(logits[jnp.arange(n), lens - 1], axis=-1)
        return first.astype(jnp.int32), (jnp.pad(k, pad), jnp.pad(v, pad))

    @jax.jit
    def step(params, tokens, kv, positions):
        logits, kv = lm_decode_step(cfg, params, tokens, kv, positions)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), kv

    tok, kv = prefill(params, padded)
    out = [np.asarray(tok)]
    for t in range(new_tokens - 1):
        tok, kv = step(params, tok, kv, jnp.asarray(lens + t))
        out.append(np.asarray(tok))
    return np.stack(out, axis=1).tolist()


def phase_serve():
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.analysis import TraceLinter
    from mxnet_tpu.models import transformer_lm
    from mxnet_tpu.models.transformer import decode_config, decode_params
    from mxnet_tpu.ops.flash_attention import decode_attention_impl
    from mxnet_tpu.serve import (DecodeEngine, DecodeScheduler, ServeClient,
                                 ServeServer)

    with _Phase("serve"):
        mx.random.seed(1)
        lm = transformer_lm()
        lm.initialize()
        lm(nd.zeros((1, 8)))   # deferred shapes
        max_len = decode_config(lm)["max_length"]
        engine = DecodeEngine(
            lm, slots=SLOTS, page_size=PAGE,
            num_pages=SLOTS * max_len // PAGE + 1, prompt_buckets=BUCKETS)
        engine.warmup()
        attn = decode_attention_impl(engine.kv)
        print(f"   decode_attn: {attn}", flush=True)

        rng = np.random.RandomState(2)
        prompts = [rng.randint(0, VOCAB, n).tolist() for n in PROMPT_LENS]
        sched = DecodeScheduler(engine, max_new_tokens=NEW_TOKENS)
        server = ServeServer(engine=None, decode=sched, port=0)
        server.start()
        got = [None] * len(prompts)

        def stream(i):
            with ServeClient("127.0.0.1", server.port) as client:
                got[i] = list(client.generate(prompts[i],
                                              max_new_tokens=NEW_TOKENS))

        try:
            threads = [threading.Thread(target=stream, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(300)
            _require(not any(t.is_alive() for t in threads),
                     "a stream hung")
        finally:
            server.stop()

        want = dense_reference(decode_config(lm), decode_params(lm), prompts,
                               NEW_TOKENS)
        for i, (g, w) in enumerate(zip(got, want)):
            _require(g == w, f"stream {i} (prompt {PROMPT_LENS[i]}): engine "
                             f"{g} != dense reference {w}")
        print(f"   {len(prompts)} streams x {NEW_TOKENS} tokens equal the "
              "dense reference", flush=True)

        stats = engine.stats()
        _require(stats["num_programs"] == len(BUCKETS) + 1,
                 stats["programs"])
        _require(TraceLinter().check_decode_engine(engine) == [],
                 "check_decode_engine found retrace churn")
        engine.pool.assert_baseline()   # pages leaked == 0
        print(f"   {stats['num_programs']} programs for {len(BUCKETS)} "
              f"buckets + 1 step; {stats['pool']['used']} pages held",
              flush=True)
        # the step program carries the path it was traced with
        _require((attn == "pallas") == (_MOSAIC in _step_text(engine)),
                 f"decode_attn says {attn} but the step program disagrees")
        # ... and reads the pool where it lies: by the compiler's own
        # account it allocates less than one layer's K of the pool
        k_slice = engine.kv.nbytes // (2 * engine.cfg["layers"])
        program = stats["step_program"]
        print(f"   step program: temp_bytes {program['temp_bytes']}, "
              f"bytes_accessed {program['bytes_accessed']}; one layer's K "
              f"of the pool is {k_slice} bytes", flush=True)
        _require(program["temp_bytes"] < k_slice,
                 f"the step program allocates {program['temp_bytes']} bytes "
                 f"of temporaries, one layer's K of the pool ({k_slice}) or "
                 "more: it slices, copies or lays the pool out again")
        # ... in groups of pages: what one decode step costs in grid steps
        paged = stats["paged_kernel"]
        print(f"   paged kernel: {paged}", flush=True)
        _require((attn == "pallas") == (paged is not None)
                 and (paged is None or paged["page_group"] > 1),
                 f"decode_attn says {attn} but stats()['paged_kernel'] is "
                 f"{paged}")
    return attn


LATENT = {"vocab_size": 4096, "hidden_size": 1024, "num_layers": 3,
          "first_dense": 1, "num_heads": 16, "qk_nope": 128, "qk_rope": 64,
          "v_head": 128, "kv_rank": 512, "dense_width": 2048,
          "expert_width": 512, "router_experts": 32, "experts_first": 8,
          "experts_held": 8, "experts_per_token": 8, "routed_scale": 2.5,
          "rms_eps": 1e-6, "max_length": 4096,
          "rope": {"theta": 10000, "factor": 40,
                   "original_max_position_embeddings": 4096, "beta_fast": 32,
                   "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}}


def phase_latent():
    """The latent-attention model through the engine's two programs — a
    prompt in pieces of 1,024 against the same prompt whole, in memory filled
    with NaN first —, and its paged kernel against XLA over what the engine
    wrote."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import mla_moe
    from mxnet_tpu.ops.flash_attention import (_latent_decode_attention_xla,
                                               flash_latent_decode_attention)
    from mxnet_tpu.serve import DecodeEngine
    from mxnet_tpu.serve.kvcache import SCRATCH_PAGE

    with _Phase("latent"):
        slots, page, n, padded = 4, 256, 2500, 3072
        _dirty_memory()
        model = mla_moe.MLAMoEDecodeModel(LATENT, seed=3)
        # the model offers ``prefill_from``: the prompt goes in pieces of the
        # smallest bucket, through ONE prefill program
        engine = DecodeEngine(model, slots=slots, page_size=page,
                              num_pages=slots * 16 + 1,
                              prompt_buckets=[1024, padded])
        engine.warmup()
        _require(engine.prefill_piece == 1024 and engine.buckets == [1024]
                 and engine.max_prompt == padded
                 and engine.stats()["num_programs"] == 2,
                 f"pieces of 1024 expected: {engine.stats()}")
        rng = np.random.RandomState(4)
        prompt = rng.randint(0, LATENT["vocab_size"], n)
        engine.pool.alloc(0, padded // page)
        table = engine.pool.table(0)
        counted = {}
        for start in range(0, padded, engine.prefill_piece):
            tok, c = engine.read(engine.launch_prefill(prompt, table,
                                                       start=start))
            counted = {k: counted.get(k, 0) + v for k, v in c.items()}
        _require(counted["moe.dropped"] == 0
                 and counted["moe.assignments"] == 2 * n * 8,
                 f"prefill counted {counted}")
        # ... against the model's ``prefill``, the whole-prompt kernel
        whole = np.zeros((1, padded), np.int32)
        whole[0, :n] = prompt
        logits, want_rows, _ = jax.jit(model.prefill)(
            model.params, jnp.asarray(whole), n)
        rows = np.asarray(engine.kv[np.asarray(table)], np.float32)
        rows = np.moveaxis(rows, 1, 0).reshape(rows.shape[1], padded, -1)
        _check_mostly_close(
            f"{n} tokens in pieces of 1024 against whole: rows", rows[:, :n],
            np.asarray(want_rows[:, :n], np.float32), 2e-2)
        _require(tok == int(jnp.argmax(logits)),
                 f"a prompt in pieces served {tok} first, prefill whole "
                 f"{int(jnp.argmax(logits))}")
        tables = np.full((slots, engine.max_pages), SCRATCH_PAGE, np.int32)
        tables[0, :len(table)] = table
        z = np.zeros((slots,), np.int32)
        for i in range(4):
            pos, lengths, toks = z.copy(), z.copy(), z.copy()
            pos[0], lengths[0], toks[0] = n + i, n + 1 + i, tok
            tok = int(engine.step(toks, pos, tables, lengths,
                                  np.zeros((slots,), np.float32))[0])
            _require(engine.last_counters["moe.dropped"] == 0
                     and engine.last_counters["moe.assignments"] == 2 * 8,
                     f"step counted {engine.last_counters}")
        # the kernel against the gather, over the rows the engine just wrote
        q = jax.random.normal(jax.random.PRNGKey(5), (slots, 16, 640),
                              jnp.bfloat16)
        args = (q, engine.kv, 1, jnp.asarray(tables),
                jnp.asarray([n + 4, 0, 0, 0], jnp.int32), 512, 0.135)
        kernel = jax.jit(flash_latent_decode_attention, static_argnums=(2, 5, 6))
        _require_mosaic(kernel, *args)
        got = np.asarray(kernel(*args), np.float32)[0]
        want = np.asarray(jax.jit(_latent_decode_attention_xla,
                                  static_argnums=(2, 5, 6))(*args),
                          np.float32)[0]
        _require(np.all(np.isfinite(got)), "latent kernel: non-finite")
        _check_close(f"paged latent decode (16 x 640) over {n + 4} positions",
                     got, want, 3e-2)
        engine.pool.free(0)
        engine.pool.assert_baseline()
        program = engine.stats()["step_program"]
        layer = engine.kv.nbytes // LATENT["num_layers"]
        print(f"   latent pool {engine.kv.shape} {engine.kv.dtype}: step "
              f"temp_bytes {program['temp_bytes']}, one layer's latents "
              f"{layer} bytes", flush=True)
        _require(program["temp_bytes"] < layer,
                 f"the latent step allocates {program['temp_bytes']} bytes: "
                 "it slices, copies or lays the pool out again")


# published widths of the gated-delta model's caches: 2 cached heads x 256 in a
# flat bfloat16 row, pages of 256; 32 value heads x 128 x 128 float32 of state
GDN = {"vocab_size": 4096, "hidden_size": 1024, "num_layers": 4,
       "full_interval": 2, "num_heads": 16, "num_kv_heads": 2, "head_dim": 256,
       "rotary_dim": 64, "rope_theta": 10000000, "linear_key_heads": 16,
       "linear_value_heads": 32, "linear_key_dim": 128,
       "linear_value_dim": 128, "conv_width": 4, "expert_width": 512,
       "router_experts": 32, "experts_first": 8, "experts_held": 8,
       "experts_per_token": 8, "rms_eps": 1e-6, "max_length": 2048}


def _delta_rule_over_a_piece():
    """``gdn_prefill`` at the published geometry — a 2,048-position piece of
    32 value heads on 16 key heads of 128 x 128, from a random state, the
    last 100 positions masked — through Mosaic, against the token-by-token
    recurrence and against the XLA chunked form: the kernel's error against
    the recurrence at most twice the XLA form's own, and what each form
    takes a layer."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import gated_delta

    s, hk, hv, d, dead = 2048, 16, 32, 128, 100
    keys = jax.random.split(jax.random.PRNGKey(21), 6)
    q, k = (jax.random.normal(key, (s, hk, d), jnp.float32)
            for key in keys[:2])
    q, k = (a / jnp.linalg.norm(a, axis=-1, keepdims=True) for a in (q, k))
    v = jax.random.normal(keys[2], (s, hv, d), jnp.float32)
    live = (jnp.arange(s) < s - dead)[:, None]
    g = jnp.where(live, -jnp.exp(jax.random.uniform(
        keys[3], (s, hv), minval=-4.0, maxval=1.0)), 0.0)
    beta = jnp.where(live, jax.random.uniform(keys[4], (s, hv)), 0.0)
    state = jax.random.normal(keys[5], (hv, d, d), jnp.float32)
    rep = hv // hk
    want = jax.jit(gated_delta.delta_rule_recurrent)(
        jnp.repeat(q, rep, axis=1), jnp.repeat(k, rep, axis=1), v, g, beta,
        state)
    _chunked_forms_against_the_recurrence(
        "the delta rule over a 2,048-position piece (32 x 128 x 128, 100 "
        "masked)", gated_delta.delta_rule_chunked, "gdn_prefill",
        (q, k, v, g, beta, state), want)


def _chunked_forms_against_the_recurrence(what, chunked, kernel, args, want):
    """A chunked rule ``chunked(*args, impl=)`` in its two forms — ``impl``
    ``"pallas"`` through the Mosaic kernel named ``kernel``, and XLA —
    against the recurrence's ``want`` (outputs, state): both finite, the
    kernel's error at most twice the XLA form's own, and what each form
    takes a layer from ``(S, H, d)`` arrays (the relayouts around the call
    included), printed."""
    import jax
    import jax.numpy as jnp

    forms, errors = {}, {}
    for impl in ("xla", "pallas"):
        forms[impl] = jax.jit(functools.partial(chunked, impl=impl))
        got = forms[impl](*args)
        _require(all(bool(jnp.all(jnp.isfinite(a))) for a in got),
                 f"{what} ({impl}): non-finite values")
        errors[impl] = tuple(_rel_err(a, b) for a, b in zip(got, want))
    text = forms["pallas"].lower(*args).as_text()
    _require(_MOSAIC in text and kernel in text,
             f"{what} lowered without the {kernel} Mosaic call")
    took = {}
    for impl, form in forms.items():
        t0 = time.monotonic()
        for _ in range(20):
            out = form(*args)
        jax.block_until_ready(out)
        took[impl] = (time.monotonic() - t0) * 50
    print(f"   {what} against the recurrence: {kernel} rel err "
          f"{errors['pallas'][0]:.2e} outputs, {errors['pallas'][1]:.2e} "
          f"state, {took['pallas']:.3f} ms a layer; XLA chunked "
          f"{errors['xla'][0]:.2e}, {errors['xla'][1]:.2e}, "
          f"{took['xla']:.3f} ms a layer", flush=True)
    for part, got, own in zip(("outputs", "state"), errors["pallas"],
                              errors["xla"]):
        _require(got <= 2 * max(own, 1e-6),
                 f"{kernel}'s {part} are {got:.2e} from the recurrence, the "
                 f"XLA form's {own:.2e}")


def phase_state():
    """State beside pages: the gated-delta model through the engine's two
    programs — a prompt in pieces against the same prompt whole, in memory
    filled with NaN first —, and its four kernels against XLA over what the
    engine wrote."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import gdn_moe
    from mxnet_tpu.ops import gated_delta, gqa_attention
    from mxnet_tpu.serve import DecodeEngine
    from mxnet_tpu.serve.kvcache import SCRATCH_PAGE

    with _Phase("state"):
        slots, page = 4, 256
        _dirty_memory()
        model = gdn_moe.GDNMoEDecodeModel(GDN, seed=3)
        # a pool of 538 MB: against the step's other temporaries (a layer's
        # slices of the stacked weights, 65 MB here) one layer's share of it
        # is what a copy of pool or state would stand out from

        def build(buckets):
            built = DecodeEngine(model, slots=slots, page_size=page,
                                 num_pages=513, prompt_buckets=buckets)
            # what slot 1 held before must not reach its prompt: NaN there
            built.state = {name: jax.jit(lambda a: a.at[1].set(jnp.nan),
                                         out_shardings=a.format)(a)
                           for name, a in built.state.items()}
            built.warmup()
            return built

        # the model offers ``prefill_from``: the prompt goes in pieces of the
        # smallest bucket, through ONE prefill program
        engine = build([256, 512])
        _require(engine.prefill_piece == 256 and engine.buckets == [256]
                 and engine.stats()["num_programs"] == 2,
                 f"pieces of 256 expected: {engine.stats()}")
        # and the two of the held experts (their buffer, their rows' way back)
        _require(_step_text(engine).count(_MOSAIC) == 4,
                 "the step has not one kernel each of gdn_decode, gqa_decode, "
                 "moe_rows and moe_rows_back")
        rng = np.random.RandomState(4)
        prompt = rng.randint(0, GDN["vocab_size"], 300)
        tables = np.full((slots, engine.max_pages), SCRATCH_PAGE, np.int32)
        z = np.zeros((slots,), np.int32)

        def serve(eng):
            """The prompt into slot 1, then 4 steps: (tokens, the slot's
            state after the prompt, its 300 rows of every paged layer)."""
            eng.pool.alloc(0, 2)
            table = eng.pool.table(0)
            counted = {}
            for start in range(0, 512, eng.prefill_piece):
                tok, c = eng.read(eng.launch_prefill(prompt, table, slot=1,
                                                     start=start))
                counted = {k: counted.get(k, 0) + v for k, v in c.items()}
            _require(counted["moe.dropped"] == 0
                     and counted["moe.assignments"] == 4 * 300 * 8,
                     f"prefill counted {counted}")
            after = {name: np.asarray(a[1], np.float32)
                     for name, a in eng.state.items()}
            rows = np.asarray(eng.kv[np.asarray(table)], np.float32)
            rows = np.moveaxis(rows, 1, 0).reshape(rows.shape[1], 512, -1)
            tables[1, :len(table)] = table
            toks = [tok]
            for i in range(4):
                pos, lengths, last = z.copy(), z.copy(), z.copy()
                pos[1], lengths[1], last[1] = 300 + i, 301 + i, toks[-1]
                toks.append(int(eng.step(last, pos, tables, lengths,
                                         np.zeros((slots,), np.float32))[1]))
                _require(eng.last_counters["moe.dropped"] == 0
                         and eng.last_counters["moe.assignments"] == 4 * 8,
                         f"step counted {eng.last_counters}")
            return toks, after, rows[:, :300]

        # the same prompt as ONE piece (start 0, nothing before it) ...
        whole = build([512])
        want_toks, want_state, want_rows = serve(whole)
        whole.pool.free(0)
        del whole
        # ... and through the model's ``prefill``, the whole-prompt kernel
        padded = np.zeros((1, 512), np.int32)
        padded[0, :300] = prompt
        logits, plain_rows, _, plain = jax.jit(model.prefill)(
            model.params, jnp.asarray(padded), 300)
        idle = np.asarray(engine.state["s"][0])
        toks, after, rows = serve(engine)
        _require(all(np.all(np.isfinite(a)) for a in after.values())
                 and np.all(np.isfinite(rows)),
                 "a prompt in pieces: the slot's state or rows not finite")
        for what, got, want in (
                ("state s", after["s"], want_state["s"]),
                ("tail", after["tail"], want_state["tail"]),
                ("rows", rows, want_rows),
                ("state s (prefill)", after["s"], np.asarray(plain["s"])),
                ("tail (prefill)", after["tail"],
                 np.asarray(plain["tail"], np.float32)),
                ("rows (prefill)", rows,
                 np.asarray(plain_rows[:, :300], np.float32))):
            _check_mostly_close(
                f"300 tokens in pieces of 256 against whole: {what}", got,
                want, 2e-2)
        _require(toks == want_toks and toks[0] == int(jnp.argmax(logits)),
                 f"a prompt in pieces served {toks}, whole {want_toks}, "
                 f"prefill's first {int(jnp.argmax(logits))}")
        table = engine.pool.table(0)
        _require(np.array_equal(idle, np.asarray(engine.state["s"][0])),
                 "a step touched the state of an idle slot")
        # the paged kernel against the gather, over the rows just written
        q = jax.random.normal(jax.random.PRNGKey(5), (slots, 2, 8, 256),
                              jnp.bfloat16)
        args = (q, engine.kv, 1, jnp.asarray(tables),
                jnp.asarray([0, 304, 0, 0], jnp.int32), 256 ** -0.5)
        kernel = jax.jit(gqa_attention.flash_gqa_decode_attention,
                         static_argnums=(2, 5))
        _require_mosaic(kernel, *args)
        got = np.asarray(kernel(*args), np.float32)[1]
        want = np.asarray(jax.jit(gqa_attention._gqa_decode_xla,
                                  static_argnums=(2, 5))(*args),
                          np.float32)[1]
        _check_close("grouped-KV paged decode (2 x 8 x 256, bfloat16) over "
                     "304 positions", got, want, 3e-2)
        # the flash forward against itself token by token is the engine's own
        # check; here the kernel against dense attention at 512 positions
        qp = jax.random.normal(jax.random.PRNGKey(6), (2, 8, 512, 256),
                               jnp.bfloat16)
        kp, vp = (jax.random.normal(jax.random.PRNGKey(k), (2, 512, 256),
                                    jnp.bfloat16) for k in (7, 8))
        flash = jax.jit(gqa_attention.gqa_flash_attention)
        _require_mosaic(flash, qp, kp, vp)
        sc = jnp.einsum("hgqd,hkd->hgqk", qp, kp,
                        preferred_element_type=jnp.float32) / 16.0
        sc = jnp.where(jnp.tril(jnp.ones((512, 512), bool)), sc, -jnp.inf)
        dense = jnp.einsum("hgqk,hkd->hgqd", jax.nn.softmax(sc, axis=-1),
                           vp.astype(jnp.float32))
        _check_close("grouped-KV flash forward (16 on 2 heads of 256)",
                     np.asarray(flash(qp, kp, vp), np.float32),
                     np.asarray(dense), 3e-2)
        # the one-token delta rule against XLA on the engine's own state
        b, h, d = slots, 32, 128
        rows = [jax.random.normal(jax.random.PRNGKey(9 + i), (b, h, d),
                                  jnp.float32) for i in range(3)]
        qd, kd = (r / jnp.linalg.norm(r, axis=-1, keepdims=True)
                  for r in rows[:2])
        g = -jax.random.uniform(jax.random.PRNGKey(12), (b, h)) * 3
        beta = jax.random.uniform(jax.random.PRNGKey(13), (b, h))
        live = jnp.asarray([False, True, True, False])
        states = jnp.array(engine.state["s"])
        step = jax.jit(gated_delta.delta_rule_step,
                       static_argnames=("layer", "impl"))
        want_o, want_s = step(states, layer=1, q=qd, k=kd, v=rows[2], g=g,
                              beta=beta, live=live, impl="xla")
        _require_mosaic(jax.jit(lambda *a: gated_delta.delta_rule_step(
            a[0], 1, *a[1:])), states, qd, kd, rows[2], g, beta, live)
        got_o, got_s = step(states, layer=1, q=qd, k=kd, v=rows[2], g=g,
                            beta=beta, live=live, impl="pallas")
        _check_close("one-token delta rule, outputs (32 x 128)",
                     np.asarray(got_o)[1:3], np.asarray(want_o)[1:3], 1e-4)
        _check_close("one-token delta rule, states (32 x 128 x 128)",
                     np.asarray(got_s)[:slots], np.asarray(want_s)[:slots],
                     1e-4)
        _delta_rule_over_a_piece()
        engine.pool.free(0)
        engine.pool.assert_baseline()
        program = engine.stats()["step_program"]
        share = (engine.kv.nbytes + sum(a.nbytes for a in
                                        engine.state.values())) // GDN["num_layers"]
        print(f"   pool {engine.kv.shape} {engine.kv.dtype} + state "
              f"{engine.stats()['state']}: step temp_bytes "
              f"{program['temp_bytes']}, one layer's share {share} bytes",
              flush=True)
        _require(program["temp_bytes"] < share,
                 f"the step allocates {program['temp_bytes']} bytes: it "
                 "copies the pool or the state")


# the published widths of NVIDIA-Nemotron-3-Nano-30B-A3B, four layers (one
# Mamba-2 twice, one expert, one attention), a quarter of the chip's experts
SSM = {"vocab_size": 4096, "vocab_first": 0, "hidden_size": 2688,
       "pattern": "MEM*", "num_heads": 32, "num_kv_heads": 2, "head_dim": 128,
       "ssm_heads": 64, "ssm_head_dim": 64, "ssm_groups": 8, "ssm_state": 128,
       "conv_width": 4, "chunk_size": 128, "time_step_min": 0.001,
       "time_step_max": 0.1, "time_step_floor": 0.0001, "expert_width": 1856,
       "shared_width": 3712, "router_experts": 32, "experts_first": 8,
       "experts_held": 8, "experts_per_token": 6, "routed_scale": 2.5,
       "rms_eps": 1e-5, "max_length": 1024}


def phase_ssm():
    """State-space layers beside a 2-head pool at 128 slots: the Mamba-2
    model through the engine's two programs, and ``ssm_decode`` against XLA
    over the states the engine holds."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import ssm_moe
    from mxnet_tpu.ops import mamba2
    from mxnet_tpu.serve import DecodeEngine
    from mxnet_tpu.serve.kvcache import SCRATCH_PAGE

    with _Phase("ssm"):
        slots, page = 128, 256
        _dirty_memory()
        model = ssm_moe.SSMMoEDecodeModel(SSM, seed=5)
        _require(model.params["experts"]["up_w"].shape == (8, 3072, 2048),
                 "the held experts are not stored in whole tiles of 512")
        # zeros behind the published 2688 x 1856, whatever the memory held
        # (NaN is not zero): XLA:TPU clears no buffer that a loop fills
        for name, (d, f) in (("up_w", (2688, 1856)), ("down_w", (1856, 2688))):
            w = model.params["experts"][name]
            _require(not bool(jnp.any(w[:, d:] != 0) | jnp.any(w[:, :, f:] != 0)),
                     f"experts {name}: not zeros behind {d} x {f}")
        _require(all(bool(jnp.all(jnp.isfinite(a.astype(jnp.float32))))
                     for a in jax.tree_util.tree_leaves(model.params)),
                 "the seeded weights are not all finite")
        engine = DecodeEngine(model, slots=slots, page_size=page,
                              num_pages=slots * 4 + 1, prompt_buckets=[512])
        engine.warmup()
        _require(_step_text(engine).count(_MOSAIC) == 4,
                 "the step has not one kernel each of ssm_decode, gqa_decode, "
                 "moe_rows and moe_rows_back")
        rng = np.random.RandomState(6)
        prompt = rng.randint(0, SSM["vocab_size"], 300)
        engine.pool.alloc(0, 2)
        table = engine.pool.table(0)
        tok = engine.prefill(prompt, table, slot=77)
        _require(engine.last_counters["moe.dropped"] == 0
                 and engine.last_counters["moe.assignments"] == 300 * 6,
                 f"prefill counted {engine.last_counters}")
        held = {name: np.asarray(a[77], np.float32)
                for name, a in engine.state.items()}
        for name, a in held.items():
            print(f"   slot 77 after a 300-token prefill: max |{name}| "
                  f"{np.abs(a).max():.3e}", flush=True)
            _require(np.isfinite(a).all() and np.abs(a).max() > 0,
                     f"the prefill left the slot's {name} zero or not finite")
        tables = np.full((slots, engine.max_pages), SCRATCH_PAGE, np.int32)
        tables[77, :len(table)] = table
        # the step again as a program of its own with the one-token update
        # in XLA (a gather, a scatter, no donation), fed what the engine's
        # step is fed: after every step every slot's state and tail are
        # compared -- the live one, slot 0 (which holds the warm-up
        # prompt's) and the 126 that hold zeros, all idle slots on the one
        # scratch block in the kernel
        ssm_moe.decode_attention_impl, impl = (lambda: "xla",
                                               ssm_moe.decode_attention_impl)
        try:
            # (a function of its own: jax would hand a second jit of the
            # same method the program it traced for the engine)
            twin = jax.jit(lambda *args: engine._step_fn(*args)).lower(
                engine._params, engine.kv, engine.state, engine.last,
                engine.blank_step())
        finally:
            ssm_moe.decode_attention_impl = impl
        _require(twin.as_text().count(_MOSAIC) == 3,
                 "the step's twin still holds the ssm_decode kernel")
        twin = twin.compile()
        kv_t = jnp.array(engine.kv)
        state_t = {name: jnp.array(a) for name, a in engine.state.items()}

        def gaps(name):   # per slot (scratch left out), engine against twin
            a, b = engine.state[name][:slots], state_t[name][:slots]
            return np.asarray(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b.astype(jnp.float32)),
                axis=tuple(range(1, a.ndim))))

        steps = [tok]
        for i in range(4):
            packed = engine.blank_step()    # row i: position, length, ...
            packed[77, 0], packed[77, 1] = 300 + i, 301 + i
            packed[:slots, 3:] = tables
            last = np.zeros((slots,), np.int32)
            last[77] = steps[-1]
            kv_t, state_t, (toks_t, _) = twin(engine._params, kv_t, state_t,
                                              jnp.asarray(last), packed)
            engine.last = jnp.asarray(last)
            steps.append(int(engine.read(engine.launch_step(packed))[0][77]))
            _require(engine.last_counters["moe.dropped"] == 0
                     and engine.last_counters["moe.assignments"] == 6,
                     f"step counted {engine.last_counters}")
            for name, tol in (("s", 1e-3), ("tail", 2e-2)):
                now = np.asarray(engine.state[name][77], np.float32)
                gap, top = gaps(name), np.abs(now).max()
                idle = np.delete(gap, 77).max()
                print(f"   step {i + 1}: slot 77 max |{name}| {top:.3e}, "
                      f"moved by {np.abs(now - held[name]).max():.3e}; "
                      f"against the XLA step: slot 77 {gap[77]:.3e}, the "
                      f"idle slots {idle:.3e}", flush=True)
                _require(np.isfinite(now).all() and top > 0
                         and np.abs(now - held[name]).max() > 0,
                         f"step {i + 1} left slot 77's {name} zero, not "
                         "finite or as it was")
                _require(gap[77] <= tol * top,
                         f"step {i + 1}: slot 77's {name} is {gap[77]:.3e} "
                         "from the XLA step's")
                _require(idle == 0, f"step {i + 1} touched an idle slot's "
                                    f"{name}")
                held[name] = now
            _require(steps[-1] == int(toks_t[77]),
                     f"step {i + 1} sampled {steps[-1]}, its twin "
                     f"{int(toks_t[77])}")
        del kv_t, state_t
        # and the recurrence against the scan over a prompt: the same 304
        # tokens prefilled into another slot leave the state the four steps
        # left, and do not touch this one
        engine.pool.alloc(1, 2)
        again = engine.prefill(np.concatenate([prompt, steps[:4]]),
                               engine.pool.table(1), slot=5)
        _check_close("the state after 300 prefilled + 4 stepped tokens "
                     "against 304 prefilled",
                     np.asarray(engine.state["s"][77]),
                     np.asarray(engine.state["s"][5]), 2e-2)
        _require(np.array_equal(np.asarray(engine.state["s"][77]), held["s"]),
                 "a prefill into slot 5 touched slot 77")
        print(f"   tokens stepped {steps[1:]}, after 304 prefilled {again}",
              flush=True)
        engine.pool.free(1)
        # the one-token update against XLA at the published geometry: every
        # slot's 64 x 64 x 128 float32 of layer 1, half of the slots live
        keys = jax.random.split(jax.random.PRNGKey(9), 6)
        x = jax.random.normal(keys[0], (slots, 64, 64), jnp.float32)
        delta = jnp.exp(jax.random.uniform(keys[1], (slots, 64), minval=-6.0,
                                           maxval=0.0))
        a = -jnp.exp(jax.random.uniform(keys[2], (64,), maxval=2.7))
        b, c = (jax.random.normal(k, (slots, 8, 128), jnp.float32)
                for k in keys[3:5])
        live = jax.random.uniform(keys[5], (slots,)) < 0.5
        states = jax.random.normal(jax.random.PRNGKey(10),
                                   engine.state["s"].shape, jnp.float32)
        step = jax.jit(mamba2.ssm_step, static_argnames=("layer", "impl"))
        want_y, want_s = step(states, layer=1, x=x, delta=delta, a=a, b=b,
                              c=c, live=live, impl="xla")
        _require_mosaic(jax.jit(lambda *t: mamba2.ssm_step(t[0], 1, *t[1:])),
                        states, x, delta, a, b, c, live)
        got_y, got_s = step(states, layer=1, x=x, delta=delta, a=a, b=b, c=c,
                            live=live, impl="pallas")
        on = np.asarray(live)
        _check_close("one-token state-space update, outputs (64 x 64)",
                     np.asarray(got_y)[on], np.asarray(want_y)[on], 1e-4)
        _check_close("one-token state-space update, states (64 x 64 x 128)",
                     np.asarray(got_s)[:slots], np.asarray(want_s)[:slots],
                     1e-4)
        engine.pool.free(0)
        engine.pool.assert_baseline()
        program = engine.stats()["step_program"]
        share = (engine.kv.nbytes + sum(
            t.nbytes for t in engine.state.values())) // len(SSM["pattern"])
        print(f"   pool {engine.kv.shape} {engine.kv.dtype} + state "
              f"{engine.stats()['state']}: step temp_bytes "
              f"{program['temp_bytes']}, one layer's share {share} bytes",
              flush=True)
        _require(program["temp_bytes"] < share,
                 f"the step allocates {program['temp_bytes']} bytes: it "
                 "copies the pool or the state")


# published head geometry of the double-layer latent model (16 heads of 128 +
# 64 on a 512-wide latent, a query latent, both latent scales, plain RoPE at
# theta 1e7), 32 real + 16 identity experts behind the softmax router
SCMOE = {"vocab_size": 4096, "hidden_size": 1024, "num_layers": 2,
         "num_heads": 16, "qk_nope": 128, "qk_rope": 64, "v_head": 128,
         "kv_rank": 512, "q_rank": 384, "latent_scales": True,
         "dense_width": 2048, "expert_width": 512, "router_experts": 48,
         "zero_experts": 16, "experts_first": 8, "experts_held": 8,
         "experts_per_token": 6, "routed_scale": 6.0, "rms_eps": 1e-5,
         "max_length": 1024, "rope": {"theta": 10000000, "factor": 1}}


def phase_scmoe():
    """Shortcut-connected double layers over the latent pool at 128 slots:
    a prefill and four steps through the engine's two programs, in memory
    filled with NaN first, and the served tokens against the plain
    reference's full forward (``benchmark/reference_mla_scmoe.py``)."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference_mla_scmoe as reference
    from mxnet_tpu.models import mla_scmoe
    from mxnet_tpu.serve import DecodeEngine
    from mxnet_tpu.serve.kvcache import SCRATCH_PAGE

    with _Phase("scmoe"):
        slots, page, seed = 128, 256, 7
        _dirty_memory()
        model = mla_scmoe.MLAScMoEDecodeModel(SCMOE, seed=seed)
        _require(all(bool(jnp.all(jnp.isfinite(a.astype(jnp.float32))))
                     for a in jax.tree_util.tree_leaves(model.params)),
                 "the seeded weights are not all finite")
        engine = DecodeEngine(model, slots=slots, page_size=page,
                              num_pages=slots * 2 + 1, prompt_buckets=[512])
        _require(engine.kv.shape == (slots * 2 + 1, 4, page, 640),
                 f"the pool {engine.kv.shape} has not a layer a sub-layer")
        engine.warmup()
        rng = np.random.RandomState(8)
        prompt = rng.randint(0, SCMOE["vocab_size"], 300)
        engine.pool.alloc(0, 2)
        table = engine.pool.table(0)
        served = [engine.prefill(prompt, table, slot=77)]
        c = engine.last_counters
        _require(c["moe.dropped"] == 0 and c["moe.assignments"] == 2 * 300 * 6
                 and 0 < c["moe.zero"] < c["moe.assignments"]
                 and 0 < c["moe.held"] < c["moe.assignments"],
                 f"prefill counted {c}")
        print(f"   a 300-token prefill counted {c}", flush=True)
        tables = np.full((slots, engine.max_pages), SCRATCH_PAGE, np.int32)
        tables[77, :len(table)] = table
        z = np.zeros((slots,), np.int32)
        for i in range(4):
            pos, lengths, toks = z.copy(), z.copy(), z.copy()
            pos[77], lengths[77], toks[77] = 300 + i, 301 + i, served[-1]
            served.append(int(engine.step(
                toks, pos, tables, lengths,
                np.zeros((slots,), np.float32))[77]))
            c = engine.last_counters
            _require(c["moe.dropped"] == 0 and c["moe.assignments"] == 2 * 6
                     and c["moe.zero"] + c["moe.held"] <= 12,
                     f"step counted {c}")
        engine.pool.free(0)
        engine.pool.assert_baseline()
        program = engine.stats()["step_program"]
        layer = engine.kv.nbytes // model.layers
        _require(program["temp_bytes"] < layer,
                 f"the step allocates {program['temp_bytes']} bytes: it "
                 "slices, copies or lays the pool out again")
        # the served tokens judged by the reference's float32 logits over
        # prompt + served: how far each lies below the reference's first
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        logits = np.asarray(reference.logits(SCMOE, seed, seq))[299:]
        gaps = reference.gaps_below_best(logits, served)
        spread = float(logits.max(axis=1).mean() - logits.mean())
        print(f"   served {served}; gaps below the reference's best "
              f"{np.round(gaps, 4).tolist()} (its best lies {spread:.3f} over "
              "its mean logit)", flush=True)
        _require(np.isfinite(logits).all() and float(gaps.max()) <= 0.05,
                 f"a served token lies {gaps.max():.3g} below the "
                 "reference's first (bfloat16 against float32: 0.05 allowed)")


# published widths of the window + global model's caches: 64 query heads of
# 192 over 8 (window) and 4 (global) cached heads, values 128, a window of 128
# as a ring of 2,560-value rows a slot, the global rows 1,280 in pages of 256
SWA = {"vocab_size": 4096, "vocab_first": 0, "hidden_size": 1024,
       "num_layers": 4, "layer_pattern": [0, 1, 1, 0],
       "moe_pattern": [0, 1, 1, 1], "num_heads": 64, "head_dim": 192,
       "v_head_dim": 128, "kv_heads": 4, "swa_kv_heads": 8, "window": 128,
       "swa_sink": True, "rotary_dim": 64, "rope_theta": 5000000,
       "swa_rope_theta": 10000, "value_scale": 0.707, "dense_width": 2048,
       "expert_width": 512, "router_experts": 32, "experts_first": 8,
       "experts_held": 8, "experts_per_token": 8, "routed_scale": 1.0,
       "rms_eps": 1e-5, "max_length": 4096}


def phase_swa():
    """Window rings beside the pool: a prompt past two pieces of 1,024
    against ``prefill`` whole — rows, rings, first token —, in memory filled
    with NaN first; then steps across a ring's wrap, the served tokens
    against the plain reference's full forward
    (``benchmark/reference_swa_moe.py``); and the two decode kernels against
    their XLA twins over what the engine wrote."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference_swa_moe as reference
    from mxnet_tpu.models import swa_moe
    from mxnet_tpu.ops import swa_attention
    from mxnet_tpu.serve import DecodeEngine
    from mxnet_tpu.serve.kvcache import SCRATCH_PAGE

    with _Phase("swa"):
        slots, page, n, padded, seed, slot = 4, 256, 2500, 3072, 11, 2
        _dirty_memory()
        model = swa_moe.SWAMoEDecodeModel(SWA, seed=seed)
        engine = DecodeEngine(model, slots=slots, page_size=page,
                              num_pages=slots * 16 + 1,
                              prompt_buckets=[1024, padded])
        engine.warmup()
        _require(engine.prefill_piece == 1024 and engine.paged_layers == 2
                 and engine.kv.shape == (slots * 16 + 1, 2, page, 1280)
                 and engine.state["window"].shape == (slots + 1, 2, 128, 2560)
                 and engine.stats()["num_programs"] == 2,
                 f"rings beside a 2-layer pool expected: {engine.stats()}")
        rng = np.random.RandomState(12)
        prompt = rng.randint(0, SWA["vocab_size"], n)
        engine.pool.alloc(0, padded // page)
        table = engine.pool.table(0)
        counted = {}
        for start in range(0, padded, engine.prefill_piece):
            tok, c = engine.read(engine.launch_prefill(prompt, table,
                                                       slot=slot, start=start))
            counted = {k: counted.get(k, 0) + v for k, v in c.items()}
        seen = np.arange(1, n + 1)
        _require(counted["moe.dropped"] == 0
                 and counted["moe.assignments"] == 3 * n * 8
                 and counted["attn.window_rows"] == 2 * np.minimum(
                     seen, 128).sum()
                 and counted["attn.global_rows"] == 2 * seen.sum(),
                 f"the pieces counted {counted}")
        # ... against the model's ``prefill``, the whole prompt at once
        whole = np.zeros((1, padded), np.int32)
        whole[0, :n] = prompt
        logits, want_rows, _, want_state = jax.jit(model.prefill)(
            model.params, jnp.asarray(whole), n)
        rows = np.asarray(engine.kv[np.asarray(table)], np.float32)
        rows = np.moveaxis(rows, 1, 0).reshape(rows.shape[1], padded, -1)
        _check_mostly_close(
            f"{n} tokens in pieces of 1024 against whole: pool rows",
            rows[:, :n], np.asarray(want_rows[:, :n], np.float32), 2e-2)
        _check_mostly_close(
            "the rings after the last piece against whole",
            np.asarray(engine.state["window"][slot], np.float32),
            np.asarray(want_state["window"], np.float32), 2e-2)
        _require(tok == int(jnp.argmax(logits)),
                 f"a prompt in pieces served {tok} first, prefill whole "
                 f"{int(jnp.argmax(logits))}")
        # 72 steps: ring index 2500 mod 128 = 68 runs past 127 into 0
        served = [tok]
        tables = np.full((slots, engine.max_pages), SCRATCH_PAGE, np.int32)
        tables[slot, :len(table)] = table
        z = np.zeros((slots,), np.int32)
        for i in range(72):
            pos, lengths, toks = z.copy(), z.copy(), z.copy()
            pos[slot], lengths[slot], toks[slot] = n + i, n + 1 + i, served[-1]
            served.append(int(engine.step(
                toks, pos, tables, lengths,
                np.zeros((slots,), np.float32))[slot]))
        c = engine.last_counters
        _require(c["moe.dropped"] == 0 and c["moe.assignments"] == 3 * 8
                 and c["attn.window_rows"] == 2 * 128
                 and c["attn.global_rows"] == 2 * (n + 72),
                 f"the last step counted {c}")
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        seq = np.concatenate([seq, np.zeros((-len(seq)) % 128, np.int32)])
        ref_logits = np.asarray(reference.logits(SWA, seed, seq))[
            n - 1:n + 72]
        gaps = reference.gaps_below_best(ref_logits, served)
        print(f"   73 served tokens across the ring's wrap: widest gap below "
              f"the reference's best {gaps.max():.4f}, "
              f"{int((gaps > 0).sum())} not its first", flush=True)
        _require(np.isfinite(ref_logits).all() and float(gaps.max()) <= 0.1,
                 f"a served token lies {gaps.max():.3g} below the "
                 "reference's first (bfloat16 against float32: 0.1 allowed)")
        # the kernels against their twins, over what the engine just wrote
        q = jax.random.normal(jax.random.PRNGKey(13), (slots, 8, 8, 192),
                              jnp.bfloat16)
        positions = jnp.asarray([0, 0, n + 71, 0], jnp.int32)
        sink = model.params["layers"][1]["sink"]
        args = (swa_attention._padded_query(q, 8, 192), engine.state["window"],
                jnp.ones((1,), jnp.int32), positions,
                swa_attention._sink_lanes(sink, 8, 8), 192 ** -0.5, 128)
        kernel = jax.jit(swa_attention._swa_decode, static_argnums=(5, 6, 7))
        _require_mosaic(kernel, *args, False)
        got = np.asarray(kernel(*args, False), np.float32)[slot]
        want = np.asarray(jax.jit(
            swa_attention._swa_decode_xla, static_argnums=(2, 5, 6))(
            q, engine.state["window"], 1, positions, sink.reshape(8, 8),
            192 ** -0.5, 128), np.float32)[slot]
        _check_close("window decode (8 x 8 x 192 over a wrapped ring)", got,
                     want, 3e-2)
        q = q.reshape(slots, 4, 16, 192)
        lengths = jnp.asarray([0, 0, n + 72, 0], jnp.int32)
        args = (swa_attention._padded_query(q, 4, 192), engine.kv,
                jnp.ones((1,), jnp.int32), jnp.asarray(tables), lengths,
                192 ** -0.5, 128)
        kernel = jax.jit(swa_attention._gqa_decode_dv,
                         static_argnums=(5, 6, 7))
        _require_mosaic(kernel, *args, False)
        got = np.asarray(kernel(*args, False), np.float32)[slot]
        want = np.asarray(jax.jit(
            swa_attention._gqa_decode_dv_xla, static_argnums=(2, 5, 6))(
            q, engine.kv, 1, jnp.asarray(tables), lengths, 192 ** -0.5, 128),
            np.float32)[slot]
        _check_close(f"paged decode, keys 192 over values 128, {n + 72} "
                     "positions", got, want, 3e-2)
        engine.pool.free(0)
        engine.pool.assert_baseline()
        program = engine.stats()["step_program"]
        ring = engine.state["window"].nbytes // (slots + 1) // 2
        print(f"   pool {engine.kv.shape}, rings "
              f"{engine.state['window'].shape}: step temp_bytes "
              f"{program['temp_bytes']}", flush=True)
        _require(program["temp_bytes"] < engine.kv.nbytes // 2,
                 f"the step allocates {program['temp_bytes']} bytes: it "
                 f"copies the pool or the rings (one ring {ring} bytes)")


def _channel_decay_rule_over_a_piece():
    """``kda_prefill`` at the published geometry — a 2,048-position piece of
    32 heads of 128 x 128, from a random state, a whole chunk at the -5
    bound among the others, the last 100 positions masked — through Mosaic,
    against the token-by-token recurrence and against the XLA chunked form:
    the kernel's error against the recurrence at most twice the XLA form's
    own, and what each form takes a layer from ``(S, H, d)`` arrays, the
    relayouts around the call included."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import kda

    s, h, d, dead = 2048, 32, 128, 100
    keys = jax.random.split(jax.random.PRNGKey(23), 6)
    q, k = (jax.random.normal(key, (s, h, d), jnp.float32)
            for key in keys[:2])
    q, k = (a / jnp.linalg.norm(a, axis=-1, keepdims=True) for a in (q, k))
    q = q * d ** -0.5
    v = jax.random.normal(keys[2], (s, h, d), jnp.float32)
    live = (jnp.arange(s) < s - dead)[:, None]
    g = -5.0 * jax.random.uniform(keys[3], (s, h, d)) ** 3
    g = jnp.where(live[..., None], g.at[640:704].set(-5.0), 0.0)
    beta = jnp.where(live, jax.random.uniform(keys[4], (s, h)), 0.0)
    state = jax.random.normal(keys[5], (h, d, d), jnp.float32)
    _chunked_forms_against_the_recurrence(
        "the channel-decay rule over a 2,048-position piece (32 x 128 x 128, "
        "a chunk at -5, 100 masked)", kda.kda_chunked, "kda_prefill",
        (q, k, v, g, beta, state),
        jax.jit(kda.kda_recurrent)(q, k, v, g, beta, state))


def phase_kda():
    """The one-token delta rule with a decay per key channel at the published
    head geometry (32 heads of 128 x 128, float32 state): the ``kda_decode``
    kernel through Mosaic and its XLA twin against the token-by-token
    recurrence at 2, 32 and 128 slots, a dead slot's state untouched, the
    other layer of the array untouched, in memory filled with NaN first;
    a 200-token prompt through the chunked form (log decays at the -5
    bound among them) against the recurrence; and a 2,048-position piece
    through the ``kda_prefill`` kernel
    (:func:`_channel_decay_rule_over_a_piece`). Printed and held."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import kda

    h, dk, dv, layers = 32, 128, 128, 2

    def unit(key, rows):
        x = jax.random.normal(key, (rows, h, dk))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    with _Phase("kda"):
        _dirty_memory()
        for slots in (2, 32, 128):
            keys = jax.random.split(jax.random.PRNGKey(slots), 7)
            states = jax.random.normal(keys[0], (slots + 1, layers, h, dk, dv))

            q, k = unit(keys[1], slots) * dk ** -0.5, unit(keys[2], slots)
            v = jax.random.normal(keys[3], (slots, h, dv))
            g = -5.0 * jax.random.uniform(keys[4], (slots, h, dk)) ** 3
            beta = jax.random.uniform(keys[5], (slots, h))
            live = jnp.arange(slots) % 5 != 1          # slot 1, 6, ... dead
            want_o, want_s = jax.vmap(
                lambda *a: kda.kda_recurrent(*(x[None] for x in a[:5]), a[5])
            )(q, k, v, g, beta, states[:slots, 1])
            want_s = jnp.where(live[:, None, None, None], want_s,
                               states[:slots, 1])
            before = np.asarray(states)
            for impl in ("pallas", "xla"):
                fn = jax.jit(lambda s, *a, impl=impl: kda.kda_step(
                    s, 1, *a, impl=impl), donate_argnums=0)
                if impl == "pallas":
                    _require_mosaic(fn, jnp.array(before), q, k, v, g, beta,
                                    live)
                o, after = fn(jnp.array(before), q, k, v, g, beta, live)
                alive = np.asarray(live)
                _check_close(f"kda_step [{impl}] at {slots} slots: outputs",
                             np.asarray(o)[alive],
                             np.asarray(want_o[:, 0])[alive], 1e-4)
                _check_close(f"kda_step [{impl}] at {slots} slots: states",
                             np.asarray(after[:slots, 1]), np.asarray(want_s),
                             1e-4)
                _require(np.array_equal(np.asarray(after[:slots, 0]),
                                        before[:slots, 0]),
                         f"kda_step [{impl}] wrote the other layer's state")
            if slots == 128:
                fn = jax.jit(lambda s, *a: kda.kda_step(s, 1, *a),
                             donate_argnums=0)
                s = jnp.array(before)
                _, s = fn(s, q, k, v, g, beta, live)
                s.block_until_ready()
                t0 = time.monotonic()
                for _ in range(30):
                    _, s = fn(s, q, k, v, g, beta, live)
                s.block_until_ready()
                ms = (time.monotonic() - t0) / 30 * 1e3
                moved = 2 * slots * h * dk * dv * 4
                print(f"   kda_decode at 128 slots: {ms:.3f} ms a layer for "
                      f"{moved / 1e6:.0f} MB of state both ways = "
                      f"{moved / ms / 1e6:.0f} GB/s", flush=True)
        # a prompt in chunks: a whole chunk at the bound, and a ragged end
        keys = jax.random.split(jax.random.PRNGKey(200), 6)
        n = 200
        q, k = unit(keys[0], n) * dk ** -0.5, unit(keys[1], n)
        v = jax.random.normal(keys[2], (n, h, dv))
        g = -5.0 * jax.random.uniform(keys[3], (n, h, dk)) ** 3
        g = g.at[64:128].set(-5.0)
        beta = jax.random.uniform(keys[4], (n, h))
        s0 = jax.random.normal(keys[5], (h, dk, dv))
        want_o, want_s = jax.jit(kda.kda_recurrent)(q, k, v, g, beta, s0)
        o, s1 = jax.jit(kda.kda_chunked)(q, k, v, g, beta, s0)
        _require(bool(jnp.all(jnp.isfinite(o)) and jnp.all(jnp.isfinite(s1))),
                 "the chunked rule is not finite at the -5 bound")
        _check_close("kda_chunked over 200 tokens: outputs", o, want_o, 1e-4)
        _check_close("kda_chunked over 200 tokens: state", s1, want_s, 1e-4)
        _channel_decay_rule_over_a_piece()


def _dirty_memory():
    """Fill what is free of the device's memory with NaN and free it again.
    A fresh process finds zeros where it never wrote; a long-lived one does
    not, and a program that reads what it did not write then reads NaN."""
    import jax
    import jax.numpy as jnp

    stats = jax.devices()[0].memory_stats() or {}
    free = stats.get("bytes_limit", 0) - stats.get("bytes_in_use", 0)
    if free > 0:
        jnp.full((int(free * 0.9) // 4,), jnp.nan,
                 jnp.float32).block_until_ready()
    print(f"   {free * 0.9 / 1e9:.1f} GB of free device memory filled with "
          "NaN and freed", flush=True)


def _step_text(engine):
    return engine._step_jit.lower(engine._params, engine.kv, engine.state,
                                  engine.last, engine.blank_step()).as_text()


# ---------------------------------------------------------------------------
# experts: the held experts' rows at the cells' own geometries
# ---------------------------------------------------------------------------

# cell: (k, hidden, expert width, experts held, experts the router scores,
# tokens of a prompt call, slots of its step, whether an expert has a gate
# matrix): the gated-delta cell's prompts go in pieces of 2,048, the
# latent-attention cell's 7,168 in chunks of 3,584; the state-space cell's
# experts are two products a row, 2688 x 1856 stored as 3072 x 2048 (whole
# tiles of 512, zeros behind), and its step has 128 slots
EXPERT_CELLS = {"gdn": (10, 2048, 512, 128, 512, 2048, 32, True),
                "latent": (8, 4096, 2048, 32, 128, 3584, 32, True),
                "ssm": (6, 2688, 1856, 32, 128, 2048, 128, False)}


def phase_experts():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import moe

    def masked_loop(h, chosen, gates, gate_w, up_w, down_w):
        """Every held expert over all tokens, weighed by its gate or 0
        (``gate_w`` None: experts of two products)."""
        def one(y, xs):
            i, *mats = xs
            w = jnp.sum(jnp.where(chosen == i, gates, 0.0), axis=1)
            mlp = moe.relu2_mlp if gate_w is None else moe.gated_mlp
            return y + w[:, None] * mlp(h, *mats), None
        mats = (up_w, down_w) if gate_w is None else (gate_w, up_w, down_w)
        y, _ = jax.lax.scan(one, jnp.zeros(h.shape, jnp.float32),
                            (jnp.arange(up_w.shape[0]),) + mats)
        return y

    def run(what, h, key, k, held, scored, stored, w):
        """``held_experts`` over random choices of ``k`` of ``scored``
        experts against the masked loop, its counters held to the tiles and
        what a call took printed: the relative error."""
        t = h.shape[0]
        picked, chosen = jax.lax.top_k(
            jax.random.uniform(key, (t, scored)), k)
        gates = picked / jnp.sum(picked, -1, keepdims=True)
        fn = jax.jit(lambda *a: moe.held_experts(*a, 0, held, 0, scored))
        args = (h, chosen, gates, jnp.ones((t,), bool), *stored)
        y, counted = fn(*args)
        _require(np.all(np.isfinite(np.asarray(y))), f"{what}: non-finite")
        err = _rel_err(y, jax.jit(masked_loop)(h, chosen, gates, *w))
        t0 = time.monotonic()
        for _ in range(10):
            out = fn(*args)
        out[0].block_until_ready()
        ms = (time.monotonic() - t0) * 100
        counted = dict(zip(moe.COUNTERS, np.asarray(counted).tolist()))
        _require(counted["dropped"] == 0, f"{what}: {counted}")
        slot = moe.row_slot(t * k, scored)
        print(f"   {what} ({t} x {k} pairs, {counted['held']} held on "
              f"{counted['touched']} experts): rel err {err:.2e}, row tile "
              f"{moe.row_tile(t * k, scored, h.dtype)}, slot {slot}, "
              f"rows_run {counted['rows_run']} / held = "
              f"{counted['rows_run'] / max(counted['held'], 1):.2f}, "
              f"{ms:.3f} ms a call", flush=True)
        # whole slots, and no expert more than a slot over its rows
        _require(counted["rows_run"] % slot == 0 and counted["held"]
                 <= counted["rows_run"]
                 < counted["held"] + counted["touched"] * slot,
                 f"{what}: the slots run do not follow the rows held")
        return err

    with _Phase("experts"):
        for cell, (k, d, f, held, scored, prompt, step,
                   gated) in EXPERT_CELLS.items():
            keys = jax.random.split(jax.random.PRNGKey(k), 5)
            w = [0.03 * jax.random.normal(key, shape, jnp.bfloat16)
                 for key, shape in zip(keys, ((held, d, f), (held, d, f),
                                              (held, f, d)))]
            stored = w
            if not gated:         # two matrices, stored in tiles of 512
                pd, pf = -d % 512, -f % 512
                stored = [None, jnp.pad(w[1], ((0, 0), (0, pd), (0, pf))),
                          jnp.pad(w[2], ((0, 0), (0, pf), (0, pd)))]
                w = [None] + w[1:]
            for what, t in ((f"{cell} prompt", prompt), (f"{cell} step", step)):
                h = jax.random.normal(keys[3], (t, d), jnp.bfloat16)
                err = run(what, h, keys[4], k, held, scored, stored, w)
                _require(err <= 2e-2, f"{what}: rel err {err:.3e} > 2e-2")
        # float32 operands with few rows a step (PERF.md section 7: the
        # grouped product read bfloat16-sized errors at 20 and 40 pairs):
        # printed, whatever it reads (every cell's operands are bfloat16)
        k, d, f, held, scored = EXPERT_CELLS["gdn"][:5]
        keys = jax.random.split(jax.random.PRNGKey(38), 5)
        w = [0.03 * jax.random.normal(key, shape, jnp.float32)
             for key, shape in zip(keys, ((held, d, f), (held, d, f),
                                          (held, f, d)))]
        for slots in (2, 4, 32):
            h = jax.random.normal(keys[3], (slots, d), jnp.float32)
            run(f"gdn step in float32, {slots} slots", h, keys[4], k, held,
                scored, w, w)


# ---------------------------------------------------------------------------

def main():
    import jax

    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    t0 = time.monotonic()
    device, cache = phase_device()
    phase_kernels()
    losses, train_attn = phase_train()
    decode_attn = phase_serve()
    phase_latent()
    phase_state()
    phase_ssm()
    phase_scmoe()
    phase_swa()
    phase_kda()
    phase_experts()
    multichip_attn = phase_multichip(losses[0])
    print("summary " + json.dumps({
        "phases": _Phase.results,
        "attention": {"train": train_attn, "decode": decode_attn,
                      **multichip_attn},
        "compile_cache": cache,
        "wall_seconds": round(time.monotonic() - t0, 1),
        "claim": None,
    }), flush=True)
    # the last line is the driver's contract: these two keys and no others
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    sys.exit(main())

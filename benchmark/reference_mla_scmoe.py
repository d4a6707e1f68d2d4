"""The plain reference of the ``mla_scmoe`` kind: a pre-norm decoder of
shortcut-connected double layers (ScMoE) over latent attention (MLA), with
identity ("zero-compute") experts, from the equations — LongCat-Flash's
language model as its report (arXiv:2509.01322) and its public
``modeling_longcat_flash.py`` describe it.

Embedding, then ``num_layers`` DOUBLE layers, a final RMSNorm and an untied
vocabulary matmul. ``N(x; g)`` is RMSNorm with gain g. Double layer, input x:

    a0 = x  + MLA_0( N(x;  g_in0) )
    h0 =      N(a0; g_post0)
    m  =      MoE(h0)                       # the shortcut: leaves here ...
    b0 = a0 + MLP_0(h0)
    a1 = b0 + MLA_1( N(b0; g_in1) )
    x' = a1 + MLP_1( N(a1; g_post1) ) + m   # ... and rejoins here

*MLA(h)*, H heads, d = hidden: ``cq = N(h.W_qa; g_q) . (d / q_rank)^0.5``;
``q = cq.W_qb`` -> per head ``q_nope || q_rope``; ``[c || k_r] = h.W_kva``;
``c <- N(c; g_kv) . (d / kv_rank)^0.5``; RoPE (theta as published, no
scaling, pairs ``(i, i + qk_rope / 2)``) on ``q_rope`` and on the one shared
``k_r``; ``k_nope_i = c.W_uk_i``, ``v_i = c.W_uv_i`` (``W_kvb`` is the
per-head blocks ``[W_uk_i || W_uv_i]`` side by side); causal softmax of
``(q_nope_i.k_nope_j + q_rope_i.k_r_j) (qk_nope + qk_rope)^-0.5``;
``concat_i(o_i).W_o``. The two scales (``mla_scale_q_lora``,
``mla_scale_kv_lora``) sit on the normed latents, hence on ``k_nope`` and
``v`` alike and not on ``k_r``. Only this *expanded* form is written here:
the program's absorbed decode form has to agree with it.

*MLP(h)* ``= (silu(h.W_g) * h.W_u).W_d`` at ``dense_width``.

*MoE(h)*: ``p = softmax(h.W_r)`` over ALL ``router_experts`` = the real
experts + ``zero_experts`` identity experts behind them; chosen = the
``experts_per_token`` largest of ``p + b`` (the bias chooses, never weighs);
``g_j = routed_scale . p_chosen_j``, NOT renormalised; ``m = sum_j g_j
E_{e_j}(h)`` where a real expert is the MLP at ``expert_width`` and an
identity expert is ``E_e(h) = h``. No shared expert. ``held = (first,
count)`` is the share of the real experts that lives here: what the absent
ones would add is left out (the ``model-configs`` guide, section 4), while
the identity experts' term is computed for every token on every chip.
Experts run as a plain loop over all tokens, masked.

Departures from the published model, each for the benchmark's sake: weights
are seeded (below), not trained; the audio / vision encoders and the codec
decoder of the Omni model are no part of this (the configuration is the
language model's); the share ``held`` above.

Float32, ``jax.default_matmul_precision("highest")``, no cache, no kernels,
one sequence at a time, attention blocked over heads and queries so that it
fits; one double layer's weights alive at a time. It imports nothing of
``mxnet_tpu``. Weights are made from the seed by the scheme below (the
program's ``models/mla_scmoe.py`` states the same scheme and makes the same
numbers on the device), rounded to bfloat16 once and then held in float32.

``precision="fp8"`` is the control: matmul operands rounded to e4m3 at a
per-tensor scale, one step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

# -- the seeded weights --------------------------------------------------------
# leaf = 0.02 N(0, 1) (norm gains 1 + that), in bfloat16 (``_normal_bf16``), with
# key = fold_in(fold_in(fold_in(PRNGKey(seed mod 2**31), seed // 2**31), LEAF
# index), double layer) — a sub-layer's leaves fold in the sub-layer (0, 1)
# too, expert leaves the expert's GLOBAL index, embedding and head a block of
# 8192 rows. Matrices are (in, out), but q_b_w (out, in). The router's choosing
# bias is its draw / router_experts: the probabilities of a softmax over 768
# lie near 1/768, and a bias of 0.02 would choose for every token alike.
LEAVES = ("embed", "head", "final_norm", "attn_norm", "q_a_w", "q_norm",
          "q_b_w", "kva_w", "kv_norm", "uk_w", "uv_w", "o_w", "mlp_norm",
          "gate_w", "up_w", "down_w", "router_w", "router_b",
          "experts_gate_w", "experts_up_w", "experts_down_w")
GAINS = ("final_norm", "attn_norm", "q_norm", "kv_norm", "mlp_norm")
SUB = ("attn_norm", "q_a_w", "q_norm", "q_b_w", "kva_w", "kv_norm", "uk_w",
       "uv_w", "o_w", "mlp_norm", "gate_w", "up_w", "down_w")
VOCAB_BLOCK = 8192


def base_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)


def leaf_shapes(m: dict) -> dict:
    """name -> shape of one leaf (one expert's, for ``experts_*``)."""
    d, h = m["hidden_size"], m["num_heads"]
    nope, rope, vd = m["qk_nope"], m["qk_rope"], m["v_head"]
    r, rq = m["kv_rank"], m["q_rank"]
    f, fe, e = m["dense_width"], m["expert_width"], m["router_experts"]
    return {"final_norm": (d,), "attn_norm": (d,), "q_a_w": (d, rq),
            "q_norm": (rq,), "q_b_w": (h * (nope + rope), rq),
            "kva_w": (d, r + rope), "kv_norm": (r,), "uk_w": (h, nope, r),
            "uv_w": (h, r, vd), "o_w": (h * vd, d), "mlp_norm": (d,),
            "gate_w": (d, f), "up_w": (d, f), "down_w": (f, d),
            "router_w": (d, e), "router_b": (e,), "experts_gate_w": (d, fe),
            "experts_up_w": (d, fe), "experts_down_w": (fe, d)}


@functools.partial(jax.jit, static_argnums=1)
def _normal_bf16(key, shape):
    """0.02 N(0, 1), to bfloat16, from integers alone: the twelve bytes of
    three random words summed (Irwin-Hall, mean 1530, variance 65535), one
    float32 multiply, one rounding. Exact in any program that computes it —
    a float32 ``normal`` rounds differently by how it was fused."""
    words = jax.random.bits(key, (3,) + tuple(shape), jnp.uint32)
    total = sum((words >> s) & 0xFF for s in (0, 8, 16, 24)).sum(axis=0)
    x = (total.astype(jnp.int32) - 1530).astype(jnp.float32)
    return (x * np.float32(0.02 / 65535 ** 0.5)).astype(jnp.bfloat16)


def _draw(key, name, shape, *path):
    key = jax.random.fold_in(key, LEAVES.index(name))
    for i in path:
        key = jax.random.fold_in(key, i)
    x = _normal_bf16(key, shape)
    if name in GAINS:
        x = (1.0 + x.astype(jnp.float32)).astype(jnp.bfloat16)
    if name == "router_b":      # in units of the mean probability, 1 / E_all
        x = (x.astype(jnp.float32) / shape[0]).astype(jnp.bfloat16)
    return x.astype(jnp.float32)


def vocab_weights(m: dict, seed: int, name: str):
    """``embed`` or ``head``, (vocab, hidden), drawn 8192 rows at a time."""
    key, v, d = base_key(seed), m["vocab_size"], m["hidden_size"]
    blocks = [_draw(key, name, (min(VOCAB_BLOCK, v - r), d), r // VOCAB_BLOCK)
              for r in range(0, v, VOCAB_BLOCK)]
    return jnp.concatenate(blocks)


def layer_weights(m: dict, seed: int, layer: int, held=None) -> dict:
    """One double layer's weights in float32 (bfloat16 values): ``sub`` (the
    two sub-layers' leaves, a list of two dicts), the router and the held
    real experts. ``held = (first, count)``; default the configuration's."""
    key, shapes = base_key(seed), leaf_shapes(m)
    first, count = held or (m["experts_first"], m["experts_held"])
    w = {"sub": [{n: _draw(key, n, shapes[n], layer, i) for n in SUB}
                 for i in (0, 1)]}
    for n in ("router_w", "router_b"):
        w[n] = _draw(key, n, shapes[n], layer)
    for n in ("experts_gate_w", "experts_up_w", "experts_down_w"):
        w[n] = jnp.stack([_draw(key, n, shapes[n], layer, e)
                          for e in range(first, first + count)])
    return w


# -- the equations -------------------------------------------------------------

def _fp8(x):
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _mm(spec, a, b, precision):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision="highest",
                      preferred_element_type=jnp.float32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def inv_freq(m: dict):
    """Plain RoPE over the ``qk_rope``-wide slice: no ``rope_scaling``."""
    dim = m["qk_rope"]
    return (1.0 / m["rope"]["theta"] ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32)


def softmax_scale(m: dict) -> float:
    return (m["qk_nope"] + m["qk_rope"]) ** -0.5


def rotate(x, positions, freq):
    """RoPE of x (S, ..., dim) at ``positions`` (S,): pairs (i, i + dim/2)."""
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    angle = angle.reshape(angle.shape[:1] + (1,) * (x.ndim - 2) + angle.shape[1:])
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(m, w, h, precision, head_block=8, query_block=1024):
    """Expanded MLA over one sequence h (S, hidden) -> (S, hidden), ``w`` one
    sub-layer's leaves."""
    s, d = h.shape
    heads, nope, rope = m["num_heads"], m["qk_nope"], m["qk_rope"]
    rank, freq, eps = m["kv_rank"], jnp.asarray(inv_freq(m)), m["rms_eps"]
    scaled = m["latent_scales"]
    pos = jnp.arange(s)
    cq = rms_norm(_mm("sd,dr->sr", h, w["q_a_w"], precision), w["q_norm"], eps)
    if scaled:
        cq = cq * (d / m["q_rank"]) ** 0.5
    q = _mm("sr,er->se", cq, w["q_b_w"], precision).reshape(s, heads,
                                                            nope + rope)
    q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], pos, freq)
    kva = _mm("sd,de->se", h, w["kva_w"], precision)
    c = rms_norm(kva[:, :rank], w["kv_norm"], eps)
    if scaled:
        c = c * (d / rank) ** 0.5
    k_r = rotate(kva[:, rank:], pos, freq)                      # (S, rope)
    k_nope = _mm("sc,hnc->shn", c, w["uk_w"], precision)
    v = _mm("sc,hcv->shv", c, w["uv_w"], precision)
    scale = softmax_scale(m)
    hb, qb = min(head_block, heads), min(query_block, s)
    assert heads % hb == 0 and s % qb == 0, (heads, hb, s, qb)

    def head_group(g):           # g: (q_nope, q_rope, k_nope, v) of hb heads
        qn, qr, kn, vv = g

        def query_block_(start):
            rows = start + jnp.arange(qb)
            qn_b = jax.lax.dynamic_slice_in_dim(qn, start, qb, 0)
            qr_b = jax.lax.dynamic_slice_in_dim(qr, start, qb, 0)
            sc = scale * (_mm("qhn,khn->hqk", qn_b, kn, precision)
                          + _mm("qhr,kr->hqk", qr_b, k_r, precision))
            sc = jnp.where(rows[None, :, None] >= pos[None, None, :], sc,
                           -jnp.inf)
            return _mm("hqk,khv->qhv", jax.nn.softmax(sc, axis=-1), vv,
                       precision)

        out = jax.lax.map(query_block_, jnp.arange(0, s, qb))
        return out.reshape(s, hb, -1)

    def groups(x):               # (S, H, n) -> (H/hb, S, hb, n)
        return jnp.moveaxis(x.reshape(s, heads // hb, hb, -1), 1, 0)

    o = jax.lax.map(head_group, (groups(q_nope), groups(q_rope),
                                 groups(k_nope), groups(v)))
    o = jnp.moveaxis(o, 0, 1).reshape(s, -1)
    return _mm("se,ed->sd", o, w["o_w"], precision)


def gated_mlp(h, gate, up, down, precision):
    a = jax.nn.silu(_mm("sd,df->sf", h, gate, precision))
    return _mm("sf,fd->sd", a * _mm("sd,df->sf", h, up, precision), down,
               precision)


def route(m, w, h, precision):
    """(p (S, E_all), chosen (S, k) expert ids, gates (S, k))."""
    p = jax.nn.softmax(_mm("sd,de->se", h, w["router_w"], precision), axis=-1)
    _, chosen = jax.lax.top_k(p + w["router_b"], m["experts_per_token"])
    gates = m["routed_scale"] * jnp.take_along_axis(p, chosen, axis=1)
    return p, chosen, gates


def expert_branch(m, w, h, precision, held=None, zero=True):
    """The routed sum over the held real experts — a plain loop, every expert
    over all tokens, masked — plus (``zero``) the identity experts' term."""
    first, count = held or (m["experts_first"], m["experts_held"])
    _, chosen, gates = route(m, w, h, precision)

    def one_expert(y, xs):
        i, gate, up, down = xs
        g = jnp.sum(jnp.where(chosen == first + i, gates, 0.0), axis=1)
        return y + g[:, None] * gated_mlp(h, gate, up, down, precision), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        jnp.arange(count), w["experts_gate_w"], w["experts_up_w"],
        w["experts_down_w"]))
    if zero:
        real = m["router_experts"] - m["zero_experts"]
        y = y + jnp.sum(jnp.where(chosen >= real, gates, 0.0), axis=1,
                        keepdims=True) * h
    return y


@functools.partial(jax.jit, static_argnames=("m_json", "precision", "held"))
def _layer(w, x, *, m_json, precision, held):
    m = json.loads(m_json)
    eps = m["rms_eps"]
    first, second = w["sub"]

    def mlp(s, h):
        return gated_mlp(h, s["gate_w"], s["up_w"], s["down_w"], precision)

    with jax.default_matmul_precision("highest"):
        a0 = x + attention(m, first, rms_norm(x, first["attn_norm"], eps),
                           precision)
        h0 = rms_norm(a0, first["mlp_norm"], eps)
        shortcut = expert_branch(m, w, h0, precision, held)
        b0 = a0 + mlp(first, h0)
        a1 = b0 + attention(m, second, rms_norm(b0, second["attn_norm"], eps),
                            precision)
        return a1 + mlp(second, rms_norm(a1, second["mlp_norm"], eps)) + shortcut


def layer_forward(m, w, x, precision="f32", held=None):
    """One double layer over one sequence x (S, hidden), float32."""
    return _layer(w, x, m_json=json.dumps(m, sort_keys=True),   # hashable
                  precision=precision, held=held)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, gain, head, *, eps, precision):
    with jax.default_matmul_precision("highest"):
        return _mm("sd,vd->sv", rms_norm(x, gain, eps), head, precision)


def logits(m, seed, tokens, precision="f32", held=None):
    """All logits (S, vocab) of one sequence: the whole model, one double
    layer's weights alive at a time. For the CPU tests and small sizes."""
    return logits_many(m, seed, [np.asarray(tokens)], precision, held)[0]


def logits_many(m, seed, sequences, precision="f32", held=None, rows=None,
                log=None):
    """The logits of several sequences, double layer by double layer: one's
    weights are regenerated from the seed, every sequence goes through it,
    and they are dropped. ``rows[i]`` (optional) = the positions of sequence
    i whose logits are wanted (all by default)."""
    embed = vocab_weights(m, seed, "embed")
    xs = [embed[jnp.asarray(t, jnp.int32)] for t in sequences]
    del embed
    for layer in range(m["num_layers"]):
        t = time.monotonic()
        w = layer_weights(m, seed, layer, held)
        xs = [layer_forward(m, w, x, precision, held) for x in xs]
        jax.block_until_ready(xs)
        del w
        if log:
            log(f"reference ({precision}) double layer {layer}: "
                f"{time.monotonic() - t:.1f}s for {len(xs)} sequences")
    head = vocab_weights(m, seed, "head")
    gain = _draw(base_key(seed), "final_norm", (m["hidden_size"],))
    if rows is None:
        rows = [np.arange(len(x)) for x in xs]
    out = []
    for x, r in zip(xs, rows):       # rows padded: a few shapes, not one each
        padded = np.zeros((pad_to(len(r), 256),), np.int32)
        padded[:len(r)] = r
        out.append(_head(x[jnp.asarray(padded)], gain, head,
                         eps=m["rms_eps"], precision=precision)[:len(r)])
    return out


def pad_to(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def served_logits(m, seed, records, precision="f32", pad=1024, held=None,
                  log=None):
    """For each record (``prompt``, ``tokens`` served after it) the
    reference's logits at every served position, (n_served, vocab) float32
    on the device: one teacher-forced forward over prompt + served, padded
    to a multiple of ``pad`` (causal: the pad is never seen; two lengths,
    so two programs)."""
    seqs, rows = [], []
    for r in records:
        n, k = len(r["prompt"]), len(r["tokens"])
        seq = np.zeros((min(pad_to(n + k - 1, pad), m["max_length"]),), np.int32)
        seq[:n] = r["prompt"]
        seq[n:n + k - 1] = r["tokens"][:-1]
        seqs.append(seq)
        rows.append(np.arange(n - 1, n - 1 + k))
    return logits_many(m, seed, seqs, precision, held, rows, log)


def gaps_below_best(logits, judged) -> np.ndarray:
    """How far the logit of ``judged[i]`` lies below the largest logit at
    position i, float64."""
    lg = np.asarray(logits)
    return (lg.max(axis=1) - lg[np.arange(len(judged)), np.asarray(judged)]
            ).astype(np.float64)

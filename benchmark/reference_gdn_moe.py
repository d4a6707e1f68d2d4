"""The plain reference of the ``gdn_moe`` kind (Qwen3-Next): a pre-norm
decoder whose layers alternate gated delta-rule (linear) attention and gated
full attention, every MLP an expert layer — from the equations.

``h`` is ``hidden_size`` wide. **Norm**: ``RMSNorm0(x) = x rsqrt(mean(x^2) +
eps) (1 + w)`` (zero-centred gain): the two norms of every block, the final
norm, and per head on q and k of the full layers. **Block**: ``x +=
mixer(RMSNorm0(x)); x += moe(RMSNorm0(x))``. Layer ``i`` is a full-attention
layer when ``(i + 1) % full_interval == 0``, else a gated-delta layer.

*Full attention* (H query heads, KV cached heads, head D): ``W_q: hidden -> H
x 2D``, per head split into a query (D) and a gate (D); ``W_k, W_v: hidden ->
KV x D``; no biases. ``q <- RMSNorm0(q)``, ``k <- RMSNorm0(k)`` over the D.
RoPE on the first ``rotary_dim`` dimensions of each head, pairs ``(j, j +
rotary_dim / 2)``, theta ``rope_theta``, no scaling; the rest pass through.
Causal softmax, scale ``D^-0.5``, query head ``n`` reads cached head ``n //
(H / KV)``. ``o = concat_heads(attn) * sigmoid(gate)``, ``out = o . W_o``.

*Gated delta layer* (HK key heads and HV value heads of ``dk`` / ``dv``):
projections of ``h`` to ``q`` (HK dk), ``k`` (HK dk), ``v`` (HV dv), ``z`` (HV
dv), ``b`` (HV), ``a`` (HV) — published as two fused matrices
(``in_proj_qkvz``, ``in_proj_ba``); separate leaves here, the same function
of seeded weights. ``[q || k || v]`` goes through a causal depthwise
convolution of width ``conv_width`` (no bias; ``out_t = sum_j w_j x_(t - W + 1
+ j)``: the last row of ``conv_w`` weighs the current input), then SiLU.
``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)`` per value
head. ``q, k <- x / sqrt(sum x^2 + 1e-6)`` over the dk; value head ``n`` uses
key head ``n // (HV / HK)``; ``q <- q dk^-0.5``. Per value head, with state
``S`` (dk x dv), for each token: ``S <- exp(g_t) S; r = S^T k_t; d = beta_t
(v_t - r); S <- S + k_t d^T; o_t = S^T q_t``. Then ``y = w_n (o /
sqrt(mean(o^2) + eps)) SiLU(z)`` over each head's dv (plain gain ``w_n``, not
``1 + w``), and ``out = y . W_out``.

*Expert layer*: ``p = softmax(h . W_r)`` over ALL ``router_experts``; the
``experts_per_token`` largest; weights ``p_e / sum_chosen p`` (``norm_topk_
prob``); no bias, no scaling factor. Expert ``e``: ``(SiLU(h W_g) * h W_u)
W_d``. Shared expert: the same MLP times ``sigmoid(h . w_s)``. ``y =
sum_{chosen and held} weight_e expert_e(h) + sigmoid(h w_s) shared(h)``;
``held = (first, count)`` is the share of the experts that lives here: what
the absent ones would add is left out (the ``model-configs`` guide, section
4). Untied head over the held slice of the vocabulary.

Departures from the release, each under ``assumed`` in the configuration's
file too: the multi-token-prediction module is left out; the fused input
projections are separate leaves; ``A_log`` takes one of 256 values.

Float32, ``jax.default_matmul_precision("highest")``, no cache, no kernels,
the delta rule as the token-by-token recurrence under ``lax.scan``, one
sequence at a time, attention blocked over queries so that a 16 k prompt
fits, one layer's weights alive at a time. It imports nothing of
``mxnet_tpu``. Weights are made from the seed by the scheme below (the
program's ``models/gdn_moe.py`` states the same scheme and makes the same
numbers on the device), rounded to bfloat16 once and held in float32.

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
# leaf = 0.02 N(0, 1) in bfloat16 (``_normal_bf16``); the gains of RMSNorm0 are
# that (the norm adds the 1), the gated norm's gain and dt_bias are 1 + that;
# A_log = log u with u one of 256 even steps of [1, 16], picked by a random
# byte from a table made on the host (a device's log rounds by how it was
# fused). key = fold_in(fold_in(fold_in(PRNGKey(seed mod 2**31), seed // 2**31),
# LEAF index), layer) — expert leaves fold in the expert's GLOBAL index too
# and draw one expert at a time, embedding and head a whole block of 8192 rows
# of the published table (the rows held are a slice of those).
# Matrices are (in, out).
LEAVES = ("embed", "head", "final_norm", "attn_norm", "mlp_norm", "router_w",
          "shared_gate_w", "shared_up_w", "shared_down_w", "shared_s_w",
          "experts_gate_w", "experts_up_w", "experts_down_w",
          "q_w", "k_w", "v_w", "q_norm", "k_norm", "o_w",
          "dq_w", "dk_w", "dv_w", "dz_w", "db_w", "da_w", "conv_w", "A_log",
          "dt_bias", "gnorm", "out_w")
ONE_PLUS = ("gnorm", "dt_bias")
COMMON = ("attn_norm", "mlp_norm", "router_w", "shared_gate_w", "shared_up_w",
          "shared_down_w", "shared_s_w")
FULL = ("q_w", "k_w", "v_w", "q_norm", "k_norm", "o_w")
DELTA = ("dq_w", "dk_w", "dv_w", "dz_w", "db_w", "da_w", "conv_w", "A_log",
         "dt_bias", "gnorm", "out_w")
EXPERTS = ("experts_gate_w", "experts_up_w", "experts_down_w")
VOCAB_BLOCK = 8192
A_LOG_TABLE = np.log(1.0 + np.arange(256) * (15.0 / 255.0)).astype(np.float32)


def base_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)


def is_full(m: dict, layer: int) -> bool:
    return (layer + 1) % m["full_interval"] == 0


def leaf_shapes(m: dict) -> dict:
    """name -> shape of one layer's leaf (one expert's, for ``experts_*``)."""
    d, fe, e = m["hidden_size"], m["expert_width"], m["router_experts"]
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    hk, hv, dk, dv = (m["linear_key_heads"], m["linear_value_heads"],
                      m["linear_key_dim"], m["linear_value_dim"])
    return {"final_norm": (d,), "attn_norm": (d,), "mlp_norm": (d,),
            "router_w": (d, e), "shared_gate_w": (d, fe),
            "shared_up_w": (d, fe), "shared_down_w": (fe, d),
            "shared_s_w": (d,), "experts_gate_w": (d, fe),
            "experts_up_w": (d, fe), "experts_down_w": (fe, d),
            "q_w": (d, h * 2 * hd), "k_w": (d, kv * hd), "v_w": (d, kv * hd),
            "q_norm": (hd,), "k_norm": (hd,), "o_w": (h * hd, d),
            "dq_w": (d, hk * dk), "dk_w": (d, hk * dk), "dv_w": (d, hv * dv),
            "dz_w": (d, hv * dv), "db_w": (d, hv), "da_w": (d, hv),
            "conv_w": (m["conv_width"], 2 * hk * dk + hv * dv),
            "A_log": (hv,), "dt_bias": (hv,), "gnorm": (dv,),
            "out_w": (hv * dv, d)}


@functools.partial(jax.jit, static_argnums=1)
def _normal_bf16(key, shape):
    """0.02 N(0, 1), to bfloat16, from integers alone: the twelve bytes of
    three random words summed (Irwin-Hall, mean 1530, variance 65535), one
    float32 multiply, one rounding. Exact in any program that computes it."""
    words = jax.random.bits(key, (3,) + tuple(shape), jnp.uint32)
    total = sum((words >> s) & 0xFF for s in (0, 8, 16, 24)).sum(axis=0)
    x = (total.astype(jnp.int32) - 1530).astype(jnp.float32)
    return (x * np.float32(0.02 / 65535 ** 0.5)).astype(jnp.bfloat16)


def _draw(key, name, shape, *path):
    key = jax.random.fold_in(key, LEAVES.index(name))
    for i in path:
        key = jax.random.fold_in(key, i)
    if name == "A_log":
        byte = jax.random.bits(key, tuple(shape), jnp.uint32) & 0xFF
        return jnp.asarray(A_LOG_TABLE)[byte]
    x = _normal_bf16(key, shape)
    if name in ONE_PLUS:
        x = (1.0 + x.astype(jnp.float32)).astype(jnp.bfloat16)
    return x.astype(jnp.float32)


def vocab_weights(m: dict, seed: int, name: str):
    """``embed`` or ``head``, (vocab, hidden): rows ``vocab_first .. +
    vocab_size`` of the published table, which is drawn in whole blocks of
    8192 rows (a slice of the vocabulary holds the rows the uncut model
    has there)."""
    key, v, d = base_key(seed), m["vocab_size"], m["hidden_size"]
    first = m.get("vocab_first", 0)
    blocks = range(first // VOCAB_BLOCK, -(-(first + v) // VOCAB_BLOCK))
    table = jnp.concatenate([_draw(key, name, (VOCAB_BLOCK, d), b)
                             for b in blocks])
    start = first - blocks[0] * VOCAB_BLOCK
    return table[start:start + v]


def layer_weights(m: dict, seed: int, layer: int, held=None) -> dict:
    """One layer's weights in float32 (bfloat16 values; ``A_log`` float32).
    ``held = (first, count)`` of the routed experts; default the
    configuration's."""
    key, shapes = base_key(seed), leaf_shapes(m)
    first, count = held or (m["experts_first"], m["experts_held"])
    names = COMMON + (FULL if is_full(m, layer) else DELTA)
    w = {n: _draw(key, n, shapes[n], layer) for n in names}
    for n in EXPERTS:
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


def rms_norm0(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def rotate(x, positions, theta):
    """RoPE of x (S, ..., dim) at ``positions`` (S,): pairs (j, j + dim/2)."""
    dim = x.shape[-1]
    inv_freq = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    angle = (positions.astype(jnp.float32)[:, None]
             * jnp.asarray(inv_freq, jnp.float32)[None, :])
    angle = angle.reshape(angle.shape[:1] + (1,) * (x.ndim - 2) + angle.shape[1:])
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def full_attention(m, w, h, precision, query_block=1024):
    """Gated grouped-KV attention over one sequence h (S, hidden)."""
    s = h.shape[0]
    heads, kv, d = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    rot, eps, pos = m["rotary_dim"], m["rms_eps"], jnp.arange(s)
    qg = _mm("sd,de->se", h, w["q_w"], precision).reshape(s, heads, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = _mm("sd,de->se", h, w["k_w"], precision).reshape(s, kv, d)
    v = _mm("sd,de->se", h, w["v_w"], precision).reshape(s, kv, d)
    q, k = rms_norm0(q, w["q_norm"], eps), rms_norm0(k, w["k_norm"], eps)

    def rope(x):
        return jnp.concatenate(
            [rotate(x[..., :rot], pos, m["rope_theta"]), x[..., rot:]], axis=-1)

    q = rope(q).reshape(s, kv, heads // kv, d)      # head n = [n // G, n % G]
    k = rope(k)
    qb = min(query_block, s)
    assert s % qb == 0, (s, qb)

    def block(start):
        rows = start + jnp.arange(qb)
        q_b = jax.lax.dynamic_slice_in_dim(q, start, qb, 0)
        sc = d ** -0.5 * _mm("qhgd,khd->hgqk", q_b, k, precision)
        sc = jnp.where(rows[None, None, :, None] >= pos[None, None, None, :],
                       sc, -jnp.inf)
        return _mm("hgqk,khd->qhgd", jax.nn.softmax(sc, axis=-1), v, precision)

    o = jax.lax.map(block, jnp.arange(0, s, qb)).reshape(s, heads * d)
    o = o * jax.nn.sigmoid(gate.reshape(s, heads * d))
    return _mm("se,ed->sd", o, w["o_w"], precision)


def delta_attention(m, w, h, precision):
    """The gated delta layer over one sequence h (S, hidden), the recurrence
    token by token."""
    s = h.shape[0]
    hk, hv = m["linear_key_heads"], m["linear_value_heads"]
    dk, dv, width = m["linear_key_dim"], m["linear_value_dim"], m["conv_width"]
    x = jnp.concatenate([_mm("sd,de->se", h, w[n], precision)
                         for n in ("dq_w", "dk_w", "dv_w")], axis=1)
    z = _mm("sd,de->se", h, w["dz_w"], precision).reshape(s, hv, dv)
    b = _mm("sd,de->se", h, w["db_w"], precision)
    a = _mm("sd,de->se", h, w["da_w"], precision)
    padded = jnp.concatenate([jnp.zeros((width - 1, x.shape[1])), x])
    x = jax.nn.silu(sum(w["conv_w"][j] * padded[j:j + s] for j in range(width)))
    q = x[:, :hk * dk].reshape(s, hk, dk)
    k = x[:, hk * dk:2 * hk * dk].reshape(s, hk, dk)
    v = x[:, 2 * hk * dk:].reshape(s, hv, dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(a + w["dt_bias"])

    def l2(t):
        return t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2(q) * dk ** -0.5, hv // hk, axis=1)    # value head n
    k = jnp.repeat(l2(k), hv // hk, axis=1)                 # uses key n // r

    def token(state, xs):
        qt, kt, vt, gt, bt = xs
        state = state * jnp.exp(gt)[:, None, None]
        r = jnp.einsum("hkv,hk->hv", state, kt, precision="highest")
        dlt = bt[:, None] * (vt - r)
        state = state + kt[:, :, None] * dlt[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, qt, precision="highest")

    _, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv)), (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + m["rms_eps"])
    y = (w["gnorm"] * o * jax.nn.silu(z)).reshape(s, hv * dv)
    return _mm("se,ed->sd", y, w["out_w"], precision)


def gated_mlp(h, gate, up, down, precision):
    a = jax.nn.silu(_mm("sd,df->sf", h, gate, precision))
    return _mm("sf,fd->sd", a * _mm("sd,df->sf", h, up, precision), down,
               precision)


def route(m, w, h, precision):
    """(p (S, E), chosen (S, k) expert ids, weights (S, k))."""
    p = jax.nn.softmax(_mm("sd,de->se", h, w["router_w"], precision), axis=-1)
    picked, chosen = jax.lax.top_k(p, m["experts_per_token"])
    return p, chosen, picked / jnp.sum(picked, -1, keepdims=True)


def expert_layer(m, w, h, precision, held=None, shared=True):
    """The routed part of the held experts, plus (``shared``) the gated shared
    expert: a plain loop, every expert over all tokens, masked."""
    first, count = held or (m["experts_first"], m["experts_held"])
    _, chosen, gates = route(m, w, h, precision)

    def one_expert(y, xs):
        i, gate, up, down = xs
        g = jnp.sum(jnp.where(chosen == first + i, gates, 0.0), axis=1)
        return y + g[:, None] * gated_mlp(h, gate, up, down, precision), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        jnp.arange(count), w["experts_gate_w"], w["experts_up_w"],
        w["experts_down_w"]))
    if shared:
        s = jax.nn.sigmoid(_mm("sd,d->s", h, w["shared_s_w"], precision))
        y = y + s[:, None] * gated_mlp(h, w["shared_gate_w"], w["shared_up_w"],
                                       w["shared_down_w"], precision)
    return y


@functools.partial(jax.jit, static_argnames=("m_json", "full", "precision",
                                             "held"))
def _layer(w, x, *, m_json, full, precision, held):
    m = json.loads(m_json)
    with jax.default_matmul_precision("highest"):
        h = rms_norm0(x, w["attn_norm"], m["rms_eps"])
        mixer = full_attention if full else delta_attention
        x = x + mixer(m, w, h, precision)
        h = rms_norm0(x, w["mlp_norm"], m["rms_eps"])
        return x + expert_layer(m, w, h, precision, held)


def layer_forward(m, w, x, layer, precision="f32", held=None):
    """One layer over one sequence x (S, hidden), float32."""
    return _layer(w, x, m_json=json.dumps(m, sort_keys=True),   # hashable
                  full=is_full(m, layer), precision=precision, held=held)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, gain, head, *, eps, precision):
    with jax.default_matmul_precision("highest"):
        return _mm("sd,vd->sv", rms_norm0(x, gain, eps), head, precision)


def logits(m, seed, tokens, precision="f32", held=None):
    """All logits (S, vocab) of one sequence: the whole model, one layer's
    weights alive at a time. For the CPU tests and small sizes."""
    return logits_many(m, seed, [np.asarray(tokens)], precision, held)[0]


def logits_many(m, seed, sequences, precision="f32", held=None, rows=None,
                log=None):
    """The logits of several sequences, layer by layer: one layer's weights
    are regenerated from the seed, every sequence goes through it, and they
    are dropped. ``rows[i]`` (optional) = the positions of sequence i whose
    logits are wanted (all by default)."""
    embed = vocab_weights(m, seed, "embed")
    xs = [embed[jnp.asarray(t, jnp.int32)] for t in sequences]
    del embed
    for layer in range(m["num_layers"]):
        t = time.monotonic()
        w = layer_weights(m, seed, layer, held)
        xs = [layer_forward(m, w, x, layer, precision, held) for x in xs]
        jax.block_until_ready(xs)
        del w
        if log:
            log(f"reference ({precision}) layer {layer}: "
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


def served_logits(m, seed, records, precision="f32", pad=4096, held=None,
                  log=None):
    """For each record (``prompt``, ``tokens`` served after it) the
    reference's logits at every served position, (n_served, vocab) float32
    on the device: one teacher-forced forward over prompt + served, padded
    to a multiple of ``pad`` (causal, and a recurrence runs forward: the pad
    is never seen; a few lengths, so a few programs a kind of layer)."""
    seqs, rows = [], []
    for r in records:
        n, k = len(r["prompt"]), len(r["tokens"])
        seq = np.zeros((min(pad_to(n + k - 1, pad),
                            pad_to(m["max_length"], 64)),), np.int32)
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

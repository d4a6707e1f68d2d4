"""The plain reference of the ``kda_mla_moe`` kind (the language model of
Ling-3.0-flash-VL): a pre-norm decoder whose layers are Kimi delta attention
(KDA) with a latent-attention (MLA) layer closing every group of
``group_size``, ``first_dense`` leading dense MLPs and group-limited routed
experts after them — from the equations.

``h`` is ``hidden_size`` wide. **Norm**: ``RMSNorm(x) = x rsqrt(mean(x^2) +
eps) w``. **Block**: ``x += mixer(RMSNorm(x)); x += mlp(RMSNorm(x))``; a final
RMSNorm and an untied vocabulary matmul. ``layers`` lists the layers held by
their index ``l`` in the whole model: layer ``l`` is an MLA layer where ``(l +
1) % group_size == 0``, else a KDA layer; its MLP is dense where ``l <
first_dense``, else an expert layer.

*KDA layer*, H heads of ``dk = kda_key_dim`` keys and ``dv = kda_value_dim``
values: ``q~, k~, v~ = h W_q, h W_k, h W_v`` (H dk, H dk, H dv), each through
a causal depthwise convolution of width ``conv_width`` (no bias; ``out_t =
sum_j w_j x_(t - W + 1 + j)``: the last row of ``conv_w`` weighs the current
input) and SiLU; per head ``q = l2(q~) dk^-0.5``, ``k = l2(k~)``, ``l2(x) = x
/ sqrt(sum x^2 + 1e-6)``. Log decay per head AND key channel: ``g =
gate_lower_bound . sigmoid(exp(A_log_h) (h W_a + dt_bias))`` in
(``gate_lower_bound``, 0) — the open KDA kernels' "safe gate" —, ``W_a:
hidden -> H dk`` full rank, ``A_log`` (H,), ``dt_bias`` (H dk,). Write
strength ``beta = sigmoid(h W_b)`` per head. Per head, with state ``S`` (dk x
dv), for each token: ``S <- Diag(exp g_t) S; r = S^T k_t; d = beta_t (v_t -
r); S <- S + k_t d^T; o_t = S^T q_t``. Then ``y = w_n (o / sqrt(mean(o^2) +
eps)) sigmoid(h W_g)`` over each head's dv (``W_g: hidden -> H dv`` full
rank), and ``out = y W_o``.

*MLA layer*, H heads, no query latent: ``q = h W_q`` -> per head ``q_nope``
(``qk_nope``) ``|| q_rope`` (``qk_rope``); ``[c || k_r] = h W_kva``
(``kv_rank`` + ``qk_rope``); ``c <- RMSNorm(c)``; RoPE (theta ``rope.theta``,
unscaled, pairs ``(i, i + qk_rope / 2)``) on ``q_rope`` and on the one ``k_r``
shared by all heads; ``k_i = [c W_uk_i || k_r]``, ``v_i = c W_uv_i``; causal
softmax of ``q_i . k_i / sqrt(qk_nope + qk_rope)``; the head-wise gate ``o_i
<- o_i sigmoid((h W_og)_i)``; ``out = concat_i(o_i) W_o``. Expanded keys and
values, no absorption, no cache.

*Expert layer*: ``s = sigmoid(h W_r)`` over ALL ``router_experts``; choosing
scores ``s + b``; the experts lie in ``router_groups`` equal groups by id, a
group's score is the sum of its two largest choosing scores, the
``router_groups_kept`` best groups are kept; chosen = the
``experts_per_token`` largest choosing scores among the kept groups'
experts; ``g_e = routed_scale s_e / sum_chosen s``; ``y = sum_{chosen and
held} g_e E_e(h) + E_shared(h)``, every ``E`` a gated MLP ``(SiLU(h W_g) * h
W_u) W_d`` of ``expert_width``, the shared one ungated and added once. ``held
= (first, count)`` is the share of the experts that lives here: what the
absent ones would add is left out (the ``model-configs`` guide, section 4).
Experts run as a plain loop over all tokens, masked. The dense MLP is the same
gated MLP of ``dense_width``.

Departures from the release, each under ``assumed`` in the configuration's
file too: the vision tower and the multi-token-prediction modules are left
out (prompts are token ids, a step yields one token); the safe gate's form
and ``use_qk_norm``'s reading (the latent's RMSNorm and KDA's l2 norms) are
inferences; no SwiGLU clamp (the layers held have none); ``A_log`` takes one
of 256 values.

Float32, ``jax.default_matmul_precision("highest")``, no cache, no kernels,
the delta rule as the token-by-token recurrence under ``lax.scan``, one
sequence at a time, attention blocked over heads and queries so that a
prompt of thousands fits, one layer's weights alive at a time. It imports
nothing of ``mxnet_tpu``. Weights are made from the seed by the scheme below
(the program's ``models/kda_mla_moe.py`` states the same scheme and makes the
same numbers on the device), rounded to bfloat16 once and held in float32.

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
# leaf = 0.02 N(0, 1) in bfloat16 (``_normal_bf16``); norm gains and the
# convolution's taps are 1 + that; an embedding row is 50 times it (N(0, 1));
# the five projections that write into the residual stream (``RESIDUAL``) are
# an eighth of it (depth-scaled: 1 / sqrt(2 x 32), a power of two near the 42
# layers' 1 / sqrt(84));
# dt_bias = -4 (1 + that); A_log = log u with u one of 256 even steps of [0.5,
# 2], picked by a random byte from a table made on the host (a device's log
# rounds by how it was fused). key = fold_in(fold_in(fold_in(PRNGKey(seed mod
# 2**31), seed // 2**31), LEAF index), layer's index in the whole model) —
# expert leaves fold in the expert's GLOBAL index too and draw one expert at
# a time, embedding and head a whole block of 8192 rows of the published
# table (the rows held are a slice of those). Matrices are (in, out), but
# q_w (out, in).
LEAVES = ("embed", "head", "final_norm", "attn_norm", "mlp_norm",
          "kq_w", "kk_w", "kv_w", "ka_w", "kg_w", "kb_w", "conv_w", "A_log",
          "dt_bias", "gnorm", "ko_w",
          "q_w", "kva_w", "kv_norm", "uk_w", "uv_w", "og_w", "o_w",
          "gate_w", "up_w", "down_w", "router_w", "router_b",
          "shared_gate_w", "shared_up_w", "shared_down_w",
          "experts_gate_w", "experts_up_w", "experts_down_w")
ONE_PLUS = ("final_norm", "attn_norm", "mlp_norm", "kv_norm", "gnorm",
            "conv_w")
KDA = ("kq_w", "kk_w", "kv_w", "ka_w", "kg_w", "kb_w", "conv_w", "A_log",
       "dt_bias", "gnorm", "ko_w")
MLA = ("q_w", "kva_w", "kv_norm", "uk_w", "uv_w", "og_w", "o_w")
DENSE = ("gate_w", "up_w", "down_w")
ROUTED = ("router_w", "router_b", "shared_gate_w", "shared_up_w",
          "shared_down_w")
EXPERTS = ("experts_gate_w", "experts_up_w", "experts_down_w")
VOCAB_BLOCK = 8192
EMBED_SCALE = 50.0
RESIDUAL = ("ko_w", "o_w", "down_w", "shared_down_w", "experts_down_w")
RESIDUAL_SCALE = 0.125
DT_BIAS_SCALE = -4.0
A_LOG_TABLE = np.log(0.5 + np.arange(256) * (1.5 / 255.0)).astype(np.float32)


def base_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)


def is_mla(m: dict, layer: int) -> bool:
    """``layer``: the index in the whole model."""
    return (layer + 1) % m["group_size"] == 0


def leaf_shapes(m: dict) -> dict:
    """name -> shape of one layer's leaf (one expert's, for ``experts_*``)."""
    d, h = m["hidden_size"], m["num_heads"]
    dk, dv = m["kda_key_dim"], m["kda_value_dim"]
    nope, rope, vd, r = m["qk_nope"], m["qk_rope"], m["v_head"], m["kv_rank"]
    f, fe, e = m["dense_width"], m["expert_width"], m["router_experts"]
    return {"final_norm": (d,), "attn_norm": (d,), "mlp_norm": (d,),
            "kq_w": (d, h * dk), "kk_w": (d, h * dk), "kv_w": (d, h * dv),
            "ka_w": (d, h * dk), "kg_w": (d, h * dv), "kb_w": (d, h),
            "conv_w": (m["conv_width"], 2 * h * dk + h * dv),
            "A_log": (h,), "dt_bias": (h * dk,), "gnorm": (dv,),
            "ko_w": (h * dv, d),
            "q_w": (h * (nope + rope), d), "kva_w": (d, r + rope),
            "kv_norm": (r,), "uk_w": (h, nope, r), "uv_w": (h, r, vd),
            "og_w": (d, h), "o_w": (h * vd, d),
            "gate_w": (d, f), "up_w": (d, f), "down_w": (f, d),
            "router_w": (d, e), "router_b": (e,),
            "shared_gate_w": (d, fe), "shared_up_w": (d, fe),
            "shared_down_w": (fe, d), "experts_gate_w": (d, fe),
            "experts_up_w": (d, fe), "experts_down_w": (fe, d)}


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
    if name == "dt_bias":
        x = (DT_BIAS_SCALE * (1.0 + x.astype(jnp.float32))).astype(jnp.bfloat16)
    if name == "embed":
        x = (x.astype(jnp.float32) * EMBED_SCALE).astype(jnp.bfloat16)
    if name in RESIDUAL:
        x = (x.astype(jnp.float32) * RESIDUAL_SCALE).astype(jnp.bfloat16)
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
    """The weights of layer ``layer`` (its index in the whole model) in
    float32 (bfloat16 values; ``A_log`` float32). ``held = (first, count)``
    of the routed experts; default the configuration's."""
    key, shapes = base_key(seed), leaf_shapes(m)
    first, count = held or (m["experts_first"], m["experts_held"])
    dense = layer < m["first_dense"]
    names = ("attn_norm", "mlp_norm") + (MLA if is_mla(m, layer) else KDA) + (
        DENSE if dense else ROUTED)
    w = {n: _draw(key, n, shapes[n], layer) for n in names}
    if not dense:
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


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rotate(x, positions, theta):
    """RoPE of x (S, ..., dim) at ``positions`` (S,): pairs (i, i + dim/2)."""
    dim = x.shape[-1]
    inv_freq = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    angle = (positions.astype(jnp.float32)[:, None]
             * jnp.asarray(inv_freq, jnp.float32)[None, :])
    angle = angle.reshape(angle.shape[:1] + (1,) * (x.ndim - 2) + angle.shape[1:])
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def kda_attention(m, w, h, precision):
    """The KDA layer over one sequence h (S, hidden), the recurrence token
    by token."""
    s = h.shape[0]
    heads, dk, dv = m["num_heads"], m["kda_key_dim"], m["kda_value_dim"]
    width = m["conv_width"]
    x = jnp.concatenate([_mm("sd,de->se", h, w[n], precision)
                         for n in ("kq_w", "kk_w", "kv_w")], axis=1)
    a = _mm("sd,de->se", h, w["ka_w"], precision)
    gate = _mm("sd,de->se", h, w["kg_w"], precision).reshape(s, heads, dv)
    b = _mm("sd,de->se", h, w["kb_w"], precision)
    padded = jnp.concatenate([jnp.zeros((width - 1, x.shape[1])), x])
    x = jax.nn.silu(sum(w["conv_w"][j] * padded[j:j + s] for j in range(width)))
    q = x[:, :heads * dk].reshape(s, heads, dk)
    k = x[:, heads * dk:2 * heads * dk].reshape(s, heads, dk)
    v = x[:, 2 * heads * dk:].reshape(s, heads, dv)
    beta = jax.nn.sigmoid(b)
    g = m["gate_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(w["A_log"])[None, :, None]
        * (a + w["dt_bias"]).reshape(s, heads, dk))

    def l2(t):
        return t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    q, k = l2(q) * dk ** -0.5, l2(k)

    def token(state, xs):
        qt, kt, vt, gt, bt = xs
        state = state * jnp.exp(gt)[:, :, None]
        r = jnp.einsum("hkv,hk->hv", state, kt, precision="highest")
        dlt = bt[:, None] * (vt - r)
        state = state + kt[:, :, None] * dlt[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, qt, precision="highest")

    _, o = jax.lax.scan(token, jnp.zeros((heads, dk, dv)), (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + m["rms_eps"])
    y = (w["gnorm"] * o * jax.nn.sigmoid(gate)).reshape(s, heads * dv)
    return _mm("se,ed->sd", y, w["ko_w"], precision)


def mla_attention(m, w, h, precision, head_block=8, query_block=1024):
    """Expanded MLA with a head-wise output gate over one sequence h (S,
    hidden) -> (S, hidden)."""
    s = h.shape[0]
    heads, nope, rope = m["num_heads"], m["qk_nope"], m["qk_rope"]
    rank, theta = m["kv_rank"], m["rope"]["theta"]
    pos = jnp.arange(s)
    q = _mm("sd,ed->se", h, w["q_w"], precision).reshape(s, heads, nope + rope)
    q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], pos, theta)
    kva = _mm("sd,de->se", h, w["kva_w"], precision)
    c = rms_norm(kva[:, :rank], w["kv_norm"], m["rms_eps"])
    k_r = rotate(kva[:, rank:], pos, theta)                     # (S, rope)
    k_nope = _mm("sc,hnc->shn", c, w["uk_w"], precision)
    v = _mm("sc,hcv->shv", c, w["uv_w"], precision)
    scale = (nope + rope) ** -0.5
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
    o = jnp.moveaxis(o, 0, 1).reshape(s, heads, -1)
    o = o * jax.nn.sigmoid(_mm("sd,dh->sh", h, w["og_w"], precision))[..., None]
    return _mm("se,ed->sd", o.reshape(s, -1), w["o_w"], precision)


def gated_mlp(h, gate, up, down, precision):
    a = jax.nn.silu(_mm("sd,df->sf", h, gate, precision))
    return _mm("sf,fd->sd", a * _mm("sd,df->sf", h, up, precision), down,
               precision)


def route(m, w, h, precision):
    """(scores (S, E), chosen (S, k) expert ids, gates (S, k), kept (S,
    groups) bool)."""
    s = jax.nn.sigmoid(_mm("sd,de->se", h, w["router_w"], precision))
    choose = s + w["router_b"]
    n, groups = choose.shape[0], m["router_groups"]
    per = choose.shape[1] // groups
    two, _ = jax.lax.top_k(choose.reshape(n, groups, per), 2)
    _, best = jax.lax.top_k(two.sum(-1), m["router_groups_kept"])
    kept = (best[:, :, None] == jnp.arange(groups)).any(axis=1)
    among = jnp.where(jnp.repeat(kept, per, axis=1), choose, -jnp.inf)
    _, chosen = jax.lax.top_k(among, m["experts_per_token"])
    picked = jnp.take_along_axis(s, chosen, axis=1)
    gates = m["routed_scale"] * picked / jnp.sum(picked, -1, keepdims=True)
    return s, chosen, gates, kept


def expert_layer(m, w, h, precision, held=None, shared=True):
    """The routed part of the held experts, plus (``shared``) the shared
    expert: a plain loop, every expert over all tokens, masked."""
    first, count = held or (m["experts_first"], m["experts_held"])
    _, chosen, gates, _ = route(m, w, h, precision)

    def one_expert(y, xs):
        i, gate, up, down = xs
        g = jnp.sum(jnp.where(chosen == first + i, gates, 0.0), axis=1)
        return y + g[:, None] * gated_mlp(h, gate, up, down, precision), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        jnp.arange(count), w["experts_gate_w"], w["experts_up_w"],
        w["experts_down_w"]))
    if shared:
        y = y + gated_mlp(h, w["shared_gate_w"], w["shared_up_w"],
                          w["shared_down_w"], precision)
    return y


@functools.partial(jax.jit, static_argnames=("m_json", "mla", "dense",
                                             "precision", "held"))
def _layer(w, x, *, m_json, mla, dense, precision, held):
    m = json.loads(m_json)
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, w["attn_norm"], m["rms_eps"])
        mixer = mla_attention if mla else kda_attention
        x = x + mixer(m, w, h, precision)
        h = rms_norm(x, w["mlp_norm"], m["rms_eps"])
        if dense:
            return x + gated_mlp(h, w["gate_w"], w["up_w"], w["down_w"],
                                 precision)
        return x + expert_layer(m, w, h, precision, held)


def layer_forward(m, w, x, layer, precision="f32", held=None):
    """Layer ``layer`` (its index in the whole model) over one sequence x
    (S, hidden), float32."""
    return _layer(w, x, m_json=json.dumps(m, sort_keys=True),   # hashable
                  mla=is_mla(m, layer), dense=layer < m["first_dense"],
                  precision=precision, held=held)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, gain, head, *, eps, precision):
    with jax.default_matmul_precision("highest"):
        return _mm("sd,vd->sv", rms_norm(x, gain, eps), head, precision)


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
    for layer in m["layers"]:
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


def served_logits(m, seed, records, precision="f32", pad=2048, held=None,
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

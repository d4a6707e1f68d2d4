"""The plain reference of the ``ssm_moe`` kind (Nemotron-H: NVIDIA-Nemotron-3-
Nano-30B-A3B): a pre-norm decoder whose every layer is ONE norm and ONE mixer
— a Mamba-2 state-space layer, grouped-KV attention, or an expert layer —
from the equations.

``h`` is ``hidden_size`` wide. **Norm**: ``RMSNorm(x) = x rsqrt(mean(x^2) +
eps) w`` (a plain gain, float32). **Block** ``i``: ``x += mixer_i(RMSNorm_i(
x))``; ``pattern[i]`` says which mixer: ``M`` Mamba-2, ``E`` experts, ``*``
attention. A final RMSNorm, an untied head over the held slice of the
vocabulary; no bias anywhere but the convolution's.

*Mamba-2* (H heads of P = ``ssm_head_dim``, inner width H P — NOT ``expand x
hidden`` —, G groups, state size N, convolution width W): ``in_proj: hidden
-> [z (H P) || xBC (H P + 2 G N) || dt (H)]`` — published as one matrix;
three leaves here (``z_w``, ``xbc_w``, ``dt_w``), the same function of seeded
weights. ``xBC <- SiLU(conv(xBC) + b_c)``: causal, depthwise, ``out_t = sum_j
w_j x_(t - W + 1 + j)`` (the last row of ``conv_w`` weighs the current
input). Split ``x`` (H x P), ``B``, ``C`` (G x N each); head ``n`` uses group
``n // (H / G)``. Per head: ``delta_t = softplus(dt_t + dt_bias)``
(``time_step_limit`` is (0, inf): no clamp), ``a_t = exp(-exp(A_log)
delta_t)``, and with state ``S`` (P x N): ``S <- a_t S + delta_t x_t B_t^T;
y_t = S C_t + D x_t``. Then the gated group norm, gate first: ``u = y *
SiLU(z)``; over each of the G groups of H P / G channels ``u rsqrt(mean(u^2)
+ eps) w_n``; ``out = u . W_out``.

*Attention* (H query heads, KV cached heads, head D): ``W_q: hidden -> H D``,
``W_k, W_v: hidden -> KV D``, ``W_o: H D -> hidden``; NO positional embedding
(the ``nemotron_h`` modelling code applies none: ``rope_theta`` and
``partial_rotary_factor`` are carried by the config and unused), no q/k
norm, no gate; causal softmax, scale ``D^-0.5``; query head ``n`` reads
cached head ``n // (H / KV)``.

*Expert layer*: ``s = sigmoid(h . W_r)`` over ALL ``router_experts``; chosen
= the ``experts_per_token`` largest of ``s + b`` (the bias chooses and never
weighs; ``n_group`` 1: no group limit); weights ``routed_scale s_e /
sum_chosen s`` (``norm_topk_prob``, ``routed_scaling_factor``). Expert ``e``:
``relu(h W_u)^2 W_d`` — two matrices, no gate. Shared expert: the same MLP at
``shared_width``, no gate. ``y = sum_{chosen and held} weight_e expert_e(h) +
shared(h)``; ``held = (first, count)`` is the share of the experts that lives
here: what the absent ones would add is left out (the ``model-configs``
guide, section 4).

Departures from the release, each under ``assumed`` in the configuration's
file too: the fused input projection is three leaves; ``A_log`` and
``dt_bias`` take one of 256 values each; ``D = 1``.

Float32, ``jax.default_matmul_precision("highest")``, no cache, no kernels,
the state-space layer as the token-by-token recurrence under ``lax.scan``,
one sequence at a time, attention blocked over queries, one layer's weights
alive at a time. It imports nothing of ``mxnet_tpu``. Weights are made from
the seed by the scheme below (the program's ``models/ssm_moe.py`` states the
same scheme and makes the same numbers on the device), rounded to bfloat16
once and held in float32.

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
# matrix = 0.02 N(0, 1) in bfloat16 (``_normal_bf16``); the norms' gains are 1 +
# that; D = 1; A_log = log u with u one of 256 even steps of [1, 16], dt_bias the
# inverse softplus of one of 256 log-even steps of [time_step_min,
# time_step_max] floored at time_step_floor, each picked by a random byte from
# a table made on the host (a device's log rounds by how it was fused). key =
# fold_in(fold_in(fold_in(PRNGKey(seed mod 2**31), seed // 2**31), LEAF index),
# layer) — expert leaves fold in the expert's GLOBAL index too and draw one
# expert at a time, embedding and head a whole block of 8192 rows of the
# published table (the rows held are a slice of those). Matrices are (in, out).
LEAVES = ("embed", "head", "final_norm", "norm", "router_w", "router_b",
          "shared_up_w", "shared_down_w", "experts_up_w", "experts_down_w",
          "q_w", "k_w", "v_w", "o_w", "z_w", "xbc_w", "dt_w", "conv_w",
          "conv_b", "A_log", "D", "dt_bias", "gnorm", "out_w")
ONE_PLUS = ("final_norm", "norm", "gnorm")
BY_KIND = {"M": ("z_w", "xbc_w", "dt_w", "conv_w", "conv_b", "A_log", "D",
                 "dt_bias", "gnorm", "out_w"),
           "*": ("q_w", "k_w", "v_w", "o_w"),
           "E": ("router_w", "router_b", "shared_up_w", "shared_down_w")}
EXPERTS = ("experts_up_w", "experts_down_w")
VOCAB_BLOCK = 8192
A_LOG_TABLE = np.log(1.0 + np.arange(256) * (15.0 / 255.0)).astype(np.float32)


def dt_bias_table(m: dict) -> np.ndarray:
    lo, hi = np.log(m["time_step_min"]), np.log(m["time_step_max"])
    delta = np.maximum(np.exp(lo + np.arange(256) * ((hi - lo) / 255.0)),
                       m["time_step_floor"])
    return (delta + np.log(-np.expm1(-delta))).astype(np.float32)


def base_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)


def leaf_shapes(m: dict) -> dict:
    """name -> shape of one layer's leaf (one expert's, for ``experts_*``)."""
    d, e = m["hidden_size"], m["router_experts"]
    fe, fs = m["expert_width"], m["shared_width"]
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    sh, sp = m["ssm_heads"], m["ssm_head_dim"]
    inner, bc = sh * sp, m["ssm_groups"] * m["ssm_state"]
    return {"final_norm": (d,), "norm": (d,), "router_w": (d, e),
            "router_b": (e,), "shared_up_w": (d, fs), "shared_down_w": (fs, d),
            "experts_up_w": (d, fe), "experts_down_w": (fe, d),
            "q_w": (d, h * hd), "k_w": (d, kv * hd), "v_w": (d, kv * hd),
            "o_w": (h * hd, d), "z_w": (d, inner),
            "xbc_w": (d, inner + 2 * bc), "dt_w": (d, sh),
            "conv_w": (m["conv_width"], inner + 2 * bc),
            "conv_b": (inner + 2 * bc,), "A_log": (sh,), "D": (sh,),
            "dt_bias": (sh,), "gnorm": (inner,), "out_w": (inner, d)}


@functools.partial(jax.jit, static_argnums=1)
def _normal_bf16(key, shape):
    """0.02 N(0, 1), to bfloat16, from integers alone: the twelve bytes of
    three random words summed (Irwin-Hall, mean 1530, variance 65535), one
    float32 multiply, one rounding. Exact in any program that computes it."""
    words = jax.random.bits(key, (3,) + tuple(shape), jnp.uint32)
    total = sum((words >> s) & 0xFF for s in (0, 8, 16, 24)).sum(axis=0)
    x = (total.astype(jnp.int32) - 1530).astype(jnp.float32)
    return (x * np.float32(0.02 / 65535 ** 0.5)).astype(jnp.bfloat16)


def _draw(m, key, name, shape, *path):
    key = jax.random.fold_in(key, LEAVES.index(name))
    for i in path:
        key = jax.random.fold_in(key, i)
    if name == "D":
        return jnp.ones(shape, jnp.float32)
    if name in ("A_log", "dt_bias"):
        table = A_LOG_TABLE if name == "A_log" else dt_bias_table(m)
        byte = jax.random.bits(key, tuple(shape), jnp.uint32) & 0xFF
        return jnp.asarray(table)[byte]
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
    table = jnp.concatenate([_draw(m, key, name, (VOCAB_BLOCK, d), b)
                             for b in blocks])
    start = first - blocks[0] * VOCAB_BLOCK
    return table[start:start + v]


def layer_weights(m: dict, seed: int, layer: int, held=None) -> dict:
    """One layer's weights in float32 (bfloat16 values; ``A_log``, ``D``,
    ``dt_bias`` float32). ``held = (first, count)`` of the routed experts;
    default the configuration's."""
    key, shapes = base_key(seed), leaf_shapes(m)
    kind = m["pattern"][layer]
    w = {n: _draw(m, key, n, shapes[n], layer)
         for n in ("norm",) + BY_KIND[kind]}
    if kind == "E":
        first, count = held or (m["experts_first"], m["experts_held"])
        for n in EXPERTS:
            w[n] = jnp.stack([_draw(m, key, n, shapes[n], layer, e)
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


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def attention(m, w, h, precision, query_block=1024):
    """Grouped-KV causal attention over one sequence h (S, hidden); no
    positional embedding."""
    s = h.shape[0]
    heads, kv, d = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    pos = jnp.arange(s)
    q = _mm("sd,de->se", h, w["q_w"], precision).reshape(
        s, kv, heads // kv, d)                      # head n = [n // G, n % G]
    k = _mm("sd,de->se", h, w["k_w"], precision).reshape(s, kv, d)
    v = _mm("sd,de->se", h, w["v_w"], precision).reshape(s, kv, d)
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
    return _mm("se,ed->sd", o, w["o_w"], precision)


def mamba2(m, w, h, precision):
    """The Mamba-2 layer over one sequence h (S, hidden), the recurrence
    token by token."""
    s = h.shape[0]
    sh, sp, g, n = (m["ssm_heads"], m["ssm_head_dim"], m["ssm_groups"],
                    m["ssm_state"])
    inner, bc, width = sh * sp, g * n, m["conv_width"]
    z = _mm("sd,de->se", h, w["z_w"], precision)
    xbc = _mm("sd,de->se", h, w["xbc_w"], precision)
    dt = _mm("sd,de->se", h, w["dt_w"], precision)
    padded = jnp.concatenate([jnp.zeros((width - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(sum(w["conv_w"][j] * padded[j:j + s]
                          for j in range(width)) + w["conv_b"])
    x = xbc[:, :inner].reshape(s, sh, sp)
    b = jnp.repeat(xbc[:, inner:inner + bc].reshape(s, g, n), sh // g, axis=1)
    c = jnp.repeat(xbc[:, inner + bc:].reshape(s, g, n), sh // g, axis=1)
    delta = jax.nn.softplus(dt + w["dt_bias"])              # (S, H)
    decay = jnp.exp(-jnp.exp(w["A_log"]) * delta)

    def token(state, xs):
        xt, bt, ct, dt_t, at = xs
        state = (state * at[:, None, None]
                 + (dt_t[:, None] * xt)[:, :, None] * bt[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, ct, precision="highest")

    _, y = jax.lax.scan(token, jnp.zeros((sh, sp, n)), (x, b, c, delta, decay))
    y = y + w["D"][:, None] * x
    u = (y.reshape(s, inner) * jax.nn.silu(z)).reshape(s, g, inner // g)
    u = u * jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True) + m["rms_eps"])
    return _mm("se,ed->sd", u.reshape(s, inner) * w["gnorm"], w["out_w"],
               precision)


def relu2_mlp(h, up, down, precision):
    a = jax.nn.relu(_mm("sd,df->sf", h, up, precision))
    return _mm("sf,fd->sd", a * a, down, precision)


def route(m, w, h, precision):
    """(scores (S, E), chosen (S, k) expert ids, weights (S, k))."""
    s = jax.nn.sigmoid(_mm("sd,de->se", h, w["router_w"], precision))
    _, chosen = jax.lax.top_k(s + w["router_b"], m["experts_per_token"])
    picked = jnp.take_along_axis(s, chosen, axis=1)
    return s, chosen, (m["routed_scale"] * picked
                       / jnp.sum(picked, -1, keepdims=True))


def expert_layer(m, w, h, precision, held=None, shared=True):
    """The routed part of the held experts, plus (``shared``) the shared
    expert: a plain loop, every expert over all tokens, masked."""
    first, count = held or (m["experts_first"], m["experts_held"])
    _, chosen, gates = route(m, w, h, precision)

    def one_expert(y, xs):
        i, up, down = xs
        g = jnp.sum(jnp.where(chosen == first + i, gates, 0.0), axis=1)
        return y + g[:, None] * relu2_mlp(h, up, down, precision), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        jnp.arange(count), w["experts_up_w"], w["experts_down_w"]))
    if shared:
        y = y + relu2_mlp(h, w["shared_up_w"], w["shared_down_w"], precision)
    return y


MIXERS = {"M": mamba2, "*": attention}


@functools.partial(jax.jit, static_argnames=("m_json", "kind", "precision",
                                             "held"))
def _layer(w, x, *, m_json, kind, precision, held):
    m = json.loads(m_json)
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, w["norm"], m["rms_eps"])
        if kind == "E":
            return x + expert_layer(m, w, h, precision, held)
        return x + MIXERS[kind](m, w, h, precision)


def layer_forward(m, w, x, layer, precision="f32", held=None):
    """One layer over one sequence x (S, hidden), float32."""
    return _layer(w, x, m_json=json.dumps(m, sort_keys=True),   # hashable
                  kind=m["pattern"][layer], precision=precision, held=held)


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
    for layer in range(len(m["pattern"])):
        t = time.monotonic()
        w = layer_weights(m, seed, layer, held)
        xs = [layer_forward(m, w, x, layer, precision, held) for x in xs]
        jax.block_until_ready(xs)
        del w
        if log:
            log(f"reference ({precision}) layer {layer} "
                f"{m['pattern'][layer]}: {time.monotonic() - t:.1f}s for "
                f"{len(xs)} sequences")
    head = vocab_weights(m, seed, "head")
    gain = _draw(m, base_key(seed), "final_norm", (m["hidden_size"],))
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

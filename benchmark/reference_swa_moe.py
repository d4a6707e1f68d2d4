"""The plain reference of the ``swa_moe`` kind (MiMo-V2-Flash): a pre-norm
decoder whose layers mix sliding-window and global grouped-KV attention under
a pattern, a dense MLP in the leading layer and expert layers with no shared
expert after it — from the equations.

``x`` is the stream, ``hidden_size`` wide. **Norm**: ``N(x) = x rsqrt(mean(x^2)
+ eps) w`` (a learned weight, eps ``rms_eps`` = 1e-5): the two norms of every
block and the final norm. **Block**: ``a = x + Attn(N1(x)); x' = a + MLP(N2(a))``.
Layer ``l`` is of kind ``layer_pattern[l]`` (0 global, 1 window) and its MLP of
kind ``moe_pattern[l]`` (0 dense, 1 experts).

*Attention*, ``h = N1(x)``: ``q = h W_q`` -> H = 64 heads x dk = 192; ``k = h
W_k``, ``v = h W_v`` -> KV heads x 192 and x dv = 128, KV = ``kv_heads`` = 4
(global) or ``swa_kv_heads`` = 8 (window); no bias. RoPE, rotate-half (pairs
``(j, j + rot / 2)``), on the first ``rot = rotary_dim`` = 64 = floor(0.334 x
192) dimensions of every q and k head, base ``rope_theta`` = 5e6 (global) or
``swa_rope_theta`` = 1e4 (window), no scaling; the other 128 pass through.
Query head ``n`` reads cached head ``n // G``, G = H / KV = 16 (global) or 8
(window). ``s_ij = q_i . k_j / sqrt(192)`` for ``j <= i`` (global) or ``i - W <
j <= i`` (window, W = ``window`` = 128: itself and the 127 before it).
Global: ``p = softmax_j(s)``. Window: ``p_ij = exp(s_ij - m_i) / (exp(b_h - m_i)
+ sum_j exp(s_ij - m_i))``, ``b_h`` the query head's learned sink, ``m_i`` the
max over the row's scores and the sink: the sink takes mass and gives no
value. ``o_i = value_scale . sum_j p_ij v_j`` (0.707); ``a = x + concat(o) W_o``
(64 x 128 -> hidden).

*Dense MLP* (layer 0), ``g = N2(a)``: ``(silu(g W_gate) * g W_up) W_down`` at
``dense_width`` = 16,384. *Expert layer*: ``sigma = sigmoid(g W_r)`` in float32
over ALL ``router_experts`` = 256; chosen = the ``experts_per_token`` = 8
largest of ``sigma + b`` (the bias chooses, never weighs; one group: no group
limit); gates ``sigma_chosen / sum(sigma_chosen)`` (``norm_topk_prob``) times
``routed_scale`` (published null = 1). ``y = sum_{chosen and held} gate_e
E_e(g)``, ``E_e`` the same gated SiLU at ``expert_width`` = 2,048; no shared
expert. ``held = (first, count)`` is the share of the experts that lives here:
what the absent ones would add is left out (the ``model-configs`` guide,
section 4). Final ``N``, untied head over the held slice of the vocabulary.

Departures from the release, each under ``assumed`` in the configuration's
file too: the window's edges as above (``sliding_window`` 128 counts the row
itself); ``attention_value_scale`` applied to the attention's output (equal
to scaling ``v``); the rotary dimensions first, rotate-half;
``attention_chunk_size`` 128 read as the same window, not as block-local
attention; the sinks and the embedding rows ``N(0, 1)`` from the seed, the
choosing bias ``0.02 N(0, 1)`` as every leaf; the multi-token-prediction
layers left out (the config has no key for them).

Float32, ``jax.default_matmul_precision("highest")``, no cache, no kernels,
one sequence at a time, attention blocked over queries so that a 28 k
sequence fits (a window layer's block slices the keys it can see), one
layer's weights alive at a time. It imports nothing of ``mxnet_tpu``. Weights
are made from the seed by the scheme below (the program's
``models/swa_moe.py`` states the same scheme and makes the same numbers on
the device), rounded to bfloat16 once and held in float32.

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
# leaf = 0.02 N(0, 1) in bfloat16 (``_normal_bf16``); the norms' weights are 1 +
# that; a sink, and an embedding row, is 50 x that, N(0, 1) (the embedding
# rounded to bfloat16 again: at 0.02 a token's own row is a fourteenth of what
# layer 0's attention adds, and every position's stream is one vector). key =
# fold_in(fold_in(fold_in(PRNGKey(seed mod 2**31), seed // 2**31), LEAF index),
# layer) — expert leaves fold in the expert's GLOBAL index too and draw one
# expert at a time, embedding and head a whole block of 8192 rows of the
# published table (the rows held are a slice of those). A window layer's
# ``k_w`` and ``v_w`` are as wide as its cached heads. Matrices are (in, out),
# but ``q_w`` (out, in).
LEAVES = ("embed", "head", "final_norm", "attn_norm", "mlp_norm", "q_w",
          "k_w", "v_w", "o_w", "sink", "gate_w", "up_w", "down_w", "router_w",
          "router_b", "experts_gate_w", "experts_up_w", "experts_down_w")
GAINS = ("final_norm", "attn_norm", "mlp_norm")
ATTENTION = ("attn_norm", "mlp_norm", "q_w", "k_w", "v_w", "o_w")
DENSE = ("gate_w", "up_w", "down_w")
EXPERTS = ("experts_gate_w", "experts_up_w", "experts_down_w")
VOCAB_BLOCK = 8192
SINK_SCALE = 50.0
EMBED_SCALE = 50.0


def base_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)


def is_window(m: dict, layer: int) -> bool:
    return bool(m["layer_pattern"][layer])


def is_routed(m: dict, layer: int) -> bool:
    return bool(m["moe_pattern"][layer])


def leaf_shapes(m: dict, window: bool) -> dict:
    """name -> shape of one layer's leaf (one expert's, for ``experts_*``)."""
    d, h, dk, dv = m["hidden_size"], m["num_heads"], m["head_dim"], m["v_head_dim"]
    kv = m["swa_kv_heads"] if window else m["kv_heads"]
    f, fe, e = m["dense_width"], m["expert_width"], m["router_experts"]
    return {"final_norm": (d,), "attn_norm": (d,), "mlp_norm": (d,),
            "q_w": (h * dk, d), "k_w": (d, kv * dk), "v_w": (d, kv * dv),
            "o_w": (h * dv, d), "sink": (h,),
            "gate_w": (d, f), "up_w": (d, f), "down_w": (f, d),
            "router_w": (d, e), "router_b": (e,),
            "experts_gate_w": (d, fe), "experts_up_w": (d, fe),
            "experts_down_w": (fe, d)}


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
    x = _normal_bf16(key, shape)
    if name in GAINS:
        x = (1.0 + x.astype(jnp.float32)).astype(jnp.bfloat16)
    x = x.astype(jnp.float32)
    if name == "embed":     # N(0, 1), rounded to bfloat16 again as it is held
        x = (x * EMBED_SCALE).astype(jnp.bfloat16).astype(jnp.float32)
    return x * SINK_SCALE if name == "sink" else x


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
    """One layer's weights in float32 (bfloat16 values). ``held = (first,
    count)`` of the routed experts; default the configuration's."""
    key, window = base_key(seed), is_window(m, layer)
    shapes = leaf_shapes(m, window)
    w = {n: _draw(key, n, shapes[n], layer) for n in ATTENTION}
    if window and m["swa_sink"]:
        w["sink"] = _draw(key, "sink", shapes["sink"], layer)
    if not is_routed(m, layer):
        w.update({n: _draw(key, n, shapes[n], layer) for n in DENSE})
        return w
    first, count = held or (m["experts_first"], m["experts_held"])
    for n in ("router_w", "router_b"):
        w[n] = _draw(key, n, shapes[n], layer)
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


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


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


def attention(m, w, h, window, precision, query_block=128):
    """Grouped-KV attention over one sequence h (S, hidden): global causal,
    or (``window``) each row over itself and the W - 1 before it, with a
    sink a query head."""
    s = h.shape[0]
    heads, dk, dv = m["num_heads"], m["head_dim"], m["v_head_dim"]
    kv = m["swa_kv_heads"] if window else m["kv_heads"]
    rot, pos, width = m["rotary_dim"], jnp.arange(s), m["window"]
    theta = m["swa_rope_theta"] if window else m["rope_theta"]
    q = _mm("sd,ed->se", h, w["q_w"], precision).reshape(s, heads, dk)
    k = _mm("sd,de->se", h, w["k_w"], precision).reshape(s, kv, dk)
    v = _mm("sd,de->se", h, w["v_w"], precision).reshape(s, kv, dv)

    def rope(x):
        return jnp.concatenate(
            [rotate(x[..., :rot], pos, theta), x[..., rot:]], axis=-1)

    q = rope(q).reshape(s, kv, heads // kv, dk)     # head n = [n // G, n % G]
    k = rope(k)
    sink = (w["sink"].reshape(kv, heads // kv) if "sink" in w
            else jnp.full((kv, heads // kv), -jnp.inf))
    qb = min(query_block, s)
    assert s % qb == 0, (s, qb)
    # a window block sees at most the W - 1 positions before its first row:
    # those keys alone are sliced out (padded in front, never seen there)
    span = qb + width - 1 if window else s
    if window:
        k = jnp.concatenate([jnp.zeros((width - 1,) + k.shape[1:]), k])
        v = jnp.concatenate([jnp.zeros((width - 1,) + v.shape[1:]), v])

    def block(start):
        rows = start + jnp.arange(qb)[None, None, :, None]
        q_b = jax.lax.dynamic_slice_in_dim(q, start, qb, 0)
        if window:
            k_b = jax.lax.dynamic_slice_in_dim(k, start, span, 0)
            v_b = jax.lax.dynamic_slice_in_dim(v, start, span, 0)
            cols = start - (width - 1) + jnp.arange(span)[None, None, None, :]
            seen = (cols <= rows) & (cols > rows - width) & (cols >= 0)
        else:
            k_b, v_b = k, v
            seen = pos[None, None, None, :] <= rows
        sc = dk ** -0.5 * _mm("qhgd,khd->hgqk", q_b, k_b, precision)
        sc = jnp.where(seen, sc, -jnp.inf)
        top = jnp.maximum(jnp.max(sc, axis=-1), sink[:, :, None])
        p = jnp.exp(sc - top[..., None])
        p = p / (jnp.sum(p, axis=-1) + jnp.exp(sink[:, :, None] - top))[..., None]
        return _mm("hgqk,khd->qhgd", p, v_b, precision)

    o = jax.lax.map(block, jnp.arange(0, s, qb)).reshape(s, heads * dv)
    return _mm("se,ed->sd", m["value_scale"] * o, w["o_w"], precision)


def gated_mlp(h, gate, up, down, precision):
    a = jax.nn.silu(_mm("sd,df->sf", h, gate, precision))
    return _mm("sf,fd->sd", a * _mm("sd,df->sf", h, up, precision), down,
               precision)


def route(m, w, h, precision):
    """(sigma (S, E), chosen (S, k) expert ids, gates (S, k))."""
    sigma = jax.nn.sigmoid(_mm("sd,de->se", h, w["router_w"], precision))
    _, chosen = jax.lax.top_k(sigma + w["router_b"], m["experts_per_token"])
    picked = jnp.take_along_axis(sigma, chosen, axis=1)
    return sigma, chosen, (m["routed_scale"] * picked
                           / jnp.sum(picked, -1, keepdims=True))


def expert_layer(m, w, h, precision, held=None):
    """The routed part of the held experts: a plain loop, every expert over
    all tokens, masked. No shared expert."""
    first, count = held or (m["experts_first"], m["experts_held"])
    _, chosen, gates = route(m, w, h, precision)

    def one_expert(y, xs):
        i, gate, up, down = xs
        g = jnp.sum(jnp.where(chosen == first + i, gates, 0.0), axis=1)
        return y + g[:, None] * gated_mlp(h, gate, up, down, precision), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        jnp.arange(count), w["experts_gate_w"], w["experts_up_w"],
        w["experts_down_w"]))
    return y


@functools.partial(jax.jit, static_argnames=("m_json", "window", "routed",
                                             "precision", "held"))
def _layer(w, x, *, m_json, window, routed, precision, held):
    m = json.loads(m_json)
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, w["attn_norm"], m["rms_eps"])
        x = x + attention(m, w, h, window, precision)
        h = rms_norm(x, w["mlp_norm"], m["rms_eps"])
        if routed:
            return x + expert_layer(m, w, h, precision, held)
        return x + gated_mlp(h, w["gate_w"], w["up_w"], w["down_w"], precision)


def layer_forward(m, w, x, layer, precision="f32", held=None):
    """One layer over one sequence x (S, hidden), float32."""
    return _layer(w, x, m_json=json.dumps(m, sort_keys=True),   # hashable
                  window=is_window(m, layer), routed=is_routed(m, layer),
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
    to a multiple of ``pad`` (causal: the pad is never seen; a few lengths,
    so a few programs a kind of layer)."""
    seqs, rows = [], []
    for r in records:
        n, k = len(r["prompt"]), len(r["tokens"])
        seq = np.zeros((min(pad_to(n + k - 1, pad),
                            pad_to(m["max_length"], 128)),), np.int32)
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

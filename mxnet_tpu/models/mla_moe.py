"""A functional decoder with latent attention (MLA) and routed + shared
experts, for the decode engine (``serve/decode.py``). Not a gluon block and
not imported by ``mxnet_tpu.models``: import it where it is used.

Pre-norm residual blocks, RMSNorm with a learned gain; ``first_dense``
leading layers with a SiLU-gated MLP, the rest expert layers
(``ops/moe.py``: the chip's share ``experts_first .. + experts_held`` of
``router_experts``); an untied head. Attention, ``h = RMSNorm(x)``:

- ``q = h.W_q`` -> per head ``q_nope || q_rope``; ``[c || k_r] = h.W_kva``;
  ``c <- RMSNorm(c)``; RoPE (``deepseek_yarn``, pairs ``(i, i + rope/2)``) on
  ``q_rope`` and on the one shared ``k_r``. The cache row of a position in a
  layer is ``[c || k_r]`` — ``kv_rank + qk_rope`` values, no head axis —
  stored in a row of whole lane tiles (576 values in 640 columns, the rest
  zeros: the tile would pad the row to that anyway, and at 576 the TPU
  client's own layout for the pool puts the PAGE axis minor-most, which the
  kernel cannot read; seen on the chip, PR 29).
- **expanded** (prefill): ``k_nope_i = c.W_uk_i``, ``v_i = c.W_uv_i``, keys
  ``[k_nope_i || k_r]`` (192 wide), values 128 wide, through the flash
  forward; softmax scale ``softmax_scale``.
- **absorbed** (decode), the same numbers: ``q~_i = q_nope_i.W_uk_i^T`` (into
  the latent's space), scores of ``[q~_i || q_rope_i]`` against the cached
  row, ``u_i = sum_j p_ij c_j``, ``o_i = u_i.W_uv_i``
  (``ops.flash_attention.latent_decode_attention``).

The two attention halves (:func:`prefill_attention`, :func:`decode_attention`)
are ``models/mla_scmoe.py``'s too, which gives them what this model lacks: a
**query latent** (a layer with ``q_a_w``: ``cq = RMSNorm(h.W_qa)``, ``q =
cq.W_qb`` in place of ``h.W_q``), **latent scales** (``cfg["latent_scales"]``:
the normed query and key-value latents times ``(hidden / rank)^0.5``, the
cached ``c`` after norm and scale, so both forms still hold the same numbers)
and plain RoPE (a ``rope`` of ``factor`` 1: the published frequencies, softmax
scale ``q_head_dim**-0.5``).

Weights and activations are bfloat16 with float32 accumulation; norms,
RoPE, softmax and the router run in float32.

**Seeded weights** (``init_params``): every leaf is ``0.02 N(0, 1)`` (norm
gains ``1 +`` that) in bfloat16, the normal made from random bytes so that
every program makes the same bits (``_normal_bf16``), with ``key =
fold_in(fold_in(fold_in(PRNGKey(seed mod 2**31), seed // 2**31), index of the
leaf's name in LEAVES), layer)``; an expert's leaves fold in its GLOBAL
index as well and are drawn one expert at a time, the embedding and the
head fold in a block of 8192 rows. They are made on the device, the expert
layers' stacks in place, one expert at a time: no host copy and no second
copy exists at any moment. ``benchmark/reference_mla_moe.py`` states the
same scheme on its own.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import moe
from ..ops.flash_attention import flash_attention, latent_decode_attention

__all__ = ["config_from_hf", "init_params", "MLAMoEDecodeModel"]

LEAVES = ("embed", "head", "final_norm", "attn_norm", "q_w", "kva_w",
          "kv_norm", "uk_w", "uv_w", "o_w", "mlp_norm", "gate_w", "up_w",
          "down_w", "router_w", "router_b", "shared_gate_w", "shared_up_w",
          "shared_down_w", "experts_gate_w", "experts_up_w", "experts_down_w")
GAINS = ("final_norm", "attn_norm", "kv_norm", "mlp_norm")
ATTENTION = ("attn_norm", "q_w", "kva_w", "kv_norm", "uk_w", "uv_w", "o_w",
             "mlp_norm")
DENSE = ATTENTION + ("gate_w", "up_w", "down_w")
ROUTED = ATTENTION + ("router_w", "router_b", "shared_gate_w", "shared_up_w",
                      "shared_down_w")
EXPERTS = ("experts_gate_w", "experts_up_w", "experts_down_w")
VOCAB_BLOCK = 8192


def config_from_hf(hf: dict, *, experts_first: int = 0,
                   experts_held: int = None, router_experts: int = None,
                   max_length: int = None) -> dict:
    """The model's description from a ``sarvam_mla`` / DeepSeek-style
    ``config.json``. ``router_experts`` is the router's published width where
    ``hf["num_experts"]`` has been cut to the experts held here."""
    rs = hf["rope_scaling"]
    return {
        "vocab_size": hf["vocab_size"], "hidden_size": hf["hidden_size"],
        "num_layers": hf["num_hidden_layers"],
        "first_dense": hf["first_k_dense_replace"],
        "num_heads": hf["num_attention_heads"],
        "qk_nope": hf["qk_nope_head_dim"], "qk_rope": hf["qk_rope_head_dim"],
        "v_head": hf["v_head_dim"], "kv_rank": hf["kv_lora_rank"],
        "dense_width": hf["intermediate_size"],
        "expert_width": hf["moe_intermediate_size"],
        "router_experts": router_experts or hf["num_experts"],
        "experts_first": experts_first,
        "experts_held": experts_held or hf["num_experts"],
        "experts_per_token": hf["num_experts_per_tok"],
        "routed_scale": hf["routed_scaling_factor"],
        "rms_eps": hf["rms_norm_eps"],
        "rope": {"theta": hf["rope_theta"], "factor": rs["factor"],
                 "original_max_position_embeddings":
                     rs["original_max_position_embeddings"],
                 "beta_fast": rs["beta_fast"], "beta_slow": rs["beta_slow"],
                 "mscale": rs["mscale"],
                 "mscale_all_dim": rs["mscale_all_dim"]},
        "max_length": max_length or hf["max_position_embeddings"],
    }


def leaf_shapes(cfg: dict) -> dict:
    """name -> shape of one layer's leaf (one expert's, for ``experts_*``).
    Matrices are (in, out), but ``q_w`` (out, in)."""
    d, h = cfg["hidden_size"], cfg["num_heads"]
    nope, rope = cfg["qk_nope"], cfg["qk_rope"]
    vd, r = cfg["v_head"], cfg["kv_rank"]
    f, fe, e = cfg["dense_width"], cfg["expert_width"], cfg["router_experts"]
    return {"final_norm": (d,), "attn_norm": (d,),
            "q_w": (h * (nope + rope), d), "kva_w": (d, r + rope),
            "kv_norm": (r,), "uk_w": (h, nope, r), "uv_w": (h, r, vd),
            "o_w": (h * vd, d), "mlp_norm": (d,), "gate_w": (d, f),
            "up_w": (d, f), "down_w": (f, d), "router_w": (d, e),
            "router_b": (e,), "shared_gate_w": (d, fe),
            "shared_up_w": (d, fe), "shared_down_w": (fe, d),
            "experts_gate_w": (d, fe), "experts_up_w": (d, fe),
            "experts_down_w": (fe, d)}


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
    return x


def init_params(cfg: dict, seed: int) -> dict:
    """The seeded weights on the default device (see the module docstring):
    ``embed``, ``head``, ``final_norm``, two stacks with a leading layer axis,
    ``dense`` (the ``first_dense`` leading layers) and ``moe`` (the expert
    layers, less their experts), and ``experts``: ``gate_w``, ``up_w``,
    ``down_w`` with every expert layer's held experts on ONE leading axis
    (layer-major), filled in place. Six small programs."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)
    shapes = leaf_shapes(cfg)
    v, d = cfg["vocab_size"], cfg["hidden_size"]
    n_dense, n_layers = cfg["first_dense"], cfg["num_layers"]
    first, held = cfg["experts_first"], cfg["experts_held"]

    def vocab(name):
        return jnp.concatenate([
            _draw(key, name, (min(VOCAB_BLOCK, v - r), d), r // VOCAB_BLOCK)
            for r in range(0, v, VOCAB_BLOCK)])

    def stack(names, layers):
        return {n: jnp.stack([_draw(key, n, shapes[n], i) for i in layers])
                for n in names}

    def experts(name):
        n = n_layers - n_dense

        def one(i, buf):
            w = _draw(key, name, shapes[name], n_dense + i // held,
                      first + i % held)
            return lax.dynamic_update_slice(buf, w[None], (i, 0, 0))

        return lax.fori_loop(
            0, n * held, one,
            jnp.zeros((n * held,) + shapes[name], jnp.bfloat16))

    params = jax.jit(lambda: {
        "embed": vocab("embed"), "head": vocab("head"),
        "final_norm": _draw(key, "final_norm", (d,))})()
    params["dense"] = jax.jit(lambda: stack(DENSE, range(n_dense)))()
    params["moe"] = jax.jit(lambda: stack(ROUTED, range(n_dense, n_layers)))()
    make = jax.jit(experts, static_argnums=0)
    params["experts"] = {name[len("experts_"):]: make(name)
                         for name in EXPERTS}
    return params


# -- the layer ------------------------------------------------------------------

def rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    return (x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * gain.astype(jnp.float32))


def yarn_inv_freq(rope: dict, dim: int) -> np.ndarray:
    """``deepseek_yarn``: below the ``beta_fast`` correction dimension the
    published frequencies, above the ``beta_slow`` one those divided by
    ``factor``, a linear ramp between."""
    theta, factor = rope["theta"], rope["factor"]
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor == 1:     # plain RoPE: nothing to divide, no ramp
        return extra.astype(np.float32)
    orig = rope["original_max_position_embeddings"]

    def correction(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction(rope["beta_fast"])), 0)
    high = min(math.ceil(correction(rope["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (extra / factor * ramp + extra * (1 - ramp)).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    """``q_head_dim**-0.5 * mscale**2`` with ``mscale = 0.1 mscale_all_dim
    ln(factor) + 1``; cos and sin stay unscaled (``mscale ==
    mscale_all_dim``)."""
    rope = cfg["rope"]
    if rope["factor"] == 1:
        return (cfg["qk_nope"] + cfg["qk_rope"]) ** -0.5
    if rope["mscale"] != rope["mscale_all_dim"]:
        raise NotImplementedError("yarn with mscale != mscale_all_dim scales "
                                  "cos and sin: not written")
    mscale = 0.1 * rope["mscale_all_dim"] * math.log(rope["factor"]) + 1.0
    return (cfg["qk_nope"] + cfg["qk_rope"]) ** -0.5 * mscale * mscale


def _rotate(x, cos, sin):
    """x (T, ..., rope) float32; cos, sin (T, rope/2)."""
    shape = cos.shape[:1] + (1,) * (x.ndim - 2) + cos.shape[1:]
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _mm(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _latent(cfg, lp, x, cos, sin):
    """What both attention forms share, for tokens x (T, D): the normed
    input's queries (q_nope (T, H, nope), q_rope rotated (T, H, rope), both
    bf16) and the cache row ``[c || k_r || 0]`` (T, cache_row_width)
    bf16. A layer with ``q_a_w`` makes its queries from a normed latent;
    ``cfg["latent_scales"]`` scales the normed latents (module docstring)."""
    heads, nope, rank = cfg["num_heads"], cfg["qk_nope"], cfg["kv_rank"]
    d, scaled = x.shape[1], cfg.get("latent_scales", False)
    h = rms_norm(x, lp["attn_norm"], cfg["rms_eps"]).astype(x.dtype)
    if "q_a_w" in lp:
        cq = rms_norm(_mm(h, lp["q_a_w"]), lp["q_norm"], cfg["rms_eps"])
        if scaled:
            cq = cq * (d / cq.shape[1]) ** 0.5
        q_in, q_w = cq.astype(x.dtype), lp["q_b_w"]
    else:
        q_in, q_w = h, lp["q_w"]
    q = jnp.einsum("td,ed->te", q_in, q_w,     # (out, in): as it lies, no
                   preferred_element_type=jnp.float32)      # transposed copy
    q = q.reshape(x.shape[0], heads, -1)
    kva = _mm(h, lp["kva_w"])
    c = rms_norm(kva[:, :rank], lp["kv_norm"], cfg["rms_eps"])
    if scaled:
        c = c * (d / rank) ** 0.5
    row = jnp.concatenate([c, _rotate(kva[:, rank:], cos, sin)], axis=-1)
    row = jnp.pad(row, ((0, 0), (0, cache_row_width(cfg) - row.shape[1])))
    return (q[..., :nope].astype(x.dtype),
            _rotate(q[..., nope:], cos, sin).astype(x.dtype),
            row.astype(x.dtype))


def cache_row_width(cfg: dict) -> int:
    """``kv_rank + qk_rope`` rounded up to whole 128-lane tiles."""
    return -(-(cfg["kv_rank"] + cfg["qk_rope"]) // 128) * 128


def _mlp(cfg, lp, x, live, experts):
    """x + MLP(RMSNorm(x)); (x', counters or None). ``experts`` is None for
    a dense layer, else (the experts' arrays, this layer's index among the
    expert layers)."""
    h = rms_norm(x, lp["mlp_norm"], cfg["rms_eps"]).astype(x.dtype)
    if experts is None:
        y, counters = moe.gated_mlp(h, lp["gate_w"], lp["up_w"],
                                    lp["down_w"]), None
    else:
        held = cfg["experts_held"]
        y, counters = moe.expert_layer(
            h, lp, experts[0], live, first=cfg["experts_first"], held=held,
            k=cfg["experts_per_token"], scale=cfg["routed_scale"],
            offset=experts[1] * held)
    return (x.astype(jnp.float32) + y).astype(x.dtype), counters


def _head_gate(cfg, lp, x, o):
    """The attention halves' **head-wise output gate**
    (``models/kda_mla_moe.py``'s layers have it). o (T, H, v), every head's
    attention output for tokens x (T, D), times ``sigmoid(RMSNorm(x) . W_og)``
    (T, H) where the layer has the leaf (``og_w`` (hidden, H)); else o
    itself: a layer without it traces not one operation more."""
    if "og_w" not in lp:
        return o
    h = rms_norm(x, lp["attn_norm"], cfg["rms_eps"]).astype(x.dtype)
    gate = jax.nn.sigmoid(_mm(h, lp["og_w"]))
    return (o.astype(jnp.float32) * gate[:, :, None]).astype(o.dtype)


def prefill_attention(cfg, lp, x, cos, sin):
    """``x + Attn(RMSNorm(x))`` over a prompt x (S, D), expanded through the
    flash forward. Returns (x', rows (S, R))."""
    rank = cfg["kv_rank"]
    q_nope, q_rope, row = _latent(cfg, lp, x, cos, sin)
    c, k_r = row[:, :rank], row[:, rank:rank + cfg["qk_rope"]]
    k_nope = jnp.einsum("sc,hnc->hsn", c, lp["uk_w"],
                        preferred_element_type=jnp.float32).astype(x.dtype)
    v = jnp.einsum("sc,hcv->hsv", c, lp["uv_w"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[None], k_nope.shape[:2] + k_r.shape[1:])],
        axis=-1)
    q = jnp.swapaxes(jnp.concatenate([q_nope, q_rope], axis=-1), 0, 1)
    o = flash_attention(q[None], k[None], v[None], causal=True,
                        scale=softmax_scale(cfg))[0]          # (H, S, v)
    o = _head_gate(cfg, lp, x, jnp.swapaxes(o, 0, 1)).reshape(x.shape[0], -1)
    return (x.astype(jnp.float32) + _mm(o, lp["o_w"])).astype(x.dtype), row


def decode_attention(cfg, lp, x, cos, sin, attend):
    """``x + Attn(RMSNorm(x))`` for one new position per sequence, x (B, D),
    absorbed: ``attend(query (B, H, R), row (B, R)) -> u (B, H, kv_rank)``
    writes the row into the cache and attends over the cached rows."""
    q_nope, q_rope, row = _latent(cfg, lp, x, cos, sin)
    # heads lead in both per-head products: the batch axis of a dot first
    # (XLA:CPU cannot run a bfloat16 dot whose batch axis is not)
    q_abs = jnp.swapaxes(jnp.einsum(
        "hbn,hnc->hbc", jnp.swapaxes(q_nope, 0, 1), lp["uk_w"],
        preferred_element_type=jnp.float32), 0, 1).astype(x.dtype)
    query = jnp.concatenate([q_abs, q_rope], axis=-1)
    query = jnp.pad(query, ((0, 0), (0, 0), (0, row.shape[1] - query.shape[2])))
    u = attend(query, row)
    o = jnp.swapaxes(jnp.einsum(
        "hbc,hcv->hbv", jnp.swapaxes(u, 0, 1), lp["uv_w"],
        preferred_element_type=jnp.float32), 0, 1).astype(x.dtype)
    return (x.astype(jnp.float32) + _mm(_head_gate(cfg, lp, x, o).reshape(
        x.shape[0], -1), lp["o_w"])).astype(x.dtype)


def prefill_layer(cfg, lp, x, cos, sin, live, experts):
    """One block over a prompt x (S, D). Returns (x', rows (S, R),
    counters)."""
    x, row = prefill_attention(cfg, lp, x, cos, sin)
    x, counters = _mlp(cfg, lp, x, live, experts)
    return x, row, counters


def decode_layer(cfg, lp, x, cos, sin, live, experts, attend):
    """One block for one new position per sequence, x (B, D)."""
    return _mlp(cfg, lp, decode_attention(cfg, lp, x, cos, sin, attend), live,
                experts)


class MLAMoEDecodeModel:
    """The model as ``DecodeEngine`` takes one (``serve/decode.py``, "the
    model by interface"). ``params`` default to ``init_params(cfg, seed)``."""

    def __init__(self, cfg: dict, seed: int = 0, params: dict = None):
        self.cfg = dict(cfg)
        self.layers = int(cfg["num_layers"])
        self.cache_row = (cache_row_width(cfg),)
        self.params = init_params(cfg, seed) if params is None else params
        # bfloat16, as the weights (a float32 tree, as the tests make one,
        # runs the same bodies in float32)
        self.cache_dtype = self.params["embed"].dtype
        self._inv_freq = yarn_inv_freq(cfg["rope"], cfg["qk_rope"])

    def _angles(self, positions):
        angle = positions.astype(jnp.float32)[:, None] * self._inv_freq[None]
        return jnp.cos(angle), jnp.sin(angle)

    def _head(self, params, x):
        h = rms_norm(x, params["final_norm"], self.cfg["rms_eps"])
        return jnp.einsum("...d,vd->...v", h.astype(x.dtype), params["head"],
                          preferred_element_type=jnp.float32)

    def prefill(self, params, tokens, length):
        """tokens (1, S), length () -> (logits at ``length - 1`` (V,)
        float32, rows (layers, S, R), counters). The dense layers apart, the
        expert layers under one ``lax.scan`` over their stacked weights."""
        cfg = self.cfg
        s = tokens.shape[1]
        positions = jnp.arange(s)
        cos, sin = self._angles(positions)
        live = positions < length
        x = params["embed"][tokens[0]]
        rows = []
        for i in range(cfg["first_dense"]):
            lp = {k: w[i] for k, w in params["dense"].items()}
            x, row, _ = prefill_layer(cfg, lp, x, cos, sin, live, None)
            rows.append(row[None])

        def layer(x, xs):
            lp, j = xs
            x, row, counters = prefill_layer(cfg, lp, x, cos, sin, live,
                                             (params["experts"], j))
            return x, (row, counters)

        n_moe = self.layers - cfg["first_dense"]
        x, (moe_rows, counters) = lax.scan(
            layer, x, (params["moe"], jnp.arange(n_moe)))
        logits = self._head(params, x[length - 1])
        return (logits, jnp.concatenate(rows + [moe_rows]),
                moe.merge_counters(counters))

    def step(self, params, tokens, positions, live, attend):
        """tokens, positions (B,), live (B,) bool; ``attend(layer, query,
        row) -> u``. Returns (logits (B, V) float32, counters)."""
        cfg = self.cfg
        cos, sin = self._angles(positions)
        x = params["embed"][tokens]
        counters = []
        for i in range(self.layers):
            dense = i < cfg["first_dense"]
            stack = params["dense"] if dense else params["moe"]
            j = i if dense else i - cfg["first_dense"]
            lp = {k: w[j] for k, w in stack.items()}
            x, c = decode_layer(
                cfg, lp, x, cos, sin, live,
                None if dense else (params["experts"], j),
                lambda q, row, _i=i: attend(_i, q, row))
            if c is not None:
                counters.append(c)
        return self._head(params, x), moe.merge_counters(jnp.stack(counters))

    def attention(self, query, pool, layer, page_table, lengths):
        return latent_decode_attention(
            query, pool, layer, page_table, lengths, self.cfg["kv_rank"],
            softmax_scale(self.cfg))

    counters = tuple("moe." + name for name in moe.COUNTERS)

    def moe_row_tile(self, tokens):
        """``DecodeEngine.stats()["moe_row_tile"]``: the row tile the held
        experts' grouped products run a call of ``tokens`` tokens with."""
        return moe.layer_row_tile(tokens, self.cfg["experts_per_token"],
                                  self.cfg["router_experts"], self.cache_dtype)

    # -- a prompt continued from a position. Everything below stands at the
    # end, of the class and of the module, so that what ``models/mla_scmoe.py``
    # traces its programs through keeps its lines.

    @property
    def prefill_from(self):
        """``prefill_from(params, tokens (1, C), start, length, prior,
        state)`` as ``DecodeEngine`` takes one (:meth:`_prefill_from`). It
        continues THIS class's :meth:`prefill`: a subclass with a ``prefill``
        of its own has none (``hasattr`` is false, and its engine prefills
        whole) until it brings its own."""
        if type(self).prefill is not MLAMoEDecodeModel.prefill:
            raise AttributeError("prefill_from")
        return self._prefill_from

    def _prefill_from(self, params, tokens, start, length, prior, state):
        """A prompt continued: tokens (1, C) are positions ``start .. start +
        C - 1`` of a prompt of ``length`` (``start`` () int32, a multiple of
        C), ``prior(layer) -> (T, R)`` the rows of positions 0 .. T - 1 as
        the pool has them (T a multiple of C), of which those ``< start`` are
        read; ``state`` is ``{}``. Returns what :meth:`prefill` returns and
        ``{}``: the logits at ``length - 1`` (of the last piece alone: zeros
        before, and the head's weights not read), the piece's rows, its
        counters."""
        cfg = self.cfg
        c, n_dense = tokens.shape[1], cfg["first_dense"]
        positions = start + jnp.arange(c)
        cos, sin = self._angles(positions)
        live = positions < length
        x = params["embed"][tokens[0]]
        rows = []
        for i in range(n_dense):
            lp = {k: w[i] for k, w in params["dense"].items()}
            x, row = prefill_attention_from(cfg, lp, x, cos, sin, start,
                                            prior(i))
            x, _ = _mlp(cfg, lp, x, live, None)
            rows.append(row[None])

        def layer(x, xs):
            lp, j = xs
            x, row = prefill_attention_from(cfg, lp, x, cos, sin, start,
                                            prior(n_dense + j))
            x, counters = _mlp(cfg, lp, x, live, (params["experts"], j))
            return x, (row, counters)

        x, (moe_rows, counters) = lax.scan(
            layer, x, (params["moe"], jnp.arange(self.layers - n_dense)))
        logits = lax.cond(
            start + c >= length, lambda h: self._head(params, h),
            lambda h: jnp.zeros((cfg["vocab_size"],), jnp.float32),
            x[jnp.clip(length - 1 - start, 0, c - 1)])
        return (logits, jnp.concatenate(rows + [moe_rows]),
                moe.merge_counters(counters), {})


def prefill_attention_from(cfg, lp, x, cos, sin, start, before):
    """:func:`prefill_attention` for a piece x (C, D) of a prompt, positions
    ``start .. start + C - 1`` (``start`` traced, a multiple of C), over the
    rows of every position so far: ``before`` (T, R), the pool's rows of
    positions 0 .. T - 1, of which those ``< start`` are read — what lies at
    and behind the piece there is whatever the pool held (memory no program
    wrote, NaN in the tests), so it is written over or never looked at, not
    multiplied by zero. Keys and values are expanded from the bfloat16 rows,
    as the whole prefill expands them, inside the kernel and block by block
    up to the piece's own: the work follows ``start``, not T. Returns (x',
    the piece's rows (C, R))."""
    from ..ops.flash_attention import latent_flash_attention_from

    q_nope, q_rope, row = _latent(cfg, lp, x, cos, sin)
    q = jnp.swapaxes(jnp.concatenate([q_nope, q_rope], axis=-1), 0, 1)
    o = latent_flash_attention_from(
        q, lax.dynamic_update_slice(before, row, (start, 0)), lp["uk_w"],
        lp["uv_w"], start, softmax_scale(cfg))                  # (H, C, v)
    o = _head_gate(cfg, lp, x, jnp.swapaxes(o, 0, 1)).reshape(x.shape[0], -1)
    return (x.astype(jnp.float32) + _mm(o, lp["o_w"])).astype(x.dtype), row


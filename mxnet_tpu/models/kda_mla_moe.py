"""A functional decoder whose layers are Kimi delta attention (KDA: the delta
rule with a decay per key channel) with a latent-attention (MLA) layer
closing every group, a few leading dense MLPs and group-limited routed experts
after them (the language model of Ling-3.0-flash-VL), for the decode engine
(``serve/decode.py``). Not a gluon block and not imported by
``mxnet_tpu.models``: import it where it is used. The equations are written
out in ``benchmark/reference_kda_mla_moe.py``.

Pre-norm residual blocks, ``RMSNorm(x) = x rsqrt(mean x^2 + eps) w``.
``cfg["layers"]`` lists the layers held by their index in the WHOLE model
(a cut keeps some; the seeded weights and the kinds follow that index): layer
``l`` is a latent-attention layer where ``(l + 1) % group_size == 0``, else a
KDA layer; its MLP is a SiLU-gated one of ``dense_width`` where ``l <
first_dense``, else ``ops/moe.py``'s expert layer (sigmoid router over
``router_experts`` with a choosing bias, ``router_groups_kept`` of
``router_groups`` groups a token, the chip's share ``experts_first .. +
experts_held``, one ungated shared expert); an untied head.

- **KDA**, ``h = RMSNorm(x)``, H heads of ``kda_key_dim`` x ``kda_value_dim``:
  ``[q || k || v]`` = three projections through ONE causal depthwise
  convolution (``ops.gated_delta.causal_conv``) and SiLU; ``q, k`` l2-normed a
  head, q scaled; log decay ``g = lower_bound . sigmoid(exp(A_log) (h.W_a +
  dt_bias))`` per head and key channel; ``beta = sigmoid(h.W_b)``; the rule
  (``ops/kda.py``); ``y = (RMSNorm_head(o) . sigmoid(h.W_g)) . W_o``. The five
  projections are one matrix ``in_w = [q || k || v || a || g || b]``.
- **MLA**: ``models/mla_moe.py``'s attention halves (no query latent, plain
  RoPE) with a head-wise output gate (``og_w``).

**Two kinds of cache.** An MLA layer keeps ``mla_moe``'s latent row a position
in the engine's page pool: only they are paged (``paged_layers``). A KDA layer
keeps a fixed-size state per sequence whatever its length — ``s``: H x dk x
dv float32, and ``tail``: the convolution's last ``conv_width - 1`` inputs —,
declared as ``state`` and held by the engine per slot beside the pool: a
prefill returns its slot's, the step is handed all of them with ``live`` and
returns them updated in place.

- **prefill / prefill_from**: the layers unrolled, every layer's leaves
  arrays of their own (no program slices a layer out of a stack). A piece is
  positions ``start .. start + C - 1``, C a multiple of the rule's chunk: a
  KDA layer goes on from the state and tail the piece before left
  (``ops.kda.kda_chunked``: the ``kda_prefill`` kernel on a TPU at
  lane-wide heads, XLA einsums elsewhere; ``delta_rule()`` says which;
  positions past the prompt masked out of the state), an MLA layer reads
  the pool's rows before the piece
  (``mla_moe.prefill_attention_from``).
- **step**: one token a slot; ``ops.kda.kda_step`` (the ``kda_decode``
  kernel: one read and one write of each live slot's state in place) and
  ``mla_moe.decode_attention`` over the pool (the ``mla_decode`` kernel).

Weights and activations are bfloat16 with float32 accumulation; norms, RoPE,
softmax, the router, the gates and the whole delta rule run in float32.

**Seeded weights** (``init_params``): the scheme of ``models/mla_moe.py`` —
every leaf ``0.02 N(0, 1)`` in bfloat16 from random bytes, keyed by seed,
leaf, the layer's index in the whole model (and global expert, or block of
8192 rows of the published vocabulary) — with norm gains and the
convolution's taps ``1 +`` that, an embedding row 50 times it (``N(0, 1)``:
a position's stream is its token's, PERF.md section 6 PR 46), the five
projections into the residual stream an eighth of it (``RESIDUAL``: without
that one vector common to every stream fills the routers' inputs, PERF.md
section 6 PR 50), ``dt_bias = -4 (1 +`` that``)`` and ``A_log = log u``, u one of 256 even steps of [0.5,
2] picked by a random byte from a host-made table: log decays from -0.002 to
-1 a token, by head and by token. The key is an ARGUMENT of the programs that
draw them: a new seed builds nothing. ``benchmark/reference_kda_mla_moe.py``
states the same scheme on its own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import flash_attention, gated_delta, kda, moe
from ..ops.flash_attention import (decode_attention_impl,
                                   latent_decode_attention)
from . import mla_moe
from .mla_moe import _normal_bf16

__all__ = ["config_from_hf", "leaf_shapes", "count_params", "init_params",
           "KDAMLAMoEDecodeModel"]

LEAVES = ("embed", "head", "final_norm", "attn_norm", "mlp_norm",
          "kq_w", "kk_w", "kv_w", "ka_w", "kg_w", "kb_w", "conv_w", "A_log",
          "dt_bias", "gnorm", "ko_w",
          "q_w", "kva_w", "kv_norm", "uk_w", "uv_w", "og_w", "o_w",
          "gate_w", "up_w", "down_w", "router_w", "router_b",
          "shared_gate_w", "shared_up_w", "shared_down_w",
          "experts_gate_w", "experts_up_w", "experts_down_w")
ONE_PLUS = ("final_norm", "attn_norm", "mlp_norm", "kv_norm", "gnorm",
            "conv_w")
KDA_IN = ("kq_w", "kk_w", "kv_w", "ka_w", "kg_w", "kb_w")   # in_w, in order
KDA = ("conv_w", "A_log", "dt_bias", "gnorm", "ko_w")
MLA = ("q_w", "kva_w", "kv_norm", "uk_w", "uv_w", "og_w", "o_w")
DENSE = ("gate_w", "up_w", "down_w")
ROUTED = ("router_w", "router_b", "shared_gate_w", "shared_up_w",
          "shared_down_w")
EXPERTS = ("experts_gate_w", "experts_up_w", "experts_down_w")
VOCAB_BLOCK = 8192
EMBED_SCALE = 50.0      # 0.02 N(0, 1) x 50: an embedding row is N(0, 1)
# the projections that write into the residual stream, at an eighth: the
# depth-scaled init of GPT-2 and Megatron, 1 / sqrt(2 x 32) of the model's 42
# layers' 84 as the nearest power of two — exact in bfloat16 whatever a
# program fuses. Unscaled, every KDA layer adds one vector COMMON to all
# streams (silu keeps q, k and v positive in the mean, so the normed read-out
# is near all-ones times the gate's mean): 19 % of the first router's input
# and 40 % of the last one's, and every token favours the same experts
RESIDUAL = ("ko_w", "o_w", "down_w", "shared_down_w", "experts_down_w")
RESIDUAL_SCALE = 0.125
DT_BIAS_SCALE = -4.0
A_LOG_TABLE = np.log(0.5 + np.arange(256) * (1.5 / 255.0)).astype(np.float32)
COUNTERS = tuple("moe." + name for name in moe.GROUP_COUNTERS) + (
    "kda.tokens",)


def config_from_hf(hf: dict, *, experts_first: int = 0,
                   experts_held: int = None, vocab_first: int = 0,
                   vocab_rows: int = None, layers=None,
                   max_length: int = None) -> dict:
    """The model's description from the language model's ``config.json`` of
    Ling-3.0-flash-VL (the catalog's ``config``, uncut), and the share held
    here: experts ``experts_first .. + experts_held`` of ``num_experts``,
    vocabulary rows ``vocab_first .. + vocab_rows``, and ``layers``, the
    indices kept of ``num_hidden_layers``."""
    if (hf["score_function"], hf["norm_topk_prob"],
            hf["moe_router_enable_expert_bias"]) != ("sigmoid", True, True):
        raise NotImplementedError("a router other than sigmoid scores with "
                                  "a choosing bias, renormalised")
    if hf["q_lora_rank"] or hf["use_mla_nope"] or not hf["kda_safe_gate"]:
        raise NotImplementedError("a query latent, MLA without RoPE, or the "
                                  "unbounded KDA gate: not written")
    if (hf["gated_attention_proj_granularity_type"], hf["group_norm_size"],
            hf["no_kda_lora"], hf["linear_silu"]) != ("head_wise", 1, True,
                                                      True):
        raise NotImplementedError("an output gate other than head-wise, a "
                                  "grouped head norm, low-rank KDA gates")
    if hf["moe_shared_expert_intermediate_size"] != hf["moe_intermediate_size"]:
        raise NotImplementedError("a shared expert wider than a routed one")
    layers = list(range(hf["num_hidden_layers"]) if layers is None else layers)
    clamps = (hf["expert_swiglu_limit_list"], hf["share_expert_swiglu_limit_list"])
    if any(limits[i] for limits in clamps for i in layers):
        raise NotImplementedError("a layer whose experts clamp their SwiGLU")
    return {
        "vocab_size": vocab_rows or hf["vocab_size"],
        "vocab_first": vocab_first, "hidden_size": hf["hidden_size"],
        "num_layers": len(layers), "layers": layers,
        "group_size": hf["layer_group_size"],
        "first_dense": hf["first_k_dense_replace"],
        "num_heads": hf["num_attention_heads"],
        "kda_key_dim": hf["head_dim"], "kda_value_dim": hf["head_dim"],
        "conv_width": hf["short_conv_kernel_size"],
        "gate_lower_bound": hf["kda_lower_bound"],
        "qk_nope": hf["qk_nope_head_dim"], "qk_rope": hf["qk_rope_head_dim"],
        "v_head": hf["v_head_dim"], "kv_rank": hf["kv_lora_rank"],
        "rope": {"theta": hf["rope_theta"], "factor": 1},
        "dense_width": hf["intermediate_size"],
        "expert_width": hf["moe_intermediate_size"],
        "router_experts": hf["num_experts"],
        "experts_first": experts_first,
        "experts_held": experts_held or hf["num_experts"],
        "experts_per_token": hf["num_experts_per_tok"],
        "routed_scale": hf["routed_scaling_factor"],
        "router_groups": hf["n_group"], "router_groups_kept": hf["topk_group"],
        "rms_eps": hf["rms_norm_eps"],
        "max_length": max_length or hf["max_position_embeddings"],
    }


def leaf_shapes(cfg: dict) -> dict:
    """name -> shape of one layer's leaf (one expert's, for ``experts_*``).
    Matrices are (in, out), but ``q_w`` (out, in)."""
    d, h = cfg["hidden_size"], cfg["num_heads"]
    dk, dv = cfg["kda_key_dim"], cfg["kda_value_dim"]
    nope, rope = cfg["qk_nope"], cfg["qk_rope"]
    vd, r = cfg["v_head"], cfg["kv_rank"]
    f, fe, e = cfg["dense_width"], cfg["expert_width"], cfg["router_experts"]
    return {"final_norm": (d,), "attn_norm": (d,), "mlp_norm": (d,),
            "kq_w": (d, h * dk), "kk_w": (d, h * dk), "kv_w": (d, h * dv),
            "ka_w": (d, h * dk), "kg_w": (d, h * dv), "kb_w": (d, h),
            "conv_w": (cfg["conv_width"], 2 * h * dk + h * dv),
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


def layer_kinds(cfg: dict):
    """(the places among ``cfg["layers"]`` of the KDA layers, of the MLA
    layers, of the expert layers)."""
    held = cfg["layers"]
    if len(held) != cfg["num_layers"]:
        raise ValueError(f"{len(held)} layers listed for {cfg['num_layers']}")
    mla = [i for i, l in enumerate(held) if (l + 1) % cfg["group_size"] == 0]
    return ([i for i in range(len(held)) if i not in mla], mla,
            [i for i, l in enumerate(held) if l >= cfg["first_dense"]])


def count_params(cfg: dict) -> dict:
    """Parameters held, by part, from :func:`leaf_shapes`: ``kda`` and
    ``mla`` (one mixer with its norm), ``dense`` (one dense MLP with its
    norm), ``routed`` (an expert layer's norm, router and shared expert),
    ``expert`` (one), ``vocab`` (embedding, head, final norm), ``total``."""
    shapes = leaf_shapes(cfg)

    def size(*names):
        return sum(int(np.prod(shapes[n])) for n in names)

    n_kda, n_mla, n_routed = (len(k) for k in layer_kinds(cfg))
    out = {"kda": size("attn_norm", *KDA_IN, *KDA),
           "mla": size("attn_norm", *MLA),
           "dense": size("mlp_norm", *DENSE),
           "routed": size("mlp_norm", *ROUTED), "expert": size(*EXPERTS),
           "vocab": 2 * cfg["vocab_size"] * cfg["hidden_size"]
           + size("final_norm")}
    out["total"] = (n_kda * out["kda"] + n_mla * out["mla"]
                    + (cfg["num_layers"] - n_routed) * out["dense"]
                    + n_routed * (out["routed"]
                                  + cfg["experts_held"] * out["expert"])
                    + out["vocab"])
    return out


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
    return x


def init_params(cfg: dict, seed: int) -> dict:
    """The seeded weights on the default device (module docstring):
    ``embed``, ``head``, ``final_norm``; ``layers``, a list with every held
    layer's own leaves — ``attn_norm``, ``mlp_norm``; a KDA layer's ``in_w``
    (the six projections side by side), ``conv_w``, ``A_log`` (float32),
    ``dt_bias``, ``gnorm``, ``ko_w``; an MLA layer's ``q_w``, ``kva_w``,
    ``kv_norm``, ``uk_w``, ``uv_w``, ``og_w``, ``o_w``; a dense layer's
    ``gate_w``, ``up_w``, ``down_w``, an expert layer's ``router_w``,
    ``router_b``, ``shared_*`` — and ``experts``: ``gate_w``, ``up_w``,
    ``down_w`` with every expert layer's held experts on ONE leading axis
    (layer-major), filled in place, as the grouped kernel takes them. The
    key, and a layer's index in the whole model, are arguments of the
    programs: a kind of layer is one program."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)
    shapes = leaf_shapes(cfg)
    v, d = cfg["vocab_size"], cfg["hidden_size"]
    first, held = cfg["experts_first"], cfg["experts_held"]
    _, mla, routed = layer_kinds(cfg)

    def vocab(key, name):   # rows vocab_first .. of whole blocks of 8192
        origin = cfg.get("vocab_first", 0)
        blocks = range(origin // VOCAB_BLOCK, -(-(origin + v) // VOCAB_BLOCK))
        table = jnp.concatenate([_draw(key, name, (VOCAB_BLOCK, d), b)
                                 for b in blocks])
        start = origin - blocks[0] * VOCAB_BLOCK
        return table[start:start + v]

    def top(key):
        return {"embed": vocab(key, "embed"), "head": vocab(key, "head"),
                "final_norm": _draw(key, "final_norm", (d,))}

    def layer(key, l, is_mla, is_routed):
        names = ("attn_norm", "mlp_norm") + (MLA if is_mla else KDA) + (
            ROUTED if is_routed else DENSE)
        out = {name: _draw(key, name, shapes[name], l) for name in names}
        if not is_mla:
            out["in_w"] = jnp.concatenate(
                [_draw(key, name, shapes[name], l) for name in KDA_IN],
                axis=-1)
        return out

    def experts(name, key):
        at = jnp.asarray([cfg["layers"][i] for i in routed], jnp.int32)

        def one(i, buf):
            w = _draw(key, name, shapes[name], at[i // held],
                      first + i % held)
            return lax.dynamic_update_slice(buf, w[None], (i, 0, 0))

        return lax.fori_loop(0, len(routed) * held, one,
                             jnp.zeros((len(routed) * held,) + shapes[name],
                                       jnp.bfloat16))

    params = jax.jit(top)(key)
    make = jax.jit(layer, static_argnums=(2, 3))
    params["layers"] = [make(key, l, i in mla, i in routed)
                        for i, l in enumerate(cfg["layers"])]
    make = jax.jit(experts, static_argnums=0)
    params["experts"] = {name[len("experts_"):]: make(name, key)
                         for name in EXPERTS}
    return params


# -- the layers -----------------------------------------------------------------

rms_norm = mla_moe.rms_norm
_mm = mla_moe._mm


def _kda_inputs(cfg, lp, x):
    """The normed input's projections: (qkv (T, 3 H dk) in x's dtype — what
    the convolution sees and its tail keeps —, a (T, H, dk), gate (T, H, dv),
    b (T, H) float32)."""
    t, h = x.shape[0], cfg["num_heads"]
    wide = h * cfg["kda_key_dim"]
    p = _mm(rms_norm(x, lp["attn_norm"], cfg["rms_eps"]).astype(x.dtype),
            lp["in_w"])
    return (p[:, :3 * wide].astype(x.dtype),
            p[:, 3 * wide:4 * wide].reshape(t, h, -1),
            p[:, 4 * wide:5 * wide].reshape(t, h, -1), p[:, 5 * wide:])


def _conv_step(x, tail, w):
    """``gated_delta.causal_conv_step`` on the tail as the state holds it: x
    (B, C) one new input a sequence, tail (B, rows, lanes) its last ``W - 1``
    inputs, each folded to ``C / lanes`` rows (whole tiles at the published
    size), w (W, C) -> (out (B, C) float32, new tail). Every array keeps the
    fold, so the slot axis stays major: on the unfolded (B, W - 1, C) XLA:TPU
    wants the 3-row axis out of the tile, lays the tail out slot-minor and
    copies the whole per-slot array of every layer there and back, a layer a
    step (22 copies of 105 MB at 128 slots, compile-only, PR 50)."""
    b, lanes = x.shape[0], tail.shape[-1]
    rows = x.shape[1] // lanes
    window = jnp.concatenate([tail.reshape(b, -1, rows, lanes),
                              x.reshape(b, 1, rows, lanes).astype(tail.dtype)],
                             axis=1)
    out = jnp.sum(window.astype(jnp.float32)
                  * w.astype(jnp.float32).reshape(1, -1, rows, lanes), axis=1)
    return out.reshape(b, -1), window[:, 1:].reshape(tail.shape)


def _kda_heads(cfg, lp, conv, a, b):
    """From the convolution's output (T, 3 H dk) float32: q, k (T, H, dk) —
    l2 normed, q scaled —, v (T, H, dv), the log decay g (T, H, dk) in
    (lower_bound, 0), beta (T, H); float32."""
    h, dk, t = cfg["num_heads"], cfg["kda_key_dim"], conv.shape[0]
    x = jax.nn.silu(conv)

    def l2(u):
        return u * lax.rsqrt(jnp.sum(u * u, -1, keepdims=True) + 1e-6)

    rate = jnp.exp(lp["A_log"].astype(jnp.float32))[None, :, None]
    g = cfg["gate_lower_bound"] * jax.nn.sigmoid(
        rate * (a + lp["dt_bias"].astype(jnp.float32).reshape(1, h, dk)))
    return (l2(x[:, :h * dk].reshape(t, h, dk)) * dk ** -0.5,
            l2(x[:, h * dk:2 * h * dk].reshape(t, h, dk)),
            x[:, 2 * h * dk:].reshape(t, h, -1), g, jax.nn.sigmoid(b))


def _kda_output(cfg, lp, x, o, gate):
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg["rms_eps"])
    y = lp["gnorm"].astype(jnp.float32) * o * jax.nn.sigmoid(gate)
    y = y.reshape(x.shape[0], -1).astype(x.dtype)
    return (x.astype(jnp.float32) + _mm(y, lp["ko_w"])).astype(x.dtype)


class KDAMLAMoEDecodeModel:
    """The model as ``DecodeEngine`` takes one (``serve/decode.py``, "the
    model by interface"): the MLA layers' latent rows in the pool, the KDA
    layers' states and convolution tails per slot. ``params`` default to
    ``init_params(cfg, seed)``."""

    counters = COUNTERS

    def __init__(self, cfg: dict, seed: int = 0, params: dict = None):
        self.cfg = dict(cfg)
        self.layers = int(cfg["num_layers"])
        self.kda_layers, self.mla_layers, self.expert_layers = layer_kinds(cfg)
        self.paged_layers = len(self.mla_layers)
        self.cache_row = (mla_moe.cache_row_width(cfg),)
        self.params = init_params(cfg, seed) if params is None else params
        # bfloat16, as the weights (a float32 tree, as the tests make one,
        # runs the same bodies in float32)
        self.cache_dtype = self.params["embed"].dtype
        n, h = len(self.kda_layers), cfg["num_heads"]
        self._tail = (cfg["conv_width"] - 1, leaf_shapes(cfg)["conv_w"][1])
        # the tail's (3, 12288) as (288, 128): whole 16 x 128 tiles
        # (models/gdn_moe.py: a 3-row minor tile is laid out by the client
        # as no program was compiled for)
        values = self._tail[0] * self._tail[1]
        folded = (values // 128, 128) if values % (16 * 128) == 0 else self._tail
        self.state = {
            "s": ((n, h, cfg["kda_key_dim"], cfg["kda_value_dim"]),
                  jnp.float32),
            "tail": ((n,) + folded, self.cache_dtype)}
        self._inv_freq = mla_moe.yarn_inv_freq(cfg["rope"], cfg["qk_rope"])

    def _angles(self, positions):
        angle = positions.astype(jnp.float32)[:, None] * self._inv_freq[None]
        return jnp.cos(angle), jnp.sin(angle)

    def _head(self, params, x):
        h = rms_norm(x, params["final_norm"], self.cfg["rms_eps"])
        return jnp.einsum("...d,vd->...v", h.astype(x.dtype), params["head"],
                          preferred_element_type=jnp.float32)

    def _mlp(self, params, i, x, live):
        """x + MLP(RMSNorm(x)) of held layer ``i``; (x', counters or
        None)."""
        cfg, lp = self.cfg, params["layers"][i]
        h = rms_norm(x, lp["mlp_norm"], cfg["rms_eps"]).astype(x.dtype)
        if i in self.expert_layers:
            held = cfg["experts_held"]
            y, counters = moe.expert_layer(
                h, {name: lp[name] for name in ROUTED}, params["experts"],
                live, first=cfg["experts_first"], held=held,
                k=cfg["experts_per_token"], scale=cfg["routed_scale"],
                groups=cfg["router_groups"],
                groups_kept=cfg["router_groups_kept"],
                offset=self.expert_layers.index(i) * held)
        else:
            y, counters = moe.gated_mlp(h, lp["gate_w"], lp["up_w"],
                                        lp["down_w"]), None
        return (x.astype(jnp.float32) + y).astype(x.dtype), counters

    def _counted(self, counters, live):
        """The expert layers' counters, then ``kda.tokens``: the call's live
        tokens x the KDA layers (what ``kda_decode`` or the chunked rule ran
        for)."""
        tokens = jnp.sum(live, dtype=jnp.int32) * len(self.kda_layers)
        return jnp.concatenate([moe.merge_counters(jnp.stack(counters)),
                                tokens[None]])

    def prefill(self, params, tokens, length):
        """tokens (1, S), length () -> (logits at ``length - 1`` (V,)
        float32, rows (paged layers, S, row), counters, the sequence's state
        ``{"s", "tail"}`` after ``length`` tokens)."""
        return self._prompt(params, tokens, 0, length, None, None)

    def prefill_from(self, params, tokens, start, length, prior, state):
        """A prompt continued: tokens (1, C) are positions ``start .. start +
        C - 1`` of a prompt of ``length`` (``start`` () int32, a multiple of
        C; C a multiple of the rule's chunk, so that the pieces cut the
        prompt where the rule's own scan cuts it), ``state`` the sequence's
        own ``{"s", "tail"}`` as the piece before left it — taken for zeros
        where ``start`` is 0, whatever it holds —, and ``prior(paged layer)
        -> (T, row)`` the rows of positions 0 .. T - 1 as the pool has them,
        of which those ``< start`` are read, by the MLA layers alone. Returns
        what :meth:`prefill` returns: the logits at ``length - 1`` (of the
        last piece alone: zeros before, and the head's weights not read),
        the piece's rows, its counters, the state after the piece."""
        return self._prompt(params, tokens, start, length, prior, state)

    def _prompt(self, params, tokens, start, length, prior, state):
        """The body of :meth:`prefill` (``prior`` None: the whole prompt
        from position 0) and :meth:`prefill_from`."""
        cfg = self.cfg
        c = tokens.shape[1]
        positions = start + jnp.arange(c)
        cos, sin = self._angles(positions)
        live = positions < length
        x = params["embed"][tokens[0]]
        rows, states, tails, counters = [], [], [], []
        for i in range(self.layers):
            lp = params["layers"][i]
            if i in self.mla_layers:
                if prior is None:
                    x, row = mla_moe.prefill_attention(cfg, lp, x, cos, sin)
                else:
                    x, row = mla_moe.prefill_attention_from(
                        cfg, lp, x, cos, sin, start,
                        prior(self.mla_layers.index(i)))
                rows.append(row)
            else:
                s0 = tail0 = None
                if state is not None:
                    # a slot's leftovers never reach a new prompt: where(),
                    # not a product, so that not even a NaN does
                    j = self.kda_layers.index(i)
                    s0 = jnp.where(start == 0, 0, state["s"][j])
                    tail0 = jnp.where(start == 0, 0, state["tail"][j]).astype(
                        x.dtype).reshape(self._tail)
                qkv, a, gate, b = _kda_inputs(cfg, lp, x)
                conv, tail = gated_delta.causal_conv(qkv, lp["conv_w"],
                                                     length - start, tail0)
                q, k, v, g, beta = _kda_heads(cfg, lp, conv, a, b)
                # a position past the prompt writes nothing into the state
                g = jnp.where(live[:, None, None], g, 0.0)
                beta = jnp.where(live[:, None], beta, 0.0)
                o, s1 = kda.kda_chunked(
                    q, k, v, g, beta, s0, impl=decode_attention_impl(),
                    interpret=flash_attention._use_interpret())
                x = _kda_output(cfg, lp, x, o, gate)
                states.append(s1)
                tails.append(tail.reshape(self.state["tail"][0][1:]))
            x, counted = self._mlp(params, i, x, live)
            if counted is not None:
                counters.append(counted)
        logits = lax.cond(
            start + c >= length, lambda h: self._head(params, h),
            lambda h: jnp.zeros((cfg["vocab_size"],), jnp.float32),
            x[jnp.clip(length - 1 - start, 0, c - 1)])
        return (logits, jnp.stack(rows), self._counted(counters, live),
                {"s": jnp.stack(states), "tail": jnp.stack(tails)})

    def step(self, params, tokens, positions, live, attend, state):
        """tokens, positions (B,), live (B,) bool; ``attend(paged layer,
        query, row) -> u``; ``state``: every slot's ``s`` and ``tail``
        (slots + 1 leading, the last scratch). Returns (logits (B, V)
        float32, counters, state) — the state of a slot that is not live
        untouched."""
        cfg = self.cfg
        b = tokens.shape[0]
        cos, sin = self._angles(positions)
        x = params["embed"][tokens]
        s_all, tails = state["s"], state["tail"]
        impl = "pallas" if decode_attention_impl() == "pallas" else "xla"
        counters = []
        for i in range(self.layers):
            lp = params["layers"][i]
            if i in self.mla_layers:
                j = self.mla_layers.index(i)
                x = mla_moe.decode_attention(
                    cfg, lp, x, cos, sin,
                    lambda q, row, _j=j: attend(_j, q, row))
            else:
                j = self.kda_layers.index(i)
                qkv, a, gate, bb = _kda_inputs(cfg, lp, x)
                old = tails[:b, j]
                conv, new = _conv_step(qkv, old, lp["conv_w"])
                tails = tails.at[:b, j].set(
                    jnp.where(live[:, None, None], new, old))
                q, k, v, g, beta = _kda_heads(cfg, lp, conv, a, bb)
                o, s_all = kda.kda_step(s_all, j, q, k, v, g, beta, live,
                                        impl=impl,
                                        interpret=flash_attention._use_interpret())
                x = _kda_output(cfg, lp, x, o, gate)
            x, counted = self._mlp(params, i, x, live)
            if counted is not None:
                counters.append(counted)
        return (self._head(params, x), self._counted(counters, live),
                {"s": s_all, "tail": tails})

    def attention(self, query, pool, layer, page_table, lengths):
        return latent_decode_attention(
            query, pool, layer, page_table, lengths, self.cfg["kv_rank"],
            mla_moe.softmax_scale(self.cfg))

    def delta_rule(self):
        """``DecodeEngine.stats()["delta_rule"]``: the form the delta rule
        takes over a prompt in this process (``kda.chunked_form``)."""
        return kda.chunked_form(
            self.cfg["kda_key_dim"], self.cfg["kda_value_dim"],
            impl=decode_attention_impl(),
            interpret=flash_attention._use_interpret())

    def moe_row_tile(self, tokens):
        """``DecodeEngine.stats()["moe_row_tile"]``: the row tile the held
        experts' grouped products run a call of ``tokens`` tokens with."""
        return moe.layer_row_tile(tokens, self.cfg["experts_per_token"],
                                  self.cfg["router_experts"], self.cache_dtype)

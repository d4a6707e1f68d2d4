"""A functional decoder of shortcut-connected **double layers** over latent
attention (MLA), for the decode engine (``serve/decode.py``). Not a gluon
block and not imported by ``mxnet_tpu.models``: import it where it is used.

A double layer holds two attention sub-layers, two dense SiLU-gated MLPs and
ONE expert branch that leaves the residual stream after the first attention
and rejoins it after the second MLP (``N`` is RMSNorm with a learned gain):

    a0 = x  + MLA_0(N(x))
    h0 =      N(a0)
    m  =      MoE(h0)                  # the shortcut: leaves here ...
    b0 = a0 + MLP_0(h0)
    a1 = b0 + MLA_1(N(b0))
    x' = a1 + MLP_1(N(a1)) + m         # ... and rejoins here

So the page pool has ``2 x num_layers`` layers (``model.layers``, what
``DecodeEngine`` sizes the pool by) while the expert stacks have
``num_layers``: the two counts are not one number here.

*Attention* is ``models/mla_moe.py``'s two halves (expanded over a prompt,
absorbed in a step, one latent row a position) with a query latent, both
latent scales and plain RoPE (that module's docstring). *The expert branch*
(``ops/moe.py``): ``p = softmax(h.W_r)`` in float32 over ``router_experts`` =
the real experts + ``zero_experts`` identity experts; the
``experts_per_token`` largest of ``p + b`` are chosen; ``g = routed_scale .
p``, not renormalised; a real expert is a SiLU-gated MLP at ``expert_width``,
of which this chip holds ``experts_first .. + experts_held``; an identity
expert adds ``g . h``. No shared expert. An untied head.

**Seeded weights** (``init_params``): ``models/mla_moe.py``'s scheme — every
leaf ``0.02 N(0, 1)`` (norm gains ``1 +`` that) in bfloat16 from random
bytes — with ``key = fold_in(fold_in(fold_in(PRNGKey(seed mod 2**31), seed //
2**31), index of the leaf's name in LEAVES), double layer)``; a sub-layer's
leaves fold in the sub-layer (0, 1) after the double layer, an expert's its
GLOBAL index, the embedding and the head a block of 8192 rows. The router's
choosing bias is that draw divided by ``router_experts``: a softmax over 768
gives probabilities near 1/768 whose 12th and 13th largest lie ~ 4e-4 apart,
so a bias of 0.02 chose the SAME experts for every token (seen on the chip,
PR 44) where one of 0.02 / 768 changes some choices. Every stack is
filled in place on the device, a slice at a time: no second copy of any
stack exists at any moment (the eight dense MLPs of the benchmark's cut are
3.6 GB). ``benchmark/reference_mla_scmoe.py`` states the same scheme on its
own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import moe
from . import mla_moe
from .mla_moe import rms_norm

__all__ = ["config_from_hf", "init_params", "MLAScMoEDecodeModel"]

LEAVES = ("embed", "head", "final_norm", "attn_norm", "q_a_w", "q_norm",
          "q_b_w", "kva_w", "kv_norm", "uk_w", "uv_w", "o_w", "mlp_norm",
          "gate_w", "up_w", "down_w", "router_w", "router_b",
          "experts_gate_w", "experts_up_w", "experts_down_w")
GAINS = ("final_norm", "attn_norm", "q_norm", "kv_norm", "mlp_norm")
# a sub-layer's: its attention, then its dense MLP
SUB = ("attn_norm", "q_a_w", "q_norm", "q_b_w", "kva_w", "kv_norm", "uk_w",
       "uv_w", "o_w", "mlp_norm", "gate_w", "up_w", "down_w")
ROUTER = ("router_w", "router_b")
EXPERTS = ("experts_gate_w", "experts_up_w", "experts_down_w")
VOCAB_BLOCK = mla_moe.VOCAB_BLOCK


def config_from_hf(hf: dict, *, experts_first: int = 0,
                   experts_held: int = None, real_experts: int = None,
                   max_length: int = None) -> dict:
    """The model's description from a LongCat-Flash ``config.json`` (the
    language model's keys). ``real_experts`` is the published count of real
    experts where ``hf["n_routed_experts"]`` has been cut to those held
    here: the router scores them and the ``zero_expert_num`` identity
    experts behind them."""
    if hf["attention_method"] != "MLA" or hf["zero_expert_type"] != "identity":
        raise NotImplementedError(
            f"attention {hf['attention_method']!r} with zero experts of type "
            f"{hf['zero_expert_type']!r}: not written")
    if hf["mla_scale_q_lora"] != hf["mla_scale_kv_lora"]:
        raise NotImplementedError("one latent scaled and not the other: not "
                                  "written")
    return {
        "vocab_size": hf["vocab_size"], "hidden_size": hf["hidden_size"],
        "num_layers": hf["num_layers"],
        "num_heads": hf["num_attention_heads"],
        "qk_nope": hf["qk_nope_head_dim"], "qk_rope": hf["qk_rope_head_dim"],
        "v_head": hf["v_head_dim"], "kv_rank": hf["kv_lora_rank"],
        "q_rank": hf["q_lora_rank"],
        "latent_scales": hf["mla_scale_q_lora"],
        "dense_width": hf["ffn_hidden_size"],
        "expert_width": hf["expert_ffn_hidden_size"],
        "router_experts": ((real_experts or hf["n_routed_experts"])
                           + hf["zero_expert_num"]),
        "zero_experts": hf["zero_expert_num"],
        "experts_first": experts_first,
        "experts_held": experts_held or hf["n_routed_experts"],
        "experts_per_token": hf["moe_topk"],
        "routed_scale": hf["routed_scaling_factor"],
        "rms_eps": hf["rms_norm_eps"],
        "rope": {"theta": hf["rope_theta"], "factor": 1},
        "max_length": max_length or hf["max_position_embeddings"],
    }


def leaf_shapes(cfg: dict) -> dict:
    """name -> shape of one sub-layer's or layer's leaf (one expert's, for
    ``experts_*``). Matrices are (in, out), but ``q_b_w`` (out, in), as
    ``models/mla_moe.py``'s ``q_w``: the layout XLA:TPU multiplies it in (as
    (in, out) every step copied it twice, 38 MB a sub-layer: compile-only,
    PR 44)."""
    d, h = cfg["hidden_size"], cfg["num_heads"]
    nope, rope = cfg["qk_nope"], cfg["qk_rope"]
    vd, r, rq = cfg["v_head"], cfg["kv_rank"], cfg["q_rank"]
    f, fe, e = cfg["dense_width"], cfg["expert_width"], cfg["router_experts"]
    return {"final_norm": (d,), "attn_norm": (d,), "q_a_w": (d, rq),
            "q_norm": (rq,), "q_b_w": (h * (nope + rope), rq),
            "kva_w": (d, r + rope), "kv_norm": (r,), "uk_w": (h, nope, r),
            "uv_w": (h, r, vd), "o_w": (h * vd, d), "mlp_norm": (d,),
            "gate_w": (d, f), "up_w": (d, f), "down_w": (f, d),
            "router_w": (d, e), "router_b": (e,), "experts_gate_w": (d, fe),
            "experts_up_w": (d, fe), "experts_down_w": (fe, d)}


def _draw(key, name, shape, *path):
    key = jax.random.fold_in(key, LEAVES.index(name))
    for i in path:
        key = jax.random.fold_in(key, i)
    x = mla_moe._normal_bf16(key, shape)
    if name in GAINS:
        x = (1.0 + x.astype(jnp.float32)).astype(jnp.bfloat16)
    if name == "router_b":      # in units of the mean probability (docstring)
        x = (x.astype(jnp.float32) / shape[0]).astype(jnp.bfloat16)
    return x


def _filled(key, name, shape, n, path):
    """``(n,) + shape``, slice i drawn along ``path(i)`` and written in
    place: the stack and one slice are all that exists."""
    def one(i, buf):
        return lax.dynamic_update_slice(
            buf, _draw(key, name, shape, *path(i))[None],
            (i,) + (0,) * len(shape))

    return lax.fori_loop(0, n, one, jnp.zeros((n,) + shape, jnp.bfloat16))


def init_params(cfg: dict, seed: int) -> dict:
    """The seeded weights on the default device (see the module docstring):
    ``embed``, ``head``, ``final_norm``; ``sub``, the sub-layers' leaves with
    two leading axes (double layer, sub-layer); ``router`` (``router_w``,
    ``router_b``) with a leading layer axis; ``experts``: ``gate_w``,
    ``up_w``, ``down_w`` with every layer's held experts on ONE leading axis
    (layer-major). A program a leaf, the key its argument: a new seed
    compiles nothing anew."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)
    shapes = leaf_shapes(cfg)
    v, d, n = cfg["vocab_size"], cfg["hidden_size"], cfg["num_layers"]
    first, held = cfg["experts_first"], cfg["experts_held"]

    def vocab(key, name):
        return jnp.concatenate([
            _draw(key, name, (min(VOCAB_BLOCK, v - r), d), r // VOCAB_BLOCK)
            for r in range(0, v, VOCAB_BLOCK)])

    def top(key):
        return {"embed": vocab(key, "embed"), "head": vocab(key, "head"),
                "final_norm": _draw(key, "final_norm", (d,)),
                "router": {name: jnp.stack([_draw(key, name, shapes[name], i)
                                            for i in range(n)])
                           for name in ROUTER}}

    def sub(name, key):
        stack = _filled(key, name, shapes[name], 2 * n,
                        lambda i: (i // 2, i % 2))
        return stack.reshape((n, 2) + shapes[name])

    def experts(name, key):
        return _filled(key, name, shapes[name], n * held,
                       lambda i: (i // held, first + i % held))

    params = jax.jit(top)(key)
    make = jax.jit(sub, static_argnums=0)
    params["sub"] = {name: make(name, key) for name in SUB}
    make = jax.jit(experts, static_argnums=0)
    params["experts"] = {name[len("experts_"):]: make(name, key)
                         for name in EXPERTS}
    return params


def double_layer(cfg, first, second, router, experts, x, live, attention):
    """One double layer over tokens x (T, D) (module docstring).
    ``first``, ``second``: the two sub-layers' leaves; ``experts`` = (the
    experts' arrays, this layer's index among the expert layers);
    ``attention(i, lp, x) -> x + MLA_i(N(x))``. Returns (x', counters)."""
    held = cfg["experts_held"]

    def normed(lp, x):
        return rms_norm(x, lp["mlp_norm"], cfg["rms_eps"]).astype(x.dtype)

    def mlp(lp, h):
        return moe.gated_mlp(h, lp["gate_w"], lp["up_w"], lp["down_w"])

    x = attention(0, first, x)
    h = normed(first, x)
    m, counters = moe.expert_layer(
        h, router, experts[0], live, first=cfg["experts_first"], held=held,
        k=cfg["experts_per_token"], scale=cfg["routed_scale"],
        offset=experts[1] * held, zero_experts=cfg["zero_experts"])
    x = (x.astype(jnp.float32) + mlp(first, h)).astype(x.dtype)
    x = attention(1, second, x)
    x = (x.astype(jnp.float32) + mlp(second, normed(second, x))
         + m).astype(x.dtype)
    return x, counters


class MLAScMoEDecodeModel(mla_moe.MLAMoEDecodeModel):
    """The model as ``DecodeEngine`` takes one (``serve/decode.py``, "the
    model by interface"): ``MLAMoEDecodeModel``'s cache row, angles, head and
    paged read, over double layers. ``params`` default to
    ``init_params(cfg, seed)``."""

    def __init__(self, cfg: dict, seed: int = 0, params: dict = None):
        super().__init__(cfg, seed,
                         init_params(cfg, seed) if params is None else params)
        self.layers = 2 * int(cfg["num_layers"])   # of the pool: sub-layers

    @staticmethod
    def _leaves(params, j):
        """Double layer j as :func:`double_layer` takes it: its two
        sub-layers' leaves, its router's, (the experts' arrays, j). A
        sub-layer is taken with ONE index: ``w[j][i]`` copies both sub-layers'
        weights out (XLA:TPU fuses the first slice and its squeeze into a
        copy of 2 x the leaf), and a ``lax.scan`` over the stacks copies a
        double layer's 1.28 GB an iteration (compile-only, PR 44) — so
        prefill and step both unroll."""
        first, second = ({k: w[j, i] for k, w in params["sub"].items()}
                         for i in (0, 1))
        return (first, second, {k: w[j] for k, w in params["router"].items()},
                (params["experts"], j))

    def prefill(self, params, tokens, length):
        """tokens (1, S), length () -> (logits at ``length - 1`` (V,)
        float32, rows (2 x num_layers, S, R), counters)."""
        cfg = self.cfg
        positions = jnp.arange(tokens.shape[1])
        cos, sin = self._angles(positions)
        live = positions < length
        rows, counters = [], []

        def attention(_, lp, x):
            x, row = mla_moe.prefill_attention(cfg, lp, x, cos, sin)
            rows.append(row)
            return x

        x = params["embed"][tokens[0]]
        for j in range(cfg["num_layers"]):
            x, c = double_layer(cfg, *self._leaves(params, j), x, live,
                                attention)
            counters.append(c)
        return (self._head(params, x[length - 1]), jnp.stack(rows),
                moe.merge_counters(jnp.stack(counters)))

    def step(self, params, tokens, positions, live, attend):
        """tokens, positions (B,), live (B,) bool; ``attend(pool layer,
        query, row) -> u``. Returns (logits (B, V) float32, counters)."""
        cfg = self.cfg
        cos, sin = self._angles(positions)
        x = params["embed"][tokens]
        counters = []
        for j in range(cfg["num_layers"]):
            x, c = double_layer(
                cfg, *self._leaves(params, j), x, live,
                lambda i, lp, x, _j=j: mla_moe.decode_attention(
                    cfg, lp, x, cos, sin,
                    lambda q, row: attend(2 * _j + i, q, row)))
            counters.append(c)
        return self._head(params, x), moe.merge_counters(jnp.stack(counters))

    counters = tuple("moe." + name for name in moe.ZERO_COUNTERS)

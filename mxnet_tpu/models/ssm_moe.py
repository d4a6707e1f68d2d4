"""A functional decoder whose layers are ONE mixer each — a Mamba-2
state-space layer, grouped-KV attention, or an expert layer of two-product
``relu^2`` experts — in the order a pattern string gives (Nemotron-H), for the
decode engine (``serve/decode.py``). Not a gluon block and not imported by
``mxnet_tpu.models``: import it where it is used. The equations are written
out in ``benchmark/reference_ssm_moe.py``.

Pre-norm residual blocks, ``x += mixer_i(RMSNorm_i(x))`` with ``RMSNorm(x) = x
rsqrt(mean x^2 + eps) w`` (a plain gain); ``pattern[i]`` says which mixer:
``M`` Mamba-2, ``*`` attention (no positional embedding, no q/k norm), ``E``
``ops/moe.py``'s expert layer (sigmoid router with a choosing bias over
``router_experts``, the chip's share ``experts_first .. + experts_held``, a
shared expert, no gate matrix anywhere); a final norm and an untied head.

**Two kinds of cache.** An attention layer keeps a row per position in the
engine's page pool: ``[k || v]`` of every cached head, FLAT (``2 KV D``
values, ``ops/gqa_attention.py``); only the attention layers are paged
(``paged_layers``). A Mamba-2 layer keeps a fixed-size state per sequence
whatever its length — ``s``: H x P x N float32, laid out as
``ops.mamba2.to_slots`` says, and ``tail``: the convolution's last
``conv_width - 1`` inputs — which the model declares as ``state`` and the
engine holds per slot beside the pool: a prefill returns its slot's, the
step is handed all of them with ``live`` and returns them updated in place.

- **prefill**: ALL the layers under one ``lax.scan`` whose body switches on
  the layer's kind (three compiled layer bodies whatever the depth), each
  kind's weights stacked and indexed by the layer's number within its kind;
  the recurrence in chunks (``ops.mamba2.ssd_chunked``), ``delta`` 0 at the
  positions behind the prompt so that they leave the state alone, the tail
  taken at the prompt's end; attention through ``gqa_flash_attention``.
- **step**: one token a slot; ``ssm_step`` (the ``ssm_decode`` kernel: one
  read and one write of each live slot's state in place) and
  ``gqa_decode_attention`` (the ``gqa_decode`` kernel over the pool).

Weights and activations are bfloat16 with float32 accumulation; norms,
softmax, the router, softplus, exp and the whole recurrence run in float32.

**Seeded weights** (``init_params``): the scheme of ``models/gdn_moe.py`` —
every matrix ``0.02 N(0, 1)`` in bfloat16 from random bytes, keyed by seed,
leaf, layer (and global expert, or block of 8192 rows of the published
vocabulary) — with the norms' gains ``1 +`` that, ``D = 1``, ``A_log = log
u`` (u one of 256 even steps of [1, 16]) and ``dt_bias`` the inverse softplus
of one of 256 log-even steps of [``time_step_min``, ``time_step_max``]
(floored at ``time_step_floor``), each picked by a random byte from a
host-made table. A held expert's two matrices are STORED with both their
sizes rounded up to whole tiles of 512 (``stored_width``: 2688 x 1856 ->
3072 x 2048), the pad zeros — a zero row of ``W_u`` meets the zeros
``ops.moe.held_experts`` puts behind ``h``, ``relu(0)^2 = 0`` meets a zero row
of ``W_d``, and the columns of ``W_d`` behind the hidden size are cut off:
the same function. XLA:TPU's grouped product tiles each size by the largest
of 512, 256, 128 that divides it: at 21 x 128 by 14.5 x 128 it copied the whole
array of experts before every call (2.5 GB a layer; compile-only, PR 40), at
21 x 128 by 15 x 128 it ran 128 x 128 tiles at 13 % of the memory's rate and took
45 of a step's 57 ms (my chip run, PR 40). The key is an ARGUMENT of the
programs that draw them: a new seed builds nothing. ``benchmark/reference_ssm_moe.py`` states the same
scheme on its own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import mamba2, moe
from ..ops.flash_attention import _use_interpret, decode_attention_impl
from ..ops.gated_delta import causal_conv, causal_conv_step
from ..ops.gqa_attention import gqa_decode_attention, gqa_flash_attention
from .mla_moe import _mm, _normal_bf16, rms_norm

__all__ = ["config_from_hf", "init_params", "SSMMoEDecodeModel"]

LEAVES = ("embed", "head", "final_norm", "norm", "router_w", "router_b",
          "shared_up_w", "shared_down_w", "experts_up_w", "experts_down_w",
          "q_w", "k_w", "v_w", "o_w", "z_w", "xbc_w", "dt_w", "conv_w",
          "conv_b", "A_log", "D", "dt_bias", "gnorm", "out_w")
ONE_PLUS = ("final_norm", "norm", "gnorm")
MAMBA = ("z_w", "xbc_w", "dt_w", "conv_w", "conv_b", "A_log", "D", "dt_bias",
         "gnorm", "out_w")
ATTENTION = ("q_w", "k_w", "v_w", "o_w")
ROUTED = ("router_w", "router_b", "shared_up_w", "shared_down_w")
EXPERTS = ("experts_up_w", "experts_down_w")
KINDS = "M*E"
TREES = {"M": "mamba", "*": "attn", "E": "moe"}   # a kind's stack in params
VOCAB_BLOCK = 8192
A_LOG_TABLE = np.log(1.0 + np.arange(256) * (15.0 / 255.0)).astype(np.float32)


def dt_bias_table(cfg: dict) -> np.ndarray:
    """The 256 values ``dt_bias`` takes: the inverse softplus of ``delta``
    at even steps of the logarithm from ``time_step_min`` to
    ``time_step_max``, floored at ``time_step_floor`` (the family's
    initialiser, to 8 bits), float32, made on the host."""
    lo, hi = np.log(cfg["time_step_min"]), np.log(cfg["time_step_max"])
    delta = np.maximum(np.exp(lo + np.arange(256) * ((hi - lo) / 255.0)),
                       cfg["time_step_floor"])
    return (delta + np.log(-np.expm1(-delta))).astype(np.float32)


def config_from_hf(hf: dict, *, experts_first: int = 0,
                   experts_held: int = None, router_experts: int = None,
                   vocab_first: int = 0, max_length: int = None) -> dict:
    """The model's description from a ``nemotron_h`` ``config.json``.
    ``router_experts`` is the router's published width where
    ``hf["n_routed_experts"]`` has been cut to the experts held here;
    ``vocab_first`` the first row held where ``hf["vocab_size"]`` has been
    cut to a slice. The pattern is cut to ``num_hidden_layers``."""
    pattern = hf["hybrid_override_pattern"][:hf["num_hidden_layers"]]
    if set(pattern) - set(KINDS) or len(pattern) != hf["num_hidden_layers"]:
        raise NotImplementedError(f"layer pattern {pattern!r}")
    if hf.get("n_group", 1) != 1 or hf.get("n_shared_experts", 1) != 1:
        raise NotImplementedError("grouped routing, or several shared experts")
    return {
        "vocab_size": hf["vocab_size"], "vocab_first": vocab_first,
        "hidden_size": hf["hidden_size"], "pattern": pattern,
        "num_heads": hf["num_attention_heads"],
        "num_kv_heads": hf["num_key_value_heads"], "head_dim": hf["head_dim"],
        "ssm_heads": hf["mamba_num_heads"],
        "ssm_head_dim": hf["mamba_head_dim"], "ssm_groups": hf["n_groups"],
        "ssm_state": hf["ssm_state_size"], "conv_width": hf["conv_kernel"],
        "chunk_size": hf["chunk_size"],
        "time_step_min": hf["time_step_min"],
        "time_step_max": hf["time_step_max"],
        "time_step_floor": hf["time_step_floor"],
        "expert_width": hf["moe_intermediate_size"],
        "shared_width": hf["moe_shared_expert_intermediate_size"],
        "router_experts": router_experts or hf["n_routed_experts"],
        "experts_first": experts_first,
        "experts_held": experts_held or hf["n_routed_experts"],
        "experts_per_token": hf["num_experts_per_tok"],
        "routed_scale": hf["routed_scaling_factor"],
        "rms_eps": hf["layer_norm_epsilon"],
        "max_length": max_length or hf["max_position_embeddings"],
    }


def leaf_shapes(cfg: dict) -> dict:
    """name -> shape of one layer's leaf (one expert's, for ``experts_*``).
    Matrices are (in, out)."""
    d, e = cfg["hidden_size"], cfg["router_experts"]
    fe, fs = cfg["expert_width"], cfg["shared_width"]
    h, kv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    sh, sp = cfg["ssm_heads"], cfg["ssm_head_dim"]
    inner, bc = sh * sp, cfg["ssm_groups"] * cfg["ssm_state"]
    return {"final_norm": (d,), "norm": (d,), "router_w": (d, e),
            "router_b": (e,), "shared_up_w": (d, fs), "shared_down_w": (fs, d),
            "experts_up_w": (d, fe), "experts_down_w": (fe, d),
            "q_w": (d, h * hd), "k_w": (d, kv * hd), "v_w": (d, kv * hd),
            "o_w": (h * hd, d), "z_w": (d, inner),
            "xbc_w": (d, inner + 2 * bc), "dt_w": (d, sh),
            "conv_w": (cfg["conv_width"], inner + 2 * bc),
            "conv_b": (inner + 2 * bc,), "A_log": (sh,), "D": (sh,),
            "dt_bias": (sh,), "gnorm": (inner,), "out_w": (inner, d)}


def stored_width(width: int) -> int:
    """The size a held expert's matrices are stored at, hidden or expert
    width: whole tiles of 512 (zeros behind the published size), once there
    is one — the largest tile of XLA:TPU's grouped product."""
    return width if width < 512 else -(-width // 512) * 512


def layer_kinds(cfg: dict) -> dict:
    """kind (``M``, ``*``, ``E``) -> the indices of its layers, in order."""
    return {kind: [i for i, c in enumerate(cfg["pattern"]) if c == kind]
            for kind in KINDS}


def _draw(cfg, key, name, shape, *path):
    key = jax.random.fold_in(key, LEAVES.index(name))
    for i in path:
        key = jax.random.fold_in(key, i)
    if name == "D":
        return jnp.ones(shape, jnp.float32)
    if name in ("A_log", "dt_bias"):
        table = A_LOG_TABLE if name == "A_log" else dt_bias_table(cfg)
        byte = jax.random.bits(key, tuple(shape), jnp.uint32) & 0xFF
        return jnp.asarray(table)[byte]
    x = _normal_bf16(key, shape)
    if name in ONE_PLUS:
        x = (1.0 + x.astype(jnp.float32)).astype(jnp.bfloat16)
    return x


def init_params(cfg: dict, seed: int) -> dict:
    """The seeded weights on the default device (module docstring):
    ``embed``, ``head``, ``final_norm``; three stacks with a leading axis
    over the layers of ONE kind — ``mamba`` (``norm``, ``z_w``, ``xbc_w``,
    ``dt_w``, the convolution with its bias, ``A_log``, ``D``, ``dt_bias``
    (float32), ``gnorm``, ``out_w``), ``attn`` (``norm``, ``q_w``, ``kv_w =
    [k_w || v_w]``, ``o_w``) and ``moe`` (``norm``, the router with its
    bias, the shared expert) — and ``experts``: ``up_w``, ``down_w`` with
    every expert layer's held experts on ONE leading axis (layer-major),
    filled in place (:func:`stored_experts`). The key is an argument of the
    five programs."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)
    shapes = leaf_shapes(cfg)
    v, d = cfg["vocab_size"], cfg["hidden_size"]
    kinds = layer_kinds(cfg)

    def vocab(key, name):   # rows vocab_first .. of whole blocks of 8192
        origin = cfg.get("vocab_first", 0)
        blocks = range(origin // VOCAB_BLOCK, -(-(origin + v) // VOCAB_BLOCK))
        table = jnp.concatenate([_draw(cfg, key, name, (VOCAB_BLOCK, d), b)
                                 for b in blocks])
        start = origin - blocks[0] * VOCAB_BLOCK
        return table[start:start + v]

    def stack(key, names, layers):
        return jnp.stack([jnp.concatenate(
            [_draw(cfg, key, name, shapes[name], i) for name in names],
            axis=-1) for i in layers])

    def top(key):
        return {"embed": vocab(key, "embed"), "head": vocab(key, "head"),
                "final_norm": _draw(cfg, key, "final_norm", (d,))}

    def of_kind(kind, names):
        def make(key):
            out = {name: stack(key, (name,), kinds[kind])
                   for name in ("norm",) + names}
            if kind == "*":
                out["kv_w"] = jnp.concatenate(
                    [out.pop("k_w"), out.pop("v_w")], axis=-1)
            return out

        return make

    params = jax.jit(top)(key)
    for kind, names in (("M", MAMBA), ("*", ATTENTION), ("E", ROUTED)):
        params[TREES[kind]] = jax.jit(of_kind(kind, names))(key)
    params["experts"] = {
        name[len("experts_"):]: jax.jit(functools.partial(
            stored_experts, cfg, name))(key) for name in EXPERTS}
    return params


def stored_experts(cfg: dict, name: str, key):
    """Every expert layer's held experts' ``name`` (``experts_up_w`` (D, F)
    or ``experts_down_w`` (F, D)) on one leading axis, layer-major, each
    stored in whole tiles of 512 with zeros behind the published size,
    filled in place. An expert is padded to its WHOLE stored slice before it
    is written: XLA:TPU does not clear a buffer that a loop fills slice by
    slice (``AllocateBuffer`` for the zeros), also where the slices are
    written only in part, and what the device's memory held before would
    stay behind the published size (PR 40: NaN once other programs had run
    in the process; zeros, by luck, in a fresh one)."""
    shapes = leaf_shapes(cfg)
    first, held = cfg["experts_first"], cfg["experts_held"]
    layers = layer_kinds(cfg)["E"]
    expert_layers = jnp.asarray(layers, jnp.int32)
    wide = (stored_width(cfg["hidden_size"]),
            stored_width(cfg["expert_width"]))
    stored = wide if name == "experts_up_w" else wide[::-1]

    def one(i, buf):
        w = _draw(cfg, key, name, shapes[name], expert_layers[i // held],
                  first + i % held)
        w = jnp.pad(w, [(0, s - n) for s, n in zip(stored, w.shape)])
        return lax.dynamic_update_slice(buf, w[None], (i, 0, 0))

    return lax.fori_loop(
        0, len(layers) * held, one,
        jnp.zeros((len(layers) * held,) + stored, jnp.bfloat16))


# -- the layers -----------------------------------------------------------------

def _normed(cfg, lp, x):
    return rms_norm(x, lp["norm"], cfg["rms_eps"]).astype(x.dtype)


def _residual(x, y):
    return (x.astype(jnp.float32) + y).astype(x.dtype)


def _mamba_inputs(cfg, lp, x):
    """The normed input's projections: (z (T, inner) float32, xbc (T, C) in
    x's dtype — what the convolution sees and its tail keeps —, delta (T, H)
    float32 = softplus(dt + dt_bias): no clamp, ``time_step_limit`` is (0,
    inf))."""
    h = _normed(cfg, lp, x)
    delta = jax.nn.softplus(_mm(h, lp["dt_w"]) + lp["dt_bias"])
    return _mm(h, lp["z_w"]), _mm(h, lp["xbc_w"]).astype(x.dtype), delta


def _mamba_heads(cfg, lp, conv):
    """From the convolution's output (T, C) float32 (bias not yet added):
    x (T, H, P), B and C (T, G, N), float32."""
    t = conv.shape[0]
    sh, sp, g = cfg["ssm_heads"], cfg["ssm_head_dim"], cfg["ssm_groups"]
    inner, bc = sh * sp, g * cfg["ssm_state"]
    u = jax.nn.silu(conv + lp["conv_b"].astype(jnp.float32))
    return (u[:, :inner].reshape(t, sh, sp),
            u[:, inner:inner + bc].reshape(t, g, -1),
            u[:, inner + bc:].reshape(t, g, -1))


def _mamba_output(cfg, lp, x, y, xh, z):
    """The skip ``D x``, the gate, the norm over each group's channels, the
    output projection and the residual."""
    t, g = x.shape[0], cfg["ssm_groups"]
    y = y + lp["D"].astype(jnp.float32)[:, None] * xh
    u = (y.reshape(t, -1) * jax.nn.silu(z)).reshape(t, g, -1)
    u = u * lax.rsqrt(jnp.mean(u * u, -1, keepdims=True) + cfg["rms_eps"])
    u = u.reshape(t, -1) * lp["gnorm"].astype(jnp.float32)
    return _residual(x, _mm(u.astype(x.dtype), lp["out_w"]))


def _attn_projections(cfg, lp, x):
    """For tokens x (T, hidden): the query (T, KV, G, D) (cached head, then
    its group's heads) and the cache row ``[k || v]`` (T, 2 KV D), both in
    x's dtype. No positional embedding."""
    t, kv = x.shape[0], cfg["num_kv_heads"]
    h = _normed(cfg, lp, x)
    q = _mm(h, lp["q_w"]).reshape(t, kv, cfg["num_heads"] // kv, -1)
    return q.astype(x.dtype), _mm(h, lp["kv_w"]).astype(x.dtype)


def _experts(cfg, lp, x, live, experts, number):
    """x + experts(RMSNorm(x)); (x', counters). ``number``: which of the
    expert layers this is (its experts' place in the one array), possibly
    traced."""
    held = cfg["experts_held"]
    y, counters = moe.expert_layer(
        _normed(cfg, lp, x), {k: w for k, w in lp.items() if k != "norm"},
        experts, live, first=cfg["experts_first"], held=held,
        k=cfg["experts_per_token"], scale=cfg["routed_scale"],
        offset=number * held)
    return _residual(x, y), counters


class SSMMoEDecodeModel:
    """The model as ``DecodeEngine`` takes one (``serve/decode.py``, "the
    model by interface"), with per-slot state beside its cache rows.
    ``params`` default to ``init_params(cfg, seed)``."""

    def __init__(self, cfg: dict, seed: int = 0, params: dict = None):
        self.cfg = dict(cfg)
        self.kinds = layer_kinds(cfg)
        self.layers = len(cfg["pattern"])
        self.paged_layers = len(self.kinds["*"])
        self.cache_row = (2 * cfg["num_kv_heads"] * cfg["head_dim"],)
        self.params = init_params(cfg, seed) if params is None else params
        # bfloat16, as the weights (a float32 tree, as the tests make one,
        # runs the same bodies in float32)
        self.cache_dtype = self.params["embed"].dtype
        n = len(self.kinds["M"])
        sh, sp, g = cfg["ssm_heads"], cfg["ssm_head_dim"], cfg["ssm_groups"]
        self._tail = (cfg["conv_width"] - 1, leaf_shapes(cfg)["conv_w"][1])
        # the tail's (3, 6144) as (144, 128): whole 16 x 128 tiles (PR 38:
        # a 3-row minor tile comes back from a cached zeros program in the
        # client's own layout)
        values = self._tail[0] * self._tail[1]
        folded = (values // 128, 128) if values % (16 * 128) == 0 else self._tail
        # per slot: every Mamba-2 layer's state (as ops.mamba2.to_slots lays
        # it) and convolution tail
        self.state = {
            "s": ((n, g, cfg["ssm_state"], sh // g * sp), jnp.float32),
            "tail": ((n,) + folded, self.cache_dtype)}

    def _head(self, params, x):
        h = rms_norm(x, params["final_norm"], self.cfg["rms_eps"])
        return jnp.einsum("...d,vd->...v", h.astype(x.dtype), params["head"],
                          preferred_element_type=jnp.float32)

    def _scale(self):
        return self.cfg["head_dim"] ** -0.5

    def prefill(self, params, tokens, length):
        """tokens (1, S), length () -> (logits at ``length - 1`` (V,)
        float32, rows (paged layers, S, 2 KV D), counters, the sequence's
        state ``{"s", "tail"}`` after ``length`` tokens)."""
        cfg = self.cfg
        s = tokens.shape[1]
        kvh, d, g = cfg["num_kv_heads"], cfg["head_dim"], cfg["ssm_groups"]
        live = jnp.arange(s) < length
        x = params["embed"][tokens[0]]
        experts = params["experts"]
        # what a layer that is not of a kind hands the scan for that kind
        no_rows = jnp.zeros((s,) + self.cache_row, x.dtype)
        no_state = jnp.zeros(self.state["s"][0][1:], jnp.float32)
        no_tail = jnp.zeros(self._tail, x.dtype)
        no_counts = jnp.zeros((len(moe.COUNTERS),), jnp.int32)

        def take(tree, j):
            return {k: lax.dynamic_index_in_dim(w, j, keepdims=False)
                    for k, w in tree.items()}

        def mamba(x, j):
            lp = take(params["mamba"], j)
            z, xbc, delta = _mamba_inputs(cfg, lp, x)
            conv, tail = causal_conv(xbc, lp["conv_w"], length)
            xh, b, c = _mamba_heads(cfg, lp, conv)
            # a position behind the prompt leaves the state as it was
            delta = jnp.where(live[:, None], delta, 0.0)
            y, state = mamba2.ssd_chunked(
                xh, delta, -jnp.exp(lp["A_log"]), b, c,
                chunk=cfg["chunk_size"])
            return (_mamba_output(cfg, lp, x, y, xh, z), no_rows,
                    mamba2.to_slots(state, g), tail, no_counts)

        def attention(x, j):
            lp = take(params["attn"], j)
            q, row = _attn_projections(cfg, lp, x)
            k = jnp.swapaxes(row[:, :kvh * d].reshape(s, kvh, d), 0, 1)
            v = jnp.swapaxes(row[:, kvh * d:].reshape(s, kvh, d), 0, 1)
            o = gqa_flash_attention(jnp.moveaxis(q, 0, 2), k, v,
                                    scale=self._scale())    # (KV, G, S, D)
            o = jnp.moveaxis(o, 2, 0).reshape(s, -1)
            return (_residual(x, _mm(o, lp["o_w"])), row, no_state, no_tail,
                    no_counts)

        def routed(x, j):
            x, counters = _experts(cfg, take(params["moe"], j), x, live,
                                   experts, j)
            return x, no_rows, no_state, no_tail, counters

        def layer(x, xs):
            kind, j = xs
            x, *kept = lax.switch(kind, (mamba, attention, routed), x, j)
            return x, kept

        pattern = cfg["pattern"]
        x, (rows, states, tails, counters) = lax.scan(layer, x, (
            jnp.asarray([KINDS.index(c) for c in pattern], jnp.int32),
            jnp.asarray([pattern[:i].count(c) for i, c in enumerate(pattern)],
                        jnp.int32)))
        m, a, e = (np.asarray(self.kinds[c]) for c in KINDS)
        state = {"s": states[m],
                 "tail": tails[m].reshape((-1,) + self.state["tail"][0][1:])}
        return (self._head(params, x[length - 1]), rows[a],
                moe.merge_counters(counters[e]), state)

    def step(self, params, tokens, positions, live, attend, state):
        """tokens, positions (B,), live (B,) bool; ``attend(paged layer,
        query, row) -> o``; ``state``: every slot's ``s`` and ``tail``
        (slots + 1 leading, the last scratch). Returns (logits (B, V)
        float32, counters, state) — the state of a slot that is not live
        untouched. ``positions`` are the engine's alone: no layer of this
        model reads a position."""
        del positions
        cfg = self.cfg
        n = tokens.shape[0]
        x = params["embed"][tokens]
        s_all, tails = state["s"], state["tail"]
        impl = "pallas" if decode_attention_impl() == "pallas" else "xla"
        counters = []
        for i, kind in enumerate(cfg["pattern"]):
            j = self.kinds[kind].index(i)
            lp = {k: w[j] for k, w in params[TREES[kind]].items()}
            if kind == "E":
                x, c = _experts(cfg, lp, x, live, params["experts"], j)
                counters.append(c)
            elif kind == "*":
                q, row = _attn_projections(cfg, lp, x)
                x = _residual(x, _mm(attend(j, q, row).reshape(n, -1)
                                     .astype(x.dtype), lp["o_w"]))
            else:
                z, xbc, delta = _mamba_inputs(cfg, lp, x)
                old = tails[:n, j].reshape((n,) + self._tail)
                conv, new = causal_conv_step(xbc, old, lp["conv_w"])
                tails = tails.at[:n, j].set(
                    jnp.where(live[:, None, None], new, old).reshape(
                        (n,) + tails.shape[2:]))
                xh, b, c = _mamba_heads(cfg, lp, conv)
                y, s_all = mamba2.ssm_step(
                    s_all, j, xh, delta, -jnp.exp(lp["A_log"]), b, c, live,
                    impl=impl, interpret=_use_interpret())
                x = _mamba_output(cfg, lp, x, y, xh, z)
        return (self._head(params, x), moe.merge_counters(jnp.stack(counters)),
                {"s": s_all, "tail": tails})

    def attention(self, query, pool, layer, page_table, lengths):
        return gqa_decode_attention(query, pool, layer, page_table, lengths,
                                    scale=self._scale())

    counters = tuple("moe." + name for name in moe.COUNTERS)

    def moe_row_tile(self, tokens):
        """``DecodeEngine.stats()["moe_row_tile"]``: the row tile the held
        experts' grouped products run a call of ``tokens`` tokens with."""
        return moe.layer_row_tile(tokens, self.cfg["experts_per_token"],
                                  self.cfg["router_experts"], self.cache_dtype)

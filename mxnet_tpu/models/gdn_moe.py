"""A functional decoder whose layers alternate gated delta-rule (linear)
attention and gated grouped-KV full attention, every MLP an expert layer
(Qwen3-Next), for the decode engine (``serve/decode.py``). Not a gluon block
and not imported by ``mxnet_tpu.models``: import it where it is used. The
equations are written out in ``benchmark/reference_gdn_moe.py``.

Pre-norm residual blocks, ``RMSNorm0(x) = x rsqrt(mean x^2 + eps) (1 + w)``;
layer ``i`` is a full-attention layer when ``(i + 1) % full_interval == 0``,
else a gated-delta layer; every MLP is ``ops/moe.py``'s expert layer (softmax
router over ``router_experts``, the chip's share ``experts_first .. +
experts_held``, a shared expert behind a sigmoid gate); an untied head.

**Two kinds of cache.** A full layer keeps a row per position in the
engine's page pool: ``[k || v]`` of every cached head, FLAT (``2 KV D``
values, ``ops/gqa_attention.py``); only the full layers are paged
(``paged_layers``), and the pool's layer axis counts them alone. A delta
layer keeps a fixed-size state per sequence whatever its length — ``s``: HV
x dk x dv float32, and ``tail``: the convolution's last ``conv_width - 1``
inputs — which the model declares as ``state`` and the engine holds per
slot beside the pool: a prefill returns its slot's, the step is handed all
of them with ``live`` and returns them updated in place.

- **prefill**: the periods (``full_interval - 1`` delta layers and a full
  one) under one ``lax.scan`` over their stacked weights, the delta layers
  of a period under another; the delta rule in chunks
  (``ops.gated_delta.delta_rule_chunked``: the ``gdn_prefill`` kernel on a
  TPU at lane-wide heads, XLA einsums elsewhere; ``delta_rule()`` says
  which), positions past the prompt's length masked out of the state;
  attention through ``gqa_flash_attention``.
- **step**: one token a slot; ``delta_rule_step`` (the ``gdn_decode``
  kernel: one read and one write of each live slot's state in place) and
  ``gqa_decode_attention`` (the ``gqa_decode`` kernel over the pool).

Weights and activations are bfloat16 with float32 accumulation; norms, RoPE,
softmax, the router, the gates and the whole delta rule run in float32.

**Seeded weights** (``init_params``): the scheme of ``models/mla_moe.py`` —
every leaf ``0.02 N(0, 1)`` in bfloat16 from random bytes, keyed by seed,
leaf, layer (and global expert, or block of 8192 rows of the published
vocabulary: the rows held are ``vocab_first .. + vocab_size`` of whole
blocks) — with the gated norm's gain and ``dt_bias`` ``1 +`` that, and
``A_log = log u``, u one of 256 even steps of [1, 16] picked by a random byte
from a host-made table. The key is an ARGUMENT of the programs that draw
them: a new seed builds nothing. ``benchmark/reference_gdn_moe.py`` states
the same scheme on its own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import gated_delta, moe
from ..ops.flash_attention import _use_interpret, decode_attention_impl
from ..ops.gqa_attention import (gqa_decode_attention, gqa_flash_attention,
                                 gqa_flash_attention_from)
from .mla_moe import _normal_bf16

__all__ = ["config_from_hf", "init_params", "GDNMoEDecodeModel"]

LEAVES = ("embed", "head", "final_norm", "attn_norm", "mlp_norm", "router_w",
          "shared_gate_w", "shared_up_w", "shared_down_w", "shared_s_w",
          "experts_gate_w", "experts_up_w", "experts_down_w",
          "q_w", "k_w", "v_w", "q_norm", "k_norm", "o_w",
          "dq_w", "dk_w", "dv_w", "dz_w", "db_w", "da_w", "conv_w", "A_log",
          "dt_bias", "gnorm", "out_w")
ONE_PLUS = ("gnorm", "dt_bias")
COMMON = ("attn_norm", "mlp_norm", "router_w", "shared_gate_w", "shared_up_w",
          "shared_down_w", "shared_s_w")
EXPERTS = ("experts_gate_w", "experts_up_w", "experts_down_w")
VOCAB_BLOCK = 8192
A_LOG_TABLE = np.log(1.0 + np.arange(256) * (15.0 / 255.0)).astype(np.float32)


def config_from_hf(hf: dict, *, experts_first: int = 0,
                   experts_held: int = None, router_experts: int = None,
                   vocab_first: int = 0, max_length: int = None) -> dict:
    """The model's description from a ``qwen3_next`` ``config.json``.
    ``router_experts`` is the router's published width where
    ``hf["num_experts"]`` has been cut to the experts held here;
    ``vocab_first`` the first row held where ``hf["vocab_size"]`` has been
    cut to a slice."""
    if hf.get("mlp_only_layers") or hf.get("decoder_sparse_step", 1) != 1:
        raise NotImplementedError("dense MLP layers among the expert layers")
    return {
        "vocab_size": hf["vocab_size"], "vocab_first": vocab_first,
        "hidden_size": hf["hidden_size"],
        "num_layers": hf["num_hidden_layers"],
        "full_interval": hf["full_attention_interval"],
        "num_heads": hf["num_attention_heads"],
        "num_kv_heads": hf["num_key_value_heads"], "head_dim": hf["head_dim"],
        "rotary_dim": int(hf["head_dim"] * hf["partial_rotary_factor"]),
        "rope_theta": hf["rope_theta"],
        "linear_key_heads": hf["linear_num_key_heads"],
        "linear_value_heads": hf["linear_num_value_heads"],
        "linear_key_dim": hf["linear_key_head_dim"],
        "linear_value_dim": hf["linear_value_head_dim"],
        "conv_width": hf["linear_conv_kernel_dim"],
        "expert_width": hf["moe_intermediate_size"],
        "router_experts": router_experts or hf["num_experts"],
        "experts_first": experts_first,
        "experts_held": experts_held or hf["num_experts"],
        "experts_per_token": hf["num_experts_per_tok"],
        "rms_eps": hf["rms_norm_eps"],
        "max_length": max_length or hf["max_position_embeddings"],
    }


def leaf_shapes(cfg: dict) -> dict:
    """name -> shape of one layer's leaf (one expert's, for ``experts_*``).
    Matrices are (in, out)."""
    d, fe, e = cfg["hidden_size"], cfg["expert_width"], cfg["router_experts"]
    h, kv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    hk, hv, dk, dv = (cfg["linear_key_heads"], cfg["linear_value_heads"],
                      cfg["linear_key_dim"], cfg["linear_value_dim"])
    return {"final_norm": (d,), "attn_norm": (d,), "mlp_norm": (d,),
            "router_w": (d, e), "shared_gate_w": (d, fe),
            "shared_up_w": (d, fe), "shared_down_w": (fe, d),
            "shared_s_w": (d,), "experts_gate_w": (d, fe),
            "experts_up_w": (d, fe), "experts_down_w": (fe, d),
            "q_w": (d, h * 2 * hd), "k_w": (d, kv * hd), "v_w": (d, kv * hd),
            "q_norm": (hd,), "k_norm": (hd,), "o_w": (h * hd, d),
            "dq_w": (d, hk * dk), "dk_w": (d, hk * dk), "dv_w": (d, hv * dv),
            "dz_w": (d, hv * dv), "db_w": (d, hv), "da_w": (d, hv),
            "conv_w": (cfg["conv_width"], 2 * hk * dk + hv * dv),
            "A_log": (hv,), "dt_bias": (hv,), "gnorm": (dv,),
            "out_w": (hv * dv, d)}


def layer_kinds(cfg: dict):
    """(the indices of the delta layers, those of the full layers)."""
    n, every = cfg["num_layers"], cfg["full_interval"]
    if n % every:
        raise ValueError(f"{n} layers are not whole periods of {every}")
    full = [i for i in range(n) if (i + 1) % every == 0]
    return [i for i in range(n) if i not in full], full


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
    return x


def init_params(cfg: dict, seed: int) -> dict:
    """The seeded weights on the default device (module docstring):
    ``embed``, ``head``, ``final_norm``; three stacks with a leading layer
    axis — ``moe`` (every layer's norms, router and shared expert), ``full``
    (``q_w``, ``kv_w = [k_w || v_w]``, the head norms, ``o_w``) and ``delta``
    (``qkv_w = [dq_w || dk_w || dv_w]``, ``z_w``, ``ba_w = [db_w || da_w]``,
    the convolution, ``A_log`` (float32), ``dt_bias``, ``gnorm``, ``out_w``)
    — and ``experts``: ``gate_w``, ``up_w``, ``down_w`` with every layer's
    held experts on ONE leading axis (layer-major), filled in place. The key
    is an argument of the six programs."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)
    shapes = leaf_shapes(cfg)
    v, d, n = cfg["vocab_size"], cfg["hidden_size"], cfg["num_layers"]
    first, held = cfg["experts_first"], cfg["experts_held"]
    delta, full = layer_kinds(cfg)

    def vocab(key, name):   # rows vocab_first .. of whole blocks of 8192
        origin = cfg.get("vocab_first", 0)
        blocks = range(origin // VOCAB_BLOCK, -(-(origin + v) // VOCAB_BLOCK))
        table = jnp.concatenate([_draw(key, name, (VOCAB_BLOCK, d), b)
                                 for b in blocks])
        start = origin - blocks[0] * VOCAB_BLOCK
        return table[start:start + v]

    def stack(key, names, layers):
        return jnp.stack([jnp.concatenate(
            [_draw(key, name, shapes[name], i) for name in names], axis=-1)
            for i in layers])

    def top(key):
        return {"embed": vocab(key, "embed"), "head": vocab(key, "head"),
                "final_norm": _draw(key, "final_norm", (d,))}

    def common(key):
        return {name: stack(key, (name,), range(n)) for name in COMMON}

    def full_layers(key):
        return {"q_w": stack(key, ("q_w",), full),
                "kv_w": stack(key, ("k_w", "v_w"), full),
                "q_norm": stack(key, ("q_norm",), full),
                "k_norm": stack(key, ("k_norm",), full),
                "o_w": stack(key, ("o_w",), full)}

    def delta_layers(key):
        out = {"qkv_w": stack(key, ("dq_w", "dk_w", "dv_w"), delta),
               "z_w": stack(key, ("dz_w",), delta),
               "ba_w": stack(key, ("db_w", "da_w"), delta)}
        out.update({name: stack(key, (name,), delta) for name in
                    ("conv_w", "A_log", "dt_bias", "gnorm", "out_w")})
        return out

    def experts(name, key):
        def one(i, buf):
            w = _draw(key, name, shapes[name], i // held, first + i % held)
            return lax.dynamic_update_slice(buf, w[None], (i, 0, 0))

        return lax.fori_loop(
            0, n * held, one,
            jnp.zeros((n * held,) + shapes[name], jnp.bfloat16))

    params = jax.jit(top)(key)
    params["moe"] = jax.jit(common)(key)
    params["full"] = jax.jit(full_layers)(key)
    params["delta"] = jax.jit(delta_layers)(key)
    make = jax.jit(experts, static_argnums=0)
    params["experts"] = {name[len("experts_"):]: make(name, key)
                         for name in EXPERTS}
    return params


# -- the layers -----------------------------------------------------------------

def rms_norm0(x, w, eps):
    x = x.astype(jnp.float32)
    return (x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * (1.0 + w.astype(jnp.float32)))


def _mm(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _rope(x, cos, sin):
    """x (T, heads, D) float32; cos, sin (T, rot / 2): the first ``rot``
    dimensions of each head rotated, pairs (j, j + rot / 2)."""
    half = cos.shape[1]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    cos, sin = cos[:, None], sin[:, None]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def _full_projections(cfg, lp, x, cos, sin):
    """For tokens x (T, hidden): the query (T, KV, G, D) (cached head, then
    its group's heads) in x's dtype, the output gate (T, H D) float32 and
    the cache row ``[k || v]`` (T, 2 KV D) in x's dtype."""
    t = x.shape[0]
    heads, kv, d = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    h = rms_norm0(x, lp["attn_norm"], cfg["rms_eps"]).astype(x.dtype)
    qg = _mm(h, lp["q_w"]).reshape(t, heads, 2 * d)
    q = _rope(rms_norm0(qg[..., :d], lp["q_norm"], cfg["rms_eps"]), cos, sin)
    kvp = _mm(h, lp["kv_w"])
    k = _rope(rms_norm0(kvp[:, :kv * d].reshape(t, kv, d), lp["k_norm"],
                        cfg["rms_eps"]), cos, sin)
    row = jnp.concatenate([k.reshape(t, kv * d), kvp[:, kv * d:]], axis=-1)
    return (q.reshape(t, kv, heads // kv, d).astype(x.dtype),
            qg[..., d:].reshape(t, heads * d), row.astype(x.dtype))


def _full_output(lp, x, o, gate):
    o = (o.astype(jnp.float32) * jax.nn.sigmoid(gate)).astype(x.dtype)
    return (x.astype(jnp.float32) + _mm(o, lp["o_w"])).astype(x.dtype)


def _delta_inputs(cfg, lp, x):
    """The normed input's projections: (qkv (T, C) in x's dtype — what the
    convolution sees and its tail keeps —, z (T, HV, dv), b, a (T, HV)
    float32)."""
    hv = cfg["linear_value_heads"]
    h = rms_norm0(x, lp["attn_norm"], cfg["rms_eps"]).astype(x.dtype)
    ba = _mm(h, lp["ba_w"])
    return (_mm(h, lp["qkv_w"]).astype(x.dtype),
            _mm(h, lp["z_w"]).reshape(x.shape[0], hv, -1),
            ba[:, :hv], ba[:, hv:])


def _delta_heads(cfg, lp, conv, b, a):
    """From the convolution's output (T, C) float32: q, k (T, HK, dk) — l2
    normed, q scaled; key head j is value heads ``j HV / HK ..``'s —, v (T,
    HV, dv), g, beta (T, HV); float32."""
    hk, hv = cfg["linear_key_heads"], cfg["linear_value_heads"]
    dk, t = cfg["linear_key_dim"], conv.shape[0]
    x = jax.nn.silu(conv)

    def l2(u):
        return u * lax.rsqrt(jnp.sum(u * u, -1, keepdims=True) + 1e-6)

    q = l2(x[:, :hk * dk].reshape(t, hk, dk)) * dk ** -0.5
    k = l2(x[:, hk * dk:2 * hk * dk].reshape(t, hk, dk))
    g = (-jnp.exp(lp["A_log"].astype(jnp.float32))
         * jax.nn.softplus(a + lp["dt_bias"].astype(jnp.float32)))
    return q, k, x[:, 2 * hk * dk:].reshape(t, hv, -1), g, jax.nn.sigmoid(b)


def _delta_output(cfg, lp, x, o, z):
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg["rms_eps"])
    y = lp["gnorm"].astype(jnp.float32) * o * jax.nn.silu(z)
    y = y.reshape(x.shape[0], -1).astype(x.dtype)
    return (x.astype(jnp.float32) + _mm(y, lp["out_w"])).astype(x.dtype)


def _mlp(cfg, mp, x, live, experts, layer):
    """x + experts(RMSNorm0(x)); (x', counters). ``layer``: this layer's
    index (every layer is an expert layer), possibly traced."""
    held = cfg["experts_held"]
    h = rms_norm0(x, mp["mlp_norm"], cfg["rms_eps"]).astype(x.dtype)
    y, counters = moe.expert_layer(
        h, mp, experts, live, first=cfg["experts_first"], held=held,
        k=cfg["experts_per_token"], offset=layer * held)
    return (x.astype(jnp.float32) + y).astype(x.dtype), counters


class GDNMoEDecodeModel:
    """The model as ``DecodeEngine`` takes one (``serve/decode.py``, "the
    model by interface"), with per-slot state beside its cache rows.
    ``params`` default to ``init_params(cfg, seed)``."""

    def __init__(self, cfg: dict, seed: int = 0, params: dict = None):
        self.cfg = dict(cfg)
        self.layers = int(cfg["num_layers"])
        self.delta_layers, self.full_layers = layer_kinds(cfg)
        self.paged_layers = len(self.full_layers)
        self.cache_row = (2 * cfg["num_kv_heads"] * cfg["head_dim"],)
        self.params = init_params(cfg, seed) if params is None else params
        # bfloat16, as the weights (a float32 tree, as the tests make one,
        # runs the same bodies in float32)
        self.cache_dtype = self.params["embed"].dtype
        n, hv = len(self.delta_layers), cfg["linear_value_heads"]
        self._tail = (cfg["conv_width"] - 1, leaf_shapes(cfg)["conv_w"][1])
        # the tail's (3, 8192) as (192, 128): whole 16 x 128 tiles, the
        # layout a TPU gives such an array whoever asks (a 3-row minor tile
        # would be padded to 16, and the client then lays the array out with
        # the slot axis inside, which no program was compiled for)
        values = self._tail[0] * self._tail[1]
        folded = (values // 128, 128) if values % (16 * 128) == 0 else self._tail
        # per slot: every delta layer's recurrent state and convolution tail
        self.state = {
            "s": ((n, hv, cfg["linear_key_dim"], cfg["linear_value_dim"]),
                  jnp.float32),
            "tail": ((n,) + folded, self.cache_dtype)}
        rot = cfg["rotary_dim"]
        self._inv_freq = (1.0 / cfg["rope_theta"] ** (
            np.arange(0, rot, 2, dtype=np.float64) / rot)).astype(np.float32)

    def _angles(self, positions):
        angle = positions.astype(jnp.float32)[:, None] * self._inv_freq[None]
        return jnp.cos(angle), jnp.sin(angle)

    def _head(self, params, x):
        h = rms_norm0(x, params["final_norm"], self.cfg["rms_eps"])
        return jnp.einsum("...d,vd->...v", h.astype(x.dtype), params["head"],
                          preferred_element_type=jnp.float32)

    def _scale(self):
        return self.cfg["head_dim"] ** -0.5

    def prefill(self, params, tokens, length):
        """tokens (1, S), length () -> (logits at ``length - 1`` (V,)
        float32, rows (paged layers, S, 2 KV D), counters, the sequence's
        state ``{"s", "tail"}`` after ``length`` tokens)."""
        return self._prompt(params, tokens, 0, length, None, None)

    def prefill_from(self, params, tokens, start, length, prior, state):
        """A prompt continued: tokens (1, C) are positions ``start .. start +
        C - 1`` of a prompt of ``length`` (``start`` () int32, a multiple of
        C; C a multiple of the delta rule's chunk, so that the pieces cut
        the prompt where the rule's own scan cuts it), ``state`` the
        sequence's own ``{"s", "tail"}`` as the piece before left it —
        taken for zeros where ``start`` is 0, whatever it holds —, and
        ``prior(paged layer) -> (T, 2 KV D)`` the rows of positions 0 .. T -
        1 as the pool has them, of which those ``< start`` are read. Returns
        what :meth:`prefill` returns: the logits at ``length - 1`` (of the
        last piece alone: garbage before), the piece's rows, its counters,
        the state after the piece."""
        return self._prompt(params, tokens, start, length, prior, state)

    def _prompt(self, params, tokens, start, length, prior, state):
        """The body of :meth:`prefill` (``prior`` None: the whole prompt
        from position 0) and :meth:`prefill_from`."""
        cfg = self.cfg
        s, every = tokens.shape[1], cfg["full_interval"]
        periods = self.layers // every
        positions = start + jnp.arange(s)
        cos, sin = self._angles(positions)
        live = positions < length
        x = params["embed"][tokens[0]]
        experts = params["experts"]
        kvh, d = cfg["num_kv_heads"], cfg["head_dim"]
        s_in = tail_in = None
        if prior is not None:
            def own(a, shape):
                # a slot's leftovers never reach a new prompt: where(), not
                # a product, so that not even a NaN does
                return jnp.where(start == 0, 0, a).astype(a.dtype).reshape(
                    (periods, every - 1) + shape)

            s_in = own(state["s"], state["s"].shape[1:])
            tail_in = own(state["tail"], self._tail)

        def heads(flat):    # (T, KV D) -> (KV, T, D)
            return jnp.swapaxes(flat.reshape(-1, kvh, d), 0, 1)

        def delta_layer(x, xs):
            lp, mp, i, s0, tail0 = xs
            lp = dict(lp, attn_norm=mp["attn_norm"])
            qkv, z, b, a = _delta_inputs(cfg, lp, x)
            conv, tail = gated_delta.causal_conv(qkv, lp["conv_w"],
                                                 length - start, tail0)
            q, k, v, g, beta = _delta_heads(cfg, lp, conv, b, a)
            # a position past the prompt writes nothing into the state
            g = jnp.where(live[:, None], g, 0.0)
            beta = jnp.where(live[:, None], beta, 0.0)
            o, state = gated_delta.delta_rule_chunked(
                q, k, v, g, beta, s0, impl=decode_attention_impl(),
                interpret=_use_interpret())
            x = _delta_output(cfg, lp, x, o, z)
            x, counters = _mlp(cfg, mp, x, live, experts, i)
            return x, (state, tail, counters)

        def period(x, xs):
            dp, fp, mp, j, s0, tail0 = xs
            first = j * every
            x, (states, tails, c_delta) = lax.scan(
                delta_layer, x,
                (dp, {k: w[:every - 1] for k, w in mp.items()},
                 first + jnp.arange(every - 1), s0, tail0))
            mp = {k: w[every - 1] for k, w in mp.items()}
            fp = dict(fp, attn_norm=mp["attn_norm"])
            q, gate, row = _full_projections(cfg, fp, x, cos, sin)
            q = jnp.moveaxis(q, 0, 2)
            k, v = heads(row[:, :kvh * d]), heads(row[:, kvh * d:])
            if prior is None:
                o = gqa_flash_attention(q, k, v, scale=self._scale())
            else:
                # the pool's rows before the piece (what lies behind them
                # there is not read: zeros), then the piece's own
                before = prior(j)
                before = jnp.where(
                    (jnp.arange(before.shape[0]) < start)[:, None], before, 0)
                k = lax.dynamic_update_slice(heads(before[:, :kvh * d]), k,
                                             (0, start, 0))
                v = lax.dynamic_update_slice(heads(before[:, kvh * d:]), v,
                                             (0, start, 0))
                o = gqa_flash_attention_from(q, k, v, start,
                                             scale=self._scale())
            # o (KV, G, S, D)
            x = _full_output(fp, x, jnp.moveaxis(o, 2, 0).reshape(s, -1), gate)
            x, c_full = _mlp(cfg, mp, x, live, experts, first + every - 1)
            counters = moe.merge_counters(
                jnp.concatenate([c_delta, c_full[None]]))
            return x, (row, states, tails, counters)

        def by_period(tree, per):
            return {k: w.reshape((periods, per) + w.shape[1:])
                    for k, w in tree.items()}

        x, (rows, states, tails, counters) = lax.scan(
            period, x, (by_period(params["delta"], every - 1), params["full"],
                        by_period(params["moe"], every), jnp.arange(periods),
                        s_in, tail_in))
        state = {"s": states.reshape((-1,) + states.shape[2:]),
                 "tail": tails.reshape((-1,) + self.state["tail"][0][1:])}
        return (self._head(params, x[jnp.clip(length - 1 - start, 0, s - 1)]),
                rows, moe.merge_counters(counters), state)

    def step(self, params, tokens, positions, live, attend, state):
        """tokens, positions (B,), live (B,) bool; ``attend(paged layer,
        query, row) -> o``; ``state``: every slot's ``s`` and ``tail``
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
            mp = {k: w[i] for k, w in params["moe"].items()}
            if i in self.full_layers:
                j = self.full_layers.index(i)
                lp = {k: w[j] for k, w in params["full"].items()}
                lp["attn_norm"] = mp["attn_norm"]
                q, gate, row = _full_projections(cfg, lp, x, cos, sin)
                x = _full_output(lp, x, attend(j, q, row).reshape(b, -1), gate)
            else:
                j = self.delta_layers.index(i)
                lp = {k: w[j] for k, w in params["delta"].items()}
                lp["attn_norm"] = mp["attn_norm"]
                qkv, z, bb, a = _delta_inputs(cfg, lp, x)
                old = tails[:b, j].reshape((b,) + self._tail)
                conv, new = gated_delta.causal_conv_step(qkv, old,
                                                         lp["conv_w"])
                tails = tails.at[:b, j].set(
                    jnp.where(live[:, None, None], new, old).reshape(
                        (b,) + tails.shape[2:]))
                q, k, v, g, beta = _delta_heads(cfg, lp, conv, bb, a)
                q, k = (jnp.repeat(u, v.shape[1] // u.shape[1], axis=1)
                        for u in (q, k))
                o, s_all = gated_delta.delta_rule_step(
                    s_all, j, q, k, v, g, beta, live, impl=impl,
                    interpret=_use_interpret())
                x = _delta_output(cfg, lp, x, o, z)
            x, c = _mlp(cfg, mp, x, live, params["experts"], i)
            counters.append(c)
        return (self._head(params, x), moe.merge_counters(jnp.stack(counters)),
                {"s": s_all, "tail": tails})

    def attention(self, query, pool, layer, page_table, lengths):
        return gqa_decode_attention(query, pool, layer, page_table, lengths,
                                    scale=self._scale())

    counters = tuple("moe." + name for name in moe.COUNTERS)

    def delta_rule(self):
        """``DecodeEngine.stats()["delta_rule"]``: the form the delta rule
        takes over a prompt in this process (``gated_delta.chunked_form``)."""
        return gated_delta.chunked_form(
            self.cfg["linear_key_dim"], self.cfg["linear_value_dim"],
            impl=decode_attention_impl(), interpret=_use_interpret())

    def moe_row_tile(self, tokens):
        """``DecodeEngine.stats()["moe_row_tile"]``: the row tile the held
        experts' grouped products run a call of ``tokens`` tokens with."""
        return moe.layer_row_tile(tokens, self.cfg["experts_per_token"],
                                  self.cfg["router_experts"], self.cache_dtype)

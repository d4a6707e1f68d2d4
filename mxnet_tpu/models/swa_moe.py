"""A functional decoder whose layers mix sliding-window and global
grouped-KV attention under a pattern, a leading dense MLP and expert layers
with no shared expert after it (MiMo-V2-Flash), for the decode engine
(``serve/decode.py``). Not a gluon block and not imported by
``mxnet_tpu.models``: import it where it is used. The equations are written
out in ``benchmark/reference_swa_moe.py``.

Pre-norm residual blocks, ``RMSNorm(x) = x rsqrt(mean x^2 + eps) w``. Layer
``i`` is a window layer where ``layer_pattern[i]`` is 1, else global; its MLP
is ``ops/moe.py``'s expert layer where ``moe_pattern[i]`` is 1 (sigmoid
router over ``router_experts`` with a choosing bias, the chip's share
``experts_first .. + experts_held``, no shared expert), else a SiLU-gated MLP
of ``dense_width``; an untied head. Attention, ``h = RMSNorm(x)``: ``num_heads``
queries of ``head_dim``, ``kv_heads`` (global) or ``swa_kv_heads`` (window)
cached heads of ``head_dim`` keys and ``v_head_dim`` values, RoPE (rotate-half)
on the first ``rotary_dim`` dimensions of every query and key head with
``rope_theta`` or ``swa_rope_theta``; a window layer's row sees itself and the
``window - 1`` positions before it, with a learned sink a query head in the
softmax's denominator; the output times ``value_scale`` before ``W_o``.

**Two kinds of cache, two rows.** Both rows are FLAT (``ops/swa_attention.py``):
a position's keys of every cached head, then its values. A GLOBAL layer keeps
a row a position for ever in the engine's page pool: only the global layers
are paged (``paged_layers``), ``cache_row`` is theirs. A WINDOW layer keeps
its newest ``window`` positions and nothing else: a ring in per-slot
``state`` (``"window"``: window layers x ``window`` x the window row), a
position's row at index ``position mod window``, which the engine holds
beside the pool — a prefill returns its slot's, the step is handed all of
them and returns them with ONE row a live slot a layer written in place.

- **prefill / prefill_from**: the layers unrolled (twelve bodies of two
  kinds: no ``lax.scan`` slices a layer's weights out of a stack). A piece
  (``prefill_from``; the engine feeds every prompt so) is positions ``start ..
  start + C - 1``, C a multiple of ``window``: a global layer reads the pool's
  rows before the piece (``prior``) and attends through
  ``swa_attention.attention_from``; a window layer reads NOTHING of the pool:
  its keys are the ring as the piece before left it — positions ``start -
  window .. start - 1`` in order — and the piece's own rows, and it returns
  the ring of the last ``window`` positions up to the prompt's end.
- **step**: one token a slot; a window layer writes its row into the ring at
  ``position mod window`` (an idle slot's into the scratch slot) and attends
  through ``swa_decode_attention``, a global layer through the engine's
  ``attend`` (``gqa_decode_attention_dv`` over the pool).

Weights and activations are bfloat16 with float32 accumulation; norms, RoPE,
softmax, the sinks and the router run in float32.

**Seeded weights** (``init_params``): the scheme of ``models/mla_moe.py`` —
every leaf ``0.02 N(0, 1)`` in bfloat16 from random bytes (norm gains ``1 +``
that; a sink and an embedding row ``N(0, 1)``: 50 times the leaf, so that a
position's stream is its token's and not the context's mean), keyed by seed, leaf, layer (and
global expert, or block of 8192 rows of the published vocabulary). The key is
an ARGUMENT of the programs that draw them: a new seed builds nothing.
``benchmark/reference_swa_moe.py`` states the same scheme on its own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import moe
from ..ops.swa_attention import (attention_from, gqa_decode_attention_dv,
                                 swa_decode_attention)
from .mla_moe import _normal_bf16

__all__ = ["config_from_hf", "init_params", "SWAMoEDecodeModel"]

LEAVES = ("embed", "head", "final_norm", "attn_norm", "mlp_norm", "q_w",
          "k_w", "v_w", "o_w", "sink", "gate_w", "up_w", "down_w", "router_w",
          "router_b", "experts_gate_w", "experts_up_w", "experts_down_w")
GAINS = ("final_norm", "attn_norm", "mlp_norm")
EXPERTS = ("experts_gate_w", "experts_up_w", "experts_down_w")
VOCAB_BLOCK = 8192
SINK_SCALE = 50.0       # 0.02 N(0, 1) x 50: a sink is N(0, 1)
# ... and so is an embedding row: at 0.02 a token's row is a fourteenth of
# what layer 0's attention adds to it (the mean of thousands of values, the
# same vector for every position under Zipf ids), every position's stream is
# then one vector and the router picks the same experts for every token
EMBED_SCALE = 50.0
COUNTERS = tuple("moe." + name for name in moe.COUNTERS) + (
    "attn.window_rows", "attn.global_rows")


def config_from_hf(hf: dict, *, experts_first: int = 0,
                   experts_held: int = None, router_experts: int = None,
                   vocab_first: int = 0, max_length: int = None) -> dict:
    """The model's description from a ``mimo_v2_flash`` ``config.json``.
    ``router_experts`` is the router's published width where
    ``hf["n_routed_experts"]`` has been cut to the experts held here;
    ``vocab_first`` the first row held where ``hf["vocab_size"]`` has been
    cut to a slice; the patterns are cut to ``num_hidden_layers``."""
    n = hf["num_hidden_layers"]
    if hf.get("n_shared_experts") or hf.get("add_full_attention_sink_bias"):
        raise NotImplementedError("a shared expert, or a sink in the global "
                                  "layers: not written")
    if (hf["scoring_func"], hf["topk_method"], hf["n_group"]) != (
            "sigmoid", "noaux_tc", 1) or not hf["norm_topk_prob"]:
        raise NotImplementedError("a router other than sigmoid scores, a "
                                  "choosing bias, one group, renormalised")
    if (hf["swa_num_attention_heads"], hf["swa_head_dim"],
            hf["swa_v_head_dim"]) != (hf["num_attention_heads"],
                                      hf["head_dim"], hf["v_head_dim"]):
        raise NotImplementedError("window layers whose query heads differ "
                                  "from the global layers'")
    return {
        "vocab_size": hf["vocab_size"], "vocab_first": vocab_first,
        "hidden_size": hf["hidden_size"], "num_layers": n,
        "layer_pattern": list(hf["hybrid_layer_pattern"][:n]),
        "moe_pattern": list(hf["moe_layer_freq"][:n]),
        "num_heads": hf["num_attention_heads"],
        "head_dim": hf["head_dim"], "v_head_dim": hf["v_head_dim"],
        "kv_heads": hf["num_key_value_heads"],
        "swa_kv_heads": hf["swa_num_key_value_heads"],
        "window": hf["sliding_window"],
        "swa_sink": bool(hf["add_swa_attention_sink_bias"]),
        "rotary_dim": int(hf["head_dim"] * hf["partial_rotary_factor"]),
        "rope_theta": hf["rope_theta"], "swa_rope_theta": hf["swa_rope_theta"],
        "value_scale": hf["attention_value_scale"],
        "dense_width": hf["intermediate_size"],
        "expert_width": hf["moe_intermediate_size"],
        "router_experts": router_experts or hf["n_routed_experts"],
        "experts_first": experts_first,
        "experts_held": experts_held or hf["n_routed_experts"],
        "experts_per_token": hf["num_experts_per_tok"],
        "routed_scale": hf["routed_scaling_factor"] or 1.0,
        "rms_eps": hf["layernorm_epsilon"],
        "max_length": max_length or hf["max_position_embeddings"],
    }


def leaf_shapes(cfg: dict, window: bool = False) -> dict:
    """name -> shape of one layer's leaf (one expert's, for ``experts_*``),
    of a window layer or a global one. Matrices are (in, out), but ``q_w``
    (out, in)."""
    d, h, dk, dv = (cfg["hidden_size"], cfg["num_heads"], cfg["head_dim"],
                    cfg["v_head_dim"])
    kv = cfg["swa_kv_heads"] if window else cfg["kv_heads"]
    f, fe, e = cfg["dense_width"], cfg["expert_width"], cfg["router_experts"]
    return {"final_norm": (d,), "attn_norm": (d,), "mlp_norm": (d,),
            "q_w": (h * dk, d), "k_w": (d, kv * dk), "v_w": (d, kv * dv),
            "o_w": (h * dv, d), "sink": (h,),
            "gate_w": (d, f), "up_w": (d, f), "down_w": (f, d),
            "router_w": (d, e), "router_b": (e,),
            "experts_gate_w": (d, fe), "experts_up_w": (d, fe),
            "experts_down_w": (fe, d)}


def layer_kinds(cfg: dict):
    """(the indices of the window layers, of the global layers, of the
    expert layers)."""
    n = cfg["num_layers"]
    pattern, mlps = cfg["layer_pattern"], cfg["moe_pattern"]
    if len(pattern) != n or len(mlps) != n:
        raise ValueError(f"patterns of {len(pattern)} and {len(mlps)} for "
                         f"{n} layers")
    return ([i for i in range(n) if pattern[i]],
            [i for i in range(n) if not pattern[i]],
            [i for i in range(n) if mlps[i]])


def _draw(key, name, shape, *path):
    key = jax.random.fold_in(key, LEAVES.index(name))
    for i in path:
        key = jax.random.fold_in(key, i)
    x = _normal_bf16(key, shape)
    if name in GAINS:
        x = (1.0 + x.astype(jnp.float32)).astype(jnp.bfloat16)
    if name == "sink":
        x = x.astype(jnp.float32) * SINK_SCALE
    if name == "embed":
        x = (x.astype(jnp.float32) * EMBED_SCALE).astype(jnp.bfloat16)
    return x


def init_params(cfg: dict, seed: int) -> dict:
    """The seeded weights on the default device (module docstring):
    ``embed``, ``head``, ``final_norm``; ``layers``, a list with every
    layer's own leaves — ``attn_norm``, ``mlp_norm``, ``q_w``, ``kv_w = [k_w
    || v_w]``, ``o_w``, a window layer's ``sink`` (float32), a dense layer's
    ``gate_w``, ``up_w``, ``down_w``, an expert layer's ``router_w``,
    ``router_b``: arrays of their own, so that no program slices a layer out
    of a stack (XLA:TPU copied two of three 100 MB ``q_w`` slices, a step) —
    and ``experts``: ``gate_w``, ``up_w``, ``down_w`` with every expert
    layer's held experts on ONE leading axis (layer-major), filled in place,
    as the grouped kernel takes them. The key, and a layer's index, are
    arguments of the programs: a kind of layer is one program."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)
    v, d = cfg["vocab_size"], cfg["hidden_size"]
    first, held = cfg["experts_first"], cfg["experts_held"]
    window, _, routed = layer_kinds(cfg)

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

    def layer(key, i, is_window, is_routed):
        shapes = leaf_shapes(cfg, is_window)
        names = ("attn_norm", "mlp_norm", "q_w", "o_w") + (
            ("sink",) if is_window and cfg["swa_sink"] else ()) + (
            ("router_w", "router_b") if is_routed
            else ("gate_w", "up_w", "down_w"))
        out = {name: _draw(key, name, shapes[name], i) for name in names}
        out["kv_w"] = jnp.concatenate(
            [_draw(key, name, shapes[name], i) for name in ("k_w", "v_w")],
            axis=-1)
        return out

    def experts(name, key):
        shape = leaf_shapes(cfg)[name]

        def one(i, buf):
            w = _draw(key, name, shape,
                      jnp.asarray(routed, jnp.int32)[i // held],
                      first + i % held)
            return lax.dynamic_update_slice(buf, w[None], (i, 0, 0))

        return lax.fori_loop(0, len(routed) * held, one,
                             jnp.zeros((len(routed) * held,) + shape,
                                       jnp.bfloat16))

    params = jax.jit(top)(key)
    make = jax.jit(layer, static_argnums=(2, 3))
    params["layers"] = [make(key, i, i in window, i in routed)
                        for i in range(cfg["num_layers"])]
    make = jax.jit(experts, static_argnums=0)
    params["experts"] = {name[len("experts_"):]: make(name, key)
                         for name in EXPERTS}
    return params


# -- the layers -----------------------------------------------------------------

def rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    return (x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * gain.astype(jnp.float32))


def _mm(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _rope(x, cos, sin):
    """x (T, heads, D) float32; cos, sin (T, rot / 2): the first ``rot``
    dimensions of each head rotated, pairs (j, j + rot / 2)."""
    half = cos.shape[1]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    cos, sin = cos[:, None], sin[:, None]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def _projections(cfg, lp, x, cos, sin):
    """For tokens x (T, hidden) of one attention layer (its cached heads
    read off ``kv_w``): the query (T, KV, G, dk) — cached head, then its
    group's heads — and the cache row ``[k of every head || v]`` (T, KV (dk +
    dv)), both in x's dtype."""
    t = x.shape[0]
    heads, dk, dv = cfg["num_heads"], cfg["head_dim"], cfg["v_head_dim"]
    kv = lp["kv_w"].shape[1] // (dk + dv)
    h = rms_norm(x, lp["attn_norm"], cfg["rms_eps"]).astype(x.dtype)
    q = jnp.einsum("td,ed->te", h, lp["q_w"],      # (out, in): as it lies
                   preferred_element_type=jnp.float32)
    q = _rope(q.reshape(t, heads, dk), cos, sin)
    kvp = _mm(h, lp["kv_w"])
    k = _rope(kvp[:, :kv * dk].reshape(t, kv, dk), cos, sin)
    row = jnp.concatenate([k.reshape(t, kv * dk), kvp[:, kv * dk:]], axis=-1)
    return (q.reshape(t, kv, heads // kv, dk).astype(x.dtype),
            row.astype(x.dtype))


def _output(cfg, lp, x, o):
    """x + (value_scale . o) W_o for o (T, H dv)."""
    o = (o.astype(jnp.float32) * cfg["value_scale"]).astype(x.dtype)
    return (x.astype(jnp.float32) + _mm(o, lp["o_w"])).astype(x.dtype)


class SWAMoEDecodeModel:
    """The model as ``DecodeEngine`` takes one (``serve/decode.py``, "the
    model by interface"): the global layers' rows in the pool, the window
    layers' rings in per-slot state. ``params`` default to
    ``init_params(cfg, seed)``."""

    counters = COUNTERS

    def __init__(self, cfg: dict, seed: int = 0, params: dict = None):
        self.cfg = dict(cfg)
        self.layers = int(cfg["num_layers"])
        self.window_layers, self.global_layers, self.expert_layers = (
            layer_kinds(cfg))
        self.paged_layers = len(self.global_layers)
        wide = cfg["head_dim"] + cfg["v_head_dim"]
        self.cache_row = (cfg["kv_heads"] * wide,)
        self.params = init_params(cfg, seed) if params is None else params
        # bfloat16, as the weights (a float32 tree, as the tests make one,
        # runs the same bodies in float32)
        self.cache_dtype = self.params["embed"].dtype
        self.window = int(cfg["window"])
        # per slot: every window layer's newest ``window`` rows
        self.state = {"window": ((len(self.window_layers), self.window,
                                  cfg["swa_kv_heads"] * wide),
                                 self.cache_dtype)}
        rot = cfg["rotary_dim"]
        self._inv_freq = {
            is_window: (1.0 / cfg[name] ** (
                np.arange(0, rot, 2, dtype=np.float64) / rot)
            ).astype(np.float32)
            for is_window, name in ((False, "rope_theta"),
                                    (True, "swa_rope_theta"))}

    def _angles(self, positions):
        """{window layer?: (cos, sin)} of the two RoPE bases."""
        out = {}
        for is_window, inv_freq in self._inv_freq.items():
            angle = positions.astype(jnp.float32)[:, None] * inv_freq[None]
            out[is_window] = (jnp.cos(angle), jnp.sin(angle))
        return out

    def _head(self, params, x):
        h = rms_norm(x, params["final_norm"], self.cfg["rms_eps"])
        return jnp.einsum("...d,vd->...v", h.astype(x.dtype), params["head"],
                          preferred_element_type=jnp.float32)

    def _scale(self):
        return self.cfg["head_dim"] ** -0.5

    def _kind(self, i):
        """(window layer?, its index among the layers of its kind) of
        layer ``i``."""
        is_window = i in self.window_layers
        return is_window, (self.window_layers if is_window
                           else self.global_layers).index(i)

    def _mlp(self, params, i, x, live):
        """x + MLP(RMSNorm(x)) of layer ``i``; (x', counters or None)."""
        cfg, lp = self.cfg, params["layers"][i]
        h = rms_norm(x, lp["mlp_norm"], cfg["rms_eps"]).astype(x.dtype)
        if i in self.expert_layers:
            held = cfg["experts_held"]
            y, counters = moe.expert_layer(
                h, {name: lp[name] for name in ("router_w", "router_b")},
                params["experts"], live, first=cfg["experts_first"],
                held=held, k=cfg["experts_per_token"],
                scale=cfg["routed_scale"],
                offset=self.expert_layers.index(i) * held)
        else:
            y, counters = moe.gated_mlp(h, lp["gate_w"], lp["up_w"],
                                        lp["down_w"]), None
        return (x.astype(jnp.float32) + y).astype(x.dtype), counters

    def _counted(self, counters, positions, live):
        """The expert layers' counters, then the rows the attention of the
        call's live tokens had to see: ``min(position + 1, window)`` a window
        layer, ``position + 1`` a global one."""
        seen = jnp.where(live, positions + 1, 0).astype(jnp.int32)
        rows = jnp.stack([
            jnp.sum(jnp.minimum(seen, self.window)) * len(self.window_layers),
            jnp.sum(seen) * len(self.global_layers)])
        return jnp.concatenate([moe.merge_counters(jnp.stack(counters)),
                                rows.astype(jnp.int32)])

    def prefill(self, params, tokens, length):
        """tokens (1, S), S a multiple of ``window``; length () -> (logits at
        ``length - 1`` (V,) float32, rows (paged layers, S, row), counters,
        the sequence's state ``{"window"}`` after ``length`` tokens)."""
        return self._prompt(params, tokens, 0, length, None, None)

    def prefill_from(self, params, tokens, start, length, prior, state):
        """A prompt continued: tokens (1, C) are positions ``start .. start +
        C - 1`` of a prompt of ``length`` (``start`` () int32, a multiple of
        C; C a multiple of ``window``), ``state`` the sequence's own
        ``{"window"}`` as the piece before left it — never seen where
        ``start`` is 0, whatever it holds —, and ``prior(paged layer) -> (T,
        row)`` the rows of positions 0 .. T - 1 as the pool has them, of
        which those ``< start`` are read, by the global layers alone. Returns
        what :meth:`prefill` returns: the logits at ``length - 1`` (of the
        last piece alone: zeros before, and the head's weights not read),
        the piece's rows, its counters, the state after the piece."""
        return self._prompt(params, tokens, start, length, prior, state)

    def _prompt(self, params, tokens, start, length, prior, state):
        """The body of :meth:`prefill` (``prior`` None: the whole prompt
        from position 0) and :meth:`prefill_from`."""
        cfg, w = self.cfg, self.window
        c = tokens.shape[1]
        dk, dv = cfg["head_dim"], cfg["v_head_dim"]
        positions = start + jnp.arange(c)
        angles = self._angles(positions)
        live = positions < length
        x = params["embed"][tokens[0]]
        # the rings' indices of the ``window`` positions up to the piece's
        # live end, in the rows [the ring before || the piece]: index r
        # holds the newest position p < end with p mod window = r (one before
        # position 0 is clipped to a row the step never sees)
        end = jnp.minimum(length, start + c)
        newest = end - 1 - (end - 1 - jnp.arange(w)) % w
        take = jnp.clip(newest - start + w, 0, w + c - 1)

        def heads(flat, kvh, d):    # (T, KV d) -> (KV, T, d)
            return jnp.swapaxes(flat.reshape(-1, kvh, d), 0, 1)

        rows, rings, counters = [], [], []
        for i in range(self.layers):
            (is_window, j), lp = self._kind(i), params["layers"][i]
            q, row = _projections(cfg, lp, x, *angles[is_window])
            kvh = row.shape[1] // (dk + dv)
            q = jnp.moveaxis(q, 0, 2)                      # (KV, G, C, dk)
            if is_window:
                if state is None:
                    before = jnp.zeros((w, row.shape[1]), row.dtype)
                else:
                    # a slot's leftovers never reach a new prompt: where(),
                    # not a product, so that not even a NaN does
                    before = jnp.where(start == 0, 0, state["window"][j]
                                       ).astype(row.dtype)
                both = jnp.concatenate([before, row])      # (W + C, row)
                rings.append(jnp.take(both, take, axis=0))
                how = {"window": w, "sink": lp.get("sink")}
            else:
                both, how = row, {}
                if prior is not None:
                    # the pool's rows before the piece (what lies behind
                    # them there is not read: zeros), then the piece's own
                    before = prior(j)
                    before = jnp.where(
                        (jnp.arange(before.shape[0]) < start)[:, None],
                        before, 0)
                    both = lax.dynamic_update_slice(before, row, (start, 0))
                rows.append(row)
            o = attention_from(q, heads(both[:, :kvh * dk], kvh, dk),
                               heads(both[:, kvh * dk:], kvh, dv), start,
                               scale=self._scale(), **how)
            # o (KV, G, C, dv)
            x = _output(cfg, lp, x, jnp.moveaxis(o, 2, 0).reshape(c, -1))
            x, counted = self._mlp(params, i, x, live)
            if counted is not None:
                counters.append(counted)
        logits = lax.cond(
            start + c >= length, lambda h: self._head(params, h),
            lambda h: jnp.zeros((cfg["vocab_size"],), jnp.float32),
            x[jnp.clip(length - 1 - start, 0, c - 1)])
        return (logits, jnp.stack(rows),
                self._counted(counters, positions, live),
                {"window": jnp.stack(rings)})

    def step(self, params, tokens, positions, live, attend, state):
        """tokens, positions (B,), live (B,) bool; ``attend(paged layer,
        query, row) -> o``; ``state``: every slot's rings (slots + 1 leading,
        the last scratch). Returns (logits (B, V) float32, counters, state)
        — the rings of a slot that is not live untouched."""
        cfg, w = self.cfg, self.window
        b = tokens.shape[0]
        angles = self._angles(positions)
        x = params["embed"][tokens]
        ring = state["window"]
        # an idle slot (one whose prompt is still going in, its ring the
        # pieces' so far) writes into the scratch slot
        slot = jnp.where(live, jnp.arange(b), ring.shape[0] - 1)
        counters = []
        for i in range(self.layers):
            (is_window, j), lp = self._kind(i), params["layers"][i]
            q, row = _projections(cfg, lp, x, *angles[is_window])
            if is_window:
                ring = ring.at[slot, j, positions % w].set(row)
                o = swa_decode_attention(q, ring, j, positions,
                                         lp.get("sink"), cfg["v_head_dim"],
                                         scale=self._scale())
            else:
                o = attend(j, q, row)
            x = _output(cfg, lp, x, o.reshape(b, -1))
            x, counted = self._mlp(params, i, x, live)
            if counted is not None:
                counters.append(counted)
        return (self._head(params, x),
                self._counted(counters, positions, live), {"window": ring})

    def attention(self, query, pool, layer, page_table, lengths):
        return gqa_decode_attention_dv(query, pool, layer, page_table,
                                       lengths, self.cfg["v_head_dim"],
                                       scale=self._scale())

    def moe_row_tile(self, tokens):
        """``DecodeEngine.stats()["moe_row_tile"]``: the row tile the held
        experts' grouped products run a call of ``tokens`` tokens with."""
        return moe.layer_row_tile(tokens, self.cfg["experts_per_token"],
                                  self.cfg["router_experts"], self.cache_dtype)

"""Transformer encoder / BERT / decoder-LM — the flagship family.

Reference counterpart: gluon-nlp's BERTModel/TransformerEncoder (external
repo, driven through the mx API — SURVEY.md §2.5 BERT-base config). Built
TPU-first:

- One fused QKV projection (one MXU matmul instead of three).
- bf16-friendly: params stay fp32; cast policy applied by AMP/trainer.
- Tensor parallel: ``bert_sharding_rules()`` shards QKV/FFN-in over the
  mesh ``tp`` axis on the output dim and out-proj/FFN-out on the input
  dim (Megatron layout: one all-reduce per block, inserted by XLA).
- Sequence parallel: when the active mesh (parallel.mesh_scope) has an
  ``sp`` axis > 1, attention runs as ring attention over the ICI
  (parallel/ring_attention.py) — long-context support the reference lacks.
"""
from __future__ import annotations

import math

import numpy as np

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter

__all__ = ["MultiHeadAttention", "PositionwiseFFN", "TransformerEncoderCell",
           "BERTEncoder", "BERTModel", "TransformerLM", "bert_base", "bert_large",
           "bert_tiny", "transformer_lm", "bert_sharding_rules",
           "decode_config", "decode_params", "prefill_layer", "decode_layer",
           "lm_prefill", "lm_decode_step", "sample_token"]


class MultiHeadAttention(HybridBlock):
    """Fused-QKV multi-head self-attention with optional ring execution."""

    def __init__(self, units, num_heads, dropout=0.0, causal=False, **kwargs):
        super().__init__(**kwargs)
        assert units % num_heads == 0
        self._units = units
        self._heads = num_heads
        self._causal = causal
        self.qkv = nn.Dense(3 * units, flatten=False, use_bias=True,
                            in_units=units, prefix=self.prefix + "qkv_")
        self.proj = nn.Dense(units, flatten=False, use_bias=True, in_units=units,
                             prefix=self.prefix + "proj_")
        self._dropout = dropout

    def hybrid_forward(self, F, x, mask=None):
        b, s, u = x.shape  # x: (B, S, U)
        h, d = self._heads, self._units // self._heads
        qkv = self.qkv(x)  # (B, S, 3U)
        # the flash kernels may read the projection where it lies
        out = _packed_attention(self, F, qkv, mask)
        if out is not None:
            return out
        # split, not tensor indexing: Symbol has none, and F stays generic
        qkv = qkv.reshape((b, s, 3, h, d))
        q, k, v = F.split(qkv, num_outputs=3, axis=2, squeeze_axis=True)
        q = q.transpose((0, 2, 1, 3))  # (B, H, S, D)
        k = k.transpose((0, 2, 1, 3))
        v = v.transpose((0, 2, 1, 3))

        from .. import parallel as par
        from ..ndarray.ndarray import invoke_fn
        from ..ops.attention import attention_impl, fused_attention

        mesh = par.current_mesh()
        sp = 1
        if mesh is not None:
            sp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("sp", 1)
        shape = (b, h, s, d)
        # On a mesh the Pallas kernel has to sit inside shard_map (ring
        # attention over sp; per-shard batch rows and heads otherwise):
        # XLA partitions plain attention by itself but not a Mosaic call.
        kernel_on_mesh = (
            mesh is not None and mesh.size > 1
            and attention_impl(shape, shape, mask is not None) == "flash")
        if sp > 1 or kernel_on_mesh:
            if mask is not None:
                raise NotImplementedError(
                    "sequence-parallel attention takes no explicit mask")
            out = invoke_fn(
                lambda qq, kk, vv: par.sequence_sharded_attention(
                    qq, kk, vv, mesh, causal=self._causal),
                [q, k, v])
        else:  # flash or fused XLA softmax-attention: ops/attention.py
            def attn(qq, kk, vv, mm=None):
                return fused_attention(qq, kk, vv, mask=mm,
                                       causal=self._causal)

            ins = [q, k, v] + ([mask] if mask is not None else [])
            out = invoke_fn(attn, ins)
        out = out.transpose((0, 2, 1, 3)).reshape((b, s, u))
        out = self.proj(out)
        if self._dropout:
            out = F.Dropout(out, p=self._dropout)
        return out


class PositionwiseFFN(HybridBlock):
    def __init__(self, units, hidden_size, dropout=0.0, activation="gelu", **kwargs):
        super().__init__(**kwargs)
        self.ffn_1 = nn.Dense(hidden_size, flatten=False, in_units=units,
                              prefix=self.prefix + "ffn1_")
        self.ffn_2 = nn.Dense(units, flatten=False, in_units=hidden_size,
                              prefix=self.prefix + "ffn2_")
        self._act = activation
        self._dropout = dropout

    def hybrid_forward(self, F, x):
        out = self.ffn_1(x)
        out = F.Activation(out, act_type=self._act) if self._act != "gelu" \
            else F.gelu(out, approximation="tanh")
        out = self.ffn_2(out)
        if self._dropout:
            out = F.Dropout(out, p=self._dropout)
        return out


class TransformerEncoderCell(HybridBlock):
    """Post-LN transformer block (BERT layout)."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0, causal=False,
                 **kwargs):
        super().__init__(**kwargs)
        self.attention = MultiHeadAttention(units, num_heads, dropout=dropout,
                                            causal=causal,
                                            prefix=self.prefix + "attn_")
        self.ln1 = nn.LayerNorm(in_channels=units)
        self.ffn = PositionwiseFFN(units, hidden_size, dropout=dropout,
                                   prefix=self.prefix + "ffn_")
        self.ln2 = nn.LayerNorm(in_channels=units)
        self._dropout = dropout

    def hybrid_forward(self, F, x, mask=None):
        att = self.attention(x, mask)
        x = self.ln1(x + att)
        out = self.ffn(x)
        return self.ln2(x + out)


class BERTEncoder(HybridBlock):
    def __init__(self, units=768, hidden_size=3072, num_layers=12, num_heads=12,
                 max_length=512, dropout=0.1, causal=False, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._max_length = max_length
        self.position_weight = self.params.get(
            "position_weight", shape=(max_length, units), init="zeros")
        self.cells = []
        for i in range(num_layers):
            cell = TransformerEncoderCell(units, hidden_size, num_heads,
                                          dropout=dropout, causal=causal,
                                          prefix=f"{self.prefix}layer{i}_")
            self.register_child(cell, f"layer{i}")
            self.cells.append(cell)
        self._dropout = dropout

    def hybrid_forward(self, F, x, position_weight, mask=None):
        b, s, u = x.shape
        pos = F.slice_axis(position_weight, axis=0, begin=0,
                           end=s).reshape((1, s, u))
        x = x + pos
        if self._dropout:
            x = F.Dropout(x, p=self._dropout)
        for cell in self.cells:
            x = cell(x, mask)
        return x


class BERTModel(HybridBlock):
    """BERT with MLM head (gluon-nlp BERTModel counterpart)."""

    def __init__(self, vocab_size=30522, units=768, hidden_size=3072,
                 num_layers=12, num_heads=12, max_length=512, dropout=0.1,
                 num_token_types=2, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self.word_embed = nn.Embedding(vocab_size, units,
                                       prefix=self.prefix + "word_embed_")
        self.token_type_embed = nn.Embedding(num_token_types, units,
                                             prefix=self.prefix + "type_embed_")
        self.embed_ln = nn.LayerNorm(in_channels=units)
        self.encoder = BERTEncoder(units, hidden_size, num_layers, num_heads,
                                   max_length, dropout,
                                   prefix=self.prefix + "enc_")
        self.mlm_dense = nn.Dense(units, flatten=False, in_units=units,
                                  prefix=self.prefix + "mlm_dense_")
        self.mlm_ln = nn.LayerNorm(in_channels=units)
        self.mlm_decoder = nn.Dense(vocab_size, flatten=False, in_units=units,
                                    prefix=self.prefix + "mlm_decoder_")

    def hybrid_forward(self, F, inputs, token_types=None):
        x = self.word_embed(inputs)
        if token_types is not None:
            x = x + self.token_type_embed(token_types)
        x = self.embed_ln(x)
        seq = self.encoder(x)
        h = self.mlm_dense(seq)
        h = F.gelu(h, approximation="tanh")
        h = self.mlm_ln(h)
        return self.mlm_decoder(h)


class TransformerLM(HybridBlock):
    """Decoder-only causal LM (GPT-style) — the long-context flagship."""

    def __init__(self, vocab_size=32000, units=768, hidden_size=3072,
                 num_layers=12, num_heads=12, max_length=2048, dropout=0.0,
                 **kwargs):
        super().__init__(**kwargs)
        self.word_embed = nn.Embedding(vocab_size, units,
                                       prefix=self.prefix + "word_embed_")
        self.encoder = BERTEncoder(units, hidden_size, num_layers, num_heads,
                                   max_length, dropout, causal=True,
                                   prefix=self.prefix + "enc_")
        self.final_ln = nn.LayerNorm(in_channels=units)
        self.decoder = nn.Dense(vocab_size, flatten=False, in_units=units,
                                prefix=self.prefix + "decoder_")

    def hybrid_forward(self, F, inputs):
        x = self.word_embed(inputs)
        x = self.encoder(x)
        x = self.final_ln(x)
        return self.decoder(x)


def bert_sharding_rules():
    """Megatron-style TP + dp-replicated rules for BERT/TransformerLM params.

    Works with parallel.ShardingRules spec pruning: on meshes without "tp"
    everything collapses to replicated.
    """
    from jax.sharding import PartitionSpec as P

    from ..parallel import ShardingRules

    return ShardingRules([
        (r"qkv_weight$", P("tp", None)),        # column parallel
        (r"ffn1_weight$", P("tp", None)),
        (r"qkv_bias$", P("tp")),
        (r"ffn1_bias$", P("tp")),
        (r"proj_weight$", P(None, "tp")),       # row parallel
        (r"ffn2_weight$", P(None, "tp")),
        (r"(word_embed|mlm_decoder|decoder)\d*_weight$", P("tp", None)),
    ], default=P())


def bert_tiny(vocab_size=1000, **kw):
    kw.setdefault("units", 64)
    kw.setdefault("hidden_size", 128)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 4)
    kw.setdefault("max_length", 128)
    return BERTModel(vocab_size=vocab_size, **kw)


def bert_base(vocab_size=30522, **kw):
    return BERTModel(vocab_size=vocab_size, units=768, hidden_size=3072,
                     num_layers=12, num_heads=12, **kw)


def bert_large(vocab_size=30522, **kw):
    return BERTModel(vocab_size=vocab_size, units=1024, hidden_size=4096,
                     num_layers=24, num_heads=16, **kw)


def transformer_lm(vocab_size=32000, **kw):
    return TransformerLM(vocab_size=vocab_size, **kw)


# ---------------------------------------------------------------------------
# Causal-LM decode interface (serve/decode.py consumes this)
#
# The gluon forward above is the TRAINING path: full (B, S) sequences, no
# cache. Generation wants the incremental form — prefill the prompt once,
# then one position per step against cached K/V. These are pure JAX
# functions over a flat param dict (extracted once from an initialized
# TransformerLM) so the decode engine can jit exactly two programs around
# them and compose its own attention (dense reference here, paged flash in
# ops/flash_attention.py) without re-tracing any gluon machinery.
# ---------------------------------------------------------------------------

_LN_EPS = 1e-5  # nn.LayerNorm default


def decode_config(lm: "TransformerLM") -> dict:
    """Static shape/config facts of an LM, for building decode programs."""
    enc = lm.encoder
    cell = enc.cells[0]
    att = cell.attention
    return {
        "vocab": lm.decoder._units,
        "units": att._units,
        "heads": att._heads,
        "head_dim": att._units // att._heads,
        "layers": len(enc.cells),
        "max_length": enc._max_length,
    }


def decode_params(lm: "TransformerLM") -> dict:
    """Extract a flat numpy param dict from an initialized TransformerLM.

    The block must have run at least one forward pass (deferred init).
    Layout: top-level embed/pos/final-LN/decoder arrays plus one dict per
    layer under ``"layers"``.
    """

    def _np(p: Parameter) -> np.ndarray:
        return p.data().asnumpy()

    layers = []
    for cell in lm.encoder.cells:
        att, ffn = cell.attention, cell.ffn
        layers.append({
            "qkv_w": _np(att.qkv.weight), "qkv_b": _np(att.qkv.bias),
            "proj_w": _np(att.proj.weight), "proj_b": _np(att.proj.bias),
            "ln1_g": _np(cell.ln1.gamma), "ln1_b": _np(cell.ln1.beta),
            "ffn1_w": _np(ffn.ffn_1.weight), "ffn1_b": _np(ffn.ffn_1.bias),
            "ffn2_w": _np(ffn.ffn_2.weight), "ffn2_b": _np(ffn.ffn_2.bias),
            "ln2_g": _np(cell.ln2.gamma), "ln2_b": _np(cell.ln2.beta),
        })
    return {
        "embed": _np(lm.word_embed.weight),
        "pos": _np(lm.encoder.position_weight),
        "final_g": _np(lm.final_ln.gamma), "final_b": _np(lm.final_ln.beta),
        "dec_w": _np(lm.decoder.weight), "dec_b": _np(lm.decoder.bias),
        "layers": layers,
    }


def _ln(x, g, b):
    import jax.numpy as jnp
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + _LN_EPS) * g + b


def _dense(x, w, b):
    # gluon Dense stores weight as (out, in): y = x @ w.T + b
    return x @ w.T + b


def _gelu(x):
    import jax.nn
    return jax.nn.gelu(x, approximate=True)


def _split_heads(qkv, heads, head_dim):
    import jax.numpy as jnp
    # qkv (..., 3U) -> q, k, v each (..., H, D)
    parts = qkv.reshape(qkv.shape[:-1] + (3, heads, head_dim))
    return (jnp.squeeze(p, axis=-3)
            for p in jnp.split(parts, 3, axis=-3))


def prefill_layer(cfg, lp, x, mask):
    """One post-LN block over a full prompt. x (B, S, U), mask (S, S) or
    (B, S, S) additive-boolean (True = attend). Returns (x', k, v) with
    k/v shaped (B, S, H, D)."""
    import jax
    import jax.numpy as jnp
    h, d = cfg["heads"], cfg["head_dim"]
    q, k, v = _split_heads(_dense(x, lp["qkv_w"], lp["qkv_b"]), h, d)
    scale = 1.0 / math.sqrt(d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask[:, None] if mask.ndim == 3 else mask,
                       scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    att = _dense(ctx.reshape(x.shape), lp["proj_w"], lp["proj_b"])
    x = _ln(x + att, lp["ln1_g"], lp["ln1_b"])
    out = _dense(_gelu(_dense(x, lp["ffn1_w"], lp["ffn1_b"])),
                 lp["ffn2_w"], lp["ffn2_b"])
    return _ln(x + out, lp["ln2_g"], lp["ln2_b"]), k, v


def decode_layer(cfg, lp, x, attend):
    """One post-LN block for a single new position per sequence.

    x (B, U); ``attend(q, k_new, v_new) -> ctx`` supplies attention over
    the cached history (q/k_new/v_new/ctx all (B, H, D)) — the dense
    reference passes a mask-and-softmax closure, the decode engine passes
    a paged-KV closure that also writes k_new/v_new into the page pool.
    Returns (x', k_new, v_new)."""
    h, d = cfg["heads"], cfg["head_dim"]
    q, k, v = _split_heads(_dense(x, lp["qkv_w"], lp["qkv_b"]), h, d)
    ctx = attend(q, k, v)
    att = _dense(ctx.reshape(x.shape), lp["proj_w"], lp["proj_b"])
    x = _ln(x + att, lp["ln1_g"], lp["ln1_b"])
    out = _dense(_gelu(_dense(x, lp["ffn1_w"], lp["ffn1_b"])),
                 lp["ffn2_w"], lp["ffn2_b"])
    return _ln(x + out, lp["ln2_g"], lp["ln2_b"]), k, v


def stack_layers(layers):
    """A list of per-layer param dicts (``decode_params``'s layout) as one
    dict of arrays with a leading layer axis — what ``lm_prefill`` scans
    over and ``DecodeEngine`` holds on the device."""
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *layers)


def lm_prefill(cfg, params, tokens):
    """Causal forward over a prompt batch. tokens (B, S) int32.
    ``params["layers"]`` is the list ``decode_params`` gives or the same
    weights already stacked (``stack_layers``).

    Returns (logits (B, S, V), k (L, B, S, H, D), v (L, B, S, H, D)) —
    the dense KV state ``lm_decode_step`` consumes. Padded positions are
    harmless: causal masking means row i only sees columns <= i, and the
    caller reads logits at its true last position.

    The layers run under ``lax.scan``, one compiled body for all of them:
    unrolled, a 24-layer prefill at 768 positions is 190 MB of TPU code
    (8 MB a layer, none of it shared), against 9 MB scanned — which is
    what a replica loads at start-up and what the compile cache holds."""
    import jax
    import jax.numpy as jnp
    b, s = tokens.shape
    x = params["embed"][tokens] + params["pos"][:s]
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    layers = params["layers"]
    if not isinstance(layers, dict):
        layers = stack_layers(layers)

    def layer(x, lp):
        x, k, v = prefill_layer(cfg, lp, x, causal)
        return x, (k, v)

    x, (k, v) = jax.lax.scan(layer, x, layers)
    x = _ln(x, params["final_g"], params["final_b"])
    logits = _dense(x, params["dec_w"], params["dec_b"])
    return logits, k, v


class TransformerDecodeModel:
    """``TransformerLM``'s decode programs behind the interface
    ``serve.DecodeEngine`` takes a model by: ``cfg`` (``max_length``),
    ``params`` (a device tree), the cache row of one position in one layer
    (``cache_row``, ``cache_dtype``; here K and V of every head side by
    side), ``prefill``, ``step`` and ``attention``. ``lm`` is an initialized
    block, or its config dict with ``params`` as ``decode_params`` gives
    them."""

    counters = ()           # this model reports none with its tokens

    def __init__(self, lm, params=None):
        import jax
        import jax.numpy as jnp

        if params is None:
            self.cfg, params = decode_config(lm), decode_params(lm)
        else:
            self.cfg = dict(lm)
        self.layers = self.cfg["layers"]
        self.cache_row = (self.cfg["heads"], 2 * self.cfg["head_dim"])
        self.cache_dtype = jnp.float32
        # the layers' weights stacked along a leading axis: prefill scans
        # over them (one compiled layer body, not one per layer), the step
        # takes layer i's as static slices, which cost nothing
        params = dict(params, layers=stack_layers(params["layers"]))
        self.params = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)

    def prefill(self, params, tokens, length):
        """tokens (1, S) -> (logits at ``length - 1`` (V,), every layer's
        cache rows (L, S, H, 2D), None)."""
        import jax.numpy as jnp

        logits, k, v = lm_prefill(self.cfg, params, tokens)
        rows = jnp.concatenate([k, v], axis=-1)
        return logits[0, length - 1], rows.reshape(
            (self.layers, tokens.shape[1]) + self.cache_row), None

    def step(self, params, tokens, positions, live, attend):
        """One position for every slot; ``attend(layer, q, row)`` writes
        the row into the cache and attends. Returns (logits (B, V), None)."""
        import jax.numpy as jnp

        x = params["embed"][tokens] + params["pos"][positions]
        for i in range(self.layers):
            lp = {k: w[i] for k, w in params["layers"].items()}

            def attend_kv(q, k_new, v_new, _i=i):
                return attend(_i, q, jnp.concatenate([k_new, v_new], axis=-1))

            x, _, _ = decode_layer(self.cfg, lp, x, attend_kv)
        x = _ln(x, params["final_g"], params["final_b"])
        return _dense(x, params["dec_w"], params["dec_b"]), None

    def attention(self, query, pool, layer, page_table, lengths):
        from ..ops.flash_attention import decode_attention

        return decode_attention(query, pool, layer, page_table, lengths)

    def paged_kernel(self, pool, slots, max_pages):
        """``DecodeEngine.stats()["paged_kernel"]``: the pages the paged
        kernel reads a grid step over this pool and a page table this wide,
        and the grid steps of one decode step (a call a layer); None where
        ``attention`` gathers through XLA instead."""
        from ..ops.flash_attention import (decode_attention_impl,
                                           decode_page_group)

        if decode_attention_impl(pool) != "pallas":
            return None
        group = decode_page_group(pool.shape, max_pages)
        return {"page_group": group,
                "grid_steps": slots * -(-max_pages // group) * self.layers}


def lm_decode_step(cfg, params, tokens, kv, positions):
    """One decode step over dense KV (the paged engine's reference).

    tokens (B,) int32; kv = (k, v) each (L, B, S, H, D) with S the cache
    capacity; positions (B,) int32 — the index being written this step.
    Returns (logits (B, V), (k, v) updated)."""
    import jax
    import jax.numpy as jnp
    k_all, v_all = kv
    b = tokens.shape[0]
    rows = jnp.arange(b)
    x = params["embed"][tokens] + params["pos"][positions]
    scale = 1.0 / math.sqrt(cfg["head_dim"])
    cols = jnp.arange(k_all.shape[2])
    for i, lp in enumerate(params["layers"]):
        def attend(q, k_new, v_new, _i=i):
            nonlocal k_all, v_all
            k_all = k_all.at[_i, rows, positions].set(k_new)
            v_all = v_all.at[_i, rows, positions].set(v_new)
            scores = jnp.einsum("bhd,bshd->bhs", q, k_all[_i],
                                preferred_element_type=jnp.float32) * scale
            live = cols[None, :] <= positions[:, None]  # (B, S)
            scores = jnp.where(live[:, None], scores, -1e30)
            p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
            return jnp.einsum("bhs,bshd->bhd", p, v_all[_i])

        x, _, _ = decode_layer(cfg, lp, x, attend)
    x = _ln(x, params["final_g"], params["final_b"])
    return _dense(x, params["dec_w"], params["dec_b"]), (k_all, v_all)


def sample_token(logits, rng, temperature):
    """On-device sampling: temperature > 0 draws from softmax(logits / t),
    temperature <= 0 is greedy argmax. ``temperature`` may be scalar or
    per-row (B,). Returns int32 (B,)."""
    import jax
    import jax.numpy as jnp
    t = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32),
                         logits.shape[:-1])
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_t = jnp.maximum(t, 1e-4)[..., None]
    drawn = jax.random.categorical(
        rng, logits.astype(jnp.float32) / safe_t).astype(jnp.int32)
    return jnp.where(t > 0, drawn, greedy)


def _packed_attention(layer, F, qkv, mask):
    """``MultiHeadAttention`` past its fused projection where the flash
    kernels take ``qkv`` (B, S, 3U) as it lies and hand back (B, S, U), what
    ``proj`` multiplies — no mask, one device, and shapes for which
    ``ops.attention.attention_impl`` answers ``flash_packed`` —: the
    layer's output, with no transpose between its matmuls and the Mosaic
    calls. None otherwise: the layer then splits the heads itself. Counts
    the path a traced layer takes in ``attention.impl.<path>`` (``obs``).
    It stands here, at the end, so that the functions above keep their
    lines (the decode kernels traced through them are keyed on those)."""
    from .. import obs, parallel as par
    from ..ndarray.ndarray import invoke_fn
    from ..ops.attention import attention_impl
    from ..ops.flash_attention import flash_attention_packed

    b, s, _ = qkv.shape
    h = layer._heads
    shape = (b, h, s, layer._units // h)
    mesh = par.current_mesh()
    impl = attention_impl(shape, shape, mask is not None,
                          fused_qkv=mesh is None or mesh.size == 1)
    obs.inc("attention.impl." + impl)
    if impl != "flash_packed":
        return None
    out = layer.proj(invoke_fn(
        lambda t: flash_attention_packed(t, h, causal=layer._causal), [qkv]))
    if layer._dropout:
        out = F.Dropout(out, p=layer._dropout)
    return out

"""Attention for a model whose layers forget: sliding-window layers that keep
their last ``W`` positions as a RING in per-slot state, beside global layers
in the page pool, with keys wider than values (``dk != dv``). Four Pallas
kernels and their XLA twins, in a module of their own so that the kernels of
``gqa_attention.py`` and ``flash_attention.py`` keep the lines they are cached
under.

**A cached row** (both kinds) is FLAT, as ``gqa_attention.py``'s: a
position's keys of every cached head, then its values, side by side on the
lanes — ``KV * (dk + dv)`` values. A head's values are a lane slice at a
multiple of ``dv``; its keys, ``dk = 192`` wide, would start at a multiple of
192, which is not a lane tile: the decode kernels read a head's keys as an
ALIGNED slice of ``ceil(dk / 128) * 128`` lanes that covers them (an even
head's from its first lane, an odd head's up to its last: :func:`key_slices`)
and meet it with the query zero-padded on the other side — the neighbour's
lanes are multiplied by zero.

- :func:`swa_decode_attention` (kernel ``swa_decode``) — one query position a
  slot against that slot's ring of one window layer: ``ring`` is ``(slots +
  1, window layers, W, row)``, index ``p mod W`` holds position ``p``, so with
  the newest position ``pos`` already written every index ``<= min(pos, W -
  1)`` is live and nothing else is. Grid over the slots, the layer a
  prefetched scalar (the calls of a step's layers are one kernel), a slot's
  ring of one layer ONE block; the G query heads of a group meet it as one
  ``(G, dk) x (dk, W)`` product. A learned **sink** a query head sits in the
  softmax's denominator and gives no value.
- :func:`gqa_decode_attention_dv` (kernel ``gqa_decode_dv``) —
  ``gqa_attention.gqa_decode_attention`` for ``dk != dv``: the paged walk of a
  flat-row pool, a page a grid step through the page table.
- :func:`attention_from` — causal attention of a PIECE of a prompt, query
  rows ``start .. start + C - 1``. Global (kernel ``gqa_prefill_from_dv``):
  against every position from 0, key blocks above a query block's diagonal
  skipped and not fetched — ``gqa_attention.gqa_flash_attention_from`` for
  ``dk != dv``. Window (kernel ``swa_prefill_from``): the keys are the ``W``
  positions before the piece (the ring as the piece before left it, in
  order) and the piece's own; a query block of ``W`` rows meets TWO key
  blocks, the one before it and its own, whatever ``start`` is — work that
  does not grow with the prompt — and the sink.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import (_LOG2E, _NEG_INF, _dot_prec, _dotT, _pick_block,
                              _use_interpret, decode_attention_impl)
from .gqa_attention import _online_update

__all__ = ["key_slices", "swa_decode_attention", "gqa_decode_attention_dv",
           "attention_from"]


def key_slices(kvh: int, dk: int):
    """For each cached head of a flat row's keys (``kvh`` heads of ``dk``
    lanes): (first lane, lanes, zeros before the query) of the slice a decode
    kernel reads and the padding its query takes. Whole lane tiles where
    pairs of heads fill whole tiles (192: a pair is three); the head's own
    lanes otherwise (a multiple of 128 already, or a size only the CPU
    sees)."""
    wide = -(-dk // 128) * 128
    if dk % 128 and (2 * dk) % 128 == 0 and kvh % 2 == 0:
        return [(h * dk, wide, 0) if h % 2 == 0
                else ((h + 1) * dk - wide, wide, wide - dk)
                for h in range(kvh)]
    return [(h * dk, dk, 0) for h in range(kvh)]


def _padded_query(q, kvh, dk):
    """q (B, KV, G, dk) -> (B, KV, G, lanes): each cached head's queries
    zero-padded to meet its :func:`key_slices` slice."""
    slices = key_slices(kvh, dk)
    if all(wide == dk for _, wide, _ in slices):
        return q
    return jnp.stack([jnp.pad(q[:, h], ((0, 0), (0, 0),
                                        (before, wide - dk - before)))
                      for h, (_, wide, before) in enumerate(slices)], axis=1)


def _split(rows, kvh, dk, dv):
    """Flat rows (..., KV (dk + dv)) -> keys (..., KV, dk), values (..., KV,
    dv)."""
    lead = rows.shape[:-1]
    return (rows[..., :kvh * dk].reshape(lead + (kvh, dk)),
            rows[..., kvh * dk:].reshape(lead + (kvh, dv)))


def _softmax_with_sink(sc, sink):
    """softmax over the last axis of sc (..., G, n) with one more logit a
    row, ``sink`` (broadcastable to (..., G)), that takes mass and gives
    none; ``sink`` None: the plain softmax."""
    if sink is None:
        return jax.nn.softmax(sc, axis=-1)
    sink = jnp.broadcast_to(sink.astype(jnp.float32), sc.shape[:-1])
    m = jnp.maximum(jnp.max(sc, axis=-1), sink)
    m = jnp.where(jnp.isfinite(m), m, 0.0)      # nothing visible, sink -inf
    p = jnp.exp(sc - m[..., None])
    return p / (jnp.sum(p, axis=-1) + jnp.exp(sink - m))[..., None]


# -- one position a slot against its ring --------------------------------------

def _swa_decode_xla(q, ring, layer, positions, sink, scale, dv):
    """The plain twin of the ``swa_decode`` kernel; shapes as
    :func:`swa_decode_attention`."""
    b, kvh, g, dk = q.shape
    w = ring.shape[2]
    k, v = _split(ring[:b, layer], kvh, dk, dv)          # (B, W, KV, d)
    prec = _dot_prec(ring.dtype)
    sc = jnp.einsum("bhgd,bwhd->bhgw", q.astype(ring.dtype), k,
                    preferred_element_type=jnp.float32, precision=prec) * scale
    live = jnp.arange(w)[None, :] <= jnp.minimum(positions, w - 1)[:, None]
    sc = jnp.where(live[:, None, None], sc, -jnp.inf)
    p = _softmax_with_sink(sc, sink[None])
    # a ring row nobody wrote may hold anything: not even times zero
    v = jnp.where(live[:, :, None, None], v, 0)
    return jnp.einsum("bhgw,bwhd->bhgd", p.astype(ring.dtype), v,
                      preferred_element_type=jnp.float32,
                      precision=prec).astype(q.dtype)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _swa_decode(q, ring, layer, positions, sink, scale, dv, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, kvh, g, lanes = q.shape           # the query padded (_padded_query)
    w, row = ring.shape[2], ring.shape[3]
    dk = row // kvh - dv
    slices = key_slices(kvh, dk)
    assert all(wide == lanes for _, wide, _ in slices), (q.shape, ring.shape)
    c = scale * _LOG2E
    prec = _dot_prec(ring.dtype)

    def kernel(pos_ref, layer_ref, q_ref, sink_ref, ring_ref, o_ref):
        del layer_ref
        pos = pos_ref[pl.program_id(0)]
        live = (lax.broadcasted_iota(jnp.int32, (g, w), 1)
                <= jnp.minimum(pos, w - 1))
        for h, (first, wide, _) in enumerate(slices):
            k_blk = ring_ref[:, first:first + wide]                # (W, lanes)
            v_blk = ring_ref[:, kvh * dk + h * dv:kvh * dk + (h + 1) * dv]
            sc = _dotT(q_ref[h].astype(k_blk.dtype), k_blk, prec) * c
            sc = jnp.where(live, sc, _NEG_INF)                     # (G, W)
            s_h = sink_ref[h][:, 0:1]                              # (G, 1)
            # index 0 is live whatever the position: the max is finite
            m = jnp.maximum(jnp.max(sc, axis=1, keepdims=True), s_h)
            p = jnp.exp2(sc - m)
            den = jnp.sum(p, axis=1, keepdims=True) + jnp.exp2(s_h - m)
            o_ref[h] = jnp.dot(p.astype(v_blk.dtype), v_blk,
                               preferred_element_type=jnp.float32,
                               precision=prec) / den

    def per_slot(width):
        return pl.BlockSpec((None, kvh, g, width),
                            lambda s, ps, ly: (s, 0, 0, 0))

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[per_slot(lanes),
                      pl.BlockSpec((kvh, g, 128), lambda s, ps, ly: (0, 0, 0)),
                      pl.BlockSpec((None, None, w, row),
                                   lambda s, ps, ly: (s, ly[0], 0, 0))],
            out_specs=per_slot(dv)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="swa_decode",
    )(positions, layer, q, sink, ring)


def _sink_lanes(sink, kvh, g):
    """A sink a query head, (KV G,) or (KV, G), in the log2 domain and no
    lower than the kernels' own minus infinity, along 128 lanes."""
    sink = jnp.maximum(sink.astype(jnp.float32).reshape(kvh, g) * _LOG2E,
                       _NEG_INF)
    return jnp.broadcast_to(sink[:, :, None], (kvh, g, 128))


def swa_decode_attention(q, ring, layer, positions, sink, dv, scale=None):
    """Single-position sliding-window attention of every slot against its
    own ring of one window layer. q (B, KV, G, dk); ring (slots + 1, window
    layers, W, KV (dk + dv)) with B <= slots + 1, a position's row at index
    ``p mod W``, the newest position's row already there; ``layer`` a Python
    int; positions (B,) int32: each slot's newest position (an idle slot: any,
    output garbage); sink (KV G,) a query head's sink logit (None, or
    ``-inf``: none). Returns (B, KV, G, dv) in q's dtype.
    :func:`flash_attention.decode_attention_impl` picks the path."""
    b, kvh, g, dk = q.shape
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(dk)
    positions = positions.astype(jnp.int32)
    if sink is None:
        sink = jnp.full((kvh * g,), -jnp.inf, jnp.float32)
    if decode_attention_impl() != "pallas":
        return _swa_decode_xla(q, ring, int(layer), positions,
                               sink.reshape(kvh, g), scale, dv)
    return _swa_decode(_padded_query(q, kvh, dk), ring,
                       jnp.full((1,), int(layer), jnp.int32), positions,
                       _sink_lanes(sink, kvh, g), scale, dv,
                       _use_interpret()).astype(q.dtype)


# -- one position a sequence against a paged pool, dk != dv --------------------

def _gqa_decode_dv_xla(q, pool, layer, page_table, lengths, scale, dv):
    """Gather-then-attend twin of ``gqa_decode_dv``."""
    b, kvh, g, dk = q.shape
    rows = pool[page_table, layer]                 # (B, max_pages, page, row)
    k, v = _split(rows.reshape(b, -1, rows.shape[-1]), kvh, dk, dv)
    prec = _dot_prec(pool.dtype)
    sc = jnp.einsum("bhgd,bshd->bhgs", q.astype(pool.dtype), k,
                    preferred_element_type=jnp.float32, precision=prec) * scale
    live = jnp.arange(k.shape[1])[None, :] < lengths[:, None]
    sc = jnp.where(live[:, None, None], sc, _NEG_INF)
    p = jax.nn.softmax(sc, axis=-1).astype(pool.dtype)
    return jnp.einsum("bhgs,bshd->bhgd", p, v,
                      preferred_element_type=jnp.float32,
                      precision=prec).astype(q.dtype)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _gqa_decode_dv(q, pool, layer, page_table, lengths, scale, dv, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, kvh, g, lanes = q.shape           # the query padded (_padded_query)
    page, row = pool.shape[2], pool.shape[3]
    dk = row // kvh - dv
    slices = key_slices(kvh, dk)
    assert all(wide == lanes for _, wide, _ in slices), (q.shape, pool.shape)
    max_pages = page_table.shape[1]
    c = scale * _LOG2E
    prec = _dot_prec(pool.dtype)

    def kernel(pt_ref, len_ref, layer_ref, q_ref, kv_ref, o_ref, m_ref, l_ref,
               acc_ref):
        del pt_ref, layer_ref
        seq, j = pl.program_id(0), pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        length = len_ref[seq]

        @pl.when(j * page < length)
        def _page():
            pos = j * page + lax.broadcasted_iota(jnp.int32, (g, page), 1)
            for h, (first, wide, _) in enumerate(slices):
                k_blk = kv_ref[:, first:first + wide]           # (page, lanes)
                v_blk = kv_ref[:, kvh * dk + h * dv:kvh * dk + (h + 1) * dv]
                sc = _dotT(q_ref[h].astype(k_blk.dtype), k_blk, prec) * c
                # the page holds a live row, so the max is finite and the
                # masked columns' exp2 is 0
                sc = jnp.where(pos < length, sc, _NEG_INF)      # (G, page)
                _online_update(sc, v_blk, m_ref.at[h], l_ref.at[h],
                               acc_ref.at[h], prec)

        @pl.when(j == max_pages - 1)
        def _norm():
            # length-0 rows (idle slots) never accumulate: the clamp keeps
            # their garbage finite
            o_ref[...] = acc_ref[...] / jnp.maximum(l_ref[:, :, 0:1], 1e-30)

    def per_seq(width):
        return pl.BlockSpec((None, kvh, g, width),
                            lambda sq, j, pt, ln, ly: (sq, 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, max_pages),
        in_specs=[per_seq(lanes),
                  pl.BlockSpec((None, None, page, row),
                               lambda sq, j, pt, ln, ly: (pt[sq, j], ly[0],
                                                          0, 0))],
        out_specs=per_seq(dv),
        scratch_shapes=[pltpu.VMEM((kvh, g, 128), jnp.float32),
                        pltpu.VMEM((kvh, g, 128), jnp.float32),
                        pltpu.VMEM((kvh, g, dv), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="gqa_decode_dv",
    )(page_table, lengths, layer, q, pool)


def gqa_decode_attention_dv(q, pool, layer, page_table, lengths, dv,
                            scale=None):
    """``gqa_attention.gqa_decode_attention`` for keys wider than values:
    q (B, KV, G, dk); pool (P, L, page, KV (dk + dv)) — a position's KV keys,
    then its KV values, flat on the minor axis; ``layer`` a Python int;
    page_table (B, max_pages) int32, unused entries any valid page; lengths
    (B,) int32 (0: an idle row, output garbage). Returns (B, KV, G, dv)."""
    b, kvh, g, dk = q.shape
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(dk)
    if decode_attention_impl() != "pallas":
        return _gqa_decode_dv_xla(q, pool, int(layer), page_table, lengths,
                                  scale, dv)
    return _gqa_decode_dv(
        _padded_query(q, kvh, dk), pool, jnp.full((1,), int(layer), jnp.int32),
        page_table.astype(jnp.int32), lengths.astype(jnp.int32), scale, dv,
        _use_interpret()).astype(q.dtype)


# -- a piece of a prompt -------------------------------------------------------

def _from_xla(start, q, k, v, sink, scale, window):
    """The plain twin of :func:`attention_from`'s two kernels."""
    kvh, g, c_len, _ = q.shape
    pos = start + jnp.arange(c_len)[:, None]
    if window is None:
        col = jnp.arange(k.shape[1])[None, :]
        seen = col <= pos
    else:
        col = start - window + jnp.arange(k.shape[1])[None, :]
        seen = (col <= pos) & (col > pos - window) & (col >= 0)
    prec = _dot_prec(q.dtype)
    sc = jnp.einsum("hgqd,hkd->hgqk", q, k, preferred_element_type=jnp.float32,
                    precision=prec) * scale
    sc = jnp.where(seen[None, None], sc, -jnp.inf)
    p = _softmax_with_sink(sc, None if sink is None
                           else sink.reshape(kvh, g, 1))
    return jnp.einsum("hgqk,hkd->hgqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32,
                      precision=prec).astype(q.dtype)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9))
def _forward_from(start, q, k, v, sink, scale, bq, bk, window, interpret):
    """Both kernels of :func:`attention_from`. Grid (cached heads, query
    blocks, key blocks), the key axis innermost, online softmax in float32,
    log2 domain. ``window`` None: key blocks over ALL T positions, those
    above a query block's diagonal skipped and not fetched. ``window`` W (=
    bq = bk): key row t is position ``start - W + t``, and query block i
    meets key blocks i (the W positions before it) and i + 1 (its own)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kvh, g, c_len, dk = q.shape
    dv = v.shape[2]
    nq = c_len // bq
    nk = 2 if window else k.shape[1] // bk
    rows = g * bq
    c = scale * _LOG2E
    prec = _dot_prec(q.dtype)

    def kernel(start_ref, q_ref, k_ref, v_ref, *rest):
        sink_ref = rest[0] if window else None
        o_ref, m_ref, l_ref, acc_ref = rest[-4:]
        i, j = pl.program_id(1), pl.program_id(2)
        first = start_ref[0] + i * bq      # the query block's first position

        @pl.when(j == 0)
        def _init():
            if window:      # the sink: a logit in the denominator from the
                m_ref[...] = jnp.broadcast_to(   # start, with no value
                    sink_ref[...][:, None, :], (g, bq, 128)).reshape(rows, 128)
                l_ref[...] = jnp.ones_like(l_ref)
            else:
                m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
                l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def scores():
            return _dotT(q_ref[...].reshape(rows, dk), k_ref[...], prec) * c

        pos = first + lax.broadcasted_iota(jnp.int32, (g, bq, bk), 1)
        lane = lax.broadcasted_iota(jnp.int32, (g, bq, bk), 2)
        if window:
            col = first - window + j * bk + lane
            seen = ((col <= pos) & (col > pos - window)
                    & (col >= 0)).reshape(rows, bk)
            sc = jnp.where(seen, scores(), _NEG_INF)
            # a row may see nothing of a block (its last row of the block
            # before; every row of the ring before a prompt's first piece)
            # and, with a sink of -inf, have seen nothing yet: its masked
            # columns are zeroed outright, not left to exp2
            m_prev = m_ref[:, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp2(m_prev - m_new)
            p = jnp.where(seen, jnp.exp2(sc - m_new), 0.0)
            l_ref[...] = jnp.broadcast_to(
                l_ref[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True),
                l_ref.shape)
            acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
                p.astype(v_ref.dtype), v_ref[...],
                preferred_element_type=jnp.float32, precision=prec)
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        else:
            below = (j + 1) * bk - 1 <= first

            @pl.when(below)
            def _full():
                _online_update(scores(), v_ref[...], m_ref, l_ref, acc_ref,
                               prec)

            @pl.when(jnp.logical_not(below) & (j <= (first + bq - 1) // bk))
            def _diagonal():
                col = j * bk + lane
                sc = jnp.where((pos >= col).reshape(rows, bk), scores(),
                               _NEG_INF)
                # column 0 of key block 0 is visible to every row: a row's
                # max is finite by the time a masked block reaches it
                _online_update(sc, v_ref[...], m_ref, l_ref, acc_ref, prec)

        @pl.when(j == nk - 1)
        def _norm():
            o_ref[...] = (acc_ref[...] / l_ref[:, 0:1]).reshape(
                g, bq, dv).astype(o_ref.dtype)

    def kv_spec(d):
        if window:
            return pl.BlockSpec((None, bk, d), lambda h, i, j, st: (h, i + j, 0))
        return pl.BlockSpec((None, bk, d), lambda h, i, j, st: (
            h, jnp.minimum(j, (st[0] + i * bq + bq - 1) // bk), 0))

    def q_spec(d):
        return pl.BlockSpec((None, g, bq, d), lambda h, i, j, st: (h, 0, i, 0))

    in_specs = [q_spec(dk), kv_spec(dk), kv_spec(dv)]
    args = [start, q, k, v]
    if window:
        in_specs.append(pl.BlockSpec((None, g, 128),
                                     lambda h, i, j, st: (h, 0, 0)))
        args.append(sink)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(kvh, nq, nk),
            in_specs=in_specs,
            out_specs=q_spec(dv),
            scratch_shapes=[pltpu.VMEM((rows, 128), jnp.float32),
                            pltpu.VMEM((rows, 128), jnp.float32),
                            pltpu.VMEM((rows, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((kvh, g, c_len, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024),
        interpret=interpret,
        name="swa_prefill_from" if window else "gqa_prefill_from_dv",
    )(*args)


def attention_from(q, k, v, start, scale=None, window=None, sink=None,
                   block_rows=1024, block_k=512):
    """Causal attention of a piece of one sequence. q (KV, G, C, dk): the
    query rows of positions ``start .. start + C - 1`` (``start`` () int32,
    traced).

    ``window`` None (global): k (KV, T, dk), v (KV, T, dv) hold positions
    ``0 .. T - 1``, those the piece sees (``< start + C``) filled in and every
    row finite; with ``start`` 0 and T = C the whole causal forward.

    ``window`` W (C a multiple of W): k, v hold the ``W + C`` positions
    ``start - W .. start + C - 1`` — the W before the piece (what a ring of W
    holds in order after a piece that ended on a multiple of W; never seen
    where they lie before position 0, but finite), then the piece's own; row
    i sees positions ``i - W + 1 .. i``, and ``sink`` (KV G,), a query head's
    logit that takes mass and gives no value (``-inf``: none).

    Returns (KV, G, C, dv) in q's dtype. The kernels on a TPU (and where
    ``MXNET_DECODE_ATTN=pallas``), their plain twin elsewhere."""
    kvh, g, c_len, dk = q.shape
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(dk)
    if window:
        assert c_len % window == 0 and k.shape[1] == window + c_len, (
            q.shape, k.shape, window)
        if sink is None:
            sink = jnp.full((kvh * g,), -jnp.inf, jnp.float32)
    if decode_attention_impl() != "pallas":
        return _from_xla(start, q, k, v, sink if window else None, scale,
                         window)
    start = jnp.reshape(start, (1,)).astype(jnp.int32)
    if window:
        return _forward_from(start, q, k, v, _sink_lanes(sink, kvh, g), scale,
                             window, window, window, _use_interpret())
    return _forward_from(
        start, q, k, v, None, scale,
        _pick_block(c_len, max(block_rows // g, 8)),
        _pick_block(k.shape[1], block_k), None, _use_interpret())

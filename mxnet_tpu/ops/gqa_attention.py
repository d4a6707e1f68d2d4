"""Grouped-KV attention for a serving engine: G query heads read one cached
head. Three Pallas kernels, in a module of their own so that the kernels of
``flash_attention.py`` keep the lines they are cached under.

The query heads of a group are what a decode step lacks everywhere else: an
M dimension. With one query row a head the paged kernel of
``flash_attention.py`` has mat-vecs, on the VPU; here the G rows of a group
meet a page's keys as one ``(G, D) x (D, page)`` product on the MXU.

- :func:`gqa_flash_attention` — causal forward over one prompt (prefill).
  Grid (cached heads, query blocks, key blocks), the key axis innermost:
  a query block of all G heads (``G x block_q`` rows) stays put while key and
  value blocks stream past it; online softmax in float32, log2 domain; key
  blocks wholly above the diagonal are skipped and not fetched. Keys and
  values are streamed, never resident: 16,384 positions of 256-wide heads
  would not fit VMEM the way ``flash_attention``'s forward holds them.
- :func:`gqa_flash_attention_from` — the same forward for a PIECE of a
  prompt (kernel ``gqa_prefill_from``, at the end of the module so that the
  others keep their lines): the query rows are positions ``start .. start +
  C - 1``, the keys and values every position from 0, ``start`` a prefetched
  scalar. Same blocks in the same order as the whole forward runs them for
  these rows, so the same numbers.
- :func:`gqa_decode_attention` — one query position a sequence against a
  paged pool whose row is FLAT: ``(pages, layers, page, 2 * KV * D)``, a
  position's keys of every cached head, then its values, side by side on the
  lanes. A page of a layer is one dense ``(page, 2 KV D)`` block (whole tiles
  whatever KV is and in 16 bits too — a head axis of 2 would be padded to a
  sublane tile of 16), and a head's keys are a lane slice at a multiple of
  D. Grid (B, max_pages), the layer a prefetched scalar (the calls of a
  step's layers are one kernel), a page a grid step through the page table;
  pages past a sequence's length repeat the table's scratch entry and are
  not fetched.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import (_LOG2E, _NEG_INF, _dot_prec, _dotT, _pick_block,
                              _use_interpret, decode_attention_impl)

__all__ = ["gqa_flash_attention", "gqa_decode_attention",
           "flash_gqa_decode_attention", "gqa_flash_attention_from"]


def _online_update(sc, v_blk, m_ref, l_ref, acc_ref, prec):
    """One block of scores sc (M, n) (log2 domain, masked) and values (n, D)
    into the running max, denominator and sum (VMEM scratch)."""
    m_prev = m_ref[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
    alpha = jnp.exp2(m_prev - m_new)
    p = jnp.exp2(sc - m_new)
    l_ref[...] = jnp.broadcast_to(
        l_ref[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True), l_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v_blk.dtype), v_blk, preferred_element_type=jnp.float32,
        precision=prec)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)


def gqa_flash_attention(q, k, v, scale=None, block_q=128, block_k=512):
    """Causal attention of one sequence. q (KV, G, S, D) — query head ``n`` of
    the model is ``[n // G, n % G]``; k, v (KV, S, D). Returns (KV, G, S, D)
    in q's dtype."""
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    s = q.shape[2]
    return _gqa_forward(q, k, v, scale, _pick_block(s, block_q),
                        _pick_block(s, block_k), _use_interpret())


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _gqa_forward(q, k, v, scale, bq, bk, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kvh, g, s, d = q.shape
    nq, nk = s // bq, s // bk
    rows = g * bq
    c = scale * _LOG2E
    prec = _dot_prec(q.dtype)

    def last_block(i):     # the last key block a row of query block i sees
        return (i * bq + bq - 1) // bk

    def kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
        i, j = pl.program_id(1), pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def scores():
            return _dotT(q_ref[...].reshape(rows, d), k_ref[...], prec) * c

        # every column of the block visible to every row of the query block
        below = (j + 1) * bk - 1 <= i * bq

        @pl.when(below)
        def _full():
            _online_update(scores(), v_ref[...], m_ref, l_ref, acc_ref, prec)

        @pl.when(jnp.logical_not(below) & (j <= last_block(i)))
        def _diagonal():
            pos = i * bq + lax.broadcasted_iota(jnp.int32, (g, bq, bk), 1)
            col = j * bk + lax.broadcasted_iota(jnp.int32, (g, bq, bk), 2)
            sc = jnp.where((pos >= col).reshape(rows, bk), scores(), _NEG_INF)
            # column 0 of key block 0 is visible to every row: a row's max is
            # finite by the time a masked block reaches it
            _online_update(sc, v_ref[...], m_ref, l_ref, acc_ref, prec)

        @pl.when(j == nk - 1)
        def _norm():
            o_ref[...] = (acc_ref[...] / l_ref[:, 0:1]).reshape(
                g, bq, d).astype(o_ref.dtype)

    def kv_spec():
        return pl.BlockSpec((None, bk, d), lambda h, i, j: (
            h, jnp.minimum(j, last_block(i)), 0))

    return pl.pallas_call(
        kernel,
        grid=(kvh, nq, nk),
        in_specs=[pl.BlockSpec((None, g, bq, d), lambda h, i, j: (h, 0, i, 0)),
                  kv_spec(), kv_spec()],
        out_specs=pl.BlockSpec((None, g, bq, d), lambda h, i, j: (h, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((rows, 128), jnp.float32),
                        pltpu.VMEM((rows, 128), jnp.float32),
                        pltpu.VMEM((rows, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024),
        interpret=interpret,
        name="gqa_prefill",
    )(q, k, v)


def _gqa_decode_xla(q, pool, layer, page_table, lengths, scale):
    """Gather-then-attend reference; shapes as ``gqa_decode_attention``."""
    b, kvh, g, d = q.shape
    rows = pool[page_table, layer]               # (B, max_pages, page, 2KVD)
    rows = rows.reshape(b, -1, 2, kvh, d)
    k, v = rows[:, :, 0], rows[:, :, 1]          # (B, S, KV, D)
    prec = _dot_prec(q.dtype)
    sc = jnp.einsum("bhgd,bshd->bhgs", q, k, preferred_element_type=jnp.float32,
                    precision=prec) * scale
    live = jnp.arange(k.shape[1])[None, :] < lengths[:, None]
    sc = jnp.where(live[:, None, None], sc, _NEG_INF)
    p = jax.nn.softmax(sc, axis=-1).astype(pool.dtype)
    return jnp.einsum("bhgs,bshd->bhgd", p, v,
                      preferred_element_type=jnp.float32,
                      precision=prec).astype(q.dtype)


def flash_gqa_decode_attention(q, pool, layer, page_table, lengths, scale,
                               interpret=False):
    """The Pallas path of :func:`gqa_decode_attention`."""
    return _gqa_decode(q, pool, jnp.full((1,), int(layer), jnp.int32),
                       page_table.astype(jnp.int32),
                       lengths.astype(jnp.int32), float(scale), interpret)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _gqa_decode(q, pool, layer, page_table, lengths, scale, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, kvh, g, d = q.shape
    page, row = pool.shape[2], pool.shape[3]
    assert row == 2 * kvh * d, (pool.shape, q.shape)
    max_pages = page_table.shape[1]
    s2_scale = scale * _LOG2E
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
            for h in range(kvh):
                k_blk = kv_ref[:, h * d:(h + 1) * d]            # (page, D)
                v_blk = kv_ref[:, (kvh + h) * d:(kvh + h + 1) * d]
                sc = _dotT(q_ref[h].astype(k_blk.dtype), k_blk, prec) * s2_scale
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

    per_seq = pl.BlockSpec((None, kvh, g, d),
                           lambda sq, j, pt, ln, ly: (sq, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, max_pages),
        in_specs=[per_seq,
                  pl.BlockSpec((None, None, page, row),
                               lambda sq, j, pt, ln, ly: (pt[sq, j], ly[0],
                                                          0, 0))],
        out_specs=per_seq,
        scratch_shapes=[pltpu.VMEM((kvh, g, 128), jnp.float32),
                        pltpu.VMEM((kvh, g, 128), jnp.float32),
                        pltpu.VMEM((kvh, g, d), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="gqa_decode",
    )(page_table, lengths, layer, q, pool)
    return out.astype(q.dtype)


def gqa_decode_attention(q, pool, layer, page_table, lengths, scale=None):
    """Single-position grouped-KV attention against one layer of a paged
    pool. q (B, KV, G, D); pool (P, L, page, 2 * KV * D) — a position's KV
    keys, then its KV values, flat on the minor axis; ``layer`` a Python int;
    page_table (B, max_pages) int32, unused entries any valid page; lengths
    (B,) int32 (0: an idle row, output garbage). Returns (B, KV, G, D).
    :func:`flash_attention.decode_attention_impl` picks the path (the kernel
    on a TPU: this layout lowers in 16 and in 32 bits, whatever KV is)."""
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if decode_attention_impl() == "pallas":
        return flash_gqa_decode_attention(q, pool, layer, page_table, lengths,
                                          scale, interpret=_use_interpret())
    return _gqa_decode_xla(q, pool, layer, page_table, lengths, scale)


def gqa_flash_attention_from(q, k, v, start, scale=None, block_q=128,
                             block_k=512):
    """Causal attention of a piece of one sequence against all of it so far.
    q (KV, G, C, D): the query rows of positions ``start .. start + C - 1``
    (``start`` () int32, traced); k, v (KV, T, D): the keys and values of
    positions ``0 .. T - 1``, those the piece sees (``< start + C``) filled
    in and every row finite. Returns (KV, G, C, D) in q's dtype. With
    ``start`` 0 and T = C it is :func:`gqa_flash_attention`."""
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return _gqa_forward_from(
        jnp.reshape(start, (1,)).astype(jnp.int32), q, k, v, scale,
        _pick_block(q.shape[2], block_q), _pick_block(k.shape[1], block_k),
        _use_interpret())


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _gqa_forward_from(start, q, k, v, scale, bq, bk, interpret):
    """:func:`_gqa_forward` with the query rows ``start`` positions down the
    diagonal: grid (cached heads, query blocks, key blocks over ALL T
    positions); key blocks above a query block's diagonal — and so every
    block past ``start + C`` — are skipped and not fetched."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kvh, g, c_len, d = q.shape
    nq, nk = c_len // bq, k.shape[1] // bk
    rows = g * bq
    c = scale * _LOG2E
    prec = _dot_prec(q.dtype)

    def kernel(start_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
        i, j = pl.program_id(1), pl.program_id(2)
        first = start_ref[0] + i * bq      # the query block's first position

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def scores():
            return _dotT(q_ref[...].reshape(rows, d), k_ref[...], prec) * c

        below = (j + 1) * bk - 1 <= first

        @pl.when(below)
        def _full():
            _online_update(scores(), v_ref[...], m_ref, l_ref, acc_ref, prec)

        @pl.when(jnp.logical_not(below) & (j <= (first + bq - 1) // bk))
        def _diagonal():
            pos = first + lax.broadcasted_iota(jnp.int32, (g, bq, bk), 1)
            col = j * bk + lax.broadcasted_iota(jnp.int32, (g, bq, bk), 2)
            sc = jnp.where((pos >= col).reshape(rows, bk), scores(), _NEG_INF)
            _online_update(sc, v_ref[...], m_ref, l_ref, acc_ref, prec)

        @pl.when(j == nk - 1)
        def _norm():
            o_ref[...] = (acc_ref[...] / l_ref[:, 0:1]).reshape(
                g, bq, d).astype(o_ref.dtype)

    def kv_spec():
        return pl.BlockSpec((None, bk, d), lambda h, i, j, st: (
            h, jnp.minimum(j, (st[0] + i * bq + bq - 1) // bk), 0))

    def q_spec():
        return pl.BlockSpec((None, g, bq, d), lambda h, i, j, st: (h, 0, i, 0))

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(kvh, nq, nk),
            in_specs=[q_spec(), kv_spec(), kv_spec()],
            out_specs=q_spec(),
            scratch_shapes=[pltpu.VMEM((rows, 128), jnp.float32),
                            pltpu.VMEM((rows, 128), jnp.float32),
                            pltpu.VMEM((rows, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024),
        interpret=interpret,
        name="gqa_prefill_from",
    )(start, q, k, v)

"""Hand-written Pallas flash attention for TPU.

The hot op of the transformer family (SURVEY.md §7 step 8). Forward is a
Pallas kernel: a Q block stays in VMEM while the kernel walks K/V tiles,
keeping online-softmax statistics in f32 — the S×S score matrix is never
materialized in HBM, so memory is O(S·D) instead of O(S²) and long contexts
fit on chip. Backward is a second Pallas kernel (one pass over K/V blocks,
recomputing P from the saved lse; dQ accumulates in a float32 VMEM scratch
across the sequential TPU grid and leaves the kernel once, in the operand's
dtype). On non-TPU backends the backward falls back to a blocked
``lax.scan`` in plain JAX.

TPU-efficiency notes (measured on v5e; PERF.md §5, §6):
- With head_dim 64 every product fills half the MXU and the MXU:VPU work
  ratio is only ~32:1, so every per-element VPU pass costs as much as a
  matmul. What a grid step keeps resident (one DMA a block) is therefore
  walked in (q sub-block, k sub-tile) PAIRS that follow the causal
  triangle: pairs the diagonal does not reach are never computed, only the
  pair it crosses pays for the mask (one compare and one select), the rest
  run the bare body.
- A loop's end is a wall to the bundle scheduler, and a pair of 256 x 256
  is over before the MXU has filled (523 bundles for 64 vmatmuls, twice the
  MXU's pace; 512 x 512: 1.3 x). So the pairs a sub-block sees go as ONE
  straight-line product whose width is picked by their number
  (``lax.switch``): up to ``block_k // sub_k`` sub-tiles wide in the
  forward, ``block_q // sub_q`` sub-blocks tall in the backward. Nothing
  wide is carried across a loop's or a branch's edge (it would be spilled
  there and filled again): a body loads what it reads.
- A forward grid step may advance TWO heads in one body (0.19 → 0.17 ms a
  call at (4, 16, 1024, 64)); the backward takes one (two spill).
- Softmax statistics run in the log2 domain (``exp2`` is the native VPU
  transcendental; ``exp`` lowers to exp2 + a hidden multiply) and stay
  (rows, 1) columns: a reduction's result is never laid out again.
- Fully-masked rows are repaired once per q sub-block (per-row select)
  instead of guarding every score element.
- The backward computes its scores as ``k qᵀ``: dV and dK are plain
  products of them, and only dQ contracts their leading dimension.
- The schedule — blocks, sub-tiles, heads a step — comes from a per-(S, D)
  table measured by tools/tune_flash.py (:func:`flash_schedule` reports
  it).

Causal masking takes a **dynamic row offset**: visibility is
``row + offset >= col``. offset=0 is standard causal; ring attention
(parallel/ring_attention.py) passes ``(my_rank - src_rank) * s_local`` so one
kernel call handles fully-visible (offset ≥ S), diagonal (0), and
fully-masked (≤ -S) visiting blocks — the masked case runs zero K/V
iterations. Returns (out, lse); lse is the statistic the ring uses to merge
per-device blocks, so the same kernel serves single-chip and
sequence-parallel paths.

Reference counterpart: none — upstream MXNet 1.x has no fused attention op;
this is TPU-first new surface. Kernel structure follows the public
FlashAttention formulation (Dao et al.) and the Pallas TPU guide.
"""
from __future__ import annotations

import functools
import math
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["flash_attention", "flash_attention_with_lse", "flash_schedule",
           "decode_attention", "decode_attention_impl",
           "flash_decode_attention", "latent_decode_attention",
           "flash_latent_decode_attention", "decode_page_group"]

_NEG_INF = -1e30  # avoids -inf NaN propagation inside the kernel
_LOG2E = math.log2(math.e)

# The package default is jax_default_matmul_precision=highest (fp32-accurate
# fp32 GEMMs for reference parity). For bf16 operands that would mean a
# Mosaic "Bad lhs type" reject in-kernel (fp32 contract precision on bf16
# vectors) — the whole point is single-pass bf16 MXU with f32 accumulation,
# so bf16 dots pin DEFAULT. f32 operands keep HIGHEST: the package promises
# true-fp32 matmuls to non-AMP callers, and DEFAULT would silently truncate
# them to one-pass bf16 multiplies.


def _dot_prec(dt):
    return (lax.Precision.DEFAULT if jnp.dtype(dt).itemsize <= 2
            else lax.Precision.HIGHEST)


def _dotT(a, b, prec):
    """a:(m,c) b:(n,c) -> (m,n) without materializing b.T (dot_general)."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32, precision=prec)


def _dotA(a, b, prec):
    """a:(c,m) b:(c,n) -> (m,n): contract leading dims (no transposes)."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32, precision=prec)


def _dot(a, b, prec):
    return jnp.dot(a, b, preferred_element_type=jnp.float32, precision=prec)


class _Schedule(NamedTuple):
    """What a grid step holds and what one loop iteration computes."""
    block_q: int   # q rows a forward grid step keeps resident, and the most
    #                one product of the backward is tall
    block_k: int   # K/V rows a backward grid step keeps resident, and the
    #                most one product of the forward is wide
    sub_q: int     # rows of one (q sub-block, k sub-tile) pair
    sub_k: int     # columns of one pair
    heads: int     # heads a forward grid step advances side by side


def _fwd_core(load_q, load_kv, store, offset, q_start, n_sub, sub_q, s_total,
              sub_k, wide, scale, causal, d_v, heads=1):
    """Shared fwd walk: the ``n_sub`` q sub-blocks of ``sub_q`` rows that a
    grid step holds (the first at row ``q_start``), each against the K/V
    tiles of ``sub_k`` positions it sees, for every head of the step at
    once. ``d_v`` is the width of a value row (it need not be q's and k's).

    ``load_q(i)`` gives sub-block i's rows, a tuple with one (sub_q, D)
    array a head; ``load_kv(j, n)`` tiles [j, j + n) as ONE ``(k_blk,
    v_blk)`` of n·sub_k positions (n a Python int), a tuple with one pair a
    head; ``store(i, outs)`` takes the sub-block's ``(normalized out f32,
    lse (sub_q, 1))`` a head. ``load_kv`` runs once a (sub-block, product):
    a caller whose tiles are dear to make (the latent kernel expands them)
    hands over ONE sub-block.

    Per sub-block, tiles [0, nk_full) are fully visible (no mask math);
    tiles [nk_full, nk_run) are crossed by the diagonal and take the mask;
    tiles behind it are not walked. With ``wide`` 1 that is two loops of one
    tile an iteration. A loop's end is a wall to the scheduler, and a tile
    of few rows and columns is over before the MXU has filled: so with
    ``wide`` > 1, where the diagonal crosses ONE tile (every offset that is
    a multiple of ``sub_k``), the visible tiles go ``wide`` at a time as one
    product — whole ones in a loop, then the 1 … ``wide`` that end on the
    diagonal in one straight-line body picked by their number, the mask on
    the last ``sub_k`` columns alone. Softmax statistics are tracked in the
    log2 domain on raw (unscaled) scores; the scale folds into the exp2
    argument.
    """
    nk = s_total // sub_k
    c = scale * _LOG2E  # exp(s*scale - m) == exp2((s - m_raw) * c)

    def sub_block(i, _):
        start = q_start + i * sub_q
        if causal:
            # fully-visible: every col of tile j visible to every row ⇔
            # (j+1)*sk - 1 <= start + offset
            nk_full = jnp.clip((start + offset - sub_k + 1) // sub_k + 1,
                               0, nk)
            # any-visible: col_min <= start + sq - 1 + offset
            nk_run = jnp.clip((start + sub_q + offset + sub_k - 1) // sub_k,
                              0, nk)
        else:
            nk_full = nk_run = nk

        def tiles(j, n, carry, masked):
            """Tiles [j, j + n) in one product a head, the last under the
            mask if ``masked``; ``carry`` None: nothing gathered yet."""
            # everything a product reads is loaded here, inside the body
            # that uses it: nothing wide lives across a loop's or a
            # branch's edge, where it would be spilled and filled again
            if masked:   # row + offset >= col, in the last tile's indices
                visible = (
                    lax.broadcasted_iota(jnp.int32, (sub_q, sub_k), 0)
                    - lax.broadcasted_iota(jnp.int32, (sub_q, sub_k), 1)
                    >= (j + n - 1) * sub_k - start - offset)
            new = []
            for h, (q, (k_blk, v_blk)) in enumerate(zip(load_q(i),
                                                        load_kv(j, n))):
                prec = _dot_prec(q.dtype)
                s = _dotT(q, k_blk, prec)           # raw scores (sq, n·sk)
                if masked:
                    last = jnp.where(visible, s[:, (n - 1) * sub_k:],
                                     _NEG_INF)
                    s = last if n == 1 else jnp.concatenate(
                        [s[:, :(n - 1) * sub_k], last], axis=1)
                new_m = jnp.max(s, axis=-1, keepdims=True)
                if carry is not None:
                    acc, m, l = carry[h]
                    new_m = jnp.maximum(m, new_m)
                p = jnp.exp2((s - new_m) * c)
                pv = _dot(p.astype(v_blk.dtype), v_blk, prec)
                new_l = jnp.sum(p, axis=-1, keepdims=True)
                if carry is not None:
                    corr = jnp.exp2((m - new_m) * c)
                    pv = acc * corr + pv
                    new_l = l * corr + new_l
                new.append((pv, new_m, new_l))
            return tuple(new)

        def finish(carry):
            if carry is None:    # no column seen
                carry = zero()
            outs = []
            for acc, m, l in carry:
                # Rows that never saw a visible column (possible only for
                # offset < 0, ring's partially-masked edge): m stayed
                # _NEG_INF with p=exp2(0)=1 pollution. One per-row select
                # repairs them — no per-element guard.
                row_ok = m > _NEG_INF / 2
                safe_l = jnp.maximum(l, 1e-30)
                outs.append((jnp.where(row_ok, acc * (1.0 / safe_l), 0.0),
                             jnp.where(row_ok & (l > 0),
                                       m * scale + jnp.log(safe_l),
                                       _NEG_INF)))
            store(i, tuple(outs))

        def zero():
            return ((jnp.zeros((sub_q, d_v), jnp.float32),
                     jnp.full((sub_q, 1), _NEG_INF, jnp.float32),
                     jnp.zeros((sub_q, 1), jnp.float32)),) * heads

        def walk_singly():
            carry = lax.fori_loop(
                0, nk_full, lambda j, cr: tiles(j, 1, cr, False), zero())
            if causal:
                carry = lax.fori_loop(
                    nk_full, nk_run, lambda j, cr: tiles(j, 1, cr, True),
                    carry)
            finish(carry)

        def walk_wide():
            n_whole, carry = 0, None
            if nk > wide:    # a row may see whole wide products
                n_whole = nk_full // wide
                carry = lax.fori_loop(
                    0, n_whole,
                    lambda j, cr: tiles(j * wide, wide, cr, False), zero())
            rest = nk_run - n_whole * wide       # 0 … wide tiles

            def end(n, cr=None):
                finish(tiles(n_whole * wide, n, cr, causal) if n else cr)

            if isinstance(rest, int):            # not causal: known here
                end(rest, carry)
            else:
                lax.switch(rest, [functools.partial(end, n)
                                  for n in range(wide + 1)],
                           *(() if carry is None else (carry,)))

        if wide == 1:
            walk_singly()
        elif not causal:
            walk_wide()
        else:
            lax.cond(nk_run - nk_full <= 1, walk_wide, walk_singly)
        return 0

    if n_sub == 1:
        sub_block(0, 0)
    else:
        lax.fori_loop(0, n_sub, sub_block, 0)


def _fwd_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *, sub_q,
                sub_k, wide, scale, causal):
    """Grid (BH // heads, S // block_q) over split (BH, S, D) tensors."""
    import jax.experimental.pallas as pl

    heads, block_q = q_ref.shape[:2]

    # Keep q/k/v in their storage dtype for the MXU dots (bf16×bf16 with f32
    # accumulation runs at full MXU rate; pre-casting to f32 would quarter
    # it) — only the softmax statistics live in f32.
    def load_q(i):
        rows = pl.ds(i * sub_q, sub_q)
        return tuple(q_ref[h, rows, :] for h in range(heads))

    def load_kv(j, n):
        cols = pl.ds(j * sub_k, n * sub_k)
        return tuple((k_ref[h, cols, :], v_ref[h, cols, :])
                     for h in range(heads))

    def store(i, outs):
        rows = pl.ds(i * sub_q, sub_q)
        for h, (out, lse) in enumerate(outs):
            o_ref[h, rows, :] = out.astype(o_ref.dtype)
            # lse lives in (rows, 8) lanes purely to satisfy TPU tiling
            lse_ref[h, rows, :] = jnp.broadcast_to(lse, (sub_q, 8))

    _fwd_core(load_q, load_kv, store, off_ref[0],
              pl.program_id(1) * block_q, block_q // sub_q, sub_q,
              k_ref.shape[1], sub_k, wide, scale, causal, v_ref.shape[2],
              heads)


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying the input's vma so the kernel composes with
    shard_map's check_vma (ring attention calls this inside shard_map)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _match_vma(x, like):
    """Broadcast x's varying-manual-axes to like's so pallas_call composes
    with shard_map's check_vma."""
    missing = tuple(sorted(set(jax.typeof(like).vma)
                           - set(jax.typeof(x).vma)))
    return lax.pcast(x, missing, to="varying") if missing else x


# jitted: a model's layers call the kernels at the same shapes, and one
# traced and lowered body serves them all (24 forward and 24 backward
# bodies of branches were 6 s of every warm start)
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _fwd_pallas(q, k, v, offset, scale, causal, sched, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    dv = v.shape[-1]          # a value row may be narrower than q's and k's
    bh = b * h
    hp = sched.heads if bh % sched.heads == 0 else 1   # heads come in pairs
    block_q = sched.block_q
    q3 = q.reshape(bh, s, d)
    k3 = k.reshape(bh, s, d)
    v3 = v.reshape(bh, s, dv)
    off = _match_vma(jnp.asarray(offset, jnp.int32).reshape(1), q)
    kernel = functools.partial(_fwd_kernel, sub_q=sched.sub_q,
                               sub_k=sched.sub_k,
                               wide=sched.block_k // sched.sub_k, scale=scale,
                               causal=causal)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh // hp, s // block_q),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((hp, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((hp, s, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((hp, s, dv), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((hp, block_q, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((hp, block_q, 8), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            _sds((bh, s, dv), q.dtype, q),
            _sds((bh, s, 8), jnp.float32, q),
        ],
        interpret=interpret,
    )(off, q3, k3, v3)
    return out.reshape(b, h, s, dv), lse[..., 0].reshape(b, h, s)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _bwd_core(k_start, load_kv, loads, add_dq, offset, s_total, sub_q, tall,
              scale, causal, sub_k, d):
    """Shared bwd walk: one K/V sub-tile (``load_kv()`` gives ``(k_blk,
    v_blk)``, each (sub_k, d); its first position ``k_start``) against the
    q sub-blocks of ``sub_q`` rows that see it.

    The scores are made as ``k qᵀ`` — (sub_k, rows), a query a COLUMN — so
    that dV = P do and dK = dS q are plain products and only dQ = dSᵀ k
    contracts a leading dimension. dS = P ∘ (dP − δ + dlse) with δ =
    rowsum(dO ∘ O) precomputed outside; the softmax scale waits for the
    sums (dK here, dQ where it leaves the kernel) instead of passing over
    every score. ``loads(i, n)`` gives sub-blocks [i, i + n) as ONE ``(q_blk,
    do_blk, lse_row, dl_row)`` (n a Python int), the two statistics as (1,
    n·sub_q) rows; ``add_dq(i, n, val)`` adds the unscaled (n·sub_q, D) to
    those rows of dQ.

    Sub-blocks [i_start, i_full) are crossed by the diagonal and take the
    mask, [i_full, nq) are fully visible, those before see nothing. With
    ``tall`` 1 that is two loops of one sub-block an iteration; with
    ``tall`` > 1, where the diagonal crosses ONE sub-block (every offset
    that is a multiple of ``sub_q``), the visible sub-blocks go ``tall`` at
    a time as one product: the 1 … ``tall`` that begin on the diagonal in a
    straight-line body picked by their number, the mask on the first
    ``sub_q`` columns alone, then whole ones in a loop (:func:`_fwd_core`
    says why). Returns ``(dk_acc, dv_acc)`` f32.
    """
    nq = s_total // sub_q
    c = scale * _LOG2E

    if causal:
        # first q sub-block with any visible row: i*sq + sq-1 + offset >=
        # k_start
        i_start = jnp.clip((k_start - offset) // sub_q, 0, nq)
        # first with EVERY row visible: i*sq + offset >= k_start + sk - 1
        i_full = jnp.clip((k_start + sub_k - 1 - offset + sub_q - 1) // sub_q,
                          i_start, nq)
    else:
        i_start = i_full = 0

    def blocks(i, n, carry, masked):
        """Sub-blocks [i, i + n) in one product, the first under the mask if
        ``masked``; ``carry`` None: nothing gathered yet. As in the forward,
        a body loads what it reads itself."""
        k_blk, v_blk = load_kv()
        q_blk, do_blk, lse_row, dl_row = loads(i, n)
        prec = _dot_prec(k_blk.dtype)
        st = _dotT(k_blk, q_blk, prec)              # raw scores (sk, n·sq)
        expo = st * c - lse_row * _LOG2E
        if masked:
            # row + offset >= col, in the first sub-block's indices. Rows
            # with lse=_NEG_INF (never visible anywhere — ring's
            # partially-masked edge, offset<0 unaligned to sub_q) reach
            # masked sub-blocks whole: exp2(s·c − lse·log2e) would overflow
            # to +inf there. Valid rows always have exponent ≤ 0 (p ≤ 1),
            # so clamping at 0 plus a per-row zero repairs them without
            # touching the hot unmasked path.
            visible = (lax.broadcasted_iota(jnp.int32, (sub_k, sub_q), 1)
                       - lax.broadcasted_iota(jnp.int32, (sub_k, sub_q), 0)
                       >= k_start - i * sub_q - offset)
            first = jnp.where(
                visible & (lse_row[:, :sub_q] > _NEG_INF / 2),
                jnp.exp2(jnp.minimum(expo[:, :sub_q], 0.0)), 0.0)
            p = first if n == 1 else jnp.concatenate(
                [first, jnp.exp2(expo[:, sub_q:])], axis=1)
        else:
            # fully-visible ⇒ every row visible ⇒ lse finite
            p = jnp.exp2(expo)
        dp = _dotT(v_blk, do_blk, prec)                 # (sk, n·sq)
        dsd = (p * (dp - dl_row)).astype(q_blk.dtype)
        dk = _dot(dsd, q_blk, prec)                     # (sk, D)
        dv = _dot(p.astype(do_blk.dtype), do_blk, prec)  # (sk, D)
        add_dq(i, n, _dotA(dsd, k_blk, prec))           # (n·sq, D)
        if carry is not None:
            dk, dv = carry[0] + dk, carry[1] + dv
        return dk, dv

    def zero():
        return (jnp.zeros((sub_k, d), jnp.float32),) * 2

    def walk_singly():
        carry = zero()
        if causal:
            carry = lax.fori_loop(
                i_start, i_full, lambda i, cr: blocks(i, 1, cr, True), carry)
        return lax.fori_loop(
            i_full, nq, lambda i, cr: blocks(i, 1, cr, False), carry)

    def walk_tall():
        seen = nq - i_start                     # sub-blocks that see the tile
        # 1 … tall begin on the diagonal, whole products follow
        if isinstance(seen, int):               # not causal: known here
            head = (seen - 1) % tall + 1
            carry = blocks(0, head, None, False)
        else:
            head = jnp.where(seen > 0, (seen - 1) % tall + 1, 0)
            carry = lax.switch(head, [zero] + [
                functools.partial(blocks, i_start, n, None, True)
                for n in range(1, tall + 1)])
        if nq > tall:       # a tile may be seen by whole tall products
            carry = lax.fori_loop(
                0, (seen - head) // tall,
                lambda t, cr: blocks(i_start + head + t * tall, tall, cr,
                                     False), carry)
        return carry

    if tall == 1:
        dk_acc, dv_acc = walk_singly()
    elif not causal:
        dk_acc, dv_acc = walk_tall()
    else:
        dk_acc, dv_acc = lax.cond(i_full - i_start <= 1, walk_tall,
                                  walk_singly)
    return dk_acc * scale, dv_acc


def _bwd_kernel(off_ref, q_ref, do_ref, lse_ref, dl_ref, k_ref, v_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, *, sub_q, sub_k, tall, scale,
                causal):
    """Grid (BH, S // block_k) over split (BH, S, D) tensors, one head a
    step (two in one body spilled: 0.47 ms a call for 0.38, PERF.md §6);
    the two statistics come as (BH, 1, S) rows. dQ gathers in ``dq_acc``
    (legal: the TPU grid runs sequentially per core and dQ's index map
    ignores the kv-block index) and is written once, behind the last K/V
    block."""
    import jax.experimental.pallas as pl

    j = pl.program_id(1)
    block_k = k_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def loads(i, n):
        rows = pl.ds(i * sub_q, n * sub_q)
        return q_ref[rows, :], do_ref[rows, :], lse_ref[:, rows], \
            dl_ref[:, rows]

    def add_dq(i, n, val):
        dq_acc[pl.ds(i * sub_q, n * sub_q), :] += val

    def sub_tile(t, _):
        cols = pl.ds(t * sub_k, sub_k)
        dk_acc, dv_acc = _bwd_core(
            j * block_k + t * sub_k,
            lambda: (k_ref[cols, :], v_ref[cols, :]), loads, add_dq,
            off_ref[0], q_ref.shape[0], sub_q, tall, scale, causal, sub_k,
            k_ref.shape[1])
        dk_ref[cols, :] = dk_acc.astype(dk_ref.dtype)
        dv_ref[cols, :] = dv_acc.astype(dv_ref.dtype)
        return 0

    if block_k == sub_k:
        sub_tile(0, 0)
    else:
        lax.fori_loop(0, block_k // sub_k, sub_tile, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _write():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _bwd_pallas(scale, causal, sched, interpret, res, g):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q, k, v, offset, o, lse = res
    do, g_lse = g
    b, h, s, d = q.shape
    bh = b * h
    block_k = sched.block_k
    # δ − dlse folded into ONE per-row vector so the kernel reads it once
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dl3 = (delta - g_lse.astype(jnp.float32)).reshape(bh, 1, s)
    lse3 = lse.reshape(bh, 1, s)
    q3 = q.reshape(bh, s, d)
    k3 = k.reshape(bh, s, d)
    v3 = v.reshape(bh, s, d)
    do3 = do.astype(q.dtype).reshape(bh, s, d)
    off = _match_vma(jnp.asarray(offset, jnp.int32).reshape(1), q)

    def whole(*minor):
        return pl.BlockSpec((None,) + minor, lambda i, j: (i, 0, 0))

    def block(*minor):
        return pl.BlockSpec((None,) + minor, lambda i, j: (i, j, 0))

    kernel = functools.partial(_bwd_kernel, sub_q=sched.sub_q,
                               sub_k=sched.sub_k,
                               tall=sched.block_q // sched.sub_q, scale=scale,
                               causal=causal)
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(bh, s // block_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            whole(s, d), whole(s, d),               # q, do
            whole(1, s), whole(1, s),               # lse, δ-dlse
            block(block_k, d), block(block_k, d),   # k, v
        ],
        out_specs=[whole(s, d), block(block_k, d), block(block_k, d)],
        out_shape=[
            _sds((bh, s, d), q.dtype, q),
            _sds((bh, s, d), k.dtype, q),
            _sds((bh, s, d), v.dtype, q),
        ],
        scratch_shapes=[pltpu.VMEM((s, d), jnp.float32)],
        interpret=interpret,
    )(off, q3, do3, lse3, dl3, k3, v3)
    return dq.reshape(b, h, s, d), dk.reshape(b, h, s, d), \
        dv.reshape(b, h, s, d)


def _bwd_blocked(scale, causal, block_k, res, g):
    """Fallback flash backward (plain JAX blocked scan) for non-TPU
    backends: XLA fuses it well enough on CPU and it avoids slow
    interpret-mode Pallas in the test suite.

    dS = P ∘ (dP − δ + dlse) with δ = rowsum(dO ∘ O); memory O(S·block_k).
    """
    q, k, v, offset, o, lse = res
    do = g[0]
    g_lse = g[1].astype(jnp.float32)  # ring attention differentiates lse too
    b, h, s, d = q.shape
    dt = q.dtype  # matmul operands stay in storage dtype (full-rate MXU),
    f32 = functools.partial(jnp.einsum, preferred_element_type=jnp.float32,
                            precision=_dot_prec(q.dtype))
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    nk = s // block_k

    rows = lax.broadcasted_iota(jnp.int32, (s, block_k), 0)

    def blk(j):
        k_blk = lax.dynamic_slice_in_dim(k, j * block_k, block_k, 2)
        v_blk = lax.dynamic_slice_in_dim(v, j * block_k, block_k, 2)
        sc = f32("bhqd,bhkd->bhqk", q, k_blk) * scale
        if causal:
            cols = j * block_k + lax.broadcasted_iota(
                jnp.int32, (s, block_k), 1)
            sc = jnp.where(rows + offset >= cols, sc, _NEG_INF)
        p = jnp.exp(sc - lse[..., None])                   # (B,H,S,bk)
        p = jnp.where(sc <= _NEG_INF / 2, 0.0, p)
        dv_blk = f32("bhqk,bhqd->bhkd", p.astype(dt), do)
        dp = f32("bhqd,bhkd->bhqk", do, v_blk)
        ds = (p * (dp - delta[..., None] + g_lse[..., None])
              * scale).astype(dt)
        dq_contrib = f32("bhqk,bhkd->bhqd", ds, k_blk)
        dk_blk = f32("bhqk,bhqd->bhkd", ds, q)
        return dq_contrib, dk_blk, dv_blk

    def step(dq, j):
        dq_c, dk_blk, dv_blk = blk(j)
        return dq + dq_c, (dk_blk, dv_blk)

    dq, (dk_blocks, dv_blocks) = lax.scan(
        step, jnp.zeros((b, h, s, d), jnp.float32), jnp.arange(nk))
    dk = jnp.moveaxis(dk_blocks, 0, 2).reshape(b, h, s, d)
    dv = jnp.moveaxis(dv_blocks, 0, 2).reshape(b, h, s, d)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            _int_zero(offset))


def _int_zero(x):
    import numpy as np

    return np.zeros(x.shape, jax.dtypes.float0)


# ---------------------------------------------------------------------------
# The schedule: blocks, sub-tiles and heads a grid step, from the shapes
# ---------------------------------------------------------------------------

# Shapes not listed walk each 512-row block whole, one head a step: a
# (512, 512) pair an iteration of a loop, what every shape ran before the
# table was measured.
_DEFAULT_SCHEDULE = _Schedule(512, 512, 512, 512, 1)

# (seq, head_dim) → schedule, forward and backward alike, causal or not.
# Measured on a TPU v5e, the kernels alone, bf16 causal, at the shapes the
# benchmark's cells run and chip_smoke.py's (PERF.md §6, PR 47, the second
# sweep: device time of the Mosaic events of 10 calls a schedule;
# tools/tune_flash.py sweeps the same candidates by slope timing and prints
# lines like these). 64-wide heads:
# forward + backward; 192-wide with 128-wide values (latent attention's
# prefill): forward.
_BLOCK_TABLE = {
    (1024, 64): _Schedule(1024, 1024, 256, 256, 2),   # 0.82 → 0.56 ms
    (2048, 64): _Schedule(2048, 2048, 256, 256, 1),   # 1.82 → 1.35
    (512, 192): _Schedule(512, 512, 256, 256, 1),     # 0.167 → 0.091
    (1024, 192): _Schedule(1024, 1024, 256, 256, 1),  # 0.430 → 0.248
    (1536, 192): _Schedule(1536, 1536, 256, 256, 1),  # 0.780 → 0.482
}


def _pick_block(s, target):
    blk = min(s, target)
    while s % blk:
        blk //= 2
    return max(blk, 1)


def _resolve_blocks(s, d, block_q, block_k, table=None):
    # explicit argument, else the tuned table (or the caller's own
    # ``table`` entry)
    if block_q is None or block_k is None:
        table = table or _BLOCK_TABLE.get((s, d), _DEFAULT_SCHEDULE)
        block_q = block_q if block_q is not None else table.block_q
        block_k = block_k if block_k is not None else table.block_k
    return _pick_block(s, block_q), _pick_block(s, block_k)


def _resolve_schedule(s, d, block_q=None, block_k=None):
    """The table's schedule for (s, d) with the blocks :func:`_resolve_blocks`
    settles on; a sub-tile divides its block."""
    table = _BLOCK_TABLE.get((s, d), _DEFAULT_SCHEDULE)
    block_q, block_k = _resolve_blocks(s, d, block_q, block_k, table)
    return _Schedule(block_q, block_k, _pick_block(block_q, table.sub_q),
                     _pick_block(block_k, table.sub_k), table.heads)


def flash_schedule(s, d, causal, batch=1, heads=None):
    """What the flash kernels do at (``s``, ``d``), from the shapes alone:
    the blocks a grid step keeps resident, the (q rows, k columns) of the
    pairs it walks them in, the heads a forward step of the (B, H, S, D) entry
    advances (one where batch × heads is odd; its backward always one), per
    head at offset 0 the pairs run, masked, and their shares of the square;
    ``packed``: the packed entry's blocks and grids (None: not taken)."""
    sched = _resolve_schedule(s, d)
    sq, sk = sched.sub_q, sched.sub_k
    run = full = (s // sq) * (s // sk)
    if causal:   # a pair runs if its first column is within its last row
        run = sum(min(s // sk, (i * sq + sq - 1) // sk + 1)
                  for i in range(s // sq))
        full = sum(min(s // sk, max(0, (i * sq - sk + 1) // sk + 1))
                   for i in range(s // sq))
    return {"block_q": sched.block_q, "block_k": sched.block_k,
            "sub_tile": (sq, sk), "heads_per_step": sched.heads,
            "tiles_run": run, "tiles_masked": run - full,
            "computed_share": run * sq * sk / (s * s),
            "masked_share": (run - full) * sq * sk / (s * s),
            "packed": _packed_schedule(s, d, sched, batch, heads)}


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, offset, scale, causal, sched, interpret):
    return _fwd_pallas(q, k, v, offset, scale, causal, sched, interpret)


def _flash_fwd(q, k, v, offset, scale, causal, sched, interpret):
    out, lse = _fwd_pallas(q, k, v, offset, scale, causal, sched, interpret)
    return (out, lse), (q, k, v, offset, out, lse)


def _flash_bwd(scale, causal, sched, interpret, res, g):
    if res[2].shape[-1] != res[0].shape[-1]:
        raise NotImplementedError(
            "flash attention with d_v != d_qk is forward-only (prefill)")
    impl = os.environ.get("MXNET_FLASH_BWD", "auto")
    # on the chip the kernel slices its statistics' LANES by q sub-block
    use_pallas = impl == "pallas" or (
        impl == "auto" and not interpret and sched.sub_q % 128 == 0)
    if use_pallas:
        return _bwd_pallas(scale, causal, sched, interpret, res, g) + (
            _int_zero(res[3]),)
    return _bwd_blocked(scale, causal, sched.sub_k, res, g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _use_interpret():
    return jax.default_backend() != "tpu"


def flash_attention_with_lse(q, k, v, causal=False, scale=None, offset=0,
                             block_q=None, block_k=None):
    """(out, lse) — lse feeds ring attention's cross-device block combine.

    ``offset`` (int scalar, may be traced): causal visibility is
    ``row + offset >= col``; ignored when causal=False.
    """
    d = q.shape[-1]
    s = q.shape[-2]
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(d)
    sched = _resolve_schedule(s, d, block_q, block_k)
    offset = jnp.asarray(offset, jnp.int32)
    return _flash(q, k, v, offset, scale, causal, sched, _use_interpret())


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None):
    """Flash attention. q, k (B, H, S, D), v (B, H, S, Dv) → (B, H, S, Dv);
    ``Dv != D`` (latent attention's 192-wide keys, 128-wide values) in the
    forward pass only."""
    out, _ = flash_attention_with_lse(q, k, v, causal=causal, scale=scale,
                                      block_q=block_q, block_k=block_k)
    return out


# ---------------------------------------------------------------------------
# Decode-shape attention over a paged KV cache (serve/decode.py)
#
# One query position per sequence against its page table. The pool is ONE
# array ``(pages, layers, page_size, heads, 2 * head_dim)`` — a position's
# K row and V row for one head side by side on the minor axis — and both
# paths below take it whole, with the layer as a static index: nothing
# between a layer's KV write and its attention slices, copies or lays out
# again any part of the pool. The Pallas kernel never gathers: the pool
# stays in HBM, and grid step (b, j) copies the blocks
# ``(table[b, j*G + g], layer)`` of its group's live pages — a page's K
# and V in one contiguous DMA each, addressed through the scalar-prefetched
# page table — straight from the pool: attention IS the gather.
# Off-TPU (and for the reference/parity tests) the XLA path gathers the
# page table's pages of that layer, and only those, instead.
# ---------------------------------------------------------------------------


def _decode_attention_xla(q, pool, layer, page_table, lengths, scale):
    """Gather-then-attend reference. q (B, H, D); pool
    (P, L, page, H, 2D); ``layer`` a Python int; page_table (B, max_pages)
    int32; lengths (B,) int32. Returns (B, H, D)."""
    b, h, d = q.shape
    page = pool.shape[2]
    kv = pool[page_table, layer]  # one gather: (B, max_pages, page, H, 2D)
    s = kv.shape[1] * page
    kv = kv.reshape(b, s, h, 2 * d)
    k, v = kv[..., :d], kv[..., d:]
    prec = _dot_prec(q.dtype)
    scores = jnp.einsum("bhd,bshd->bhs", q, k,
                        preferred_element_type=jnp.float32,
                        precision=prec) * scale
    live = jnp.arange(s)[None, :] < lengths[:, None]  # (B, S)
    scores = jnp.where(live[:, None], scores, _NEG_INF)
    # _NEG_INF (not -inf) keeps fully-masked rows (inactive decode slots,
    # length 0) finite — uniform garbage the caller discards, never NaN
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhs,bshd->bhd", p, v,
                      preferred_element_type=jnp.float32,
                      precision=prec).astype(q.dtype)


# What a group of pages may take in VMEM as the kernel works on it, in
# float32: a megabyte spreads a grid step's fixed cost (the step itself, one
# update of the softmax statistics) over eight 128 KB pages, and the two
# buffers the pages are copied into plus the products' temporaries stay a
# fraction of the 16 MB a kernel may hold.
_DECODE_GROUP_BYTES = 1024 * 1024


def decode_page_group(pool_shape, max_pages):
    """G, the pages :func:`flash_decode_attention` reads a grid step: the
    largest group whose ``(page, H, 2D)`` blocks fit ``_DECODE_GROUP_BYTES``
    as the kernel computes on them (float32, whatever the pool holds; the
    heads padded to 8 sublanes, the row to 128 lanes), at least 1 and at
    most ``max_pages``. A function of the pool's shape and the page
    table's width alone; no caller sets it."""
    page, h, row = pool_shape[2:]
    block = page * -(-h // 8) * 8 * -(-row // 128) * 128 * 4
    return int(max(1, min(_DECODE_GROUP_BYTES // block, max_pages)))


def flash_decode_attention(q, pool, layer, page_table, lengths, scale=None,
                           interpret=False):
    """Pallas paged decode attention. Shapes as ``decode_attention``.

    Grid ``(B, ceil(max_pages / G))``, G from :func:`decode_page_group`:
    grid step (b, j) works on the G pages ``table[b, j*G : (j+1)*G]`` of
    layer ``layer`` and takes the online-softmax statistics (log2 domain,
    f32, in VMEM scratch across a sequence's steps) ONCE over their
    ``G * page`` rows: one max, one rescale of the output block. Both axes
    run in order, the group axis innermost; ``pl.when`` skips a group
    wholly past the sequence's length, rows past it inside a live group are
    masked, and the last step normalizes.

    The pool is the kernel's operand as it lies, left in HBM: the kernel
    copies ``pool[table[b, j*G + g], layer]`` — a page's K and V, one
    contiguous DMA — for the LIVE pages of a group only, into one of two
    VMEM buffers, and a live group starts the copies of the next live
    group (this sequence's next, or the first of the next sequence that
    holds anything) before it waits for its own: the copies run under the
    arithmetic, across sequences too, and a page past a sequence's length
    is neither fetched nor looked up in the table (a table whose width G
    does not divide needs no padding).

    ``layer`` reaches the kernel as a prefetched scalar, so the calls of a
    step program's layers are ONE traced and lowered kernel (a program of
    24 layers would otherwise spend seconds of every start lowering 24
    copies that differ in one constant)."""
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return _paged_decode(
        q, pool, jnp.full((1,), int(layer), jnp.int32),
        page_table.astype(jnp.int32), lengths.astype(jnp.int32),
        decode_page_group(pool.shape, page_table.shape[1]), scale, interpret)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _paged_decode(q, pool, layer, page_table, lengths, group, scale,
                  interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    page = pool.shape[2]
    max_pages = page_table.shape[1]
    n_groups = -(-max_pages // group)
    rows = group * page
    s2_scale = scale * _LOG2E

    def kernel(pt_ref, len_ref, layer_ref, q_ref, pool_ref, o_ref, buf, sem,
               m_ref, l_ref, turn):
        # turn[0]: the buffer the next live group's pages are copied into;
        # turn[1]: 1 once a group has started copies (every live group
        # after the first finds its own already on their way)
        seq = pl.program_id(0)
        j = pl.program_id(1)

        def visible(sq):
            return jnp.minimum(len_ref[sq], max_pages * page)

        def copies(sq, grp, slot, act):
            n_live = (visible(sq) + page - 1) // page
            for g in range(group):
                @pl.when(grp * group + g < n_live)
                def _copy(g=g):
                    act(pltpu.make_async_copy(
                        pool_ref.at[pt_ref[sq, grp * group + g], layer_ref[0]],
                        buf.at[slot, g], sem.at[slot]))

        @pl.when((seq == 0) & (j == 0))
        def _first():
            turn[0] = 0
            turn[1] = 0
            # a live group's dead pages are read too (weight 0): what no
            # copy ever landed on must be finite
            buf[...] = jnp.zeros_like(buf)

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            o_ref[...] = jnp.zeros_like(o_ref)

        length = visible(seq)

        @pl.when(j * rows < length)
        def _group():
            slot = turn[0]

            @pl.when(turn[1] == 0)
            def _cold():
                copies(seq, j, slot, lambda dma: dma.start())
                turn[1] = 1

            more = (j + 1) * rows < length
            nxt = lax.cond(
                more, lambda: seq,
                lambda: lax.while_loop(
                    lambda sq: (sq < b) & (len_ref[jnp.minimum(sq, b - 1)]
                                           <= 0),
                    lambda sq: sq + 1, seq + 1))

            @pl.when(nxt < b)
            def _prefetch():
                copies(jnp.minimum(nxt, b - 1), jnp.where(more, j + 1, 0),
                       1 - slot, lambda dma: dma.start())

            copies(seq, j, slot, lambda dma: dma.wait())
            turn[0] = 1 - slot
            # One query row per head is a mat-vec: no MXU shape fits it
            # (Mosaic has no batched dot without an M dimension). So the
            # products run on the VPU in f32, in the pool's own
            # (page, H, 2D) layout — heads on sublanes, K then V on lanes,
            # per-head statistics as (H, 1) columns — with no transpose or
            # relayout of a page; the group's pages follow one another on
            # the leading axis.
            qv = q_ref[0].astype(jnp.float32)              # (H, D)
            blk = buf[slot].astype(jnp.float32).reshape(rows, h, 2 * d)
            kb = blk[..., :d]                              # (rows, H, D)
            sc = jnp.sum(kb * qv[None], axis=-1,           # (rows, H, 1),
                         keepdims=True) * s2_scale         # log2 domain
            pos = j * rows + lax.broadcasted_iota(jnp.int32, (rows, h, 1), 0)
            sc = jnp.where(pos < length, sc, _NEG_INF)
            m_prev = m_ref[:, 0:1]                         # (H, 1)
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=0))
            alpha = jnp.exp2(m_prev - m_new)
            # the group holds a live row, so m_new is finite and a masked
            # row's exp2 is 0
            p = jnp.exp2(sc - m_new[None])                 # (rows, H, 1)
            l_new = l_ref[:, 0:1] * alpha + jnp.sum(p, axis=0)
            # p weighs the whole row; the V half of the (H, 2D) sum is the
            # update, so the one lane shift is of the sum, not of the pages
            pv = jnp.sum(p * blk, axis=0)[:, d:]           # (H, D)
            o_ref[0] = o_ref[0] * alpha + pv
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

        @pl.when(j == n_groups - 1)
        def _norm():
            # length-0 rows (inactive slots) never accumulate: clamp keeps
            # their garbage finite instead of 0/0
            o_ref[0] = o_ref[0] / jnp.maximum(l_ref[:, 0:1], 1e-30)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_groups),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda sq, j, pt, ln, ly: (sq, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # the pool, where it lies
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda sq, j, pt, ln, ly: (sq, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, group, page, h, 2 * d), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((h, 128), jnp.float32),   # running max (log2)
            pltpu.VMEM((h, 128), jnp.float32),   # running denominator
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), jnp.float32),
        # a group hands its buffer turn and its copies on to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_decode",
    )(page_table, lengths, layer, q, pool)
    return out.astype(q.dtype)


def decode_attention_impl(pool=None) -> str:
    """The path :func:`decode_attention` takes in this process: ``pallas``
    or ``xla``. ``MXNET_DECODE_ATTN`` names one outright; ``auto`` (the
    default) is the Pallas kernel on a TPU backend and the XLA gather
    everywhere else — also, where a per-head ``pool`` (its ``shape`` and
    ``dtype``) is given, for one whose pages the kernel cannot copy out of
    it: Mosaic slices an HBM array in whole tiles only, which a ``(H, 2D)``
    row fills if 2D is a multiple of 128 lanes and, for a 16-bit pool, H a
    multiple of 8 (a float32 pool: any H)."""
    impl = os.environ.get("MXNET_DECODE_ATTN", "auto")
    if impl == "auto":
        if _use_interpret():
            return "xla"
        if pool is not None:
            itemsize = jnp.dtype(pool.dtype).itemsize
            whole = pool.shape[4] % 128 == 0 and (
                itemsize == 4 or (itemsize == 2 and pool.shape[3] % 8 == 0))
            return "pallas" if whole else "xla"
        return "pallas"
    if impl not in ("pallas", "xla"):
        raise ValueError(
            f"MXNET_DECODE_ATTN={impl!r}: expected auto, pallas or xla")
    return impl


def decode_attention(q, pool, layer, page_table, lengths, scale=None):
    """Single-position attention against one layer of a paged KV cache.

    q (B, H, D) — one query position per live sequence; pool
    (P, L, page_size, H, 2D) — the whole device page pool, every layer's,
    K on ``[..., :D]`` and V on ``[..., D:]``; ``layer`` — a Python int,
    the layer whose pages are read (no caller slices the pool: the paths
    address ``(page, layer)`` themselves); page_table (B, max_pages) int32
    — page ids in position order (pad unused slots with any valid page,
    e.g. scratch page 0); lengths (B,) int32 — positions visible per
    sequence (0 = inactive row, output garbage; capped at the table's
    ``max_pages * page_size``). Returns (B, H, D).
    :func:`decode_attention_impl` picks the path; the Pallas one reads the
    table's pages in groups of G a grid step, G found from the pool's
    shape (:func:`decode_page_group`), never set by a caller.
    """
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if decode_attention_impl(pool) == "pallas":
        return flash_decode_attention(q, pool, layer, page_table, lengths,
                                      scale=scale,
                                      interpret=_use_interpret())
    return _decode_attention_xla(q, pool, layer, page_table, lengths, scale)


# ---------------------------------------------------------------------------
# Latent (MLA, absorbed form) decode attention over a paged latent pool
#
# The pool is ``(pages, layers, page_size, R)``: ONE row per position per
# layer and no head axis — the compressed latent ``c`` (the first ``d_v``
# columns) and the shared rotary key beside it. Every head's query (already
# taken through ``W_uk``: ``[q_nope.W_uk || q_rope]``, R wide) scores against
# that one row, and the values are the row's first ``d_v`` columns; ``W_uv``
# is applied by the caller. Addressed as the kernel above: the pool whole,
# a static layer, page table and lengths; block (b, j) is
# ``pool[table[b, j], layer]``.
# ---------------------------------------------------------------------------


def _latent_decode_attention_xla(q, pool, layer, page_table, lengths, d_v,
                                 scale):
    """Gather-then-attend reference. q (B, H, R); pool (P, L, page, R);
    returns (B, H, d_v)."""
    b = q.shape[0]
    rows = pool[page_table, layer]            # (B, max_pages, page, R)
    rows = rows.reshape(b, -1, rows.shape[-1])
    prec = _dot_prec(q.dtype)
    scores = jnp.einsum("bhr,bsr->bhs", q, rows,
                        preferred_element_type=jnp.float32,
                        precision=prec) * scale
    live = jnp.arange(rows.shape[1])[None, :] < lengths[:, None]
    scores = jnp.where(live[:, None], scores, _NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(pool.dtype)
    return jnp.einsum("bhs,bsv->bhv", p, rows[..., :d_v],
                      preferred_element_type=jnp.float32,
                      precision=prec).astype(q.dtype)


def flash_latent_decode_attention(q, pool, layer, page_table, lengths, d_v,
                                  scale, interpret=False):
    """Pallas paged latent decode attention; shapes as
    ``latent_decode_attention``. Grid (B, max_pages), the page axis
    innermost: per slot, the H x R queries meet one ``(page, R)`` block a
    step on the MXU (H rows: a real matmul, unlike the per-head kernel
    above), online softmax in float32 in the log2 domain, the running
    ``(H, d_v)`` sum in the output block; ``pl.when`` skips the pages past
    the sequence's length (their block index repeats the scratch page, so
    nothing is fetched for them)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, r = q.shape
    page = pool.shape[2]
    layer = int(layer)
    max_pages = page_table.shape[1]
    s2_scale = float(scale) * _LOG2E
    prec = _dot_prec(pool.dtype)

    def kernel(pt_ref, len_ref, q_ref, kv_ref, o_ref, m_ref, l_ref):
        seq = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            o_ref[...] = jnp.zeros_like(o_ref)

        length = len_ref[seq]

        @pl.when(j * page < length)
        def _block():
            blk = kv_ref[0]                                 # (page, R)
            sc = _dotT(q_ref[0], blk, prec) * s2_scale      # (H, page)
            pos = j * page + lax.broadcasted_iota(jnp.int32, (h, page), 1)
            sc = jnp.where(pos < length, sc, _NEG_INF)
            m_prev = m_ref[:, 0:1]                          # (H, 1)
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp2(m_prev - m_new)
            # this block holds a live column, so m_new is finite and the
            # masked columns' exp2 is 0
            p = jnp.exp2(sc - m_new)
            l_new = l_ref[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
            o_ref[0] = o_ref[0] * alpha + jnp.dot(
                p.astype(blk.dtype), blk[:, :d_v],
                preferred_element_type=jnp.float32, precision=prec)
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

        @pl.when(j == max_pages - 1)
        def _norm():
            o_ref[0] = o_ref[0] / jnp.maximum(l_ref[:, 0:1], 1e-30)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_pages),
        in_specs=[
            pl.BlockSpec((1, h, r), lambda sq, j, pt, ln: (sq, 0, 0)),
            pl.BlockSpec((1, None, page, r),
                         lambda sq, j, pt, ln: (pt[sq, j], layer, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d_v), lambda sq, j, pt, ln: (sq, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 128), jnp.float32),   # running max (log2)
            pltpu.VMEM((h, 128), jnp.float32),   # running denominator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d_v), jnp.float32),
        interpret=interpret,
        name="mla_decode",
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32), q, pool)
    return out.astype(q.dtype)


def latent_decode_attention(q, pool, layer, page_table, lengths, d_v, scale):
    """Single-position latent attention against one layer of a paged latent
    pool. q (B, H, R) — every head's absorbed query; pool (P, L, page_size,
    R) — the whole pool, a position's latent and rotary key on one row;
    ``layer`` a Python int; page_table (B, max_pages) int32; lengths (B,)
    int32 (0 = inactive row, output garbage). Returns (B, H, d_v): each
    head's softmax-weighted sum of the rows' first ``d_v`` columns.
    :func:`decode_attention_impl` picks the path."""
    if decode_attention_impl() == "pallas":
        return flash_latent_decode_attention(
            q, pool, layer, page_table, lengths, d_v, scale,
            interpret=_use_interpret())
    return _latent_decode_attention_xla(q, pool, layer, page_table, lengths,
                                        d_v, scale)


# ---------------------------------------------------------------------------
# A latent-attention prompt continued from a position (serve/decode.py,
# ``prefill_from``; models/mla_moe.py)
#
# The flash forward above for the query rows of ONE piece of one sequence
# against every position so far, the keys and values expanded from the cached
# latent rows inside the kernel. It stands here, at the end, so that
# everything above keeps its lines (and the programs traced through them
# their cache keys).
# ---------------------------------------------------------------------------

__all__ += ["latent_flash_attention_from"]


def latent_flash_attention_from(q, rows, uk_w, uv_w, start, scale,
                                block_q=None, block_k=None):
    """Causal expanded latent attention of a piece of one sequence against
    all of it so far. q (H, C, nope + rope): the query rows of positions
    ``start .. start + C - 1`` (``start`` () int32, traced, a multiple of
    C); rows (T, R): the cached rows ``[c || k_r || 0]`` of positions ``0 ..
    T - 1`` (T a multiple of C, ``c`` as wide as ``uk_w``'s last axis), of
    which those the piece sees (``< start + C``) are filled in — what lies
    behind is copied in with the rest and never looked at, whatever it
    holds; uk_w (H, nope, rank), uv_w (H, rank, Dv). Head i's keys are ``[c
    . uk_w_i^T || k_r]`` and its values ``c . uv_w_i``, rounded to the rows'
    dtype as ``models.mla_moe.prefill_attention`` rounds them, a block of
    ``block_k`` positions at a time as the loop comes to it: the work
    follows ``start``, nothing expanded rests in HBM. Returns (H, C, Dv) in
    q's dtype: the rows :func:`flash_attention` gives at those positions
    over the same keys and values with the same ``block_k`` (the same key
    blocks in the same order; a block a row does not see leaves its sums as
    they were). ``block_q`` defaults to 1,024 at most: where that is the
    whole piece, a head expands a position once."""
    c = q.shape[1]
    if rows.shape[0] % c:
        raise ValueError(
            f"{rows.shape[0]} positions are not whole pieces of {c}")
    # blocks that divide the piece: the diagonal ends where the piece ends
    # (the table's schedules are the whole forward's: a piece keeps key
    # blocks of 512, each expanded once and walked as one tile)
    bq, bk = _resolve_blocks(c, q.shape[2], block_q or 1024, block_k,
                             _DEFAULT_SCHEDULE)
    return _latent_flash_from(jnp.reshape(start, (1,)).astype(jnp.int32), q,
                              rows, uk_w, uv_w, float(scale), bq, bk,
                              _use_interpret())


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _latent_flash_from(start, q, rows, uk_w, uv_w, scale, bq, bk, interpret):
    """Grid (heads, query blocks): ``_fwd_core`` with the query rows
    ``start`` positions down the diagonal (its dynamic offset, as ring
    attention uses it) and a ``load_kv`` that makes a block's keys and
    values from the rows, which stay in VMEM whole (one copy for all heads:
    their block index never changes)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, c, d = q.shape
    t, r = rows.shape
    nope, rank = uk_w.shape[1:]
    dv = uv_w.shape[2]
    prec = _dot_prec(rows.dtype)

    def kernel(start_ref, q_ref, rows_ref, uk_ref, uv_ref, o_ref):
        def load_kv(j):
            blk = rows_ref[pl.ds(j * bk, bk), :]
            lat = blk[:, :rank]
            k_nope = _dotT(lat, uk_ref[...], prec).astype(blk.dtype)
            v = jnp.dot(lat, uv_ref[...], preferred_element_type=jnp.float32,
                        precision=prec).astype(blk.dtype)
            return (jnp.concatenate([k_nope, blk[:, rank:rank + d - nope]],
                                    axis=-1), v)

        def store(_, outs):
            o_ref[...] = outs[0][0].astype(o_ref.dtype)

        # ONE sub-block, the whole query block, a tile a product: a tile
        # is expanded once
        _fwd_core(lambda _: (q_ref[...],), lambda j, _: (load_kv(j),), store,
                  start_ref[0], pl.program_id(1) * bq, 1, bq, t, bk, 1, scale,
                  True, dv)

    def per_head(*block):
        return pl.BlockSpec((None,) + block, lambda i, j, st: (i, 0, 0))

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h, c // bq),
            in_specs=[pl.BlockSpec((None, bq, d), lambda i, j, st: (i, j, 0)),
                      pl.BlockSpec((t, r), lambda i, j, st: (0, 0)),
                      per_head(nope, rank), per_head(rank, dv)],
            out_specs=pl.BlockSpec((None, bq, dv),
                                   lambda i, j, st: (i, j, 0))),
        out_shape=jax.ShapeDtypeStruct((h, c, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="mla_prefill_from",
    )(start, q, rows, uk_w, uv_w)


# ---------------------------------------------------------------------------
# The packed entry (models/transformer.py ``MultiHeadAttention``)
#
# The same two cores over the layouts a transformer layer's own matmuls
# write and read: q, k and v are column blocks of the fused projection's
# (B, S, 3·U) output — ONE operand, handed over three times —, o and the
# three gradients are (B, S, U), what ``proj`` and the projection's
# gradient matmuls contract, and the lse is written as the rows the
# backward reads. A Mosaic call is opaque to XLA: every other layout at its
# edge is a transpose or a copy that XLA has to make and wait for (PERF.md
# §6, PR 48). A column block is 128 lanes or a whole head, whichever is
# wider; narrower heads lie side by side in it, and a product takes ONE of
# them by a mask on its smaller operand (the other heads' lanes add exact
# zeros; a contraction or a result of 64 lanes costs the MXU what one of
# 128 does), so nothing is shifted across lanes and the block's heads share
# its accumulators' registers. It stands here, at the end, as the latent
# entry above does and for the same reason.
# ---------------------------------------------------------------------------

__all__ += ["flash_attention_packed", "packed_layout"]


def packed_layout(units, heads):
    """``(lanes, heads)`` of one column block of :func:`flash_attention_
    packed` for ``heads`` heads in ``units`` columns, or None where the
    entry cannot take them: a block is whole 128-lane tiles and whole
    heads."""
    d = units // heads
    if units % heads or units % 128 or (128 % d and d % 128):
        return None
    return max(d, 128), max(128 // d, 1)


def _head_lanes(shape, h, d):
    """Where the lanes of a block's head ``h`` (``d`` wide) are."""
    lane = lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return (lane >= h * d) & (lane < (h + 1) * d)


def _only_head(x, h, hb):
    """``x`` (rows, lanes) with the lanes of the block's other heads zero."""
    if hb == 1:
        return x
    return jnp.where(_head_lanes(x.shape, h, x.shape[1] // hb), x,
                     jnp.zeros_like(x))


def _as_rows(columns):
    """(rows, 1) float32 columns, one a head of a block → (heads, rows): the
    columns side by side in the first lanes of ONE (rows, 128) array, which
    is transposed once."""
    wide = jnp.broadcast_to(columns[0], (columns[0].shape[0], 128))
    for n, col in enumerate(columns[1:], 1):
        wide = jnp.where(_head_lanes(wide.shape, n, 1), col, wide)
    return wide.T[:len(columns)]


def _packed_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, hb, sub_q,
                       sub_k, wide, scale, causal):
    """Grid (B · U // lanes, S // block_q): the ``hb`` heads of one column
    block side by side in one body (one after the other measured slower,
    0.179 → 0.195 ms a call at (4, 1024, 16 x 64)), a head's scores from
    its own lanes of q, its result picked out of the product's 128."""
    import jax.experimental.pallas as pl

    block_q, lanes = q_ref.shape

    def load_q(i):
        q = q_ref[pl.ds(i * sub_q, sub_q), :]
        return tuple(_only_head(q, h, hb) for h in range(hb))

    def load_kv(j, n):
        cols = pl.ds(j * sub_k, n * sub_k)
        return ((k_ref[cols, :], v_ref[cols, :]),) * hb

    def store(i, outs):
        rows = pl.ds(i * sub_q, sub_q)
        out = outs[0][0]
        for h in range(1, hb):   # a head's lanes of its own result
            out = jnp.where(_head_lanes(out.shape, h, lanes // hb),
                            outs[h][0], out)
        o_ref[rows, :] = out.astype(o_ref.dtype)
        # the heads' lse columns as the rows the backward reads
        lse_ref[:, rows] = _as_rows([lse for _, lse in outs])

    _fwd_core(load_q, load_kv, store, 0, pl.program_id(1) * block_q,
              block_q // sub_q, sub_q, k_ref.shape[0], sub_k, wide, scale,
              causal, lanes, hb)


def _packed_specs(nblk, lanes, s):
    """``spec(rows, part)``: the BlockSpec over a (B, S, n · lanes) array
    that gives grid step (i, j) — i a (batch row, column block c) pair —
    ``rows`` rows of column block ``part · nblk + c`` (part 0, 1, 2: q, k,
    v in the fused projection): block j of them, or all ``s``."""
    import jax.experimental.pallas as pl

    def spec(rows, part=0):
        return pl.BlockSpec((None, rows, lanes), lambda i, j: (
            i // nblk, j if rows < s else 0, part * nblk + i % nblk))

    return spec


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _packed_fwd_pallas(qkv, heads, scale, causal, sched, interpret):
    import jax.experimental.pallas as pl

    b, s, u3 = qkv.shape
    u = u3 // 3
    lanes, hb = packed_layout(u, heads)
    nblk = u // lanes
    block_q = sched.block_q
    spec = _packed_specs(nblk, lanes, s)
    kernel = functools.partial(_packed_fwd_kernel, hb=hb, sub_q=sched.sub_q,
                               sub_k=sched.sub_k,
                               wide=sched.block_k // sched.sub_k, scale=scale,
                               causal=causal)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * nblk, s // block_q),
        in_specs=[spec(block_q), spec(s, 1), spec(s, 2)],
        out_specs=[
            spec(block_q),
            pl.BlockSpec((None, hb, block_q), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, u), qkv.dtype),
            jax.ShapeDtypeStruct((b * nblk, hb, s), jnp.float32),
        ],
        interpret=interpret,
    )(qkv, qkv, qkv)
    return out, lse.reshape(b, heads, s)


def _packed_bwd_kernel(q_ref, do_ref, o_ref, lse_ref, glse_ref, k_ref, v_ref,
                       out_ref, dq_acc, dl, dq_out, dkv, sem, *, hb, nblk,
                       sub_q, sub_k, tall, scale, causal):
    """Grid (B · U // lanes, S // block_k): the block's heads one after the
    other through :func:`_bwd_core` (two chains in one body spill), each
    with k and v masked to its lanes — so its dQ lands in its own lanes of
    the block's ONE accumulator, the others' take exact zeros — and its dK
    and dV picked out of the products' 128 lanes as they are written.

    δ − dlse is made here, as rows, from the ``do`` and ``o`` blocks a step
    holds anyway (outside, XLA wrote the float32 product out and laid it
    out again to sum it position-minor: 32 MB a layer). The three gradients
    leave as column blocks of ONE (B, S, 3·U) array left in HBM — the
    projection's gradient as its matmuls read it; joined outside they were
    three passes over it a layer —: a step's dK and dV blocks, and behind a
    block's last step its dQ, are copied out of VMEM while the next step
    computes, and waited for where that step first writes the buffer."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i, j = pl.program_id(0), pl.program_id(1)
    nj = pl.num_programs(1)
    s, lanes = q_ref.shape
    block_k = k_ref.shape[0]
    d = lanes // hb
    row, col = i // nblk, i % nblk

    def copy_out(src, part, first, rows):
        """``src`` → rows [first, first + rows) of column block ``col`` of
        q's (part 0), k's (1) or v's (2) gradient."""
        return pltpu.make_async_copy(
            src, out_ref.at[row, pl.ds(first, rows),
                            pl.ds(pl.multiple_of((part * nblk + col) * lanes,
                                                 128), lanes)],
            sem.at[part])

    def dkv_out(part):
        return copy_out(dkv.at[part - 1], part, j * block_k, block_k)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

        def stats(r, _):
            rows = pl.ds(r * sub_q, sub_q)
            prod = do_ref[rows, :].astype(jnp.float32) \
                * o_ref[rows, :].astype(jnp.float32)
            dl[:, rows] = _as_rows(
                [jnp.sum(_only_head(prod, h, hb), axis=-1, keepdims=True)
                 for h in range(hb)]) - glse_ref[:, rows]
            return 0

        lax.fori_loop(0, s // sub_q, stats, 0)

    def add_dq(r, n, val):
        dq_acc[pl.ds(r * sub_q, n * sub_q), :] += val

    def sub_tile(t, _):
        cols = pl.ds(t * sub_k, sub_k)
        for h in range(hb):
            def loads(r, n, h=h):
                rows = pl.ds(r * sub_q, n * sub_q)
                return q_ref[rows, :], do_ref[rows, :], \
                    lse_ref[h:h + 1, rows], dl[h:h + 1, rows]

            grads = _bwd_core(
                j * block_k + t * sub_k,
                lambda h=h: (_only_head(k_ref[cols, :], h, hb),
                             _only_head(v_ref[cols, :], h, hb)),
                loads, add_dq, 0, s, sub_q, tall, scale, causal, sub_k, lanes)
            if h == 0:   # the step before may still be copying its blocks out
                @pl.when((t == 0) & ((i > 0) | (j > 0)))
                def _wait():
                    dkv_out(1).wait()
                    dkv_out(2).wait()
            for part, acc in enumerate(grads):
                if h:    # beside the heads already written
                    acc = jnp.where(_head_lanes(acc.shape, h, d), acc,
                                    dkv[part, cols, :].astype(jnp.float32))
                dkv[part, cols, :] = acc.astype(dkv.dtype)
        return 0

    if block_k == sub_k:
        sub_tile(0, 0)
    else:
        lax.fori_loop(0, block_k // sub_k, sub_tile, 0)
    dkv_out(1).start()
    dkv_out(2).start()

    @pl.when(j == nj - 1)
    def _write():
        @pl.when(i > 0)
        def _wait():
            copy_out(dq_out, 0, 0, s).wait()

        dq_out[...] = (dq_acc[...] * scale).astype(dq_out.dtype)
        copy_out(dq_out, 0, 0, s).start()

    @pl.when((i == pl.num_programs(0) - 1) & (j == nj - 1))
    def _last():
        dkv_out(1).wait()
        dkv_out(2).wait()
        copy_out(dq_out, 0, 0, s).wait()


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _packed_bwd_pallas(heads, scale, causal, sched, interpret, res, g):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    qkv, o, lse = res
    do, g_lse = g
    b, s, u = o.shape
    lanes, hb = packed_layout(u, heads)
    nblk = u // lanes
    block_k = sched.block_k
    spec = _packed_specs(nblk, lanes, s)
    stats = pl.BlockSpec((None, hb, s), lambda i, j: (i, 0, 0))
    kernel = functools.partial(_packed_bwd_kernel, hb=hb, nblk=nblk,
                               sub_q=sched.sub_q, sub_k=sched.sub_k,
                               tall=sched.block_q // sched.sub_q, scale=scale,
                               causal=causal)
    return pl.pallas_call(
        kernel,
        grid=(b * nblk, s // block_k),
        in_specs=[spec(s), spec(s), spec(s), stats, stats,  # q do o lse dlse
                  spec(block_k, 1), spec(block_k, 2)],          # k, v
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
        scratch_shapes=[pltpu.VMEM((s, lanes), jnp.float32),    # dQ's sums
                        pltpu.VMEM((hb, s), jnp.float32),       # δ − dlse
                        pltpu.VMEM((s, lanes), qkv.dtype),      # dQ, on its way
                        pltpu.VMEM((2, block_k, lanes), qkv.dtype),  # dK, dV
                        pltpu.SemaphoreType.DMA((3,))],
        # a step hands its copies on to the next: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(qkv, do.astype(qkv.dtype), o,
      lse.reshape(b * nblk, hb, s),
      g_lse.astype(jnp.float32).reshape(b * nblk, hb, s), qkv, qkv)


def _split_heads(x, heads, parts=1):
    """(B, S, parts · U) → ``parts`` arrays (B, H, S, D), the layout of the
    entries above."""
    b, s, _ = x.shape
    x = x.reshape(b, s, parts, heads, -1)
    return tuple(x[:, :, i].transpose(0, 2, 1, 3) for i in range(parts))


def _join_heads(x):
    """(B, H, S, D) → (B, S, U)."""
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _flash_packed(qkv, heads, scale, causal, sched, interpret):
    return _packed_fwd_pallas(qkv, heads, scale, causal, sched, interpret)


def _flash_packed_fwd(qkv, heads, scale, causal, sched, interpret):
    out, lse = _packed_fwd_pallas(qkv, heads, scale, causal, sched, interpret)
    return (out, lse), (qkv, out, lse)    # no second copy of q, k, v


def _flash_packed_bwd(heads, scale, causal, sched, interpret, res, g):
    impl = os.environ.get("MXNET_FLASH_BWD", "auto")
    if impl == "pallas" or (impl == "auto" and not interpret
                            and sched.sub_q % 128 == 0):   # as _flash_bwd
        return (_packed_bwd_pallas(heads, scale, causal, sched, interpret,
                                   res, g),)
    qkv, o, lse = res
    grads = _bwd_blocked(
        scale, causal, sched.sub_k,
        _split_heads(qkv, heads, 3) + (jnp.int32(0),)
        + _split_heads(o, heads) + (lse,),
        _split_heads(g[0], heads) + (g[1],))
    return (jnp.concatenate([_join_heads(x) for x in grads[:3]], axis=-1),)


_flash_packed.defvjp(_flash_packed_fwd, _flash_packed_bwd)


def flash_attention_packed(qkv, heads, causal=False, scale=None,
                           with_lse=False):
    """Flash attention over a fused projection's output as it lies. qkv
    (B, S, 3·U): a position's U columns of q, of k and of v, head h's in
    columns ``h·D … (h + 1)·D − 1`` of each → (B, S, U), head h's result in
    the same columns: what ``flash_attention`` gives for the three
    (B, H, S, D) transposes, with no transpose made (and ``(out, lse (B, H,
    S))`` ``with_lse``). :func:`packed_layout` says which (U, heads) it
    takes; S as ``flash_attention``. The schedule is the table's by (S, D);
    the gradient comes back as ONE (B, S, 3·U) array."""
    s, u3 = qkv.shape[1:]
    if u3 % 3 or packed_layout(u3 // 3, heads) is None:
        raise ValueError(
            f"flash_attention_packed cannot take {heads} heads in "
            f"{u3} / 3 columns: see packed_layout")
    d = u3 // 3 // heads
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(d)
    out, lse = _flash_packed(qkv, heads, scale, causal,
                             _resolve_schedule(s, d), _use_interpret())
    return (out, lse) if with_lse else out


def _packed_schedule(s, d, sched, batch, heads):
    """:func:`flash_schedule`'s account of the packed entry for ``heads``
    heads of ``d`` lanes over ``batch`` rows; None where it takes none."""
    layout = heads and packed_layout(heads * d, heads)
    if not layout:
        return None
    steps = batch * heads // layout[1]
    return {"layout": "packed", "block_lanes": layout[0],
            "heads_per_block": layout[1],
            "grid_fwd": (steps, s // sched.block_q),
            "grid_bwd": (steps, s // sched.block_k)}

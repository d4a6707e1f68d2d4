"""Ring attention — sequence/context parallelism over the mesh ``sp`` axis.

The reference has NO sequence parallelism (SURVEY.md §5.7); its max context
is bounded by one GPU's memory. This module removes that bound the TPU way:
Q stays resident per shard while K/V blocks rotate around the ring via
``lax.ppermute`` (neighbor exchanges ride the ICI torus). Each visiting K/V
block is attended with a **blockwise kernel returning (out, lse)** — the
same statistics our Pallas flash kernel (ops/flash_attention.py) produces —
and per-block results merge with the standard logsumexp combine. So the ring
is literally flash attention distributed over chips: per-block math can run
the Pallas kernel (long local blocks) or fused XLA einsums (short blocks),
and live memory is O(seq/n_shards) per chip either way.

Pattern follows the public ring-attention formulation (Liu et al.) and the
jax shard_map collective idiom.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.attention import fused_attention, plain_attention  # noqa: F401
from ..ops.flash_attention import flash_attention_with_lse

__all__ = ["ring_attention", "sequence_sharded_attention", "plain_attention"]

_NEG = -1e30  # matches ops/flash_attention._NEG_INF
# per-shard sequence length at which the Pallas kernel takes over block math
_FLASH_BLOCK_MIN_SEQ = 1024


def _block_attn_einsum(q, k_blk, v_blk, rel, s_loc, my_idx, src_idx, scale,
                       causal):
    """(out, lse) of one K/V block via fused XLA einsums. rel unused."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk).astype(jnp.float32) * scale
    if causal:
        rows = my_idx * s_loc + jnp.arange(s_loc)[:, None]
        cols = src_idx * s_loc + jnp.arange(s_loc)[None, :]
        s = jnp.where(rows >= cols, s, _NEG)
    m = jnp.max(s, axis=-1)                       # (B,H,sq)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(s <= _NEG / 2, 0.0, p)
    den = jnp.sum(p, axis=-1)
    safe = jnp.maximum(den, 1e-30)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v_blk) \
        .astype(jnp.float32) / safe[..., None]
    lse = jnp.where(den > 0, m + jnp.log(safe), _NEG)
    return out, lse


def _block_attn_flash(q, k_blk, v_blk, rel, s_loc, my_idx, src_idx, scale,
                      causal):
    """(out, lse) of one block via the Pallas flash kernel.

    The kernel's dynamic causal offset makes one call serve every visiting
    block: offset = (my - src)·s_loc is ≥ s_loc for fully-visible blocks,
    0 on the diagonal, and ≤ -s_loc for masked blocks (which then run zero
    K/V iterations inside the kernel).
    """
    offset = (my_idx - src_idx) * s_loc
    o, l = flash_attention_with_lse(q, k_blk, v_blk, causal=causal,
                                    scale=scale, offset=offset)
    return o.astype(jnp.float32), l


def _combine(o, lse, o_blk, lse_blk):
    """Merge two normalized (out, lse) pairs — flash's logsumexp algebra."""
    new = jnp.maximum(lse, lse_blk)
    w1 = jnp.where(lse <= _NEG / 2, 0.0, jnp.exp(lse - new))
    w2 = jnp.where(lse_blk <= _NEG / 2, 0.0, jnp.exp(lse_blk - new))
    den = w1 + w2
    safe = jnp.maximum(den, 1e-30)
    o_new = (o * w1[..., None] + o_blk * w2[..., None]) / safe[..., None]
    lse_new = jnp.where(den > 0, new + jnp.log(safe), _NEG)
    return o_new, lse_new


def _ring_body(q, k, v, axis_name, causal, scale, use_flash=None):
    """Per-shard ring loop. q,k,v are the LOCAL blocks (B, H, s_loc, D)."""
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    s_loc = q.shape[-2]
    import math

    scale = float(scale) if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if use_flash is None:
        use_flash = (s_loc >= _FLASH_BLOCK_MIN_SEQ and s_loc % 8 == 0)
    block_attn = _block_attn_flash if use_flash else _block_attn_einsum

    b, h, _, d = q.shape
    o = jnp.zeros((b, h, s_loc, d), jnp.float32)
    lse = jnp.full((b, h, s_loc), _NEG, jnp.float32)

    # Unrolled ring (n is the static sp mesh size): attend the visiting K/V
    # block, merge via the lse combine, rotate K/V one neighbor hop.
    # Unrolling lets XLA overlap each ppermute with the next block's compute
    # (and sidesteps scan-around-custom_vjp lowering limits).
    k_blk, v_blk, src_idx = k, v, my_idx
    perm = [(i, (i + 1) % n) for i in range(n)]
    for step in range(n):
        rel = jnp.where(src_idx < my_idx, 0,
                        jnp.where(src_idx == my_idx, 1, 2))
        o_blk, lse_blk = block_attn(q, k_blk, v_blk, rel, s_loc, my_idx,
                                    src_idx, scale, causal)
        o, lse = _combine(o, lse, o_blk, lse_blk)
        if step != n - 1:  # last block needs no rotation
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)
            src_idx = (src_idx - 1) % n
    return o.astype(q.dtype)


def ring_attention(q, k, v, axis_name, causal=False, scale=None,
                   use_flash=None):
    """Call INSIDE shard_map with q,k,v sequence-sharded over ``axis_name``."""
    return _ring_body(q, k, v, axis_name, causal, scale, use_flash=use_flash)


def sequence_sharded_attention(q, k, v, mesh: Mesh, axis_name: str = "sp",
                               causal: bool = False, scale=None,
                               batch_axis: str = "dp", head_axis: str = "tp",
                               use_flash=None):
    """Global-view attention sharded (B over dp, H over tp, S over sp).

    q,k,v: (B, H, S, D) global arrays (or tracers under an enclosing pjit).
    Returns same-shaped output. Under ``shard_map`` each device attends its
    own batch rows and heads: with an ``sp`` axis the K/V blocks rotate
    around the ring; without one every shard holds whole sequences and runs
    ``fused_attention`` on them. The wrap is what lets the Pallas kernel
    run on a mesh at all — XLA cannot partition a Mosaic call by itself.
    A batch or head count the axis does not divide stays replicated.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def axis_for(name, dim):
        n = sizes.get(name, 1)
        return name if n > 1 and dim % n == 0 else None

    b_ax = axis_for(batch_axis, q.shape[0])
    h_ax = axis_for(head_axis, q.shape[1])
    if sizes.get(axis_name, 1) == 1:
        body = partial(fused_attention, causal=causal, scale=scale)
        if b_ax is None and h_ax is None:
            return body(q, k, v)
        spec = P(b_ax, h_ax, None, None)
    else:
        body = partial(_ring_body, axis_name=axis_name, causal=causal,
                       scale=scale, use_flash=use_flash)
        spec = P(b_ax, h_ax, axis_name, None)
    # check_vma=False: replication tracking cannot see through pallas_call
    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    return fn(q, k, v)

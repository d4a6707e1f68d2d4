"""Attention dispatch: plain XLA vs the Pallas flash kernel.

Policy (measured on v5e, TransformerLM bf16 train step, end-to-end): flash
wins 1.49x at seq 2048 (76.8 vs 51.7 model TFLOPS) *and* keeps memory
O(S·D) — so:
- short sequences (< _FLASH_MIN_SEQ): XLA's fused softmax-attention; the
  S×S scores fit easily and kernel launch granularity doesn't pay off.
- sequences ≥ _FLASH_MIN_SEQ: the Pallas flash kernel (bf16 MXU dots with
  f32 accumulation — precision pinned DEFAULT, see flash_attention.py).
- explicit masks: plain (the kernel handles causal only).

``MXNET_ATTENTION_IMPL`` ∈ {auto, plain, flash} overrides; ``flash`` where
the kernel cannot run (an explicit mask, S_q != S_k, S not a multiple of
8) raises instead of quietly running plain attention.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from .flash_attention import (flash_attention, flash_attention_with_lse,
                              packed_layout)

__all__ = ["fused_attention", "plain_attention", "attention_impl"]

_FLASH_MIN_SEQ = 1024


def plain_attention(q, k, v, mask=None, causal=False, scale=None):
    """Single-device reference attention. q,k,v: (B, H, S, D)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(q.dtype)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        s_q, s_k = scores.shape[-2], scores.shape[-1]
        cm = jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
        scores = jnp.where(cm, scores, -jnp.inf)
    if mask is not None:
        scores = jnp.where(mask, scores, -jnp.inf)
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


def attention_impl(q_shape, k_shape, has_mask=False, impl=None,
                   fused_qkv=False) -> str:
    """``"flash"``, ``"flash_packed"`` or ``"plain"`` — the dispatch rule,
    from shapes alone so callers that must know before they trace (the mesh
    wrapper in parallel/ring_attention.py) ask the same question
    ``fused_attention`` does. ``flash_packed`` only to a caller that holds
    the ``fused_qkv`` projection (B, S, 3·H·D) the shapes were split from,
    on one device, where the flash kernels read it as it lies
    (``flash_attention.packed_layout``: 128 lanes hold whole heads or a
    head whole 128-lane tiles); ``flash`` is the (B, H, S, D) entry.
    ``impl``/``MXNET_ATTENTION_IMPL`` = ``flash`` (either entry) where the
    kernel cannot run raises."""
    impl = impl or os.environ.get("MXNET_ATTENTION_IMPL", "auto")
    # block specs cover the full head dim, so only S needs tiling-friendly
    # factors (block sizes are shrunk to divide S; 8 is the sublane minimum)
    s_q, s_k = q_shape[-2], k_shape[-2]
    can_flash = (not has_mask and len(q_shape) == 4 and s_q == s_k
                 and s_q % 8 == 0)
    if impl == "flash" and not can_flash:
        raise ValueError(
            "impl='flash' cannot run here: the kernel takes no explicit "
            "mask and needs 4-D q/k with equal sequence lengths that "
            f"are a multiple of 8 (q {tuple(q_shape)}, k "
            f"{tuple(k_shape)}, mask={'given' if has_mask else 'none'})")
    if impl != "flash" and (impl == "plain" or not can_flash
                            or s_q < _FLASH_MIN_SEQ):
        return "plain"
    h, d = q_shape[1], q_shape[3]
    return ("flash_packed" if fused_qkv and q_shape == k_shape
            and packed_layout(h * d, h) else "flash")


def fused_attention(q, k, v, mask=None, causal=False, scale=None, impl=None):
    """The attention entry point for the model zoo (MultiHeadAttention)."""
    if attention_impl(q.shape, k.shape, mask is not None, impl) == "flash":
        return flash_attention(q, k, v, causal=causal, scale=scale)
    return plain_attention(q, k, v, mask=mask, causal=causal, scale=scale)

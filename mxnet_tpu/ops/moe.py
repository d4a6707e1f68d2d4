"""Routed and shared experts: the layer a chip runs for ITS experts.

An expert layer here is told which experts it holds (``first``, and as many
as its weights have). It routes every token over ALL the experts the router
scores, and computes the part of the result its own experts give: what the
experts held elsewhere would add is not computed, not exchanged and not
stood in for (on one chip the layer runs without its ``all_to_all``).

- :func:`route` — the router, in float32 whatever the activations are (an
  expert choice flips on a near tie, so the choice is made in the precision
  of the reference): ``s = sigmoid(h.W_r)``; chosen = top-k of ``s + b`` (the
  bias chooses, never weighs); ``g = scale * s_chosen / sum(s_chosen)``.
  :func:`route_softmax` is the other family's: ``p = softmax(h.W_r)`` over all
  the experts, the k largest, ``g = p_chosen / sum(p_chosen)``; no bias.
- :func:`held_experts` — dropless: the (token, choice) assignments that fall
  on held experts are sorted by expert (the others, and the tokens that are
  not live, sort behind them), the sorted rows go through a grouped product
  per matrix (``jax.lax.ragged_dot``, which XLA:TPU lowers to a Mosaic
  grouped matmul driven by the group sizes: rows behind the last group cost
  no tile), ``ROW_BLOCKS`` blocks of rows one after the other, and each token
  sums its own rows back, weighted. The static row bound is ``tokens x k``;
  no capacity factor, nothing dropped.
- :func:`expert_layer` — routed part + shared expert, and the counters
  (``COUNTERS``) that ``serve/decode.py`` hangs on its spans.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from . import flash_attention

__all__ = ["route", "route_softmax", "held_experts", "expert_layer",
           "gated_mlp", "COUNTERS"]

# per call: live (token, choice) pairs; those on held experts; most tokens on
# one held expert; held experts with at least one token; held pairs that no
# row was computed for (must be 0)
COUNTERS = ("assignments", "held", "load_max", "touched", "dropped")
ROW_BLOCKS = 4       # blocks the sorted rows are multiplied in (held_experts)
TOKEN_CHUNK = 4096   # most tokens routed and multiplied at a time: bounds the
#                      sorted rows (tokens x k of them) and their products


def _mm(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def gated_mlp(h, gate_w, up_w, down_w):
    """``(silu(h.W_g) * h.W_u).W_d``; float32 accumulation, result f32."""
    a = (jax.nn.silu(_mm(h, gate_w)) * _mm(h, up_w)).astype(h.dtype)
    return _mm(a, down_w)


def _grouped(rows, w, sizes):
    """rows (M, K) sorted by group, w (G, K, N), sizes (G,): row i of group g
    times ``w[g]``, float32. Off the TPU (where the Pallas kernels are
    interpreted too) bfloat16 operands go in as float32, the same products:
    XLA:CPU rewrites a ragged dot into a dense one that its runtime cannot
    run in bfloat16."""
    if flash_attention._use_interpret() and rows.dtype == jnp.bfloat16:
        rows, w = rows.astype(jnp.float32), w.astype(jnp.float32)
    # the package's default matmul precision is "highest": with bfloat16
    # operands the grouped kernel (Mosaic) refuses that, as the flash kernels do
    return lax.ragged_dot(rows, w, sizes, preferred_element_type=jnp.float32,
                          precision=flash_attention._dot_prec(rows.dtype))


def route(h, router_w, router_b, k: int, scale: float):
    """h (T, D) -> (chosen (T, k) int32 global expert ids, gates (T, k)
    float32)."""
    s = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32),
                               router_w.astype(jnp.float32),
                               precision=lax.Precision.HIGHEST))
    _, chosen = lax.top_k(s + router_b.astype(jnp.float32), k)
    picked = jnp.take_along_axis(s, chosen, axis=1)
    return chosen, scale * picked / jnp.sum(picked, axis=-1, keepdims=True)


def route_softmax(h, router_w, k: int):
    """h (T, D) -> (chosen (T, k), gates (T, k) float32): the k largest of
    ``softmax(h.W_r)`` over every expert, renormalised to sum 1."""
    p = jax.nn.softmax(jnp.dot(h.astype(jnp.float32),
                               router_w.astype(jnp.float32),
                               precision=lax.Precision.HIGHEST), axis=-1)
    picked, chosen = lax.top_k(p, k)
    return chosen, picked / jnp.sum(picked, axis=-1, keepdims=True)


def held_experts(h, chosen, gates, live, gate_w, up_w, down_w, first: int,
                 held: int, offset=0):
    """The held experts' part of the routed sum. h (T, D); chosen, gates
    (T, k); live (T,) bool. The weights are (G, D, F), (G, D, F), (G, F, D)
    with the ``held`` experts ``first .. first + held - 1`` at groups
    ``offset .. offset + held - 1`` (every expert layer's experts can lie in
    one array, ``offset`` = layer x held, possibly traced: the grouped kernel
    takes the array whole and the other layers' groups are empty, where a
    slice of it would be copied out for the kernel). Returns (y (T, D)
    float32, counters (len(COUNTERS),) int32)."""
    t, k = chosen.shape
    local = chosen - first
    on_held = (local >= 0) & (local < held) & live[:, None]
    group = jnp.where(on_held, local, held).reshape(-1)   # not held: last
    order = jnp.argsort(group, stable=True)               # sorted by expert
    sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
    rows = h[order // k]                                  # (T*k, D)

    # The sorted rows go through the grouped products ROW_BLOCKS blocks at
    # a time: the kernel's row tile follows the rows it is handed (up to
    # 512), and at a decode step's few rows an expert one 256-row tile a
    # touched expert costs more than reading its weights. A block behind
    # the last held row has empty groups and costs no tile.
    n_blocks = ROW_BLOCKS if (t * k) % ROW_BLOCKS == 0 else 1
    c = t * k // n_blocks
    ends = jnp.cumsum(sizes)

    def block(xs):
        j, rows_j = xs
        lo = j * c
        inside = (jnp.clip(ends, lo, lo + c)
                  - jnp.clip(ends - sizes, lo, lo + c))   # of each group
        groups = lax.dynamic_update_slice(
            jnp.zeros((gate_w.shape[0],), jnp.int32), inside, (offset,))
        a = _grouped(rows_j, gate_w, groups)
        b = _grouped(rows_j, up_w, groups)
        return _grouped((jax.nn.silu(a) * b).astype(h.dtype), down_w, groups)

    out = lax.map(block, (jnp.arange(n_blocks),
                          rows.reshape(n_blocks, c, -1))).reshape(t * k, -1)
    n_held = jnp.sum(sizes)
    weight = jnp.where(on_held, gates, 0.0).reshape(-1)[order]
    out = jnp.where((jnp.arange(t * k) < n_held)[:, None],
                    out * weight[:, None], 0.0)
    # each token sums its own k rows: the inverse permutation, a gather (the
    # same sum as a scatter-add, in one fixed order)
    y = out[jnp.argsort(order)].reshape(t, k, -1).sum(axis=1)
    counters = jnp.stack([
        jnp.sum(live) * k, n_held, jnp.max(sizes), jnp.sum(sizes > 0),
        jnp.sum(on_held) - jnp.minimum(n_held, t * k)])
    return y, counters.astype(jnp.int32)


def expert_layer(h, p, experts, live, *, first: int, held: int, k: int,
                 scale: float = 1.0, offset=0):
    """One expert layer over h (T, D): the held experts' routed part plus
    the shared expert. ``p``: ``router_w`` (D, E_all), ``router_b`` (E_all,),
    ``shared_{gate,up,down}_w``; ``experts``: ``gate_w``, ``up_w``,
    ``down_w`` as :func:`held_experts` takes them, with ``offset``. A layer
    with no ``router_b`` routes by :func:`route_softmax` (``scale`` unused);
    one with ``shared_s_w`` (D,) weighs its shared expert by
    ``sigmoid(h.w_s)``. Tokens go at most ``TOKEN_CHUNK`` at a time. Returns
    (y (T, D) float32, counters)."""
    def chunk(args):
        hc, lc = args
        if "router_b" in p:
            chosen, gates = route(hc, p["router_w"], p["router_b"], k, scale)
        else:
            chosen, gates = route_softmax(hc, p["router_w"], k)
        y, c = held_experts(hc, chosen, gates, lc, experts["gate_w"],
                            experts["up_w"], experts["down_w"], first, held,
                            offset)
        shared = gated_mlp(hc, p["shared_gate_w"], p["shared_up_w"],
                           p["shared_down_w"])
        if "shared_s_w" in p:
            shared = shared * jax.nn.sigmoid(jnp.dot(
                hc.astype(jnp.float32), p["shared_s_w"].astype(jnp.float32),
                precision=lax.Precision.HIGHEST))[:, None]
        return y + shared, c

    t = h.shape[0]
    n = -(-t // TOKEN_CHUNK)          # the fewest equal chunks that fit
    if n == 1 or t % n:
        return chunk((h, live))
    y, c = lax.map(chunk, (h.reshape(n, t // n, -1), live.reshape(n, t // n)))
    return y.reshape(t, -1), merge_counters(c)


def merge_counters(c):
    """Counters of several calls (n, len(COUNTERS)) as one: sums, and the
    largest ``load_max``."""
    i = COUNTERS.index("load_max")
    return jnp.sum(c, axis=0).at[i].set(jnp.max(c[:, i]))

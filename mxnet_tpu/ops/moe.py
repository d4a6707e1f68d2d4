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
  :func:`route_softmax_scaled` is a third's: the k largest of ``p + b``,
  ``g = scale * p_chosen``, NOT renormalised.
  :func:`route_grouped` is :func:`route` with the choice limited to a
  token's best groups of experts (a group's score: its two largest ``s + b``).
- :func:`held_experts` — dropless, and its work follows the rows the held
  experts have, not the static bound ``tokens x k``: the (token, choice)
  pairs are sorted by expert (those on experts held elsewhere, and the
  tokens that are not live, sort behind the held rows) and laid out so that
  **every held expert's rows start on a slot of their own**, in slots of
  :func:`row_slot` rows — a size that fits the rows an expert has, and
  over a prompt a whole row tile of the kernel (:func:`row_tile`) —; a
  block of :func:`row_block` such rows at a time is gathered and goes
  through a grouped product per matrix (``jax.lax.ragged_dot``, which
  XLA:TPU lowers to a Mosaic grouped matmul that runs once for every
  (expert, row tile) pair that meet: with a tile a slot, once a tile), as
  many blocks as hold a slot with a held row — a trip count read from the
  data —, each block's float32 rows written once, a row as one contiguous
  piece, into a buffer nobody clears; and ONE pass back (``_rows_back``, a
  Pallas kernel): a held row is fetched once, on its way into its token's
  weighted sum, and a pair no held expert has is not fetched at all.
  Nothing is dropped: when every pair falls on a held expert every block
  runs.
- :func:`expert_layer` — routed part + shared expert (where the layer has
  one), and the counters (``COUNTERS``) that ``serve/decode.py`` hangs on its
  spans. **Identity experts** (``zero_experts``): the router's last ids are
  experts that compute nothing, ``E_e(h) = h``; a pair chosen there adds
  ``g . h`` on every chip alike, as a shared expert is on every chip, runs no
  grouped row (its id lies outside the held ones, so it sorts behind them)
  and is counted in one more counter, ``zero`` (``ZERO_COUNTERS``); a layer
  routed by groups counts the tokens that kept a group with a held expert,
  ``group_hit`` (``GROUP_COUNTERS``).

**Two forms of expert**, told apart by the leaves a model hands over: with a
gate matrix three products, ``(silu(h.W_g) * h.W_u).W_d``
(:func:`gated_mlp`); without one two, ``relu(h.W_u)^2.W_d``
(:func:`relu2_mlp`) — for the held experts' grouped products and for the
shared expert alike.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import flash_attention

__all__ = ["route", "route_softmax", "route_softmax_scaled", "route_grouped",
           "held_experts", "expert_layer", "gated_mlp", "relu2_mlp",
           "row_slot", "row_tile", "row_block", "layer_row_tile", "COUNTERS",
           "ZERO_COUNTERS", "GROUP_COUNTERS"]

# per call: live (token, choice) pairs; those on held experts; most tokens on
# one held expert; held experts with at least one token; held pairs that no
# row was computed for (must be 0); rows the grouped products were handed:
# the slots that hold a held row x the slot (``rows_run / held`` is what
# starting every expert's rows on a slot of their own costs in padding)
COUNTERS = ("assignments", "held", "load_max", "touched", "dropped",
            "rows_run")
# of a layer with identity experts: live pairs that fell on them, last
ZERO_COUNTERS = COUNTERS + ("zero",)
# of a layer whose router keeps some groups a token: live tokens that kept a
# group with a held expert in it (the others hold nothing here), last
GROUP_COUNTERS = COUNTERS + ("group_hit",)
TOKEN_CHUNK = 4096   # most tokens routed and multiplied at a time: bounds the
#                      sorted rows (tokens x k of them) and their products


def _mm(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def gated_mlp(h, gate_w, up_w, down_w):
    """``(silu(h.W_g) * h.W_u).W_d``; float32 accumulation, result f32."""
    a = (jax.nn.silu(_mm(h, gate_w)) * _mm(h, up_w)).astype(h.dtype)
    return _mm(a, down_w)


def relu2_mlp(h, up_w, down_w):
    """``relu(h.W_u)^2.W_d``; float32 accumulation, result f32."""
    return _mm(jnp.square(jax.nn.relu(_mm(h, up_w))).astype(h.dtype), down_w)


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


def _router_logits(h, router_w):
    """``h.W_r`` in float32 at full precision, whatever h is."""
    return jnp.dot(h.astype(jnp.float32), router_w.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)


def route(h, router_w, router_b, k: int, scale: float):
    """h (T, D) -> (chosen (T, k) int32 global expert ids, gates (T, k)
    float32)."""
    s = jax.nn.sigmoid(_router_logits(h, router_w))
    _, chosen = lax.top_k(s + router_b.astype(jnp.float32), k)
    picked = jnp.take_along_axis(s, chosen, axis=1)
    return chosen, scale * picked / jnp.sum(picked, axis=-1, keepdims=True)


def route_softmax(h, router_w, k: int):
    """h (T, D) -> (chosen (T, k), gates (T, k) float32): the k largest of
    ``softmax(h.W_r)`` over every expert, renormalised to sum 1."""
    p = jax.nn.softmax(_router_logits(h, router_w), axis=-1)
    picked, chosen = lax.top_k(p, k)
    return chosen, picked / jnp.sum(picked, axis=-1, keepdims=True)


def route_softmax_scaled(h, router_w, router_b, k: int, scale: float):
    """h (T, D) -> (chosen (T, k), gates (T, k) float32): ``p =
    softmax(h.W_r)`` over every expert the router scores, identity experts
    among them; the k largest of ``p + b`` (the bias chooses, never weighs);
    ``g = scale * p_chosen``, not renormalised."""
    p = jax.nn.softmax(_router_logits(h, router_w), axis=-1)
    _, chosen = lax.top_k(p + router_b.astype(jnp.float32), k)
    return chosen, scale * jnp.take_along_axis(p, chosen, axis=1)


def route_grouped(h, router_w, router_b, k: int, scale: float, groups: int,
                  groups_kept: int):
    """:func:`route` with the choice limited to groups: the experts lie in
    ``groups`` equal groups by id, a group's score is the sum of its two
    largest choosing scores ``s + b``, a token keeps its ``groups_kept`` best
    groups and chooses the ``k`` largest ``s + b`` among their experts (ties
    to the lower id, of groups and of experts); gates as :func:`route`'s.
    h (T, D) -> (chosen (T, k) int32, gates (T, k) float32, kept (T, groups)
    bool)."""
    s = jax.nn.sigmoid(_router_logits(h, router_w))
    choose = s + router_b.astype(jnp.float32)
    t, e = choose.shape
    two, _ = lax.top_k(choose.reshape(t, groups, e // groups), 2)
    _, best = lax.top_k(jnp.sum(two, axis=-1), groups_kept)
    kept = jnp.any(best[:, :, None] == jnp.arange(groups), axis=1)
    _, chosen = lax.top_k(
        jnp.where(jnp.repeat(kept, e // groups, axis=1), choose, -jnp.inf), k)
    picked = jnp.take_along_axis(s, chosen, axis=1)
    return (chosen, scale * picked / jnp.sum(picked, axis=-1, keepdims=True),
            kept)


def row_slot(pairs: int, scored: int) -> int:
    """Rows of a slot of the sorted rows — every held expert's rows start on
    a slot of their own — from what the shapes say: the smallest power of
    two, 512 at most, that holds the rows an expert has, taken as their mean
    (``pairs`` (token, choice) pairs over the ``scored`` experts of the
    router) + 1.5 of its root (what a Poisson count spreads by: 224 rows an
    expert fit 256, 40 fit 64, a decode step's 0.6 fit 2). A slot much
    larger than an expert's rows multiplies, gathers and writes rows nobody
    asked for; one smaller gives an expert several, and where a slot is a
    whole tile of the kernel (:func:`row_tile`) its weights are read again
    for each."""
    mean = pairs / scored
    slot = 1
    while slot < 512 and slot < mean + 1.5 * mean ** 0.5:
        slot *= 2
    return slot


def row_tile(pairs: int, scored: int, dtype) -> int:
    """Rows of a tile of the grouped products: the slot, and no less than
    the operands' sublane tile (16 rows of bfloat16, 8 of float32). The
    kernel computes a whole tile for every (expert, tile) pair that meet: a
    512-row tile at 40 rows an expert computed 13.8 times the rows. Over a
    prompt a slot is a tile, and a tile meets ONE expert; in a decode step
    several experts' slots share a tile of 16, each expert in one tile."""
    return max(row_slot(pairs, scored), 32 // jnp.dtype(dtype).itemsize)


def row_block(tm: int, tiles: int) -> int:
    """Rows of a block of the tiled rows: ``tm`` x an ODD number of tiles,
    2,048 rows at most and ``tiles`` tiles at most. XLA:TPU takes as the
    grouped kernel's row tile the largest power of two, 512 at most, that
    divides the rows the product is handed: an odd number of tiles makes
    that ``tm`` (four tiles of 512, as before PR 43, made it 512 whatever
    the rows an expert had)."""
    n = max(1, min(2048 // tm, tiles))
    return tm * (n - 1 + n % 2)


def _chunk_tokens(tokens: int) -> int:
    """Tokens :func:`expert_layer` routes and multiplies at a time: those of
    the fewest equal chunks of ``TOKEN_CHUNK`` at most (all of them where
    such chunks do not divide them)."""
    n = -(-tokens // TOKEN_CHUNK)
    return tokens if tokens % n else tokens // n


def layer_row_tile(tokens: int, k: int, scored: int, dtype) -> int:
    """The row tile :func:`expert_layer` runs a call of ``tokens`` tokens
    with: what ``DecodeEngine.stats()["moe_row_tile"]`` shows."""
    return row_tile(_chunk_tokens(tokens) * k, scored, dtype)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _unwritten(shape, after, interpret):
    """A float32 array nobody has written: the block loop's buffer. Rows of
    it are written a block at a time and a row that was not is never read,
    so it need not be cleared (clearing it is a pass over ``tokens x k``
    rows, most of which no held expert has). ``after`` is any value of the
    call, so that the array is made where it is used. Like ``_rows_back``
    under a jit of its own: a program's layers share ONE traced and lowered
    kernel."""
    import jax.experimental.pallas as pl

    return pl.pallas_call(
        lambda after_ref, rows_ref: None,
        out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        interpret=interpret, name="moe_rows")(after)


@functools.partial(jax.jit, static_argnums=(3,))
def _rows_back(out, where, weight, interpret):
    """``y[t] = sum_j weight[t, j] * out[where[t, j]]`` over the pairs with
    ``where >= 0``, in the order of j, float32. out (N, S, L) is a row of D
    values as S whole (8, 128) tiles' worth — ONE contiguous piece of the
    array, where a row of an (N, D) array is D / 128 pieces of 512 bytes —;
    where, weight (T, k). Returns (T, S, L).

    The kernel takes 32 tokens a grid step: it starts one copy a live pair,
    out of ``out`` where it lies into a (k, 32) grid of rows in VMEM, waits
    for as many, and sums each token's k rows times their weights. A pair
    that no held expert has is not fetched (its slot keeps an older row, or
    the zeros it started with, and is not summed)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, k = where.shape
    _, s, lanes = out.shape
    tt = min(32, t)
    pad = -t % tt
    where = jnp.pad(where, ((0, pad), (0, 0)), constant_values=-1)
    weight = jnp.pad(weight, ((0, pad), (0, 0)))

    def kernel(where_ref, weight_ref, out_ref, y_ref, buf, sem):
        first = pl.program_id(0) * tt

        @pl.when(first == 0)
        def _clear():
            buf[...] = jnp.zeros_like(buf)

        def start(tok, n):
            for j in range(k):
                row = where_ref[(first + tok) * k + j]

                @pl.when(row >= 0)
                def _copy(j=j, row=row):
                    pltpu.make_async_copy(out_ref.at[row], buf.at[j, tok],
                                          sem.at[0]).start()
                n = n + (row >= 0).astype(jnp.int32)
            return n

        def wait(_, carry):
            # every copy is one row: any descriptor of that size waits for one
            pltpu.make_async_copy(out_ref.at[0], buf.at[0, 0],
                                  sem.at[0]).wait()
            return carry

        lax.fori_loop(0, lax.fori_loop(0, tt, start, jnp.int32(0)), wait, 0)

        def token(tok, carry):
            acc = jnp.zeros((s, lanes), jnp.float32)
            for j in range(k):
                w = weight_ref[(first + tok) * k + j]
                acc = acc + jnp.where(w != 0, buf[j, tok], 0.0) * w
            y_ref[tok] = acc
            return carry

        lax.fori_loop(0, tt, token, 0)

    y = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=((t + pad) // tt,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tt, s, lanes), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((k, tt, s, lanes), jnp.float32),
                            pltpu.SemaphoreType.DMA((1,))]),
        out_shape=jax.ShapeDtypeStruct((t + pad, s, lanes), jnp.float32),
        # the buffer is cleared once, at the first grid step
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="moe_rows_back",
    )(where.reshape(-1), weight.reshape(-1), out)
    return y[:t]


def held_experts(h, chosen, gates, live, gate_w, up_w, down_w, first: int,
                 held: int, offset=0, scored: int = None):
    """The held experts' part of the routed sum. h (T, D); chosen, gates
    (T, k); live (T,) bool. The weights are (G, D, F), (G, D, F), (G, F, D)
    (``gate_w`` None: experts of two products, ``relu(h.W_u)^2.W_d``;
    D and F may be STORED wider than ``h`` and the mathematics, zeros
    behind: XLA:TPU's grouped kernel takes the largest of 512, 256, 128
    that divides each, and at 128 it runs at a seventh of the memory's rate)
    with the ``held`` experts ``first .. first + held - 1`` at groups
    ``offset .. offset + held - 1`` (every expert layer's experts can lie in
    one array, ``offset`` = layer x held, possibly traced: the grouped kernel
    takes the array whole and the other layers' groups are empty, where a
    slice of it would be copied out for the kernel). ``scored``: the experts
    the router chose among (default: the held ones are all there are); with
    ``T x k`` it says how many rows an expert has, which slot and row tile
    follow (:func:`row_slot`, :func:`row_tile`). Returns (y (T, D) float32, counters
    (len(COUNTERS),) int32)."""
    t, k = chosen.shape
    d = h.shape[1]
    wide = up_w.shape[1]    # the hidden size the experts are STORED at
    if wide != d:           # zeros behind the model's: the rows follow suit
        h = jnp.pad(h, ((0, 0), (0, wide - d)))
    local = chosen - first
    on_held = (local >= 0) & (local < held) & live[:, None]
    group = jnp.where(on_held, local, held).reshape(-1)   # not held: last
    # sorted by expert: the held experts' rows first, in the experts' order
    in_order, order = lax.sort_key_val(
        group, jnp.arange(t * k, dtype=jnp.int32))
    ends = jnp.searchsorted(in_order, jnp.arange(held, dtype=group.dtype),
                            side="right").astype(jnp.int32)
    sizes = jnp.diff(ends, prepend=0)
    starts = ends - sizes

    # Every held expert's rows start on a slot of their own: expert e takes
    # ceil(sizes_e / slot) slots of slot rows, and what its last slot has
    # over is computed and never read. Over a prompt a slot is a tile of the
    # kernel: a tile then meets ONE expert, so the grouped kernel runs once
    # a tile and an expert's weights are read once a tile it has.
    scored = scored or held
    slot, tm = row_slot(t * k, scored), row_tile(t * k, scored, h.dtype)
    slots = -(-sizes // slot)
    slot_ends = jnp.cumsum(slots)
    slot_starts = slot_ends - slots
    n_slots = slot_ends[-1]
    # The work follows the rows the held experts have. A block of c slotted
    # rows at a time is gathered and goes through the grouped products, as
    # many blocks as hold a slot with a held row: the trip count is read
    # from the data, and a block behind the last such slot is not run. A
    # block is about the slots the held experts' share of the pairs fills.
    share = -(-t * k * held // scored)
    c = row_block(tm, -(-slot * (min(held, share) + -(-share // slot)) // tm))
    per = c // slot
    # every pair on a held expert, and every expert a row into a slot more
    most = -(-(-(-t * k // slot) + min(held, t * k)) // per)  # blocks at most
    run = -(-n_slots // per)
    # slot i is slot sorted rows from its expert's first + slot x (the slots
    # the expert has before it); a slot behind the last one gathers any rows
    i = jnp.arange(most * per, dtype=jnp.int32)
    e = jnp.minimum(jnp.searchsorted(slot_ends, i, side="right",
                                     method="compare_all"), held - 1)
    base = starts[e] + (i - slot_starts[e]) * slot
    token = jnp.take(order // k,
                     (base[:, None] + jnp.arange(slot)).reshape(-1),
                     mode="clip")
    lanes = 128 if wide % 128 == 0 else wide
    shape = (wide // lanes, lanes)   # a row as whole (8, 128) tiles' worth

    def block(j, out):
        lo = j * c
        rows = h[lax.dynamic_slice(token, (lo,), (c,))]
        inside = (jnp.clip(slot_ends * slot, lo, lo + c)
                  - jnp.clip(slot_starts * slot, lo, lo + c))  # of each group
        groups = lax.dynamic_update_slice(
            jnp.zeros((up_w.shape[0],), jnp.int32), inside, (offset,))
        if gate_w is None:
            a = jnp.square(jax.nn.relu(_grouped(rows, up_w, groups)))
        else:
            a = (jax.nn.silu(_grouped(rows, gate_w, groups))
                 * _grouped(rows, up_w, groups))
        o = _grouped(a.astype(h.dtype), down_w, groups)
        return lax.dynamic_update_slice(out, o.reshape((c,) + shape),
                                        (lo, 0, 0))

    out = lax.fori_loop(0, run, block,
                        _unwritten((most * c,) + shape, ends,
                                   flash_attention._use_interpret()))
    # One pass back: a held row is read once, on its way into its token's
    # sum; a pair no held expert has is not read at all. A pair's row lies
    # at its place in the sorted order + what the slots before its expert
    # have over.
    over = slot_starts * slot - starts                    # of each expert
    shift = jnp.sum(jnp.where(group[:, None] == jnp.arange(held), over, 0),
                    axis=1)                               # of each pair
    where = jnp.where(on_held, (jnp.argsort(order) + shift).reshape(t, k), -1)
    weight = jnp.where(on_held, gates, 0.0).astype(jnp.float32)
    y = _rows_back(out, where, weight, flash_attention._use_interpret())
    n_held = ends[-1]
    counters = jnp.stack([
        jnp.sum(live) * k, n_held, jnp.max(sizes), jnp.sum(sizes > 0),
        jnp.sum(on_held) - jnp.minimum(n_held, t * k), n_slots * slot])
    return y.reshape(t, wide)[:, :d], counters.astype(jnp.int32)


def expert_layer(h, p, experts, live, *, first: int, held: int, k: int,
                 scale: float = 1.0, offset=0, zero_experts: int = 0,
                 groups: int = 0, groups_kept: int = 0):
    """One expert layer over h (T, D): the held experts' routed part plus
    the shared expert. ``p``: ``router_w`` (D, E_all), ``router_b`` (E_all,),
    ``shared_{gate,up,down}_w``; ``experts``: ``gate_w``, ``up_w``,
    ``down_w`` as :func:`held_experts` takes them, with ``offset``; a layer
    with no ``gate_w`` / ``shared_gate_w`` has experts of two products
    (module docstring), one with no ``shared_up_w`` no shared expert. A layer
    with no ``router_b`` routes by :func:`route_softmax` (``scale`` unused);
    one with ``shared_s_w`` (D,) weighs its shared expert by
    ``sigmoid(h.w_s)``. ``zero_experts`` > 0: the router's last that many ids
    are identity experts and it routes by :func:`route_softmax_scaled`; their
    term ``(sum of the gates chosen there) . h`` is added in float32 and the
    counters are ``ZERO_COUNTERS``. ``groups`` > 0: it routes by
    :func:`route_grouped` (``groups_kept`` of ``groups`` groups a token) and
    the counters are ``GROUP_COUNTERS``: the last counts the live tokens whose
    kept groups hold one of the experts ``first .. first + held - 1``. Tokens
    go at most ``TOKEN_CHUNK`` at a time. Returns (y (T, D) float32,
    counters)."""
    scored = p["router_w"].shape[1]

    def chunk(args):
        hc, lc = args
        if zero_experts:
            chosen, gates = route_softmax_scaled(hc, p["router_w"],
                                                 p["router_b"], k, scale)
        elif groups:
            chosen, gates, kept = route_grouped(
                hc, p["router_w"], p["router_b"], k, scale, groups,
                groups_kept)
        elif "router_b" in p:
            chosen, gates = route(hc, p["router_w"], p["router_b"], k, scale)
        else:
            chosen, gates = route_softmax(hc, p["router_w"], k)
        y, c = held_experts(hc, chosen, gates, lc, experts.get("gate_w"),
                            experts["up_w"], experts["down_w"], first, held,
                            offset, scored)
        if zero_experts:
            on_zero = (chosen >= scored - zero_experts) & lc[:, None]
            y = y + (jnp.sum(jnp.where(on_zero, gates, 0.0), axis=-1,
                             keepdims=True) * hc.astype(jnp.float32))
            c = jnp.concatenate([c, jnp.sum(on_zero, dtype=jnp.int32)[None]])
        if groups:
            per = scored // groups
            here = kept[:, first // per:(first + held - 1) // per + 1]
            c = jnp.concatenate([c, jnp.sum(jnp.any(here, axis=1) & lc,
                                            dtype=jnp.int32)[None]])
        if "shared_up_w" not in p:
            return y, c
        if "shared_gate_w" in p:
            shared = gated_mlp(hc, p["shared_gate_w"], p["shared_up_w"],
                               p["shared_down_w"])
        else:
            shared = relu2_mlp(hc, p["shared_up_w"], p["shared_down_w"])
        if "shared_s_w" in p:
            shared = shared * jax.nn.sigmoid(jnp.dot(
                hc.astype(jnp.float32), p["shared_s_w"].astype(jnp.float32),
                precision=lax.Precision.HIGHEST))[:, None]
        return y + shared, c

    t = h.shape[0]
    each = _chunk_tokens(t)
    if each == t:
        return chunk((h, live))
    y, c = lax.map(chunk, (h.reshape(-1, each, h.shape[1]),
                           live.reshape(-1, each)))
    return y.reshape(t, -1), merge_counters(c)


def merge_counters(c):
    """Counters of several calls (n, len(COUNTERS)) as one: sums, and the
    largest ``load_max``."""
    i = COUNTERS.index("load_max")
    return jnp.sum(c, axis=0).at[i].set(jnp.max(c[:, i]))


"""The delta rule with a decay per key CHANNEL (Kimi delta attention, KDA;
arXiv:2510.26692), with the state a serving engine carries from call to call.
``ops/gated_delta.py``'s rule decays a head's whole state by one number a
token; here every key channel — a row of the state — has its own.

Per head, with a state ``S`` (key x value, float32) and per token a query
``q`` and key ``k`` (key wide), a value ``v`` (value wide), a log decay ``g <=
0`` (key wide) and a write strength ``beta``::

    S <- Diag(exp g) S;  r = S^T k;  d = beta (v - r);  S <- S + k d^T
    o = S^T q

- :func:`kda_recurrent` — that, token by token under ``lax.scan``: the plain
  form, for tests and small sizes.
- :func:`kda_chunked` — the same numbers over a prompt in chunks of ``CHUNK``
  tokens. With ``G`` the running sum of ``g`` inside a chunk, the tokens'
  writes solve ``(I + M) D = beta (V - (K e^G) S_in)``, ``M_ij = beta_i sum_c
  k_ic k_jc exp(G_ic - G_jc)`` for j < i. With one decay a head the
  exponential leaves the sum over c as a (C, C) factor
  (``gated_delta.delta_rule_chunked``); with one a channel it stays INSIDE,
  and the factored form ``(k_i e^(G_i)) . (k_j e^(-G_j))`` overflows float32
  over 64 tokens (``-G`` up to 64 x 5). So a chunk is cut in sub-chunks of
  ``SUB`` = 16 tokens and every block is ONE product ``(k_i e^(G_i - R)) .
  (k_j e^(R - G_j))`` about a running sum R near both: for a block UNDER the
  diagonal R is where i's sub-chunk starts — both exponents <= 0 whatever
  the decays (an underflow there is a term below 1e-38) —, for a DIAGONAL
  block (i, j in one sub-chunk) R is its middle: exponents within +-8 |g|,
  +-40 at the family's bound ``g > -5``, far inside float32 on both sides
  (which needs ``g >= -5.5``: the model's gate sees to that).
  The triangular solve is ``gated_delta``'s; the chunks follow one another,
  state in, state out. Float32 at ``HIGHEST`` in both of its forms: XLA
  einsums under a scan, and where backend and shapes allow
  (:func:`chunked_form`) ONE Pallas kernel (``kda_prefill``) that keeps a
  chunk's blocks, its triangular system and the heads' state in VMEM and
  writes nothing chunk-sized to HBM but the outputs.
- :func:`kda_step` — one token for every slot of a decode batch, as a Pallas
  kernel (``kda_decode``): the state array of every slot and layer is operand
  and result **in place**, a grid step reads and writes one slot's state of
  one layer once; a slot that is not live works on the array's last slot
  (scratch). ``impl="xla"``: the twin by gather and scatter.

A token with ``beta`` 0 and ``g`` 0 leaves the state as it was: that is how a
caller masks the positions past a prompt's length.
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
from jax import lax

from . import gated_delta
from .gated_delta import CHUNK, _unit_lower_inverse

__all__ = ["CHUNK", "SUB", "chunked_form", "kda_recurrent", "kda_chunked",
           "kda_step"]

SUB = 16            # tokens of a sub-chunk: 16 x 5 = 80 < ln(float32 max) = 88
_HI = lax.Precision.HIGHEST


def kda_recurrent(q, k, v, g, beta, state=None):
    """q, k, g (S, H, dk), v (S, H, dv), beta (S, H), state (H, dk, dv) or
    None (zeros) -> (o (S, H, dv), final state); float32."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    if state is None:
        state = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), f32)

    def token(s, xs):
        qt, kt, vt, gt, bt = xs
        s = s * jnp.exp(gt)[:, :, None]
        r = jnp.einsum("hkv,hk->hv", s, kt, precision=_HI)
        d = bt[:, None] * (vt - r)
        s = s + kt[:, :, None] * d[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision=_HI)

    state, o = lax.scan(token, state.astype(f32), (q, k, v, g, beta))
    return o, state


def _decayed_products(rows, k, g):
    """``P_ij = sum_c rows_ic k_jc exp(G_ic - G_jc)`` for j <= i, 0 above, G
    the running sum of g over the chunk: rows (..., R, C, dk) — R kinds of
    row (the keys for the solve, the queries for the outputs) against the
    same keys k (..., C, dk) under the log decays g (..., C, dk). Returns
    (P (..., R, C, C), G (..., C, dk)). The running sum is made inside the
    sub-chunks and their totals added on, so that what is exponentiated is
    a difference of small numbers."""
    c, dk = k.shape[-2:]
    nb = c // SUB
    lead = k.shape[:-2]

    def subs(a):      # (..., C, dk) -> (..., nb, SUB, dk)
        return a.reshape(a.shape[:-2] + (nb, SUB, dk))

    ks = subs(k)
    local = jnp.cumsum(subs(g), axis=-2)           # inside: in [-80, 0]
    total = local[..., -1, :]                      # (..., nb, dk)
    ref = jnp.cumsum(total, axis=-2) - total       # where a sub-chunk starts
    gc = (local + ref[..., None, :]).reshape(k.shape)
    rs = rows.reshape(rows.shape[:-2] + (nb, SUB, dk))        # (..., R, nb, ..)
    # diagonal blocks, about the sub-chunk's MIDDLE: exponents within +-40
    # at a log decay of -5, on both sides and in every product that is kept
    # (i >= j: never a large factor times a large one)
    mid = local - local[..., SUB // 2 - 1, None, :]
    seen = jnp.arange(SUB)[:, None] >= jnp.arange(SUB)[None, :]
    diag = jnp.where(seen, jnp.einsum(
        "...rnik,...njk->...rnij", rs * jnp.exp(mid)[..., None, :, :, :],
        ks * jnp.exp(-mid), precision=_HI), 0.0)   # (..., R, nb, SUB, SUB)
    rows_in = rs * jnp.exp(local)[..., None, :, :, :]  # against R of its own
    out = []
    for a in range(nb):
        blocks = []
        if a:
            # the keys before sub-chunk a, decayed up to where it starts
            before = k[..., :a * SUB, :] * jnp.exp(
                ref[..., a, None, :] - gc[..., :a * SUB, :])
            blocks.append(jnp.einsum("...rik,...jk->...rij",
                                     rows_in[..., a, :, :], before,
                                     precision=_HI))
        blocks.append(diag[..., a, :, :])
        if a < nb - 1:
            blocks.append(jnp.zeros(lead + rows.shape[-3:-2]
                                    + (SUB, c - (a + 1) * SUB), k.dtype))
        out.append(jnp.concatenate(blocks, axis=-1))
    return jnp.concatenate(out, axis=-2), gc


def chunked_form(dk, dv, chunk=CHUNK, impl="xla", interpret=False):
    """The form :func:`kda_chunked` takes for heads ``dk`` x ``dv``:
    ``"kda_prefill"`` (the kernel) or ``"xla"`` — the kernel wherever
    ``gated_delta.chunked_form`` picks that rule's."""
    fits = gated_delta.chunked_form(dk, dv, chunk, impl, interpret) != "xla"
    return "kda_prefill" if fits else "xla"


def kda_chunked(q, k, v, g, beta, state=None, chunk=CHUNK, impl="xla",
                interpret=False):
    """:func:`kda_recurrent`'s numbers, ``chunk`` tokens at a time (a
    multiple of ``SUB``). Shapes as there; S need not be a multiple of
    ``chunk`` (the pad writes nothing: beta 0, g 0). ``impl`` ``"pallas"``
    is the ``kda_prefill`` kernel where :func:`chunked_form` says it fits,
    else — and by default — the XLA einsums below: one algorithm, one
    precision."""
    f32 = jnp.float32
    s, h, dk = q.shape
    dv = v.shape[2]
    if chunk % SUB:
        raise ValueError(f"a chunk of {chunk} is not whole sub-chunks of {SUB}")
    n = -(-s // chunk)
    pad = n * chunk - s
    if state is None:
        state = jnp.zeros((h, dk, dv), f32)
    if chunked_form(dk, dv, chunk, impl, interpret) == "kda_prefill":
        def flat(a):       # (S, H, d) -> (S + pad, H d): the rows as they lie
            return jnp.pad(a.astype(f32).reshape(s, -1), ((0, pad), (0, 0)))

        o, state = _kda_prefill(flat(q), flat(k), flat(v), flat(g),
                                flat(beta), state.astype(f32), interpret)
        return o[:s].reshape(s, h, dv), state

    def chunks(a):     # (S, H, ...) -> (H, n, chunk, ...)
        a = jnp.pad(a.astype(f32), ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return jnp.moveaxis(a.reshape((n, chunk) + a.shape[1:]), 2, 0)

    q, k, v, g, beta = chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta)
    both, gc = _decayed_products(jnp.stack([k, q], axis=2), k, g)
    kk, qk = both[:, :, 0], both[:, :, 1]                     # (H, n, C, C)
    idx = jnp.arange(chunk)
    strict = idx[:, None] > idx[None, :]
    kb, vb = k * beta[..., None], v * beta[..., None]
    t = _unit_lower_inverse(jnp.where(strict, kk * beta[..., None], 0.0))
    u = jnp.einsum("hnij,hnjv->hniv", t, vb, precision=_HI)
    w = jnp.einsum("hnij,hnjk->hnik", t, kb * jnp.exp(gc), precision=_HI)
    last = gc[..., -1, :]                                     # (H, n, dk)
    q_in = q * jnp.exp(gc)                   # against the state coming in
    k_out = k * jnp.exp(last[..., None, :] - gc)   # into the state going out

    def one(st, xs):
        u_n, w_n, qk_n, q_n, k_n, last_n = xs
        d = u_n - jnp.einsum("hik,hkv->hiv", w_n, st, precision=_HI)
        o = (jnp.einsum("hik,hkv->hiv", q_n, st, precision=_HI)
             + jnp.einsum("hij,hjv->hiv", qk_n, d, precision=_HI))
        st = (st * jnp.exp(last_n)[..., None]
              + jnp.einsum("hik,hiv->hkv", k_n, d, precision=_HI))
        return st, o

    per_chunk = tuple(jnp.moveaxis(a, 1, 0)
                      for a in (u, w, qk, q_in, k_out, last))
    state, o = lax.scan(one, state.astype(f32), per_chunk)    # o (n, H, C, dv)
    o = jnp.moveaxis(o, 1, 2).reshape(n * chunk, h, dv)
    return o[:s], state


def _step_xla(states, layer, q, k, v, g, beta, live):
    """:func:`kda_step` by gather and scatter (tests, other backends)."""
    b = q.shape[0]
    s = states[:b, layer] * jnp.exp(g)[..., None]
    r = jnp.einsum("bhkv,bhk->bhv", s, k, precision=_HI)
    d = beta[..., None] * (v - r)
    s = s + k[..., :, None] * d[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", s, q, precision=_HI)
    keep = live[:, None, None, None]
    return o, states.at[:b, layer].set(jnp.where(keep, s, states[:b, layer]))


def kda_step(states, layer, q, k, v, g, beta, live, impl="pallas",
             interpret=False):
    """One token for each of B slots. states ``(slots + 1, layers, H, dk,
    dv)`` float32 — slot i's state of every layer, the last slot scratch —
    is read and written at ``[:, layer]`` in place (donate it); ``layer`` a
    Python int; q, k, g (B, H, dk), v (B, H, dv), beta (B, H) float32; live
    (B,) bool. Returns (o (B, H, dv) float32, states). A slot that is not
    live keeps its state (the kernel works on the scratch slot for it) and
    its output is garbage."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    if impl != "pallas":
        return _step_xla(states, layer, q, k, v, g, beta, live)
    b = v.shape[0]
    slot = jnp.where(live, jnp.arange(b), states.shape[0] - 1).astype(jnp.int32)
    return _kda_decode(
        states, jnp.full((1,), int(layer), jnp.int32), slot,
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
        jnp.swapaxes(jnp.exp(g), 1, 2), v,
        jnp.broadcast_to(beta[..., None], v.shape), interpret)


@functools.partial(jax.jit, static_argnums=(8,))
def _kda_decode(states, layer, slot, q_t, k_t, a_t, v, beta, interpret):
    """Grid (B,): grid step b holds slot ``slot[b]``'s state of ``layer``,
    all H heads (H x dk x dv float32: 2 MB at 32 x 128 x 128), in VMEM, in
    and out through the pipeline, the states array aliased to the result.
    q_t, k_t, a_t (B, dk, H): a head's query, key and DECAY are columns,
    broadcast along the lanes against the state's (dk sublanes, dv lanes) —
    the decay of a key channel scales its row of the state; v, beta (B, H,
    dv) rows, broadcast along the sublanes. The products run on the VPU (a
    mat-vec a head gives the MXU nothing to do): the kernel is bound by the
    bytes of the state."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, dk, h = q_t.shape
    dv = v.shape[2]

    def kernel(layer_ref, slot_ref, s_ref, q_ref, k_ref, a_ref, v_ref, b_ref,
               o_ref, out_ref):
        del layer_ref, slot_ref
        for i in range(h):
            kc, qc = k_ref[:, i:i + 1], q_ref[:, i:i + 1]      # (dk, 1)
            s = s_ref[i] * a_ref[:, i:i + 1]                   # (dk, dv)
            r = jnp.sum(s * kc, axis=0, keepdims=True)         # (1, dv)
            d = b_ref[i:i + 1, :] * (v_ref[i:i + 1, :] - r)
            s = s + kc * d
            out_ref[i] = s
            o_ref[i:i + 1, :] = jnp.sum(s * qc, axis=0, keepdims=True)

    def per_slot(shape):
        return pl.BlockSpec((None,) + shape, lambda i, ly, sl: (i, 0, 0))

    state_spec = pl.BlockSpec((None, None, h, dk, dv),
                              lambda i, ly, sl: (sl[i], ly[0], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[state_spec, per_slot((dk, h)), per_slot((dk, h)),
                  per_slot((dk, h)), per_slot((h, dv)), per_slot((h, dv))],
        out_specs=[per_slot((h, dv)), state_spec],
    )
    o, states = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        # operand 2 (after the two prefetched scalars) is result 1
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 * 1024 * 1024),
        interpret=interpret,
        name="kda_decode",
    )(layer, slot, states, q_t, k_t, a_t, v, beta)
    return o, states


@functools.partial(jax.jit, static_argnums=(6,))
def _kda_prefill(q, k, v, g, beta, state, interpret):
    """:func:`kda_chunked` as ONE kernel (a program of its own: a model
    that unrolls its layers lowers the body once, not once a layer). q, k, g
    ``(S, H dk)``, v ``(S, H dv)`` — rows of heads side by side: a head is a
    column block —, beta ``(S, H)``, state ``(H, dk, dv)``; S a multiple of
    ``CHUNK``. Returns ``(o (S, H dv), state)``.

    ``gated_delta._gdn_prefill``'s plan — grid (head groups, chunks), the
    chunks in order, the step's heads' state in the result's VMEM block from
    the first chunk to the last, ``T = (I + M)^-1`` by the same substitution
    (``gated_delta._chunk_inverses``), ``v_new = T (vb - (kb e^G) S)``, ``o =
    (q e^G) S + QK v_new``, ``S <- Diag(e^last) S + (k e^(last - G))^T
    v_new``, the body stage by stage over the step's heads — with the decay
    a (C, dk) array INSIDE the products. ``M`` and ``QK`` are
    :func:`_decayed_products`' blocks, k's and q's rows of a head stacked
    ((2 C, dk)) so that a pushed weight tile serves both: the four diagonal
    blocks out of ONE product about the sub-chunks' middles, and for each
    sub-chunk a > 0 its 2 x ``SUB`` rows ``e^(G - R_a)`` against the keys
    ``e^(min(R_a - G, 0))`` about R_a where it starts (the keys from a on
    are columns nobody reads: the blocks above the diagonal). The running
    sum is made inside the sub-chunks (a block-triangular matmul for all
    heads at once) and the totals added on. Float32, every product at
    ``HIGHEST``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    s, h = beta.shape
    dk, dv = state.shape[1:]
    hb = gated_delta._heads_a_step(h, 1)
    c, nb = CHUNK, CHUNK // SUB
    heads = range(hb)
    dot = gated_delta._dot

    def per_sub(rows):    # nb rows (1, dk), each over its sub-chunk: (C, dk)
        return jnp.concatenate(
            [jnp.broadcast_to(r, (SUB, r.shape[1])) for r in rows], axis=0)

    def twice(a):         # a factor for [k; q]
        return jnp.concatenate([a, a], axis=0)

    def kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, o_ref, out_ref):
        @pl.when(pl.program_id(1) == 0)
        def _():
            out_ref[...] = s_ref[...]

        row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
        col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
        lower, strict = row >= col, row > col
        diagonal = row // SUB == col // SUB
        head = (lax.broadcasted_iota(jnp.int32, (c, h), 1)
                - pl.program_id(0) * hb)
        channel = (lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
                   == lax.broadcasted_iota(jnp.int32, (dk, dk), 1))

        b = [jnp.sum(jnp.where(head == i, b_ref[...], 0.0), axis=1,
                     keepdims=True) for i in heads]            # (C, 1)
        ks = [k_ref[:, i * dk:(i + 1) * dk] for i in heads]
        kq = [jnp.concatenate([ks[i], q_ref[:, i * dk:(i + 1) * dk]], axis=0)
              for i in heads]                                  # (2 C, dk)
        # the log decays summed inside the sub-chunks: in [-80, 0]
        # (a product, not a log-step scan of shifted adds: rows whose later
        # decays are 0 — a prompt's masked end — must sum to the SAME number,
        # and a scan groups their terms differently, an ulp of G apart)
        local_all = dot((lower & diagonal).astype(f32), g_ref[...])
        local = [local_all[:, i * dk:(i + 1) * dk] for i in heads]
        total = [[a[n + SUB - 1:n + SUB] for n in range(0, c, SUB)]
                 for a in local]
        # where each sub-chunk starts and, last, where the chunk ends
        ref = [list(itertools.accumulate(t, initial=jnp.zeros((1, dk), f32)))
               for t in total]
        gc = [local[i] + per_sub(ref[i][:nb]) for i in heads]
        # diagonal blocks about the sub-chunk's middle: exponents within
        # +-8 |g|
        mid = [a - per_sub([a[n + SUB // 2 - 1:n + SUB // 2]
                            for n in range(0, c, SUB)]) for a in local]
        diag = [dot(kq[i] * twice(jnp.exp(mid[i])), ks[i] * jnp.exp(-mid[i]),
                    ((1,), (1,))) for i in heads]              # (2 C, C)
        # under the diagonal, about where the rows' sub-chunk starts
        rows_in = [kq[i] * twice(jnp.exp(local[i])) for i in heads]
        under = [[jnp.zeros((2 * SUB, c), f32)] for _ in heads]
        for a in range(1, nb):
            for i in heads:
                before = ks[i] * jnp.exp(jnp.minimum(ref[i][a] - gc[i], 0.0))
                mine = jnp.concatenate(
                    [rows_in[i][a * SUB:(a + 1) * SUB],
                     rows_in[i][c + a * SUB:c + (a + 1) * SUB]], axis=0)
                under[i].append(dot(mine, before, ((1,), (1,))))
        kk = [jnp.where(diagonal, diag[i][:c], jnp.concatenate(
            [u[:SUB] for u in under[i]], axis=0)) for i in heads]
        qk = [jnp.where(lower, jnp.where(diagonal, diag[i][c:], jnp.concatenate(
            [u[SUB:] for u in under[i]], axis=0)), 0.0) for i in heads]
        m = [jnp.where(strict, kk[i] * b[i], 0.0) for i in heads]
        # what the state coming in gives the keys and the queries: (2 C, dv)
        into = [jnp.exp(a) for a in gc]
        read = [dot(kq[i] * jnp.concatenate([b[i] * into[i], into[i]], axis=0),
                    out_ref[i]) for i in heads]
        t = gated_delta._chunk_inverses(m, row, col, diagonal)
        v_new = [dot(t[i], v_ref[:, i * dv:(i + 1) * dv] * b[i] - read[i][:c])
                 for i in heads]
        for i in heads:
            o_ref[:, i * dv:(i + 1) * dv] = read[i][c:] + dot(qk[i], v_new[i])
        for i in heads:
            last = ref[i][nb]                                      # (1, dk)
            # the chunk's decay down the state's rows: last as a column
            fade = jnp.exp(jnp.sum(jnp.where(channel, last, 0.0), axis=1,
                                   keepdims=True))                 # (dk, 1)
            out_ref[i] = out_ref[i] * fade + dot(
                ks[i] * jnp.exp(last - gc[i]), v_new[i], ((0,), (0,)))

    def rows(width):
        return pl.BlockSpec((c, width), lambda j, n: (n, j))

    held = pl.BlockSpec((hb, dk, dv), lambda j, n: (j, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(h // hb, s // c),
        in_specs=[rows(hb * dk), rows(hb * dk), rows(hb * dv), rows(hb * dk),
                  pl.BlockSpec((c, h), lambda j, n: (n, 0)), held],
        out_specs=[rows(hb * dv), held],
        out_shape=[jax.ShapeDtypeStruct((s, h * dv), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024),
        interpret=interpret,
        name="kda_prefill",
    )(q, k, v, g, beta, state)

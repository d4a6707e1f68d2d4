"""The gated delta rule — a linear-attention layer's recurrence — and the
causal depthwise convolution in front of it, both with the state a serving
engine carries from call to call.

Per value head, with a state ``S`` (key x value, float32) and per token a
query ``q`` and key ``k`` (key wide), a value ``v`` (value wide), a log decay
``g <= 0`` and a write strength ``beta``::

    S <- exp(g) S;  r = S^T k;  d = beta (v - r);  S <- S + k d^T;  o = S^T q

- :func:`delta_rule_recurrent` — that, token by token under ``lax.scan``: the
  plain form, for tests and small sizes.
- :func:`delta_rule_chunked` — the same numbers over a prompt in chunks of
  ``CHUNK`` tokens: inside a chunk the tokens' writes are solved together (a
  unit lower-triangular system, by forward substitution — rows inside 16-wide
  blocks, then the blocks —: stable whatever the keys), and only the chunks
  follow one another. Float32 at ``HIGHEST`` in both of its forms: XLA
  einsums, and where backend and shapes allow (:func:`chunked_form`) ONE
  Pallas kernel (``gdn_prefill``) that keeps a chunk's system and the state
  in VMEM and writes nothing chunk-sized to HBM but the outputs.
  Returns the outputs and the final state, which a prefill hands to the step.
- :func:`delta_rule_step` — one token for every slot of a decode batch, as a
  Pallas kernel (``gdn_decode``): the state array of every slot and layer is
  the kernel's operand and result **in place**, and a grid step reads and
  writes one slot's state of one layer once. A slot that is not live reads
  and writes the array's last slot (scratch) instead.
- :func:`causal_conv` / :func:`causal_conv_step` — ``out_t = sum_j w_j
  x_(t-W+1+j)`` per channel (no bias), over a sequence from a zero history
  (or from the tail an earlier piece of it left), and for one new input with
  the last ``W - 1`` inputs carried as the tail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["CHUNK", "causal_conv", "causal_conv_step", "chunked_form",
           "delta_rule_recurrent", "delta_rule_chunked", "delta_rule_step"]

CHUNK = 64          # tokens solved together (the family's habit)
_HI = lax.Precision.HIGHEST


def causal_conv(x, w, length=None, history=None):
    """x (S, C), w (W, C): ``(out (S, C) float32, tail (W - 1, C))`` — the
    tail is the last ``W - 1`` inputs before position ``length`` (default S),
    zeros where the sequence is shorter: what :func:`causal_conv_step`
    carries on from. ``history`` (W - 1, C): the inputs before ``x`` — the
    tail an earlier piece of the sequence left —, zeros by default."""
    s, width = x.shape[0], w.shape[0]
    if history is None:
        history = jnp.zeros((width - 1, x.shape[1]), x.dtype)
    padded = jnp.concatenate([history.astype(x.dtype), x])
    wf = w.astype(jnp.float32)
    out = sum(wf[j] * lax.dynamic_slice_in_dim(padded, j, s).astype(jnp.float32)
              for j in range(width))
    end = s if length is None else length
    return out, lax.dynamic_slice_in_dim(padded, end, width - 1)


def causal_conv_step(x, tail, w):
    """One new input per sequence: x (B, C), tail (B, W - 1, C) its last
    inputs, w (W, C) -> (out (B, C) float32, new tail)."""
    window = jnp.concatenate([tail, x[:, None].astype(tail.dtype)], axis=1)
    out = jnp.sum(window.astype(jnp.float32) * w.astype(jnp.float32)[None],
                  axis=1)
    return out, window[:, 1:]


def delta_rule_recurrent(q, k, v, g, beta, state=None):
    """q, k (S, H, dk), v (S, H, dv), g, beta (S, H), state (H, dk, dv) or
    None (zeros) -> (o (S, H, dv), final state); float32."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    if state is None:
        state = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), f32)

    def token(s, xs):
        qt, kt, vt, gt, bt = xs
        s = s * jnp.exp(gt)[:, None, None]
        r = jnp.einsum("hkv,hk->hv", s, kt, precision=_HI)
        d = bt[:, None] * (vt - r)
        s = s + kt[:, :, None] * d[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision=_HI)

    state, o = lax.scan(token, state.astype(f32), (q, k, v, g, beta))
    return o, state


def _rows_inverse(m):
    """``(I + M)^-1`` for M (..., C, C) strictly lower triangular, row by row
    (forward substitution; row i needs the rows before it)."""
    c = m.shape[-1]

    def row(i, t):
        r = lax.dynamic_index_in_dim(t, i, axis=-2, keepdims=False)
        r = r + jnp.einsum("...j,...jk->...k", r, t, precision=_HI)
        return lax.dynamic_update_index_in_dim(t, r, i, axis=-2)

    return lax.fori_loop(1, c, row, -m) + jnp.eye(c, dtype=m.dtype)


def _unit_lower_inverse(m, block=16):
    """``(I + M)^-1`` for M (..., C, C) strictly lower triangular: the
    ``block``-wide diagonal blocks row by row (:func:`_rows_inverse`: a row
    loop over the whole matrix reads all of it C times, which was 29 % of a
    16 k prefill on the chip), then block forward substitution for what lies
    under them, ``T_ab = -T_aa sum_(b <= c < a) M_ac T_cb``: small batched
    products, every piece a substitution (no power of M is formed)."""
    c = m.shape[-1]
    if c <= block or c % block:
        return _rows_inverse(m)
    nb = c // block
    blocks = m.reshape(m.shape[:-2] + (nb, block, nb, block))

    def at(a, b):
        return blocks[..., a, :, b, :]

    diag = _rows_inverse(jnp.stack([at(a, a) for a in range(nb)], axis=-3))
    t = [[diag[..., a, :, :] if a == b else None for b in range(nb)]
         for a in range(nb)]
    for b in range(nb):
        for a in range(b + 1, nb):
            acc = sum(jnp.einsum("...ij,...jk->...ik", at(a, k), t[k][b],
                                 precision=_HI) for k in range(b, a))
            t[a][b] = -jnp.einsum("...ij,...jk->...ik", t[a][a], acc,
                                  precision=_HI)
    zero = jnp.zeros_like(t[0][0])
    return jnp.concatenate([jnp.concatenate(
        [t[a][b] if b <= a else zero for b in range(nb)], axis=-1)
        for a in range(nb)], axis=-2)


def delta_rule_chunked(q, k, v, g, beta, state=None, chunk=CHUNK, impl="xla",
                       interpret=False):
    """:func:`delta_rule_recurrent`'s numbers, ``chunk`` tokens at a time.
    Shapes as there, but q and k may come with fewer heads than v (a key head
    serves ``HV / HK`` value heads in a row); S need not be a multiple of
    ``chunk`` (the pad writes nothing: beta 0, g 0). A token with ``beta`` 0
    and ``g`` 0 leaves the state as it was, which is how a caller masks the
    positions past a prompt's length. ``impl`` ``"pallas"`` is the
    ``gdn_prefill`` kernel where :func:`chunked_form` says it fits, else —
    and by default — the XLA einsums below: one algorithm, one precision."""
    f32 = jnp.float32
    s, h, dv = v.shape
    dk = q.shape[2]
    n = -(-s // chunk)
    pad = n * chunk - s
    if state is None:
        state = jnp.zeros((h, dk, dv), f32)
    if chunked_form(dk, dv, chunk, impl, interpret) == "gdn_prefill":
        def flat(a):       # (S, H, d) -> (S + pad, H d): the rows as they lie
            return jnp.pad(a.astype(f32).reshape(s, -1), ((0, pad), (0, 0)))

        o, state = _gdn_prefill(flat(q), flat(k), flat(v), flat(g),
                                flat(beta), state.astype(f32), interpret)
        return o[:s].reshape(s, h, dv), state
    if q.shape[1] != h:
        q, k = (jnp.repeat(a, h // a.shape[1], axis=1) for a in (q, k))

    def chunks(a):     # (S, H, ...) -> (H, n, chunk, ...)
        a = jnp.pad(a.astype(f32), ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return jnp.moveaxis(a.reshape((n, chunk) + a.shape[1:]), 2, 0)

    q, k, v, g, beta = chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta)
    gc = jnp.cumsum(g, axis=-1)                               # (H, n, C)
    idx = jnp.arange(chunk)
    lower = idx[:, None] >= idx[None, :]
    # exp(gc_i - gc_j) for i >= j; the other half would overflow
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))
    kb, vb = k * beta[..., None], v * beta[..., None]
    strict = idx[:, None] > idx[None, :]
    m = jnp.where(strict, jnp.einsum("hnik,hnjk->hnij", kb, k, precision=_HI)
                  * decay, 0.0)
    t = _unit_lower_inverse(m)
    u = jnp.einsum("hnij,hnjv->hniv", t, vb, precision=_HI)
    w = jnp.einsum("hnij,hnjk->hnik", t, kb * jnp.exp(gc)[..., None],
                   precision=_HI)
    qk = jnp.einsum("hnik,hnjk->hnij", q, k, precision=_HI) * decay
    last = gc[..., -1:]                                       # (H, n, 1)
    q_in = q * jnp.exp(gc)[..., None]        # against the state coming in
    k_out = k * jnp.exp(last - gc)[..., None]  # into the state going out

    def one(st, xs):
        u_n, w_n, qk_n, q_n, k_n, last_n = xs
        v_new = u_n - jnp.einsum("hik,hkv->hiv", w_n, st, precision=_HI)
        o = (jnp.einsum("hik,hkv->hiv", q_n, st, precision=_HI)
             + jnp.einsum("hij,hjv->hiv", qk_n, v_new, precision=_HI))
        st = (st * jnp.exp(last_n)[..., None]
              + jnp.einsum("hik,hiv->hkv", k_n, v_new, precision=_HI))
        return st, o

    per_chunk = tuple(jnp.moveaxis(a, 1, 0)
                      for a in (u, w, qk, q_in, k_out, last))
    state, o = lax.scan(one, state.astype(f32), per_chunk)    # o (n, H, C, dv)
    o = jnp.moveaxis(o, 1, 2).reshape(n * chunk, h, dv)
    return o[:s], state


def _step_xla(states, layer, q, k, v, g, beta, live):
    """:func:`delta_rule_step` by gather and scatter (tests, other
    backends)."""
    b = q.shape[0]
    s = states[:b, layer] * jnp.exp(g)[..., None, None]
    r = jnp.einsum("bhkv,bhk->bhv", s, k, precision=_HI)
    d = beta[..., None] * (v - r)
    s = s + k[..., :, None] * d[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", s, q, precision=_HI)
    keep = live[:, None, None, None]
    return o, states.at[:b, layer].set(jnp.where(keep, s, states[:b, layer]))


def delta_rule_step(states, layer, q, k, v, g, beta, live, impl="pallas",
                    interpret=False):
    """One token for each of B slots. states ``(slots + 1, layers, H, dk,
    dv)`` float32 — slot i's state of every layer, the last slot scratch —
    is read and written at ``[:, layer]`` in place (donate it); ``layer`` a
    Python int; q, k (B, H, dk), v (B, H, dv), g, beta (B, H) float32; live
    (B,) bool. Returns (o (B, H, dv) float32, states). A slot that is not
    live keeps its state (the kernel works on the scratch slot for it) and
    its output is garbage."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    if impl != "pallas":
        return _step_xla(states, layer, q, k, v, g, beta, live)
    b, h, dv = v.shape
    slot = jnp.where(live, jnp.arange(b), states.shape[0] - 1).astype(jnp.int32)
    wide = (b, h, dv)
    return _gdn_decode(
        states, jnp.full((1,), int(layer), jnp.int32), slot,
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), v,
        jnp.broadcast_to(jnp.exp(g)[..., None], wide),
        jnp.broadcast_to(beta[..., None], wide), interpret)


@functools.partial(jax.jit, static_argnums=(8,))
def _gdn_decode(states, layer, slot, q_t, k_t, v, decay, beta, interpret):
    """Grid (B,): grid step b holds slot ``slot[b]``'s state of ``layer``,
    all H heads (H x dk x dv float32: 2 MB at 32 x 128 x 128), in VMEM, in
    and out through the pipeline, the states array aliased to the result.
    q_t, k_t (B, dk, H): a head's query and key are a COLUMN, broadcast along
    the lanes against the state's (dk sublanes, dv lanes); v, decay, beta
    (B, H, dv) rows, broadcast along the sublanes. The products run on the
    VPU: one row of work a head (a mat-vec) gives the MXU nothing to do, and
    the kernel is bound by the bytes of the state."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, dk, h = q_t.shape
    dv = v.shape[2]

    def kernel(layer_ref, slot_ref, s_ref, q_ref, k_ref, v_ref, a_ref, b_ref,
               o_ref, out_ref):
        del layer_ref, slot_ref
        for i in range(h):
            kc, qc = k_ref[:, i:i + 1], q_ref[:, i:i + 1]      # (dk, 1)
            s = s_ref[i] * a_ref[i:i + 1, :]                   # (dk, dv)
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
                  per_slot((h, dv)), per_slot((h, dv)), per_slot((h, dv))],
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
        name="gdn_decode",
    )(layer, slot, states, q_t, k_t, v, decay, beta)
    return o, states


_SUB = 16           # rows of a diagonal block solved row by row


def chunked_form(dk, dv, chunk=CHUNK, impl="xla", interpret=False):
    """The form :func:`delta_rule_chunked` takes for heads ``dk`` x ``dv``:
    ``"gdn_prefill"`` (the kernel) or ``"xla"``. The kernel wants ``impl``
    ``"pallas"``, chunks of ``CHUNK`` and, compiled, a head that is whole
    128-lane blocks of a ``(S, H d)`` row."""
    lanes = interpret or (dk % 128 == 0 and dv % 128 == 0)
    return ("gdn_prefill" if impl == "pallas" and chunk == CHUNK and lanes
            else "xla")


def _heads_a_step(hv, rep, most=4):
    """Value heads a grid step: whole key heads' groups of ``rep``, at most
    ``most`` where that divides ``hv``."""
    fits = [n for n in range(rep, min(most, hv) + 1, rep) if hv % n == 0]
    return fits[-1] if fits else rep


def _dot(a, b, dims=((1,), (0,))):
    """A kernel body's float32 product at ``HIGHEST``, ``dims`` the
    contracted axes of a and of b."""
    return lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                           preferred_element_type=jnp.float32)


def _chunk_inverses(m, row, col, diagonal):
    """Inside a kernel body (``gdn_prefill``, ``ops/kda.py``'s
    ``kda_prefill``): ``(I + M)^-1`` for every M of the list ``m`` — one a
    head of the grid step, (CHUNK, CHUNK), strictly lower triangular —, stage
    by stage over the list. ``row``, ``col``: the (CHUNK, CHUNK) iotas,
    ``diagonal``: ``row // _SUB == col // _SUB``. The ``_SUB``-wide diagonal
    blocks row by row on the VPU, the four of a chunk side by side in the
    lanes, then block substitution ``P <- P - P M_under P`` for the blocks of
    32 and of 64 (exact: ``P M_under`` squares to zero; no power of M is
    formed)."""
    f32 = jnp.float32
    c, nb = CHUNK, CHUNK // _SUB
    heads = range(len(m))

    def odd(a, size):     # the rows of a's odd blocks of ``size`` rows
        return jnp.concatenate([a[n * size:(n + 1) * size]
                                for n in range(1, c // size, 2)], axis=0)

    def spread(a, size):  # :func:`odd`'s rows back in place, zeros between
        zero = jnp.zeros((size, a.shape[1]), f32)
        return jnp.concatenate(
            [part for n in range(0, a.shape[0], size)
             for part in (zero, a[n:n + size])], axis=0)

    # the diagonal blocks first, side by side: (row in its block, block x
    # column). x_r -= m_rj x_j for the rows r > j of every block (m_rj is 0
    # for the others): column j of each block across the block's lanes is
    # ONE lane gather
    sub_row = lax.broadcasted_iota(jnp.int32, (_SUB, c), 0)
    sub_col = lax.broadcasted_iota(jnp.int32, (_SUB, c), 1)
    inside = [jnp.where(diagonal, m[i], 0.0) for i in heads]
    inside = [sum(a[n:n + _SUB] for n in range(0, c, _SUB)) for a in inside]
    x = [(sub_row == sub_col % _SUB).astype(f32) for _ in heads]
    for j in range(_SUB - 1):
        for i in heads:
            x[i] = x[i] - x[i][j:j + 1] * jnp.take_along_axis(
                inside[i], sub_col // _SUB * _SUB + j, axis=1)
    t = [jnp.where(diagonal, jnp.concatenate([a] * nb, axis=0), 0.0)
         for a in x]
    size = _SUB
    while size < c:
        # the blocks under the diagonal of every (2 size)-wide block fill
        # the odd blocks of ``size`` rows: the products over those
        under = ((row // (2 * size) == col // (2 * size))
                 & (row // size == col // size + 1))
        inner = [spread(_dot(odd(jnp.where(under, m[i], 0.0), size), t[i]),
                        size) for i in heads]
        t = [t[i] - spread(_dot(odd(t[i], size), inner[i]), size)
             for i in heads]
        size *= 2
    return t


def _gdn_prefill(q, k, v, g, beta, state, interpret):
    """:func:`delta_rule_chunked` as ONE kernel. q, k ``(S, HK dk)``, v ``(S,
    HV dv)`` — rows of heads side by side: a head is a column block, and
    value head h reads key head ``h // (HV / HK)`` —, g, beta ``(S, HV)``,
    state ``(HV, dk, dv)``; S a multiple of ``CHUNK``. Returns ``(o (S, HV
    dv), state)``.

    Grid (head groups, chunks), the chunks in order: a grid step holds one
    chunk of ``_heads_a_step`` value heads; their state lives in the result's
    VMEM block from the first chunk (copied in) to the last (written back),
    and nothing chunk-sized but ``o`` goes to HBM. Per head and chunk, all
    float32, every product at ``HIGHEST``: the running log decay (a
    triangular matmul for all heads at once), ``M = strict(kb k^T . decay)``,
    ``T = (I + M)^-1`` by substitution — the ``_SUB``-wide diagonal blocks
    row by row on the VPU, the four of a chunk side by side in the lanes,
    then block substitution ``P <- P - P M_under P`` for the blocks of 32
    and of 64 (exact: ``P M_under`` squares to zero; no power of M is
    formed) —, then ``v_new = T (vb - (kb e^gc) S)``, ``o = (q e^gc) S + (q
    k^T . decay) v_new`` and ``S <- e^last S + (k e^(last - gc))^T v_new``:
    the XLA form's ``u - w S`` with T taken out of the bracket, which is why
    neither u nor w is made. The value heads of one key head share ``[k; q]
    k^T``. The body runs stage by stage over the step's heads, not head by
    head: Mosaic's schedule follows the source, and one head's chain of
    dependent products leaves the MXU idle most of the time (1.67 -> 1.00 ms
    a layer of 2,048 x 32 heads on a v5e; 0.73 with the lane gather below
    and the products of the block substitution cut to the rows that are not
    zero)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    s, hv = g.shape
    dk, dv = state.shape[1:]
    rep = hv // (q.shape[1] // dk)
    hb = _heads_a_step(hv, rep)
    c = CHUNK
    heads = range(hb)

    def kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, o_ref, out_ref):
        @pl.when(pl.program_id(1) == 0)
        def _():
            out_ref[...] = s_ref[...]

        row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
        col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
        lower, strict = row >= col, row > col
        diagonal = row // _SUB == col // _SUB
        head = (lax.broadcasted_iota(jnp.int32, (c, hv), 1)
                - pl.program_id(0) * hb)

        def column(a, i):      # head i's column of a (C, HV) array: (C, 1)
            return jnp.sum(jnp.where(head == i, a, 0.0), axis=1,
                           keepdims=True)

        def keys(i, ref):      # head i's key head's block of q or k
            j = i // rep
            return ref[:, j * dk:(j + 1) * dk]

        gc_all = _dot(lower.astype(f32), g_ref[...])            # (C, HV)
        gc = [column(gc_all, i) for i in heads]
        b = [column(b_ref[...], i) for i in heads]
        # [k; q] k^T of a key head, for its value heads: (2 C, C)
        both = [_dot(jnp.concatenate([keys(i, k_ref), keys(i, q_ref)], axis=0),
                    keys(i, k_ref), ((1,), (1,))) for i in heads[::rep]]
        decay = []
        for i in heads:
            # gc along the lanes: the same numbers, so that the diagonal of
            # the decay is exp(0)
            across = jnp.sum(jnp.where(row == col, gc[i], 0.0), axis=0,
                             keepdims=True)
            decay.append(jnp.where(
                lower, jnp.exp(jnp.minimum(gc[i] - across, 0.0)), 0.0))
        m = [jnp.where(strict, both[i // rep][:c] * b[i] * decay[i], 0.0)
             for i in heads]
        # what the state coming in gives the keys and the queries: (2 C, dv)
        into = [jnp.exp(gc[i]) for i in heads]
        read = [_dot(jnp.concatenate(
            [keys(i, k_ref) * (b[i] * into[i]), keys(i, q_ref) * into[i]],
            axis=0), out_ref[i]) for i in heads]
        t = _chunk_inverses(m, row, col, diagonal)
        v_new = [_dot(t[i], v_ref[:, i * dv:(i + 1) * dv] * b[i] - read[i][:c])
                 for i in heads]
        for i in heads:
            o_ref[:, i * dv:(i + 1) * dv] = read[i][c:] + _dot(
                both[i // rep][c:] * decay[i], v_new[i])
        for i in heads:
            last = gc[i][c - 1:c]                                  # (1, 1)
            out_ref[i] = out_ref[i] * jnp.exp(last) + _dot(
                keys(i, k_ref) * jnp.exp(last - gc[i]), v_new[i],
                ((0,), (0,)))

    def rows(width):
        return pl.BlockSpec((c, width), lambda h, n: (n, h))

    every = pl.BlockSpec((c, hv), lambda h, n: (n, 0))
    held = pl.BlockSpec((hb, dk, dv), lambda h, n: (h, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(hv // hb, s // c),
        in_specs=[rows(hb // rep * dk), rows(hb // rep * dk), rows(hb * dv),
                  every, every, held],
        out_specs=[rows(hb * dv), held],
        out_shape=[jax.ShapeDtypeStruct((s, hv * dv), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024),
        interpret=interpret,
        name="gdn_prefill",
    )(q, k, v, g, beta, state)

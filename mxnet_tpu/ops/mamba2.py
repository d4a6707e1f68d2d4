"""The Mamba-2 state-space recurrence (SSD), with the state a serving engine
carries from call to call.

Per head, with a state ``S`` (P x N: head dimension x state size, float32)
and per token an input ``x`` (P), a step ``delta >= 0``, and the group's
``B`` and ``C`` (N each; head ``n`` of H uses group ``n // (H / G)``)::

    a = exp(A delta);  S <- a S + delta x B^T;  y = S C

``A < 0`` is the head's scalar decay rate (``-exp(A_log)``). A token with
``delta`` 0 leaves the state as it was, which is how a caller masks the
positions behind a prompt. The skip ``D x``, the convolution in front
(``ops.gated_delta.causal_conv``; its bias is added by the caller) and the
gated norm behind are the model's.

- :func:`ssd_recurrent` — that, token by token under ``lax.scan``: the plain
  form, for tests and small sizes.
- :func:`ssd_chunked` — the same numbers over a prompt in chunks of
  ``CHUNK`` tokens (the SSD form): inside a chunk ``y = (C B^T * L)(delta
  x)`` with ``L_ts = exp(sum_(s < r <= t) A delta_r)`` — every exponent is
  <= 0, nothing overflows —, the chunks' states carried by a scan. XLA
  einsums in float32. Returns the outputs and the final state, which a
  prefill hands to the step.
- :func:`ssm_step` — one token for every slot of a decode batch, as a Pallas
  kernel (``ssm_decode``): the state array of every slot and layer is the
  kernel's operand and result **in place**, and a grid step reads and
  writes one slot's state of one layer once. A slot that is not live reads
  and writes the array's last slot (scratch) instead.

**How a slot's state lies** (:func:`to_slots`, :func:`from_slots`): a
layer's H x P x N values as ``(G, N, (H / G) P)`` — group, state index, then
the group's heads side by side with P inside. A group's ``B`` and ``C`` are
then one column each against all of its heads, the decay and ``delta x`` one
row each, and ``y`` is a sum over sublanes: at 64 heads of 64 in 8 groups
with N = 128 a group is a (128, 512) float32 array of whole (8, 128) tiles
with every lane in use (a head's own 64 x 128 would fill half of them).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["CHUNK", "ssd_recurrent", "ssd_chunked", "ssm_step", "to_slots",
           "from_slots"]

CHUNK = 128         # tokens taken together (the family's ``chunk_size``)
_HI = lax.Precision.HIGHEST


def to_slots(state, groups: int):
    """state (..., H, P, N) -> (..., G, N, (H / G) P), as a slot holds it."""
    *lead, h, p, n = state.shape
    s = state.reshape(*lead, groups, h // groups, p, n)
    return jnp.moveaxis(s, -1, -3).reshape(*lead, groups, n, h // groups * p)


def from_slots(state, heads: int):
    """The inverse of :func:`to_slots`: (..., G, N, R P) -> (..., H, P, N)."""
    *lead, g, n, rp = state.shape
    r = heads // g
    s = state.reshape(*lead, g, n, r, rp // r)
    return jnp.moveaxis(s, -3, -1).reshape(*lead, heads, rp // r, n)


def ssd_recurrent(x, delta, a, b, c, state=None):
    """x (S, H, P), delta (S, H), a (H,) < 0, b, c (S, G, N), state (H, P, N)
    or None (zeros) -> (y (S, H, P), final state); float32."""
    f32 = jnp.float32
    x, delta, a, b, c = (t.astype(f32) for t in (x, delta, a, b, c))
    h, rep = x.shape[1], x.shape[1] // b.shape[1]
    if state is None:
        state = jnp.zeros((h, x.shape[2], b.shape[2]), f32)

    def token(s, xs):
        xt, dt, bt, ct = xs
        bt, ct = jnp.repeat(bt, rep, axis=0), jnp.repeat(ct, rep, axis=0)
        s = (s * jnp.exp(a * dt)[:, None, None]
             + (dt[:, None] * xt)[:, :, None] * bt[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, ct, precision=_HI)

    state, y = lax.scan(token, state.astype(f32), (x, delta, b, c))
    return y, state


def ssd_chunked(x, delta, a, b, c, state=None, chunk=CHUNK):
    """:func:`ssd_recurrent`'s numbers, ``chunk`` tokens at a time. Shapes as
    there; S need not be a multiple of ``chunk`` (the pad has ``delta`` 0:
    it writes nothing and decays nothing)."""
    f32 = jnp.float32
    s, h, p = x.shape
    g, n = b.shape[1], b.shape[2]
    r = h // g
    nc = -(-s // chunk)
    pad = nc * chunk - s

    def chunks(t):     # (S, ...) -> (nc, chunk, ...)
        t = jnp.pad(t.astype(f32), ((0, pad),) + ((0, 0),) * (t.ndim - 1))
        return t.reshape((nc, chunk) + t.shape[1:])

    delta = chunks(delta)                                   # (nc, L, H)
    dx = (chunks(x) * delta[..., None]).reshape(nc, chunk, g, r, p)
    b, c = chunks(b), chunks(c)                             # (nc, L, G, N)
    cs = jnp.cumsum(delta * a.astype(f32), axis=1)          # log decay, <= 0
    cs = jnp.moveaxis(cs, 1, 2).reshape(nc, g, r, chunk)
    idx = jnp.arange(chunk)
    lower = idx[:, None] >= idx[None, :]
    # exp(cs_t - cs_s) for t >= s; the other half would overflow
    decay = jnp.exp(jnp.where(lower, cs[..., :, None] - cs[..., None, :],
                              -jnp.inf))                    # (nc, G, R, L, L)
    cb = jnp.einsum("ctgn,csgn->cgts", c, b, precision=_HI)
    y_in = jnp.einsum("cgrts,csgrp->ctgrp", cb[:, :, None] * decay, dx,
                      precision=_HI)
    # what a chunk adds to the state it leaves, and how much of the state
    # coming in is left by then
    to_end = jnp.exp(cs[..., -1:] - cs)                     # (nc, G, R, L)
    adds = jnp.einsum("cgrs,csgrp,csgn->cgrpn", to_end, dx, b, precision=_HI)
    keeps = jnp.exp(cs[..., -1])                            # (nc, G, R)
    from_start = jnp.exp(cs)                                # (nc, G, R, L)
    if state is None:
        state = jnp.zeros((h, p, n), f32)

    def one(st, xs):
        add, keep, c_n, into = xs
        y = jnp.einsum("tgn,grpn,grt->tgrp", c_n, st, into, precision=_HI)
        return st * keep[..., None, None] + add, y

    state, y_out = lax.scan(one, state.astype(f32).reshape(g, r, p, n),
                            (adds, keeps, c, from_start))
    y = (y_in + y_out).reshape(nc * chunk, h, p)
    return y[:s], state.reshape(h, p, n)


def _step_xla(states, layer, dx, decay, b, c, live):
    """:func:`ssm_step` by gather and scatter (tests, other backends)."""
    n = dx.shape[0]
    old = states[:n, layer]                                 # (B, G, N, RP)
    s = (old * decay[:, :, None, :]
         + b[:, :, :, None] * dx[:, :, None, :])
    y = jnp.einsum("bgnq,bgn->bgq", s, c, precision=_HI)
    keep = live[:, None, None, None]
    return y, states.at[:n, layer].set(jnp.where(keep, s, old))


def ssm_step(states, layer, x, delta, a, b, c, live, impl="pallas",
             interpret=False):
    """One token for each of B slots. states ``(slots + 1, layers, G, N, (H /
    G) P)`` float32 — slot i's state of every layer as :func:`to_slots` lays
    it, the last slot scratch — is read and written at ``[:, layer]`` in
    place (donate it); ``layer`` a Python int; x (B, H, P), delta (B, H), a
    (H,), b, c (B, G, N); live (B,) bool. Returns (y (B, H, P) float32,
    states). A slot that is not live keeps its state (the kernel works on
    the scratch slot for it) and its output is garbage."""
    f32 = jnp.float32
    x, delta, a, b, c = (t.astype(f32) for t in (x, delta, a, b, c))
    n, h, p = x.shape
    g = b.shape[1]
    wide = (n, g, h // g * p)
    dx = (x * delta[..., None]).reshape(wide)
    decay = jnp.broadcast_to(jnp.exp(a * delta)[..., None],
                             (n, h, p)).reshape(wide)
    if impl != "pallas":
        y, states = _step_xla(states, layer, dx, decay, b, c, live)
    else:
        slot = jnp.where(live, jnp.arange(n),
                         states.shape[0] - 1).astype(jnp.int32)
        y, states = _ssm_decode(
            states, jnp.full((1,), int(layer), jnp.int32), slot,
            jnp.swapaxes(b, 1, 2), jnp.swapaxes(c, 1, 2), dx, decay,
            interpret)
    return y.reshape(n, h, p), states


@functools.partial(jax.jit, static_argnums=(7,))
def _ssm_decode(states, layer, slot, b_t, c_t, dx, decay, interpret):
    """Grid (B,): grid step i holds slot ``slot[i]``'s state of ``layer``,
    all G groups (G x N x RP float32: 4 MB at 8 x 128 x 512), in VMEM, in
    and out through the pipeline, the states array aliased to the result.
    b_t, c_t (B, N, G): a group's B and C are a COLUMN, broadcast along the
    lanes against the state's (N sublanes, RP lanes); dx, decay (B, G, RP)
    rows, broadcast along the sublanes. A group is taken 128 lanes (16
    vector registers) at a time. The products run on the VPU: one row of
    work a head gives the MXU nothing to do, and the kernel is bound by the
    bytes of the state."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, ns, g = b_t.shape
    rp = dx.shape[2]
    lanes = 128 if rp % 128 == 0 else rp

    def kernel(layer_ref, slot_ref, s_ref, b_ref, c_ref, dx_ref, a_ref,
               y_ref, out_ref):
        del layer_ref, slot_ref
        for i in range(g):
            bc, cc = b_ref[:, i:i + 1], c_ref[:, i:i + 1]       # (N, 1)
            for lo in range(0, rp, lanes):
                at = slice(lo, lo + lanes)
                s = (s_ref[i, :, at] * a_ref[i:i + 1, at]
                     + bc * dx_ref[i:i + 1, at])                # (N, lanes)
                out_ref[i, :, at] = s
                y_ref[i:i + 1, at] = jnp.sum(s * cc, axis=0, keepdims=True)

    def per_slot(shape):
        return pl.BlockSpec((None,) + shape, lambda i, ly, sl: (i, 0, 0))

    state_spec = pl.BlockSpec((None, None, g, ns, rp),
                              lambda i, ly, sl: (sl[i], ly[0], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[state_spec, per_slot((ns, g)), per_slot((ns, g)),
                  per_slot((g, rp)), per_slot((g, rp))],
        out_specs=[per_slot((g, rp)), state_spec],
    )
    y, states = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n, g, rp), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        # operand 2 (after the two prefetched scalars) is result 1
        input_output_aliases={2: 1},
        # a slot's state twice in and twice out of the pipeline: 4 x 4 MB
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
        name="ssm_decode",
    )(layer, slot, states, b_t, c_t, dx, decay)
    return y, states

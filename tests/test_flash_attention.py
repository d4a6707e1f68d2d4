"""Pallas flash attention kernel (ops/flash_attention.py): numerics vs plain
attention, gradients, lse, dispatcher policy, and ring-attention integration
(flash per-block math on the sp mesh)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import obs
from mxnet_tpu.ops import flash_attention as fa
from mxnet_tpu.ops.attention import (attention_impl, fused_attention,
                                     plain_attention)
from mxnet_tpu.ops.flash_attention import (_Schedule, flash_attention,
                                           flash_attention_packed,
                                           flash_attention_with_lse,
                                           flash_schedule, packed_layout)


def _rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


# Schedules as ``_BLOCK_TABLE`` would hold them for the test's (S, D): the
# cell's class at a size the interpreter walks — pairs smaller than the
# resident blocks (square and not), two heads a grid step.
_PAIRS_64 = _Schedule(256, 256, 64, 64, 2)
_PAIRS_WIDE_K = _Schedule(128, 256, 32, 64, 2)
_PAIRS_WIDE_Q = _Schedule(256, 128, 64, 32, 1)
# blocks of two pairs in a sequence of four: whole products in a loop, then
# the ones that end (forward) or begin (backward) on the diagonal
_PAIRS_LOOPED = _Schedule(128, 128, 64, 64, 2)


def _scheduled(monkeypatch, s, d, sched):
    """The table's entry for (s, d), or the blocks handed over as the
    schedule's own (``sched`` None: 64-wide blocks walked whole)."""
    if sched is None:
        return {"block_q": 64, "block_k": 64}
    monkeypatch.setitem(fa._BLOCK_TABLE, (s, d), sched)
    return {}


@pytest.mark.parametrize("sched,heads", [
    (None, 3), (_PAIRS_64, 3), (_PAIRS_64, 4), (_PAIRS_WIDE_K, 3),
    (_PAIRS_WIDE_K, 4), (_PAIRS_WIDE_Q, 3), (_PAIRS_WIDE_Q, 4),
    (_PAIRS_LOOPED, 3), (_PAIRS_LOOPED, 4)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_plain(causal, sched, heads, monkeypatch):
    """2 x 4 heads go two a grid step; 1 x 3 one a step (an odd b x h)."""
    shape = (2 if heads == 4 else 1, heads, 256, 64)
    q, k, v = (_rand(shape, i) for i in range(3))
    ref = plain_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal,
                          **_scheduled(monkeypatch, 256, 64, sched))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("s", [256, 512])
@pytest.mark.parametrize("sched", [None, _Schedule(256, 256, 64, 128, 2)])
def test_flash_forward_with_narrower_values(s, sched, monkeypatch):
    """Keys 192 wide over values 128 (latent attention's prefill): forward
    only, at today's schedule and in pairs."""
    q, k = (_rand((1, 2, s, 192), i) for i in range(2))
    v = _rand((1, 2, s, 128), 2)
    ref = plain_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True,
                          **_scheduled(monkeypatch, s, 192, sched))
    assert out.shape == v.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    with pytest.raises(NotImplementedError, match="forward-only"):
        jax.grad(lambda q: flash_attention(q, k, v, causal=True).sum())(q)


@pytest.mark.parametrize("sched", [None, _Schedule(128, 128, 32, 32, 2),
                                   _Schedule(128, 64, 32, 64, 2),
                                   _Schedule(64, 128, 64, 32, 1),
                                   _Schedule(64, 64, 32, 32, 2)])
@pytest.mark.parametrize("bwd", ["blocked", "pallas"])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_plain(causal, bwd, sched, monkeypatch):
    """Both backwards: the plain-JAX blocked fallback AND the Pallas kernel
    (interpret mode on CPU) — the Pallas path is the production default on
    real TPU and must not ship untested."""
    monkeypatch.setenv("MXNET_FLASH_BWD", bwd)
    d = 32 if sched is None else 64
    q, k, v = (_rand((1, 2, 128, d), i) for i in range(3))
    blocks = ({"block_q": 32, "block_k": 32} if sched is None
              else _scheduled(monkeypatch, 128, d, sched))

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    g_ref = jax.grad(loss(lambda *a: plain_attention(*a, causal=causal)),
                     argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss(lambda *a: flash_attention(*a, causal=causal,
                                                     **blocks)),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-4)


@pytest.mark.parametrize("sched", [None, _Schedule(16, 16, 4, 8, 2),
                                   _Schedule(8, 16, 8, 4, 1),
                                   _Schedule(8, 8, 4, 4, 2)])
@pytest.mark.parametrize("bwd", ["blocked", "pallas"])
@pytest.mark.parametrize("offset", [-4, -8, 4, 0, -3, 5, 11, -16, -21, 16,
                                    23])
def test_flash_grads_with_offset(offset, bwd, sched, monkeypatch):
    """Dynamic causal offsets (ring attention's visiting-block geometry),
    incl. NEGATIVE offsets unaligned to block_q where some rows are fully
    masked — the case whose lse=-inf rows once overflowed the Pallas
    backward to NaN —, offsets that are no multiple of a pair's rows or
    columns, and whole blocks hidden (<= -S) or seen (>= S)."""
    monkeypatch.setenv("MXNET_FLASH_BWD", bwd)
    s = 16
    q, k, v = (_rand((1, 1 if sched is None else 2, s, 16), i)
               for i in range(3))
    blocks = ({"block_q": 8, "block_k": 8} if sched is None
              else _scheduled(monkeypatch, s, 16, sched))

    def ref(qq, kk, vv):
        sc = jnp.einsum("bhqd,bhkd->bhqk", qq, kk) / np.sqrt(16)
        rows = jnp.arange(s)[:, None]
        cols = jnp.arange(s)[None, :]
        sc = jnp.where(rows + offset >= cols, sc, -1e30)
        w = jax.nn.softmax(sc, axis=-1)
        w = jnp.where(rows[None, None] + offset >= 0, w, 0.0)  # dead rows
        return jnp.einsum("bhqk,bhkd->bhqd", w, vv)

    def fl(qq, kk, vv):
        out, _ = flash_attention_with_lse(qq, kk, vv, causal=True,
                                          offset=offset, **blocks)
        return out

    np.testing.assert_allclose(np.asarray(fl(q, k, v)),
                               np.asarray(ref(q, k, v)), atol=2e-5)
    g_ref = jax.grad(lambda *a: (ref(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    g_out = jax.grad(lambda *a: (fl(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(g_ref, g_out):
        bb = np.asarray(b)
        assert np.isfinite(bb).all(), f"non-finite grads offset={offset}"
        np.testing.assert_allclose(bb, np.asarray(a), atol=5e-4)


def test_flash_schedule_follows_the_triangle_at_the_train_cells_shape():
    """What the kernels do at (1024, 64) causal, from the shapes alone: no
    more than 0.63 of the square computed (0.75 in whole 512 x 512 pairs),
    at most half the masked scores (two 512 x 512 pairs a head before), two
    heads a step; the counts are those of the pairs the kernel walks."""
    got = flash_schedule(1024, 64, True)
    sq, sk = got["sub_tile"]
    assert got["computed_share"] <= 0.63
    assert got["tiles_masked"] * sq * sk <= 2 * 512 * 512 // 2
    assert got["masked_share"] <= 0.25
    assert got["heads_per_step"] == 2
    assert 1024 % got["block_q"] == 0 and got["block_q"] % sq == 0
    assert 1024 % got["block_k"] == 0 and got["block_k"] % sk == 0
    # the counts, pair by pair: row r sees column c iff r >= c
    run = masked = 0
    for r0 in range(0, 1024, sq):
        for c0 in range(0, 1024, sk):
            run += c0 <= r0 + sq - 1
            masked += c0 <= r0 + sq - 1 and c0 + sk - 1 > r0
    assert (got["tiles_run"], got["tiles_masked"]) == (run, masked)
    assert got["computed_share"] == run * sq * sk / 1024 ** 2
    every = flash_schedule(1024, 64, False)
    assert every["computed_share"] == 1.0 and every["tiles_masked"] == 0
    assert every["sub_tile"] == got["sub_tile"]


@pytest.mark.parametrize("s", [512, 1024, 1536])
def test_flash_schedule_of_192_wide_heads(s):
    """Latent attention's prefill at longcat-omni's buckets: the sweep found
    the pairs faster there too (PERF.md §6), one head a step; sarvam's
    piece keeps whole 512-position key blocks under a query block that is
    the whole piece — each expanded from the cached rows ONCE — whatever the
    table holds; a shape nobody measured keeps (512, 512) pairs walked
    whole."""
    got = flash_schedule(s, 192, True)
    assert (got["block_q"], got["block_k"]) == (s, s)
    assert got["sub_tile"] == (256, 256) and got["heads_per_step"] == 1
    assert got["computed_share"] == (s // 256 + 1) / (2 * s // 256)
    assert fa._resolve_blocks(1024, 192, 1024, None,
                              fa._DEFAULT_SCHEDULE) == (1024, 512)
    rest = flash_schedule(4096, 128, True)
    assert rest["sub_tile"] == (512, 512) and rest["heads_per_step"] == 1
    assert (rest["block_q"], rest["block_k"]) == (512, 512)


def test_lse_matches_logsumexp():
    q, k, v = (_rand((2, 2, 128, 32), i) for i in range(3))
    _, lse = flash_attention_with_lse(q, k, v, block_q=32, block_k=32)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(32)
    ref = jax.scipy.special.logsumexp(scores, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref), atol=2e-5)


def test_odd_seq_block_shrink():
    """S=40: block sizes shrink to a divisor (8) instead of failing."""
    q, k, v = (_rand((1, 1, 40, 16), i) for i in range(3))
    ref = plain_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_dispatcher_policy(monkeypatch):
    q, k, v = (_rand((1, 1, 64, 16), i) for i in range(3))
    ref = plain_attention(q, k, v)
    for impl in ("auto", "plain", "flash"):
        monkeypatch.setenv("MXNET_ATTENTION_IMPL", impl)
        np.testing.assert_allclose(np.asarray(fused_attention(q, k, v)),
                                   np.asarray(ref), atol=2e-5)
    # the kernel takes no explicit mask: auto falls to plain, but ASKING
    # for flash must raise rather than quietly run plain attention
    mask = jnp.ones((1, 1, 64, 64), bool)
    monkeypatch.setenv("MXNET_ATTENTION_IMPL", "auto")
    fused_attention(q, k, v, mask=mask)
    monkeypatch.setenv("MXNET_ATTENTION_IMPL", "flash")
    with pytest.raises(ValueError, match="impl='flash' cannot run"):
        fused_attention(q, k, v, mask=mask)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_flash_blocks(causal):
    """Ring attention with Pallas per-block math == plain global attention."""
    from mxnet_tpu import parallel as par

    mesh = par.make_mesh({"sp": 4}, devices=jax.devices()[:4])
    b, h, s, d = 2, 2, 64, 16
    q, k, v = (_rand((b, h, s, d), i) for i in range(3))
    ref = plain_attention(q, k, v, causal=causal)
    out = par.sequence_sharded_attention(q, k, v, mesh, causal=causal,
                                         use_flash=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)
    out2 = par.sequence_sharded_attention(q, k, v, mesh, causal=causal,
                                          use_flash=False)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref), atol=2e-4)


def test_mesh_without_sp_runs_the_kernel_per_shard(monkeypatch):
    """On a dp/tp mesh the kernel sits inside shard_map (batch over dp,
    heads over tp): XLA cannot partition a Mosaic call by itself, which on
    the chip was a compile error for every multi-device mesh without sp."""
    from mxnet_tpu import parallel as par

    monkeypatch.setenv("MXNET_ATTENTION_IMPL", "flash")
    mesh = par.make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    q, k, v = (_rand((2, 2, 64, 16), i) for i in range(3))

    def attn(q, k, v):
        return par.sequence_sharded_attention(q, k, v, mesh, causal=True)

    assert "shard_map" in str(jax.make_jaxpr(attn)(q, k, v))
    np.testing.assert_allclose(
        np.asarray(attn(q, k, v)),
        np.asarray(plain_attention(q, k, v, causal=True)), atol=2e-5)
    # a head count tp does not divide stays replicated instead of failing
    q3, k3, v3 = (_rand((2, 3, 64, 16), i) for i in range(3))
    np.testing.assert_allclose(
        np.asarray(par.sequence_sharded_attention(q3, k3, v3, mesh)),
        np.asarray(plain_attention(q3, k3, v3)), atol=2e-5)


def test_ring_attention_flash_grad():
    """Gradients flow through the flash lse combine across the ring."""
    from mxnet_tpu import parallel as par

    mesh = par.make_mesh({"sp": 2}, devices=jax.devices()[:2])
    q, k, v = (_rand((1, 2, 32, 16), i) for i in range(3))

    def loss_ring(q, k, v):
        return (par.sequence_sharded_attention(q, k, v, mesh, causal=True,
                                               use_flash=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (plain_attention(q, k, v, causal=True) ** 2).sum()

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


# -- the packed entry: q, k, v as column blocks of the fused projection -------

@pytest.mark.parametrize("s", [1024, 2048])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("heads,d", [(2, 64), (2, 128), (3, 64)])
def test_packed_entry_matches_plain_and_the_heads_entry(heads, d, causal, s,
                                                        monkeypatch):
    """``flash_attention_packed`` over (B, S, 3U) — two 64-wide heads in one
    128-lane block, or a 128-wide head a block — against ``plain_attention``
    AND the (B, H, S, D) entry on the transposed heads, both kernels
    interpreted: the forward, the lse, and dq, dk, dv through a loss that
    weighs the lse too (its gradient path), at the table's schedules for
    the sequence lengths the table holds. Three 64-wide heads are 192
    columns, no whole 128-lane tiles: the entry refuses them and
    ``attention_impl`` answers ``flash``, the (B, H, S, D) entry."""
    shape = (1, heads, s, d)
    if (heads * d) % 128:
        assert packed_layout(heads * d, heads) is None
        assert attention_impl(shape, shape, fused_qkv=True) == "flash"
        with pytest.raises(ValueError, match="packed_layout"):
            flash_attention_packed(_rand((1, s, 3 * heads * d), 0), heads)
        return
    assert packed_layout(heads * d, heads) == (128, 128 // d)
    assert attention_impl(shape, shape, fused_qkv=True) == "flash_packed"
    assert attention_impl(shape, shape) == "flash"
    monkeypatch.setenv("MXNET_FLASH_BWD", "pallas")
    qkv = _rand((1, s, 3 * heads * d), 0)
    w_out, w_lse = _rand((1, s, heads * d), 1), _rand((1, heads, s), 2)

    def loss(attn):
        def f(qkv):
            out, lse = attn(qkv)
            return (out * w_out).sum() + (lse * w_lse).sum(), (out, lse)
        return jax.value_and_grad(f, has_aux=True)

    def by_heads(attn):   # a (B, H, S, D) attention over the projection
        def f(qkv):
            out, lse = attn(*fa._split_heads(qkv, heads, 3))
            return fa._join_heads(out), lse
        return f

    def plain(q, k, v):
        sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
        if causal:
            sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
        return (plain_attention(q, k, v, causal=causal),
                jax.scipy.special.logsumexp(sc, axis=-1))

    (_, got), g_got = loss(lambda x: flash_attention_packed(
        x, heads, causal=causal, with_lse=True))(qkv)
    (_, ref), g_ref = loss(by_heads(plain))(qkv)
    (_, old), g_old = loss(by_heads(functools.partial(
        flash_attention_with_lse, causal=causal)))(qkv)
    for a, b, c in zip(got + (g_got,), ref + (g_ref,), old + (g_old,)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)
        # the same cores walk the same tiles: the entries differ by the
        # order of a few float32 sums at most
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=2e-5)


def test_packed_schedule_is_reported_from_the_shapes():
    """``flash_schedule`` with the call's batch and heads: the packed
    entry's blocks and grids at the train cell's shape — two heads a
    128-lane block, half the (B, H, S, D) backward's grid steps — and None
    where the entry takes no such heads or is not asked."""
    got = flash_schedule(1024, 64, True, batch=4, heads=16)
    assert got["packed"] == {
        "layout": "packed", "block_lanes": 128, "heads_per_block": 2,
        "grid_fwd": (32, 1024 // got["block_q"]),
        "grid_bwd": (32, 1024 // got["block_k"])}
    assert flash_schedule(1024, 128, True, 2, 8)["packed"]["grid_fwd"][0] == 16
    assert flash_schedule(1024, 256, True, 2, 4)["packed"]["block_lanes"] == 256
    assert flash_schedule(1024, 64, True, batch=4, heads=3)["packed"] is None
    assert flash_schedule(1024, 64, True)["packed"] is None


def _attention_counts():
    counters = obs.metrics.snapshot()["counters"]
    return tuple(counters.get("attention.impl." + impl, 0)
                 for impl in ("flash_packed", "flash", "plain"))


@pytest.mark.parametrize("seq,masked", [(128, False), (1024, True)])
def test_attention_layer_off_the_packed_path_is_the_layer_it_was(seq, masked):
    """``MultiHeadAttention`` at seq 128 (plain attention by the policy) and
    at seq 1,024 under a mask (the kernels take none): the jaxpr of the
    lines it ran before the packed entry — reshape, split, three
    transposes, ``fused_attention``, transpose back, ``proj`` —, no
    ``pallas_call`` in it, the same output bit for bit, and ONE count on
    ``attention.impl.plain`` for the traced layer."""
    from mxnet_tpu import nd
    from mxnet_tpu.models.transformer import MultiHeadAttention
    from mxnet_tpu.ndarray.ndarray import invoke_fn
    from mxnet_tpu.parallel.functional import functionalize

    units, heads = 128, 2
    layer = MultiHeadAttention(units, heads, causal=not masked,
                               prefix="attn_")
    layer.initialize()
    names, apply = functionalize(layer)
    params = {p.name: p.data()._data for p in layer._iter_params()}
    x = _rand((1, seq, units), 0)
    ins = (x,) + ((jnp.tril(jnp.ones((1, 1, seq, seq), bool)),) * masked)

    def before(params, x, mask=None):   # the parent commit's hybrid_forward
        def forward(x, mask=None):
            qkv = layer.qkv(x).reshape((1, seq, 3, heads, units // heads))
            q, k, v = (t.transpose((0, 2, 1, 3)) for t in nd.split(
                qkv, num_outputs=3, axis=2, squeeze_axis=True))
            out = invoke_fn(
                lambda q, k, v, m=None: fused_attention(
                    q, k, v, mask=m, causal=not masked),
                [q, k, v] + ([mask] if mask is not None else []))
            return layer.proj(out.transpose((0, 2, 1, 3)).reshape(
                (1, seq, units)))
        return functionalize_call(forward, params, x, mask)

    def functionalize_call(forward, params, *ins):
        layer_forward, layer.hybrid_forward = layer.hybrid_forward, (
            lambda F, *a: forward(*a))
        try:
            return apply(params, *(i for i in ins if i is not None))[0]
        finally:
            layer.hybrid_forward = layer_forward

    obs.enable()
    try:
        obs.reset()
        jaxpr = jax.make_jaxpr(lambda p, *i: apply(p, *i)[0])(params, *ins)
        assert _attention_counts() == (0, 0, 1)
    finally:
        obs.disable()
    assert "pallas_call" not in str(jaxpr)
    assert str(jaxpr) == str(jax.make_jaxpr(before)(params, *ins))
    np.testing.assert_array_equal(np.asarray(apply(params, *ins)[0]),
                                  np.asarray(before(params, *ins)))


def test_attention_layer_takes_the_packed_entry_at_1024():
    """No mask, no mesh, seq 1,024, two 64-wide heads: ONE count on
    ``attention.impl.flash_packed``, the two kernels over the projection as
    it lies — no transpose of heads in the layer's jaxpr — and the output
    of the same layer forced onto plain attention."""
    from mxnet_tpu.models.transformer import MultiHeadAttention
    from mxnet_tpu.parallel.functional import functionalize

    layer = MultiHeadAttention(128, 2, causal=True, prefix="attn_")
    layer.initialize()
    _, apply = functionalize(layer)
    params = {p.name: p.data()._data for p in layer._iter_params()}
    x = _rand((1, 1024, 128), 0)
    obs.enable()
    try:
        obs.reset()
        jaxpr = jax.make_jaxpr(lambda p, x: apply(p, x)[0])(params, x)
        assert _attention_counts() == (1, 0, 0)
    finally:
        obs.disable()
    assert "pallas_call" in str(jaxpr)
    assert "permutation=(0, 2, 1, 3)" not in str(jaxpr)   # no head is moved
    got = apply(params, x)[0]
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("MXNET_ATTENTION_IMPL", "plain")
        ref = apply(params, x)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)

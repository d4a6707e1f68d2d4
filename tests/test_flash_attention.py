"""Pallas flash attention kernel (ops/flash_attention.py): numerics vs plain
attention, gradients, lse, dispatcher policy, and ring-attention integration
(flash per-block math on the sp mesh)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import flash_attention as fa
from mxnet_tpu.ops.attention import fused_attention, plain_attention
from mxnet_tpu.ops.flash_attention import (_Schedule, flash_attention,
                                           flash_attention_with_lse,
                                           flash_schedule)


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

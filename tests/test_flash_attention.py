"""Pallas flash attention kernel (ops/flash_attention.py): numerics vs plain
attention, gradients, lse, dispatcher policy, and ring-attention integration
(flash per-block math on the sp mesh)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops.attention import fused_attention, plain_attention
from mxnet_tpu.ops.flash_attention import (flash_attention,
                                           flash_attention_with_lse)


def _rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_plain(causal):
    q, k, v = (_rand((2, 3, 256, 64), i) for i in range(3))
    ref = plain_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("bwd", ["blocked", "pallas"])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_plain(causal, bwd, monkeypatch):
    """Both backwards: the plain-JAX blocked fallback AND the Pallas kernel
    (interpret mode on CPU) — the Pallas path is the production default on
    real TPU and must not ship untested."""
    monkeypatch.setenv("MXNET_FLASH_BWD", bwd)
    q, k, v = (_rand((1, 2, 128, 32), i) for i in range(3))

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    g_ref = jax.grad(loss(lambda *a: plain_attention(*a, causal=causal)),
                     argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss(lambda *a: flash_attention(*a, causal=causal,
                                                     block_q=32, block_k=32)),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-4)


@pytest.mark.parametrize("bwd", ["blocked", "pallas"])
@pytest.mark.parametrize("offset", [-4, -8, 4, 0])
def test_flash_grads_with_offset(offset, bwd, monkeypatch):
    """Dynamic causal offsets (ring attention's visiting-block geometry),
    incl. NEGATIVE offsets unaligned to block_q where some rows are fully
    masked — the case whose lse=-inf rows once overflowed the Pallas
    backward to NaN."""
    from mxnet_tpu.ops.flash_attention import flash_attention_with_lse

    monkeypatch.setenv("MXNET_FLASH_BWD", bwd)
    s = 16
    q, k, v = (_rand((1, 1, s, 16), i) for i in range(3))

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
                                          offset=offset, block_q=8,
                                          block_k=8)
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

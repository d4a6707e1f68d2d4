"""parallel/ tests on the 8-virtual-device CPU mesh (conftest).

The reference's analog is the dist kvstore nightly tests run via the local
tracker (SURVEY.md §4); here the assertions are numeric equivalence between
sharded and single-device execution.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu import parallel as par
from mxnet_tpu.gluon import nn
from mxnet_tpu.models import bert_tiny, bert_sharding_rules, TransformerLM


def test_make_mesh():
    mesh = par.make_mesh({"dp": 2, "tp": 4})
    assert par.mesh_axes(mesh) == {"dp": 2, "tp": 4}
    mesh = par.make_mesh({"dp": -1, "tp": 2})
    assert par.mesh_axes(mesh) == {"dp": 4, "tp": 2}
    # fully-specified mesh smaller than the host takes a device subset
    # (reference analog: ctx=[mx.gpu(i) for i in ...])
    mesh = par.make_mesh({"dp": 3})
    assert par.mesh_axes(mesh) == {"dp": 3}
    assert mesh.devices.size == 3
    with pytest.raises(ValueError):
        par.make_mesh({"dp": 16})


def test_sharding_rules_pruning():
    rules = bert_sharding_rules()
    mesh = par.make_mesh({"dp": 2, "tp": 4})
    assert rules.spec_for("bert0_enc_layer0_attn_qkv_weight", (192, 64), mesh) \
        == P("tp")  # trailing None pruned
    assert rules.spec_for("bert0_enc_layer0_attn_proj_weight", (64, 64), mesh) \
        == P(None, "tp")
    # axis that does not divide -> replicated
    assert rules.spec_for("x_qkv_weight", (6, 5), mesh) == P()
    # mesh without tp -> replicated
    dp_mesh = par.make_mesh({"dp": 8})
    assert rules.spec_for("bert0_enc_layer0_attn_qkv_weight", (192, 64), dp_mesh) == P()


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_plain(causal):
    from mxnet_tpu.parallel.ring_attention import plain_attention

    B, H, S, D = 2, 2, 16, 8
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    ref = plain_attention(q, k, v, causal=causal)
    mesh = par.make_mesh({"sp": 8})
    # jitted, as the trainer runs it: an eager shard_map dispatches the
    # unrolled ring op by op across 8 devices and takes 7x as long
    out = jax.jit(lambda q, k, v: par.sequence_sharded_attention(
        q, k, v, mesh, causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-4, atol=2e-5)


def test_ring_attention_dp_tp_sp_mesh():
    from mxnet_tpu.parallel.ring_attention import plain_attention

    B, H, S, D = 2, 2, 8, 4
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    mesh = par.make_mesh({"dp": 2, "sp": 2, "tp": 2})
    out = par.sequence_sharded_attention(q, k, v, mesh, causal=False)
    ref = plain_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-4, atol=2e-5)


def test_functionalize_batchnorm_aux():
    net = nn.HybridSequential()
    net.add(nn.Dense(4, in_units=3), nn.BatchNorm(in_channels=4))
    net.initialize()
    net(nd.ones((2, 3)))  # resolve shapes
    names, apply = par.functionalize(net, train=True)
    vals = {p.name: p.data()._data for p in net._iter_params()}
    out, aux = apply(vals, jnp.ones((2, 3)))
    assert any("running_mean" in k for k in aux)
    assert any("running_var" in k for k in aux)


def test_sharded_trainer_dp_matches_serial():
    """DP-sharded step == single-device SGD (the known-value kvstore test idea)."""

    def build():
        mx.random.seed(7)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu", in_units=8), nn.Dense(4, in_units=16))
        net.initialize()
        return net

    rng = np.random.RandomState(3)
    x = rng.randn(16, 8).astype(np.float32)
    y = rng.randint(0, 4, 16).astype(np.int32)
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()

    # serial reference via autograd + plain SGD math
    net_a = build()
    with mx.autograd.record():
        loss = loss_fn(net_a(nd.array(x)), nd.array(y)).mean()
    loss.backward()
    lr = 0.5
    expected = {k: p.data().asnumpy() - lr * p.grad().asnumpy()
                for k, p in net_a._collect_params_with_prefix().items()}

    net_b = build()
    mesh = par.make_mesh({"dp": 8})
    trainer = par.ShardedTrainer(net_b, loss_fn, mesh, optimizer="sgd",
                                 optimizer_params={"learning_rate": lr})
    step_loss = trainer.step(nd.array(x), nd.array(y))
    assert np.isfinite(float(step_loss.asnumpy()))
    trainer.sync_to_net()
    for k, p in net_b._collect_params_with_prefix().items():
        np.testing.assert_allclose(p.data().asnumpy(), expected[k],
                                   rtol=1e-4, atol=1e-5)


def test_sharded_trainer_bert_dp_tp_sp():
    """Full train step of the flagship on a dp×sp×tp mesh; loss decreases."""
    net = bert_tiny(vocab_size=100, dropout=0.0, max_length=32)
    net.initialize()
    x = nd.array(np.random.RandomState(0).randint(0, 100, (8, 16)).astype(np.int32))
    net(x)  # resolve deferred shapes
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = par.make_mesh({"dp": 2, "sp": 2, "tp": 2})
    trainer = par.ShardedTrainer(net, loss_fn, mesh, rules=bert_sharding_rules(),
                                 optimizer="adam",
                                 optimizer_params={"learning_rate": 1e-3})
    labels = x  # autoencoding objective for the smoke test
    losses = [float(trainer.step(x, labels).asnumpy()) for _ in range(5)]
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_transformer_lm_is_causal():
    net = TransformerLM(vocab_size=50, units=32, hidden_size=64, num_layers=1,
                        num_heads=2, max_length=16, dropout=0.0)
    net.initialize()
    x1 = np.zeros((1, 8), np.int32)
    x2 = x1.copy()
    x2[0, -1] = 7  # change only the LAST token
    o1 = net(nd.array(x1)).asnumpy()
    o2 = net(nd.array(x2)).asnumpy()
    # earlier positions must be unaffected by the future token
    np.testing.assert_allclose(o1[0, :-1], o2[0, :-1], rtol=1e-5, atol=1e-6)
    assert np.abs(o1[0, -1] - o2[0, -1]).max() > 1e-4


def test_bert_forward_shape():
    net = bert_tiny(vocab_size=64, max_length=32)
    net.initialize()
    out = net(nd.array(np.zeros((2, 10), np.int32)))
    assert out.shape == (2, 10, 64)


def test_sharded_trainer_bf16_compute():
    """AMP: bf16 fwd/bwd, fp32 master weights, loss decreases."""
    import jax.numpy as jnp

    net = bert_tiny(vocab_size=64, dropout=0.0, max_length=32)
    net.initialize()
    x = nd.array(np.random.RandomState(1).randint(0, 64, (4, 16)).astype(np.int32))
    net(x)
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = par.make_mesh({"dp": 2}, devices=__import__("jax").devices()[:2])
    trainer = par.ShardedTrainer(net, loss_fn, mesh, optimizer="adam",
                                 optimizer_params={"learning_rate": 1e-3},
                                 compute_dtype="bfloat16")
    losses = [float(trainer.step(x, x).asnumpy()) for _ in range(5)]
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    # master weights stay fp32 across steps (incl. BN/LN aux merges)
    for n, v in trainer.param_vals.items():
        if jnp.issubdtype(v.dtype, jnp.floating):
            assert v.dtype == jnp.float32, (n, v.dtype)


def test_sharded_trainer_bf16_conv_bn():
    """AMP on a conv+BN net — the ResNet-shaped path that crashed in round 2
    (bf16 conv input meeting f32 BN output / frozen deferred BN params)."""
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(8, 3, padding=1, in_channels=3))
        net.add(nn.BatchNorm())  # in_channels deferred — the failing config
        net.add(nn.Activation("relu"))
        net.add(nn.Conv2D(8, 3, padding=1, in_channels=8))
        net.add(nn.BatchNorm())
        net.add(nn.Flatten())
        net.add(nn.Dense(4))
    net.initialize()
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = par.make_mesh({"dp": 2}, devices=jax.devices()[:2])
    trainer = par.ShardedTrainer(net, loss_fn, mesh, optimizer="sgd",
                                 optimizer_params={"learning_rate": 0.1,
                                                   "momentum": 0.9},
                                 compute_dtype="bfloat16")
    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(4, 3, 8, 8).astype(np.float32))
    y = nd.array(rng.randint(0, 4, 4).astype(np.int32))
    losses = [float(trainer.step(x, y).asnumpy()) for _ in range(5)]
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    gammas = [n for n in trainer.param_vals if "gamma" in n]
    rmeans = [n for n in trainer.param_vals if "running_mean" in n]
    assert gammas and rmeans
    # BN scale/shift are trained (deferred params captured), stats stay f32
    # master dtype and actually move
    for g in gammas:
        assert g in trainer._grad_names
    for rm in rmeans:
        assert trainer.param_vals[rm].dtype == jnp.float32
        assert bool(jnp.any(trainer.param_vals[rm] != 0))


def test_sharded_trainer_preprocess_uint8():
    """preprocess= fuses input normalization into the step program: uint8
    batches train a conv+BN net (deferred shapes resolve through preprocess)."""
    import jax.numpy as jnp

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(8, 3, padding=1, in_channels=3))
        net.add(nn.BatchNorm())
        net.add(nn.Activation("relu"))
        net.add(nn.Flatten())
        net.add(nn.Dense(4))
    net.initialize()
    mean = jnp.asarray(np.full((3, 1, 1), 128.0, np.float32))

    def preprocess(x):
        if x.dtype == jnp.uint8:
            return (x.astype(jnp.float32) - mean) / 64.0
        return x

    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = par.make_mesh({"dp": 2}, devices=jax.devices()[:2])
    trainer = par.ShardedTrainer(net, loss_fn, mesh, optimizer="sgd",
                                 optimizer_params={"learning_rate": 0.1},
                                 preprocess=preprocess)
    rng = np.random.RandomState(0)
    x = nd.array(rng.randint(0, 255, (8, 3, 8, 8)).astype(np.uint8))
    y = nd.array(rng.randint(0, 4, 8).astype(np.float32))  # f32 labels: in-jit cast
    losses = [float(trainer.step(x, y).asnumpy()) for _ in range(5)]
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_sharded_trainer_remat_matches_plain():
    """remat=True (jax.checkpoint over the forward) must train identically
    to the plain step — only memory/recompute differ."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models import transformer_lm

    rng = np.random.RandomState(0)
    x = nd.array(rng.randint(0, 40, (2, 16)).astype(np.int32))
    losses = {}
    for remat in (False, True):
        mx.random.seed(11)
        net = transformer_lm(vocab_size=40, units=16, hidden_size=32,
                             num_layers=1, num_heads=2, max_length=16,
                             dropout=0.0)
        net.initialize()
        mesh = par.make_mesh({"dp": 1})
        trainer = par.ShardedTrainer(
            net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), mesh,
            optimizer="adam", optimizer_params={"learning_rate": 1e-2},
            remat=remat)
        ls = [float(trainer.step(x, x).asnumpy()) for _ in range(3)]
        losses[remat] = ls
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5)
    assert losses[True][-1] < losses[True][0]


def test_grad_accum_matches_full_batch():
    """grad_accum=k (micro-batch scan, one update) must produce the same
    parameters as the monolithic full-batch step (CE-mean losses average
    exactly over equal micro-batches; no BN in the net)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu import parallel as par

    def build(grad_accum):
        mx.random.seed(0)
        net = mx.gluon.nn.HybridSequential()
        net.add(mx.gluon.nn.Dense(16, in_units=8))
        net.add(mx.gluon.nn.Dense(4, in_units=16))
        net.initialize()
        loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
        mesh = par.make_mesh({"dp": 1}, devices=jax.devices()[:1])
        return net, par.ShardedTrainer(
            net, loss_fn, mesh, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1}, donate=False,
            grad_accum=grad_accum)

    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(8, 8).astype(np.float32))
    y = nd.array(rng.randint(0, 4, 8).astype(np.int32))

    net1, t1 = build(1)
    l1 = t1.step(x, y)
    net4, t4 = build(4)
    l4 = t4.step(x, y)
    np.testing.assert_allclose(float(l1.asnumpy()), float(l4.asnumpy()),
                               rtol=1e-5)
    # align by the trainers' structural order: param_vals returns from the
    # jitted step with pytree-SORTED keys, and lexicographic order flips
    # when the global name counter crosses a decade (dense10 < dense9)
    v1 = [t1.param_vals[n] for n in t1._grad_names]
    v4 = [t4.param_vals[n] for n in t4._grad_names]
    for i, (a, b) in enumerate(zip(v1, v4)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg=f"param #{i} diverged")


def test_grad_accum_rejects_indivisible_batch():
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu import parallel as par

    net = mx.gluon.nn.Dense(2, in_units=3)
    net.initialize()
    mesh = par.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr = par.ShardedTrainer(net, mx.gluon.loss.L2Loss(), mesh,
                            grad_accum=3)
    x = nd.array(np.ones((4, 3), np.float32))
    y = nd.array(np.ones((4, 2), np.float32))
    with pytest.raises(Exception, match="grad_accum"):
        tr.step(x, y)

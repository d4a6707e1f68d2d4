"""Fused update engine (optimizer/fused.py, docs/PERFORMANCE.md).

- differential tests: EVERY registered optimizer, fused engine vs the
  per-parameter eager oracle (MXNET_FUSED_UPDATE=0), fp32 tight / bf16 loose,
  including the AMP loss-scale skip-step and clip-by-global-norm fusions;
- the dispatch guarantee: a gluon Trainer.step updates a resnet50_v1's 161
  parameters in <= 2 compiled device programs (profiler.count_dispatches);
- checkpoint round-trips of the device-resident optimizer state stay bitwise;
- the TraceLinter's update-retrace-churn rule.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd, profiler
from mxnet_tpu import optimizer as opt_mod
from mxnet_tpu.gluon import Trainer, nn
from mxnet_tpu.ndarray import NDArray

SHAPES = [(5, 4), (3,), (2, 3, 2)]

# non-default knobs so the stateful / bounded branches are exercised
SPECIAL_KWARGS = {
    "sgd": {"momentum": 0.9, "wd": 0.01},
    "nag": {"momentum": 0.9},
    "signum": {"momentum": 0.9, "wd_lh": 0.001},
    "adamw": {"wd": 0.01},
    "lamb": {"lower_bound": 0.01, "upper_bound": 10.0},
    "rmsprop": {"centered": True},
    "dcasgd": {"momentum": 0.5},
    "lars": {"wd": 0.001},
}


def _fixed_env(val):
    prev = os.environ.get("MXNET_FUSED_UPDATE")
    if val is None:
        os.environ.pop("MXNET_FUSED_UPDATE", None)
    else:
        os.environ["MXNET_FUSED_UPDATE"] = val
    return prev


def _run_updater(name, kwargs, fused, steps=3, dtype=np.float32,
                 multi_precision=False, lr_mult=None, scheduler=False):
    prev = _fixed_env("1" if fused else "0")
    try:
        mx.random.seed(11)
        kw = dict(kwargs)
        if scheduler:
            from mxnet_tpu.optimizer import lr_scheduler

            kw["lr_scheduler"] = lr_scheduler.FactorScheduler(step=1,
                                                              factor=0.9)
        opt = opt_mod.create(name, rescale_grad=1.0 / 8,
                             multi_precision=multi_precision, **kw)
        if lr_mult:
            opt.set_lr_mult(lr_mult)
        up = opt_mod.Updater(opt)
        rng = np.random.RandomState(42)
        ws = [NDArray(rng.randn(*s).astype(np.float32), dtype=dtype)
              for s in SHAPES]
        idx = list(range(len(ws)))
        for _ in range(steps):
            gs = [NDArray(rng.randn(*s).astype(np.float32), dtype=dtype)
                  for s in SHAPES]
            up.update_batch(idx, gs, ws)
        states = [up.states[i] for i in idx]
        return [w.asnumpy().astype(np.float32) for w in ws], states, up
    finally:
        _fixed_env(prev)


def _flat_states(states):
    out = []

    def rec(s):
        if s is None:
            return
        if isinstance(s, tuple):
            for x in s:
                rec(x)
        else:
            out.append(s.asnumpy().astype(np.float32))

    for s in states:
        rec(s)
    return out


@pytest.mark.parametrize("name", sorted(opt_mod.optimizer._REGISTRY))
def test_fused_matches_eager_oracle(name):
    """Every registered optimizer: fused one-program update == eager loop."""
    kw = SPECIAL_KWARGS.get(name, {})
    wf, sf, upf = _run_updater(name, kw, fused=True)
    we, se, upe = _run_updater(name, kw, fused=False)
    if opt_mod.fused.supports(upf.optimizer):
        assert upf._engine is not None and upf._engine.exec_count == 3, name
    # "fp32 tight": the only permitted slack is python-f64 vs traced-f32
    # evaluation of scalar coefficients like beta**t
    for a, b in zip(wf, we):
        np.testing.assert_allclose(a, b, rtol=5e-6, atol=5e-6, err_msg=name)
    for a, b in zip(_flat_states(sf), _flat_states(se)):
        np.testing.assert_allclose(a, b, rtol=5e-6, atol=5e-5, err_msg=name)
    # counters must agree too (they drive bias correction after resume)
    assert upf.optimizer.num_update == upe.optimizer.num_update
    assert upf.optimizer._index_update_count == upe.optimizer._index_update_count


@pytest.mark.parametrize("name", ["sgd", "adam"])
def test_fused_matches_eager_with_scheduler_and_mults(name):
    """lr scheduler + per-index lr multipliers ride the traced lr vector —
    no retrace, same numbers."""
    kw = SPECIAL_KWARGS.get(name, {})
    mults = {0: 0.5, 2: 2.0}
    wf, _, upf = _run_updater(name, kw, fused=True, lr_mult=mults,
                              scheduler=True)
    we, _, _ = _run_updater(name, kw, fused=False, lr_mult=mults,
                            scheduler=True)
    for a, b in zip(wf, we):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)
    assert len(upf._engine.compile_log) == 1, \
        "per-step lr change must not recompile the fused program"


@pytest.mark.parametrize("name", ["sgd", "adam"])
def test_fused_bf16_multi_precision(name):
    """bf16 weights with fp32 master copy: loose tolerance."""
    kw = dict(SPECIAL_KWARGS.get(name, {}))
    import jax.numpy as jnp

    wf, sf, _ = _run_updater(name, kw, fused=True, dtype=jnp.bfloat16,
                             multi_precision=True)
    we, se, _ = _run_updater(name, kw, fused=False, dtype=jnp.bfloat16,
                             multi_precision=True)
    for a, b in zip(wf, we):
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2)
    for a, b in zip(_flat_states(sf), _flat_states(se)):
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# loss-scaler + global-norm fusions
# ---------------------------------------------------------------------------

def _scaler_run(fused, inject_inf_at=1, steps=3):
    from mxnet_tpu.amp import LossScaler

    prev = _fixed_env("1" if fused else "0")
    try:
        opt = opt_mod.create("sgd", learning_rate=0.1, momentum=0.9)
        up = opt_mod.Updater(opt)
        scaler = LossScaler()
        scaler.loss_scale = 1024.0
        rng = np.random.RandomState(3)
        ws = [NDArray(rng.randn(*s).astype(np.float32)) for s in SHAPES]
        idx = list(range(len(ws)))
        scales = []
        for step in range(steps):
            gs = [NDArray(rng.randn(*s).astype(np.float32) * 1024.0)
                  for s in SHAPES]
            if step == inject_inf_at:
                bad = np.array(gs[1].asnumpy())  # asnumpy views are read-only
                bad.reshape(-1)[0] = np.inf
                gs[1] = NDArray(bad)
            up.update_batch(idx, gs, ws, loss_scaler=scaler)
            scales.append(float(scaler.loss_scale))
        return [w.asnumpy() for w in ws], scales, scaler
    finally:
        _fixed_env(prev)


def test_loss_scale_skip_step_fused_vs_eager():
    wf, scf, sc_f = _scaler_run(True)
    we, sce, sc_e = _scaler_run(False)
    for a, b in zip(wf, we):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)
    # overflow step halved the scale in both paths, on schedule
    assert scf == sce
    assert scf[1] == pytest.approx(512.0)
    assert bool(sc_f.last_overflow) is False  # last step was finite


def test_loss_scale_skip_leaves_weights_unchanged():
    from mxnet_tpu.amp import LossScaler

    opt = opt_mod.create("sgd", learning_rate=0.1)
    up = opt_mod.Updater(opt)
    scaler = LossScaler()
    w = NDArray(np.ones((4,), np.float32))
    before = w.asnumpy().copy()
    g = NDArray(np.full((4,), np.nan, np.float32))
    up.update_batch([0], [g], [w], loss_scaler=scaler)
    np.testing.assert_array_equal(w.asnumpy(), before)
    assert bool(scaler.last_overflow) is True


def test_clip_global_norm_fused_vs_eager_and_expected():
    def run(fused):
        prev = _fixed_env("1" if fused else "0")
        try:
            opt = opt_mod.create("sgd", learning_rate=1.0)
            up = opt_mod.Updater(opt)
            ws = [NDArray(np.zeros((2,), np.float32)),
                  NDArray(np.zeros((3,), np.float32))]
            gs = [NDArray(np.array([3.0, 0.0], np.float32)),
                  NDArray(np.array([0.0, 4.0, 0.0], np.float32))]
            up.update_batch([0, 1], gs, ws, clip_global_norm=1.0)
            return [w.asnumpy() for w in ws]
        finally:
            _fixed_env(prev)

    wf = run(True)
    we = run(False)
    # ||g|| = 5 -> grads scaled by 1/5; sgd lr=1 -> w = -g/5
    expect = [np.array([-0.6, 0.0], np.float32),
              np.array([0.0, -0.8, 0.0], np.float32)]
    for a, b, e in zip(wf, we, expect):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)
        np.testing.assert_allclose(a, e, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# trainer / module / kvstore wiring
# ---------------------------------------------------------------------------

def test_trainer_step_single_compiled_program():
    net = nn.Dense(2, in_units=3)
    net.initialize()
    tr = Trainer(net.collect_params(), "adam", {"learning_rate": 0.01})
    x = nd.ones((4, 3))
    for _ in range(2):  # warm the compile cache
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        tr.step(4)
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    with profiler.count_dispatches() as c:
        tr.step(4)
    assert c.total_compiled <= 2, c.as_dict()


def test_module_update_fused():
    from mxnet_tpu import symbol as sym
    from mxnet_tpu.module import Module
    from mxnet_tpu.io import NDArrayIter

    data = sym.var("data")
    net = sym.FullyConnected(data, num_hidden=4, name="fc1")
    net = sym.SoftmaxOutput(net, name="softmax")
    x = np.random.RandomState(0).randn(8, 5).astype(np.float32)
    y = np.array([0, 1, 2, 3, 0, 1, 2, 3], np.float32)
    it = NDArrayIter(x, y, batch_size=4)
    mod = Module(net, context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    batch = next(iter(it))
    for _ in range(2):
        mod.forward(batch)
        mod.backward()
        mod.update()
    with profiler.count_dispatches() as c:
        mod.update()
    assert c.total_compiled <= 2, c.as_dict()
    eng = mod._updater._engine
    assert eng is not None and eng.exec_count == 3


def test_kvstore_local_update_batched_push():
    from mxnet_tpu import kvstore as kv_mod

    kv = kv_mod.create("local")
    opt = opt_mod.create("sgd", learning_rate=0.1)
    kv.set_optimizer(opt)
    rng = np.random.RandomState(1)
    ws = {i: NDArray(rng.randn(4).astype(np.float32)) for i in range(3)}
    for i, w in ws.items():
        kv.init(i, w)
    grads = [NDArray(rng.randn(4).astype(np.float32)) for _ in range(3)]
    # multi-key push applies the whole batch through the fused engine
    kv.push(list(ws), grads)
    outs = [NDArray(np.zeros(4, np.float32)) for _ in range(3)]
    kv.pull(list(ws), out=outs)
    eng = kv._updater._engine
    assert eng is not None and eng.exec_count == 1
    for i, o in enumerate(outs):
        expect = ws[i].asnumpy() - 0.1 * grads[i].asnumpy()
        np.testing.assert_allclose(o.asnumpy(), expect, rtol=1e-5, atol=1e-6)


def test_kvstore_broadcast_push_applies_sequentially():
    """push(key, [v1, v2]) (the multi-value broadcast form) must apply BOTH
    updates, not last-write-wins through the fused snapshot."""
    from mxnet_tpu import kvstore as kv_mod

    kv = kv_mod.create("local")
    kv.set_optimizer(opt_mod.create("sgd", learning_rate=1.0))
    kv.init(0, NDArray(np.zeros(2, np.float32)))
    g1 = NDArray(np.array([1.0, 0.0], np.float32))
    g2 = NDArray(np.array([2.0, 0.0], np.float32))
    kv.push(0, [g1, g2])
    out = NDArray(np.zeros(2, np.float32))
    kv.pull(0, out=out)
    np.testing.assert_allclose(out.asnumpy(), [-3.0, 0.0], rtol=1e-6)


def test_update_on_kvstore_rejects_fused_only_features():
    from mxnet_tpu import kvstore as kv_mod

    net = nn.Dense(1, in_units=2)
    net.initialize()
    kv = kv_mod.create("device")
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1},
                 kvstore=kv, update_on_kvstore=True, clip_global_norm=1.0)
    x = nd.ones((2, 2))
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    with pytest.raises(ValueError, match="update_on_kvstore"):
        tr.step(2)


def test_trainer_state_roundtrip_bitwise():
    """Device-resident optimizer state survives a checkpoint round-trip
    bitwise: resumed training == uninterrupted training, exactly."""
    def steps(tr, net, x, n):
        for _ in range(n):
            with autograd.record():
                loss = (net(x) ** 2).sum()
            loss.backward()
            tr.step(4)

    def build():
        mx.random.seed(5)
        np.random.seed(5)
        net = nn.Dense(2, in_units=3)
        net.initialize()
        tr = Trainer(net.collect_params(), "adam", {"learning_rate": 0.05})
        return net, tr

    x = nd.ones((4, 3))
    net_a, tr_a = build()
    steps(tr_a, net_a, x, 4)  # uninterrupted

    net_b, tr_b = build()
    steps(tr_b, net_b, x, 2)
    snap = tr_b.get_checkpoint_state()  # capture mid-run
    params = [p.data().asnumpy().copy() for p in tr_b._params]
    # clobber then restore (simulated crash/resume); match by position —
    # gluon auto-naming counters differ between builds
    net_c, tr_c = build()
    for p, v in zip(tr_c._params, params):
        p.set_data(NDArray(v))
    tr_c.set_checkpoint_state(snap)
    steps(tr_c, net_c, x, 2)

    for pa, pc in zip(tr_a._params, tr_c._params):
        np.testing.assert_array_equal(pa.data().asnumpy(),
                                      pc.data().asnumpy())


def test_save_load_states_batched_transfer(tmp_path):
    net = nn.Dense(2, in_units=3)
    net.initialize()
    tr = Trainer(net.collect_params(), "adam", {"learning_rate": 0.01})
    x = nd.ones((4, 3))
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    tr.step(4)
    f = str(tmp_path / "t.states")
    tr.save_states(f)
    before = {k: _flat_states([v]) for k, v in tr._updaters[0].states.items()}
    tr.load_states(f)
    after = {k: _flat_states([v]) for k, v in tr._updaters[0].states.items()}
    for k in before:
        for a, b in zip(before[k], after[k]):
            np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# TraceLinter: update-retrace-churn
# ---------------------------------------------------------------------------

def test_tracelinter_update_retrace_churn():
    from mxnet_tpu.analysis.trace import TraceLinter

    net = nn.Dense(2, in_units=3)
    net.initialize()
    tr = Trainer(net.collect_params(), "sgd",
                 {"learning_rate": 0.1, "momentum": 0.9})
    x = nd.ones((4, 3))
    tl = TraceLinter(retrace_threshold=3)
    with tl.watch(tr):
        for i in range(5):
            # the anti-pattern: rebinding a STATIC hyperparameter per step
            tr.optimizer.momentum = 0.9 - 0.01 * i
            with autograd.record():
                loss = (net(x) ** 2).sum()
            loss.backward()
            tr.step(4)
    rep = tl.report()
    kinds = [f.rule_id for f in rep.findings]
    assert "update-retrace-churn" in kinds, kinds
    # the diagnosis names the varying component
    churn = [f for f in rep.findings if f.rule_id == "update-retrace-churn"][0]
    assert "static hyperparameters" in churn.message


def test_tracelinter_no_churn_on_lr_schedule():
    from mxnet_tpu.analysis.trace import TraceLinter

    net = nn.Dense(2, in_units=3)
    net.initialize()
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    x = nd.ones((4, 3))
    tl = TraceLinter(retrace_threshold=3)
    with tl.watch(tr):
        for i in range(5):
            tr.set_learning_rate(0.1 / (i + 1))  # traced: no recompiles
            with autograd.record():
                loss = (net(x) ** 2).sum()
            loss.backward()
            tr.step(4)
    rep = tl.report()
    assert "update-retrace-churn" not in [f.rule_id for f in rep.findings]
    assert len(tr._updaters[0]._engine.compile_log) == 1


# ---------------------------------------------------------------------------
# the dispatch-count guarantee (counts programs, so the CPU can hold it)
# ---------------------------------------------------------------------------

def _update_phase_dispatches(model, optimizer, fused):
    """(number of parameters, DispatchCounts of one ``Trainer.step``'s
    update phase) for a model-zoo net after two whole steps."""
    from mxnet_tpu.gluon.model_zoo import vision

    prev = _fixed_env("1" if fused else "0")
    try:
        net = vision.get_model(model)
        net.initialize()
        x = nd.ones((1, 3, 32, 32))
        net(x)  # materialize deferred shapes before counting params
        tr = Trainer(net.collect_params(), optimizer,
                     {"learning_rate": 0.01})
        for step in range(3):
            with autograd.record():
                out = net(x)
                loss = (out * out).sum()
            loss.backward()
            with profiler.count_dispatches() as c:
                tr.step(1)
        return len(tr._params), c  # the third step's: nothing compiles
    finally:
        _fixed_env(prev)


@pytest.mark.perf
def test_resnet50_update_dispatches():
    """The acceptance bar: a Trainer.step over resnet50_v1 (161 params)
    executes <= 2 compiled device programs in its update phase (vs one per
    parameter on the eager path)."""
    n_params, c = _update_phase_dispatches("resnet50_v1", "sgd", fused=True)
    assert n_params == 161
    assert c.total_compiled <= 2, c.as_dict()


@pytest.mark.perf
def test_profile_step_eager_comparison_small():
    """The eager/fused comparison (small net, fast): the eager path runs
    at least one program per parameter, the fused engine at most two."""
    n_params, fused = _update_phase_dispatches("resnet18_v1", "adam",
                                               fused=True)
    assert fused.total_compiled <= 2, fused.as_dict()
    _, eager = _update_phase_dispatches("resnet18_v1", "adam", fused=False)
    assert eager.total_compiled >= n_params, eager.as_dict()


# ---------------------------------------------------------------------------
# PrefetchingIter: construction-time kick-off
# ---------------------------------------------------------------------------

def test_prefetching_iter_kicks_off_at_construction():
    from mxnet_tpu.io import NDArrayIter, PrefetchingIter

    x = np.arange(48, dtype=np.float32).reshape(12, 4)
    p = PrefetchingIter(NDArrayIter(x, None, batch_size=4), prefetch=2)
    assert len(p._queue) == 2  # first fetches are already in flight
    seen = [b.data[0].asnumpy()[0, 0] for b in p]
    assert seen == [0.0, 16.0, 32.0]
    p.close()

"""Device-plane observability suite (docs/OBSERVABILITY.md "Device plane").

Covers the tentpole contracts of the device plane:

1. **Cost accounting** — every compiled program logs XLA flops +
   bytes-accessed + peak-HBM in its engine's ``compile_log``: the fused
   update engine, the serve InferenceEngine, the Executor forward AND
   backward jit sites, and CachedOp — all on CPU (the analyses are
   backend-independent).
2. **The memory track and the program table** — a 2-batch resnet
   ``Module.fit`` produces a chrome trace with a ``device.live_bytes``
   counter track and ``device.compile`` events that
   ``tools/trace_report.py`` renders as counter-track and top-programs
   tables; no span carries an MFU of its own (MFU is the benchmark's
   ``train_mfu_pct``), and nothing blocks the device to time it.
3. **Leak detection** — the steady-state detector flags a deliberately
   retained array list and stays quiet over a 20-step steady-state fit
   (the ``pytest -m perf`` memory gate).
4. **Profiler window guards** — double ``start_trace``/``stop_trace`` are
   idempotent and land as tagged obs events in the span timeline.
"""
import json
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, obs, profiler
from mxnet_tpu import symbol as sym
from mxnet_tpu.io import NDArrayIter
from mxnet_tpu.module import Module
from mxnet_tpu.obs import device as obs_device

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

COST_KEYS = ("flops", "bytes_accessed", "peak_hbm_bytes")


@pytest.fixture(autouse=True)
def _obs_clean():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


@pytest.fixture
def obs_on(_obs_clean):
    obs.enable()
    yield


def _tiny_resnet(num_classes=2):
    data = sym.Variable("data")
    body = sym.Convolution(data, num_filter=4, kernel=(3, 3), stride=(1, 1),
                           pad=(1, 1), no_bias=True, name="conv0")
    bn1 = sym.BatchNorm(body, fix_gamma=False, eps=2e-5, momentum=0.9,
                        name="bn1")
    act1 = sym.Activation(bn1, act_type="relu", name="relu1")
    conv1 = sym.Convolution(act1, num_filter=4, kernel=(3, 3), stride=(1, 1),
                            pad=(1, 1), no_bias=True, name="conv1")
    body = conv1 + body
    pool = sym.Pooling(body, global_pool=True, kernel=(8, 8),
                       pool_type="avg", name="pool1")
    flat = sym.Flatten(pool, name="flatten")
    fc = sym.FullyConnected(flat, num_hidden=num_classes, name="fc1")
    return sym.SoftmaxOutput(fc, name="softmax")


def _mlp_symbol(num_classes=2):
    x = sym.Variable("data")
    h = sym.FullyConnected(x, num_hidden=8, name="fc1")
    h = sym.Activation(h, act_type="relu", name="relu1")
    out = sym.FullyConnected(h, num_hidden=num_classes, name="fc2")
    return sym.SoftmaxOutput(out, name="softmax")


def _assert_cost_fields(entry, where):
    for k in COST_KEYS:
        assert k in entry, f"{where}: compile_log entry missing {k!r}"
        assert isinstance(entry[k], int), f"{where}: {k} not an int"
    assert entry["flops"] > 0, f"{where}: zero flops"
    assert entry["bytes_accessed"] > 0, f"{where}: zero bytes_accessed"
    assert entry["peak_hbm_bytes"] > 0, f"{where}: zero peak_hbm_bytes"


# ---------------------------------------------------------------------------
# 1. cost accounting at every compile choke point (CPU)
# ---------------------------------------------------------------------------

def test_fused_engine_compile_log_carries_device_cost(obs_on):
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.optimizer import create
    from mxnet_tpu.optimizer.fused import FusedUpdateEngine

    eng = FusedUpdateEngine(create("sgd", learning_rate=0.1))
    w = NDArray(np.ones((16, 8), np.float32))
    g = NDArray(np.full((16, 8), 0.5, np.float32))
    eng.apply([0], [w], [g], [None])
    eng.apply([0], [w], [g], [None])
    assert len(eng.compile_log) == 1  # steady state: no retrace
    _assert_cost_fields(eng.compile_log[0], "fused")
    # the cost registry mirrors the record, under the entry's own key
    assert obs_device.cost_of("update", "SGD")["flops"] > 0
    assert obs_device.cost_of("update", "SGD")["program_key"] \
        == eng.compile_log[0]["program_key"]


def test_executor_forward_backward_compile_log(obs_on):
    from mxnet_tpu.executor import Executor

    net = _mlp_symbol()
    ex = Executor(net, shapes={"data": (4, 6), "softmax_label": (4,)},
                  grad_req="write")
    ex.forward(is_train=True, data=np.ones((4, 6), np.float32))
    ex.backward()
    sites = {e["site"] for e in ex.compile_log}
    assert sites == {"forward", "backward"}
    for entry in ex.compile_log:
        _assert_cost_fields(entry, f"executor/{entry['site']}")
    # same-signature re-execution must not add compile_log entries
    ex.forward(is_train=True, data=np.ones((4, 6), np.float32))
    ex.backward()
    assert len(ex.compile_log) == 2


def test_serve_engine_compile_log_and_bitwise_with_capture(obs_on):
    from mxnet_tpu.serve import InferenceEngine

    net = _mlp_symbol()
    rng = np.random.RandomState(3)
    arg_params = {
        "fc1_weight": rng.randn(8, 6).astype(np.float32),
        "fc1_bias": np.zeros(8, np.float32),
        "fc2_weight": rng.randn(2, 8).astype(np.float32),
        "fc2_bias": np.zeros(2, np.float32),
    }
    engine = InferenceEngine(net, arg_params, data_names=["data"],
                             max_batch_size=4, lint="off")
    x = rng.randn(3, 6).astype(np.float32)
    out1 = engine.predict(x)
    out2 = engine.predict(x)  # steady state through the AOT executable
    np.testing.assert_array_equal(out1, out2)
    assert len(engine.compile_log) == 1
    _assert_cost_fields(engine.compile_log[0], "serve")
    assert engine.compile_log[0]["bucket"] == 4
    # every bucket warmup compiles with cost accounting too
    engine.warmup((6,))
    assert len(engine.compile_log) == len(engine.buckets)
    for entry in engine.compile_log:
        _assert_cost_fields(entry, "serve/warmup")


def test_cachedop_compile_log(obs_on):
    from mxnet_tpu import gluon

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"))
    net.add(gluon.nn.Dense(2))
    net.initialize()
    net.hybridize()
    x = nd.array(np.ones((2, 6), np.float32))
    net(x)
    net(x)
    log = net._cached_op.compile_log
    assert len(log) == 1
    _assert_cost_fields(log[0], "cachedop")


def test_capture_inactive_without_telemetry(_obs_clean):
    """Zero-cost-when-off: with telemetry off the executor stays on the
    plain jit path — no aval-signature bookkeeping, no compile_log
    entries, no built executable — and nothing is recorded in the cost
    registry."""
    from mxnet_tpu.executor import Executor

    assert not obs.enabled()
    ex = Executor(_mlp_symbol(), shapes={"data": (2, 6),
                                         "softmax_label": (2,)},
                  grad_req="null")
    ex.forward(is_train=False, data=np.ones((2, 6), np.float32))
    assert ex.compile_log == [] and not ex._aot and not ex._seen_sigs
    assert obs_device.costs() == {}


# ---------------------------------------------------------------------------
# 2. the flagship: 2-batch resnet fit → counter track + program table
# ---------------------------------------------------------------------------

def test_two_batch_resnet_fit_has_memory_track_and_program_costs(
        tmp_path, obs_on):
    rng = np.random.RandomState(7)
    X = rng.randn(8, 3, 8, 8).astype(np.float32)
    y = rng.randint(0, 2, 8).astype(np.float32)
    it = NDArrayIter(X, y, batch_size=4)  # 2 batches/epoch
    mod = Module(_tiny_resnet(), context=mx.cpu())
    mod.fit(it, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05})

    trace_path = str(tmp_path / "trace.json")
    obs.export(trace_path)
    doc = json.load(open(trace_path))
    evs = doc["traceEvents"]

    # the memory counter track (Perfetto counter lane), one sample/batch
    mem = [e for e in evs if e.get("ph") == "C"
           and e["name"] == "device.live_bytes"]
    assert len(mem) >= 4, "expected a device.live_bytes sample per batch"
    assert all(e["args"]["value"] > 0 for e in mem)

    # the device spans are there, and none carries an MFU of its own
    for span_name, phase in (("device.forward", "forward"),
                             ("device.backward", "backward"),
                             ("update.fused", "update")):
        attrs = [e.get("args") or {} for e in evs
                 if e.get("ph") == "X" and e["name"] == span_name]
        assert attrs, f"no {span_name} span"
        for a in attrs:
            assert not {"analytic_mfu", "achieved_tflops", "roofline"} & set(a)
        assert obs.metrics.registry.get(f"device.mfu.{phase}") is None

    # every program the fit built logged its cost, in its site's
    # compile_log and as a device.compile event
    assert {e["site"] for e in mod._exec.compile_log} \
        == {"forward", "backward"}
    for entry in mod._exec.compile_log:
        _assert_cost_fields(entry, f"executor/{entry['site']}")
    compiles = [e for e in evs if e["name"] == "device.compile"]
    assert {e["args"]["site"] for e in compiles} >= {"executor", "update"}
    assert all(e["args"]["flops"] > 0 for e in compiles)

    # device.compile events feed the top-programs table; the counter
    # track and program table render through trace_report
    import trace_report

    rep = trace_report.report(trace_path)
    tracks = {c["name"] for c in rep["counters"]}
    assert "device.live_bytes" in tracks
    assert rep["device_programs"], "no device.compile rows in the report"
    top = rep["device_programs"][0]
    assert top["flops"] > 0 and top["site"] in ("executor", "update")
    import io

    buf = io.StringIO()
    trace_report.render(rep, stream=buf)
    text = buf.getvalue()
    assert "device.live_bytes" in text
    assert "Top programs by device cost" in text

    # the merged-chrome path keeps the counter lane
    merged = trace_report.merged_chrome([trace_path])
    assert any(e.get("ph") == "C" for e in merged["traceEvents"])

    # Prometheus exposition carries the live-bytes gauge via the existing
    # telemetry plane (no new wire needed)
    from mxnet_tpu.obs.export import to_prometheus

    expo = to_prometheus(obs.metrics.snapshot())
    assert "mxnet_device_live_bytes" in expo


@pytest.mark.parametrize("observed", [True, False], ids=["obs", "plain"])
def test_sharded_trainer_ragged_batch_trains_on(observed):
    """A ragged batch after a full one trains on, ``obs`` on or off — the
    step is the jit wrapper either way, and watching must never change
    training semantics."""
    import jax

    if observed:
        obs.enable()

    from mxnet_tpu import gluon, parallel as par

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"))
    net.add(gluon.nn.Dense(2))
    net.initialize()
    mesh = par.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr = par.ShardedTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                            mesh, optimizer="sgd",
                            optimizer_params={"learning_rate": 0.1})
    x = nd.array(np.ones((4, 6), np.float32))
    y = nd.array(np.zeros(4, np.int32))
    first = float(tr.step(x, y).asnumpy())
    assert np.isfinite(first)
    # ragged final batch: different leading dim → jit retrace, no crash
    x2 = nd.array(np.ones((2, 6), np.float32))
    y2 = nd.array(np.zeros(2, np.int32))
    loss = float(tr.step(x2, y2).asnumpy())
    assert np.isfinite(loss)
    # gluon forward after donated steps must still work: the step must
    # not delete parameter buffers device_put aliased on CPU
    net.hybridize()
    out = net(x2)
    assert np.isfinite(out.asnumpy()).all()


def test_fleet_report_keeps_corpse_counter_track(tmp_path):
    """A SIGKILL'd replica's JSONL evidence carries its device.live_bytes
    counter samples into the merged fleet timeline."""
    path = str(tmp_path / "replica.jsonl")
    obs.enable(jsonl=path)
    with obs.trace.span("serve.execute"):
        pass
    obs.trace.tracer.counter("device.live_bytes", 12345.0)
    obs.disable()

    import fleet_report

    part = fleet_report.jsonl_to_part(path)
    cs = [e for e in part["spans"] if e.get("ph") == "C"]
    assert cs and cs[0]["name"] == "device.live_bytes"
    assert cs[0]["args"]["value"] == 12345.0
    from mxnet_tpu.obs.export import merge_chrome_parts

    doc = merge_chrome_parts([part])
    assert any(e.get("ph") == "C" for e in doc["traceEvents"])


# ---------------------------------------------------------------------------
# 3. leak detector (the pytest -m perf memory gate)
# ---------------------------------------------------------------------------

@pytest.mark.perf
def test_leak_detector_flags_retained_arrays(obs_on):
    """A deliberately retained array list must trip the detector."""
    import jax.numpy as jnp

    det = obs_device.LeakDetector(window=8, warmup=2,
                                  threshold_bytes_per_step=1000)
    retained = []
    fired = None
    for step in range(30):
        retained.append(jnp.ones((256,), jnp.float32))  # 1 KB/step leak
        fired = fired or det.observe(obs_device.live_bytes())
    assert fired is not None, "retained arrays never flagged"
    assert fired["slope_bytes_per_step"] > 500
    del retained


@pytest.mark.perf
def test_leak_detector_quiet_over_20_step_steady_state_fit(obs_on):
    """A 20-step steady-state fit (params update in place) must not trip
    the leak detector — the gate that makes leak events actionable."""
    rng = np.random.RandomState(0)
    X = rng.randn(40, 6).astype(np.float32)
    y = rng.randint(0, 2, 40).astype(np.float32)
    it = NDArrayIter(X, y, batch_size=2)  # 20 batches/epoch
    mod = Module(_mlp_symbol(), context=mx.cpu())
    obs_device.monitor.reset()
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.01})
    assert obs_device.monitor.findings == [], (
        "steady-state fit flagged as a leak: "
        f"{obs_device.monitor.findings}")
    leak_events = [e for e in obs.trace.events()
                   if e[1] == "device.leak_suspected"]
    assert not leak_events


@pytest.mark.perf
def test_synthetic_leak_math():
    """Pure-math detector checks: flat + jitter stays quiet, a ramp fires
    once per window (cooldown), warmup growth is forgiven."""
    det = obs_device.LeakDetector(window=5, warmup=3,
                                  threshold_bytes_per_step=100)
    # warmup allocations (compile) look like a leak — must be dropped
    for v in (1000, 50000, 90000):
        assert det.observe(v) is None
    # steady state with jitter
    for v in (90000, 90010, 89990, 90005, 89995, 90000, 90008):
        assert det.observe(v) is None
    # now a 1 KB/step ramp
    fired = [det.observe(90000 + 1000 * i) for i in range(1, 11)]
    hits = [f for f in fired if f]
    assert hits, "ramp never fired"
    assert len(hits) <= 2, "cooldown failed: detector fired per-step"


# ---------------------------------------------------------------------------
# perf gate: the dispatch bound holds with cost capture ON
# ---------------------------------------------------------------------------

@pytest.mark.perf
def test_fused_dispatch_bound_holds_with_capture(obs_on):
    """The AOT capture path must not change the one-program-per-step
    dispatch guarantee (docs/PERFORMANCE.md)."""
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.optimizer import create
    from mxnet_tpu.optimizer.fused import FusedUpdateEngine

    eng = FusedUpdateEngine(create("sgd", learning_rate=0.1, momentum=0.9))
    ws = [NDArray(np.ones((8, 4), np.float32)) for _ in range(3)]
    gs = [NDArray(np.ones((8, 4), np.float32)) for _ in range(3)]
    sts = [NDArray(np.zeros((8, 4), np.float32)) for _ in range(3)]
    eng.apply([0, 1, 2], ws, gs, sts)  # compile
    with profiler.count_dispatches() as c:
        eng.apply([0, 1, 2], ws, gs, sts)
    assert c.compiled == 1, c.as_dict()
    assert len(eng.compile_log) == 1
    _assert_cost_fields(eng.compile_log[0], "fused/momentum")


# ---------------------------------------------------------------------------
# 4. profiler window guards
# ---------------------------------------------------------------------------

def test_profiler_double_start_stop_is_idempotent(tmp_path, obs_on):
    profiler.set_config(filename=str(tmp_path / "prof"))
    profiler.set_state("run")
    profiler.set_state("run")   # second start: guarded, no deep JAX raise
    (nd.ones((4, 4)) * 2).wait_to_read()
    profiler.set_state("stop")
    profiler.set_state("stop")  # second stop: guarded no-op
    d = profiler.dump()         # dump after stop: still fine
    assert d and os.path.isdir(d)
    names = [e[1] for e in obs.trace.events()]
    assert names.count("profiler.start_trace") == 1
    assert names.count("profiler.stop_trace") == 1


def test_profiler_context_manager_reentry(tmp_path, _obs_clean):
    with profiler.Profiler(filename=str(tmp_path / "p1")):
        with profiler.Profiler(filename=str(tmp_path / "p2")):
            (nd.ones((2, 2)) + 1).wait_to_read()
    # both exits stopped cleanly; a fresh window still works
    profiler.set_state("run")
    profiler.set_state("stop")

"""Runtime telemetry suite (``pytest -m obs`` / ``make obs``).

Covers the obs layer's contracts (docs/OBSERVABILITY.md):

1. span tracer — nesting, cross-thread reentrancy, ring-buffer bounding;
2. the flagship instrumented run — a 2-batch resnet ``Module.fit`` with
   checkpointing plus a parameter-server RPC round produces a VALID
   chrome-trace JSON containing all six step phases, a kvstore RPC
   histogram, and a checkpoint span, and ``tools/trace_report.py`` renders
   it;
3. metrics registry — snapshot stability, exact concurrent counting,
   type-conflict rejection;
4. disabled mode — no-op singleton spans, no retained allocations, the
   dispatch-count fast path unchanged;
5. chaos visibility — an injected RPC drop appears as a tagged event in
   the same timeline;
6. the satellites — fused compile/execute/retrace metrics, prefetch
   queue/stall metrics, Monitor's batched device_get, Speedometer's
   monotonic clock + zero-elapsed guard, checkpoint writer error
   surfacing.
"""
import gc
import json
import os
import sys
import threading
import time
import tracemalloc

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import obs, profiler
from mxnet_tpu import symbol as sym
from mxnet_tpu.io import NDArrayIter, PrefetchingIter
from mxnet_tpu.module import Module
from mxnet_tpu.serve import decode

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

STEP_PHASES = ("data_wait", "forward", "backward", "update", "metric",
               "checkpoint")


@pytest.fixture(autouse=True)
def _obs_clean():
    """Telemetry off + empty around every test: obs state must never leak
    into (or out of) a test."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


@pytest.fixture
def obs_on(_obs_clean):
    obs.enable()
    yield


# ---------------------------------------------------------------------------
# span tracer: nesting, threads, bounding
# ---------------------------------------------------------------------------

def test_span_nesting_records_depth_and_order(obs_on):
    with obs.trace.span("outer", k=1):
        with obs.trace.span("inner"):
            pass
        with obs.trace.span("inner2"):
            pass
    evs = obs.trace.events()
    by_name = {e[1]: e for e in evs}
    assert set(by_name) == {"outer", "inner", "inner2"}
    # record tuple: (ph, name, t0, dur, tid, depth, attrs)
    assert by_name["outer"][5] == 0 and by_name["outer"][6] == {"k": 1}
    assert by_name["inner"][5] == 1 and by_name["inner2"][5] == 1
    # children close before the parent, and nest inside its interval
    assert evs[0][1] == "inner" and evs[-1][1] == "outer"
    o_t0, o_dur = by_name["outer"][2], by_name["outer"][3]
    for child in ("inner", "inner2"):
        c_t0, c_dur = by_name[child][2], by_name[child][3]
        assert o_t0 <= c_t0 and c_t0 + c_dur <= o_t0 + o_dur + 1e-6


def test_span_reentrancy_across_threads(obs_on):
    n_threads = 6
    start = threading.Barrier(n_threads)

    def worker(i):
        start.wait()
        for _ in range(3):
            with obs.trace.span("outer", worker=i):
                with obs.trace.span("inner", worker=i):
                    time.sleep(0.001)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = obs.trace.events()
    assert len(evs) == n_threads * 3 * 2
    # per-thread stacks: every inner is depth 1, every outer depth 0, and
    # depths never bleed across concurrent threads
    for e in evs:
        assert e[5] == (1 if e[1] == "inner" else 0)
    tids = {e[4] for e in evs}
    assert len(tids) == n_threads


def test_ring_buffer_is_bounded():
    from mxnet_tpu.obs.trace import Tracer, _ENABLED  # noqa: F401

    t = Tracer(capacity=16)
    obs.enable()
    for i in range(100):
        with t.span(f"s{i}"):
            pass
    assert len(t.events()) == 16
    assert t.events()[-1][1] == "s99"  # newest win, oldest drop


def test_instant_events_and_jsonl_stream(tmp_path):
    path = str(tmp_path / "events.jsonl")
    obs.enable(jsonl=path)
    with obs.trace.span("phase"):
        obs.trace.event("mark", kind="demo")
    obs.metrics.counter("demo.count").inc(3)
    obs.disable()  # appends the final metrics record
    lines = [json.loads(l) for l in open(path) if l.strip()]
    phs = [l["ph"] for l in lines]
    assert "i" in phs and "X" in phs and phs[-1] == "M"
    assert lines[-1]["metrics"]["counters"]["demo.count"] == 3
    # the instant event streams BEFORE the enclosing span closes
    assert phs.index("i") < phs.index("X")


# ---------------------------------------------------------------------------
# flagship: 2-batch resnet fit + PS RPC + checkpoint, exported and reported
# ---------------------------------------------------------------------------

def _tiny_resnet(num_classes=2):
    """One non-bottleneck residual unit at 8x8 — the smallest thing that is
    honestly a ResNet (conv/BN/relu + identity shortcut)."""
    data = sym.Variable("data")
    body = sym.Convolution(data, num_filter=4, kernel=(3, 3), stride=(1, 1),
                           pad=(1, 1), no_bias=True, name="conv0")
    bn1 = sym.BatchNorm(body, fix_gamma=False, eps=2e-5, momentum=0.9,
                        name="bn1")
    act1 = sym.Activation(bn1, act_type="relu", name="relu1")
    conv1 = sym.Convolution(act1, num_filter=4, kernel=(3, 3), stride=(1, 1),
                            pad=(1, 1), no_bias=True, name="conv1")
    body = conv1 + body  # residual shortcut
    pool = sym.Pooling(body, global_pool=True, kernel=(8, 8),
                       pool_type="avg", name="pool1")
    flat = sym.Flatten(pool, name="flatten")
    fc = sym.FullyConnected(flat, num_hidden=num_classes, name="fc1")
    return sym.SoftmaxOutput(fc, name="softmax")


def _ps_round():
    """One init/push/pull round against a live PS so the trace carries real
    kvstore RPC spans + histograms."""
    from mxnet_tpu.kvstore.ps_client import PSClient
    from mxnet_tpu.kvstore.ps_server import PSServer

    srv = PSServer(host="127.0.0.1", port=0, num_workers=1)
    srv.start()
    try:
        cli = PSClient("127.0.0.1", srv.port, timeout=5, retries=3,
                       retry_interval=0.05)
        w = np.ones((4, 3), np.float32)
        cli.init("w", w)
        cli.push("w", np.full((4, 3), 0.5, np.float32))
        out = cli.pull("w")
        np.testing.assert_allclose(out, w + 0.5)
    finally:
        srv.stop()


def test_two_batch_resnet_fit_trace_is_valid_and_phase_complete(
        tmp_path, obs_on):
    rng = np.random.RandomState(7)
    X = rng.randn(8, 3, 8, 8).astype(np.float32)
    y = rng.randint(0, 2, 8).astype(np.float32)
    it = NDArrayIter(X, y, batch_size=4)  # 2 batches/epoch
    mod = Module(_tiny_resnet(), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05},
            checkpoint=str(tmp_path / "ckpts"))
    _ps_round()

    trace_path = str(tmp_path / "trace.json")
    obs.export(trace_path)
    doc = json.load(open(trace_path))  # valid chrome-trace JSON
    assert isinstance(doc["traceEvents"], list)
    names = [e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"]
    for phase in STEP_PHASES:
        assert phase in names, f"missing step phase {phase!r}"
    # 2 batches → 2 of each per-batch phase
    for phase in ("forward", "backward", "update", "metric"):
        assert names.count(phase) == 2
    assert "checkpoint.write" in names  # the async writer's commit
    assert "kvstore.rpc" in names      # client-side RPC spans
    metrics = doc["otherData"]["metrics"]
    rpc_hists = [n for n in metrics["histograms"]
                 if n.startswith("kvstore.rpc.") and n.endswith("_seconds")]
    assert rpc_hists, "expected at least one kvstore RPC latency histogram"
    srv_hists = [n for n in metrics["histograms"]
                 if n.startswith("kvstore.server.rpc.")]
    assert srv_hists, "expected server-side RPC histograms"
    assert "checkpoint.write_seconds" in metrics["histograms"]
    assert metrics["counters"]["kvstore.bytes_pushed"] > 0
    assert metrics["counters"]["kvstore.bytes_pulled"] > 0

    # trace_report renders the same facts
    import trace_report

    rep = trace_report.report(trace_path)
    phase_names = [r["name"] for r in rep["phases"]]
    assert list(phase_names[:6]) == list(STEP_PHASES)
    import io

    buf = io.StringIO()
    trace_report.render(rep, stream=buf)
    text = buf.getvalue()
    for phase in STEP_PHASES:
        assert phase in text
    assert "kvstore.rpc." in text and "checkpoint.write" in text


def test_trace_report_cli_on_jsonl(tmp_path, capsys):
    path = str(tmp_path / "run.jsonl")
    obs.enable(jsonl=path)
    for phase in STEP_PHASES:
        with obs.trace.span(phase):
            pass
    obs.observe("kvstore.rpc.push_seq_seconds", 0.003)
    obs.disable()

    import trace_report

    trace_report.main([path, "--top", "3"])
    out = capsys.readouterr().out
    for phase in STEP_PHASES:
        assert phase in out
    assert "kvstore.rpc.push_seq_seconds" in out


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_snapshot_stable_and_isolated():
    reg = obs.metrics.registry
    reg.counter("a.count").inc(5)
    reg.gauge("a.gauge").set(1.25)
    h = reg.histogram("a.hist")
    for v in (0.001, 0.002, 0.004, 1.5):
        h.observe(v)
    s1, s2 = reg.snapshot(), reg.snapshot()
    assert s1 == s2  # no ops between snapshots → identical
    assert s1["counters"]["a.count"] == 5
    assert s1["gauges"]["a.gauge"] == 1.25
    hs = s1["histograms"]["a.hist"]
    assert hs["count"] == 4
    assert hs["min"] == pytest.approx(0.001)
    assert hs["max"] == pytest.approx(1.5)
    assert hs["sum"] == pytest.approx(1.507)
    # snapshots are copies: mutating one must not touch the registry
    s1["counters"]["a.count"] = 999
    assert reg.counter("a.count").value == 5
    # dump() renders both formats without blowing up
    assert "a.hist" in reg.dump("text")
    assert json.loads(reg.dump("json"))["counters"]["a.count"] == 5


def test_metrics_concurrent_increments_are_exact():
    reg = obs.metrics.registry
    c = reg.counter("race.count")
    h = reg.histogram("race.hist")

    def worker():
        for _ in range(1000):
            c.inc()
            h.observe(0.01)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    assert h.count == 8000
    assert h.sum == pytest.approx(80.0)


def test_metric_type_conflict_raises():
    reg = obs.metrics.registry
    reg.counter("typed.metric")
    with pytest.raises(TypeError):
        reg.gauge("typed.metric")
    with pytest.raises(TypeError):
        reg.histogram("typed.metric")


def test_histogram_quantile_estimates():
    h = obs.metrics.registry.histogram("q.hist")
    for _ in range(90):
        h.observe(0.002)
    for _ in range(10):
        h.observe(0.2)
    assert h.quantile(0.5) == pytest.approx(0.0025)  # bucket upper bound
    assert h.quantile(0.99) >= 0.2


# ---------------------------------------------------------------------------
# disabled mode: the zero-cost contract
# ---------------------------------------------------------------------------

def test_disabled_span_is_shared_noop_and_records_nothing():
    assert not obs.enabled()
    s1 = obs.trace.span("forward", epoch=1)
    s2 = obs.trace.span("backward")
    assert s1 is s2  # the shared singleton — no per-call object
    with s1:
        obs.trace.event("never", x=1)
    assert obs.trace.events() == []
    # the self-gating helpers must not even create the metrics
    obs.inc("never.count")
    obs.observe("never.hist", 1.0)
    obs.set_gauge("never.gauge", 1.0)
    assert obs.metrics.registry.get("never.count") is None
    assert obs.metrics.registry.get("never.hist") is None
    assert obs.metrics.registry.get("never.gauge") is None


class _FedStreams:
    """What ``DecodeScheduler.generate`` needs of its scheduler, with no
    engine behind it: ``submit`` hands back a stream that already holds
    ``n`` tokens and its end."""

    default_timeout = 5.0
    _cv = threading.Condition()

    def __init__(self, n):
        self.n = n

    def submit(self, tokens, **_kw):
        h = decode.StreamHandle(capacity=self.n + 1)
        for i in range(self.n):
            assert h._emit(("token", i, i + 1), 1.0)
        assert h._emit(("end", "length", self.n))
        return h


def test_disabled_hot_path_retains_no_allocations():
    assert not obs.enabled()

    def hot_loop(n):
        for _ in range(n):
            with obs.trace.span("phase"):
                pass
            obs.inc("c")
            obs.observe("h", 0.5)
        # a stream's consumer: one test of the flag a token, and nothing kept
        got = sum(1 for _ in decode.DecodeScheduler.generate(
            _FedStreams(n), [1]))
        assert got == n

    hot_loop(100)  # warm caches outside the measurement
    # what the obs layer, the consumer's loop and this file allocate: the
    # process's other threads (whatever the worker's earlier test files left
    # running under -n 6) allocate elsewhere, and are no part of the claim
    ours = [tracemalloc.Filter(True, os.path.join(REPO, "mxnet_tpu", "obs",
                                                  "*")),
            tracemalloc.Filter(True, decode.__file__),
            tracemalloc.Filter(True, __file__)]
    tracemalloc.start()
    gc.collect()    # (and empties the interpreter's free lists, which keep
    #                 up to 2,000 freed tuples of a size: freed, not retained)
    before = tracemalloc.take_snapshot().filter_traces(ours)
    hot_loop(20000)
    gc.collect()
    after = tracemalloc.take_snapshot().filter_traces(ours)
    tracemalloc.stop()
    retained = sum(s.size_diff for s in after.compare_to(before, "filename")
                   if s.size_diff > 0)
    # 20k disabled iterations must retain (essentially) nothing; a real
    # recording of 20k spans would be megabytes
    assert retained < 64 * 1024, f"disabled mode retained {retained} bytes"
    assert obs.trace.events() == []
    assert obs.trace.tracer.dropped == 0


def test_dispatch_counting_unchanged_when_disabled():
    assert not obs.enabled()
    assert not profiler.counting_dispatches()  # no region, no obs → off
    reg = obs.metrics.registry
    with profiler.count_dispatches() as c:
        a = mx.nd.ones((4, 4))
        b = (a * a + a).asnumpy()  # noqa: F841
    assert c.eager_ops >= 2 and c.d2h == 1
    # the region view IS the registry delta — same numbers, one source
    assert reg.counter("dispatch.eager_ops").value >= c.eager_ops
    assert not profiler.counting_dispatches()


def test_dispatch_counts_accumulate_globally_when_enabled(obs_on):
    assert profiler.counting_dispatches()  # obs enabled → hooks active
    before = obs.metrics.registry.counter("dispatch.eager_ops").value
    a = mx.nd.ones((2, 2))
    _ = a + a
    assert obs.metrics.registry.counter("dispatch.eager_ops").value > before


# ---------------------------------------------------------------------------
# chaos visibility: injected faults are tagged in the same timeline
# ---------------------------------------------------------------------------

def test_injected_rpc_drop_appears_as_tagged_event(obs_on):
    from mxnet_tpu.chaos import rpc as chaos_rpc
    from mxnet_tpu.kvstore.ps_client import PSClient
    from mxnet_tpu.kvstore.ps_server import PSServer

    chaos_rpc.reset()
    srv = PSServer(host="127.0.0.1", port=0, num_workers=1)
    srv.start()
    try:
        cli = PSClient("127.0.0.1", srv.port, timeout=5, retries=5,
                       retry_interval=0.01)
        w = np.zeros((3,), np.float32)
        cli.init("w", w)
        chaos_rpc.configure(
            [chaos_rpc.Rule("push_seq", "drop_reply", {1})])
        cli.push("w", np.ones((3,), np.float32))
        np.testing.assert_allclose(cli.pull("w"), np.ones(3))  # exactly once
    finally:
        chaos_rpc.reset()
        srv.stop()

    drops = [e for e in obs.trace.events()
             if e[0] == "i" and e[1] == "chaos.rpc"]
    assert drops, "injected drop not tagged in the trace"
    attrs = drops[0][6]
    assert attrs["action"] == "drop_reply" and attrs["op"] == "push_seq"
    reg = obs.metrics.registry
    assert reg.counter("chaos.injected").value >= 1
    assert reg.counter("kvstore.rpc.retries").value >= 1
    assert reg.histogram("kvstore.rpc.push_seq_seconds").count >= 1
    # the retry itself is also an event, ordered after the injection
    retries = [e for e in obs.trace.events() if e[1] == "kvstore.rpc.retry"]
    assert retries and retries[0][2] >= drops[0][2]


# ---------------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------------

def test_fused_update_compile_execute_and_retrace_metrics(obs_on):
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon import Trainer, nn

    net = nn.Dense(4)
    net.initialize()
    x = mx.nd.ones((2, 3))
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1})

    def step():
        with autograd.record():
            y = net(x)
            loss = (y * y).sum()
        loss.backward()
        trainer.step(2)

    step()
    step()
    reg = obs.metrics.registry
    assert reg.counter("update.compile").value == 1
    assert reg.counter("update.retrace").value == 0
    assert reg.histogram("update.compile_seconds").count == 1
    assert reg.histogram("update.execute_seconds").count == 1
    # churning a STATIC hyperparameter forces a recompile → retrace counter
    trainer._optimizer.clip_gradient = 5.0
    step()
    assert reg.counter("update.retrace").value == 1
    assert reg.counter("update.compile").value == 2
    spans = [e for e in obs.trace.events() if e[1] == "update.fused"]
    assert [s[6]["compile"] for s in spans] == [True, False, True]


def test_prefetch_reports_queue_depth_and_stall(obs_on):
    X = np.arange(64, dtype=np.float32).reshape(16, 4)
    y = np.zeros(16, np.float32)
    it = PrefetchingIter(NDArrayIter(X, y, batch_size=4))
    try:
        n = sum(1 for _ in it)
    finally:
        it.close()
    assert n == 4
    reg = obs.metrics.registry
    assert reg.counter("io.prefetch.batches").value == 4
    assert reg.histogram("io.prefetch.stall_seconds").count == 4
    assert reg.get("io.prefetch.queue_depth") is not None


def test_monitor_batches_stat_transfers(obs_on):
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.monitor import Monitor

    net = nn.HybridSequential()
    net.add(nn.Dense(8), nn.Dense(4))
    net.initialize()
    mon = Monitor(interval=1, pattern=".*dense.*")
    mon.install_gluon(net)
    try:
        mon.tic()
        net(mx.nd.ones((2, 6)))
        with profiler.count_dispatches() as c:
            stats = mon.toc()
    finally:
        mon.uninstall_gluon()
    assert len(stats) >= 2  # both Dense layers tapped
    for _step, _name, val in stats:
        assert isinstance(val, np.ndarray)
    # ONE batched device_get for all stats (the old code paid one blocking
    # asnumpy per watched tensor)
    assert c.d2h == 1
    # ...and the stats land in the registry as health-plane gauges (the
    # Monitor is an adapter over obs/health.py since the health PR)
    gauges = [n for n in obs.metrics.registry.names()
              if n.startswith("health.monitor.")]
    assert len(gauges) >= 2


def test_speedometer_monotonic_and_zero_elapsed_guard(obs_on):
    from mxnet_tpu.callback import BatchEndParam, Speedometer

    spm = Speedometer(batch_size=2, frequent=1)
    spm(BatchEndParam(epoch=0, nbatch=0, eval_metric=None, locals=None))
    # same clock tick as the init call — the old time.time() version could
    # divide by zero here
    spm(BatchEndParam(epoch=0, nbatch=1, eval_metric=None, locals=None))
    g = obs.metrics.registry.get("training.samples_per_sec")
    assert g is not None and g.value > 0


def test_checkpoint_writer_error_logged_counted_and_reraised(
        tmp_path, monkeypatch, caplog):
    import logging

    from mxnet_tpu.checkpoint import CheckpointError, CheckpointManager
    from mxnet_tpu.checkpoint.state import TrainingState
    from mxnet_tpu.ndarray import serialization as ser

    def boom(*a, **k):
        raise OSError("disk on fire")

    monkeypatch.setattr(ser, "save_nd", boom)
    reg = obs.metrics.registry
    before = reg.counter("checkpoint.write_errors").value
    m = CheckpointManager(str(tmp_path), async_write=True)
    st = TrainingState({"arg:w": np.ones(3, np.float32)}, {"epoch": 0})
    with caplog.at_level(logging.ERROR, logger="mxnet_tpu.checkpoint"):
        m.save(st, 1)
        # the failure surfaces on the NEXT sync point, as CheckpointError
        with pytest.raises(CheckpointError):
            m.flush()
    assert reg.counter("checkpoint.write_errors").value == before + 1
    assert any("write failed" in r.message for r in caplog.records)
    # the error is consumed once surfaced; recovery works
    monkeypatch.undo()
    m.save(st, 2)
    m.close()
    assert m.latest_step() == 2


def test_checkpoint_write_durations_recorded(tmp_path, obs_on):
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.checkpoint.state import TrainingState

    m = CheckpointManager(str(tmp_path), async_write=False)
    m.save(TrainingState({"arg:w": np.ones(4, np.float32)}, {"epoch": 0}), 1)
    m.close()
    reg = obs.metrics.registry
    for name in ("checkpoint.write_seconds", "checkpoint.array_write_seconds",
                 "checkpoint.fsync_seconds", "checkpoint.commit_seconds"):
        assert reg.histogram(name).count == 1, name
    assert reg.counter("checkpoint.saves").value == 1
    assert any(e[1] == "checkpoint.write" for e in obs.trace.events())

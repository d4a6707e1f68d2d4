"""Continuous profiler + crash flight recorder (``pytest -m blackbox`` /
``make prof``) — docs/OBSERVABILITY.md "Continuous profiling" / "Flight
recorder".

- the sampling profiler: phase attribution from the tracer's live span
  stacks, collapsed-stack export, chrome-lane coalescing, lifecycle;
- the flight recorder: the always-on ring fed from the span hot path,
  bundle schema, atomic dumps, trigger throttling, the periodic
  last-bundle flush that answers SIGKILL, signal/excepthook chains;
- the ``DUMP`` wire opcode (a remote "what is this replica doing");
- hook integration: the tsan watchdog and SLO breaches snapshot the ring;
- torn-tail tolerance: a stream truncated mid-line parses with a counted
  warning everywhere (trace_report, fleet_report, export.merge_*);
- bundle readers: ``tools/trace_report.py`` / ``tools/fleet_report.py``
  merge a corpse's bundle — profiler lane included — into the timeline;
- the env switches (``MXNET_OBS_TAIL/PROF/BLACKBOX*``) in a fresh
  process, including the SIGTERM-dump and SIGKILL-flush stories;
- (slow, chaos flagship) a ProcReplica fleet under mixed load with tail
  retention on: every deadline-exceeded request's cross-process trace is
  retained, the fast path drops, and a SIGKILL'd replica leaves a bundle
  the fleet report merges with its profiler lane.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from mxnet_tpu import nd, obs, serve, tsan
from mxnet_tpu import symbol as sym
from mxnet_tpu.obs import blackbox, context, metrics, profile, tail
from mxnet_tpu.obs.blackbox import FlightRecorder, is_bundle, read_bundle
from mxnet_tpu.obs.export import merge_chrome_parts
from mxnet_tpu.obs.profile import SamplingProfiler
from mxnet_tpu.obs.slo import SLOMonitor
from mxnet_tpu.model import save_checkpoint
from mxnet_tpu.serve import ServeClient, ServeServer
from mxnet_tpu.serve.fleet import FleetServer, ReplicaPool, Router
from mxnet_tpu.wire import SERVE_WIRE

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

pytestmark = [pytest.mark.obs, pytest.mark.blackbox]


@pytest.fixture(autouse=True)
def _clean():
    obs.disable()
    obs.reset()
    tail.disable()
    profile.stop()
    blackbox.disable()
    context.set_sample_rate(1.0)
    yield
    blackbox.disable()
    profile.stop()
    tail.disable()
    obs.disable()
    obs.reset()
    context.set_sample_rate(1.0)


# ---------------------------------------------------------------------------
# 1. the sampling profiler
# ---------------------------------------------------------------------------

def test_profiler_attributes_samples_to_the_active_span_phase():
    obs.enable()
    p = SamplingProfiler(hz=100)
    release = threading.Event()
    inside = threading.Event()

    def worker():
        with obs.trace.span("serve.execute"):
            inside.set()
            release.wait(5)

    t = threading.Thread(target=worker)
    t.start()
    try:
        assert inside.wait(5)
        taken = p.sample_once()
        assert taken >= 1
    finally:
        release.set()
        t.join()
    folded = p.folded()
    assert "serve.execute;" in folded
    # collapsed-stack format: every line is "phase;frames... count"
    for line in folded.splitlines():
        head, _, count = line.rpartition(" ")
        assert head and count.isdigit()
    assert p.phase_seconds().get("serve.execute", 0) > 0
    # threads with no active span attribute to "idle"
    assert any(ph in ("idle",) or True for ph in p.phase_seconds())


def test_bundle_profiler_slice_is_bounded_to_the_recent_window():
    """The sample ring covers ~16 min at 67 Hz; a bundle embeds only the
    last MXNET_OBS_BLACKBOX_PROF_S seconds — the periodic flush must not
    copy and coalesce the whole ring every flush period."""
    obs.enable()
    p = profile.start(hz=100)
    now = time.monotonic()
    # one stale sample (far outside the window) + one recent
    p._samples.append((now - 300.0, 1, "stale.phase", "old"))
    p._samples.append((now - 0.5, 1, "serve.execute", "fresh"))
    rec = blackbox.enable(signals=False)
    try:
        doc = rec.bundle_dict("test")
        names = {s["name"] for s in doc["profiler"]["samples"]}
        assert "prof:serve.execute" in names
        assert "prof:stale.phase" not in names
    finally:
        blackbox.disable()
        profile.stop()


def test_root_span_close_releases_the_thread_stack_registration():
    """The profiler's phase-attribution dict (``tracer._thread_stacks``)
    must not grow one entry per dead thread: a serve plane spawns a
    handler thread per connection, and an unreleased registration keeps
    every dead thread's stack list alive (and scanned at 67 Hz) forever.
    Root close drops the entry; the next span re-registers."""
    obs.enable()
    tr = obs.trace.tracer

    def worker():
        with tr.span("serve.rpc"):
            with tr.span("serve.execute"):
                pass

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dead = {t.ident for t in threads}
    assert not dead & set(tr._thread_stacks), \
        "dead handler threads still registered for phase attribution"
    # a live thread's registration comes back on its next span
    with tr.span("again"):
        assert threading.get_ident() in tr._thread_stacks
    assert threading.get_ident() not in tr._thread_stacks


def test_profiler_chrome_lane_coalesces_consecutive_samples():
    p = SamplingProfiler(hz=100)            # period 10ms
    epoch = obs.trace.tracer._epoch
    now = time.monotonic()
    # thread 1: three contiguous idle samples, a gap, then one exec sample
    for i, (phase, leaf) in enumerate([("idle", "a")] * 3):
        p._samples.append((now + i * 0.01, 1, phase, leaf))
    p._samples.append((now + 0.2, 1, "serve.execute", "b"))
    evs = p.chrome_events()
    assert [e["name"] for e in evs] == ["prof:idle", "prof:serve.execute"]
    run = evs[0]
    assert run["args"]["samples"] == 3
    assert run["args"]["leaf"] == "a"
    assert run["dur"] == pytest.approx(0.03, rel=0.2)
    assert run["ts"] == pytest.approx(now - epoch, abs=1e-3)


def test_profiler_lifecycle_and_module_singleton():
    assert not profile.enabled()
    p = profile.start(hz=200)
    try:
        assert profile.enabled()
        assert profile.start() is p       # idempotent
        deadline = time.monotonic() + 5
        while p.ticks == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert p.ticks > 0
    finally:
        profile.stop()
    assert not profile.enabled()
    assert isinstance(profile.folded(), str)
    with pytest.raises(ValueError):
        SamplingProfiler(hz=-1)


# ---------------------------------------------------------------------------
# 2. the flight recorder
# ---------------------------------------------------------------------------

def test_recorder_ring_sees_every_event_and_bundles(tmp_path):
    obs.enable()
    blackbox.enable(dirpath=str(tmp_path), flush_s=0)
    with obs.trace.span("forward", epoch=1):
        pass
    obs.event("chaos.kill", point="here")
    prof = profile.start(hz=100)
    release = threading.Event()
    t = threading.Thread(target=release.wait, args=(5,))
    t.start()  # sample_once never profiles its own caller — give it prey
    try:
        prof.sample_once()
    finally:
        release.set()
        t.join()
    doc = blackbox.bundle("unit")
    assert is_bundle(doc)
    names = {e["name"] for e in doc["events"]}
    assert {"forward", "chaos.kill"} <= names
    assert doc["pid"] == os.getpid()
    assert "metrics" in doc and "threads" in doc
    assert doc["profiler"]["stats"]["samples"] >= 1
    # a dumped bundle is valid JSON on disk, atomically written
    path = blackbox.dump("unit")
    on_disk = json.load(open(path))
    assert on_disk["reason"] == "unit"
    # read_bundle folds the profiler lane into the part's span stream
    part = read_bundle(on_disk)
    assert part["pid"] == os.getpid()
    assert any(e.get("name") == "forward" for e in part["spans"])


def test_recorder_ring_records_tail_held_spans_too():
    """The crash bundle wants "what was the process doing" — including
    spans the tail policy would later DROP."""
    obs.enable()
    tail.enable()
    # pin the uniform baseline to 0 (the test_tail idiom): the default
    # 1% keep-anyway coin flip would promote the "doomed" trace into the
    # durable ring about one run in a hundred — a flake, not a finding
    tail.buffer().policy = tail.RetentionPolicy(baseline=0.0)
    blackbox.enable()
    ctx = context.new_root()
    with context.use(ctx):
        with obs.trace.span("doomed.span"):
            pass
    tail.buffer().finish(ctx.trace_id, 0.0)  # fast path: dropped
    assert not any(r[1] == "doomed.span" for r in obs.trace.tracer.events())
    doc = blackbox.bundle("x")
    assert any(e["name"] == "doomed.span" for e in doc["events"])


def test_trigger_throttles_inside_the_cooldown(tmp_path):
    obs.enable()
    r = blackbox.enable(dirpath=str(tmp_path), flush_s=0, cooldown_s=60)
    first = blackbox.trigger("slo_breach:test")
    assert first is not None and os.path.exists(first)
    assert blackbox.trigger("slo_breach:again") is None  # throttled
    assert metrics.registry.counter("blackbox.throttled").value == 1
    assert r.dumps == 1


def test_periodic_flush_leaves_a_last_bundle(tmp_path):
    obs.enable()
    r = blackbox.enable(dirpath=str(tmp_path), flush_s=0)  # manual flush
    assert r.flush() is None         # nothing recorded yet → no write
    obs.event("something")
    path = r.flush()
    assert path and path.endswith(f"blackbox-{os.getpid()}-last.json")
    doc = json.load(open(path))
    assert doc["reason"] == "flush"
    assert r.flush() is None         # not dirty again


def test_hooks_install_and_uninstall_cleanly():
    prev_hook = sys.excepthook
    prev_term = signal.getsignal(signal.SIGTERM)
    blackbox.enable()
    assert sys.excepthook is not prev_hook
    assert signal.getsignal(signal.SIGTERM) is not prev_term
    blackbox.disable()
    assert sys.excepthook is prev_hook
    assert signal.getsignal(signal.SIGTERM) is prev_term


# ---------------------------------------------------------------------------
# 3. the DUMP wire opcode
# ---------------------------------------------------------------------------

def _serve_pair():
    data = sym.Variable("data")
    net = sym.FullyConnected(data, num_hidden=4, no_bias=True, name="fc")
    arg = {"fc_weight": np.eye(4, dtype=np.float32)}
    engine = serve.InferenceEngine(net, arg, max_batch_size=8, lint="off")
    srv = ServeServer(engine, port=0, max_linger_ms=0.0)
    srv.start()
    return srv, ServeClient("127.0.0.1", srv.port)


X = np.arange(8, dtype=np.float32).reshape(2, 4)


def test_dump_opcode_registered_in_the_wire_registry():
    names = dict(SERVE_WIRE.names())
    assert names[43] == "dump"


def test_dump_opcode_returns_a_remote_bundle(tmp_path):
    obs.enable()
    srv, cli = _serve_pair()
    try:
        np.testing.assert_array_equal(cli.infer(X), X)
        doc = cli.dump(reason="operator")   # recorder DISARMED: still works
        assert is_bundle(doc)
        assert doc["pid"] == os.getpid()    # in-process server
        assert doc["reason"] == "operator"
        assert "threads" in doc
        # armed with a directory, write=True persists server-side; the
        # ring sees the traffic that flows AFTER arming
        blackbox.enable(dirpath=str(tmp_path), flush_s=0)
        np.testing.assert_array_equal(cli.infer(X), X)
        doc2 = cli.dump(reason="persisted", write=True)
        assert os.path.exists(doc2["path"])
        ring_names = {e["name"] for e in doc2["events"]}
        assert any(n.startswith("serve.") for n in ring_names)
    finally:
        cli.close()
        srv.stop()


# ---------------------------------------------------------------------------
# 4. hook integration: watchdog + SLO breaches snapshot the ring
# ---------------------------------------------------------------------------

def test_tsan_watchdog_dump_triggers_a_bundle(tmp_path):
    obs.enable()
    blackbox.enable(dirpath=str(tmp_path), flush_s=0)
    tsan.dump_stacks("unit-test")
    bundles = [f for f in os.listdir(tmp_path)
               if f.endswith(".json") and "-last" not in f]
    assert len(bundles) == 1
    doc = json.load(open(tmp_path / bundles[0]))
    assert doc["reason"].startswith("watchdog:unit-test")


def test_slo_breach_triggers_a_bundle(tmp_path):
    obs.enable()
    blackbox.enable(dirpath=str(tmp_path), flush_s=0)
    mon = SLOMonitor(deadline_target=0.99)
    snap = {"counters": {"serve.shed_deadline": 50},
            "histograms": {"serve.latency_seconds": {
                "count": 50, "sum": 1.0, "buckets": {"0.1": 50}}}}
    rep = mon.evaluate(snap)
    assert not rep["ok"]
    bundles = [f for f in os.listdir(tmp_path)
               if f.endswith(".json") and "-last" not in f]
    assert len(bundles) == 1
    doc = json.load(open(tmp_path / bundles[0]))
    assert doc["reason"].startswith("slo_breach:")


# ---------------------------------------------------------------------------
# 5. torn-tail tolerance
# ---------------------------------------------------------------------------

def _torn_jsonl(tmp_path):
    """A JSONL stream whose final record was truncated mid-line (what a
    SIGKILL leaves behind)."""
    stream = str(tmp_path / "corpse.jsonl")
    obs.enable(jsonl=stream)
    with obs.trace.span("forward"):
        pass
    obs.event("chaos.kill")
    obs.disable()
    with open(stream, "a") as f:   # the torn tail
        f.write('{"ph": "X", "name": "half-writ')
    return stream


def test_torn_jsonl_tail_skips_with_a_counted_warning(tmp_path):
    from trace_report import load_trace_meta, report

    stream = _torn_jsonl(tmp_path)
    spans, instants, _metrics, meta = load_trace_meta(stream)
    assert meta["skipped_lines"] == 1
    assert [s["name"] for s in spans] == ["forward"]
    assert [i["name"] for i in instants] == ["chaos.kill"]
    rep = report([stream])
    assert rep["torn_records"] == 1
    assert rep["n_spans"] == 1


def test_torn_jsonl_in_fleet_report_part(tmp_path):
    from fleet_report import jsonl_to_part

    part = jsonl_to_part(_torn_jsonl(tmp_path))
    assert part["torn_records"] == 1
    assert any(e["name"] == "forward" for e in part["spans"])
    # export.merge_* swallow garbled records with a count, never raise
    part["spans"].append("not-a-record")
    doc = merge_chrome_parts([part, "torn-part"])
    assert doc["otherData"]["skipped_records"] == 2
    assert any(e.get("name") == "forward" for e in doc["traceEvents"])


# ---------------------------------------------------------------------------
# 6. bundle readers in the tools
# ---------------------------------------------------------------------------

def _bundle_with_profiler(tmp_path):
    obs.enable()
    blackbox.enable(dirpath=str(tmp_path), flush_s=0)
    p = profile.start(hz=100)
    release = threading.Event()
    inside = threading.Event()

    def worker():
        with obs.trace.span("serve.execute"):
            inside.set()
            release.wait(5)

    t = threading.Thread(target=worker)
    t.start()
    try:
        assert inside.wait(5)
        p.sample_once()
        p.sample_once()
    finally:
        release.set()
        t.join()
    with obs.trace.span("serve.rpc"):
        pass
    return blackbox.dump("test")


def test_trace_report_reads_bundles_with_profiler_lane(tmp_path):
    from trace_report import load_trace_meta, merged_chrome, report

    path = _bundle_with_profiler(tmp_path)
    spans, _ins, _met, meta = load_trace_meta(path)
    assert meta["blackbox_reason"] == "test"
    assert meta["pid"] == os.getpid()
    names = {s["name"] for s in spans}
    assert "serve.rpc" in names
    assert any(n.startswith("prof:") for n in names)
    rep = report([path])
    assert rep["profiler"] is not None
    phases = {r["phase"]: r for r in rep["profiler"]["phases"]}
    assert "serve.execute" in phases
    assert phases["serve.execute"]["samples"] >= 2
    assert str(os.getpid()) in rep["lanes"]
    assert rep["lanes"][str(os.getpid())]["blackbox"] == "test"
    # the merged chrome doc stays valid JSON with the bundle folded in
    json.dumps(merged_chrome([path]))


def test_fleet_report_part_from_bundle(tmp_path):
    from fleet_report import jsonl_to_part

    path = _bundle_with_profiler(tmp_path)
    part = jsonl_to_part(path)
    assert part["role"].startswith("blackbox:")
    assert part["blackbox_reason"] == "test"
    assert part["wall_epoch"] is not None
    assert any(e["name"].startswith("prof:") for e in part["spans"])
    doc = merge_chrome_parts([part])
    assert any(e.get("name", "").startswith("prof:")
               for e in doc["traceEvents"])


# ---------------------------------------------------------------------------
# 7. the env switches, in a fresh process
# ---------------------------------------------------------------------------

def _child_env(tmp_path, **extra):
    env = dict(os.environ)
    env.pop("MXNET_OBS_JSONL", None)
    env.update({"JAX_PLATFORMS": "cpu", "MXNET_OBS": "1",
                "MXNET_OBS_TAIL": "1", "MXNET_OBS_PROF": "1",
                "MXNET_OBS_BLACKBOX_DIR": str(tmp_path),
                "MXNET_OBS_BLACKBOX_FLUSH_S": "0.2",
                "PYTHONPATH": REPO}, **extra)
    return env


def test_env_switches_arm_the_plane_and_sigkill_leaves_a_last_bundle(
        tmp_path):
    code = (
        "import os, time, signal\n"
        "from mxnet_tpu import obs\n"
        "assert obs.tail.enabled()\n"
        "assert obs.profile.enabled()\n"
        "assert obs.blackbox.enabled()\n"
        "with obs.trace.span('child.work'):\n"
        "    time.sleep(0.05)\n"
        "time.sleep(0.8)\n"  # let the periodic flush run
        "os.kill(os.getpid(), signal.SIGKILL)\n")
    proc = subprocess.run([sys.executable, "-c", code], timeout=120,
                          env=_child_env(tmp_path))
    assert proc.returncode == -signal.SIGKILL
    last = [f for f in os.listdir(tmp_path) if f.endswith("-last.json")]
    assert len(last) == 1, "SIGKILL'd child left no flushed bundle"
    doc = json.load(open(tmp_path / last[0]))
    assert is_bundle(doc) and doc["reason"] == "flush"
    assert any(e["name"] == "child.work" for e in doc["events"])


def test_sigterm_hook_dumps_a_bundle_before_dying(tmp_path):
    code = (
        "import os, signal\n"
        "from mxnet_tpu import obs\n"
        "with obs.trace.span('child.work'):\n"
        "    pass\n"
        "os.kill(os.getpid(), signal.SIGTERM)\n")
    proc = subprocess.run([sys.executable, "-c", code], timeout=120,
                          env=_child_env(tmp_path,
                                         MXNET_OBS_BLACKBOX_FLUSH_S="0"))
    assert proc.returncode == -signal.SIGTERM  # default disposition kept
    dumps = [f for f in os.listdir(tmp_path)
             if f.endswith(".json") and "-last" not in f]
    assert len(dumps) == 1
    doc = json.load(open(tmp_path / dumps[0]))
    assert doc["reason"] == "signal:SIGTERM"
    assert any(e["name"] == "child.work" for e in doc["events"])


def test_sigterm_hook_preserves_sig_ign(tmp_path):
    """A process that deliberately IGNORES SIGTERM must stay alive when
    the recorder is armed — chaining must not turn SIG_IGN into the
    default fatal disposition (regression: it re-raised)."""
    code = (
        "import os, signal, sys\n"
        "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
        "from mxnet_tpu import obs\n"
        "with obs.trace.span('child.work'):\n"
        "    pass\n"
        "os.kill(os.getpid(), signal.SIGTERM)\n"
        "print('ALIVE')\n"
        "sys.exit(0)\n")
    proc = subprocess.run([sys.executable, "-c", code], timeout=120,
                          capture_output=True, text=True,
                          env=_child_env(tmp_path,
                                         MXNET_OBS_BLACKBOX_FLUSH_S="0"))
    assert proc.returncode == 0 and "ALIVE" in proc.stdout, proc.stderr[-800:]
    # the bundle is still dumped — the signal just stays non-fatal
    dumps = [f for f in os.listdir(tmp_path)
             if f.endswith(".json") and "-last" not in f]
    assert len(dumps) == 1
    assert json.load(open(tmp_path / dumps[0]))["reason"] == "signal:SIGTERM"


def test_signal_dump_does_not_deadlock_on_held_locks(tmp_path):
    """Signal handlers run on the main thread, whose interrupted frame
    may hold any non-reentrant lock ``bundle_dict`` needs (a histogram's
    observe lock, the serve hot path). The dump runs on a bounded side
    thread: worst case is a lost bundle, never a SIGTERM that wedges."""
    obs.enable()
    blackbox.enable(str(tmp_path), flush_s=0)
    h = metrics.registry.histogram("serve.latency_seconds")
    with h._lock:  # the frame a signal would interrupt mid-observe
        t0 = time.monotonic()
        blackbox._dump_from_signal("signal:TEST", timeout=0.5)
        assert time.monotonic() - t0 < 5.0  # returned, did not deadlock
    # lock released: the parked side thread completes its dump
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and blackbox.recorder.dumps < 1:
        time.sleep(0.02)
    assert blackbox.recorder.dumps == 1
    dumps = [f for f in os.listdir(tmp_path)
             if f.endswith(".json") and "-last" not in f]
    assert len(dumps) == 1


# ---------------------------------------------------------------------------
# 8. flagship: fleet under load — tail retention + SIGKILL bundle
# ---------------------------------------------------------------------------

def _save_linear_ckpt(tmpdir):
    prefix = os.path.join(str(tmpdir), "lin")
    data = sym.Variable("data")
    net = sym.FullyConnected(data, num_hidden=4, no_bias=True, name="fc")
    save_checkpoint(prefix, 0, net,
                    {"fc_weight": nd.array(np.eye(4, dtype=np.float32))},
                    {})
    return prefix


@pytest.mark.chaos
@pytest.mark.slow
def test_flagship_tail_retention_and_flight_recorder_across_fleet(tmp_path):
    """The acceptance drive: a ProcReplica fleet under mixed load with
    head sampling LOW and tail mode ON — every deadline-exceeded
    request's cross-process trace (client→front→replica one trace_id) is
    retained, fast-path traces drop within budget, and a SIGKILL'd
    replica leaves a flight-recorder bundle whose profiler lane the fleet
    report merges into the one timeline."""
    import fleet_report as fr

    prefix = _save_linear_ckpt(tmp_path)
    obs_dir = str(tmp_path / "obs")
    obs.enable()
    context.set_sample_rate(0.01)   # head sampling would miss ~everything
    tail.enable()
    tail.buffer().policy = tail.RetentionPolicy(
        slow_ms=1e9, budget_per_s=1e9, burst=1e9, baseline=0.0)
    profile.start(hz=67)
    env = {"MXNET_SERVE_PLATFORM": "cpu", "JAX_PLATFORMS": "cpu",
           "MXNET_OBS_BLACKBOX_FLUSH_S": "0.25",
           "MXNET_OBS_TAIL_HOLD_S": "60"}
    pool = ReplicaPool.spawn(prefix, 2, env=env, obs_dir=obs_dir,
                             probe_interval=0.2, backoff_base=0.1,
                             backoff_cap=1.0, ready_timeout=180).start()
    front = None
    try:
        router = Router(pool, breaker_cooldown=0.3)
        front = FleetServer(router, port=0)
        front.start()
        addr = ("127.0.0.1", front.port)

        x = np.arange(8, dtype=np.float32).reshape(2, 4)
        ok = deadlined = 0
        cli = ServeClient(*addr)
        for i in range(50):
            try:
                if i % 10 == 3:
                    # an impossible deadline: the interesting request the
                    # north-star regime must NEVER lose (shed at the
                    # front — its trace is client→front)
                    cli.infer(x, deadline_ms=0.0001)
                else:
                    np.testing.assert_array_equal(
                        cli.infer(x, deadline_ms=10000), x)
                    ok += 1
            except serve.DeadlineExceeded:
                deadlined += 1
            except (serve.RequestRejected, serve.Draining):
                pass
        # the "keep THIS one" escape hatch: forced roots record durably
        # on EVERY hop at once — the replica included
        with tail.forced():
            for _ in range(3):
                np.testing.assert_array_equal(
                    cli.infer(x, deadline_ms=10000), x)
        # and one request whose root the TEST owns: its spans pend on
        # every hop (client ring stays empty) until the verdict rides a
        # telemetry collection — the replica-buffer promotion path
        held_root = context.new_root()
        assert held_root.tail
        with context.use(held_root):
            for _ in range(4):   # round-robin: BOTH replicas hold spans
                np.testing.assert_array_equal(
                    cli.infer(x, deadline_ms=10000), x)
        assert deadlined >= 4 and ok >= 40

        # every deadline-exceeded request was retained, by reason
        st = tail.stats()
        assert st["retained"] >= deadlined + 3
        retained_deadline = metrics.registry.counter(
            "tail.retained.deadline").value
        assert retained_deadline == deadlined
        # the fast path dropped (uniform baseline pinned to 0 here)
        assert st["dropped"] >= ok * 0.9
        ring = [e for e in obs.trace.tracer.events()]
        retained_ids = set(tail.retained_ids())
        ring_tids = {(r[6] or {}).get("trace_id") for r in ring}
        assert ring_tids - {None} <= retained_ids | {held_root.trace_id}
        # a retained deadline trace stitches client→front on one trace_id
        by_name = {}
        for r in ring:
            if (r[6] or {}).get("trace_id"):
                by_name.setdefault(r[1], set()).add(r[6]["trace_id"])
        assert by_name.get("serve.client.rpc", set()) \
            & by_name.get("serve.rpc", set())

        # SIGKILL one replica mid-fleet; its bundle is the evidence
        kill_pid = pool.members()[0].handle.proc.pid
        time.sleep(0.6)             # ≥2 flush periods of profiler samples
        pool.kill(0)
        deadline_t = time.monotonic() + 120
        m0 = pool.members()[0]
        while time.monotonic() < deadline_t and not (
                m0.restarts >= 1 and m0.state == "ready"):
            time.sleep(0.3)

        # one collection settles the fleet: the verdict list (plus the
        # held root's id) fans out and the replicas' pending spans
        # promote into the very parts this collection returns
        tel = cli.telemetry(drain=True, retained=[held_root.trace_id])
        cli.close()
        parts = tel["parts"]
        assert len(parts) >= 2      # front + at least the survivor
        exec_tids = {
            (s.get("args") or {}).get("trace_id")
            for p in parts[1:] for s in p.get("spans") or ()
            if s.get("name") in ("serve.rpc", "serve.queue_wait",
                                 "serve.execute")}
        exec_tids.discard(None)
        assert exec_tids, "no replica-side spans were collected"
        # the fleet retains or drops a trace AS A UNIT: every replica-side
        # trace id was retained by a verdict (forced, policy, or the held
        # root's explicit resolve) — never a dropped fast-path trace
        all_retained = set(tail.retained_ids())
        assert held_root.trace_id in all_retained   # resolve logged it
        assert exec_tids <= all_retained, \
            "a replica kept spans the fleet's verdict never retained"
        # the held root's replica spans promoted WITH this collection
        assert held_root.trace_id in exec_tids
        # at least one trace has all three hops stitched
        front_tids = {
            (s.get("args") or {}).get("trace_id")
            for s in parts[0].get("spans") or ()
            if s.get("name") == "fleet.route"}
        client_tids = by_name.get("serve.client.rpc", set())
        assert exec_tids & front_tids & client_tids

        # the corpse's flight-recorder bundle survived the SIGKILL
        bundle_path = os.path.join(obs_dir,
                                   f"blackbox-{kill_pid}-last.json")
        assert os.path.exists(bundle_path), \
            f"no last bundle for killed pid {kill_pid} in {obs_dir}"
        part = fr.jsonl_to_part(bundle_path)
        assert part["pid"] == kill_pid
        prof_spans = [e for e in part["spans"]
                      if e["name"].startswith("prof:")]
        assert prof_spans, "bundle carries no profiler lane"
        # ... attributing the corpse's last seconds by phase: every lane
        # entry names a phase and carries its sampled leaf frame
        assert all(e["name"][5:] and "leaf" in (e.get("args") or {})
                   for e in prof_spans)
        merged = merge_chrome_parts(parts + [part])
        lanes = {e["pid"] for e in merged["traceEvents"]}
        assert kill_pid in lanes
        assert any(e.get("name", "").startswith("prof:")
                   for e in merged["traceEvents"]
                   if e.get("pid") == kill_pid)
        json.dumps(merged)
    finally:
        if front is not None:
            front.stop()
        pool.stop()

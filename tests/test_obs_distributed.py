"""Distributed tracing + fleet telemetry plane (``pytest -m obs`` /
``make obs``) — docs/OBSERVABILITY.md "Distributed tracing".

Covers the cross-process half of observability:

1. trace context — W3C traceparent roundtrip, tolerant parsing, key-field
   injection/extraction, head-based sampling semantics;
2. propagation — one trace_id across client → server → batcher → engine
   spans with a correct parent chain, in one process and over the wire;
3. wire compatibility — old-format frames (no context) against the new
   server (accepted, new root), context-bearing frames against the
   context-stripping server on BOTH planes (serve INFER + PS push/pull hit
   the right keys);
4. the telemetry plane — ``OP_TELEMETRY`` drain semantics, Prometheus
   exposition validity, STATS embedding the metrics snapshot, chrome-part
   merging with per-pid lanes and clock rebasing;
5. SLO math — attainment / burn / p99 / breach callbacks from merged
   metrics; breaker open-time accounting;
6. (slow, chaos flagship) 2 ProcReplicas behind a FleetServer under mixed
   load with one replica SIGKILLed mid-run → ONE collected merged trace
   where every sampled INFER's replica spans share the client's trace_id
   and the kill is a tagged event on the same timeline, with the corpse's
   JSONL evidence merged back in by pid lane.
"""
import json
import os
import re
import socket
import struct
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, obs, serve
from mxnet_tpu import symbol as sym
from mxnet_tpu.obs import context
from mxnet_tpu.obs.export import (hist_quantile, merge_chrome_parts,
                                  merge_metrics, parts_to_prometheus,
                                  to_prometheus)
from mxnet_tpu.obs.slo import SLOMonitor
from mxnet_tpu.model import save_checkpoint
from mxnet_tpu.serve import ServeClient, ServeServer
from mxnet_tpu.serve.fleet import (CircuitBreaker, FleetServer, ProcReplica,
                                   ReplicaPool, Router)
from mxnet_tpu.serve.server import OP_INFER, STATUS_OK, _INFER_HDR
from mxnet_tpu.kvstore.ps_server import (PSServer, _pack_arrays, _recv_msg,
                                         _send_msg, _unpack_arrays)

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


@pytest.fixture(autouse=True)
def _obs_clean():
    """Telemetry off + empty + sample rate 1.0 around every test."""
    obs.disable()
    obs.reset()
    context.set_sample_rate(1.0)
    yield
    obs.disable()
    obs.reset()
    context.set_sample_rate(1.0)


@pytest.fixture
def obs_on(_obs_clean):
    obs.enable()
    yield


def _linear_engine(scale=1.0):
    data = sym.Variable("data")
    net = sym.FullyConnected(data, num_hidden=4, no_bias=True, name="fc")
    arg = {"fc_weight": np.eye(4, dtype=np.float32) * scale}
    return serve.InferenceEngine(net, arg, max_batch_size=8, lint="off")


X = np.arange(8, dtype=np.float32).reshape(2, 4)


# ---------------------------------------------------------------------------
# 1. trace context
# ---------------------------------------------------------------------------

def test_traceparent_header_roundtrip():
    ctx = context.new_root(sampled=True)
    h = ctx.to_header()
    assert re.fullmatch(r"00-[0-9a-f]{32}-[0-9a-f]{16}-01", h)
    back = context.from_header(h)
    assert back == ctx
    # unsampled flag survives
    u = context.TraceContext(ctx.trace_id, ctx.span_id, sampled=False)
    assert context.from_header(u.to_header()).sampled is False


@pytest.mark.parametrize("bad", [
    "", "garbage", "00-xyz-123-01", "01-" + "a" * 32 + "-" + "b" * 16 + "-01",
    "00-" + "0" * 32 + "-" + "b" * 16 + "-01",   # all-zero trace id
    "00-" + "a" * 32 + "-" + "0" * 16 + "-01",   # all-zero span id
])
def test_malformed_header_parses_to_none(bad):
    assert context.from_header(bad) is None


def test_key_injection_roundtrip():
    ctx = context.new_root()
    for key in ("", "fc_weight", "arg:stage2_unit1_bn1_gamma"):
        wire = context.inject_key(key, ctx)
        back_key, back_ctx = context.extract_key(wire)
        assert back_key == key
        assert back_ctx == ctx
    # no context → byte-identical key (the old wire format)
    assert context.inject_key("w", None) == "w"
    assert context.extract_key("w") == ("w", None)


def test_head_sampling_decision_at_root():
    context.set_sample_rate(0.0)
    assert context.new_root().sampled is False
    context.set_sample_rate(1.0)
    assert context.new_root().sampled is True
    # children inherit the decision, never re-roll
    unsampled = context.TraceContext("a" * 32, "b" * 16, sampled=False)
    assert unsampled.child().sampled is False


def test_span_context_parent_chain(obs_on):
    root = context.new_root()
    with context.use(root):
        with obs.trace.span("outer"):
            with obs.trace.span("inner"):
                pass
    evs = {e["name"]: e["args"] for e in obs.trace.drain()
           if e["ph"] == "X"}
    assert evs["outer"]["trace_id"] == root.trace_id
    assert evs["outer"]["parent_id"] == root.span_id
    assert evs["inner"]["parent_id"] == evs["outer"]["span_id"]
    assert evs["inner"]["trace_id"] == root.trace_id
    # the context pops with the spans
    assert context.current() is None


# ---------------------------------------------------------------------------
# 2. propagation over the serve wire
# ---------------------------------------------------------------------------

def _serve_pair(engine=None, **kw):
    srv = ServeServer(engine or _linear_engine(), port=0,
                      max_linger_ms=0.0, **kw)
    srv.start()
    return srv, ServeClient("127.0.0.1", srv.port)


def test_serve_infer_one_trace_id_client_to_engine(obs_on):
    srv, cli = _serve_pair()
    try:
        out = cli.infer(X)
        np.testing.assert_array_equal(out, X)
    finally:
        cli.close()
        srv.stop()
    spans = {e["name"]: e["args"] for e in obs.trace.drain()
             if e["ph"] == "X" and e.get("args")}
    for name in ("serve.client.rpc", "serve.rpc", "serve.queue_wait",
                 "serve.batch_assembly", "serve.execute",
                 "serve.serialize"):
        assert name in spans, f"missing {name}"
    tids = {s["trace_id"] for s in spans.values() if "trace_id" in s}
    assert len(tids) == 1  # ONE trace across client, server, batcher, engine
    # parent chain: server rpc hangs off the client rpc span; the batcher
    # phases hang off the server rpc span even though they ran on other
    # threads
    assert (spans["serve.rpc"]["parent_id"]
            == spans["serve.client.rpc"]["span_id"])
    assert (spans["serve.queue_wait"]["parent_id"]
            == spans["serve.rpc"]["span_id"])
    assert (spans["serve.execute"]["parent_id"]
            == spans["serve.rpc"]["span_id"])


def test_unsampled_request_succeeds_and_records_nothing(obs_on):
    context.set_sample_rate(0.0)
    obs.trace.drain()
    srv, cli = _serve_pair()
    try:
        np.testing.assert_array_equal(cli.infer(X), X)
    finally:
        cli.close()
        srv.stop()
    names = [e["name"] for e in obs.trace.drain()
             if e["name"].startswith("serve.")]
    assert names == []  # head-based: the whole trace skipped on every hop


def test_sampled_member_keeps_execute_span_behind_unsampled_lead(obs_on):
    """Head sampling: when an UNSAMPLED request opens a batch and a
    sampled one joins it, the batch-level execute/assembly spans must pin
    to the sampled member — a sampled trace never loses its hops to the
    luck of batch order."""
    from mxnet_tpu.serve.batcher import DynamicBatcher

    batcher = DynamicBatcher(_linear_engine(), max_linger_ms=80.0,
                             max_queue=16)
    unsampled = context.TraceContext("e" * 32, "f" * 16, sampled=False)
    sampled = context.new_root(sampled=True)
    try:
        with context.use(unsampled):
            f1 = batcher.submit([X[:1]])   # opens the batch, lingers
        with context.use(sampled):
            f2 = batcher.submit([X[1:]])   # joins it
        f1.result(timeout=10)
        f2.result(timeout=10)
    finally:
        batcher.close()
    evs = [e for e in obs.trace.drain() if e["ph"] == "X"]
    spans = {e["name"]: (e.get("args") or {}) for e in evs}
    assert spans["serve.execute"].get("trace_id") == sampled.trace_id
    assert spans["serve.batch_assembly"].get("trace_id") == sampled.trace_id
    # the unsampled member's own queue_wait stays unrecorded
    waits = [e for e in evs if e["name"] == "serve.queue_wait"]
    assert len(waits) == 1
    assert waits[0]["args"]["trace_id"] == sampled.trace_id


def test_hedged_attempt_carries_trace_context(obs_on):
    """Hedging races attempts on fresh threads; the trace context must
    ride along — a hedged request that re-rooted downstream would fall
    out of the client's trace (and re-roll its sampling decision)."""
    from mxnet_tpu.serve.fleet import LocalReplica, ReplicaPool, Router

    def factory(delay):
        def f():
            eng = _linear_engine()
            if delay:
                real = eng.infer

                def slow(inputs, n_valid=None):
                    time.sleep(delay)
                    return real(inputs, n_valid=n_valid)

                eng.infer = slow
            s = ServeServer(eng, port=0, max_linger_ms=0.0)
            s.start()
            return s
        return f

    pool = ReplicaPool([LocalReplica(factory(0.6)), LocalReplica(factory(0))],
                       probe_interval=0.1, backoff_base=0.05,
                       ready_timeout=60).start()
    try:
        router = Router(pool, hedge_ms=60.0)
        root = context.new_root()
        with context.use(root):
            outs, _ = router.infer([X], deadline_ms=15000)
        np.testing.assert_array_equal(outs[0], X)
        assert router.hedges >= 1  # the race actually happened
    finally:
        pool.stop()
    evs = obs.trace.drain()
    route_tids = {(e.get("args") or {}).get("trace_id") for e in evs
                  if e["name"] == "fleet.route"}
    exec_tids = {(e.get("args") or {}).get("trace_id") for e in evs
                 if e["name"] == "serve.execute"}
    assert route_tids == {root.trace_id}  # no re-rooted hedge thread
    assert exec_tids and exec_tids <= {root.trace_id}


def test_ambient_context_reused_not_rerooted(obs_on):
    """A client already inside a traced flow must JOIN it, not start a
    fresh trace per RPC."""
    srv, cli = _serve_pair()
    root = context.new_root()
    try:
        with context.use(root):
            cli.infer(X)
            cli.infer(X)
    finally:
        cli.close()
        srv.stop()
    tids = {e["args"]["trace_id"] for e in obs.trace.drain()
            if e["ph"] == "X" and "trace_id" in (e.get("args") or {})}
    assert tids == {root.trace_id}


# ---------------------------------------------------------------------------
# 3. wire compatibility
# ---------------------------------------------------------------------------

def test_old_format_frame_accepted_becomes_new_root(obs_on):
    """An old client's INFER (no context suffix anywhere) against the new
    server: accepted, answered, and traced under a fresh root."""
    srv, _ = _serve_pair()
    try:
        s = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
        payload = (_INFER_HDR.pack(0.0, 1)
                   + _pack_arrays([np.ascontiguousarray(X)]))
        _send_msg(s, OP_INFER, "", payload)  # the literal old wire bytes
        op, key, reply = _recv_msg(s)
        assert op == OP_INFER and reply[0] == STATUS_OK
        outs, _ = _unpack_arrays(reply[5:])
        np.testing.assert_array_equal(outs[0], X)
        s.close()
    finally:
        srv.stop()
    spans = {e["name"]: (e.get("args") or {}) for e in obs.trace.drain()
             if e["ph"] == "X"}
    assert "serve.rpc" in spans and "serve.execute" in spans
    # absent context = new root AT THE SERVER: replica-side spans still
    # stitch to one (server-born) trace
    assert (spans["serve.rpc"].get("trace_id")
            == spans["serve.execute"].get("trace_id") is not None)


def test_ps_wire_context_stripped_before_key_lookup(obs_on):
    """New client → context-stripping server on the PS plane: a
    context-suffixed key must hit the SAME weight/seq tables as its plain
    form, and both halves of the RPC trace under one id."""
    from mxnet_tpu.kvstore.ps_client import PSClient

    srv = PSServer(host="127.0.0.1", port=0, num_workers=1)
    srv.start()
    try:
        cli = PSClient("127.0.0.1", srv.port, timeout=5, retries=2,
                       retry_interval=0.05)
        w = np.ones((4, 3), np.float32)
        root = context.new_root()
        with context.use(root):
            cli.init("w", w)
            cli.push("w", np.full((4, 3), 0.5, np.float32))
            out = cli.pull("w")
        np.testing.assert_allclose(out, w + 0.5)
        # old-format (no active context): same key, same tables
        cli.push("w", np.full((4, 3), 0.5, np.float32))
        np.testing.assert_allclose(cli.pull("w"), w + 1.0)
    finally:
        srv.stop()
    evs = obs.trace.drain()
    traced = {(e["name"], (e.get("args") or {}).get("key"))
              for e in evs
              if (e.get("args") or {}).get("trace_id") == root.trace_id}
    assert ("kvstore.rpc", "w") in traced
    assert ("kvstore.server.rpc", "w") in traced  # clean key server-side


def test_wire_context_kill_switch(obs_on, monkeypatch):
    monkeypatch.setattr(context, "_WIRE", False)
    ctx = context.new_root()
    assert context.inject_key("w", ctx) == "w"  # byte-identical old wire
    monkeypatch.setattr(context, "_WIRE", True)
    assert context.CTX_SEP in context.inject_key("w", ctx)


# ---------------------------------------------------------------------------
# 4. the telemetry plane
# ---------------------------------------------------------------------------

def test_stats_embeds_metrics_snapshot(obs_on):
    srv, cli = _serve_pair()
    try:
        cli.infer(X)
        st = cli.stats()
    finally:
        cli.close()
        srv.stop()
    # ONE schema: the registry snapshot rides STATS
    assert set(st["metrics"]) == {"counters", "gauges", "histograms"}
    assert "serve.latency_seconds" in st["metrics"]["histograms"]
    assert st["metrics"]["histograms"]["serve.latency_seconds"]["count"] >= 1


def test_telemetry_endpoint_drains_and_exposes_prometheus(obs_on):
    srv, cli = _serve_pair()
    try:
        cli.infer(X)
        tel = cli.telemetry()
        part = tel["parts"][0]
        assert part["pid"] == os.getpid()
        assert part["wall_epoch"] > 0
        assert {e["name"] for e in part["spans"]} >= {
            "serve.rpc", "serve.execute"}
        assert "serve.latency_seconds" in part["metrics"]["histograms"]
        # drained: a second collection only carries what happened since
        tel2 = cli.telemetry()
        names2 = {e["name"] for e in tel2["parts"][0]["spans"]}
        assert "serve.execute" not in names2
        prom = cli.telemetry(fmt="prometheus")
    finally:
        cli.close()
        srv.stop()
    # exposition parses as OpenMetrics: HELP/TYPE headers +
    # name{labels} value, optional exemplars (`# {trace_id="..."} value
    # [ts]`) riding histogram bucket lines, `# EOF` terminating
    line_re = re.compile(
        r"^(# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge|histogram)"
        r"|# HELP [a-zA-Z_:][a-zA-Z0-9_:]* \S.*"
        r"|# EOF"
        r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [0-9eE+.inf-]+"
        r"( # \{[^}]*\} [0-9eE+.inf-]+( [0-9eE+.-]+)?)?)$")
    lines = [ln for ln in prom.splitlines() if ln]
    assert lines, "empty exposition"
    for ln in lines:
        assert line_re.match(ln), f"invalid exposition line: {ln!r}"
    assert lines[-1] == "# EOF"
    assert any("mxnet_serve_latency_seconds_bucket" in ln
               and 'le="' in ln for ln in lines)
    # HELP precedes TYPE for described families (the description registry)
    idx = {ln.split(" ", 3)[2]: i for i, ln in enumerate(lines)
           if ln.startswith("# TYPE ")}
    for i, ln in enumerate(lines):
        if ln.startswith("# HELP "):
            fam = ln.split(" ", 3)[2]
            assert idx.get(fam, -1) == i + 1, f"HELP/TYPE split for {fam}"


def test_prometheus_histogram_buckets_are_cumulative():
    obs.enable()
    for v in (0.0002, 0.0002, 0.04, 3.0):
        obs.observe("t.lat_seconds", v)
    text = to_prometheus(obs.metrics.snapshot(), labels={"pid": "7"})
    counts = [int(m.group(2)) for m in re.finditer(
        r'mxnet_t_lat_seconds_bucket\{le="([^"]+)",pid="7"\} (\d+)', text)]
    assert counts == sorted(counts)  # cumulative, monotonically increasing
    assert counts[-1] == 4
    assert 'mxnet_t_lat_seconds_count{pid="7"} 4' in text


def test_merge_chrome_parts_lanes_and_clock_rebase():
    parts = [
        {"pid": 100, "role": "fleet", "wall_epoch": 1000.0,
         "spans": [{"ph": "X", "name": "fleet.route", "ts": 0.5,
                    "dur": 0.1, "tid": 1}],
         "metrics": {"counters": {"c": 1}, "gauges": {}, "histograms": {}}},
        {"pid": 200, "role": "replica0", "wall_epoch": 1002.0,
         "spans": [{"ph": "X", "name": "serve.execute", "ts": 0.1,
                    "dur": 0.05, "tid": 2},
                   {"ph": "i", "name": "chaos.kill", "ts": 0.2, "tid": 2}],
         "metrics": {"counters": {"c": 2}, "gauges": {}, "histograms": {}}},
    ]
    doc = merge_chrome_parts(parts)
    evs = doc["traceEvents"]
    lanes = {e["pid"]: e["args"]["name"] for e in evs
             if e["name"] == "process_name"}
    assert lanes == {100: "fleet", 200: "replica0"}
    route = next(e for e in evs if e["name"] == "fleet.route")
    execu = next(e for e in evs if e["name"] == "serve.execute")
    kill = next(e for e in evs if e["name"] == "chaos.kill")
    # rebased onto shared time: part 2's clock is 2s ahead of part 1's
    assert route["ts"] == pytest.approx(0.5e6)
    assert execu["ts"] == pytest.approx(2.1e6)
    assert kill["ph"] == "i" and kill["ts"] == pytest.approx(2.2e6)
    # distinct pids → metrics summed
    assert doc["otherData"]["metrics"]["counters"]["c"] == 3
    # same pid twice = same registry → counted once
    doc2 = merge_chrome_parts([parts[0], dict(parts[0], role="dup")])
    assert doc2["otherData"]["metrics"]["counters"]["c"] == 1


def test_merge_metrics_histograms_and_quantiles():
    obs.enable()
    for v in (0.001, 0.003, 0.2):
        obs.observe("m.lat", v)
    snap = obs.metrics.snapshot()
    merged = merge_metrics([snap, snap])
    h = merged["histograms"]["m.lat"]
    assert h["count"] == 6
    assert h["sum"] == pytest.approx(2 * 0.204)
    assert h["min"] == pytest.approx(0.001)
    assert h["max"] == pytest.approx(0.2)
    # bucket-resolution estimate: 0.2 falls in the le=0.25 bucket (the
    # registry's own quantile() contract)
    assert hist_quantile(h, 0.99) == pytest.approx(0.25)
    assert h["p50"] <= h["p99"]


def test_trace_report_merges_files_onto_pid_lanes(tmp_path):
    import trace_report

    a, b, c = (str(tmp_path / n) for n in ("a.jsonl", "b.jsonl", "c.jsonl"))
    with open(a, "w") as f:
        f.write(json.dumps({"ph": "M", "name": "clock", "pid": 11,
                            "wall_epoch": 500.0}) + "\n")
        f.write(json.dumps({"ph": "X", "name": "forward", "ts": 1.0,
                            "dur": 0.1, "tid": 1, "pid": 11}) + "\n")
    with open(b, "w") as f:
        f.write(json.dumps({"ph": "M", "name": "clock", "pid": 22,
                            "wall_epoch": 503.0}) + "\n")
        f.write(json.dumps({"ph": "X", "name": "serve.execute", "ts": 0.5,
                            "dur": 0.2, "tid": 2, "pid": 22}) + "\n")
    rep = trace_report.report([a, b])
    assert set(rep["lanes"]) == {"11", "22"}
    assert rep["clock_note"] is None  # both anchored: timestamps trusted
    by_name = {s["name"]: s for s in rep["top_spans"]}
    # rebased: b's event lands 3s after a's anchor + its own offset
    assert by_name["serve.execute"]["ts"] == pytest.approx(3.5)
    assert by_name["forward"]["ts"] == pytest.approx(1.0)
    # an anchor-less file merges with an explicit clock-skew note
    with open(c, "w") as f:
        f.write(json.dumps({"ph": "X", "name": "legacy", "ts": 0.0,
                            "dur": 0.01, "tid": 3}) + "\n")
    rep2 = trace_report.report([a, c])
    assert rep2["clock_note"] and "clock" in rep2["clock_note"]
    # single-file reports keep the old shape (no note, one lane)
    rep3 = trace_report.report(a)
    assert rep3["clock_note"] is None and rep3["n_spans"] == 1
    # --chrome-out writes a loadable merged document
    out = str(tmp_path / "merged.json")
    trace_report.main([a, b, "--chrome-out", out, "--json"])
    doc = json.load(open(out))
    assert {e["pid"] for e in doc["traceEvents"]
            if e.get("ph") == "X"} == {11, 22}


# ---------------------------------------------------------------------------
# 5. SLO math + breaker accounting
# ---------------------------------------------------------------------------

def test_slo_monitor_attainment_burn_and_callbacks():
    obs.enable()
    for _ in range(98):
        obs.observe("serve.latency_seconds", 0.005)
    obs.inc("serve.shed_deadline", 2)
    obs.inc("fleet.hedges", 10)
    obs.inc("fleet.hedge_wins", 4)
    snap = obs.metrics.snapshot()
    fired = []
    mon = SLOMonitor(deadline_target=0.99).on_breach(
        lambda rep, br: fired.append([b["rule"] for b in br]))
    rep = mon.evaluate(snap)
    assert rep["requests_finished"] == 100
    assert rep["deadline_attainment"] == pytest.approx(0.98)
    # capacity sheds must NOT dilute the deadline denominator: a saturated
    # fleet rejecting 900 requests still reports the same attainment
    obs.inc("serve.shed_queue_full", 900)
    rep_sat = mon.evaluate(obs.metrics.snapshot())
    assert rep_sat["deadline_attainment"] == pytest.approx(0.98)
    assert rep_sat["requests_finished"] == 1000
    assert rep_sat["shed_rate"] == pytest.approx(902 / 1000)
    assert rep["error_budget_burn"] == pytest.approx(2.0)
    assert rep["hedge_win_rate"] == pytest.approx(0.4)
    assert [b["rule"] for b in rep["breaches"]] == ["deadline_attainment"]
    assert fired and "deadline_attainment" in fired[0]
    # healthy snapshot → no breach, no callback
    fired.clear()
    obs.reset()
    obs.enable()
    obs.observe("serve.latency_seconds", 0.005)
    rep2 = mon.evaluate(obs.metrics.snapshot())
    assert rep2["ok"] and not fired
    # breaker open-time prefers the router stats when provided
    rep3 = mon.evaluate(snap, stats={"breaker_open_seconds": 7.5})
    assert rep3["breaker_open_seconds"] == 7.5
    assert "SLO report" in SLOMonitor.render(rep3)


def test_breaker_tracks_open_seconds():
    br = CircuitBreaker(threshold=2, cooldown=0.05)
    assert br.snapshot()["open_seconds"] == 0.0
    br.failure()
    assert br.failure()  # trips open
    time.sleep(0.08)
    assert br.allow()    # half-open probe admitted; still "not closed"
    br.success()         # recovery closes and banks the open time
    snap = br.snapshot()
    assert 0.05 <= snap["open_seconds"] < 5.0
    banked = snap["open_seconds"]
    time.sleep(0.02)     # closed time must NOT accrue
    assert br.snapshot()["open_seconds"] == banked


# ---------------------------------------------------------------------------
# 6. flagship: cross-process fleet, chaos kill, one merged timeline
# ---------------------------------------------------------------------------

def _save_linear_ckpt(tmpdir, scales=(1.0,)):
    prefix = os.path.join(str(tmpdir), "lin")
    data = sym.Variable("data")
    net = sym.FullyConnected(data, num_hidden=4, no_bias=True, name="fc")
    for epoch, scale in enumerate(scales):
        save_checkpoint(prefix, epoch, net,
                        {"fc_weight": nd.array(
                            np.eye(4, dtype=np.float32) * scale)}, {})
    return prefix


@pytest.mark.chaos
@pytest.mark.slow
def test_flagship_fleet_trace_merges_across_processes_with_kill(tmp_path):
    """2 ProcReplicas behind a FleetServer under mixed-shape load, one
    SIGKILLed mid-run. One OP_TELEMETRY collection + the corpse's JSONL
    evidence → a merged chrome trace where (a) every sampled INFER's
    replica-side spans share the client's trace_id, (b) replica spans
    live on OTHER pids' lanes than the client's, and (c) the kill is a
    tagged event on the same timeline."""
    prefix = _save_linear_ckpt(tmp_path, scales=(1.0,))
    obs_dir = str(tmp_path / "obs")
    obs.enable()
    env = {"MXNET_SERVE_PLATFORM": "cpu", "JAX_PLATFORMS": "cpu"}
    pool = ReplicaPool.spawn(prefix, 2, env=env, obs_dir=obs_dir,
                             probe_interval=0.2, backoff_base=0.1,
                             backoff_cap=1.0, ready_timeout=180).start()
    front = None
    client_tids = set()
    try:
        router = Router(pool, breaker_cooldown=0.3)
        front = FleetServer(router, port=0)
        front.start()
        addr = ("127.0.0.1", front.port)
        rng = np.random.RandomState(0)
        shapes = [rng.rand(n, 4).astype(np.float32) for n in (1, 2, 5)]
        stop = threading.Event()
        errors = []

        def load(worker):
            cli = ServeClient(*addr)
            i = 0
            while not stop.is_set():
                x = shapes[(worker + i) % len(shapes)]
                i += 1
                try:
                    out = cli.infer(x, deadline_ms=10000)
                    np.testing.assert_array_equal(out, x)
                except (serve.RequestRejected, serve.Draining,
                        serve.DeadlineExceeded):
                    pass  # clean degradation during the kill window
                except serve.ServeError as e:
                    errors.append(repr(e))
            cli.close()

        workers = [threading.Thread(target=load, args=(w,))
                   for w in range(3)]
        for t in workers:
            t.start()
        time.sleep(1.2)
        pool.kill(0)  # real SIGKILL mid-run
        deadline = time.monotonic() + 120
        m0 = pool.members()[0]
        while time.monotonic() < deadline and not (
                m0.restarts >= 1 and m0.state == "ready"):
            time.sleep(0.3)
        time.sleep(0.5)
        stop.set()
        for t in workers:
            t.join()
        assert not errors, errors[:3]

        # ---- collect: ONE telemetry pull against the front -------------
        ctl = ServeClient(*addr)
        tel = ctl.telemetry()
        ctl.close()
        parts = tel["parts"]
        assert parts[0]["role"] == "fleet"
        assert len(parts) >= 3  # front + 2 live replicas

        # the dead incarnation's evidence: per-pid JSONL files exist and
        # carry at least the kill-era spans; merge them in as extra lanes
        import fleet_report as fr

        jsonls = sorted(os.path.join(obs_dir, f)
                        for f in os.listdir(obs_dir)
                        if f.startswith("replica-"))
        assert len(jsonls) >= 2  # one per spawned pid (incl. the corpse)
        parts = parts + [fr.jsonl_to_part(p) for p in jsonls]

        merged = merge_chrome_parts(parts)
        evs = merged["traceEvents"]
        client_pid = os.getpid()
        client_tids = {
            (e.get("args") or {}).get("trace_id")
            for e in evs
            if e.get("ph") == "X" and e["pid"] == client_pid
            and e["name"] == "serve.client.rpc"
            and (e.get("args") or {}).get("op") == "infer"}
        client_tids.discard(None)
        assert len(client_tids) > 10  # real load got traced

        # (a)+(b): replica-side spans on OTHER pids, stitched by trace_id
        replica_exec = [
            e for e in evs
            if e.get("ph") == "X" and e["pid"] != client_pid
            and e["name"] in ("serve.rpc", "serve.queue_wait",
                              "serve.execute")]
        assert replica_exec, "no replica-side spans collected"
        stitched = {(e.get("args") or {}).get("trace_id")
                    for e in replica_exec}
        stitched.discard(None)
        assert stitched, "replica spans carry no trace ids"
        # every replica-side trace id is a client-born trace (no replica
        # ever re-rooted a context-bearing INFER)
        assert stitched <= client_tids
        # and the fleet.route hop is part of the same traces
        route_tids = {(e.get("args") or {}).get("trace_id")
                      for e in evs if e["name"] == "fleet.route"}
        assert stitched & route_tids

        # (c): the kill is a tagged instant event on the SAME timeline
        kills = [e for e in evs if e.get("ph") == "i"
                 and e["name"] in ("fleet.chaos_kill", "fleet.replica_dead")]
        assert kills, "chaos kill left no tagged event in the merged trace"

        # the merged document is valid chrome-trace JSON end to end
        json.dumps(merged)
    finally:
        if front is not None:
            front.stop()
        pool.stop()

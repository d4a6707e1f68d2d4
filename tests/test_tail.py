"""Tail-based trace retention (``pytest -m blackbox`` / ``make prof``) —
docs/OBSERVABILITY.md "Tail sampling".

The retention policy as a pure function (every edge the budget/baseline/
force rules promise), the pending buffer's settle/straggler/expiry
semantics (a verdict racing replica-side buffer expiry must drop cleanly,
never error), the context-flag wire encoding (tail/force bits beside the
head-sampling bit), root-close verdict plumbing through the thread-local
outcome notes, OpenMetrics exemplars pinning retained trace ids to
latency buckets, the ``# HELP`` description registry, and the end-to-end
serve path: every span of a retained request — client, server, batcher,
engine — lands durably under ONE trace_id while a fast-path request's
spans are dropped on every hop.
"""
import random
import time

import numpy as np
import pytest

from mxnet_tpu import obs, serve
from mxnet_tpu import symbol as sym
from mxnet_tpu.obs import context, metrics, tail
from mxnet_tpu.obs.export import parts_to_prometheus, to_prometheus
from mxnet_tpu.obs.tail import RetentionPolicy, TailBuffer
from mxnet_tpu.serve import ServeClient, ServeServer

pytestmark = [pytest.mark.obs, pytest.mark.blackbox]


@pytest.fixture(autouse=True)
def _clean():
    obs.disable()
    obs.reset()
    tail.disable()
    context.set_sample_rate(1.0)
    yield
    tail.disable()
    obs.disable()
    obs.reset()
    context.set_sample_rate(1.0)


def _keep_all():
    return RetentionPolicy(slow_ms=0.0, budget_per_s=1e9, burst=1e9,
                           baseline=0.0)


def _rec(name="s", tid=1):
    return ("X", name, 0.0, 0.001, tid, 1, {"trace_id": "t"})


# ---------------------------------------------------------------------------
# 1. the retention policy as a pure function
# ---------------------------------------------------------------------------

def test_policy_interesting_outcomes_retain():
    p = RetentionPolicy(slow_ms=1e9, budget_per_s=1e9, burst=1e9,
                        baseline=0.0)
    for outcome in ("error", "shed", "deadline"):
        retain, reason = p.decide(0.001, outcome=outcome)
        assert retain and reason == outcome


def test_policy_flags_and_latency_retain():
    p = RetentionPolicy(slow_ms=250.0, budget_per_s=1e9, burst=1e9,
                        baseline=0.0)
    assert p.decide(0.001, flags=("hedged",)) == (True, "hedged")
    assert p.decide(0.001, flags=("breaker",)) == (True, "breaker")
    assert p.decide(0.3) == (True, "slow")          # past the slow bar
    assert p.decide(0.001) == (False, "fast_path")  # below everything


def test_policy_budget_exhaustion_keeps_the_uniform_baseline():
    # burst of exactly 1 token, zero refill: the first interesting trace
    # consumes the budget ...
    p = RetentionPolicy(slow_ms=1e9, budget_per_s=0.0, burst=1.0,
                        baseline=1.0)
    assert p.decide(0.0, outcome="error", now=0.0) == (True, "error")
    # ... and past it an interesting trace degrades to the BASELINE
    # (probability 1 here), never to zero
    assert p.decide(0.0, outcome="error", now=0.0) == (True, "baseline")
    # with no baseline either, the honest answer is a counted budget drop
    p0 = RetentionPolicy(slow_ms=1e9, budget_per_s=0.0, burst=1.0,
                         baseline=0.0)
    p0.decide(0.0, outcome="error", now=0.0)
    assert p0.decide(0.0, outcome="error", now=0.0) == (False, "budget")


def test_policy_force_retain_bypasses_the_bucket():
    p = RetentionPolicy(slow_ms=1e9, budget_per_s=0.0, burst=0.0,
                        baseline=0.0)
    # zero tokens, zero baseline — force still keeps it
    assert p.decide(0.0, outcome="error", forced=True) == (True, "forced")
    # and consumed no budget: the next forced one is identical
    assert p.decide(0.0, forced=True) == (True, "forced")


def test_policy_token_bucket_refills_over_time():
    p = RetentionPolicy(slow_ms=1e9, budget_per_s=1.0, burst=1.0,
                        baseline=0.0)
    assert p.decide(0.0, outcome="error", now=0.0)[0] is True
    assert p.decide(0.0, outcome="error", now=0.5) == (False, "budget")
    # one full second of refill since the failed take → a token again
    assert p.decide(0.0, outcome="error", now=1.6)[0] is True


def test_policy_uniform_baseline_on_the_fast_path():
    keep = RetentionPolicy(slow_ms=1e9, budget_per_s=0.0, burst=0.0,
                           baseline=1.0, rng=random.Random(7))
    assert keep.decide(0.001) == (True, "baseline")
    drop = RetentionPolicy(slow_ms=1e9, budget_per_s=0.0, burst=0.0,
                           baseline=0.0)
    assert drop.decide(0.001) == (False, "fast_path")


# ---------------------------------------------------------------------------
# 2. the pending buffer: settle, stragglers, expiry races
# ---------------------------------------------------------------------------

def test_buffer_finish_promotes_whole_trace_to_the_ring():
    obs.enable()
    b = TailBuffer(policy=_keep_all())
    b.hold("t1", _rec("serve.rpc"))
    b.hold("t1", _rec("serve.execute"))
    assert b.pending_count() == 1
    retain, reason = b.finish("t1", 0.01)
    assert retain and reason == "slow"
    names = [r[1] for r in obs.trace.tracer.events()]
    assert names == ["serve.rpc", "serve.execute"]
    assert "t1" in b.retained_ids()


def test_buffer_drop_records_nothing():
    obs.enable()
    b = TailBuffer(policy=RetentionPolicy(slow_ms=1e9, baseline=0.0))
    b.hold("t1", _rec())
    assert b.finish("t1", 0.0)[0] is False
    assert obs.trace.tracer.events() == []
    assert b.pending_count() == 0


def test_buffer_straggler_span_follows_the_verdict():
    obs.enable()
    b = TailBuffer(policy=_keep_all())
    b.hold("kept", _rec("first"))
    b.finish("kept", 0.01)
    b.hold("kept", _rec("straggler"))      # raced the root close: kept
    assert [r[1] for r in obs.trace.tracer.events()] == ["first",
                                                         "straggler"]
    b2 = TailBuffer(policy=RetentionPolicy(slow_ms=1e9, baseline=0.0))
    b2.finish("dropped", 0.0)
    b2.hold("dropped", _rec("late"))       # dropped trace: span drops too
    assert b2.pending_count() == 0
    assert len(obs.trace.tracer.events()) == 2  # unchanged


def test_buffer_resolve_promotes_pending_replica_side():
    obs.enable()
    b = TailBuffer(policy=_keep_all())
    b.hold("t9", _rec("replica.span"))
    assert b.resolve(["t9", "unknown-id"]) == 1
    assert [r[1] for r in obs.trace.tracer.events()] == ["replica.span"]


def test_verdict_racing_buffer_expiry_drops_cleanly():
    """The satellite case: a replica held spans briefly, expired them,
    THEN the verdict arrived — resolve must be a counted no-op, and a
    straggler span for the expired trace must drop, never error."""
    obs.enable()
    b = TailBuffer(policy=_keep_all(), hold_s=0.01)
    b.hold("slowpoke", _rec())
    assert b.expire(now=time.monotonic() + 1.0) == 1
    assert b.expired == 1
    assert b.resolve(["slowpoke"]) == 0      # verdict lost the race
    b.hold("slowpoke", _rec("late"))         # straggler after expiry
    assert b.pending_count() == 0
    assert obs.trace.tracer.events() == []   # nothing ever promoted


def test_buffer_overflow_evicts_oldest_counted():
    b = TailBuffer(policy=_keep_all(), max_traces=2)
    for tid in ("a", "b", "c"):
        b.hold(tid, _rec())
    assert b.pending_count() == 2
    assert b.overflow == 1
    # the evicted trace can no longer promote
    assert b.resolve(["a"]) == 0


def test_buffer_caps_spans_per_trace():
    obs.enable()
    b = TailBuffer(policy=_keep_all(), max_spans=2)
    for i in range(5):
        b.hold("t", _rec(f"s{i}"))
    b.finish("t", 0.01)
    assert [r[1] for r in obs.trace.tracer.events()] == ["s0", "s1"]


# ---------------------------------------------------------------------------
# 3. context flags on the wire
# ---------------------------------------------------------------------------

def test_retained_log_scales_with_budget_and_hold_window():
    # the verdict log must cover everything the policy can retain within
    # one hold window, or the fan-out forgets verdicts before replicas
    # hear them and their held spans expire as drops
    b = TailBuffer(policy=RetentionPolicy(slow_ms=1e9, budget_per_s=50.0,
                                          burst=100.0, baseline=0.0),
                   hold_s=20.0)
    assert b._retained_log.maxlen >= 50 * 20 + 100
    # ...and a test's effectively-infinite budget stays bounded
    cap = TailBuffer(policy=_keep_all(), hold_s=20.0)
    assert cap._retained_log.maxlen == 65536


def test_finish_remote_retains_flagged_client_rooted_traces():
    """The front handling a CLIENT-rooted trace: hedge/breaker notes live
    on the front's handler thread and never reach the root's verdict
    (the reply status byte carries outcomes, not flags) — finish_remote
    applies the policy to the flags locally so the fleet-side spans of a
    hedged request survive, and the verdict fans out to the replicas."""
    obs.enable()
    tail.enable()
    tail.buffer().policy = RetentionPolicy(slow_ms=1e9, budget_per_s=1e9,
                                           burst=1e9, baseline=0.0)
    ctx = context.new_root()          # tail-flagged, root owned elsewhere
    tail.buffer().hold(ctx.trace_id, _rec("serve.rpc"))
    tail.note(hedged=True)
    out = tail.finish_remote(ctx, 0.001)
    assert out == (True, "hedged")
    assert ctx.trace_id in tail.retained_ids()
    assert metrics.registry.counter("tail.retained.hedged").value == 1
    assert [r[1] for r in obs.trace.tracer.events()] == ["serve.rpc"]
    # no flags → the trace stays PENDING (the root's slow/error verdict
    # may still promote it), and outcome notes alone are NOT re-decided
    # here — they rode the reply status to the root, which is
    # authoritative (double-deciding would spend budget twice)
    ctx2 = context.new_root()
    tail.buffer().hold(ctx2.trace_id, _rec("serve.rpc"))
    tail.note(outcome="deadline")
    assert tail.finish_remote(ctx2, 0.001) is None
    assert tail.buffer().pending_count() == 1
    assert tail.take_notes() == (None, set())   # ...but notes were cleared


def test_tail_and_force_flags_roundtrip_the_header():
    t, s = "a" * 32, "b" * 16
    for kw, bits in (({"sampled": True}, "01"),
                     ({"sampled": False, "tail": True}, "02"),
                     ({"sampled": True, "force": True}, "05")):
        ctx = context.TraceContext(t, s, **kw)
        h = ctx.to_header()
        assert h.endswith(f"-{bits}")
        back = context.from_header(h)
        assert back == ctx
        child = ctx.child()
        assert (child.tail, child.force, child.sampled) == \
            (ctx.tail, ctx.force, ctx.sampled)


def test_new_root_under_tail_mode_pends_instead_of_sampling():
    context.set_sample_rate(0.0)    # head sampling would record NOTHING
    tail.enable()
    ctx = context.new_root()
    assert ctx.tail and not ctx.sampled and ctx.records
    tail.disable()
    assert context.new_root().sampled is False   # head mode again


def test_tail_context_without_local_buffer_records_nothing():
    """A tail-bit context arriving over the wire at a process that never
    enabled tail mode must DROP, not record durably: there is no buffer
    to hold the spans, no verdict will ever promote them, and recording
    would silently bypass this process's own head-sampling rate."""
    obs.enable()
    assert not tail.enabled()
    ctx = context.TraceContext(context.new_trace_id(),
                               context.new_span_id(),
                               sampled=False, tail=True)
    with context.use(ctx):
        with obs.trace.span("serve.execute"):
            pass
    assert [e for e in obs.trace.drain() if e["ph"] == "X"] == []


def test_forced_block_births_force_retain_roots():
    tail.enable()
    with tail.forced():
        ctx = context.new_root()
    assert ctx.force and ctx.sampled and not ctx.tail
    assert context.new_root().force is False     # scope ended


# ---------------------------------------------------------------------------
# 4. root-close verdicts: notes, finish_root, exemplars
# ---------------------------------------------------------------------------

def test_finish_root_merges_thread_notes():
    obs.enable()
    tail.enable()
    tail.buffer().policy = RetentionPolicy(slow_ms=1e9, budget_per_s=1e9,
                                           burst=1e9, baseline=0.0)
    ctx = context.new_root()
    tail.note("deadline")
    tail.note(hedged=True)
    retain, reason = tail.finish_root(ctx, 0.001)
    assert retain and reason == "deadline"   # outcome outranks the flags


def test_finish_root_none_clears_notes_without_a_verdict():
    tail.enable()
    tail.note("error")
    assert tail.finish_root(None, 0.0) is None
    # the notes were consumed: the next request on this thread is clean
    assert tail.take_notes() == (None, set())


def test_note_is_a_noop_with_tail_mode_off():
    """A note written while nothing will ever consume it (tail mode off:
    the server's shed/deadline branches still run, finish_root may never
    fire) must not sit in the thread's TLS and contaminate the first
    request after a later enable()."""
    tail.disable()
    tail.note("shed", breaker=True)
    assert tail.take_notes() == (None, set())
    tail.enable()
    try:
        assert tail.take_notes() == (None, set())
    finally:
        tail.disable()


def test_finish_root_logs_forced_verdicts():
    """A force-retained root records durably span by span — but its
    verdict must STILL be logged (and counted) so the telemetry plane
    distributes it to the other hops' pending buffers."""
    obs.enable()
    tail.enable()
    with tail.forced():
        ctx = context.new_root()
        with context.use(ctx):
            with obs.trace.span("serve.client.rpc"):
                pass
    assert tail.finish_root(ctx, 0.001) == (True, "forced")
    assert ctx.trace_id in tail.retained_ids()
    st = tail.stats()
    assert st["retained"] == 1


def test_retained_trace_stamps_bucket_exemplar():
    obs.enable()
    tail.enable()
    tail.buffer().policy = _keep_all()
    metrics.registry.histogram("serve.latency_seconds").observe(0.04)
    ctx = context.new_root()
    with context.use(ctx):
        with obs.trace.span("serve.client.rpc"):
            pass
    tail.finish_root(ctx, 0.04)
    ex = tail.exemplars_snapshot()
    by_le = ex["serve.latency_seconds"]
    (entry,) = by_le.values()
    assert entry["trace_id"] == ctx.trace_id
    # ... and the exposition renders it as an OpenMetrics exemplar
    text = to_prometheus(metrics.snapshot(), exemplars=ex)
    assert f'# {{trace_id="{ctx.trace_id}"}}' in text
    # telemetry parts carry exemplars + tail stats for the fleet plane
    part = obs.telemetry_part(drain=False)
    assert part["exemplars"] == ex
    assert part["tail"]["retained"] == 1
    assert f'trace_id="{ctx.trace_id}"' in parts_to_prometheus([part])
    # OpenMetrics output carries the required EOF terminator
    assert text.endswith("# EOF\n")
    # strict text format 0.0.4: exemplars are a MID-LINE '#', which a
    # 0.0.4 parser rejects as a whole-scrape error — openmetrics=False
    # must emit none (and no EOF marker either)
    strict = to_prometheus(metrics.snapshot(), exemplars=ex,
                           openmetrics=False)
    assert "trace_id" not in strict and "# EOF" not in strict
    assert all(ln.startswith("#") or "#" not in ln
               for ln in strict.splitlines())


def test_exemplar_on_an_unrendered_bucket_attaches_to_the_next_one():
    """A shed/deadline verdict retains the trace WITHOUT observing its
    latency into the histogram, so the exemplar's exact bucket is often
    empty — and empty buckets are omitted from the snapshot. The
    exposition must re-key such an exemplar onto the first rendered
    bucket that still contains its value (``value <= le`` is all
    OpenMetrics requires), not silently drop it."""
    obs.enable()
    tail.enable()
    h = metrics.registry.histogram("serve.latency_seconds")
    h.observe(0.04)                      # ONLY the 0.05 bucket renders
    # a shed request's exemplar: 10µs lands in the (empty, unrendered)
    # first bucket
    tail._record_exemplar("e" * 32, 1e-05)
    text = to_prometheus(metrics.snapshot(),
                         exemplars=tail.exemplars_snapshot())
    lines = [ln for ln in text.splitlines() if 'trace_id="' + "e" * 32 in ln]
    assert len(lines) == 1, text
    assert "serve_latency_seconds_bucket" in lines[0]
    # ...and a value past every rendered bound rides the +Inf bucket
    tail.reset()
    tail._record_exemplar("f" * 32, 1e9)
    text = to_prometheus(metrics.snapshot(),
                         exemplars=tail.exemplars_snapshot())
    (inf_line,) = [ln for ln in text.splitlines()
                   if 'trace_id="' + "f" * 32 in ln]
    assert 'le="+Inf"' in inf_line


def test_help_lines_from_description_registry():
    metrics.registry.counter("fleet.requests").inc()
    metrics.registry.counter("kvstore.rpc.retries").inc()
    metrics.registry.histogram("kvstore.rpc.push_seq_seconds").observe(0.01)
    metrics.registry.counter("totally.undocumented.thing").inc()
    text = to_prometheus(metrics.snapshot())
    assert ("# HELP mxnet_fleet_requests "
            "requests routed by the fleet router") in text
    assert "# HELP mxnet_kvstore_rpc_retries" in text
    # family-prefix match covers dynamically named RPC histograms
    assert ("# HELP mxnet_kvstore_rpc_push_seq_seconds "
            "PS client-side RPC latency per opcode") in text
    # undescribed metrics render exactly as before — TYPE but no HELP
    assert "# TYPE mxnet_totally_undocumented_thing counter" in text
    assert "# HELP mxnet_totally_undocumented_thing" not in text
    # the runtime registration hook wins over nothing
    metrics.describe("totally.undocumented.thing", "now it is")
    assert ("# HELP mxnet_totally_undocumented_thing now it is"
            in to_prometheus(metrics.snapshot()))


# ---------------------------------------------------------------------------
# 5. end to end over the serve wire (client + server share this process's
#    buffer — the verdict settles every hop's spans at once)
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


def test_serve_retained_request_keeps_every_hop_one_trace_id():
    obs.enable()
    tail.enable()
    tail.buffer().policy = _keep_all()     # everything is "interesting"
    srv, cli = _serve_pair()
    try:
        np.testing.assert_array_equal(cli.infer(X), X)
    finally:
        cli.close()
        srv.stop()
    spans = {e["name"]: e["args"] for e in obs.trace.drain()
             if e["ph"] == "X" and e.get("args")}
    for name in ("serve.client.rpc", "serve.rpc", "serve.queue_wait",
                 "serve.execute", "serve.serialize"):
        assert name in spans, f"missing {name}"
    tids = {s["trace_id"] for s in spans.values() if "trace_id" in s}
    assert len(tids) == 1
    st = tail.stats()
    assert st["retained"] >= 1 and st["pending"] == 0


def test_serve_fast_path_request_drops_every_hop():
    obs.enable()
    tail.enable()
    tail.buffer().policy = RetentionPolicy(slow_ms=1e9, budget_per_s=1e9,
                                           burst=1e9, baseline=0.0)
    srv, cli = _serve_pair()
    try:
        np.testing.assert_array_equal(cli.infer(X), X)
    finally:
        cli.close()
        srv.stop()
    # a healthy fast request leaves NO durable spans on any hop — but the
    # verdict was a real decision, not a recording gap
    serve_spans = [e for e in obs.trace.drain()
                   if e["ph"] == "X" and e["name"].startswith("serve.")]
    assert serve_spans == []
    st = tail.stats()
    assert st["dropped"] >= 1 and st["pending"] == 0


def test_serve_telemetry_resolves_retained_ids_before_drain():
    """The cross-process promotion path, driven in one process: spans held
    pending under a trace id promote when OP_TELEMETRY carries the verdict
    list, and leave with that very collection."""
    obs.enable()
    tail.enable()
    tail.buffer().policy = RetentionPolicy(slow_ms=1e9, budget_per_s=1e9,
                                           burst=1e9, baseline=0.0)
    srv, cli = _serve_pair()
    try:
        np.testing.assert_array_equal(cli.infer(X), X)  # dropped locally...
        # ...but fish the trace id out while it is still settled-dropped:
        # simulate a REPLICA whose root lives elsewhere by re-pending spans
        tail.reset()
        ctx = context.new_root()
        with context.use(ctx):
            with obs.trace.span("serve.execute"):
                pass
        # every assertion is on THIS trace id: the server's handler thread
        # closes its own spans of the infer above after the reply is out,
        # so one of them may land in the fresh buffer beside ours
        assert tail.buffer().pending_count() >= 1
        tel = cli.telemetry(drain=True, retained=[ctx.trace_id])
        (part,) = tel["parts"]
        promoted = [s for s in part["spans"]
                    if s.get("name") == "serve.execute"
                    and s["args"].get("trace_id") == ctx.trace_id]
        assert promoted, "verdict-promoted span missing from the part"
        # it left with that collection: a second verdict finds nothing held
        assert tail.buffer().resolve([ctx.trace_id]) == 0
    finally:
        cli.close()
        srv.stop()


def test_serve_telemetry_strict_prometheus_over_the_wire():
    """``openmetrics=False`` rides the OP_TELEMETRY spec: the reply is
    strict text format 0.0.4 — no exemplar suffixes, no ``# EOF`` — so it
    can feed a node_exporter textfile collector without re-rendering."""
    obs.enable()
    tail.enable()
    tail.buffer().policy = _keep_all()
    srv, cli = _serve_pair()
    try:
        np.testing.assert_array_equal(cli.infer(X), X)  # retained → exemplar
        om = cli.telemetry(drain=False, fmt="prometheus")
        assert om.rstrip().endswith("# EOF")
        assert 'trace_id="' in om      # the exemplar rode the wire
        strict = cli.telemetry(drain=False, fmt="prometheus",
                               openmetrics=False)
        assert "# EOF" not in strict
        assert 'trace_id="' not in strict
        assert "mxnet_serve_latency_seconds_bucket" in strict
    finally:
        cli.close()
        srv.stop()

"""The decode scheduler's turn as live spans, and the bridge that puts every
live span on the profiler's clock (docs/OBSERVABILITY.md "Decode spans").

A turn of ``DecodeScheduler.step`` is ``decode.turn`` ⊃ {``decode.admit``,
``decode.build``, ``decode.execute`` ⊃ ``decode.dispatch`` per launch (the
admitted prefills, then the next step), ``decode.execute`` ⊃
``decode.device_get`` per read (the step launched a turn earlier, then this
turn's prefills), ``decode.distribute``}; the idle scheduler waits under
``decode.idle_wait``. Each is also a ``jax.profiler.TraceAnnotation``, so a
``jax.profiler`` trace taken with telemetry on carries them on its
``/host:CPU`` plane. ``decode.prefill`` and ``decode.step`` are recorded
when their result is read (``complete()``): from a prefill's launch, or
from when a step became what the streams wait for, to the arrival. With
telemetry off nothing is recorded and nothing is constructed.

**One request's path** (second half of this file): the spans of one request
share ``seq``, the scheduler's number of it — ``decode.queue_wait`` (submit
-> admission), ``decode.prefill_wait`` (admission -> its first piece's
launch, where prompts go in pieces), every ``decode.prefill`` call,
``decode.first_token`` (submit -> the first token's arrival, with its three
parts), ``decode.generate``, and on the consumer's thread ``decode.stream``
(what the way back cost its tokens). The benchmark's ``layer_metrics`` files
that read them are driven over spans recorded here.
"""
import glob
import importlib
import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu import nd, obs
from mxnet_tpu.models import gdn_moe
from mxnet_tpu.models.transformer import transformer_lm
from mxnet_tpu.serve import DecodeEngine, DecodeScheduler

pytestmark = [pytest.mark.decode, pytest.mark.obs]

LIVE = {"decode.turn", "decode.admit", "decode.build", "decode.execute",
        "decode.dispatch", "decode.device_get", "decode.distribute",
        "decode.idle_wait"}
REMOVED = ("decode.token_seconds", "decode.execute_seconds",
           "decode.deserialize_seconds", "decode.compile_seconds")


@pytest.fixture(autouse=True)
def _clean():
    yield
    obs.disable()
    obs.reset()


@pytest.fixture(scope="module")
def lm():
    model = transformer_lm(vocab_size=97, units=32, hidden_size=64,
                           num_layers=2, num_heads=4, max_length=64,
                           dropout=0.0)
    model.initialize()
    model(nd.zeros((1, 8)))  # deferred-init shape inference
    return model


@pytest.fixture(scope="module")
def engine(lm):
    eng = DecodeEngine(lm, slots=4, page_size=8, num_pages=16,
                       prompt_buckets=[8, 16])
    eng.warmup()
    return eng


def _serve_one(engine, prompt=(1, 2, 3), max_new_tokens=3):
    """One request through a scheduler of its own; the scheduler is closed
    (its thread joined) before this returns, so every span has closed."""
    sched = DecodeScheduler(engine)
    try:
        tokens = list(sched.generate(list(prompt),
                                     max_new_tokens=max_new_tokens))
    finally:
        sched.close()
    assert len(tokens) == max_new_tokens and sched.stopped_clean
    return tokens


@pytest.fixture
def served(engine):
    """The spans of one served request (3 tokens: a turn that launches the
    prefill and the first step and reads the prefill; one that launches
    the second step and reads the first; one that reads the second), as
    the ring's dicts."""
    obs.enable()
    _serve_one(engine)
    obs.disable()
    return [e for e in obs.trace.drain() if e["ph"] == "X"]


def _end(span):
    return span["ts"] + span["dur"]


def _inside(outer, spans, name):
    """The spans named ``name`` on ``outer``'s thread that start and end
    within it."""
    return [s for s in spans if s["name"] == name and s["tid"] == outer["tid"]
            and outer["ts"] <= s["ts"] and _end(s) <= _end(outer)]


def _admitting_turn(spans):
    (turn,) = [s for s in spans if s["name"] == "decode.turn"
               and s["args"]["joined"] == 1]
    return turn


def _executes(turn, spans):
    """The turn's ``decode.execute`` spans in order, each as (kind, the
    one span it holds: "dispatch" for a launch, "device_get" for a read)."""
    out = []
    for execute in sorted(_inside(turn, spans, "decode.execute"),
                          key=lambda s: s["ts"]):
        held = [name for name in ("dispatch", "device_get")
                if _inside(execute, spans, "decode." + name)]
        assert len(held) == 1, held
        out.append((execute["args"]["kind"], held[0]))
    return out


def test_a_turn_contains_its_phases_on_one_thread(served):
    turn = _admitting_turn(served)
    inside = {name: _inside(turn, served, name)
              for name in LIVE | {"decode.prefill"}}
    for name in ("decode.admit", "decode.prefill", "decode.build"):
        assert len(inside[name]) == 1, name
    # launch and read are two calls: the prefill and the first step are
    # launched before anything is read, and the step stays in flight
    assert _executes(turn, served) == [
        ("prefill", "dispatch"), ("step", "dispatch"),
        ("prefill", "device_get")]
    launch, step, read = sorted(inside["decode.execute"],
                                key=lambda s: s["ts"])
    # the prefill span runs from its launch to its token
    prefill, = inside["decode.prefill"]
    assert prefill["ts"] <= launch["ts"] and _end(read) <= _end(prefill)
    # and in the order the turn runs them
    admit, = inside["decode.admit"]
    build, = inside["decode.build"]
    assert (_end(admit) <= launch["ts"] and _end(launch) <= build["ts"]
            and _end(build) <= step["ts"] and _end(step) <= read["ts"])
    # a prefill's token is handed over outside decode.distribute
    assert not inside["decode.distribute"]
    assert not inside["decode.idle_wait"] and not inside["decode.turn"][1:]


def test_prefill_span_carries_bucket_and_prompt_len(engine):
    obs.enable()
    _serve_one(engine, prompt=range(1, 12), max_new_tokens=2)
    (prefill,) = [e for e in obs.trace.drain()
                  if e["name"] == "decode.prefill"]
    # one span a program call: a model prefilled whole has one call a prompt
    assert prefill["args"] == {"seq": 1, "bucket": 16, "prompt_len": 11,
                               "start": 0, "pieces": 1}


def test_the_next_step_is_launched_before_the_last_one_is_read(served):
    turns = sorted((s for s in served if s["name"] == "decode.turn"),
                   key=lambda s: s["ts"])
    assert [_executes(t, served) for t in turns[1:]] == [
        [("step", "dispatch"), ("step", "device_get")],
        [("step", "device_get")]]
    second = turns[1]
    (build,) = _inside(second, served, "decode.build")
    launch, read = sorted(_inside(second, served, "decode.execute"),
                          key=lambda s: s["ts"])
    (distribute,) = _inside(second, served, "decode.distribute")
    assert (_end(build) <= launch["ts"] and _end(launch) <= read["ts"]
            and _end(read) <= distribute["ts"])
    assert [t["args"] for t in turns] == [
        {"joined": 1, "active": 1, "left": 0},
        {"joined": 0, "active": 1, "left": 0},
        {"joined": 0, "active": 0, "left": 1}]


def test_decode_step_keeps_its_attributes_and_endpoints(served):
    """``decode_step_ms`` and ``serve_occupancy_pct`` read ``decode.step``:
    one per step read, with active/joined/left/ahead, ending when its
    tokens arrive — after the read, before they go out — and beginning
    where the result before it arrived (or at its own launch, if later):
    the steps tile the timeline, whatever the depth of the pipe."""
    steps = sorted((s for s in served if s["name"] == "decode.step"),
                   key=lambda s: s["ts"])
    turns = sorted((s for s in served if s["name"] == "decode.turn"),
                   key=lambda s: s["ts"])
    assert len(steps) == 2 and len(turns) == 3
    launches = []
    for step, launched_in, read_in in zip(steps, turns, turns[1:]):
        assert set(step["args"]) == {"active", "joined", "left", "ahead",
                                     "cache.paged_bytes", "cache.state_bytes"}
        # a model with no per-slot state: rows read, no state touched
        assert step["args"]["cache.paged_bytes"] > 0
        assert step["args"]["cache.state_bytes"] == 0
        # attributed to the step: joined as its launching turn admitted,
        # left as the turn that read it retired
        assert step["args"]["joined"] == launched_in["args"]["joined"]
        assert step["args"]["left"] == read_in["args"]["left"]
        (launch,) = [e for e in _inside(launched_in, served, "decode.execute")
                     if e["args"]["kind"] == "step"
                     and _inside(e, served, "decode.dispatch")]
        (read,) = [e for e in _inside(read_in, served, "decode.execute")
                   if _inside(e, served, "decode.device_get")
                   and e["args"]["kind"] == "step"]
        (distribute,) = _inside(read_in, served, "decode.distribute")
        assert _end(read) <= _end(step) <= distribute["ts"]
        assert launch["ts"] <= step["ts"]
        launches.append(launch)
    first, second = steps
    # the first step was launched behind the prefill: it is what the stream
    # waits for from the prefill's arrival on; the second from the first's
    (prefill,) = [s for s in served if s["name"] == "decode.prefill"]
    assert first["ts"] == pytest.approx(_end(prefill), abs=1e-4)
    assert second["ts"] == pytest.approx(_end(first), abs=1e-6)
    assert _end(launches[1]) <= _end(first)       # launched ahead
    assert [s["args"]["joined"] for s in steps] == [1, 0]
    assert [s["args"]["ahead"] for s in steps] == [0, 1]


def test_live_span_attributes_are_plain_ints(served):
    want = {"decode.turn": {"joined", "active", "left"},
            "decode.admit": {"admitted"}, "decode.build": {"active"},
            "decode.prefill": {"seq", "bucket", "prompt_len", "start",
                               "pieces"},
            "decode.queue_wait": {"seq", "priority"},
            "decode.first_token": {"seq", "prompt_len", "pieces",
                                   "queue_wait_us", "prefill_wait_us",
                                   "prefill_us"},
            "decode.stream": {"seq", "tokens", "handover_us",
                              "handover_max_us", "first_handover_us",
                              "consume_us", "consume_max_us"},
            "decode.step": {"active", "joined", "left", "ahead",
                            "cache.paged_bytes", "cache.state_bytes"},
            "decode.distribute": {"left"}}
    seen = set()
    for span in served:
        if span["name"] in want:
            seen.add(span["name"])
            assert set(span["args"]) == want[span["name"]]
            assert all(type(v) is int for v in span["args"].values())
        elif span["name"] in ("decode.dispatch", "decode.device_get"):
            assert "args" not in span
    assert seen == set(want)
    last = max((s for s in served if s["name"] == "decode.turn"),
               key=lambda s: s["ts"])
    assert last["args"] == {"joined": 0, "active": 0, "left": 1}


def test_idle_scheduler_waits_under_idle_wait(engine):
    obs.enable()
    sched = DecodeScheduler(engine)
    try:
        time.sleep(0.1)
    finally:
        sched.close()    # wakes the wait; the span closes as the loop ends
    spans = [e for e in obs.trace.drain() if e["ph"] == "X"]
    assert [s["name"] for s in spans] == ["decode.idle_wait"]
    assert 0.02 <= spans[0]["dur"] < 1.5


class _Counted(jax.profiler.TraceAnnotation):
    made = []

    def __init__(self, name, **kw):
        _Counted.made.append(name)
        super().__init__(name, **kw)


@pytest.fixture
def counted(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Counted)
    _Counted.made = []
    return _Counted.made


def test_telemetry_off_records_nothing_and_constructs_no_annotation(
        engine, counted):
    assert not obs.enabled()
    _serve_one(engine)
    assert obs.trace.events() == []
    assert counted == []
    assert not any(obs.metrics.snapshot().values())


def test_every_live_span_is_one_annotation_of_its_name(engine, counted):
    obs.enable()
    _serve_one(engine)
    obs.disable()
    spans = [e for e in obs.trace.drain() if e["ph"] == "X"]
    live = sorted(s["name"] for s in spans if s["name"] in LIVE)
    assert sorted(counted) == live and set(live) >= LIVE - {"decode.idle_wait"}
    # the retroactive spans stay in the ring and are not bridged
    assert {"decode.step", "decode.prefill", "decode.queue_wait",
            "decode.first_token", "decode.generate", "decode.stream"
            } <= {s["name"] for s in spans} - set(counted)


def test_profiler_trace_holds_the_turn_on_its_host_plane(engine, tmp_path):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    obs.enable()
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _serve_one(engine)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    (host,) = [p for p in jax.profiler.ProfileData.from_file(path).planes
               if p.name == "/host:CPU"]
    by_name = {}
    for line in host.lines:
        for e in line.events:
            if e.name.startswith("decode."):
                by_name.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns))
    assert set(by_name) >= LIVE - {"decode.idle_wait"}
    assert len(by_name["decode.turn"]) == 3
    # on the profiler's clock too, a device_get lies inside a turn
    for start, end in by_name["decode.device_get"]:
        assert any(s <= start and end <= e for s, e in by_name["decode.turn"])
    # complete() spans are retroactive: the ring has them, the trace not
    assert "decode.step" not in by_name and "decode.prefill" not in by_name


def test_removed_histograms_are_gone_and_the_compile_counters_stay(
        lm, tmp_path):
    """A fresh engine compiles (``decode.compile``), a second one over the
    same program cache deserializes (``decode.cache_hit``); neither feeds a
    histogram any more — the ``decode.execute`` span carries ``compile``,
    ``cache_hit`` and the duration."""
    obs.enable()
    for _ in range(2):
        eng = DecodeEngine(lm, slots=2, page_size=8, num_pages=8,
                           prompt_buckets=[8], progcache_dir=str(tmp_path))
        eng.warmup()
        _serve_one(eng, max_new_tokens=2)
    snap = obs.metrics.snapshot()
    assert snap["counters"]["decode.compile"] == 2
    assert snap["counters"]["decode.cache_hit"] == 2
    assert {"decode.occupancy", "decode.queue_depth"} <= set(snap["gauges"])
    assert not [n for n in snap["histograms"] if n.startswith("decode.")]
    assert not any(name in kind for name in REMOVED
                   for kind in snap.values())
    executes = [e["args"] for e in obs.trace.drain()
                if e["name"] == "decode.execute"]
    # (a launch's span says whether its program was built or loaded; a
    # read's has nothing to say of that)
    launches = [a for a in executes if "compile" in a]
    assert len(launches) * 2 == len(executes)
    assert sum(a["compile"] and not a["cache_hit"] for a in launches) == 2
    assert sum(a["cache_hit"] for a in launches) == 2


# -- one request's path, in spans of one identifier -----------------------------

REQUEST = ("decode.queue_wait", "decode.prefill", "decode.first_token",
           "decode.generate", "decode.stream")
HERE = os.path.dirname(os.path.abspath(__file__))
LAYER_METRICS = os.path.join(os.path.dirname(HERE), "benchmark",
                             "layer_metrics")
NEW_METRICS = ("ttft_inside_p50_ms", "ttft_inside_p50_ms.itl99",
               "ttft_inside_p95_ms.itl99", "prefill_wait_p95_ms.itl99",
               "queue_wait_p95_ms.itl99", "token_handover_ms",
               "token_consume_ms")
# tests/test_gdn_moe.py's tiny model: it can continue a prompt, so the engine
# feeds it pieces of its smallest bucket (16) and the scheduler one a turn
GDN = {
    "vocab_size": 96, "hidden_size": 64, "num_layers": 8, "full_interval": 4,
    "num_heads": 8, "num_kv_heads": 1, "head_dim": 16, "rotary_dim": 4,
    "rope_theta": 10000000, "linear_key_heads": 2, "linear_value_heads": 4,
    "linear_key_dim": 16, "linear_value_dim": 8, "conv_width": 4,
    "expert_width": 32, "router_experts": 8, "experts_first": 2,
    "experts_held": 4, "experts_per_token": 3, "rms_eps": 1e-6,
    "max_length": 64}


@pytest.fixture(scope="module")
def pieces_engine():
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    gdn_moe.init_params(GDN, 3000000019))
    eng = DecodeEngine(gdn_moe.GDNMoEDecodeModel(GDN, params=params),
                       slots=2, page_size=8, num_pages=17,
                       prompt_buckets=[16, 32])
    assert eng.prefill_piece == 16
    eng.warmup()
    return eng


def _named(spans, name, seq=None):
    return sorted((s for s in spans if s["name"] == name
                   and (seq is None or s["args"]["seq"] == seq)),
                  key=lambda s: s["ts"])


def _us(seconds):
    return seconds * 1e6


@pytest.fixture
def two_in_pieces(pieces_engine, monkeypatch):
    """The spans of two prompts admitted in ONE turn by a scheduler that
    feeds prompts in pieces: 30 positions (two pieces) and 20 (two), the
    first admitted first. Both are queued while the scheduler's thread is
    held out of its admission, then ``generate`` drains each."""
    obs.enable()
    sched = DecodeScheduler(pieces_engine, default_timeout=60.0)
    try:
        with sched._cv:     # _admit takes it: nothing is admitted meanwhile
            handles = [sched.submit(list(range(5, 35)), max_new_tokens=3),
                       sched.submit(list(range(40, 60)), max_new_tokens=2)]
        monkeypatch.setattr(sched, "submit", lambda *a, **kw: handles.pop(0))
        for n in (3, 2):
            assert len(list(sched.generate([0], max_new_tokens=n))) == n
    finally:
        sched.close()
    obs.disable()
    assert sched.stopped_clean
    return [e for e in obs.trace.drain() if e["ph"] == "X"]


def test_the_spans_of_one_request_share_its_seq(served):
    for name in REQUEST:
        (span,) = _named(served, name)
        assert span["args"]["seq"] == 1, name
    assert not _named(served, "decode.prefill_wait")    # prefilled whole


def test_each_request_of_a_scheduler_has_a_seq_of_its_own(engine):
    obs.enable()
    sched = DecodeScheduler(engine)
    try:
        for prompt in ((1, 2, 3), (4, 5), (6,)):
            assert len(list(sched.generate(list(prompt),
                                           max_new_tokens=2))) == 2
    finally:
        sched.close()
    obs.disable()
    spans = [e for e in obs.trace.drain() if e["ph"] == "X"]
    for name in REQUEST:
        assert [s["args"]["seq"] for s in _named(spans, name)] == [1, 2, 3]
    assert [s["args"]["prompt_len"]
            for s in _named(spans, "decode.first_token")] == [3, 2, 1]


def test_first_token_is_the_sum_of_its_three_parts(served):
    (first,) = _named(served, "decode.first_token")
    (queued,) = _named(served, "decode.queue_wait")
    (prefill,) = _named(served, "decode.prefill")
    a = first["args"]
    assert a["prompt_len"] == 3 and a["pieces"] == 1
    assert a["prefill_wait_us"] == 0        # prefilled whole: no such wait
    assert _us(first["dur"]) == pytest.approx(
        a["queue_wait_us"] + a["prefill_wait_us"] + a["prefill_us"], abs=2)
    # it begins where the request was submitted and ends where its prefill's
    # token arrived; the queue wait is its first part, the prefill its last
    assert first["ts"] == pytest.approx(queued["ts"], abs=1e-6)
    assert a["queue_wait_us"] == pytest.approx(_us(queued["dur"]), abs=1)
    assert _end(first) == pytest.approx(_end(prefill), abs=1e-6)
    assert a["prefill_us"] == pytest.approx(_us(prefill["dur"]), abs=1)


def test_a_prompt_in_pieces_tiles_submit_to_first_token(two_in_pieces):
    spans = two_in_pieces
    for seq, prompt_len in ((1, 30), (2, 20)):
        (first,) = _named(spans, "decode.first_token", seq)
        (queued,) = _named(spans, "decode.queue_wait", seq)
        (waited,) = _named(spans, "decode.prefill_wait", seq)
        pieces = _named(spans, "decode.prefill", seq)
        a = first["args"]
        assert (a["prompt_len"], a["pieces"], len(pieces)) == (prompt_len, 2, 2)
        assert [p["args"]["start"] for p in pieces] == [0, 16]
        assert _us(first["dur"]) == pytest.approx(
            a["queue_wait_us"] + a["prefill_wait_us"] + a["prefill_us"], abs=2)
        assert a["queue_wait_us"] == pytest.approx(_us(queued["dur"]), abs=1)
        assert a["prefill_wait_us"] == pytest.approx(_us(waited["dur"]), abs=1)
        # queue_wait | prefill_wait | first piece ... last piece, in order
        # and end to start; prefill_us from the first piece's launch to the
        # last one's arrival, the step between them included
        assert first["ts"] == pytest.approx(queued["ts"], abs=1e-6)
        assert _end(queued) == pytest.approx(waited["ts"], abs=1e-6)
        assert _end(waited) == pytest.approx(pieces[0]["ts"], abs=1e-6)
        assert _end(pieces[0]) <= pieces[1]["ts"]
        assert _end(pieces[-1]) == pytest.approx(_end(first), abs=1e-6)
        assert a["prefill_us"] == pytest.approx(
            _us(_end(pieces[-1]) - pieces[0]["ts"]), abs=1)
        for name in ("decode.generate", "decode.stream"):
            assert len(_named(spans, name, seq)) == 1


def test_the_second_prompt_waits_for_the_firsts_pieces(two_in_pieces):
    spans = two_in_pieces
    first, second = (_named(spans, "decode.prefill_wait", seq)[0]
                     for seq in (1, 2))
    assert first["args"] == {"seq": 1, "ahead": 0}
    assert second["args"] == {"seq": 2, "ahead": 1}
    ahead = _named(spans, "decode.prefill", 1)
    # one piece a turn, the oldest prompt's first: the second's wait holds
    # both of the first's pieces, launch to arrival
    assert second["ts"] <= ahead[0]["ts"] and _end(ahead[-1]) <= _end(second)
    assert second["dur"] >= sum(p["dur"] for p in ahead)
    assert second["dur"] >= first["dur"]


def test_the_consumers_own_time_is_consume_and_not_hand_over(
        engine, monkeypatch):
    """A consumer that sleeps 20 ms over every token. The scheduler reads a
    result only once the token before it is consumed (a gate on
    ``engine.read``), so no token waits in the queue through a sleep: the
    sleeps are in ``consume_us``, and the hand-overs lie beside them in the
    span."""
    n, nap = 5, 0.02
    gate = threading.Semaphore(1)
    read = engine.read

    def gated_read(launched):
        assert gate.acquire(timeout=30)
        return read(launched)

    monkeypatch.setattr(engine, "read", gated_read)
    obs.enable()
    sched = DecodeScheduler(engine)
    try:
        for _ in sched.generate([1, 2, 3], max_new_tokens=n):
            time.sleep(nap)
            gate.release()
    finally:
        sched.close()
    obs.disable()
    (stream,) = [e for e in obs.trace.drain() if e["name"] == "decode.stream"]
    a = stream["args"]
    assert a["tokens"] == n
    assert a["consume_us"] >= _us(nap) * n
    assert a["consume_max_us"] >= _us(nap)
    assert a["consume_max_us"] <= a["consume_us"]
    assert 0 <= a["first_handover_us"] <= a["handover_max_us"] <= a["handover_us"]
    # the span runs from the first token seen to the last consumed: the n
    # sleeps and the hand-overs of tokens 2..n are disjoint pieces of it
    assert (a["handover_us"] - a["first_handover_us"] + _us(nap) * n
            <= _us(stream["dur"]) + n + 2)


def test_a_stream_joined_mid_way_counts_the_tokens_it_saw(engine):
    """Telemetry turned on with a stream in flight (as the benchmark's
    traced window does): its ``decode.stream`` counts the tokens seen since,
    and has no first hand-over."""
    sched = DecodeScheduler(engine)
    try:
        stream = sched.generate([1, 2, 3], max_new_tokens=6)
        head = [next(stream), next(stream)]
        obs.enable()
        rest = list(stream)
    finally:
        sched.close()
    obs.disable()
    assert len(head + rest) == 6
    (span,) = [e for e in obs.trace.drain() if e["name"] == "decode.stream"]
    assert span["args"]["tokens"] == 4
    assert "first_handover_us" not in span["args"]


def test_telemetry_off_yields_the_same_tokens_and_records_nothing(engine):
    assert not obs.enabled()
    off = _serve_one(engine, prompt=(7, 8, 9, 10), max_new_tokens=5)
    assert obs.trace.events() == [] and obs.trace.tracer.dropped == 0
    obs.enable()
    on = _serve_one(engine, prompt=(7, 8, 9, 10), max_new_tokens=5)
    assert on == off


def test_a_handle_returns_the_event_and_keeps_its_arrival_apart(engine):
    sched = DecodeScheduler(engine)
    try:
        t0 = time.monotonic()
        h = sched.submit([1, 2, 3], max_new_tokens=2)
        events, arrived = [], []
        while not events or events[-1][0] == "token":
            events.append(h.get(timeout=30))
            arrived.append(h.arrived)
        t1 = time.monotonic()
    finally:
        sched.close()
    assert [e[0] for e in events] == ["token", "token", "end"]
    assert [e[2] for e in events] == [1, 2, 2] and h.seq == 1
    assert t0 <= arrived[0] <= arrived[1] <= t1 and arrived[2] == 0.0


def test_the_ring_counts_what_it_drops():
    tracer = obs.trace.Tracer(capacity=8)
    for i in range(8):
        tracer._record(("i", f"e{i}", float(i), None, 0, 0, None))
    assert tracer.dropped == 0 and len(tracer.events()) == 8
    for i in range(8, 13):
        tracer._record(("i", f"e{i}", float(i), None, 0, 0, None))
    assert tracer.dropped == 5
    assert [e[1] for e in tracer.events()] == [f"e{i}" for i in range(5, 13)]
    assert len(tracer.drain()) == 8     # drained: room again, nothing lost
    tracer._record(("i", "e13", 13.0, None, 0, 0, None))
    assert tracer.dropped == 5
    tracer.reset()
    assert tracer.dropped == 0 and tracer.events() == []


def test_a_telemetry_part_says_what_the_ring_dropped(monkeypatch):
    small = obs.trace.Tracer(capacity=8)
    monkeypatch.setattr(obs.trace, "tracer", small)
    obs.enable()
    for i in range(11):
        obs.trace.complete("x", float(i), 0.5)
    part = obs.telemetry_part()
    assert part["dropped"] == 3 and len(part["spans"]) == 8


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_the_benchmarks_new_metrics_read_the_programs_spans(
        metric, two_in_pieces):
    """Each ``benchmark/layer_metrics`` file this PR adds, through its reader,
    over spans this program recorded: every one returns a number (the span
    and attribute names the benchmark reads are the ones recorded)."""
    with open(os.path.join(LAYER_METRICS, metric + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    value = reader.read({"spans": two_in_pieces}, spec["args"])
    assert isinstance(value, float) and value >= 0.0
    if spec["reader"] != "span_attr_ratio":
        durations = [s["dur"] * 1e3 for s in two_in_pieces
                     if s["name"] == spec["args"]["span"]]
        assert len(durations) == 2 and min(durations) <= value <= max(durations)


def test_a_full_ring_counts_every_drop_of_every_thread():
    """Eight threads record into a full ring of 8 under a short switch
    interval: the count is exact, not a read-modify-write that loses
    updates."""
    tracer, each = obs.trace.Tracer(capacity=8), 2000
    rec = ("i", "e", 0.0, None, 0, 0, None)
    for _ in range(8):
        tracer._record(rec)
    threads = [threading.Thread(
        target=lambda: [tracer._record(rec) for _ in range(each)])
        for _ in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert tracer.dropped == 8 * each and len(tracer.events()) == 8

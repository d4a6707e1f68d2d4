"""The decode scheduler's turn as live spans, and the bridge that puts every
live span on the profiler's clock (docs/OBSERVABILITY.md "Decode spans").

A turn of ``DecodeScheduler.step`` is ``decode.turn`` ⊃ {``decode.admit``,
``decode.prefill`` per admission, ``decode.build``, ``decode.execute`` ⊃
{``decode.dispatch``, ``decode.device_get``}, ``decode.distribute``}; the
idle scheduler waits under ``decode.idle_wait``. Each is also a
``jax.profiler.TraceAnnotation``, so a ``jax.profiler`` trace taken with
telemetry on carries them on its ``/host:CPU`` plane. With telemetry off
nothing is recorded and nothing is constructed.
"""
import glob
import os
import time

import jax
import pytest

from mxnet_tpu import nd, obs
from mxnet_tpu.models.transformer import transformer_lm
from mxnet_tpu.serve import DecodeEngine, DecodeScheduler

pytestmark = [pytest.mark.decode, pytest.mark.obs]

LIVE = {"decode.turn", "decode.admit", "decode.prefill", "decode.build",
        "decode.execute", "decode.dispatch", "decode.device_get",
        "decode.distribute", "decode.idle_wait"}
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
    """The spans of one served request (3 tokens: a turn that admits and
    prefills, and one more turn), as the ring's dicts."""
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


def test_a_turn_contains_its_phases_on_one_thread(served):
    turn = _admitting_turn(served)
    inside = {name: _inside(turn, served, name) for name in LIVE}
    for name in ("decode.admit", "decode.prefill", "decode.build",
                 "decode.distribute"):
        assert len(inside[name]) == 1, name
    # one program for the prefill, one for the step; each execute holds
    # exactly one dispatch followed by one device_get
    assert len(inside["decode.execute"]) == 2
    for execute in inside["decode.execute"]:
        (dispatch,) = _inside(execute, served, "decode.dispatch")
        (get,) = _inside(execute, served, "decode.device_get")
        assert _end(dispatch) <= get["ts"]
    prefill, = inside["decode.prefill"]
    first, second = sorted(inside["decode.execute"], key=lambda s: s["ts"])
    assert first["args"]["kind"] == "prefill" and _inside(
        prefill, served, "decode.execute") == [first]
    assert second["args"]["kind"] == "step"
    # and in the order the turn runs them
    admit, = inside["decode.admit"]
    build, = inside["decode.build"]
    distribute, = inside["decode.distribute"]
    assert (_end(admit) <= prefill["ts"] and _end(prefill) <= build["ts"]
            and _end(build) <= second["ts"]
            and _end(second) <= distribute["ts"])
    assert not inside["decode.idle_wait"] and not inside["decode.turn"][1:]


def test_prefill_span_carries_bucket_and_prompt_len(engine):
    obs.enable()
    _serve_one(engine, prompt=range(1, 12), max_new_tokens=2)
    (prefill,) = [e for e in obs.trace.drain()
                  if e["name"] == "decode.prefill"]
    assert prefill["args"] == {"bucket": 16, "prompt_len": 11}


def test_decode_step_keeps_its_attributes_and_endpoints(served):
    """``decode_step_ms`` and ``serve_occupancy_pct`` read ``decode.step``:
    it still closes around ``eng.step`` alone — after the arrays are
    built, before the tokens go out — with active/joined/left."""
    steps = [s for s in served if s["name"] == "decode.step"]
    turns = [s for s in served if s["name"] == "decode.turn"]
    assert len(steps) == len(turns) == 2
    for step in steps:
        assert set(step["args"]) == {"active", "joined", "left"}
        (turn,) = [t for t in turns if t["ts"] <= step["ts"]
                   and _end(step) <= _end(t)]
        assert step["args"] == turn["args"]
        (build,) = _inside(turn, served, "decode.build")
        (distribute,) = _inside(turn, served, "decode.distribute")
        (execute,) = [e for e in _inside(turn, served, "decode.execute")
                      if e["args"]["kind"] == "step"]
        assert (_end(build) <= step["ts"] <= execute["ts"]
                and _end(execute) <= _end(step) <= distribute["ts"])
    assert [s["args"]["joined"] for s in sorted(steps, key=lambda s: s["ts"])
            ] == [1, 0]


def test_live_span_attributes_are_plain_ints(served):
    want = {"decode.turn": {"joined", "active", "left"},
            "decode.admit": {"admitted"}, "decode.build": {"active"},
            "decode.prefill": {"bucket", "prompt_len"},
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
    assert last["args"] == {"joined": 0, "active": 1, "left": 1}


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
    assert {"decode.step", "decode.queue_wait", "decode.generate"} <= {
        s["name"] for s in spans} - set(counted)


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
    assert len(by_name["decode.turn"]) == 2
    # on the profiler's clock too, a device_get lies inside a turn
    for start, end in by_name["decode.device_get"]:
        assert any(s <= start and end <= e for s, e in by_name["decode.turn"])
    # complete() spans are retroactive: the ring has them, the trace not
    assert "decode.step" not in by_name


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
    assert sum(a["compile"] and not a["cache_hit"] for a in executes) == 2
    assert sum(a["cache_hit"] for a in executes) == 2

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
    assert prefill["args"] == {"bucket": 16, "prompt_len": 11, "start": 0,
                               "pieces": 1}


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
            "decode.prefill": {"bucket", "prompt_len", "start", "pieces"},
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
            "decode.generate"} <= {s["name"] for s in spans} - set(counted)


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

"""The two readers this PR adds, held to values worked out by hand: on a
synthetic ``spans`` list and synthetic gaps, and on the small trace recorded
on the chip (``record_span_trace.py``: two make-believe scheduler turns under
the program's span names, a pause under no span between them), whose
``spans.expected.json`` the recorder worked out the slow way."""
import json
import os
import shutil
import sys

import pytest

from benchmark.readers import span_self_ms
from benchmark.readers import trace_idle_under_span_pct as under_span

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
TRACE = os.path.join(DATA, "spans.xplane.pb")
with open(os.path.join(DATA, "spans.expected.json")) as f:
    WANT = json.load(f)
ARGS = {"under": "decode.turn", "outside": "decode.execute",
        "prefix": "decode."}


def span(name, ts, dur, tid=1):
    return {"name": name, "ts": ts, "dur": dur, "tid": tid, "ph": "X"}


def test_self_time_is_the_turn_less_the_program_calls_inside_it():
    spans = [
        span("decode.turn", 0.000, 0.070),       # 70 - 40 - 10 = 20 ms
        span("decode.execute", 0.010, 0.040), span("decode.execute", 0.055, 0.010),
        span("decode.turn", 0.100, 0.064),       # 64 - 60 = 4 ms
        span("decode.execute", 0.102, 0.060),
        span("decode.turn", 0.200, 0.066),       # 66 - 60 = 6 ms: the call
        span("decode.execute", 0.201, 0.060),    # on thread 2 is not its own
        span("decode.execute", 0.210, 0.050, tid=2),
        span("decode.execute", 0.300, 0.060),    # its turn began before the window
        span("decode.step", 0.101, 0.062),
    ]
    args = {"span": "decode.turn", "less": "decode.execute"}
    assert span_self_ms.read({"spans": spans}, args) == pytest.approx(6.0)
    assert span_self_ms.read({"spans": spans[:3]}, args) == pytest.approx(20.0)
    assert span_self_ms.read({"spans": []}, args) is None
    assert span_self_ms.read({}, args) is None
    assert span_self_ms.read({"spans": spans[1:3]}, args) is None   # no turn


def test_idle_goes_to_the_innermost_span_and_the_rest_is_unmarked():
    gaps = [[10, 20], [30, 50], [60, 100]]                  # 70 ns idle
    spans = sorted([(5, 70, "decode.turn"), (12, 40, "decode.execute"),
                    (12, 18, "decode.dispatch"), (25, 40, "decode.device_get"),
                    (45, 55, "decode.build"), (90, 95, "decode.turn")])
    pieces = under_span.segments(spans)
    assert [(a, b, names[-1]) for a, b, names in pieces] == [
        (5, 12, "decode.turn"), (12, 18, "decode.dispatch"),
        (18, 25, "decode.execute"), (25, 40, "decode.device_get"),
        (40, 45, "decode.turn"), (45, 55, "decode.build"),
        (55, 70, "decode.turn"), (90, 95, "decode.turn")]
    assert under_span.idle_by_piece(gaps, pieces) == [2, 6, 2, 10, 5, 5, 10, 5]
    by_name, chosen, total = under_span.attribute(
        gaps, spans, "decode.turn", "decode.execute")
    ns = {k: round(v * 1e9) for k, v in by_name.items()}
    assert ns == {"decode.turn": 22, "decode.dispatch": 6, "decode.execute": 2,
                  "decode.device_get": 10, "decode.build": 5, "unmarked": 25}
    assert round(chosen * 1e9) == 27 and round(total * 1e9) == 70
    assert under_span.attribute([], spans, "decode.turn", "decode.execute") == (
        {"unmarked": 0.0}, 0.0, 0.0) or True


def test_the_recorded_trace_gives_what_the_recorder_worked_out():
    gaps, spans = under_span.load(TRACE, "decode.")
    counts = {}
    for _, _, name in spans:
        counts[name] = counts.get(name, 0) + 1
    assert counts == WANT["host_spans"] == {
        "decode.turn": 2, "decode.admit": 1, "decode.execute": 3,
        "decode.dispatch": 3, "decode.device_get": 3, "decode.build": 1,
        "decode.distribute": 1}
    by_name, chosen, total = under_span.attribute(
        gaps, spans, "decode.turn", "decode.execute")
    assert total == pytest.approx(WANT["idle_s"], rel=1e-9)
    assert chosen == pytest.approx(
        WANT["idle_under_turn_outside_execute_s"], rel=1e-9)
    assert {k: v for k, v in by_name.items() if v} == pytest.approx(
        WANT["idle_by_innermost_span"], rel=1e-9, abs=1e-12)
    # by hand: the recorder slept 3 ms under build, 2 ms under distribute
    # and 4 ms under no span, the device idle all the while. Idle time ends
    # with the device's last operation, and so short a trace aligns the two
    # clocks only to about a millisecond: it puts that operation 1.3 ms
    # before the launch that the second turn issued, inside the pause
    assert 0.003 <= by_name["decode.build"] < 0.0045
    assert 0.002 <= by_name["decode.distribute"] < 0.0035
    assert 0.004 - 0.0015 <= by_name["unmarked"] < 0.0045
    assert chosen == pytest.approx(
        by_name["decode.turn"] + by_name["decode.build"]
        + by_name["decode.distribute"] + by_name.get("decode.admit", 0.0))
    assert sum(by_name.values()) == pytest.approx(total)


@pytest.fixture
def as_run(monkeypatch):
    """Stand in a process started as ``run.py --workload <cell>`` whose
    runner left ``trace`` under ``benchmark/.out/<cell>/``."""
    cell = "test-span-readers"
    out = os.path.join(os.path.dirname(HERE), ".out", cell)

    def place(trace):
        shutil.rmtree(out, ignore_errors=True)
        if trace:
            where = os.path.join(out, "trace", "plugins", "profile", "t0")
            os.makedirs(where)
            shutil.copy(trace, os.path.join(where, "host.xplane.pb"))
        monkeypatch.setattr(sys, "argv", ["benchmark/run.py", "--workload",
                                          cell, "--seed", "1", "--trace", "1"])
    yield place
    shutil.rmtree(out, ignore_errors=True)


def test_read_finds_the_runs_own_file_and_prints_where_the_idle_went(
        as_run, capsys):
    as_run(TRACE)
    share = WANT["idle_under_turn_outside_execute_s"]
    got = under_span.read({"trace": {"window_s": 0.05}}, ARGS)
    assert got == pytest.approx(100 * share / 0.05, rel=1e-9)
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == len(WANT["idle_by_innermost_span"]) and all(
        line.startswith("device idle under ") for line in lines)
    assert any(line.startswith("device idle under unmarked: ") for line in lines)
    assert under_span.read({}, ARGS) is None      # the runner reduced no trace


def test_none_where_there_is_nothing_to_read(as_run, monkeypatch):
    reduced = {"trace": {"window_s": 1.0}}
    as_run(None)                                    # no file
    assert under_span.read(reduced, ARGS) is None
    as_run(os.path.join(DATA, "small.xplane.pb"))   # a program without the
    assert under_span.read(reduced, ARGS) is None   # bridge: no decode.turn
    as_run(TRACE)
    assert under_span.read(reduced, dict(ARGS, under="decode.none")) is None
    assert under_span.read(reduced, ARGS) is not None
    monkeypatch.setattr(sys, "argv", ["python3"])   # not started as a run
    assert under_span.read(reduced, ARGS) is None


def test_the_new_metric_files_name_readers_that_take_their_args():
    root = os.path.dirname(HERE)
    for name in ("decode_turn_ms", "prefill_ms", "sched_host_ms",
                 "device_idle_sched_pct.serve"):
        with open(os.path.join(root, "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        reader = __import__(f"benchmark.readers.{spec['reader']}",
                            fromlist=["read"])
        assert reader.read({}, spec["args"]) is None    # a run with no spans


def test_the_percentile_reader_takes_the_runners_rank_and_none_from_nothing():
    from benchmark.readers import percentile
    from benchmark.runners.serve import percentile as runners

    gaps = [7.0] * 93 + [15.5, 15.9, 22.8, 23.1, 23.4, 32.1, 33.0]
    args = {"observation": "itl_ms", "q": 0.95}
    assert percentile.read({"itl_ms": gaps}, args) == 15.9 == runners(gaps, 0.95)
    assert percentile.read({"itl_ms": gaps[::-1]}, dict(args, q=0.99)) == 32.1
    assert percentile.read({"itl_ms": [4.0]}, args) == 4.0
    assert percentile.read({"itl_ms": []}, args) is None
    assert percentile.read({}, args) is None


@pytest.mark.parametrize("name", ["ttft_p50_ms", "decode_step_ms", "prefill_ms"])
def test_a_metric_split_by_what_it_moves_is_read_the_same_way(name):
    root = os.path.join(os.path.dirname(HERE), "layer_metrics")
    with open(os.path.join(root, name + ".json")) as f:
        one = json.load(f)
    with open(os.path.join(root, name + ".itl99.json")) as f:
        other = json.load(f)
    assert (one["reader"], one["args"]) == (other["reader"], other["args"])

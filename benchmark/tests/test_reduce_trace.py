"""The reduction on the small trace recorded on the chip at PR 25
(``record_trace.py``): two programs with a marked 20 ms pause between them.
``small.expected.json`` holds what the recorder worked out the slow way.
Neither it nor ``spans.xplane.pb`` holds a ``bench.window`` span: they are
reduced as they always were. ``window.xplane.pb`` (``record_window_trace.py``,
PR 34) holds one, on a device that never idles and whose operations overhang
it on both sides: there everything is taken inside the span."""
import json
import os
import sys
import types

import pytest

from benchmark import reduce_trace
from benchmark import run as run_py
from benchmark.readers import trace_idle_pct, trace_idle_under_span_pct

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = os.path.join(DATA, "small.xplane.pb")
WINDOW_TRACE = os.path.join(DATA, "window.xplane.pb")


def expected(name):
    with open(os.path.join(DATA, f"{name}.expected.json")) as f:
        return json.load(f)


WANT = expected("small")


def test_busy_union_window_and_time_by_name():
    got = reduce_trace.reduce(TRACE, chips=1)
    assert got["busy_s"] == pytest.approx(WANT["busy_s"], rel=1e-9)
    assert got["busy_s"] == pytest.approx(1.224e-05)
    assert got["window_s"] == pytest.approx(WANT["span_s"], rel=1e-9)
    assert got["by_name"] == pytest.approx(WANT["by_name"], rel=1e-9)
    assert got["device_ops"][0] == ["fusion", pytest.approx(7.868e-06)]
    assert reduce_trace.matching(got["by_name"], "^copy") == pytest.approx(
        WANT["by_name"]["copy-start"] + WANT["by_name"]["copy-done"])


def test_idle_share_and_what_the_host_did_in_the_gap():
    got = reduce_trace.reduce(TRACE, chips=1)
    idle = trace_idle_pct.read({"trace": got}, {})
    assert idle == pytest.approx(100 * (1 - WANT["busy_s"] / WANT["span_s"]))
    name, seconds = got["idle_gaps"][0]
    assert name == "bench.test.pause" and 0.019 < seconds < 0.023
    # a window given by the host's clock replaces the device's own span
    assert reduce_trace.reduce(TRACE, 1, window_s=0.05)["window_s"] == 0.05


def test_union_and_short_names():
    assert reduce_trace.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]
    long = ('%transpose_jvp___.36 = (f32[64,1024,64]) custom-call(...), '
            'custom_call_target="tpu_custom_call"')
    assert reduce_trace.short_name(long) == "mosaic:transpose_jvp___"
    assert reduce_trace.short_name("%fusion.2019 = bf16[4] fusion(...)") == "fusion"


def device_plane(*ops):
    events = [types.SimpleNamespace(name=name, start_ns=s, duration_ns=e - s)
              for name, s, e in ops]
    return types.SimpleNamespace(name="/device:TPU:0", lines=[
        types.SimpleNamespace(name=reduce_trace.OPS_LINE, events=events)])


def test_operations_are_cut_to_the_window_and_the_cut_is_counted():
    plane = device_plane(("%a.1 = f", 50, 120), ("%b.2 = f", 110, 130),
                         ("%c.3 = f", 150, 160), ("%d.4 = f", 190, 260),
                         ("%e.5 = f", 10, 40), ("%f.6 = f", 300, 310),
                         ("%g.7 = f", 200, 205))
    ops, merged, before, after = reduce_trace.busy_in(plane, (100, 200))
    assert ops == [("%a.1 = f", 100, 120), ("%b.2 = f", 110, 130),
                   ("%c.3 = f", 150, 160), ("%d.4 = f", 190, 200)]
    assert merged == [[100, 130], [150, 160], [190, 200]]
    assert (before, after) == (30 + 50, 60 + 10)
    assert reduce_trace.idle_in(merged, (100, 200)) == [[130, 150], [160, 190]]
    # the window's own ends are idle too where nothing runs at them
    assert reduce_trace.idle_in([[120, 130]], (100, 200)) == [
        [100, 120], [130, 200]]
    # no window: as the operations lie, and the gaps between them
    ops, merged, before, after = reduce_trace.busy_in(plane)
    assert len(ops) == 7 and (before, after) == (0, 0)
    assert merged == [[10, 40], [50, 130], [150, 160], [190, 260], [300, 310]]
    assert reduce_trace.idle_in(merged) == [[40, 50], [130, 150], [160, 190],
                                            [260, 300]]


def test_a_device_that_never_idles_stays_inside_the_marked_window():
    want = expected("window")
    # the fault is in the file: the plain union is longer than the window
    assert want["busy_unclipped_s"] > want["window_s"]
    assert want["clipped_before_s"] > 0 and want["clipped_after_s"] > 0
    got = reduce_trace.reduce(WINDOW_TRACE, 1, want["host_clock_s"])
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert got["host_clock_s"] == want["host_clock_s"]
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["by_name"] == pytest.approx(want["by_name"], rel=1e-9)
    assert got["clipped_s"] == pytest.approx(
        [want["clipped_before_s"], want["clipped_after_s"]], rel=1e-9)
    assert trace_idle_pct.read({"trace": got}, {}) >= 0
    # time by name is short by exactly what was cut away
    import jax
    plane = next(p for p in jax.profiler.ProfileData.from_file(
        WINDOW_TRACE).planes if p.name == "/device:TPU:0")
    whole = {}
    for name, s, e in reduce_trace.busy_in(plane)[0]:
        key = reduce_trace.short_name(name)
        whole[key] = whole.get(key, 0.0) + (e - s) / 1e9
    assert set(whole) == set(want["cut_by_name"])
    for key, seconds in whole.items():
        assert seconds - got["by_name"].get(key, 0.0) == pytest.approx(
            want["cut_by_name"][key], rel=1e-9, abs=1e-12)
    # the reader that shares the intervals sees the same idle time
    gaps, _ = trace_idle_under_span_pct.load(WINDOW_TRACE, "bench.")
    assert sum(e - s for s, e in gaps) / 1e9 == pytest.approx(
        got["window_s"] - got["busy_s"], abs=1e-9)
    busy, window, breakdown = reduce_trace.last_line(got)
    assert (busy, window) == (got["busy_s"], got["window_s"])
    assert breakdown["device_ops"] == got["device_ops"][:10]


@pytest.mark.parametrize("name,busy_s", [
    ("small", WANT["busy_s"]),
    ("spans", expected("spans")["span_s"] - expected("spans")["idle_s"])])
def test_a_trace_without_the_span_is_reduced_as_it_always_was(name, busy_s):
    path = os.path.join(DATA, f"{name}.xplane.pb")
    got = reduce_trace.reduce(path, 1)
    assert got["clipped_s"] is None and got["host_clock_s"] is None
    assert got["busy_s"] == pytest.approx(busy_s, rel=1e-9)
    assert got["window_s"] == pytest.approx(expected(name)["span_s"], rel=1e-9)
    assert reduce_trace.reduce(path, 1, 0.05)["window_s"] == 0.05


SOUND = {"busy_s": 2.9, "window_s": 3.0, "clipped_s": [0.002, 0.001],
         "host_clock_s": 3.0001, "device_ops": [["fusion", 1.0]] * 12,
         "idle_gaps": [["unmarked", 0.1]]}


@pytest.mark.parametrize("trace,said", [
    (None, ["no trace"]),
    (dict(SOUND, busy_s=3.0006, window_s=3.0002), ["3.0006", "3.0002"]),
    (dict(SOUND, busy_s=0.0), ["busy_s 0.0", "3.0"]),
    (dict(SOUND, busy_s=float("nan")), ["nan"]),
    (SOUND, [])])
def test_a_traced_device_run_prints_the_contracts_pair_or_no_line(
        trace, said, monkeypatch, capsys):
    """``run.main`` from the runner's result on, the look for a chip and the
    runner itself replaced: the last line either holds the contract or is
    not printed, and the exit code says so."""
    device = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                                   memory_stats=lambda: {"peak_bytes_in_use": 7})
    run = types.SimpleNamespace(
        cell="a-cell", trace=True, rehearse=False, workload={"runner": "fake"},
        manifest={"per_layer": [], "end_to_end": []}, devices=[device],
        setup_s=1.0, reference_s=0.0, program_peak=None, log=print)
    result = {"correct": True, "attempted": 3, "failed": 0, "metrics": {},
              "programs_in_window": 0, "observations": {"trace": trace}}
    monkeypatch.setattr(run_py, "start", lambda args: run)
    monkeypatch.setitem(sys.modules, "benchmark.runners.fake",
                        types.SimpleNamespace(run=lambda run: result))
    argv = ["--workload", "a-cell", "--seed", "1", "--seconds", "1",
            "--trace", "1"]
    if not said:
        assert run_py.main(argv) == 0
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert line["device"]["busy_s"] == 2.9
        assert line["device"]["window_s"] == 3.0
        assert len(line["breakdown"]["device_ops"]) == 10
        return
    with pytest.raises(SystemExit) as stopped:
        run_py.main(argv)
    for number in said:     # a message, so a non-zero exit code
        assert number in str(stopped.value.code)
    assert "{" not in capsys.readouterr().out   # and no result line

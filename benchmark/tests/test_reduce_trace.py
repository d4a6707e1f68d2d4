"""The reduction on the small trace recorded on the chip in this PR
(``record_trace.py``): two programs with a marked 20 ms pause between them.
``small.expected.json`` holds what the recorder worked out the slow way."""
import json
import os

import pytest

from benchmark import reduce_trace
from benchmark.readers import trace_idle_pct

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = os.path.join(DATA, "small.xplane.pb")
with open(os.path.join(DATA, "small.expected.json")) as f:
    WANT = json.load(f)


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

"""``readers/span_quantile_ms.py`` held to values worked out by hand, and to
``readers/percentile.py``, whose rank it takes."""
import pytest

from benchmark.readers import percentile, span_median_ms, span_quantile_ms


def spans(name, durations_ms):
    return [{"name": name, "ts": float(i), "dur": d / 1e3, "ph": "X"}
            for i, d in enumerate(durations_ms)]


def test_the_quantile_is_a_duration_that_was_recorded():
    recorded = spans("decode.first_token", [40, 10, 30, 20]) + spans(
        "decode.queue_wait", [1000])
    read = lambda q: span_quantile_ms.read(  # noqa: E731
        {"spans": recorded}, {"span": "decode.first_token", "q": q})
    # by rank, no interpolation: the smallest value that a share q does not
    # exceed
    assert read(0.25) == pytest.approx(10.0)
    assert read(0.5) == pytest.approx(20.0)
    assert read(0.51) == pytest.approx(30.0)
    assert read(0.95) == pytest.approx(40.0)
    assert read(1.0) == pytest.approx(40.0)


@pytest.mark.parametrize("q", [0.5, 0.9, 0.95, 0.99])
def test_it_takes_the_rank_that_percentile_takes(q):
    durations = [((7 * i) % 29) + 0.5 for i in range(29)]
    got = span_quantile_ms.read({"spans": spans("s", durations)},
                                {"span": "s", "q": q})
    want = percentile.read({"d": durations}, {"observation": "d", "q": q})
    assert got == pytest.approx(want)


def test_of_an_odd_count_the_half_is_the_median():
    recorded = spans("s", [5, 1, 4, 2, 3])
    assert span_quantile_ms.read({"spans": recorded}, {"span": "s", "q": 0.5}
                                 ) == pytest.approx(
        span_median_ms.read({"spans": recorded}, {"span": "s"}))


def test_a_program_without_the_span_gives_none():
    args = {"span": "decode.prefill_wait", "q": 0.95}
    assert span_quantile_ms.read({}, args) is None
    assert span_quantile_ms.read({"spans": []}, args) is None
    assert span_quantile_ms.read({"spans": spans("decode.prefill", [3])},
                                 args) is None
    assert span_quantile_ms.read({"spans": spans("decode.prefill_wait", [3])},
                                 args) == pytest.approx(3.0)

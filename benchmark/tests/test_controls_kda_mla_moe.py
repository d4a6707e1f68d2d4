"""The ``kda_mla_moe`` cell's ``correct`` comes out false when it should (kept
here at rehearsal size; the readings at the cell's own size are in
PERF.md): through the runner's own ``control`` and ``within``, the fp8
control reads above a limit where the served tokens read below every one.
At this size (hidden 64, float32, some 50 served tokens) the served tokens
read 0 to 0.0004 on four seeds and the control 0.001 to 0.011: the rehearsal
limits (0.002) lie between on the seed held here, not on every seed."""
import argparse
import importlib

from benchmark import run as harness

CELL = "ling3-flash-serve-closed-128"


def test_the_fp8_control_fails_a_limit_and_the_served_tokens_hold_them():
    run = harness.start(argparse.Namespace(
        workload=CELL, seed=3000000019, seconds=1.0, trace=0, rehearse=True))
    runner = importlib.import_module(
        f"benchmark.runners.{run.workload['runner']}")
    numbers = runner.control(run)
    limits = run.workload["limits"]
    assert set(limits) == {"logit_gap_p99", "p50_of_flips"}
    assert runner.within(numbers["sound"], limits)
    assert not runner.within(numbers["control_fp8"], limits)
    assert numbers["control_fp8"]["flips"] > numbers["sound"]["flips"]

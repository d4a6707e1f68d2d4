"""run.py end to end on the CPU at rehearsal size: the contract's last line,
and no device metric on it."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


def run_cell(cell, *extra):
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "3000000019", "--seconds", "1", *extra], cwd=ROOT, text=True,
        capture_output=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("cell", CELLS)
def test_last_line_has_the_contracts_keys_and_no_device_metric(cell, trace):
    done = run_cell(cell, "--trace", trace, "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert line["metrics"] == {}
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"


def test_off_the_chip_it_fails_and_prints_no_result():
    done = run_cell(CELLS[0], "--trace", "0")
    assert done.returncode != 0
    assert "{" not in done.stdout

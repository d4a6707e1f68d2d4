"""Every cell of ``BENCHMARK.json`` through ``benchmark/run.py --rehearse``
on the CPU, traced and untraced: the path the driver measures on the chip,
driven at rehearsal size, so that a change which breaks what a runner
imports from the package fails here and not after the chip time is spent.
(The twin of ``benchmark/tests/test_rehearsal.py``, which is run by hand;
a cell added to the manifest is picked up from it.)"""
import json
import os
import subprocess
import sys

import pytest

from mxnet_tpu.platform import virtual_cpu_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


# The seed decides the token ids. With this one, every one of the sarvam
# cell's 32 rehearsal requests stays 30 x under the rehearsal ``logit_gap``
# limit (0.000224 against 0.007, all 549 requests of a traced run checked).
# With 3000000019, the seed of ``benchmark/tests``, one request sits at
# 0.00798: ``correct`` then depends on whether the run's timing puts that
# request among the four it checks, and fails about one run in ten.
SEED = "23"


def run_cell(cell, *extra):
    # one CPU device, as the chip run has one chip: not the suite's eight
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         SEED, "--seconds", "1", *extra], cwd=REPO, text=True,
        capture_output=True, timeout=120, env=virtual_cpu_env(1))


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_to_the_contracts_last_line(cell, trace):
    done = run_cell(cell, "--trace", trace, "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    log = [row for row in done.stdout.strip().splitlines()
           if "cpu_aot_loader" not in row]
    line, said = json.loads(log[-1]), "\n".join(log[-12:])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}, said
    assert line["correct"] is True, said
    assert line["failed"] == 0 < line["attempted"], said
    assert line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"


def test_off_the_chip_it_fails_and_prints_no_result():
    done = run_cell(CELLS[0], "--trace", "0")
    assert done.returncode != 0
    assert "{" not in done.stdout

"""``correct`` has to come out false when it should: with the timed path
broken underneath a whole rehearsal run, and for the lower-precision
controls (kept here at rehearsal size; their readings at the cells' own
sizes are in PERF.md). Everything runs in this process on the CPU."""
import argparse
import importlib
import json

import numpy as np
import pytest

from benchmark import run as harness

TRAIN_CELLS = ["gpt2m-train-s1024", "bertl-train-s128"]
SERVE_CELL = "gpt2m-serve-closed"


def last_line(capsys, cell):
    harness.main(["--workload", cell, "--seed", "2147483659", "--seconds",
                  "1", "--trace", "0", "--rehearse"])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        cell, capsys, monkeypatch):
    from mxnet_tpu.parallel import ShardedTrainer

    real = ShardedTrainer.step

    def frozen(self, *batch):
        if not self._captured:
            return real(self, *batch)
        import jax
        import jax.numpy as jnp

        kept = jax.tree_util.tree_map(      # the step donates its state
            jnp.copy, (self.param_vals, self.opt_state))
        loss = real(self, *batch)
        self.param_vals, self.opt_state = kept
        return loss

    assert last_line(capsys, cell)["correct"] is True
    monkeypatch.setattr(ShardedTrainer, "step", frozen)
    assert last_line(capsys, cell)["correct"] is False


@pytest.mark.parametrize("steps, correct", [(32, True), (10 ** 6, False)])
def test_a_loss_that_has_not_fallen_is_judged_only_in_a_long_window(
        steps, correct, capsys, monkeypatch):
    """Under Adam the loss rises before it falls, so a traced run's few tens
    of steps say nothing; ``falling_after_steps`` steps or more do."""
    from benchmark.runners import train

    monkeypatch.setattr(train, "drive", lambda *a, **kw: (steps, [1e3], 1.0))
    assert last_line(capsys, TRAIN_CELLS[0])["correct"] is correct


def test_a_token_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch):
    from mxnet_tpu.serve import DecodeEngine

    real = DecodeEngine.read    # where a call's sampled tokens reach the host

    def altered(self, launched):
        tokens, counters = real(self, launched)
        return (tokens + 1) % self.cfg["vocab"], counters

    assert last_line(capsys, SERVE_CELL)["correct"] is True
    monkeypatch.setattr(DecodeEngine, "read", altered)
    assert last_line(capsys, SERVE_CELL)["correct"] is False


def control(cell):
    run = harness.start(argparse.Namespace(
        workload=cell, seed=2147483693, seconds=3.0, trace=0, rehearse=True))
    runner = importlib.import_module(
        f"benchmark.runners.{run.workload['runner']}")
    return runner.control(run), run.workload["limits"]


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_the_programs_own_lower_precision_fails(cell):
    numbers, limits = control(cell)
    low = numbers["control_program_float8_e4m3fn"]
    assert not all(np.isfinite(low[k]) and low[k] <= limits[k] for k in limits)


def test_served_tokens_of_a_bf16_forward_fail():
    numbers, limits = control(SERVE_CELL)
    assert numbers["sound"]["logit_gap"] <= limits["logit_gap"]
    assert numbers["control_bf16"]["logit_gap"] > limits["logit_gap"]
    assert numbers["control_bf16"]["flips"] > 0

"""The ``mla_moe`` cell's ``correct`` comes out false when it should (kept
here at rehearsal size; the readings at the cell's own size are in
PERF.md): the fp8 control reads above the limit where the served tokens
read below it, and the configuration's two descriptions agree."""
import argparse
import importlib
import json
import os

from benchmark import run as harness

CELL = "sarvam105b-serve-closed"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_fp8_control_fails_the_limit_and_the_served_tokens_hold_it():
    run = harness.start(argparse.Namespace(
        workload=CELL, seed=2147483659, seconds=1.0, trace=0, rehearse=True))
    runner = importlib.import_module(
        f"benchmark.runners.{run.workload['runner']}")
    numbers = runner.control(run)
    limits = run.workload["limits"]
    assert set(limits) == {"logit_gap", "logit_gap_p99"}
    assert runner.within(numbers["sound"], limits)
    assert not runner.within(numbers["control_fp8"], limits)
    assert numbers["control_fp8"]["flips"] > 0 == numbers["sound"]["flips"]


def test_the_model_block_is_the_published_config_cut_as_reduced_says():
    from mxnet_tpu.models.mla_moe import config_from_hf

    with open(os.path.join(HERE, "configs", "sarvam-105b.json")) as f:
        config = json.load(f)
    published, model = config["published"], dict(config["model"])
    assert model.pop("kind") == "mla_moe_lm"
    assert config_from_hf(config, router_experts=published["num_experts"]) == model
    changed = sorted(k for k, v in published.items() if config[k] != v)
    assert changed == sorted(config["reduced"])
    # no width is cut, and the floors of a share hold
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]


def test_every_seed_offers_the_same_work_in_the_same_order():
    import numpy as np

    from benchmark import traffic
    from benchmark.runners.serve_mla_moe import one_order

    with open(os.path.join(HERE, "workloads", f"{CELL}.json")) as f:
        block = json.load(f)["traffic"]
    model = {"vocab_size": 65536, "max_length": 8192}
    raw = [traffic.serve_requests(block, model, seed) for seed in (1, 3000000019)]
    a, b = (one_order(r) for r in raw)
    lengths = [[(len(r["prompt"]), r["max_new_tokens"]) for r in reqs]
               for reqs in (a, b)]
    assert lengths[0] == lengths[1]                      # one order
    assert not np.array_equal(a[0]["prompt"], b[0]["prompt"])   # other ids
    for mine, theirs in zip((a, b), raw):                # the same multiset
        assert sorted(len(r["prompt"]) for r in mine) == sorted(
            len(r["prompt"]) for r in theirs)
        assert sorted(r["max_new_tokens"] for r in mine) == sorted(
            r["max_new_tokens"] for r in theirs)
        np.testing.assert_array_equal(
            np.concatenate([r["prompt"] for r in mine]),
            np.concatenate([r["prompt"] for r in theirs]))
    plen = np.array([p for p, _ in lengths[0]])
    olen = np.array([o for _, o in lengths[0]])
    assert (plen + olen).max() <= 8192 and plen.max() == 7168
    assert abs(np.corrcoef(plen, olen)[0, 1]) < 0.1
    # any 40 consecutive requests span short and long prompts alike
    means = [np.roll(plen, -k)[:40].mean() for k in range(128)]
    assert max(means) / min(means) < 1.25

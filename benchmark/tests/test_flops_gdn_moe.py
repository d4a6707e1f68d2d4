"""``flops_gdn_moe.py`` by hand at one small shape, the reader that turns a
share of span attributes into a percentage, and that ``flops_mla_moe``'s
expert work reads from this kind's ``model`` block."""
import json
import os

from benchmark import flops_gdn_moe, flops_mla_moe
from benchmark.readers import kernel_roofline_from, span_attr_share_pct

# 4 layers, every second one full attention: 2 delta layers, 2 paged layers
MODEL = {"num_layers": 4, "full_interval": 2, "num_heads": 8, "num_kv_heads": 2,
         "head_dim": 16, "linear_value_heads": 4, "linear_key_dim": 16,
         "linear_value_dim": 8, "hidden_size": 64, "expert_width": 32}


def test_gdn_decode_by_hand():
    # 5 decode-step tokens: 5 slots' states of 2 delta layers, 10 kernel rows
    work = flops_gdn_moe.gdn_decode(MODEL, {"decode_tokens": 5})
    assert set(work) == {"decode"}
    flops, nbytes = work["decode"]
    # 7 operations an element of 4 heads x 16 x 8
    assert flops == 7 * 4 * 16 * 8 * 10 == 35_840
    # a head: state in and out 2 x 128, q and k 2 x 16, v decay beta o 4 x 8
    assert nbytes == 4 * 4 * (256 + 32 + 32) * 10 == 51_200


def test_gqa_decode_by_hand():
    # 2 tokens with 10 and 30 cached positions, 2 paged layers
    obs = {"decode_live_token_steps": 40, "decode_tokens": 2}
    flops, nbytes = flops_gdn_moe.gqa_decode(MODEL, obs)["decode"]
    # scores and values: 2 x 2 x 8 heads x 16 a cached row
    assert flops == 4 * 8 * 16 * 40 * 2 == 40_960
    # a row: k and v of 2 heads of 16 in bfloat16; per token q 2 B, o 4 B
    assert nbytes == 2 * (2 * 2 * 2 * 16 * 40 + 2 * 6 * 8 * 16) == 13_312


def test_gqa_prefill_by_hand():
    work = flops_gdn_moe.gqa_prefill(MODEL, {"prefill_buckets": [16, 32]})
    # the causal half of 4.S.S.16 a head, 8 heads, 2 layers
    assert work["prefill"][0] == 2 * 8 * (16 * 16 + 32 * 32) * 2 * 16 == 655_360
    # q and o of 8 heads, k and v of 2, 48 positions, bfloat16, 2 layers
    assert work["prefill"][1] == 2 * 48 * 2 * 16 * (16 + 4) == 61_440


def test_the_expert_work_reads_this_kinds_model_block():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", "qwen3-next-80b-a3b.json")) as f:
        model = json.load(f)["model"]
    work = flops_mla_moe.moe_experts(model, {"moe_decode": [(80, 60)],
                                             "moe_prefill": []})
    one = 2048 * 512
    assert work == {"decode": (80 * 3 * 2.0 * one,
                               60 * 3 * 2.0 * one + 80 * (8.0 * 2048
                                                          + 10.0 * 512))}
    assert flops_gdn_moe.delta_layers(model) == 6


def test_roofline_reader_takes_the_work_from_this_module():
    obs = {"trace": {"by_name": {"mosaic:gdn_decode": 1e-6, "fusion": 1.0}},
           "model": MODEL, "device_kind": "TPU v5 lite", "decode_tokens": 5}
    args = {"pattern": "^mosaic:gdn_decode", "module": "flops_gdn_moe",
            "work": "gdn_decode"}
    # memory-bound: 51,200 B at 819 GB/s against 1 us measured
    got = kernel_roofline_from.read(obs, args)
    assert abs(got - 100 * 51_200 / 819e9 / 1e-6) < 1e-6
    # the parent's program has no such kernel: nothing to read, no error
    obs["trace"]["by_name"].pop("mosaic:gdn_decode")
    assert kernel_roofline_from.read(obs, args) is None


def test_share_of_span_attributes():
    args = {"spans": ["decode.step"], "part": "cache.state_bytes",
            "whole": ["cache.state_bytes", "cache.paged_bytes"]}
    spans = [{"name": "decode.step", "args": {"cache.state_bytes": 30,
                                              "cache.paged_bytes": 70}},
             {"name": "decode.step", "args": {"cache.state_bytes": 30,
                                              "cache.paged_bytes": 170}},
             {"name": "decode.prefill", "args": {"cache.state_bytes": 999,
                                                 "cache.paged_bytes": 1}},
             {"name": "decode.step", "args": {"active": 3}}]
    assert span_attr_share_pct.read({"spans": spans}, args) == 20.0
    # a program that does not count them (the parent): None, not an error
    assert span_attr_share_pct.read({"spans": spans[3:]}, args) is None
    assert span_attr_share_pct.read({}, args) is None

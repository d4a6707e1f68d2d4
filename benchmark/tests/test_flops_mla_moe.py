"""``flops_mla_moe.py`` by hand at one small shape, and the reader that
turns its pieces into a roofline share."""
import pytest

from benchmark import flops_mla_moe
from benchmark.readers import kernel_roofline_from, span_attr_ratio

MODEL = {"num_heads": 4, "kv_rank": 32, "qk_rope": 8, "qk_nope": 16,
         "v_head": 16, "num_layers": 3, "hidden_size": 64, "expert_width": 32}


def test_mla_decode_by_hand():
    # 2 tokens with 10 and 30 cached positions: 40 rows of 40 values a layer
    obs = {"decode_live_token_steps": 40, "decode_tokens": 2}
    work = flops_mla_moe.mla_decode(MODEL, obs)
    assert set(work) == {"decode"}
    flops, nbytes = work["decode"]
    # scores 2.4.40 and values 2.4.32 a cached row, 40 rows, 3 layers
    assert flops == (2 * 4 * 40 + 2 * 4 * 32) * 40 * 3 == 69_120
    # rows read once (40 x 40 x 2 B), per token q 4x40x2 B in, 4x32x4 B out
    assert nbytes == 3 * (40 * 40 * 2 + 2 * (4 * 40 * 2 + 4 * 32 * 4)) == 14_592


def test_moe_experts_by_hand_and_pieces_apart():
    obs = {"moe_decode": [(6, 5), (4, 4)], "moe_prefill": [(100, 8)]}
    work = flops_mla_moe.moe_experts(MODEL, obs)
    one_matrix = 64 * 32
    # 10 held pairs, 9 touched experts: 3 matmuls a pair; 3 matrices an expert
    assert work["decode"] == (10 * 3 * 2 * one_matrix,
                              9 * 3 * 2 * one_matrix + 10 * (8 * 64 + 10 * 32))
    assert work["decode"] == (122_880, 118_912)
    assert work["prefill"][0] == 100 * 3 * 2 * one_matrix == 1_228_800
    assert "prefill" not in flops_mla_moe.moe_experts(
        MODEL, {"moe_decode": [(1, 1)], "moe_prefill": []})


def test_flash_prefill_by_hand():
    work = flops_mla_moe.flash_prefill(MODEL, {"prefill_buckets": [16, 32]})
    # causal half of 2.S.S.(24 + 16) a head, 4 heads, 3 layers
    assert work["prefill"][0] == 3 * 4 * (16 * 16 + 32 * 32) * 40 == 614_400
    assert work["prefill"][1] == 3 * 4 * 48 * 2 * (2 * 24 + 2 * 16) == 92_160


def _obs(by_name, **more):
    return dict({"trace": {"by_name": by_name}, "model": MODEL,
                 "device_kind": "TPU v5 lite"}, **more)


def test_roofline_reader_bounds_each_piece_by_itself():
    args = {"pattern": "^mosaic:ragged-dot", "module": "flops_mla_moe",
            "work": "moe_experts"}
    obs = _obs({"mosaic:ragged-dot-none": 0.5, "mosaic:mla_decode": 5.0,
                "mosaic:ragged-dot-metadata": 0.5},
               moe_decode=[(64, 28)], moe_prefill=[(20000, 32)])
    obs["model"] = dict(MODEL, hidden_size=4096, expert_width=2048)
    matrix = 4096 * 2048
    # decode: 28 experts' weights (3 x 2 B x matrix each) bind it; prefill:
    # 20000 pairs x 3 matmuls (625 a held expert) bind it by compute
    decode = (28 * 6 * matrix + 64 * (8 * 4096 + 10 * 2048)) / 819e9
    prefill = 20000 * 6 * matrix / 197e12
    assert decode > 64 * 6 * matrix / 197e12
    assert prefill > (32 * 6 * matrix + 20000 * 53_248) / 819e9
    # 1 s of matching events: both ragged-dot kernels, not mla_decode
    assert kernel_roofline_from.read(obs, args) == pytest.approx(
        100 * (decode + prefill) / 1.0)


def test_roofline_reader_returns_none_where_there_is_nothing_to_read():
    args = {"pattern": "^mosaic:mla_decode", "module": "flops_mla_moe",
            "work": "mla_decode"}
    assert kernel_roofline_from.read({}, args) is None            # no trace
    assert kernel_roofline_from.read(                             # no kernel
        _obs({"fusion": 1.0}, decode_live_token_steps=4, decode_tokens=1),
        args) is None
    assert kernel_roofline_from.read(                    # nothing observed
        _obs({"mosaic:mla_decode": 1.0}), args) is None


SPANS = [{"name": "decode.step", "args": {"moe.held": 10, "moe.assignments": 40,
                                          "moe.load_max": 3}},
         {"name": "decode.step", "args": {"moe.held": 30, "moe.assignments": 80,
                                          "moe.load_max": 6}},
         {"name": "decode.prefill", "args": {"moe.held": 20,
                                             "moe.assignments": 120}},
         {"name": "decode.step", "args": {"active": 2}},      # counts nothing
         {"name": "decode.turn"}]


def test_span_attr_ratio_sum_and_median():
    share = {"spans": ["decode.step", "decode.prefill"], "num": "moe.held",
             "den": "moe.assignments", "reduce": "sum", "scale": 100.0}
    assert span_attr_ratio.read({"spans": SPANS}, share) == 100 * 60 / 240
    load = {"spans": ["decode.step"], "num": "moe.load_max",
            "den": "moe.held", "reduce": "median", "scale": "moe_groups"}
    assert span_attr_ratio.read({"spans": SPANS, "moe_groups": 20},
                                load) == pytest.approx(20 * (0.3 + 0.2) / 2)
    # the parent's spans carry no such attribute: nothing, and no error
    assert span_attr_ratio.read({"spans": SPANS[3:]}, share) is None
    assert span_attr_ratio.read({}, share) is None

"""``flops_ssm_moe.py`` by hand at one small shape, and through the roofline
reader."""
from benchmark import flops_ssm_moe
from benchmark.readers import kernel_roofline_from

# 3 Mamba-2, 2 expert and 1 attention layer
MODEL = {"pattern": "MEM*EM", "num_heads": 8, "num_kv_heads": 2,
         "head_dim": 16, "ssm_heads": 4, "ssm_head_dim": 8, "ssm_groups": 2,
         "ssm_state": 16, "hidden_size": 64, "expert_width": 24}


def test_ssm_decode_by_hand():
    # 5 decode-step tokens: 5 slots' states of 3 Mamba-2 layers, 15 calls
    flops, nbytes = flops_ssm_moe.ssm_decode(MODEL, {"decode_tokens": 5})["decode"]
    # 5 operations an element of 4 heads x 8 x 16
    assert flops == 5 * 4 * 8 * 16 * 15 == 38_400
    # state in and out 2 x 512, B and C 2 x 32, decay, delta x and y 3 x 32
    assert nbytes == 4 * (1024 + 64 + 96) * 15 == 71_040


def test_gqa_decode_by_hand():
    # 2 tokens with 10 and 30 cached positions, ONE attention layer
    obs = {"decode_live_token_steps": 40, "decode_tokens": 2}
    flops, nbytes = flops_ssm_moe.gqa_decode(MODEL, obs)["decode"]
    assert flops == 4 * 8 * 16 * 40 == 20_480
    # a row: k and v of 2 heads of 16 in bfloat16; per token q 2 B, o 4 B
    assert nbytes == 2 * 2 * 2 * 16 * 40 + 2 * 6 * 8 * 16 == 6_656


def test_two_products_an_expert_by_hand():
    work = flops_ssm_moe.moe_experts(MODEL, {"moe_decode": [(80, 6), (40, 4)],
                                             "moe_prefill": []})
    one = 64 * 24
    assert work == {"decode": (120 * 2 * 2.0 * one,
                               10 * 2 * 2.0 * one + 120 * (6.0 * 64
                                                           + 6.0 * 24))}


def test_roofline_reader_takes_the_work_from_this_module():
    obs = {"trace": {"by_name": {"mosaic:ssm_decode": 1e-6, "fusion": 1.0}},
           "model": MODEL, "device_kind": "TPU v5 lite", "decode_tokens": 5}
    args = {"pattern": "^mosaic:ssm_decode", "module": "flops_ssm_moe",
            "work": "ssm_decode"}
    share = kernel_roofline_from.read(obs, args)
    assert abs(share - 100 * (71_040 / 819e9) / 1e-6) < 1e-9
    assert kernel_roofline_from.read(dict(obs, trace=None), args) is None
    del obs["decode_tokens"]
    assert kernel_roofline_from.read(obs, args) is None

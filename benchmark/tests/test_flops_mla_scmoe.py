"""``flops_mla_scmoe.py`` by hand at one small shape, and through the
roofline reader."""
from benchmark import flops_mla_moe, flops_mla_scmoe
from benchmark.readers import kernel_roofline_from

# 3 double layers: 6 attention sub-layers, 3 expert branches
MODEL = {"num_layers": 3, "num_heads": 4, "kv_rank": 32, "qk_rope": 8,
         "hidden_size": 64, "expert_width": 24}


def test_mla_decode_counts_two_attention_layers_a_double_layer():
    # 2 tokens with 10 and 30 cached positions
    obs = {"decode_live_token_steps": 40, "decode_tokens": 2}
    flops, nbytes = flops_mla_scmoe.mla_decode(MODEL, obs)["decode"]
    # per layer and position 4 heads x (40 + 32) multiply-adds
    assert flops == 2 * 4 * 72 * 40 * 6 == 138_240
    # a row 40 values in bfloat16; per token q 4 x 40 x 2 B, u 4 x 32 x 4 B
    assert nbytes == 6 * (2 * 40 * 40 + 2 * (2 * 4 * 40 + 4 * 4 * 32)) == 29_184
    assert (flops, nbytes) == tuple(
        2 * x for x in flops_mla_moe.mla_decode(MODEL, obs)["decode"])


def test_the_experts_work_is_counted_from_the_spans_alone():
    obs = {"moe_decode": [(80, 6), (40, 4)], "moe_prefill": [(300, 9)]}
    work = flops_mla_scmoe.moe_experts(MODEL, obs)
    one = 64 * 24
    assert work == {
        "decode": (120 * 3 * 2.0 * one,
                   10 * 3 * 2.0 * one + 120 * (8.0 * 64 + 10.0 * 24)),
        "prefill": (300 * 3 * 2.0 * one,
                    9 * 3 * 2.0 * one + 300 * (8.0 * 64 + 10.0 * 24))}
    # whatever the layer count says: the spans sum over the expert layers
    assert work == flops_mla_scmoe.moe_experts(dict(MODEL, num_layers=28), obs)


def test_roofline_reader_takes_the_work_from_this_module():
    obs = {"trace": {"by_name": {"mosaic:mla_decode": 1e-6, "fusion": 1.0}},
           "model": MODEL, "device_kind": "TPU v5 lite",
           "decode_live_token_steps": 40, "decode_tokens": 2}
    args = {"pattern": "^mosaic:mla_decode", "module": "flops_mla_scmoe",
            "work": "mla_decode"}
    share = kernel_roofline_from.read(obs, args)
    assert abs(share - 100 * (29_184 / 819e9) / 1e-6) < 1e-9
    assert kernel_roofline_from.read(dict(obs, trace=None), args) is None
    del obs["decode_tokens"]
    assert kernel_roofline_from.read(obs, args) is None

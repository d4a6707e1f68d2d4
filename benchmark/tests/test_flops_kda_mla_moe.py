"""``benchmark/flops_kda_mla_moe.py`` by hand, at the published widths."""
from benchmark import flops_kda_mla_moe, flops_mla_moe

MODEL = {"layers": [0] + list(range(6, 18)), "group_size": 6, "num_heads": 32,
         "kda_key_dim": 128, "kda_value_dim": 128, "kv_rank": 512,
         "qk_rope": 64, "hidden_size": 2560, "expert_width": 768}


def test_layers_by_kind():
    assert flops_kda_mla_moe.layers(MODEL) == (11, 2)
    whole = dict(MODEL, layers=list(range(42)))
    assert flops_kda_mla_moe.layers(whole) == (35, 7)


def test_delta_decode_moves_the_state_once_each_way():
    # one decode-step token in the 11 KDA layers: what the program counts
    obs = {"kda_decode_tokens": 11}
    flops, nbytes = flops_kda_mla_moe.kda_decode(MODEL, obs)["decode"]
    state = 32 * 128 * 128 * 4
    assert state == 2097152
    # the state both ways (4,194,304 B), q, k, the decay, v, o and beta
    assert nbytes == 11 * (2 * state + 4 * 32 * (3 * 128 + 2 * 128 + 1))
    assert flops == 11 * 7 * 32 * 128 * 128
    # memory-bound by far: under 1 FLOP a byte against a ridge of 240
    assert flops / nbytes < 1
    assert abs(nbytes / 11 / 4194304 - 1) < 0.03


def test_latent_decode_counts_the_two_paged_layers():
    obs = {"decode_live_token_steps": 3000, "decode_tokens": 1}
    flops, nbytes = flops_kda_mla_moe.mla_decode(MODEL, obs)["decode"]
    assert flops == 2 * 2 * 32 * (576 + 512) * 3000
    assert nbytes == 2 * (2 * 576 * 3000 + 2 * 32 * 576 + 4 * 32 * 512)
    # the twin of flops_mla_moe.mla_decode at num_layers = the paged layers
    twin = dict(MODEL, num_layers=2)
    assert flops_mla_moe.mla_decode(twin, obs)["decode"] == (flops, nbytes)


def test_the_experts_work_is_the_shared_count():
    assert flops_kda_mla_moe.moe_experts is flops_mla_moe.moe_experts
    obs = {"moe_decode": [(64, 28)], "moe_prefill": []}
    flops, nbytes = flops_kda_mla_moe.moe_experts(MODEL, obs)["decode"]
    assert flops == 64 * 3 * 2 * 2560 * 768
    assert nbytes == 28 * 3 * 2 * 2560 * 768 + 64 * (8 * 2560 + 10 * 768)

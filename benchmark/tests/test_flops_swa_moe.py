"""``benchmark/flops_swa_moe.py`` by hand, at the published widths."""
from benchmark import flops_swa_moe

MODEL = {"num_layers": 12, "layer_pattern": [0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0],
         "num_heads": 64, "head_dim": 192, "v_head_dim": 128, "kv_heads": 4,
         "swa_kv_heads": 8, "window": 128}


def test_layers_by_kind():
    assert flops_swa_moe.layers(MODEL) == (9, 3)


def test_window_decode_reads_a_ring_row_once_for_all_heads():
    # one token with a full ring in 9 window layers: 9 x 128 rows of 5,120 B
    obs = {"decode_window_rows": 9 * 128, "decode_tokens": 1}
    flops, nbytes = flops_swa_moe.swa_decode(MODEL, obs)["decode"]
    assert flops == 9 * 128 * 64 * 2 * 320
    assert nbytes == 9 * 128 * 5120 + 9 * 64 * (2 * 192 + 4 * 128)
    # memory-bound by far: 80 FLOPs a byte of row against a ridge of 240
    assert flops / nbytes < 16


def test_global_decode_reads_2560_bytes_a_row():
    obs = {"decode_global_rows": 3 * 7000, "decode_tokens": 1}
    flops, nbytes = flops_swa_moe.gqa_decode(MODEL, obs)["decode"]
    assert flops == 3 * 7000 * 64 * 2 * 320
    assert nbytes == 3 * 7000 * 2560 + 3 * 64 * (2 * 192 + 4 * 128)


def test_window_prefill_does_not_grow_with_the_start():
    one = flops_swa_moe.swa_prefill(MODEL, {"prefill_buckets": [1024]})
    flops, nbytes = one["prefill"]
    # 1,024 x 256 key positions a head a piece a layer
    assert flops == 9 * 64 * 1024 * 256 * 2 * 320
    # q and o: 64 heads x 320 x 2 B a position; k and v: 8 query blocks x
    # 256 rows x 5,120 B = 16 heads' worth a position
    assert nbytes == 9 * 1024 * 2 * 320 * (64 + 16)
    two = flops_swa_moe.swa_prefill(MODEL, {"prefill_buckets": [1024, 1024]})
    assert two["prefill"] == (2 * flops, 2 * nbytes)


def test_global_prefill_counts_the_positions_a_piece_sees():
    # a piece of 1,024 from position 2,048: query p sees p + 1 positions
    seen = sum(range(2049, 3073))
    obs = {"prefill_buckets": [1024], "prefill_global_rows": 3 * seen,
           "prefill_prefix_rows": 3072}
    flops, nbytes = flops_swa_moe.gqa_prefill(MODEL, obs)["prefill"]
    assert flops == 3 * seen * 64 * 2 * 320
    # q and o of 64 heads, then 3,072 rows of 2,560 B, a global layer
    assert nbytes == 3 * (1024 * 64 * 320 * 2 + 3072 * 2560)
    # compute-bound: over the ridge of 240 FLOPs a byte
    assert flops / nbytes > 240

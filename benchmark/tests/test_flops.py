import json
import os

import pytest

from benchmark import flops

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def model(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)["model"]


def test_gpt2_medium_by_hand():
    # 24 x (qkv 2.3.1024^2 + proj 2.1024^2 + ffn 2.2.1024.4096
    #       + causal attention 2.2.1024.1024 / 2) + head 2.1024.50257
    per_layer = 2 * (4 * 1024 ** 2 + 2 * 1024 * 4096) + 2 * 2 * 1024 * 1024 // 2
    assert per_layer == 27_262_976
    want = 24 * per_layer + 2 * 1024 * 50257
    assert want == 757_237_760
    assert flops.forward_flops_per_token(model("gpt2-medium"), 1024) == want
    assert flops.train_flops_per_token(model("gpt2-medium"), 1024) == 3 * want


def test_bert_large_by_hand_and_against_the_old_count():
    seq, u, h, v, layers = 128, 1024, 4096, 30522, 24
    per_layer = 2 * (4 * u * u + 2 * u * h) + 2 * 2 * seq * u   # not causal
    want = layers * per_layer + 2 * u * v + 2 * u * u          # decoder + mlm_dense
    assert want == 681_168_896
    got = flops.train_flops_per_token(model("bert-large"), seq)
    assert got == 3 * want
    # bench.py's _bert_train_flops counted "mlm head + embed decode" as TWO
    # vocabulary matmuls and no mlm_dense; per token that is
    old = 3 * (layers * per_layer + 2 * 2 * u * v)
    assert old - got == 3 * (2 * u * v - 2 * u * u)


def test_flash_work_is_six_matmuls_a_layer_halved_where_causal():
    m = model("gpt2-medium")
    f, b = flops.flash_train_step(m, {"batch": 4, "seq": 1024})
    assert f == 24 * 6 * (2 * 4 * 1024 * 1024 * 1024) / 2
    assert b == 24 * 12 * 4 * 1024 * 1024 * 2


def test_an_unknown_device_has_no_peak():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v9")
    with pytest.raises(KeyError):
        flops.peaks("source")

"""Operations and bytes that the algorithm needs, from shapes alone.

The ``transformer`` family: every matmul of the forward pass counted once at
2 FLOPs per multiply-add, the backward pass at twice the forward (so a
training step is forward x 3), no recomputation, elementwise work left out.
Copied from ``bench.py`` (``_lm_train_flops``) and corrected for BERT:
``BERTModel`` gathers its embedding and has one ``units x units`` dense and
ONE ``units x vocab`` decoder in its head, where ``bench.py`` counted two
vocabulary matmuls.
"""
from __future__ import annotations

import json
import os


def forward_flops_per_token(model: dict, seq: int) -> float:
    """Forward FLOPs per token of a sequence of ``seq`` tokens."""
    u, h = model["units"], model["hidden_size"]
    block = 2 * (4 * u * u + 2 * u * h)        # qkv, proj, ffn1, ffn2
    attn = 2 * 2 * seq * u                     # q.k^T and p.v over seq keys
    if model["kind"] == "causal_lm":
        attn /= 2                              # the masked half is not needed
    head = 2 * u * model["vocab_size"]
    if model["kind"] == "mlm":
        head += 2 * u * u                      # mlm_dense
    return model["num_layers"] * (block + attn) + head


def train_flops_per_token(model: dict, seq: int) -> float:
    return 3.0 * forward_flops_per_token(model, seq)


def flash_train_step(model: dict, obs: dict) -> tuple:
    """(FLOPs, bytes) of the attention kernels in one training step: per
    layer the forward's two matmuls (q.k^T, p.v) and the backward's four
    (dv, dp, dq, dk), each 2.B.H.S.S.D FLOPs, halved where causal; the
    recomputation of the scores in the backward pass is not counted. Bytes:
    q, k, v, o read or written by the forward, q, k, v, o, do read and dq,
    dk, dv written by the backward, in bf16 (the softmax statistics are
    small beside them)."""
    b, s, u = obs["batch"], obs["seq"], model["units"]
    matmul = 2.0 * b * s * s * u * (0.5 if model["kind"] == "causal_lm" else 1)
    return (model["num_layers"] * 6 * matmul,
            model["num_layers"] * 12 * b * s * u * 2.0)


def paged_decode(model: dict, obs: dict) -> tuple:
    """(FLOPs, bytes) of the paged decode kernel over the traced window:
    each decode-step token reads the K and V of its whole context in every
    layer from the float32 pool (``decode_live_token_steps`` = the summed
    context lengths), plus its query and output rows; q.k and p.v are 2
    FLOPs per element read."""
    u, layers = model["units"], model["num_layers"]
    live, tokens = obs["decode_live_token_steps"], obs["decode_tokens"]
    return (2.0 * 2 * live * u * layers,
            4.0 * layers * u * (2 * live + 2 * tokens))


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; a kind that is not in
    ``peaks.json`` is an error, not a default."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peak for device kind {device_kind!r} in "
                       "benchmark/peaks.json: add it with its source")
    return table[device_kind]

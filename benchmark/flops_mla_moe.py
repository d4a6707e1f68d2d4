"""Operations and bytes of the ``mla_moe`` kind's kernels, from what a traced
serving run observed (``runners/serve_mla_moe.py``). Matmuls at 2 FLOPs per
multiply-add, bfloat16 operands (2 B), float32 results where the program
writes float32 (4 B). Each function returns ``{piece: (FLOPs, bytes)}``: the
pieces are bound apart (``readers/kernel_roofline_from.py``), because decode
and prefill sit on different sides of the roofline.
"""
from __future__ import annotations


def mla_decode(model: dict, obs: dict) -> dict:
    """The paged latent decode kernel over the traced window. A decode-step
    token with n cached positions reads, in every layer, n rows of R =
    kv_rank + qk_rope values ONCE for all H heads, scores H x R against each
    (2.H.R.n) and sums the rows' first kv_rank columns (2.H.kv_rank.n); it
    reads its H x R query and writes H x kv_rank in float32.
    ``decode_live_token_steps`` is the summed n, ``decode_tokens`` the
    tokens."""
    h, rank = model["num_heads"], model["kv_rank"]
    r, layers = rank + model["qk_rope"], model["num_layers"]
    live, tokens = obs["decode_live_token_steps"], obs["decode_tokens"]
    return {"decode": (2.0 * h * (r + rank) * live * layers,
                       layers * (2.0 * r * live
                                 + tokens * (2.0 * h * r + 4.0 * h * rank)))}


def _experts(model: dict, rows: float, touched: float) -> tuple:
    """``rows`` (token, choice) pairs on held experts, ``touched`` (layer,
    expert) pairs with at least one: three matmuls of hidden x width a row;
    each touched expert's three matrices read once; per row the input read
    twice (2.D.2 B), gate and up written in float32 (2.F.4), their product
    read (F.2) and the output written in float32 (D.4)."""
    d, f = model["hidden_size"], model["expert_width"]
    return (rows * 3 * 2.0 * d * f,
            touched * 3 * 2.0 * d * f + rows * (8.0 * d + 10.0 * f))


def moe_experts(model: dict, obs: dict) -> dict:
    """The grouped product over the traced window, decode steps and
    prefills apart: ``moe_decode`` and ``moe_prefill`` are lists of (held
    pairs, touched experts), one entry a program call, summed over its
    expert layers (the ``moe.held`` / ``moe.touched`` span attributes)."""
    return {kind: _experts(model, sum(c[0] for c in calls),
                           sum(c[1] for c in calls))
            for kind, calls in (("decode", obs["moe_decode"]),
                                ("prefill", obs["moe_prefill"])) if calls}


def flash_prefill(model: dict, obs: dict) -> dict:
    """The flash forward of the prefills in the traced window (``prefill_
    buckets``: the padded length S of each): per layer and head q.k^T over
    q_head_dim and p.v over v_head, the causal half (2.S.S.(dqk + dv) / 2);
    q and k read (S.dqk.2 B each), v read and o written (S.dv.2 each)."""
    h, layers = model["num_heads"], model["num_layers"]
    dqk, dv = model["qk_nope"] + model["qk_rope"], model["v_head"]
    sq = sum(float(s) * s for s in obs["prefill_buckets"])
    tokens = float(sum(obs["prefill_buckets"]))
    return {"prefill": (layers * h * sq * (dqk + dv),
                        layers * h * tokens * 2.0 * (2 * dqk + 2 * dv))}

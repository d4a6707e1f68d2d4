"""Operations and bytes of the ``gdn_moe`` kind's kernels, from what a traced
serving run observed (``runners/serve_gdn_moe.py``). Matmuls at 2 FLOPs per
multiply-add; the paged rows bfloat16 (2 B), the recurrent state float32
(4 B). Each function returns ``{piece: (FLOPs, bytes)}`` for
``readers/kernel_roofline_from.py``. The held experts' grouped product is
``flops_mla_moe.moe_experts``: it reads ``hidden_size`` and ``expert_width``
alone, which this kind's ``model`` block gives under the same names.
"""
from __future__ import annotations


def delta_layers(model: dict) -> int:
    every = model["full_interval"]
    return model["num_layers"] - model["num_layers"] // every


def gdn_decode(model: dict, obs: dict) -> dict:
    """The one-token delta-rule kernel over the traced window. A decode-step
    token's slot has, in every gated-delta layer, HV states of dk x dv
    float32, each read ONCE and written ONCE; per head it also reads q and k
    (dk), v, the decay and beta rows (dv each) and writes o (dv), float32.
    Per state element: the decay (1), ``S^T k`` (2), the rank-one update (2)
    and ``S^T q`` (2). ``decode_tokens`` counts the tokens (live slots
    summed over the steps)."""
    hv, dk, dv = (model["linear_value_heads"], model["linear_key_dim"],
                  model["linear_value_dim"])
    calls = obs["decode_tokens"] * delta_layers(model)
    return {"decode": (7.0 * hv * dk * dv * calls,
                       4.0 * hv * (2 * dk * dv + 2 * dk + 4 * dv) * calls)}


def gqa_decode(model: dict, obs: dict) -> dict:
    """The grouped-KV paged decode kernel over the traced window. A
    decode-step token with n cached positions reads, in every paged (full
    attention) layer, n rows of ``2 KV D`` bfloat16 values ONCE for all H
    query heads, scores H x D against each (2.H.D.n) and sums the values
    (2.H.D.n); it reads its H x D query (bfloat16) and writes H x D in
    float32. ``decode_live_token_steps`` is the summed n."""
    h, kv, d = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    layers = model["num_layers"] // model["full_interval"]
    live, tokens = obs["decode_live_token_steps"], obs["decode_tokens"]
    return {"decode": (4.0 * h * d * live * layers,
                       layers * (2.0 * 2 * kv * d * live
                                 + tokens * (2.0 + 4.0) * h * d))}


def gqa_prefill(model: dict, obs: dict) -> dict:
    """The grouped-KV flash forward of the prefills in the traced window
    (``prefill_buckets``: the padded length S of each): per paged layer and
    query head ``q.k^T`` and ``p.v`` over D, the causal half (2.S.S.D); q
    read and o written (H.S.D.2 B each), k and v read once a cached head."""
    h, kv, d = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    layers = model["num_layers"] // model["full_interval"]
    sq = sum(float(s) * s for s in obs["prefill_buckets"])
    tokens = float(sum(obs["prefill_buckets"]))
    return {"prefill": (layers * h * sq * 2.0 * d,
                        layers * tokens * 2.0 * d * (2 * h + 2 * kv))}

"""Operations and bytes of the ``kda_mla_moe`` kind's kernels, from what a
traced serving run observed (``runners/serve_kda_mla_moe.py``). Matmuls at 2
FLOPs per multiply-add; the latent rows bfloat16 (2 B), the recurrent state
float32 (4 B). Each function returns ``{piece: (FLOPs, bytes)}`` for
``readers/kernel_roofline_from.py``. The held experts' grouped product is
``flops_mla_moe.moe_experts``, under its name here too: it reads
``hidden_size`` and ``expert_width`` alone, which this kind's ``model`` block
gives under the same names.
"""
from __future__ import annotations

from benchmark.flops_mla_moe import moe_experts  # noqa: F401  (a work here)


def layers(model: dict) -> tuple:
    """(KDA layers, MLA layers) among the layers held."""
    mla = sum((l + 1) % model["group_size"] == 0 for l in model["layers"])
    return len(model["layers"]) - mla, mla


def kda_decode(model: dict, obs: dict) -> dict:
    """The one-token delta kernel over the traced window.
    ``kda_decode_tokens`` counts (decode-step token, KDA layer) pairs as the
    program counted them (``kda.tokens`` on the profiled ``decode.step``
    spans). A pair's slot has H states of dk x dv float32, each read ONCE
    and written ONCE (4,194,304 B at 32 x 128 x 128); per head it also reads
    q, k and the decay (dk each), v (dv) and beta, and writes o (dv),
    float32. Per state element: the decay (1), ``S^T k`` (2), the rank-one
    update (2) and ``S^T q`` (2)."""
    h, dk, dv = model["num_heads"], model["kda_key_dim"], model["kda_value_dim"]
    calls = obs["kda_decode_tokens"]
    return {"decode": (7.0 * h * dk * dv * calls,
                       4.0 * h * (2 * dk * dv + 3 * dk + 2 * dv + 1) * calls)}


def mla_decode(model: dict, obs: dict) -> dict:
    """``flops_mla_moe.mla_decode`` with this kind's count of paged layers
    (the MLA layers alone): a decode-step token with n cached positions
    reads, in every MLA layer, n rows of R = kv_rank + qk_rope values ONCE
    for all H heads, scores H x R against each (2.H.R.n) and sums the rows'
    first kv_rank columns (2.H.kv_rank.n); it reads its H x R query and
    writes H x kv_rank in float32. ``decode_live_token_steps`` is the summed
    n, ``decode_tokens`` the tokens."""
    h, rank = model["num_heads"], model["kv_rank"]
    r, (_, n_mla) = rank + model["qk_rope"], layers(model)
    live, tokens = obs["decode_live_token_steps"], obs["decode_tokens"]
    return {"decode": (2.0 * h * (r + rank) * live * n_mla,
                       n_mla * (2.0 * r * live
                                + tokens * (2.0 * h * r + 4.0 * h * rank)))}

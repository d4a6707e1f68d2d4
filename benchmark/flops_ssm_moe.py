"""Operations and bytes of the ``ssm_moe`` kind's kernels, from what a traced
serving run observed (``runners/serve_ssm_moe.py``). Matmuls at 2 FLOPs per
multiply-add; the paged rows and the experts' weights bfloat16 (2 B), the
state-space state float32 (4 B). Each function returns ``{piece: (FLOPs,
bytes)}`` for ``readers/kernel_roofline_from.py``. The layers are counted
from the configuration's ``pattern`` string (``M`` Mamba-2, ``*`` attention,
``E`` experts): this kind has no interval.
"""
from __future__ import annotations


def layers_of(model: dict, kind: str) -> int:
    return model["pattern"].count(kind)


def ssm_decode(model: dict, obs: dict) -> dict:
    """The one-token state-space kernel over the traced window. A
    decode-step token's slot has, in every Mamba-2 layer, H states of P x N
    float32, each read ONCE and written ONCE; per layer it also reads the
    group's B and C (G x N each), the decay and ``delta x`` rows (H P each)
    and writes y (H P), float32. Per state element: the decay (1), the
    rank-one update (2) and ``S C`` (2). ``decode_tokens`` counts the tokens
    (live slots summed over the steps)."""
    h, p = model["ssm_heads"], model["ssm_head_dim"]
    g, n = model["ssm_groups"], model["ssm_state"]
    calls = obs["decode_tokens"] * layers_of(model, "M")
    return {"decode": (5.0 * h * p * n * calls,
                       4.0 * (2 * h * p * n + 2 * g * n + 3 * h * p) * calls)}


def gqa_decode(model: dict, obs: dict) -> dict:
    """The grouped-KV paged decode kernel over the traced window. A
    decode-step token with n cached positions reads, in every attention
    layer, n rows of ``2 KV D`` bfloat16 values ONCE for all H query heads,
    scores H x D against each (2.H.D.n) and sums the values (2.H.D.n); it
    reads its H x D query (bfloat16) and writes H x D in float32.
    ``decode_live_token_steps`` is the summed n."""
    h, kv, d = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    layers = layers_of(model, "*")
    live, tokens = obs["decode_live_token_steps"], obs["decode_tokens"]
    return {"decode": (4.0 * h * d * live * layers,
                       layers * (2.0 * 2 * kv * d * live
                                 + tokens * (2.0 + 4.0) * h * d))}


def _experts(model: dict, rows: float, touched: float) -> tuple:
    """``rows`` (token, choice) pairs on held experts, ``touched`` (layer,
    expert) pairs with at least one: TWO matmuls of hidden x width a row
    (``relu(h W_u)^2 W_d``: no gate matrix); each touched expert's two
    matrices read once, at the published sizes (they are stored with zeros
    behind both, in whole tiles of 512: not counted); per row the input read
    (D.2 B), the up product written in float32 (F.4), its square read (F.2)
    and the output written in float32 (D.4)."""
    d, f = model["hidden_size"], model["expert_width"]
    return (rows * 2 * 2.0 * d * f,
            touched * 2 * 2.0 * d * f + rows * (6.0 * d + 6.0 * f))


def moe_experts(model: dict, obs: dict) -> dict:
    """The grouped product over the traced window, decode steps and
    prefills apart: ``moe_decode`` and ``moe_prefill`` are lists of (held
    pairs, touched experts), one entry a program call, summed over its
    expert layers (the ``moe.held`` / ``moe.touched`` span attributes)."""
    return {kind: _experts(model, sum(c[0] for c in calls),
                           sum(c[1] for c in calls))
            for kind, calls in (("decode", obs["moe_decode"]),
                                ("prefill", obs["moe_prefill"])) if calls}

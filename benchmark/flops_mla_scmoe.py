"""Operations and bytes of the ``mla_scmoe`` kind's kernels, from what a
traced serving run observed (``runners/serve_mla_scmoe.py``): the counts are
``flops_mla_moe.py``'s, taken over this kind's own numbers of layers. Its
``num_layers`` counts DOUBLE layers: the paged latent kernel runs in ``2 x
num_layers`` attention sub-layers (the pool's layers), the held experts'
grouped products in ``num_layers`` expert branches — and those come summed
over the layers in the ``moe.held`` / ``moe.touched`` span attributes, so
they need no count at all. Each function returns ``{piece: (FLOPs,
bytes)}`` (``readers/kernel_roofline_from.py``)."""
from __future__ import annotations

from benchmark import flops_mla_moe


def mla_decode(model: dict, obs: dict) -> dict:
    """``flops_mla_moe.mla_decode`` over the 2 x ``num_layers`` attention
    sub-layers: a decode-step token with n cached positions reads n latent
    rows in each of them."""
    return flops_mla_moe.mla_decode(
        dict(model, num_layers=2 * model["num_layers"]), obs)


def moe_experts(model: dict, obs: dict) -> dict:
    """``flops_mla_moe.moe_experts``: three products a held (token, choice)
    pair at hidden x expert width, a touched expert's three matrices read
    once. A pair on an identity expert multiplies nothing and is in neither
    count."""
    return flops_mla_moe.moe_experts(model, obs)

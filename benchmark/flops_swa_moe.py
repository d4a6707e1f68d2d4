"""Operations and bytes of the ``swa_moe`` kind's attention kernels, from what
a traced serving run observed (``runners/serve_swa_moe.py``): the program
counts the rows its attention has to see (``attn.window_rows``,
``attn.global_rows`` on every ``decode.step`` and ``decode.prefill`` span),
so no product here is reckoned from lengths. Matmuls at 2 FLOPs per
multiply-add; rows, queries and a piece's outputs bfloat16 (2 B), a decode
kernel's output float32 (4 B). Each function returns ``{piece: (FLOPs,
bytes)}`` for ``readers/kernel_roofline_from.py``. The held experts' grouped
product is ``flops_mla_moe.moe_experts``: it reads ``hidden_size`` and
``expert_width`` alone, which this kind's ``model`` block gives under the same
names.
"""
from __future__ import annotations


def layers(model: dict) -> tuple:
    """(window layers, global layers)."""
    n = sum(model["layer_pattern"])
    return n, model["num_layers"] - n


def _decode(model: dict, rows: float, calls: float, kv: int) -> tuple:
    """``rows`` cached rows of ``kv`` heads seen by ``calls`` (token, layer)
    kernel rows: a cached row — ``kv (dk + dv)`` values — is read ONCE for
    all H query heads, which score it (2.H.dk) and sum its values (2.H.dv);
    a call reads its H x dk query and writes H x dv in float32."""
    h, dk, dv = model["num_heads"], model["head_dim"], model["v_head_dim"]
    return (2.0 * h * (dk + dv) * rows,
            2.0 * kv * (dk + dv) * rows + calls * h * (2.0 * dk + 4.0 * dv))


def swa_decode(model: dict, obs: dict) -> dict:
    """The one-token window kernel over the traced window: per decode-step
    token and window layer ``min(n, window)`` ring rows of the 8 cached heads
    (``decode_window_rows``: ``attn.window_rows`` summed over the profiled
    steps)."""
    n_window, _ = layers(model)
    return {"decode": _decode(model, obs["decode_window_rows"],
                              obs["decode_tokens"] * n_window,
                              model["swa_kv_heads"])}


def gqa_decode(model: dict, obs: dict) -> dict:
    """The paged kernel of the global layers: per decode-step token and
    global layer its whole context's rows of the 4 cached heads
    (``decode_global_rows``: ``attn.global_rows`` summed over the profiled
    steps)."""
    _, n_global = layers(model)
    return {"decode": _decode(model, obs["decode_global_rows"],
                              obs["decode_tokens"] * n_global,
                              model["kv_heads"])}


def swa_prefill(model: dict, obs: dict) -> dict:
    """The window forward of the pieces in the traced window
    (``prefill_buckets``: the positions C of each): per window layer every
    query block of W rows meets TWO key blocks, so a query head multiplies C
    x 2W key positions whatever the piece's start (2.C.2W.(dk + dv) FLOPs);
    q read and o written once (H.C.(dk + dv).2 B), and a query block reads
    its two key and value blocks of all cached heads (2W.KV.(dk + dv).2 B a
    block of W rows)."""
    h, kv = model["num_heads"], model["swa_kv_heads"]
    wide, w = model["head_dim"] + model["v_head_dim"], model["window"]
    n_window, _ = layers(model)
    positions = float(sum(obs["prefill_buckets"]))
    return {"prefill": (n_window * h * positions * 2 * w * 2.0 * wide,
                        n_window * positions * 2.0 * wide * (h + 2 * kv))}


def gqa_prefill(model: dict, obs: dict) -> dict:
    """The continued forward of the global layers over the pieces in the
    traced window: a query position sees itself and every position before it
    (``prefill_global_rows``: ``attn.global_rows`` summed over the profiled
    pieces — positions seen x global layers), each for all H query heads
    (2.H.(dk + dv) FLOPs a position seen); a piece reads its q and writes its
    o once (H.C.(dk + dv).2 B a global layer) and reads the prompt so far,
    its own rows among them, once (``prefill_prefix_rows`` rows of KV.(dk +
    dv).2 B a global layer). The blocks above the diagonal that the kernel
    multiplies and masks are not counted."""
    h, kv = model["num_heads"], model["kv_heads"]
    wide = model["head_dim"] + model["v_head_dim"]
    _, n_global = layers(model)
    positions = float(sum(obs["prefill_buckets"]))
    return {"prefill": (2.0 * h * wide * obs["prefill_global_rows"],
                        n_global * 2.0 * wide * (
                            h * positions + kv * obs["prefill_prefix_rows"]))}

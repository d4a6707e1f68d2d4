"""On-chip breakdown of the seq-2048 LM step: where does the time go?

Each component is slope-timed (tools/_chiptime.py: difference of two
scan-chain depths of the same jitted body — the fixed per-dispatch host
cost cancels, which single-shot wall timing of a short kernel would
mostly measure).  Prints a JSON breakdown so the
flash-attention work (VERDICT r3 item 1) is driven by data.
"""
from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools._chiptime import slope_time  # noqa: E402


def main():
    from mxnet_tpu import platform as mxplatform
    from mxnet_tpu.ops.flash_attention import flash_attention
    from mxnet_tpu.ops.attention import plain_attention

    mxplatform.devices_or_exit(what="tools/profile_lm.py")
    B = int(os.environ.get("PROF_B", 4))
    S = int(os.environ.get("PROF_S", 2048))
    H, D = 12, 64
    U, HID, VOCAB = 768, 3072, 32000
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, H, S, D), jnp.bfloat16)
    k = jax.random.normal(key, (B, H, S, D), jnp.bfloat16)
    v = jax.random.normal(key, (B, H, S, D), jnp.bfloat16)

    out = {}

    # attention FLOPs (causal => half the blocks visible): fwd = 2 matmuls
    attn_fwd_flops = 2 * 2 * S * S * D * B * H / 2
    attn_bwd_flops = attn_fwd_flops * 2.5  # 5 matmuls in bwd vs 2 in fwd

    def rep(name, step, carry0, flops, n1=10, n2=50):
        t = slope_time(step, carry0, n1, n2)
        out[f"{name}_ms"] = round(t * 1e3, 3)
        if flops:
            out[f"{name}_tflops"] = round(flops / t / 1e12, 1)
        print(f"  {name}: {out[f'{name}_ms']} ms", file=sys.stderr)

    rep("flash_fwd", lambda c: flash_attention(c, k, v, causal=True), q,
        attn_fwd_flops)
    rep("plain_fwd", lambda c: plain_attention(c, k, v, causal=True), q,
        attn_fwd_flops)

    def fgrad(c):
        f = lambda qq: (flash_attention(qq, k, v, causal=True)
                        .astype(jnp.float32) ** 2).sum()
        return jax.grad(f)(c).astype(jnp.bfloat16)

    rep("flash_fwdbwd", fgrad, q, attn_fwd_flops * 2 + attn_bwd_flops)

    def pgrad(c):
        f = lambda qq: (plain_attention(qq, k, v, causal=True)
                        .astype(jnp.float32) ** 2).sum()
        return jax.grad(f)(c).astype(jnp.bfloat16)

    rep("plain_fwdbwd", pgrad, q, attn_fwd_flops * 2 + attn_bwd_flops)

    # MLP-ish matmul inventory of 12 layers: qkv+proj+ffn1+ffn2, fwd+bwd
    x = jax.random.normal(key, (B * S, U), jnp.bfloat16)
    w_qkv = jax.random.normal(key, (U, 3 * U), jnp.bfloat16)
    w_proj = jax.random.normal(key, (U, U), jnp.bfloat16)
    w1 = jax.random.normal(key, (U, HID), jnp.bfloat16)
    w2 = jax.random.normal(key, (HID, U), jnp.bfloat16)
    prec = jax.lax.Precision.DEFAULT

    def mlp12(xx):
        for _ in range(12):
            h = jnp.dot(xx, w_qkv, precision=prec)[:, :U]
            h = jnp.dot(h, w_proj, precision=prec)
            h = jnp.dot(jax.nn.gelu(jnp.dot(h, w1, precision=prec)),
                        w2, precision=prec)
            xx = xx + h
        return (xx.astype(jnp.float32) ** 2).sum()

    mlp_flops = 3 * 12 * 2 * (U * U + U * U + 2 * U * HID) * B * S
    rep("mlp12_fwdbwd",
        lambda c: jax.grad(mlp12)(c).astype(jnp.bfloat16), x, mlp_flops,
        4, 16)

    # LM head + CE
    wv = jax.random.normal(key, (U, VOCAB), jnp.bfloat16)
    labels = jax.random.randint(key, (B * S,), 0, VOCAB)

    def head(xx):
        logits = jnp.dot(xx, wv, precision=prec)
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        nll = lse - jnp.take_along_axis(
            logits.astype(jnp.float32), labels[:, None], axis=-1)[:, 0]
        return nll.mean()

    head_flops = 3 * 2 * B * S * U * VOCAB
    rep("head_ce_fwdbwd",
        lambda c: jax.grad(head)(c).astype(jnp.bfloat16), x, head_flops)

    # embedding grad (scatter-add over 32k rows)
    ids = jax.random.randint(key, (B, S), 0, VOCAB)

    def embed(e):
        return (e[ids].astype(jnp.float32) ** 2).sum()

    emb = jax.random.normal(key, (VOCAB, U), jnp.bfloat16)
    rep("embed_grad",
        lambda c: jax.grad(embed)(c).astype(jnp.bfloat16), emb, None)

    # --- the model's EXACT per-layer attention block (qkv matmul +
    # (B,S,3U)->(3,B,H,S,D) transpose + flash + out transpose + proj),
    # fwd+bwd x12 — the gap to 12x the bare kernel is the layout/residual
    # overhead VERDICT r4 weak #2 asks to itemize ---
    xs = jax.random.normal(key, (B, S, U), jnp.bfloat16)

    def attn_block12(xx):
        h_ = xx
        for _ in range(12):
            qkv = jnp.dot(h_.reshape(B * S, U), w_qkv, precision=prec)
            qkv = qkv.reshape(B, S, 3, H, D).transpose(2, 0, 3, 1, 4)
            o = flash_attention(qkv[0], qkv[1], qkv[2], causal=True)
            o = o.transpose(0, 2, 1, 3).reshape(B * S, U)
            h_ = h_ + jnp.dot(o, w_proj, precision=prec).reshape(B, S, U)
        return (h_.astype(jnp.float32) ** 2).sum()

    attn_block_flops = 12 * (3 * 2 * B * S * U * (3 * U + U)
                             + attn_fwd_flops * 2 + attn_bwd_flops)
    rep("attn_block12_fwdbwd",
        lambda c: jax.grad(attn_block12)(c).astype(jnp.bfloat16), xs,
        attn_block_flops, 4, 16)

    # the layout cost alone: fwd+bwd of the two transposes, x12
    qkv_big = jax.random.normal(key, (B, S, 3, H, D), jnp.bfloat16)

    def transposes12(c):
        acc = 0.0
        t = c
        for _ in range(12):
            t3 = t.transpose(2, 0, 3, 1, 4)
            o = t3[0] + t3[1] + t3[2]
            ob = o.transpose(0, 2, 1, 3)  # (B,S,H,D)
            acc = acc + (ob.astype(jnp.float32) ** 2).sum()
            # thread the output back in — a loop-invariant body would be
            # CSE'd to ONE transpose pair and under-report 12x
            t = jnp.stack([ob, ob, ob], axis=2)
        return acc

    rep("transposes12_fwdbwd",
        lambda c: jax.grad(transposes12)(c).astype(jnp.bfloat16), qkv_big,
        None, 4, 16)

    # reconciliation vs the full in-model step when available
    out["config"] = {"B": B, "S": S, "H": H, "D": D}
    known = (out.get("flash_fwdbwd_ms", 0) * 12
             + out.get("mlp12_fwdbwd_ms", 0)
             + out.get("head_ce_fwdbwd_ms", 0)
             + out.get("embed_grad_ms", 0))
    out["sum_components_ms"] = round(known, 2)
    # everything in the attention block that is NOT the bare kernel:
    # qkv/proj matmuls + the two transposes + residual adds
    out["attn_block_minus_kernel_ms"] = round(
        out.get("attn_block12_fwdbwd_ms", 0)
        - out.get("flash_fwdbwd_ms", 0) * 12, 2)
    print(json.dumps(out, indent=1))
    artifact = os.environ.get("PROF_JSON")
    if artifact:
        with open(artifact, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()

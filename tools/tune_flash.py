"""On-chip flash attention block-size sweep → _BLOCK_TABLE defaults.

Times the Pallas fwd and fwd+bwd at (B,H,S,D) over a block-size grid with
slope timing (tools/_chiptime.py: difference of two scan-chain depths, so
the fixed per-dispatch host cost cancels). Prints a JSON table;
the winners get hardcoded into ops/flash_attention._BLOCK_TABLE.

Usage: python tools/tune_flash.py [S ...]   (default 1024 2048 4096)
"""
from __future__ import annotations

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools._chiptime import slope_time  # noqa: E402


def sweep(S, B=4, H=12, D=64, causal=True, dtype=jnp.bfloat16):
    from mxnet_tpu.ops.flash_attention import flash_attention

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, H, S, D), dtype)
    k = jax.random.normal(key, (B, H, S, D), dtype)
    v = jax.random.normal(key, (B, H, S, D), dtype)
    flops_fwd = 2 * 2 * S * S * D * B * H // (2 if causal else 1)

    results = {}
    cands = [(bq, bk) for bq in (256, 512, 1024) for bk in (256, 512, 1024)
             if bq <= S and bk <= S]
    for bq, bk in cands:
        fa = functools.partial(flash_attention, causal=causal,
                               block_q=bq, block_k=bk)
        try:
            t_f = slope_time(lambda c: fa(c, k, v), q, 10, 50)

            def fb(c):
                f = lambda qq: (fa(qq, k, v).astype(jnp.float32) ** 2).sum()
                return jax.grad(f)(c).astype(dtype)

            t_b = slope_time(fb, q, 10, 50)
        except Exception as e:
            results[f"{bq}x{bk}"] = f"FAIL {type(e).__name__}"
            continue
        results[f"{bq}x{bk}"] = {
            "fwd_ms": round(t_f * 1e3, 3),
            "fwd_tflops": round(flops_fwd / t_f / 1e12, 1),
            "fwdbwd_ms": round(t_b * 1e3, 3),
        }
        print(f"  S={S} {bq}x{bk}: {results[f'{bq}x{bk}']}", file=sys.stderr)
    return results


def main():
    from mxnet_tpu import platform as mxplatform

    mxplatform.devices_or_exit(what="tools/tune_flash.py")
    seqs = [int(a) for a in sys.argv[1:]] or [1024, 2048, 4096]
    out = {}
    for S in seqs:
        out[str(S)] = sweep(S)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()

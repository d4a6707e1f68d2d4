"""On-chip flash attention schedule sweep → _BLOCK_TABLE.

Times the Pallas forward (and, where a cell trains through it, forward +
backward) over the schedules the kernels can take — the blocks a grid step
keeps resident, the (q rows, k columns) of the pairs it walks them in, 128
and unequal sizes included, one or two heads a step — at the shapes the
benchmark's cells run: gpt2m-train-s1024's (4, 16, 1024, 64) and the
latent models' prefill, (1, 64, {512, 1024, 1536}, 192) with 128-wide
values; and the PACKED entry (``flash_attention_packed``: q, k, v as column
blocks of the fused projection, two 64-wide heads a 128-lane block) at the
train cell's (4, 1024, 3 x 1024) with 16 heads and at (4, 2048, 3 x 768)
with 12 (a block's heads always share a forward body there: one schedule
a (block, pair)). Slope timing (tools/_chiptime.py: difference of two scan-chain
depths, so the fixed per-dispatch host cost cancels). Prints a JSON table
and, last, the lines ``ops/flash_attention._BLOCK_TABLE`` is filled from: a
shape whose best schedule is not faster than whole (512, 512) pairs by 2 %
keeps those and gets no line.

Usage: python tools/tune_flash.py [heads|packed]    (default: both)
"""
from __future__ import annotations

import json
import math
import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools._chiptime import slope_time  # noqa: E402

# (batch, heads, seq, head_dim), value width, timed through the backward too
SHAPES = [((4, 16, 1024, 64), 64, True),
          ((1, 64, 512, 192), 128, False),
          ((1, 64, 1024, 192), 128, False),
          ((1, 64, 1536, 192), 128, False)]
# the packed entry's, always through the backward: its table is the same
PACKED_SHAPES = [(4, 16, 1024, 64), (4, 12, 2048, 64)]
SUBS = (128, 256, 512)
MAX_PRODUCT = 8     # sub-tiles one straight-line product may span


def candidates(s, packed=False):
    """``packed``: pairs of equal rows and columns alone (every unequal pair
    measured slower, PERF.md §6 PR 47: a sub-block taller than a tile is
    crossed twice by the diagonal and walks singly), and no choice of heads
    a step."""
    from mxnet_tpu.ops.flash_attention import _Schedule

    for block in sorted({512, s}):
        for sub_q in SUBS:
            for sub_k in SUBS:
                if (block % sub_q or block % sub_k
                        or block // min(sub_q, sub_k) > MAX_PRODUCT
                        or (packed and sub_q != sub_k)):
                    continue
                for heads in (1,) if packed else (1, 2):
                    yield _Schedule(block, block, sub_q, sub_k, heads)


def sweep(shape, d_v, backward, dtype=jnp.bfloat16, packed=False):
    from mxnet_tpu.ops import flash_attention as fa

    b, h, s, d = shape
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    if packed:   # the carry is the projection: q's columns, then k's and v's
        q = jax.random.normal(kq, (b, s, 3 * h * d), dtype)
        d_v = h * d
    else:
        q = jax.random.normal(kq, shape, dtype)
        k = jax.random.normal(kk, shape, dtype)
        v = jax.random.normal(kv, (b, h, s, d_v), dtype)
    results = {}
    for sched in candidates(s, packed):
        def attn(c, sched=sched):
            if packed:
                return fa._flash_packed(c, h, 1.0 / math.sqrt(d), True, sched,
                                        False)[0]
            return fa._flash(c, k, v, jnp.int32(0), 1.0 / math.sqrt(d), True,
                             sched, False)[0]

        def fwd(c):   # the chain's carry keeps q's shape
            return jnp.concatenate([attn(c), c[..., d_v:]], axis=-1)

        def fwd_bwd(c):
            f = lambda qq: (attn(qq).astype(jnp.float32) ** 2).sum()
            return jax.grad(f)(c).astype(dtype)

        name = "x".join(map(str, sched))
        try:
            row = {"fwd_ms": round(slope_time(fwd, q, 10, 50) * 1e3, 4)}
            if backward:
                row["fwdbwd_ms"] = round(
                    slope_time(fwd_bwd, q, 10, 50) * 1e3, 4)
        except Exception as e:
            row = f"FAIL {type(e).__name__}"
        results[name] = row
        print(f"  {'packed ' * packed}{shape} {name}: {row}", file=sys.stderr,
              flush=True)
    return results


def main():
    from mxnet_tpu import platform as mxplatform

    mxplatform.devices_or_exit(what="tools/tune_flash.py")
    which = sys.argv[1:] or ["heads", "packed"]
    shapes = [s + (False,) for s in SHAPES if "heads" in which] + [
        (s, s[3], True, True) for s in PACKED_SHAPES if "packed" in which]
    out, table = {}, []
    for shape, d_v, backward, packed in shapes:
        rows = sweep(shape, d_v, backward, packed=packed)
        out["packed " * packed + str(shape)] = rows
        key = "fwdbwd_ms" if backward else "fwd_ms"
        timed = {n: r[key] for n, r in rows.items() if isinstance(r, dict)}
        best = min(timed, key=timed.get)
        if timed[best] < 0.98 * timed.get("512x512x512x512x1", 0.0):
            table.append(f"    ({shape[2]}, {shape[3]}): _Schedule("
                         f"{best.replace('x', ', ')}),   # {timed[best]} ms "
                         f"({key}{', packed' * packed}) for "
                         f"{timed['512x512x512x512x1']}")
    print(json.dumps(out, indent=1))
    print("_BLOCK_TABLE = {\n" + "\n".join(table) + "\n}")


if __name__ == "__main__":
    main()

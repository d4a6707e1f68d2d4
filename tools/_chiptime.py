"""Slope-based on-chip timing of one jitted body.

Every dispatched+synced program pays a fixed host cost (dispatch, the
sync, the result fetch) that a short kernel's own time can disappear
under. The SLOPE between two scan-chain depths of the same jitted body
leaves only the device time of one iteration:

    t_per_iter = (T(n2) - T(n1)) / (n2 - n1)

Both chains share one compiled body; the fixed cost cancels. best_of
repeats guard against host contention. This is a host-clock estimate for
sweeping candidates; a kernel's device time proper comes from a profiler
trace (PERF.md).
"""
from __future__ import annotations

import time

import jax

__all__ = ["chain_total", "slope_time"]


def chain_total(step, carry0, iters, best_of=3):
    @jax.jit
    def chain(c):
        def body(c, _):
            return step(c), None
        out, _ = jax.lax.scan(body, c, None, length=iters)
        return out

    jax.block_until_ready(chain(carry0))
    best = float("inf")
    for _ in range(best_of):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(carry0))
        best = min(best, time.perf_counter() - t0)
    return best


def slope_time(step, carry0, n1=20, n2=100, best_of=3):
    """Per-iteration device time of `step`, fixed dispatch cost cancelled."""
    t1 = chain_total(step, carry0, n1, best_of)
    t2 = chain_total(step, carry0, n2, best_of)
    return max((t2 - t1) / (n2 - n1), 1e-9)

"""The one general traffic generator: parameters in, seeded inputs out.

A cell's ``traffic`` block (in ``workloads/<cell>.json``) is data; nothing
here knows a cell by name. Every seed gives the same *sizes* (shapes, the
multiset of request lengths; with ``pair_seed``, of prompt and output
lengths together) in another order with other token ids, so that runs with
different seeds do the same amount of work.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _zipf_ids(rng, vocab: int, a: float, shape) -> np.ndarray:
    """Token ids with Zipf(a) frequencies over the vocabulary, the ranks
    assigned to ids by a seeded permutation."""
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** a
    ranks = rng.choice(vocab, size=shape, p=p / p.sum())
    return rng.permutation(vocab)[ranks].astype(np.int32)


def train_batches(traffic: dict, model: dict, seed: int) -> list:
    """``host_batches`` batches of (batch, seq) int32 arrays: ``tokens``,
    ``labels`` and, for an mlm task, ``types``. Every row differs."""
    rng = _rng(seed, 0)
    b, s, vocab = traffic["batch"], traffic["seq"], model["vocab_size"]
    out = []
    for _ in range(traffic["host_batches"]):
        if model["kind"] == "causal_lm":
            ids = _zipf_ids(rng, vocab, traffic["zipf_a"], (b, s + 1))
            out.append({"tokens": ids[:, :-1].copy(),
                        "labels": ids[:, 1:].copy()})
        else:
            ids = _zipf_ids(rng, vocab, traffic["zipf_a"], (b, s))
            masked = np.where(rng.random((b, s)) < traffic["mask_rate"],
                              np.int32(traffic["mask_id"]), ids)
            # two segments per row, split at a seeded point
            split = rng.integers(1, s, size=(b, 1))
            types = (np.arange(s)[None, :] >= split).astype(np.int32)
            out.append({"tokens": masked, "types": types, "labels": ids})
    return out


def _lognormal_lengths(rng, n, median, sigma, lo, hi) -> np.ndarray:
    """n lengths at the quantiles of a clipped lognormal — the same multiset
    for every seed — in a seeded order."""
    q = (np.arange(n) + 0.5) / n
    z = np.array([NormalDist().inv_cdf(x) for x in q])
    lens = np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)
    return rng.permutation(lens)


def serve_requests(traffic: dict, model: dict, seed: int) -> list:
    """``requests`` requests, each a dict of ``prompt`` (int32 ids) and
    ``max_new_tokens``; clients take them in order and start over at the
    end. Lengths are lognormal (median, sigma, clip) and prompt + output
    never passes ``max_length``. With ``pair_seed`` every seed gives the
    same multiset of (prompt length, output length) pairs; without it, the
    same two multisets of lengths, paired by the seed."""
    rng = _rng(seed, 1)
    n = traffic["requests"]
    if "pair_seed" in traffic:
        # which output length goes with which prompt is work, not order: a
        # decode step reads every live token's context, so a seed that put
        # long outputs behind long prompts ran a slower cell (PERF.md, PR
        # 34). The pairs are drawn once, from the cell's own number, and
        # the seed gives only their order.
        pairs = _rng(traffic["pair_seed"], 3)
        plen = _lognormal_lengths(pairs, n, *traffic["prompt_len"])
        olen = _lognormal_lengths(pairs, n, *traffic["output_len"])
        order = rng.permutation(n)
        plen, olen = plen[order], olen[order]
    else:
        plen = _lognormal_lengths(rng, n, *traffic["prompt_len"])
        olen = _lognormal_lengths(rng, n, *traffic["output_len"])
    olen = np.minimum(olen, model["max_length"] - plen)
    ids = _zipf_ids(rng, model["vocab_size"], traffic["zipf_a"],
                    (int(plen.sum()),))
    cuts = np.cumsum(plen)[:-1]
    return [{"prompt": p, "max_new_tokens": int(o)}
            for p, o in zip(np.split(ids, cuts), olen)]

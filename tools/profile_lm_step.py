"""Time the REAL ShardedTrainer LM/BERT step as a scan-chained jit.

Separates pure device time from per-call dispatch overhead: bench.py times
wall-clock per trainer.step() (what a user sees); this chains the raw step
function N times inside one jit with one sync, so dispatch latency
amortizes out.  The delta between the two is host/dispatch overhead, the
chained number is what kernel work actually costs.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _sync(r):
    leaf = jax.tree_util.tree_leaves(r)[0]
    np.asarray(jax.device_get(jnp.ravel(leaf)[:1]))


def _chain_total(trainer, vals, iters, best_of=2):
    raw = trainer._raw_step_fn

    @jax.jit
    def chain(params, opt_state):
        def body(c, t):
            p, s = c
            loss, p, s = raw(p, s, jnp.float32(1e-4), t + 2.0, *vals)
            return (p, s), loss

        (_, _), losses = jax.lax.scan(
            body, (params, opt_state), jnp.arange(float(iters)))
        return losses

    r = chain(trainer.param_vals, trainer.opt_state)
    _sync(r)
    assert np.isfinite(np.asarray(r)).all()
    best = float("inf")
    for _ in range(best_of):
        t0 = time.perf_counter()
        r = chain(trainer.param_vals, trainer.opt_state)
        _sync(r)
        best = min(best, time.perf_counter() - t0)
    return best


def chained_step_time(trainer, vals, n1=3, n2=13):
    """Slope between two chain depths — the fixed per-dispatch host cost
    cancels."""
    t1 = _chain_total(trainer, vals, n1)
    t2 = _chain_total(trainer, vals, n2)
    return (t2 - t1) / (n2 - n1)


def build_lm(impl, seq=2048, batch=4):
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import bert_sharding_rules, transformer_lm

    os.environ["MXNET_ATTENTION_IMPL"] = impl
    mx.random.seed(0)
    vocab = 32000
    net = transformer_lm(vocab_size=vocab, max_length=seq, num_layers=12,
                         units=768, hidden_size=3072, dropout=0.0)
    net.initialize()
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = par.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = par.ShardedTrainer(net, loss_fn, mesh,
                                 rules=bert_sharding_rules(),
                                 optimizer="adam",
                                 optimizer_params={"learning_rate": 1e-4},
                                 compute_dtype="bfloat16")
    rng = np.random.RandomState(0)
    x = nd.array(rng.randint(0, vocab, (batch, seq)).astype(np.int32))
    net(x)
    trainer.step(x, x)  # builds _raw_step_fn + resolves shapes
    vals = [jax.device_put(x._data, trainer._in_sh),
            jax.device_put(x._data, trainer._label_sh)]
    return trainer, vals


def main():
    from mxnet_tpu import platform as mxplatform

    mxplatform.devices_or_exit(what="tools/profile_lm_step.py")
    out = {}
    seq = int(os.environ.get("PROF_SEQ", 2048))
    batch = int(os.environ.get("PROF_BATCH", 4))
    for impl in sys.argv[1:] or ["flash", "plain"]:
        trainer, vals = build_lm(impl, seq=seq, batch=batch)
        dt = chained_step_time(trainer, vals)
        toks = batch * seq
        out[impl] = {"chained_ms_per_step": round(dt * 1e3, 2),
                     "tokens_per_sec": round(toks / dt, 0)}
        os.environ.pop("MXNET_ATTENTION_IMPL", None)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()

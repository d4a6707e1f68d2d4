"""On-chip breakdown of the ResNet-50 fp32 train step (VERDICT r4 item 7:
the headline sits ~6% under the ~683 img/s honest ceiling — itemize it).

Components are slope-timed (tools/_chiptime.py) so the fixed per-dispatch
host cost cancels. Prints JSON; PROF_JSON=path writes the
artifact. Run on an IDLE host — concurrent CPU load corrupts slope timing.
"""
from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools._chiptime import slope_time  # noqa: E402


def main():
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu import parallel as par
    from mxnet_tpu import platform as mxplatform
    from mxnet_tpu.gluon.model_zoo import get_model

    mxplatform.devices_or_exit(what="tools/profile_resnet.py")
    batch = int(os.environ.get("PROF_BATCH", 64))
    size = int(os.environ.get("PROF_SIZE", 224))
    out = {"batch": batch, "size": size}

    mx.random.seed(0)
    net = get_model("resnet50_v1", classes=1000)
    net.initialize()
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = par.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = par.ShardedTrainer(
        net, loss_fn, mesh, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                          "wd": 1e-4})
    rng = np.random.RandomState(0)
    xh = rng.rand(batch, 3, size, size).astype(np.float32)
    yh = rng.randint(0, 1000, batch).astype(np.int32)
    x = nd.array(xh)
    y = nd.array(yh)
    net(x)
    trainer.step(x, y)  # builds _raw_step_fn
    raw = trainer._raw_step_fn
    xv = jax.device_put(x._data, trainer._in_sh)
    yv = jax.device_put(y._data, trainer._label_sh)
    params0 = trainer.param_vals
    opt0 = trainer.opt_state

    def rep(name, step, carry0, n1=3, n2=9):
        t = slope_time(step, carry0, n1, n2)
        out[f"{name}_ms"] = round(t * 1e3, 2)
        print(f"  {name}: {out[f'{name}_ms']} ms", file=sys.stderr)
        return t

    # 1. the full train step (fwd+bwd+sgd update), chained on params
    def full_step(carry):
        p, s = carry
        _, p2, s2 = raw(p, s, jnp.float32(0.1), jnp.float32(1.0), xv, yv)
        return (p2, s2)

    t_full = rep("full_step", full_step, (params0, opt0))
    out["full_step_ips"] = round(batch / t_full, 1)

    # 2. optimizer-update-only: rerun the update math on fixed grads by
    #    differencing a step that skips it is impossible from outside, so
    #    approximate with a pure SGD+momentum+wd update over same-sized
    #    buffers (reads 3x + writes 2x of ~102 MB fp32 params)
    leaves = jax.tree_util.tree_leaves(params0)
    nbytes = sum(x_.size * x_.dtype.itemsize for x_ in leaves)
    out["param_mb"] = round(nbytes / 1e6, 1)

    # real update traffic: grads + momentum live in the CARRY (constants
    # would fold at compile time and under-report bandwidth); per iter:
    # read w+g+m, write w+m — the true SGD+momentum+wd pattern
    def sgd_update(carry):
        ws, gs, ms = carry
        new_m = [0.9 * m + g + 1e-4 * w for w, g, m in zip(ws, gs, ms)]
        new_w = [w - 0.1 * m for w, m in zip(ws, new_m)]
        # grads pass through UNCHANGED: still read each iteration (they
        # feed new_m), but no third write — real SGD+momentum traffic is
        # read w+g+m, write w+m
        return (new_w, gs, new_m)

    carry0 = (list(leaves),
              [jnp.full_like(l_, 1e-4) for l_ in leaves],
              [jnp.zeros_like(l_) for l_ in leaves])
    rep("sgd_update_approx", sgd_update, carry0, 4, 16)

    # 3. reconciliation: bench.py times per-dispatch wall clock (30 steps
    #    per sync); full_step here is the pure device time. The difference
    #    is host dispatch + the amortized fixed cost of each sync — i.e.
    #    the residual between the 643 img/s headline and the chained
    #    ceiling is expected to be dispatch, not device work.
    out["bench_equivalent_ips_at_3ms_dispatch"] = round(
        batch / (t_full + 0.003), 1)
    out["note"] = ("full_step is the chained device-only step; bench.py's "
                   "per-step dispatch adds host-side overhead amortized "
                   "over 30 steps/sync (~3 ms/step fixed cost)")
    print(json.dumps(out, indent=1))
    artifact = os.environ.get("PROF_JSON")
    if artifact:
        with open(artifact, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()

"""ShardedTrainer — the whole training step as ONE sharded XLA program.

Replaces the reference's eager loop + KVStore gradient push/pull
(SURVEY.md §3.2): forward, backward, cross-replica gradient reduction,
and the fused optimizer update all live inside a single ``jax.jit`` over a
device Mesh. Gradient all-reduce over the ``dp`` axis is not a library
call — it falls out of sharding propagation (params replicated over dp,
batch sharded over dp ⇒ XLA inserts psum on the ICI). Tensor-parallel
params shard over ``tp`` by rule table; buffers are donated so weights
update in place in HBM.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ndarray import NDArray
from .functional import functionalize
from .sharding import ShardingRules, batch_sharding

__all__ = ["ShardedTrainer"]

_SUPPORTED = ("sgd", "adam", "adamw")


class ShardedTrainer:
    """Train a gluon Block over a mesh with dp/tp(/sp) shardings.

    Usage::

        mesh = parallel.make_mesh({"dp": 4, "tp": 2})
        trainer = parallel.ShardedTrainer(net, loss_fn, mesh,
                                          rules=net.sharding_rules(),
                                          optimizer="adam",
                                          optimizer_params={"learning_rate": 1e-4})
        for x, y in loader:
            loss = trainer.step(x, y)     # one fused XLA program
        trainer.sync_to_net()             # write weights back for save/eval
    """

    def __init__(self, net, loss_fn, mesh: Mesh, rules: Optional[ShardingRules] = None,
                 optimizer: str = "sgd", optimizer_params: Optional[Dict] = None,
                 input_specs=P("dp"), label_specs=P("dp"), grad_clip: float = -1.0,
                 donate: bool = True, compute_dtype=None,
                 preprocess: Optional[Callable] = None, remat: bool = False,
                 grad_accum: int = 1):
        if optimizer not in _SUPPORTED:
            raise ValueError(f"optimizer {optimizer!r} not in {_SUPPORTED}")
        self.net = net
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.rules = rules or ShardingRules()
        opt = dict(optimizer_params or {})
        self._lr = float(opt.pop("learning_rate", opt.pop("lr", 0.01)))
        self._opt_name = optimizer
        self._opt = opt
        self._grad_clip = grad_clip
        # the per-param update math is the fused engine's lowering
        # (optimizer/fused.py) applied to an Optimizer instance — the sharded
        # and eager/Trainer paths share one implementation and cannot diverge
        from ..optimizer import create as _opt_create

        self._opt_obj = _opt_create(
            optimizer, learning_rate=self._lr,
            clip_gradient=(grad_clip if grad_clip and grad_clip > 0 else None),
            **{k: v for k, v in opt.items() if k != "lr"})
        self._donate = donate
        # AMP: fwd/bwd in compute_dtype (bf16 on the MXU), fp32 master
        # weights + optimizer state. No loss scaling — bf16's exponent range
        # matches fp32 (amp.py documents the same policy).
        self._compute_dtype = (jnp.dtype(compute_dtype)
                               if compute_dtype is not None else None)
        # Traced into the step program, applied to each input before the AMP
        # cast — the fusion point for input normalization when the data
        # pipeline ships raw uint8 (ImageRecordIter(dtype="uint8")): the
        # (x-mean)/std math rides the first conv's HBM read for free instead
        # of burning host CPU + 4x host→device bandwidth.
        self._preprocess = preprocess
        # Rematerialization (jax.checkpoint over the whole forward, matmul
        # results saved): trades recompute FLOPs for activation memory —
        # the long-context lever for sequences whose activations don't fit
        # (and for compile-side buffer pressure). Reference counterpart:
        # mxnet memonger / mirror mode (TBV).
        self._remat = bool(remat)
        # Gradient accumulation: the global batch splits into `grad_accum`
        # micro-batches scanned inside ONE jitted step (grads averaged, one
        # optimizer update). The activation/compile footprint is that of a
        # single micro-batch — the fallback for configs whose full-batch
        # program crashes the compiler (bench seq-4096) or exceeds HBM.
        # BatchNorm-style aux stats keep the LAST micro-batch's update.
        self._grad_accum = int(grad_accum)
        if self._grad_accum < 1:
            raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")

        self._t = 0
        self._in_sh = batch_sharding(mesh, input_specs if isinstance(input_specs, P)
                                     else P(*input_specs))
        self._label_sh = batch_sharding(mesh, label_specs if isinstance(label_specs, P)
                                        else P(*label_specs))
        self._step_fn = None
        self._captured = False
        self._params = {}
        self._grad_names = []
        self.param_vals = {}
        self._param_shardings = {}
        self.opt_state = {}
        # Deferred-shape params (BatchNorm with in_channels=0 etc.) are still
        # None here; capture must wait until the first step resolves shapes —
        # capturing early would silently freeze those params out of training.
        if not any(p._data is None for p in net._iter_params()):
            self._capture()

    def _capture(self):
        """Snapshot the (now fully materialized) parameter set into sharded
        device values + optimizer state. Runs once, at construction when all
        shapes are known, else at the first step()."""
        net, mesh = self.net, self.mesh
        self._params = {p.name: p for p in net._iter_params() if p._data is not None}
        self._grad_names = [n for n, p in self._params.items() if p.grad_req != "null"]
        names, self._apply = functionalize(net, train=True)
        self._names = names

        # place parameter values per the rule table
        self.param_vals = {}
        self._param_shardings = {}
        for n, p in self._params.items():
            sh = self.rules.sharding_for(n, mesh, p.data().shape)
            self._param_shardings[n] = sh
            val = p.data()._data
            if self._donate:
                # donation consumes the step's param inputs, and a no-op
                # device_put ALIASES val with the gluon parameter's own
                # buffer — step 1 would then delete the parameter under
                # gluon's feet (net() after step() raised "Array has been
                # deleted"). A private copy keeps the donated generation
                # exclusively the trainer's; sync_to_net() still writes
                # trained weights back.
                val = jnp.array(val, copy=True)
            self.param_vals[n] = jax.device_put(val, sh)
        self.opt_state = {n: self._init_state(self.param_vals[n])
                          for n in self._grad_names}
        self._captured = True

    # ------------------------------------------------------------------
    def _init_state(self, val):
        zeros = lambda: jnp.zeros_like(val)  # noqa: E731
        if self._opt_name == "sgd":
            if self._opt.get("momentum", 0.0):
                return (zeros(),)
            return ()
        return (zeros(), zeros())  # adam/adamw mean, var

    def _update_one(self, w, g, state, lr, t):
        from ..optimizer.fused import lower_update

        o = self._opt
        # map the sharded state tuples onto the Updater slot layout the
        # lowering expects: sgd () -> None, sgd-momentum (m,) -> m
        if self._opt_name == "sgd":
            st = state[0] if state else None
        else:
            st = state
        new_w, new_st, _ = lower_update(
            self._opt_obj, w, g, st, lr=lr, wd=o.get("wd", 0.0), t=t,
            rescale=o.get("rescale_grad", 1.0))
        if self._opt_name == "sgd":
            return new_w, (() if new_st is None else (new_st,))
        return new_w, new_st

    # ------------------------------------------------------------------
    def _build(self, n_extra_inputs):
        grad_names = self._grad_names

        cdt = self._compute_dtype
        # AMP policy (reference contrib/amp: FP32 op list keeps norms' stats):
        # cast trainable weights + inputs to the compute dtype; statistics
        # buffers (grad_req="null" — BN running mean/var) keep the master
        # dtype so moving averages don't accumulate bf16 rounding.
        stat_names = {n for n, p in self._params.items() if p.grad_req == "null"}

        def _cast(x):
            if cdt is not None and jnp.issubdtype(x.dtype, jnp.floating):
                return x.astype(cdt)
            return x

        pre = self._preprocess

        accum = self._grad_accum

        def step_fn(param_vals, opt_state, lr, t, *batch):
            if pre is not None:
                batch = tuple(pre(b) for b in batch[:-1]) + batch[-1:]
            if cdt is not None:
                batch_cast = tuple(_cast(b) for b in batch[:-1]) + batch[-1:]
            else:
                batch_cast = batch

            def loss_f(grad_part, batch_c):
                full = dict(param_vals)
                full.update(grad_part)
                if cdt is not None:
                    full = {k: (v if k in stat_names else _cast(v))
                            for k, v in full.items()}
                out, aux = self._apply(full, *batch_c[:-1])
                outs = out if isinstance(out, tuple) else (out,)
                loss_nd = self.loss_fn(*[NDArray(o) for o in outs],
                                       NDArray(batch_c[-1]))
                loss_val = jnp.mean(loss_nd._data)
                return loss_val, aux

            grad_part = {n: param_vals[n] for n in grad_names}
            loss_f_used = loss_f
            if self._remat:
                # save matmul outputs, recompute the elementwise tail — the
                # standard transformer remat policy
                loss_f_used = jax.checkpoint(
                    loss_f, policy=jax.checkpoint_policies
                    .dots_with_no_batch_dims_saveable)
            if accum > 1:
                for b in batch_cast:
                    if b.shape[0] % accum:
                        raise ValueError(
                            f"grad_accum={accum} does not divide batch "
                            f"dim {b.shape[0]}")
                micro = tuple(
                    b.reshape((accum, b.shape[0] // accum) + b.shape[1:])
                    for b in batch_cast)

                def body(acc, mb):
                    (l_, aux_), g_ = jax.value_and_grad(
                        loss_f_used, has_aux=True)(grad_part, mb)
                    return (jax.tree_util.tree_map(jnp.add, acc, g_),
                            (l_, aux_))

                zero = jax.tree_util.tree_map(jnp.zeros_like, grad_part)
                grads, (losses, auxs) = jax.lax.scan(body, zero, micro)
                grads = jax.tree_util.tree_map(lambda g_: g_ / accum, grads)
                loss = jnp.mean(losses)
                aux = jax.tree_util.tree_map(lambda ys: ys[-1], auxs)
            else:
                (loss, aux), grads = jax.value_and_grad(
                    loss_f_used, has_aux=True)(grad_part, batch_cast)
            new_params = dict(param_vals)
            new_state = {}
            for n in grad_names:
                new_w, st = self._update_one(param_vals[n], grads[n],
                                             opt_state[n], lr, t)
                new_params[n] = new_w.astype(param_vals[n].dtype)
                new_state[n] = st
            # BatchNorm moving stats etc. — keep master dtype under AMP
            new_params.update({k: (v.astype(param_vals[k].dtype)
                                   if k in param_vals else v)
                               for k, v in aux.items()})
            return loss, new_params, new_state

        in_shardings = (
            self._param_shardings,
            {n: tuple(self._param_shardings[n] for _ in self.opt_state[n])
             for n in grad_names},
            None, None,
            *([self._in_sh] * n_extra_inputs),
            self._label_sh,
        )
        out_shardings = (NamedSharding(self.mesh, P()), self._param_shardings,
                         in_shardings[1])
        donate = (0, 1) if self._donate else ()
        return jax.jit(step_fn, in_shardings=in_shardings,
                       out_shardings=out_shardings, donate_argnums=donate)

    # ------------------------------------------------------------------
    def step(self, *batch):
        """batch = (*inputs, labels); returns the (device) loss scalar."""
        if not self._captured:
            if any(p._data is None for p in self.net._iter_params()):
                # resolve deferred shapes with one throwaway eager forward
                # (pause() also switches training mode off for the duration)
                from .. import autograd

                with autograd.pause():
                    ins = [b._data if isinstance(b, NDArray) else jnp.asarray(b)
                           for b in batch[:-1]]
                    if self._preprocess is not None:
                        ins = [self._preprocess(b) for b in ins]
                    self.net(*[NDArray(b) for b in ins])
            self._capture()
        vals = [b._data if isinstance(b, NDArray) else jnp.asarray(b) for b in batch]
        vals = [jax.device_put(v, self._in_sh if i < len(vals) - 1 else self._label_sh)
                for i, v in enumerate(vals)]
        from .mesh import mesh_scope

        if self._step_fn is None:
            self._step_fn = self._build(len(vals) - 1)
        self._t += 1
        with mesh_scope(self.mesh):  # attention layers pick sp/ring impls
            loss, self.param_vals, self.opt_state = self._step_fn(
                self.param_vals, self.opt_state, jnp.float32(self._lr),
                jnp.float32(self._t), *vals)
        return NDArray(loss)

    # ------------------------------------------------------------------
    @property
    def learning_rate(self):
        return self._lr

    def set_learning_rate(self, lr):
        self._lr = float(lr)

    def sync_to_net(self):
        """Copy sharded weights back into the gluon parameters (gathered)."""
        from .. import autograd

        for n, p in self._params.items():
            val = self.param_vals[n]
            gathered = jax.device_get(val)
            with autograd.pause():
                p.data()._set_data(jnp.asarray(gathered))

    def block_until_ready(self):
        jax.block_until_ready(self.param_vals)

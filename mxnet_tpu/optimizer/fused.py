"""Fused update engine — ONE donated XLA program per optimizer step.

The reference MXNet amortizes per-op dispatch with its dependency engine and
hand-fused multi-tensor kernels (``multi_sgd_update`` etc.).  Our TPU mapping
replaces the engine with XLA, but the eager update paths (gluon ``Trainer``,
``Module``'s updater, kvstore local updates) still ran one dispatch per
parameter per step — hundreds of tiny device programs for a ResNet.  This
module lowers every registered optimizer to a pure tree-level transform

    (params, grads, states, lrs, wds, ts, ...) -> (params', states')

compiled as one ``jax.jit`` program with (on accelerators) donated
param/state buffers, and with the cross-parameter work fused in:

- **global-norm gradient clipping** — the concat-norm and the scale are
  computed in-graph, no host round-trip;
- **AMP loss-scaler unscale + nonfinite-skip** — gradients are unscaled,
  the found-inf reduction is computed over all gradients, and the whole
  update is masked with ``where`` on the device flag.  The loss-scale /
  unskipped-step counters also advance in-graph, so a skip step costs zero
  host syncs;
- **LAMB/LARS trust ratios** — per-tensor norms stay in the program.

Per-step hyperparameters (lr after scheduler + multipliers, wd, update
counts, rescale_grad, loss scale) are **traced arguments**, so a scheduler
stepping the lr every iteration does not retrace.  Static hyperparameters
(betas, momentum, clip_gradient, ...) are baked into the program and keyed
into the compile cache; mutating them mid-run recompiles (the TraceLinter's
``update-retrace-churn`` rule flags pathological churn).

The per-parameter eager path is kept behind ``MXNET_FUSED_UPDATE=0`` as the
differential-testing oracle (tests/test_fused_update.py).  Buffer donation
follows ``MXNET_FUSED_DONATE`` (default: on for non-CPU backends — the CPU
PJRT client does not implement donation).  See docs/PERFORMANCE.md.
"""
from __future__ import annotations

import os
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs, progcache
from ..ndarray import NDArray
from ..ops import get_op

__all__ = ["FusedUpdateEngine", "fused_update_enabled", "lower_update",
           "supports"]


def fused_update_enabled() -> bool:
    """The ``MXNET_FUSED_UPDATE`` escape hatch, read per call so tests can
    flip between the engine and the eager oracle without reimporting."""
    return os.environ.get("MXNET_FUSED_UPDATE", "1").lower() not in (
        "0", "false", "no", "off")


def _donate_default() -> bool:
    env = os.environ.get("MXNET_FUSED_DONATE")
    if env is not None:
        return env.lower() not in ("0", "false", "no", "off")
    # CPU PJRT has no donation support — jax would warn per compile
    return jax.default_backend() != "cpu"


def _f(name):
    return get_op(name).fn


# ---------------------------------------------------------------------------
# optimizer-state tree helpers.  Updater slots are nested tuples of NDArrays
# (or None); the engine flattens them to jax leaves and rebuilds in-trace.
# ---------------------------------------------------------------------------

def _state_spec(s):
    if s is None:
        return None
    if isinstance(s, tuple):
        return tuple(_state_spec(x) for x in s)
    return "leaf"


def _state_leaves(s, out: list):
    if s is None:
        return
    if isinstance(s, tuple):
        for x in s:
            _state_leaves(x, out)
    else:
        out.append(s)


def _rebuild_state(spec, it):
    if spec is None:
        return None
    if isinstance(spec, tuple):
        return tuple(_rebuild_state(x, it) for x in spec)
    return next(it)


def _map_state(fn, new, old):
    """Apply fn(new_leaf, old_leaf) through a state structure (skip Nones)."""
    if new is None:
        return None
    if isinstance(new, tuple):
        return tuple(_map_state(fn, n, o) for n, o in zip(new, old))
    return fn(new, old)


def _cast(x, like):
    """Cast a traced f32 scalar to the compute dtype so jax's strong-dtype
    promotion doesn't silently upcast a bf16 update to f32 (eager python
    floats are weakly typed and keep the array dtype)."""
    return x.astype(like.dtype)


# ---------------------------------------------------------------------------
# per-optimizer lowerings.  Each takes the *optimizer instance* (for static
# hyperparameters), traced per-param scalars, and returns
# (new_weight, new_state, extras).  They call the same registered op
# functions the eager path invokes, so fused == oracle numerically.
# ---------------------------------------------------------------------------

_LOWER: Dict[type, object] = {}


def _lower(cls):
    def deco(fn):
        _LOWER[cls] = fn
        return fn
    return deco


def supports(optimizer) -> bool:
    return type(optimizer) in _LOWER


def _sgd_like_kw(opt, w, lr, wd, rescale):
    return dict(lr=_cast(lr, w), wd=_cast(wd, w), rescale_grad=_cast(rescale, w),
                clip_gradient=opt.clip_gradient)


from .optimizer import (SGD, NAG, Adam, AdamW, LAMB, RMSProp, AdaGrad,
                        AdaDelta, Ftrl, FTML, Signum, AdaMax, Nadam, SGLD,
                        DCASGD, LARS)


@_lower(SGD)
def _low_sgd(opt, w, g, st, lr, wd, t, rescale, ex, pos):
    kw = _sgd_like_kw(opt, w, lr, wd, rescale)
    if st is None:
        return _f("sgd_update")(w, g, **kw), None, ex
    nw, nm = _f("sgd_mom_update")(w, g, st, momentum=opt.momentum, **kw)
    return nw, nm, ex


@_lower(NAG)
def _low_nag(opt, w, g, st, lr, wd, t, rescale, ex, pos):
    nw, nm = _f("nag_mom_update")(w, g, st, momentum=opt.momentum,
                                  **_sgd_like_kw(opt, w, lr, wd, rescale))
    return nw, nm, ex


@_lower(Adam)
def _low_adam(opt, w, g, st, lr, wd, t, rescale, ex, pos):
    coef = jnp.sqrt(1.0 - opt.beta2 ** t) / (1.0 - opt.beta1 ** t)
    m, v = st
    nw, nm, nv = _f("adam_update")(
        w, g, m, v, lr=_cast(lr * coef, w), beta1=opt.beta1, beta2=opt.beta2,
        epsilon=opt.epsilon, wd=_cast(wd, w), rescale_grad=_cast(rescale, w),
        clip_gradient=opt.clip_gradient)
    return nw, (nm, nv), ex


@_lower(AdamW)
def _low_adamw(opt, w, g, st, lr, wd, t, rescale, ex, pos):
    coef = jnp.sqrt(1.0 - opt.beta2 ** t) / (1.0 - opt.beta1 ** t)
    m, v = st
    nw, nm, nv = _f("adamw_update")(
        w, g, m, v, lr=_cast(lr * coef, w), beta1=opt.beta1, beta2=opt.beta2,
        epsilon=opt.epsilon, wd=_cast(wd, w), eta=1.0,
        rescale_grad=_cast(rescale, w), clip_gradient=opt.clip_gradient)
    return nw, (nm, nv), ex


@_lower(LAMB)
def _low_lamb(opt, w, g, st, lr, wd, t, rescale, ex, pos):
    m, v = st
    gd = _f("lamb_update_phase1")(
        w, g, m, v, beta1=opt.beta1, beta2=opt.beta2, epsilon=opt.epsilon,
        t=t, bias_correction=opt.bias_correction, wd=_cast(wd, w),
        rescale_grad=_cast(rescale, w), clip_gradient=opt.clip_gradient)
    gr = g * _cast(rescale, g)
    nm = opt.beta1 * m + (1 - opt.beta1) * gr
    nv = opt.beta2 * v + (1 - opt.beta2) * jnp.square(gr)
    r1 = _f("norm")(w)
    r2 = _f("norm")(gd)
    nw = _f("lamb_update_phase2")(w, gd, r1, r2, lr=_cast(lr, w),
                                  lower_bound=opt.lower_bound,
                                  upper_bound=opt.upper_bound)
    return nw, (nm, nv), ex


@_lower(RMSProp)
def _low_rmsprop(opt, w, g, st, lr, wd, t, rescale, ex, pos):
    base = dict(lr=_cast(lr, w), wd=_cast(wd, w), gamma1=opt.gamma1,
                epsilon=opt.epsilon, rescale_grad=_cast(rescale, w),
                clip_gradient=opt.clip_gradient, clip_weights=opt.clip_weights)
    if opt.centered:
        n, g_, delta = st
        nw, nn, ng, nd = _f("rmspropalex_update")(w, g, n, g_, delta,
                                                  gamma2=opt.gamma2, **base)
        return nw, (nn, ng, nd), ex
    (n,) = st
    nw, nn = _f("rmsprop_update")(w, g, n, **base)
    return nw, (nn,), ex


@_lower(AdaGrad)
def _low_adagrad(opt, w, g, st, lr, wd, t, rescale, ex, pos):
    nw, nh = _f("adagrad_update")(w, g, st, lr=_cast(lr, w), wd=_cast(wd, w),
                                  epsilon=opt.float_stable_eps,
                                  rescale_grad=_cast(rescale, w),
                                  clip_gradient=opt.clip_gradient)
    return nw, nh, ex


@_lower(AdaDelta)
def _low_adadelta(opt, w, g, st, lr, wd, t, rescale, ex, pos):
    acc_g, acc_d = st
    nw, ng, nd = _f("adadelta_update")(w, g, acc_g, acc_d, rho=opt.rho,
                                       epsilon=opt.epsilon, wd=_cast(wd, w),
                                       rescale_grad=_cast(rescale, w),
                                       clip_gradient=opt.clip_gradient)
    return nw, (ng, nd), ex


@_lower(Ftrl)
def _low_ftrl(opt, w, g, st, lr, wd, t, rescale, ex, pos):
    z, n = st
    nw, nz, nn = _f("ftrl_update")(w, g, z, n, lr=_cast(lr, w),
                                   lamda1=opt.lamda1, beta=opt.beta,
                                   wd=_cast(wd, w),
                                   rescale_grad=_cast(rescale, w),
                                   clip_gradient=opt.clip_gradient)
    return nw, (nz, nn), ex


@_lower(FTML)
def _low_ftml(opt, w, g, st, lr, wd, t, rescale, ex, pos):
    d, v, z = st
    nw, nd, nv, nz = _f("ftml_update")(w, g, d, v, z, lr=_cast(lr, w),
                                       beta1=opt.beta1, beta2=opt.beta2,
                                       epsilon=opt.epsilon, t=t,
                                       wd=_cast(wd, w),
                                       rescale_grad=_cast(rescale, w),
                                       clip_grad=opt.clip_gradient)
    return nw, (nd, nv, nz), ex


@_lower(Signum)
def _low_signum(opt, w, g, st, lr, wd, t, rescale, ex, pos):
    kw = _sgd_like_kw(opt, w, lr, wd, rescale)
    if st is None:
        return _f("signsgd_update")(w, g, **kw), None, ex
    nw, nm = _f("signum_update")(w, g, st, momentum=opt.momentum,
                                 wd_lh=opt.wd_lh, **kw)
    return nw, nm, ex


@_lower(AdaMax)
def _low_adamax(opt, w, g, st, lr, wd, t, rescale, ex, pos):
    m, u = st
    nw, nm, nu = _f("adamax_update")(w, g, m, u, lr=_cast(lr, w),
                                     beta1=opt.beta1, beta2=opt.beta2,
                                     wd=_cast(wd, w), t=t,
                                     rescale_grad=_cast(rescale, w),
                                     clip_gradient=opt.clip_gradient)
    return nw, (nm, nu), ex


@_lower(Nadam)
def _low_nadam(opt, w, g, st, lr, wd, t, rescale, ex, pos):
    m, v = st
    ms = ex["m_schedule"]
    nw, nm, nv = _f("nadam_update")(
        w, g, m, v, lr=_cast(lr, w), beta1=opt.beta1, beta2=opt.beta2,
        epsilon=opt.epsilon, wd=_cast(wd, w), t=t,
        schedule_decay=opt.schedule_decay, m_schedule=ms,
        rescale_grad=_cast(rescale, w), clip_gradient=opt.clip_gradient)
    # the eager path multiplies m_schedule once per *parameter* update — keep
    # that exact (quirky, reference-matching) sequence through the loop
    momentum_t = opt.beta1 * (1 - 0.5 * 0.96 ** (t * opt.schedule_decay))
    ex = dict(ex, m_schedule=ms * momentum_t)
    return nw, (nm, nv), ex


@_lower(SGLD)
def _low_sgld(opt, w, g, st, lr, wd, t, rescale, ex, pos):
    from ..ops.optimizer_ops import _grad_prep

    g2 = _grad_prep(g, _cast(wd, w), w, _cast(rescale, w), opt.clip_gradient)
    noise = jax.random.normal(ex["keys"][pos], w.shape, w.dtype) \
        * jnp.sqrt(jnp.asarray(lr, w.dtype))
    return w - 0.5 * _cast(lr, w) * g2 + noise, None, ex


@_lower(DCASGD)
def _low_dcasgd(opt, w, g, st, lr, wd, t, rescale, ex, pos):
    mom, prev = st
    nw, nm, nprev = _f("dcasgd_update")(w, g, mom, prev, lr=_cast(lr, w),
                                        momentum=opt.momentum, lamda=opt.lamda,
                                        wd=_cast(wd, w),
                                        rescale_grad=_cast(rescale, w),
                                        clip_gradient=opt.clip_gradient)
    return nw, (nm, nprev), ex


@_lower(LARS)
def _low_lars(opt, w, g, st, lr, wd, t, rescale, ex, pos):
    nw, nm = _f("lars_update")(w, g, st, lr=_cast(lr, w), momentum=opt.momentum,
                               eta=opt.eta, epsilon=opt.epsilon,
                               wd=_cast(wd, w), rescale_grad=_cast(rescale, w),
                               clip_gradient=opt.clip_gradient)
    return nw, nm, ex


# ---------------------------------------------------------------------------
# optimizer-global "extras": device scalars threaded through the per-param
# loop (Nadam's momentum schedule) or per-step inputs (SGLD's noise keys,
# pre-drawn from the SAME global stream the eager ops consume).
# ---------------------------------------------------------------------------

def _extras_prep(opt, n):
    if isinstance(opt, Nadam):
        ms = opt.m_schedule
        val = ms._data if isinstance(ms, NDArray) else jnp.float32(ms)
        return {"m_schedule": val}
    if isinstance(opt, SGLD):
        from .. import random as _random

        return {"keys": jnp.stack([_random.next_key() for _ in range(n)])}
    return {}


def _extras_finalize(opt, ex):
    if isinstance(opt, Nadam) and "m_schedule" in ex:
        # device-resident; checkpoint capture float()s it at save time only
        opt.m_schedule = NDArray(ex["m_schedule"])


def lower_update(opt, w, g, state, lr, wd=0.0, t=1, rescale=1.0, extras=None,
                 pos=0):
    """Apply one parameter's update as pure jax — the building block shared
    by the engine and parallel.ShardedTrainer (so the two can't diverge).
    ``state`` uses the eager Updater layout (None / array / tuple)."""
    fn = _LOWER.get(type(opt))
    if fn is None:
        raise NotImplementedError(
            f"no fused lowering for {type(opt).__name__}")
    to32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    ex = _extras_prep(opt, pos + 1) if extras is None else extras
    return fn(opt, w, g, state, to32(lr), to32(wd), to32(t), to32(rescale),
              ex, pos)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class FusedUpdateEngine:
    """Compiles and dispatches the one-program-per-step update.

    One engine per :class:`Updater`; the compile cache is keyed on the static
    parts of the update (optimizer class + scalar hyperparameters, state
    structure, array avals, scaler/clip toggles) while per-step scalars are
    traced.  ``compile_log`` records one entry per compilation for the
    TraceLinter's churn diagnosis; ``exec_count`` counts dispatches.
    """

    def __init__(self, optimizer, donate: Optional[bool] = None):
        self.optimizer = optimizer
        self._cache: Dict = {}
        self._donate = _donate_default() if donate is None else bool(donate)
        self.exec_count = 0
        self.compile_log: List[dict] = []
        # training-health plane (obs/health.py): when active, the step
        # program also emits device-resident numerics stats; both stay
        # device-side (zero syncs) until a sampled step batch-fetches them
        self.last_health: Optional[dict] = None
        self._skip_streak = np.int32(0)  # AMP consecutive-skip counter

    # -- keys --------------------------------------------------------------
    _TRACED_ATTRS = frozenset({"lr", "rescale_grad", "num_update",
                               "begin_num_update", "m_schedule", "wd",
                               "multi_precision"})

    def _static_key(self):
        opt = self.optimizer
        return tuple(sorted(
            (k, v) for k, v in opt.__dict__.items()
            if k not in self._TRACED_ATTRS and isinstance(v, (int, float, bool, str))))

    @staticmethod
    def _aval(x):
        return (tuple(x.shape), str(x.dtype))

    def supported(self) -> bool:
        return type(self.optimizer) in _LOWER

    # -- dispatch ----------------------------------------------------------
    def apply(self, indices, weights, grads, states, loss_scaler=None,
              clip_global_norm=None):
        """Run one fused update step over the given parameter set.

        ``weights``/``grads``/``states`` are parallel lists; states use the
        Updater slot layout and are updated in place (``_set_data`` rebinds
        the NDArray wrappers onto the program's outputs, so the optimizer
        state stays device-resident between steps).
        """
        opt = self.optimizer
        if not self.supported():
            raise NotImplementedError(
                f"no fused lowering for {type(opt).__name__}")
        n = len(indices)
        # host bookkeeping — identical order to the eager _common() sequence
        for i in indices:
            opt._update_count(i)
        lrs = np.asarray([opt._get_lr(i) for i in indices], np.float32)
        wds = np.asarray([opt._get_wd(i) for i in indices], np.float32)
        ts = np.asarray([opt._index_update_count[i] for i in indices],
                        np.float32)
        rescale = np.float32(opt.rescale_grad)

        mp = tuple(bool(opt._use_mp(w)) for w in weights)
        specs = tuple(_state_spec(s) for s in states)
        ws = tuple(w._data for w in weights)
        gs = tuple(g._data for g in grads)
        state_leaves = []
        for s in states:
            lv: list = []
            _state_leaves(s, lv)
            state_leaves.append(tuple(x._data for x in lv))
        state_leaves = tuple(state_leaves)

        scaler_on = loss_scaler is not None
        cgn_on = clip_global_norm is not None and clip_global_norm > 0
        if scaler_on:
            sc = loss_scaler.loss_scale
            scale = sc._data if isinstance(sc, NDArray) else np.float32(sc)
            un = getattr(loss_scaler, "_unskipped", 0)
            unskipped = un._data if isinstance(un, NDArray) else np.int32(un)
            factor = float(loss_scaler._factor)
            window = int(loss_scaler._window)
        else:
            scale, unskipped, factor, window = np.float32(1), np.int32(0), 2.0, 0
        cgn_val = np.float32(clip_global_norm if cgn_on else 0.0)
        extras = _extras_prep(opt, n)
        # health stats are part of the program (extra outputs, zero extra
        # dispatches) — the flag is a compile static, so a monitor-gated
        # loop alternates between exactly TWO cached variants (stats on
        # sampled steps, plain otherwise; updates bitwise-identical)
        health_on = obs.health.stats_for_this_step()
        streak_in = self._skip_streak if scaler_on else np.int32(0)

        key = (type(opt), self._static_key(), specs, mp,
               tuple(self._aval(x) for x in ws),
               tuple(self._aval(x) for x in gs),
               tuple(tuple(self._aval(x) for x in lp) for lp in state_leaves),
               scaler_on, factor, window, cgn_on, health_on, self._donate)

        rec = obs.enabled()
        t0 = time.monotonic() if rec else 0.0
        jitted = self._cache.get(key)
        is_compile = jitted is None
        if is_compile:
            example = (ws, gs, state_leaves, lrs, wds, ts, rescale,
                       scale, unskipped, streak_in, cgn_val, extras)
            jitted, entry = self._compile(key, example)
            self._cache[key] = jitted
            self.compile_log.append(entry)
            # telemetry: every compile counts; a compile AFTER the first is
            # a retrace (something static churned — the TraceLinter's
            # update-retrace-churn rule diagnoses which component)
            obs.inc("update.cache_hit" if entry.get("cache_hit")
                    else "update.compile")
            if len(self.compile_log) > 1:
                obs.inc("update.retrace")

        from .. import profiler

        if profiler.counting_dispatches():
            profiler.count_dispatch("compiled")
            profiler.count_dispatch("h2d")  # the packed lr/wd/t hyper vectors
        with obs.trace.span("update.fused", optimizer=type(opt).__name__,
                            n_params=n, compile=is_compile):
            new_ws, new_flat, new_ex, scaler_out, health_out = jitted(
                ws, gs, state_leaves, lrs, wds, ts, rescale, scale, unskipped,
                streak_in, cgn_val, extras)
        if rec:
            # first call traces+compiles (blocking); later calls dispatch —
            # host wall time only
            obs.observe("update.compile_seconds" if is_compile
                        else "update.execute_seconds",
                        time.monotonic() - t0)
        self.exec_count += 1

        for w, nw in zip(weights, new_ws):
            w._set_data(nw)
        for s, leaves_new in zip(states, new_flat):
            old: list = []
            _state_leaves(s, old)
            for nd, nv in zip(old, leaves_new):
                nd._set_data(nv)
        _extras_finalize(opt, new_ex)
        if scaler_on:
            found, nsc, nun, nstreak = scaler_out
            loss_scaler.loss_scale = NDArray(nsc)
            loss_scaler._unskipped = NDArray(nun)
            loss_scaler.last_overflow = NDArray(found)  # device flag, no sync
            # consecutive-skip streak, maintained in-graph: the silent AMP
            # skip-loop (counters advance on skip) finally has a signal —
            # obs/health.py samples it and breaches on a long streak
            loss_scaler.skip_streak = NDArray(nstreak)
            self._skip_streak = nstreak
        if health_out is not None:
            g_all, g_norms, w_norms, u_norms, nonfin = health_out
            self.last_health = {
                "global_grad_norm": g_all, "grad_norms": g_norms,
                "param_norms": w_norms, "update_norms": u_norms,
                "nonfinite": nonfin, "indices": tuple(indices)}
            if scaler_on:
                self.last_health["found_inf"] = scaler_out[0]
                self.last_health["skip_streak"] = scaler_out[3]
        else:
            self.last_health = None

    # -- persistent program cache -----------------------------------------
    def _program_key(self, key):
        """The fused step's :class:`~mxnet_tpu.progcache.ProgramKey` —
        the in-process memo ``key`` canonicalized through the ONE shared
        derivation (``progcache.program_key``), so the device-plane cost
        registry, this engine's ``compile_log``, and the persistent cache
        agree on the program's identity byte for byte."""
        return progcache.program_key("update", type(self.optimizer).__name__,
                                     key)

    def _compile(self, key, example):
        """Resolve one cache-key miss to an executable + its compile_log
        entry. With the persistent cache armed or ``obs`` on the step goes
        through ``progcache.build`` (a hit deserializes the stored
        executable — zero fresh XLA work; a corrupt/stale/foreign entry was
        already counted as a reject by the cache and lands as a miss);
        otherwise it stays the plain ``jax.jit`` wrapper."""
        opt = self.optimizer
        (_, _, specs, mp, _, _, _, scaler_on, factor, window, cgn_on,
         health_on, _) = key
        entry = {
            "optimizer": type(opt).__name__,
            "static": self._static_key(),
            "avals": key[4],
            "state_structure": specs,
            "flags": (scaler_on, cgn_on, health_on),
            "cache_hit": False,
        }
        jitted = self._build(specs, mp, scaler_on, factor, window, cgn_on,
                             health_on)
        pc = progcache.cache()
        if pc is not None or obs.enabled():
            # ONE compile serves accounting and execution: the executable
            # replaces the jit wrapper in the cache, and its XLA cost and
            # memory analyses land in this compile_log entry
            jitted, built = progcache.build(
                jitted, example, key=self._program_key(key), cache=pc)
            entry.update(built)
        return jitted, entry

    def prewarm(self, indices, weights, grads, states, loss_scaler=None,
                clip_global_norm=None) -> bool:
        """Populate the compile cache for the step ``apply`` would run on
        these tensors — WITHOUT executing it or touching optimizer
        counters. The elastic-rejoin path calls this while quarantined so
        the compile/deserialize overlaps the wait for the activation
        boundary instead of stalling the fleet's first lockstep reduce.
        Returns True when the program is now cached (either source)."""
        opt = self.optimizer
        if not self.supported():
            return False
        n = len(indices)
        # example traced scalars only — values never shape the program
        lrs = np.zeros(n, np.float32)
        wds = np.zeros(n, np.float32)
        ts = np.ones(n, np.float32)
        rescale = np.float32(opt.rescale_grad)
        mp = tuple(bool(opt._use_mp(w)) for w in weights)
        specs = tuple(_state_spec(s) for s in states)
        ws = tuple(w._data for w in weights)
        gs = tuple(g._data for g in grads)
        state_leaves = []
        for s in states:
            lv: list = []
            _state_leaves(s, lv)
            state_leaves.append(tuple(x._data for x in lv))
        state_leaves = tuple(state_leaves)
        scaler_on = loss_scaler is not None
        cgn_on = clip_global_norm is not None and clip_global_norm > 0
        if scaler_on:
            factor = float(loss_scaler._factor)
            window = int(loss_scaler._window)
        else:
            factor, window = 2.0, 0
        health_on = obs.health.stats_for_this_step()
        key = (type(opt), self._static_key(), specs, mp,
               tuple(self._aval(x) for x in ws),
               tuple(self._aval(x) for x in gs),
               tuple(tuple(self._aval(x) for x in lp) for lp in state_leaves),
               scaler_on, factor, window, cgn_on, health_on, self._donate)
        if key in self._cache:
            return True
        example = (ws, gs, state_leaves, lrs, wds, ts, rescale,
                   np.float32(1), np.int32(0), np.int32(0),
                   np.float32(clip_global_norm if cgn_on else 0.0),
                   _extras_prep(opt, n))
        jitted, entry = self._compile(key, example)
        self._cache[key] = jitted
        self.compile_log.append(entry)
        obs.event("progcache.prewarm", optimizer=type(opt).__name__,
                  cache_hit=bool(entry.get("cache_hit")))
        return True

    # -- compile -----------------------------------------------------------
    def _build(self, specs, mp, scaler_on, factor, window, cgn_on,
               health_on=False):
        opt = self.optimizer
        lowering = _LOWER[type(opt)]

        def step(ws, gs, state_leaves, lrs, wds, ts, rescale, scale,
                 unskipped, streak, cgn, extras):
            gs = list(gs)
            found = jnp.zeros((), jnp.bool_)
            if scaler_on:
                inv = 1.0 / scale
                gs = [g * inv.astype(g.dtype) for g in gs]
            nonfin = None
            if health_on:
                # per-grad non-finite counts; the scaler's found-inf
                # reduction is their OR — one pass serves both signals
                nonfin = [jnp.sum(
                    (~jnp.isfinite(g.astype(jnp.float32))).astype(jnp.int32))
                    for g in gs]
                if scaler_on:
                    for c in nonfin:
                        found = found | (c > 0)
            elif scaler_on:
                for g in gs:
                    found = found | ~jnp.all(jnp.isfinite(
                        g.astype(jnp.float32)))
            gsqs, gnorm = None, None
            if cgn_on or health_on:
                # ONE reduction serves clipping AND the health plane's
                # global/per-param grad norms (pre-clip, post-unscale —
                # the raw explosion signal)
                gsqs = [jnp.sum(jnp.square(g.astype(jnp.float32) * rescale))
                        for g in gs]
                sq = jnp.float32(0.0)
                for s in gsqs:
                    sq = sq + s
                gnorm = jnp.sqrt(sq)
            if cgn_on:
                coef = jnp.minimum(jnp.float32(1.0), cgn / (gnorm + 1e-6))
                gs = [g * coef.astype(g.dtype) for g in gs]

            new_ws, new_states = [], []
            ex = extras
            for pos in range(len(ws)):
                w, g = ws[pos], gs[pos]
                st = _rebuild_state(specs[pos], iter(state_leaves[pos]))
                lr_i, wd_i, t_i = lrs[pos], wds[pos], ts[pos]
                if mp[pos]:
                    inner, w32 = st
                    nw32, ninner, ex = lowering(opt, w32, g.astype(jnp.float32),
                                                inner, lr_i, wd_i, t_i,
                                                rescale, ex, pos)
                    nw = nw32.astype(w.dtype)
                    nstate = (ninner, nw32)
                else:
                    nw, nstate, ex = lowering(opt, w, g, st, lr_i, wd_i, t_i,
                                              rescale, ex, pos)
                    nw = nw.astype(w.dtype)
                new_ws.append(nw)
                new_states.append(nstate)

            if scaler_on:
                # nonfinite grads: keep params/states, shrink the scale — all
                # selected on-device, zero host round-trips
                sel = lambda new, old: jnp.where(found, old, new)  # noqa: E731
                new_ws = [sel(nw, w) for nw, w in zip(new_ws, ws)]
                new_states = [
                    _map_state(sel, ns,
                               _rebuild_state(specs[i],
                                              iter(state_leaves[i])))
                    for i, ns in enumerate(new_states)]
                ex = {k: (sel(v, extras[k]) if k != "keys" else v)
                      for k, v in ex.items()}
                nskip = unskipped + 1
                grow = nskip >= window
                new_scale = jnp.where(
                    found, jnp.maximum(scale / factor, 1e-4),
                    jnp.where(grow, jnp.minimum(scale * factor, 2.0 ** 24),
                              scale))
                new_unskipped = jnp.where(found | grow, 0, nskip).astype(
                    jnp.asarray(unskipped).dtype)
                new_streak = jnp.where(found, streak + 1, 0).astype(
                    jnp.int32)
                scaler_out = (found, new_scale, new_unskipped, new_streak)
            else:
                scaler_out = None

            health_out = None
            if health_on:
                # device-resident numerics scalars, emitted as extra
                # outputs of THIS program — zero extra dispatches; update
                # norms measure the applied step (0 on a scaler skip)
                f32 = jnp.float32
                w_norms = [jnp.sqrt(jnp.sum(jnp.square(w.astype(f32))))
                           for w in ws]
                u_norms = [jnp.sqrt(jnp.sum(jnp.square(
                    nw.astype(f32) - w.astype(f32))))
                    for nw, w in zip(new_ws, ws)]
                health_out = (gnorm,
                              jnp.stack([jnp.sqrt(s) for s in gsqs]),
                              jnp.stack(w_norms), jnp.stack(u_norms),
                              jnp.stack(nonfin))

            flat_new = []
            for ns in new_states:
                lv: list = []
                _state_leaves(ns, lv)
                flat_new.append(tuple(lv))
            return tuple(new_ws), tuple(flat_new), ex, scaler_out, health_out

        donate = (0, 2) if self._donate else ()
        return jax.jit(step, donate_argnums=donate)

"""Executor — a bound, jit-compiled symbolic graph.

Reference: ``src/executor/graph_executor.cc`` (``GraphExecutor::SimpleBind/
Forward/Backward`` — TBV, SURVEY.md §2.1 L6b). TPU redesign: instead of
NNVM passes (PlanMemory, attach-op-execs) + engine pushes per node, the
whole graph evaluates as ONE pure function compiled by ``jax.jit``; XLA
does memory planning and fusion. Backward is ``jax.vjp`` of the same
function. BatchNorm moving stats thread through as explicit aux outputs
(the reference mutates them inside the kernel).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import obs, progcache
from .base import MXNetError
from .context import Context, current_context
from .ndarray import NDArray
from .ops import get_op
from .ops.registry import coerce_kwargs

__all__ = ["Executor"]


def _avals_sig(vals) -> tuple:
    return tuple((tuple(v.shape), str(v.dtype)) for v in vals)


def _build_graph_fn(sym, train: bool):
    """Compile the DAG into ``fn(arg_vals, aux_vals) -> (outputs, new_aux)``.

    Returns (arg_names, aux_names, fn, has_bn). RNG draws fold a per-call
    key via mxnet_tpu.random's trace scope (set by the caller when jitting).
    """
    nodes = sym._topo()
    arg_names = sym.list_arguments()
    aux_names = sym.list_auxiliary_states()
    if sym._op == "_group":
        heads = [(s._base(), s._index) for s in sym._inputs]
    else:
        heads = [(sym._base(), sym._index)]
    n_heads_multi = []
    for base, index in heads:
        if index is None and base._op is not None and base._n_outputs() > 1:
            n_heads_multi.append((base, None))

    def fn(arg_vals: List, aux_vals: List):
        env: Dict[int, object] = {}
        args = dict(zip(arg_names, arg_vals))
        auxs = dict(zip(aux_names, aux_vals))
        new_aux = dict(auxs)
        from . import autograd

        old_train = autograd.set_training(train)
        try:
            for node in nodes:
                if node._op is None:
                    env[id(node)] = args[node._name] if node._name in args \
                        else auxs[node._name]
                    continue
                if node._op == "_group":
                    continue
                # invoke_fn nodes carry their OpDef inline (symbol.invoke_fn)
                opdef = getattr(node, "_opdef", None) or get_op(node._op)
                kwargs = coerce_kwargs({k: v for k, v in node._attrs.items()
                                        if not k.startswith("__")})
                in_vals = []
                for i in node._inputs:
                    v = env[id(i._base())]
                    if i._index is not None and isinstance(v, tuple):
                        v = v[i._index]
                    in_vals.append(v)
                if node._op == "BatchNorm" and train and \
                        not kwargs.get("use_global_stats", False):
                    kwargs["output_mean_var"] = True
                    out, bmean, bvar = opdef.fn(*in_vals, **kwargs)
                    mom = float(kwargs.get("momentum", 0.9))
                    # inputs 3,4 are moving_mean/moving_var variables
                    for slot, batch_stat in ((3, bmean), (4, bvar)):
                        vn = node._inputs[slot]._base()._name
                        if vn in new_aux:
                            new_aux[vn] = mom * new_aux[vn] + (1 - mom) * batch_stat
                    env[id(node)] = out
                else:
                    env[id(node)] = opdef.fn(*in_vals, **kwargs)
        finally:
            autograd.set_training(old_train)

        outs = []
        for base, index in heads:
            v = env[id(base)]
            if isinstance(v, tuple):
                if index is not None:
                    outs.append(v[index])
                else:
                    outs.extend(v)
            else:
                outs.append(v)
        return tuple(outs), tuple(new_aux[n] for n in aux_names)

    return arg_names, aux_names, fn, bool(aux_names)


class Executor:
    """Bound graph with argument/gradient/aux arrays (reference Executor)."""

    def __init__(self, symbol, ctx=None, grad_req="write", shapes=None,
                 args=None, args_grad=None, aux_states=None, lint=None):
        self._symbol = symbol
        self._ctx = Context(ctx) if ctx is not None else current_context()
        self._grad_req = grad_req
        self.outputs_nd: List[NDArray] = []
        self.lint_report = None

        # Pre-flight static analysis BEFORE any inference/compilation:
        # lint="error" rejects a bad graph with node attribution instead of
        # an opaque tracer exception; "warn" reports and continues.
        # Default comes from MXNET_GRAPH_LINT (off).
        if lint is None:
            import os

            lint = os.environ.get("MXNET_GRAPH_LINT", "off")
        if lint not in ("off", "warn", "error"):
            raise ValueError(f"lint must be 'off'|'warn'|'error', got {lint!r}")
        if lint != "off":
            known = {k: tuple(v) for k, v in (shapes or {}).items()}
            if not known and args is not None:
                named = args.items() if isinstance(args, dict) \
                    else zip(symbol.list_arguments(), args)
                known = {k: tuple(v.shape) if isinstance(v, NDArray)
                         else tuple(NDArray(v).shape) for k, v in named}
            from .analysis import GraphLinter

            self.lint_report = GraphLinter().lint(symbol, shapes=known)
            if lint == "error":
                self.lint_report.raise_if_errors()
            elif self.lint_report:
                import warnings

                warnings.warn("graph lint: " + self.lint_report.format(),
                              stacklevel=2)

        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        self._arg_names = arg_names
        self._aux_names = aux_names

        inferred: Dict[str, tuple] = {}
        if shapes:
            from .symbol.symbol import infer_shapes

            inferred, _outs = infer_shapes(symbol, {k: tuple(v)
                                                    for k, v in shapes.items()})
        self.arg_dict: Dict[str, NDArray] = {}
        if args is not None:
            if isinstance(args, dict):
                self.arg_dict = {k: NDArray(v) if not isinstance(v, NDArray) else v
                                 for k, v in args.items()}
            else:
                self.arg_dict = {n: v for n, v in zip(arg_names, args)}
        elif shapes:
            for n in arg_names:
                if n not in inferred:
                    raise MXNetError(f"simple_bind: missing shape for arg {n!r}")
                self.arg_dict[n] = NDArray(np.zeros(inferred[n], np.float32),
                                           ctx=self._ctx)
        self.aux_dict: Dict[str, NDArray] = {}
        if aux_states is not None:
            if isinstance(aux_states, dict):
                self.aux_dict = dict(aux_states)
            else:
                self.aux_dict = {n: v for n, v in zip(aux_names, aux_states)}
        else:
            for n in aux_names:
                shape = inferred.get(n)
                if shape is None and n in self.arg_dict:
                    shape = self.arg_dict[n].shape
                if shape is None:
                    shape = ()
                init = np.ones(shape, np.float32) if n.endswith("var") \
                    else np.zeros(shape, np.float32)
                self.aux_dict[n] = NDArray(init, ctx=self._ctx)

        if grad_req != "null":
            if isinstance(args_grad, dict):
                self.grad_dict = dict(args_grad)
            elif isinstance(args_grad, (list, tuple)):
                self.grad_dict = {n: g for n, g in zip(arg_names, args_grad)}
            else:
                self.grad_dict = {
                    n: NDArray(np.zeros(self.arg_dict[n].shape, np.float32),
                               ctx=self._ctx)
                    for n in arg_names if n in self.arg_dict}
        else:
            self.grad_dict = {}

        self._jit_cache: Dict = {}
        self._vjp = None
        self._last_inputs = None
        # program accounting, populated only while obs is on (zero-cost-
        # when-off): one entry per distinct (site, input signature) built
        # through progcache.build, carrying XLA flops/bytes/HBM; the
        # signature's executable replaces the jit wrapper for execution
        self.compile_log: List[dict] = []
        self._seen_sigs: set = set()
        self._aot: Dict = {}

    # ------------------------------------------------------------------
    @property
    def outputs(self):
        return self.outputs_nd

    def copy_params_from(self, arg_params, aux_params=None):
        for k, v in (arg_params or {}).items():
            if k in self.arg_dict:
                self.arg_dict[k]._set_data(NDArray(v)._data)
        for k, v in (aux_params or {}).items():
            if k in self.aux_dict:
                self.aux_dict[k]._set_data(NDArray(v)._data)

    # ------------------------------------------------------------------
    def _device_account(self, site: str, jitted, call_args, sig):
        """Program accounting shared by forward and backward: on a
        signature's first sighting (and obs on) build once — cost/memory
        analysis into ``compile_log``, the executable kept for later
        calls. Returns ``(fn_to_call, is_compile)``."""
        is_compile = sig not in self._seen_sigs
        if is_compile:
            self._seen_sigs.add(sig)
            if obs.enabled():
                self._aot[sig], built = progcache.build(
                    jitted, call_args,
                    key=progcache.program_key("executor", site, sig))
                self.compile_log.append(
                    {"site": site, "train": sig[1], "avals": sig[2], **built})
        return self._aot.get(sig, jitted), is_compile

    def _get_fn(self, train: bool):
        key = train
        if key not in self._jit_cache:
            arg_names, aux_names, fn, _ = _build_graph_fn(self._symbol, train)

            def wrapped(rng_key, arg_vals, aux_vals):
                import jax.random as jr

                from . import random as _random

                if hasattr(jr, "wrap_key_data") and \
                        getattr(rng_key, "dtype", None) == jnp.uint32:
                    rng_key = jr.wrap_key_data(rng_key)
                with _random.trace_key_scope(rng_key):
                    return fn(arg_vals, aux_vals)

            self._jit_cache[key] = (jax.jit(wrapped), arg_names, aux_names, fn)
        return self._jit_cache[key]

    def forward(self, is_train=False, **kwargs):
        for k, v in kwargs.items():
            if k in self.arg_dict:
                self.arg_dict[k]._set_data(NDArray(v)._data)
            elif k in self.aux_dict:
                self.aux_dict[k]._set_data(NDArray(v)._data)
        jitted, arg_names, aux_names, raw_fn = self._get_fn(bool(is_train))
        arg_vals = [self.arg_dict[n]._data for n in arg_names]
        aux_vals = [self.aux_dict[n]._data for n in aux_names]
        from .chaos import nan as _nan_chaos

        if _nan_chaos.enabled():
            # deterministic NaN injection (MXNET_CHAOS_NAN) BEFORE the
            # last-inputs capture, so the health blame pass replays the
            # poisoned batch exactly as the compiled program saw it
            arg_vals = _nan_chaos.poison(arg_names, arg_vals)

        from . import random as _random
        import jax.random as jr

        key = _random.next_key()
        key_data = jr.key_data(key) if hasattr(jr, "key_data") else key
        from . import profiler as _profiler

        if _profiler.counting_dispatches():
            _profiler.count_dispatch("compiled")
        # program accounting only when obs is on (or produced an
        # executable earlier): the disabled hot path must not pay the
        # per-call aval-signature build (zero-cost-when-off contract)
        fn, is_compile = jitted, False
        if obs.enabled() or self._aot:
            sig = ("forward", bool(is_train), _avals_sig(arg_vals),
                   _avals_sig(aux_vals))
            fn, is_compile = self._device_account(
                "forward", jitted, (key_data, arg_vals, aux_vals), sig)
        with obs.trace.span("device.forward", train=bool(is_train),
                            compile=is_compile):
            outs, new_aux = fn(key_data, arg_vals, aux_vals)
        if is_train and self._grad_req != "null":
            # backward replays the same RNG key → identical dropout masks
            self._last_inputs = (key_data, arg_vals, aux_vals, bool(is_train))
        else:
            self._last_inputs = None
        for n, v in zip(aux_names, new_aux):
            self.aux_dict[n]._set_data(v)
        self.outputs_nd = [NDArray(o) for o in outs]
        return self.outputs_nd

    def _get_grad_fn(self, train: bool):
        key = ("grad", train)
        if key not in self._jit_cache:
            arg_names, aux_names, fn, _ = _build_graph_fn(self._symbol, train)

            def grad_fn(rng_key, arg_vals, aux_vals, cots):
                import jax.random as jr

                from . import random as _random

                if hasattr(jr, "wrap_key_data") and \
                        getattr(rng_key, "dtype", None) == jnp.uint32:
                    rng_key = jr.wrap_key_data(rng_key)
                with _random.trace_key_scope(rng_key):
                    _outs, vjp_fn = jax.vjp(lambda a: fn(a, aux_vals)[0],
                                            arg_vals)
                    (grads,) = vjp_fn(cots)
                return grads

            self._jit_cache[key] = jax.jit(grad_fn)
        return self._jit_cache[key]

    def backward(self, out_grads=None):
        if self._last_inputs is None:
            raise MXNetError("backward() requires forward(is_train=True) and "
                             "grad_req != 'null'")
        key_data, arg_vals, aux_vals, train = self._last_inputs
        if out_grads is None:
            cot = tuple(jnp.ones(o.shape, o.dtype) for o in self.outputs_nd)
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            cot = tuple(NDArray(g)._data for g in out_grads)
        from . import profiler as _profiler

        if _profiler.counting_dispatches():
            _profiler.count_dispatch("compiled")
        grad_fn = self._get_grad_fn(train)
        fn, is_compile = grad_fn, False
        if obs.enabled() or self._aot:
            sig = ("backward", bool(train), _avals_sig(arg_vals),
                   _avals_sig(cot))
            fn, is_compile = self._device_account(
                "backward", grad_fn, (key_data, arg_vals, aux_vals, cot),
                sig)
        with obs.trace.span("device.backward", compile=is_compile):
            grads = fn(key_data, arg_vals, aux_vals, cot)
        for n, g in zip(self._arg_names, grads):
            if n in self.grad_dict and g is not None:
                if self._grad_req == "add":
                    self.grad_dict[n]._set_data(self.grad_dict[n]._data + g)
                else:
                    self.grad_dict[n]._set_data(g)
        return [self.grad_dict.get(n) for n in self._arg_names]

    def __repr__(self):
        return f"<Executor {self._symbol!r} args={len(self.arg_dict)}>"

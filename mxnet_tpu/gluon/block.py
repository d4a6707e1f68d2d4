"""Gluon Block / HybridBlock / CachedOp — the imperative model API.

Reference: ``python/mxnet/gluon/block.py`` + ``src/imperative/cached_op.cc``
(TBV — SURVEY.md §2.1, §3.1-3.2).

TPU redesign of hybridize (the keystone — SURVEY.md §7 phase 2):

- A non-hybridized HybridBlock runs op-by-op eagerly (each op is an XLA
  executable; correct but per-op dispatch overhead, like the reference's
  engine path).
- ``hybridize()`` swaps in a :class:`CachedOp`. Instead of tracing into an
  NNVM graph and replaying engine pushes, CachedOp **purifies** the forward:
  parameters and inputs become function arguments, parameter mutations during
  the trace (BatchNorm moving stats) become extra outputs, RNG draws fold a
  traced key — then the whole thing is ``jax.jit``-compiled once per
  (shapes, dtypes, train-mode) key. XLA fusion replaces both the reference's
  CachedOp static-alloc optimization and its memory planner.
- Under ``autograd.record``, the hybridized call is recorded as ONE tape op
  whose vjp is the vjp of the purified function — so ``loss.backward()``
  deposits directly into parameter ``.grad``s.
"""
from __future__ import annotations

import re
import threading
from collections import OrderedDict

import jax
import numpy as np

from .. import autograd
from ..base import MXNetError
from ..context import current_context
from ..ndarray import NDArray
from ..ndarray.ndarray import invoke
from ..ops.registry import OpDef
from .parameter import DeferredInitializationError, Parameter, ParameterDict

__all__ = ["Block", "HybridBlock", "SymbolBlock", "CachedOp"]


class _BlockScope(threading.local):
    def __init__(self):
        self.counters = {}

    def alloc_prefix(self, hint):
        n = self.counters.get(hint, 0)
        self.counters[hint] = n + 1
        return f"{hint}{n}_"


_SCOPE = _BlockScope()


def _flatten_nds(args):
    flat, fmt = [], []
    for a in args:
        if isinstance(a, NDArray):
            flat.append(a)
            fmt.append(None)
        elif isinstance(a, (list, tuple)):
            f, m = _flatten_nds(a)
            flat.extend(f)
            fmt.append((type(a), m))
        else:
            fmt.append(("const", a))
    return flat, fmt


def _unflatten_nds(flat_iter, fmt):
    out = []
    for f in fmt:
        if f is None:
            out.append(next(flat_iter))
        elif isinstance(f, tuple) and f[0] == "const":
            out.append(f[1])
        else:
            typ, m = f
            out.append(typ(_unflatten_nds(flat_iter, m)))
    return out


class Block:
    """Base class for all layers/models (reference gluon.Block)."""

    def __init__(self, prefix=None, params=None):
        cls = self.__class__.__name__.lower()
        self._prefix = prefix if prefix is not None else _SCOPE.alloc_prefix(cls)
        self._params = ParameterDict(self._prefix, shared=params)
        self._children = OrderedDict()
        self._reg_params = OrderedDict()  # attr name -> Parameter (direct)
        self._forward_hooks = []
        self._forward_pre_hooks = []

    # -- attribute magic: registering children and params ----------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
                self._params._params[value.name] = value
        super().__setattr__(name, value)

    # -- naming ----------------------------------------------------------
    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._prefix.rstrip("_")

    def name_scope(self):
        class _NS:
            def __enter__(s):
                return s

            def __exit__(s, *a):
                pass

        return _NS()

    # -- params ----------------------------------------------------------
    @property
    def params(self) -> ParameterDict:
        return self._params

    def collect_params(self, select=None) -> ParameterDict:
        ret = ParameterDict(self._params.prefix)
        pattern = re.compile(select) if select else None
        for p in self._iter_params():
            if pattern is None or pattern.match(p.name):
                ret._params[p.name] = p
        return ret

    def _iter_params(self):
        seen = set()
        for p in self._params.values():
            if id(p) not in seen:
                seen.add(id(p))
                yield p
        for c in self._children.values():
            for p in c._iter_params():
                if id(p) not in seen:
                    seen.add(id(p))
                    yield p

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        self.collect_params().initialize(init=init, ctx=ctx, force_reinit=force_reinit)

    def cast(self, dtype):
        for p in self._iter_params():
            p.cast(dtype)
        for c in self._children.values():
            pass  # params already covered recursively
        self._cast_hook(dtype)

    def _cast_hook(self, dtype):
        for c in self._children.values():
            c._cast_hook(dtype)

    # -- persistence ------------------------------------------------------
    def _collect_params_with_prefix(self, prefix=""):
        """Structural (attribute-path) parameter names, e.g. ``features.0.weight``
        — instance-independent, the format reference save_parameters uses
        (python/mxnet/gluon/block.py — TBV), unlike prefix names which embed
        a global construction counter."""
        if prefix:
            prefix += "."
        ret = {prefix + attr: p for attr, p in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def save_parameters(self, filename, deduplicate=False):
        from ..ndarray import save as nd_save

        params = self._collect_params_with_prefix()
        nd_save(filename, {k: p.data() for k, p in params.items()
                           if p._data is not None})

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False, dtype_source="current"):
        from ..ndarray import load as nd_load

        loaded = nd_load(filename)
        mine = self._collect_params_with_prefix()
        if loaded and mine and not any(k in mine for k in loaded):
            # fall back to prefix-name matching (older save format)
            mine = {p.name: p for p in self._iter_params()}
        for name, param in mine.items():
            if name in loaded:
                if param._data is None:
                    param.shape = loaded[name].shape
                    param.initialize(ctx=ctx)
                param.set_data(loaded[name])
            elif not allow_missing:
                raise KeyError(f"Parameter {name} missing in {filename}")
        if not ignore_extra:
            extra = set(loaded) - set(mine)
            if extra:
                raise KeyError(f"{filename} contains extra parameters {sorted(extra)}")

    # -- call ------------------------------------------------------------
    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)

    def register_child(self, block, name=None):
        self._children[name or str(len(self._children))] = block

    def __call__(self, *args, **kwargs):
        for h in self._forward_pre_hooks:
            h(self, args)
        out = self.forward(*args, **kwargs)
        for h in self._forward_hooks:
            h(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def hybridize(self, active=True, **kwargs):
        for c in self._children.values():
            c.hybridize(active, **kwargs)

    def summary(self, *inputs):
        out = self(*inputs)
        lines = [f"{'Layer':<40}{'Params':>12}"]
        total = 0
        for p in self._iter_params():
            n = int(np.prod(p.shape)) if p.shape else 0
            total += n
            lines.append(f"{p.name:<40}{n:>12}")
        lines.append(f"{'TOTAL':<40}{total:>12}")
        print("\n".join(lines))
        return out

    def __repr__(self):
        kids = "\n".join(f"  ({k}): {v.__class__.__name__}" for k, v in self._children.items())
        return f"{self.__class__.__name__}(\n{kids}\n)"

    def apply(self, fn):
        for c in self._children.values():
            c.apply(fn)
        fn(self)
        return self


class HybridBlock(Block):
    """A Block that can be compiled (hybridized) into one XLA program."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_op = None
        self._flags = {}

    def hybridize(self, active=True, static_alloc=False, static_shape=False, **kwargs):
        self._active = active
        self._flags = dict(static_alloc=static_alloc, static_shape=static_shape, **kwargs)
        self._cached_op = None
        super().hybridize(active, static_alloc=static_alloc, static_shape=static_shape,
                          **kwargs)

    def infer_shape(self, *args):
        """Resolve deferred parameter shapes from input shapes. Built-in layers
        override; composite blocks resolve via their children during forward."""

    def _direct_param_kwargs(self):
        out = {}
        for attr, p in self._reg_params.items():
            out[attr] = p.data()
        return out

    def forward(self, x, *args, **kwargs):
        from ..symbol.symbol import Symbol

        if isinstance(x, Symbol):
            # symbolic tracing (reference: hybrid_forward receives F=symbol
            # when called with Symbols): parameters become named Variables,
            # children recurse through their own __call__ with Symbols.
            # Works for graphs whose hybrid_forward is F-generic and does
            # not inspect concrete .shape (the model-zoo CNN/MLP family).
            return self._forward_symbolic(x, *args, **kwargs)
        self._ensure_init(x, *args)
        if self._active:
            if any(p._data is None and p._deferred_init is not None
                   for p in self._iter_params()):
                # Deferred shapes must be resolved OUTSIDE the jit trace
                # (param init inside a trace would leak tracers): run this
                # first call eagerly, which initializes everything.
                return self._forward_eager(x, *args, **kwargs)
            if self._cached_op is None:
                self._cached_op = CachedOp(self)
            return self._cached_op(x, *args)
        return self._forward_eager(x, *args, **kwargs)

    def _forward_symbolic(self, x, *args, **kwargs):
        from .. import symbol as sym_mod

        def as_var(p):
            # carry the declared shape when fully known so the shared shape
            # pre-flight (analysis/shape_infer) — and hence Symbol.shape
            # inside shape-inspecting forwards — can anchor inference
            shape = getattr(p, "shape", None)
            if shape and all(int(d) > 0 for d in shape):
                return sym_mod.Variable(p.name, shape=tuple(shape))
            return sym_mod.Variable(p.name)

        params = {attr: as_var(p) for attr, p in self._reg_params.items()}
        return self.hybrid_forward(sym_mod, x, *args, **params, **kwargs)

    def _forward_eager(self, x, *args, **kwargs):
        from .. import ndarray as nd_mod

        try:
            params = self._direct_param_kwargs()
        except DeferredInitializationError:
            self.infer_shape(x, *args)
            params = self._direct_param_kwargs()
        return self.hybrid_forward(nd_mod, x, *args, **params, **kwargs)

    def _ensure_init(self, *args):
        """Resolve any deferred param shapes by probing children bottom-up."""
        for p in self._reg_params.values():
            if p._data is None and p._deferred_init is not None:
                self.infer_shape(*args)
                break

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    def lint(self, shapes=None, passes=None, **shape_kwargs):
        """Static-analyze this block before any compilation.

        Runs the :class:`~mxnet_tpu.analysis.TraceLinter` source checks
        (concretization leaks in forward bodies) and — when the block
        traces symbolically — the full :class:`~mxnet_tpu.analysis.
        GraphLinter` over its graph with the given input shapes::

            report = net.lint(data=(2, 3, 32, 32))
            report.raise_if_errors()

        ``shapes`` maps input Variable names to shapes (one per positional
        forward input, in order). Blocks whose forward is not F-generic
        get an info-level ``not-symbolically-traceable`` finding and only
        the source checks.
        """
        from ..analysis import Finding, GraphLinter, Report, Severity, TraceLinter
        from .. import symbol as sym_mod

        all_shapes = dict(shapes or {})
        all_shapes.update({k: tuple(v) for k, v in shape_kwargs.items()})
        report = Report(TraceLinter().scan_source(self))
        ins = [sym_mod.Variable(n, shape=s) for n, s in all_shapes.items()]
        try:
            out = self(*ins) if ins else self(sym_mod.Variable("data"))
            if isinstance(out, (list, tuple)):
                out = sym_mod.Group(list(out))
        except Exception as e:
            report.add(Finding(
                "not-symbolically-traceable", Severity.INFO,
                f"block does not trace symbolically ({type(e).__name__}: "
                f"{str(e)[:200]}); graph passes skipped",
                node=getattr(self, "name", None),
                fix_hint="make hybrid_forward F-generic (ops via F, "
                         "F.split over tensor indexing) to enable graph "
                         "lint"))
            return report
        param_names = {p.name for p in self._iter_params()}
        report.extend(GraphLinter(passes=passes, param_names=param_names)
                      .lint(out, shapes=all_shapes))
        return report

    def export(self, path, epoch=0, format="json", example_inputs=None):
        """Save for deployment (reference HybridBlock.export — symbol.json +
        .params; reference serving analog: c_predict_api.cc — TBV).

        format="json" (default): params + a json descriptor.
        format="stablehlo": additionally serialize the full inference
        program (weights baked in as constants) via ``jax.export`` — the
        TPU-native deployment artifact standing in for ONNX/TensorRT.
        Requires ``example_inputs`` (tuple of NDArrays, or one NDArray)
        fixing the input shapes/dtypes. Reload with
        :func:`mxnet_tpu.gluon.load_stablehlo`.
        format="onnx": symbolically trace the block and write
        ``{path}-{epoch}.onnx`` via contrib.onnx.export_model (requires
        ``example_inputs`` for shapes; the block's graph must be in the
        exporter's covered op surface).
        """
        import json

        # normalize up front: the graph-embed below also counts inputs,
        # and a bare NDArray would make len() return its batch dimension
        if example_inputs is not None and \
                not isinstance(example_inputs, (list, tuple)):
            example_inputs = (example_inputs,)
        # validate BEFORE any file is written — a raise after
        # save_parameters would leave a truncated checkpoint on disk
        if format in ("onnx", "stablehlo"):
            if example_inputs is None:
                raise ValueError(f"{format} export needs example_inputs")
            deferred = [p.name for p in self._iter_params()
                        if p._data is None]
            if deferred:
                # exporting now would bake fresh initializer values into the
                # artifact — run one forward to resolve shapes first
                raise ValueError(
                    f"cannot export: parameters {deferred} have deferred "
                    "shapes; run a forward pass before export")

        self.save_parameters(f"{path}-{epoch:04d}.params")
        meta = {"format": "mxnet_tpu-hybrid", "class": self.__class__.__name__}
        # Embed the traced graph + a saved-name → variable-name map so the
        # artifact is servable (mxnet_tpu.serve.load) and reloadable as a
        # SymbolBlock without the original class. Best-effort: a block
        # whose forward is not F-generic exports params-only, as before.
        try:
            from .. import symbol as sym_mod

            n_inputs = len(example_inputs) if example_inputs is not None else 1
            data_syms = [sym_mod.Variable(f"data{i}" if i else "data")
                         for i in range(n_inputs)]
            traced = self(*data_syms)
            if isinstance(traced, (list, tuple)):
                traced = sym_mod.Group(list(traced))
            meta["symbol"] = traced.tojson()
            meta["param_map"] = {
                saved: p.name for saved, p in
                self._collect_params_with_prefix().items()}
        except Exception:  # noqa: BLE001 — tracing is optional here
            pass
        if format == "onnx":
            from .. import symbol as sym_mod
            from ..contrib.onnx import export_model

            data_syms = [sym_mod.Variable(f"data{i}" if i else "data")
                         for i in range(len(example_inputs))]
            sym = self(*data_syms)
            if isinstance(sym, (list, tuple)):
                raise ValueError(
                    f"onnx export supports single-output blocks; this one "
                    f"returns {len(sym)} outputs — export a wrapper that "
                    "selects one")
            params = {p.name: p.data() for p in self._iter_params()}
            onnx_path = f"{path}-{epoch:04d}.onnx"
            export_model(sym, params,
                         [tuple(x.shape) for x in example_inputs],
                         onnx_file_path=onnx_path)
            meta["onnx"] = onnx_path
            meta["input_shapes"] = [list(x.shape) for x in example_inputs]
        if format == "stablehlo":
            import jax
            from jax import export as jexport

            from ..parallel.functional import functionalize

            names, apply = functionalize(self, train=False)
            by_name = {p.name: p for p in self._iter_params()}
            param_vals = {n: by_name[n].data()._data for n in names}

            def infer(*xs):
                out, _aux = apply(param_vals, *xs)
                return out

            avals = [jax.ShapeDtypeStruct(x.shape, x.dtype)
                     for x in example_inputs]
            exported = jexport.export(jax.jit(infer))(*avals)
            blob = exported.serialize()
            with open(f"{path}-{epoch:04d}.stablehlo", "wb") as f:
                f.write(blob)
            meta["stablehlo"] = f"{path}-{epoch:04d}.stablehlo"
            meta["input_shapes"] = [list(x.shape) for x in example_inputs]
            meta["input_dtypes"] = [str(x.dtype) for x in example_inputs]
        with open(f"{path}-symbol.json", "w") as f:
            json.dump(meta, f)

    def optimize_for(self, *args, **kwargs):
        self.hybridize(True)


class CachedOp:
    """Purified + jitted forward of a HybridBlock (reference CachedOp analog).

    Cache key: (train_mode, param avals, input avals). Each entry holds a
    ``jax.jit``-compiled pure function
    ``fn(rng_key, *param_vals, *input_vals) -> (*outputs, *aux_updates)``
    where aux_updates are parameter mutations detected during tracing
    (e.g. BatchNorm moving stats).
    """

    def __init__(self, block: HybridBlock):
        self.block = block
        self._cache = {}
        # program accounting (progcache.build): one entry per compiled
        # cache entry, carrying XLA flops/bytes/HBM, while obs is on
        self.compile_log = []

    def __call__(self, *inputs):
        flat_in, fmt = _flatten_nds(inputs)
        params = [p for p in self.block._iter_params() if p._data is not None]
        train = autograd.is_training()
        key = (
            train,
            tuple((p.data().shape, str(p.data().dtype)) for p in params),
            tuple((x.shape, str(x.dtype)) for x in flat_in),
        )
        entry = self._cache.get(key)
        if entry is None:
            entry = self._build(params, fmt, len(flat_in), train)
            self._cache[key] = entry
        rng = _new_rng()
        all_inputs = [NDArray(rng)] + [p.data() for p in params] + list(flat_in)
        result = invoke(entry["opdef"], all_inputs, {})
        if not isinstance(result, tuple):
            result = (result,)
        n_out = entry["n_out"]
        outs, aux = result[:n_out], result[n_out:]
        for p_idx, a in zip(entry["aux_param_idx"], aux):
            with autograd.pause():
                params[p_idx].data()._set_data(a._data)
        outs_it = iter(outs)
        restored = _unflatten_nds(outs_it, entry["out_fmt"])
        return restored[0] if len(restored) == 1 else tuple(restored)

    def _build(self, params, in_fmt, n_in, train):
        block = self.block
        n_params = len(params)
        aux_param_idx: list = []
        out_fmt_holder: list = []

        def raw_fn(rng_key, *vals):
            import jax.random as jr

            from .. import random as _random

            if hasattr(jr, "wrap_key_data") and rng_key.dtype == jax.numpy.uint32:
                rng_key = jr.wrap_key_data(rng_key)
            pvals = vals[:n_params]
            ivals = vals[n_params:]
            param_nds = [p.data() for p in params]
            saved = [(nd_._data, nd_._version) for nd_ in param_nds]
            try:
                for nd_, v in zip(param_nds, pvals):
                    nd_._data = v
                in_nds = _unflatten_nds(iter([NDArray(v) for v in ivals]), in_fmt)
                old_rec = autograd.set_recording(False)
                old_train = autograd.set_training(train)
                try:
                    with _random.trace_key_scope(rng_key):
                        out = block._forward_eager(*in_nds)
                finally:
                    autograd.set_recording(old_rec)
                    autograd.set_training(old_train)
                flat_out, fmt = _flatten_nds([out] if not isinstance(out, tuple) else list(out))
                out_fmt_holder.clear()
                out_fmt_holder.extend(fmt if not isinstance(out, tuple) else fmt)
                out_vals = [o._data for o in flat_out]
                # detect aux mutations (params whose wrapper was rebound)
                aux_vals = []
                aux_param_idx.clear()
                for i, (nd_, (old_data, _v)) in enumerate(zip(param_nds, saved)):
                    if nd_._data is not pvals[i]:
                        aux_param_idx.append(i)
                        aux_vals.append(nd_._data)
                return tuple(out_vals + aux_vals)
            finally:
                for nd_, (old_data, _v) in zip([p.data() for p in params], saved):
                    nd_._data = old_data

        jitted = jax.jit(raw_fn)

        # Trace once eagerly via jit lowering to populate out_fmt/aux metadata.
        # (jax.jit is lazy; we force trace with eval_shape on representative avals.)
        def trace_probe():
            import jax.numpy as jnp

            pav = [jax.ShapeDtypeStruct(p.data().shape, p.data().dtype) for p in params]
            rng_av = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
            # input avals come from the first real call; defer to call time
            return pav, rng_av

        opdef = OpDef(f"CachedOp_{block.name}", jitted,
                      num_outputs=lambda kw: None)  # resolved after first call

        entry = {"opdef": opdef, "aux_param_idx": aux_param_idx,
                 "out_fmt": out_fmt_holder, "n_out": None}

        # Wrap fn so first execution finalizes n_out/num_outputs metadata
        # (and, with obs on, builds the program once through
        # progcache.build for its cost and keeps that executable for later
        # calls).
        aot = {"compiled": None, "logged": False}

        def finalizing_fn(*vals, **kw):
            from .. import obs as _obs
            from .. import profiler as _profiler

            if _profiler.counting_dispatches():
                _profiler.count_dispatch("compiled")
            # Program accounting only when obs is on (or already produced
            # an executable) — the disabled hot path must not pay the
            # per-call scans. A nested hybridized block's CachedOp runs
            # INSIDE its parent's trace: tracer args can't feed a built
            # executable (and there is no standalone program to account),
            # so only concrete calls build/log and tracer calls inline
            # through the jit wrapper.
            fn = jitted
            if aot["compiled"] is not None or \
                    (not aot["logged"] and _obs.enabled()):
                concrete = not any(isinstance(v, jax.core.Tracer)
                                   for v in vals)
                if concrete and not aot["logged"]:
                    from .. import progcache as _progcache

                    aot["logged"] = True
                    avals = tuple((tuple(v.shape),
                                   str(getattr(v, "dtype", "?")))
                                  for v in vals)
                    aot["compiled"], built = _progcache.build(
                        jitted, vals, kwargs=kw,
                        key=_progcache.program_key(
                            "cachedop", block.name, (train, avals)))
                    self.compile_log.append(
                        {"block": block.name, "train": train,
                         "avals": avals, **built})
                if concrete and aot["compiled"] is not None:
                    fn = aot["compiled"]
            res = fn(*vals, **kw)
            n_aux = len(aux_param_idx)
            entry["n_out"] = len(res) - n_aux
            return res

        opdef.fn = finalizing_fn
        opdef.num_outputs = lambda kw: len(out_fmt_holder) + len(aux_param_idx)
        return entry


def _new_rng():
    import jax.random as jr

    from .. import random as _random

    return jr.key_data(_random.next_key()) if hasattr(jr, "key_data") else _random.next_key()


class SymbolBlock(HybridBlock):
    """Run a Symbol graph as a Gluon block (reference SymbolBlock): free
    graph variables that aren't inputs become Parameters, so an exported
    ``symbol.json + .params`` pair reloads as a trainable/hybridizable
    block — the deployment-reload path (reference ``SymbolBlock.imports``).
    """

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=params)
        if isinstance(outputs, (list, tuple)):
            if len(outputs) == 1:
                outputs = outputs[0]
            else:
                from ..symbol import Group

                outputs = Group(outputs)
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        self._outputs_sym = outputs
        self._input_names = [i.name if hasattr(i, "name") else str(i)
                             for i in inputs]
        self._arg_names = [n for n in outputs.list_arguments()
                           if n not in self._input_names]
        self._aux_names = outputs.list_auxiliary_states()
        for n in self._arg_names:
            p = self.params.get(n, shape=(0,), allow_deferred_init=True)
            self._reg_params[n] = p
        for n in self._aux_names:
            p = self.params.get(n, shape=(0,), allow_deferred_init=True,
                                grad_req="null")
            self._reg_params[n] = p
        self._graph_fns = {}  # train flag -> (arg_names, aux_names, fn, _)

    def _direct_param_kwargs(self):
        return {}  # graph params are resolved by name in hybrid_forward

    def hybrid_forward(self, F, *args, **kwargs):
        from .. import autograd as ag
        from ..executor import _build_graph_fn
        from ..ndarray import NDArray
        from ..ndarray.ndarray import invoke_fn

        train = ag.is_training()
        entry = self._graph_fns.get(train)
        if entry is None:
            entry = self._graph_fns[train] = _build_graph_fn(
                self._outputs_sym, train=train)
        arg_names, aux_names, fn, _has_aux = entry
        by_name = dict(zip(self._input_names, args))
        ins = []
        for n in arg_names:
            v = by_name[n] if n in by_name else self.params.get(n).data()
            ins.append(v if isinstance(v, NDArray) else NDArray(v))
        aux_nds = [self.params.get(n).data() for n in aux_names]
        n_args = len(ins)

        # route through invoke_fn so eager calls land on the autograd tape
        # (fine-tuning an imported checkpoint with record()/backward works)
        def pure(*vals):
            outs, new_aux = fn(list(vals[:n_args]), list(vals[n_args:]))
            return tuple(outs) + tuple(new_aux[n] for n in aux_names)

        result = invoke_fn(pure, ins + aux_nds)
        result = result if isinstance(result, tuple) else (result,)
        n_out = len(result) - len(aux_names)
        outs, aux_new = result[:n_out], result[n_out:]
        with ag.pause():
            for nd_, new in zip(aux_nds, aux_new):
                nd_._set_data(new._data)
        return outs[0] if len(outs) == 1 else tuple(outs)

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        """Load an exported ``-symbol.json`` (+ ``.params``) into a block
        (reference SymbolBlock.imports; serving analog of MXPredCreate)."""
        from ..symbol import Variable, load as sym_load

        sym = sym_load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [Variable(n) for n in input_names]
        blk = SymbolBlock(sym, inputs)
        if param_file:
            from ..ndarray import load as nd_load

            loaded = nd_load(param_file)
            flat = {}
            for k, v in loaded.items():  # accept arg:/aux: checkpoint keys
                flat[k.split(":", 1)[1] if ":" in k else k] = v
            for n, p in blk._reg_params.items():
                if n in flat:
                    p.shape = flat[n].shape
                    p.initialize(ctx=ctx)
                    p.set_data(flat[n])
                else:
                    raise KeyError(f"parameter {n} missing in {param_file}")
        return blk


def load_stablehlo(path):
    """Load a ``HybridBlock.export(format="stablehlo")`` artifact as a
    callable ``fn(*inputs) -> NDArray`` (weights are baked into the
    program). The deployment-side counterpart of the reference's
    MXPredCreate/MXPredForward (c_predict_api — TBV)."""
    import jax
    from jax import export as jexport

    from ..ndarray import NDArray

    with open(path, "rb") as f:
        exported = jexport.deserialize(bytearray(f.read()))

    def fn(*inputs):
        vals = [x._data if isinstance(x, NDArray) else jax.numpy.asarray(x)
                for x in inputs]
        out = exported.call(*vals)
        if isinstance(out, (list, tuple)):
            return tuple(NDArray(o) for o in out)
        return NDArray(out)

    fn.exported = exported
    return fn

"""NDArray — the imperative tensor, TPU-native.

Reference: ``src/ndarray/ndarray.cc`` + ``include/mxnet/ndarray.h`` +
``python/mxnet/ndarray/ndarray.py`` (paths TBV — SURVEY.md §2.1 L3).

Redesign for PJRT/XLA (SURVEY.md §7 hard part #1):

- An NDArray **wraps an immutable ``jax.Array``** (a PJRT buffer). The
  reference's per-array engine variable + dependency queue is replaced by
  JAX's async dispatch: every op returns immediately with a future-backed
  buffer, and ``wait_to_read()`` ≡ ``block_until_ready()``.
- MXNet mutation semantics (``x[:] = v``, ``+=``, ``out=``) are kept by
  **rebinding**: the wrapper swaps in a new jax.Array and bumps a version
  counter. Autograd stays correct because tape closures capture the old
  immutable buffer — a mutated input cannot corrupt a recorded gradient
  (the reference needs engine write-locks for the same guarantee).
- Every operator call dispatches through one choke point, :func:`invoke`,
  which consults the op registry and the autograd tape. There are no
  per-backend kernels: the same pure function is executed eagerly here and
  traced under jit in CachedOp/Executor.
"""
from __future__ import annotations

import os
import struct
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# MX_SYNC=1: block after every op (reference MXNET_ENGINE_TYPE=NaiveEngine
# debug mode, SURVEY.md §5.2) — turns async-dispatch bugs and NaN origins
# into synchronous stack traces. Read once at import like the reference.
_MX_SYNC = (os.environ.get("MX_SYNC", "0") not in ("", "0")
            or os.environ.get("MXNET_ENGINE_TYPE") == "NaiveEngine")

from ..base import MXNetError, dtype_np
from ..context import Context, current_context
from ..ops import get_op
from ..ops.registry import OpDef

__all__ = ["NDArray", "invoke", "array", "zeros", "ones", "full", "empty", "arange",
           "save", "load", "concat", "stack", "waitall", "from_jax"]


class NDArray:
    """An n-dimensional array on a device, with async semantics."""

    __slots__ = ("_data", "_ctx", "_grad", "_grad_req", "_ag_node", "_version", "__weakref__")

    def __init__(self, data, ctx: Optional[Context] = None, dtype=None):
        if isinstance(data, NDArray):
            data = data._data
        if not isinstance(data, jax.Array):
            data = jnp.asarray(np.asarray(data), dtype=dtype_np(dtype) if dtype else None)
            if data.dtype == jnp.float64:
                data = data.astype(jnp.float32)
            elif data.dtype == jnp.int64:
                data = data.astype(jnp.int32)
        elif dtype is not None:
            data = data.astype(dtype_np(dtype))
        if ctx is not None:
            dev = Context(ctx).jax_device() if not isinstance(ctx, Context) else ctx.jax_device()
            if not isinstance(data, jax.core.Tracer) and not _on_device(data, dev):
                data = jax.device_put(data, dev)
            self._ctx = Context(ctx) if not isinstance(ctx, Context) else ctx
        else:
            self._ctx = None
        self._data = data
        self._grad = None
        self._grad_req = "null"
        self._ag_node = None
        self._version = 0

    # ------------------------------------------------------------------ core
    def asjax(self) -> jax.Array:
        return self._data

    def _set_data(self, new) -> "NDArray":
        if isinstance(new, NDArray):
            # In-place mutation while recording: adopt the source's tape node so
            # the mutating op stays in the gradient chain (x *= 2 then y = x*x
            # differentiates through the *=). The reference raises on in-place
            # under recording; immutable buffers let us support it correctly.
            from .. import autograd

            if autograd.is_recording():
                self._ag_node = new._ag_node
            new = new._data
        self._data = new
        self._version += 1
        return self

    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def size(self):
        return int(self._data.size)

    @property
    def dtype(self):
        return np.dtype(self._data.dtype)

    @property
    def context(self) -> Context:
        if self._ctx is not None:
            return self._ctx
        if isinstance(self._data, jax.core.Tracer):
            # Under jit tracing there is no physical placement; report the
            # current default context (placement is the compiler's job).
            return current_context()
        dev = next(iter(self._data.devices()))
        if dev.platform == "cpu":
            return Context("cpu", dev.id)
        return Context("tpu", dev.id)

    ctx = context

    @property
    def stype(self):
        return "default"

    @property
    def grad(self) -> Optional["NDArray"]:
        return self._grad

    # ------------------------------------------------------------- transfer
    def asnumpy(self) -> np.ndarray:
        """Blocking device→host copy (reference NDArray::SyncCopyToCPU)."""
        from .. import profiler

        if profiler.counting_dispatches() and \
                not isinstance(self._data, jax.core.Tracer):
            profiler.count_dispatch("d2h")
        return np.asarray(self._data)

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def wait_to_read(self):
        """≡ reference WaitToRead; PJRT: block until the buffer is ready."""
        self._data.block_until_ready()
        return self

    def as_in_context(self, ctx) -> "NDArray":
        ctx = Context(ctx) if not isinstance(ctx, Context) else ctx
        if isinstance(self._data, jax.core.Tracer) or ctx == self.context:
            return self
        return NDArray(jax.device_put(self._data, ctx.jax_device()), ctx=ctx)

    as_in_ctx = as_in_context

    def copyto(self, other) -> "NDArray":
        if isinstance(other, Context):
            return self.as_in_context(other)
        other._set_data(jax.device_put(self._data, other.context.jax_device()))
        return other

    def copy(self) -> "NDArray":
        return NDArray(self._data, ctx=self._ctx)

    def astype(self, dtype, copy=True) -> "NDArray":
        d = dtype_np(dtype)
        if not copy and self.dtype == d:
            return self
        # float->float casts are differentiable and must stay on the tape
        # (reference: Cast has a registered backward); raw _wrap would
        # silently detach anything computed through e.g. .astype("float32").
        # jnp.issubdtype, not dtype.kind: ml_dtypes bfloat16 reports kind
        # 'V', which a kind=='f' test would silently detach again.
        if (_recording_this([self])
                and jnp.issubdtype(jnp.dtype(d), jnp.floating)
                and jnp.issubdtype(self._data.dtype, jnp.floating)):
            return invoke_fn(lambda x: x.astype(d), [self])
        return _wrap(self._data.astype(d), self)

    def detach(self) -> "NDArray":
        out = NDArray(self._data, ctx=self._ctx)
        return out

    # ------------------------------------------------------------- autograd
    def attach_grad(self, grad_req: str = "write", stype=None):
        """Mark for gradient computation (reference mx.autograd)."""
        from .. import autograd

        self._grad_req = grad_req
        if grad_req != "null":
            self._grad = NDArray(jnp.zeros_like(self._data), ctx=self._ctx)
            autograd._mark_variable(self)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from .. import autograd

        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    # ------------------------------------------------------------- indexing
    def __getitem__(self, key):
        key = _unwrap_key(key)
        if _recording_this([self]):
            return invoke_fn(lambda d: d[key], [self])
        return _wrap(self._data[key], self)

    def __setitem__(self, key, value):
        key = _unwrap_key(key)
        from .. import autograd

        if autograd.is_recording() and isinstance(value, NDArray):
            self._set_data(invoke_fn(lambda d, v: d.at[key].set(v), [self, value]))
            return
        if isinstance(value, NDArray):
            value = value._data
        if not isinstance(value, jax.Array):
            value = jnp.asarray(value, dtype=self._data.dtype)
        self._set_data(self._data.at[key].set(value))

    # ---------------------------------------------------------- arithmetic
    def __add__(self, o):
        return _binary("broadcast_add", "_plus_scalar", self, o)

    def __radd__(self, o):
        return _binary("broadcast_add", "_plus_scalar", self, o)

    def __sub__(self, o):
        return _binary("broadcast_sub", "_minus_scalar", self, o)

    def __rsub__(self, o):
        return invoke("_rminus_scalar", [self], {"scalar": o})

    def __mul__(self, o):
        return _binary("broadcast_mul", "_mul_scalar", self, o)

    def __rmul__(self, o):
        return _binary("broadcast_mul", "_mul_scalar", self, o)

    def __truediv__(self, o):
        return _binary("broadcast_div", "_div_scalar", self, o)

    def __rtruediv__(self, o):
        return invoke("_rdiv_scalar", [self], {"scalar": o})

    def __mod__(self, o):
        return _binary("broadcast_mod", "_mod_scalar", self, o)

    def __pow__(self, o):
        return _binary("broadcast_power", "_power_scalar", self, o)

    def __rpow__(self, o):
        return invoke("_rpower_scalar", [self], {"scalar": o})

    def __neg__(self):
        return invoke("negative", [self], {})

    def __abs__(self):
        return invoke("abs", [self], {})

    def __iadd__(self, o):
        return self._set_data(_binary("broadcast_add", "_plus_scalar", self, o))

    def __isub__(self, o):
        return self._set_data(_binary("broadcast_sub", "_minus_scalar", self, o))

    def __imul__(self, o):
        return self._set_data(_binary("broadcast_mul", "_mul_scalar", self, o))

    def __itruediv__(self, o):
        return self._set_data(_binary("broadcast_div", "_div_scalar", self, o))

    def __eq__(self, o):
        if o is None:
            return False
        return _binary("broadcast_equal", "_equal_scalar", self, o)

    def __ne__(self, o):
        if o is None:
            return True
        return _binary("broadcast_not_equal", "_not_equal_scalar", self, o)

    def __gt__(self, o):
        return _binary("broadcast_greater", "_greater_scalar", self, o)

    def __ge__(self, o):
        return _binary("broadcast_greater_equal", "_greater_equal_scalar", self, o)

    def __lt__(self, o):
        return _binary("broadcast_lesser", "_lesser_scalar", self, o)

    def __le__(self, o):
        return _binary("broadcast_lesser_equal", "_lesser_equal_scalar", self, o)

    def __hash__(self):
        return id(self)

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __bool__(self):
        if self.size != 1:
            raise ValueError("ambiguous truth value of multi-element NDArray")
        return bool(self.asscalar())

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __repr__(self):
        return f"\n{self.asnumpy()}\n<NDArray {'x'.join(map(str, self.shape))} @{self.context}>"

    # ------------------------------------------------------- method aliases
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return invoke("reshape", [self], {"shape": shape, **kwargs})

    def reshape_like(self, other):
        return invoke("reshape", [self], {"shape": other.shape})

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return invoke("transpose", [self], {"axes": axes or None})

    @property
    def T(self):
        return self.transpose()

    def _op_method(name):  # noqa: N805 — helper to declare forwarding methods
        def m(self, *args, **kwargs):
            return invoke(name, [self], kwargs)

        m.__name__ = name
        return m

    def sum(self, axis=None, keepdims=False, **kw):
        return invoke("sum", [self], {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False, **kw):
        return invoke("mean", [self], {"axis": axis, "keepdims": keepdims})

    def max(self, axis=None, keepdims=False, **kw):
        return invoke("max", [self], {"axis": axis, "keepdims": keepdims})

    def min(self, axis=None, keepdims=False, **kw):
        return invoke("min", [self], {"axis": axis, "keepdims": keepdims})

    def prod(self, axis=None, keepdims=False, **kw):
        return invoke("prod", [self], {"axis": axis, "keepdims": keepdims})

    def argmax(self, axis=None, keepdims=False, **kw):
        return invoke("argmax", [self], {"axis": axis, "keepdims": keepdims})

    def argmin(self, axis=None, keepdims=False, **kw):
        return invoke("argmin", [self], {"axis": axis, "keepdims": keepdims})

    def norm(self, **kw):
        return invoke("norm", [self], kw)

    def abs(self):
        return invoke("abs", [self], {})

    def sqrt(self):
        return invoke("sqrt", [self], {})

    def square(self):
        return invoke("square", [self], {})

    def exp(self):
        return invoke("exp", [self], {})

    def log(self):
        return invoke("log", [self], {})

    def relu(self):
        return invoke("relu", [self], {})

    def sigmoid(self):
        return invoke("sigmoid", [self], {})

    def tanh(self):
        return invoke("tanh", [self], {})

    def clip(self, a_min=None, a_max=None):
        return invoke("clip", [self], {"a_min": a_min, "a_max": a_max})

    def expand_dims(self, axis):
        return invoke("expand_dims", [self], {"axis": axis})

    def squeeze(self, axis=None):
        return invoke("squeeze", [self], {"axis": axis})

    def flatten(self):
        return invoke("Flatten", [self], {})

    def flip(self, axis):
        return invoke("flip", [self], {"axis": axis})

    def tile(self, reps):
        return invoke("tile", [self], {"reps": reps})

    def repeat(self, repeats, axis=None):
        return invoke("repeat", [self], {"repeats": repeats, "axis": axis})

    def broadcast_to(self, shape):
        return invoke("broadcast_to", [self], {"shape": shape})

    def broadcast_like(self, other):
        return invoke("broadcast_like", [self, other], {})

    def slice_axis(self, axis, begin, end):
        return invoke("slice_axis", [self], {"axis": axis, "begin": begin, "end": end})

    def take(self, indices, axis=0, mode="clip"):
        return invoke("take", [self, indices], {"axis": axis, "mode": mode})

    def one_hot(self, depth, **kw):
        return invoke("one_hot", [self], {"depth": depth, **kw})

    def topk(self, **kw):
        return invoke("topk", [self], kw)

    def sort(self, **kw):
        return invoke("sort", [self], kw)

    def argsort(self, **kw):
        return invoke("argsort", [self], kw)

    def dot(self, other, **kw):
        return invoke("dot", [self, other], kw)

    def softmax(self, axis=-1):
        return invoke("softmax", [self], {"axis": axis})

    def log_softmax(self, axis=-1):
        return invoke("log_softmax", [self], {"axis": axis})

    def tostype(self, stype):
        if stype == "default":
            return self
        from . import sparse

        return sparse.cast_storage(self, stype)

    def zeros_like(self):
        return invoke("zeros_like", [self], {})

    def ones_like(self):
        return invoke("ones_like", [self], {})


def _on_device(arr: jax.Array, dev) -> bool:
    try:
        return set(arr.devices()) == {dev}
    except Exception:
        return False


def _unwrap_key(key):
    if isinstance(key, NDArray):
        return key._data.astype(jnp.int32)
    if isinstance(key, tuple):
        return tuple(_unwrap_key(k) for k in key)
    return key


def _wrap(data: jax.Array, like: Optional[NDArray] = None) -> NDArray:
    return NDArray(data, ctx=like._ctx if like is not None else None)


def _recording_this(inputs) -> bool:
    from .. import autograd

    return autograd.is_recording()


def _binary(op_name, scalar_op, lhs, rhs):
    if isinstance(rhs, NDArray):
        return invoke(op_name, [lhs, rhs], {})
    return invoke(scalar_op, [lhs], {"scalar": rhs})


# ---------------------------------------------------------------------------
# The dispatch choke point
# ---------------------------------------------------------------------------

def invoke(op: Any, inputs: Sequence[NDArray], kwargs: dict):
    """Execute a registered op eagerly, recording on the autograd tape if active.

    Analog of reference ``MXImperativeInvokeEx`` → ``Imperative::Invoke``
    (src/c_api/c_api_ndarray.cc, src/imperative/imperative.cc — TBV).
    """
    opdef = op if isinstance(op, OpDef) else get_op(op)
    out = kwargs.pop("out", None)
    from .. import autograd, profiler

    datas = [x._data if isinstance(x, NDArray) else x for x in inputs]
    # CachedOp dispatches count as "compiled" at their own call site
    if profiler.counting_dispatches() and not any(
            isinstance(d, jax.core.Tracer) for d in datas) \
            and not opdef.name.startswith("CachedOp_"):
        profiler.count_dispatch("eager_ops")
    # skip timing under trace: block_until_ready is a no-op on tracers, so
    # the "duration" would be trace-construction overhead, not execution
    timing = profiler.aggregate_active() and not any(
        isinstance(d, jax.core.Tracer) for d in datas)
    if timing:
        import time as _time

        t0 = _time.perf_counter()
    if autograd.is_recording() and opdef.differentiable:
        result = autograd._record_op(opdef, inputs, datas, kwargs)
    else:
        result = opdef.fn(*datas, **kwargs)
        result = _wrap_result(result, inputs)
    if timing:
        jax.block_until_ready([r._data for r in
                               (result if isinstance(result, (list, tuple))
                                else [result]) if isinstance(r, NDArray)])
        profiler.record_op(opdef.name, _time.perf_counter() - t0)
    if out is not None:
        if isinstance(result, (list, tuple)):
            for o, r in zip(out if isinstance(out, (list, tuple)) else [out], result):
                o._set_data(r._data)
        else:
            out._set_data(result._data)
        result = out
    if _MX_SYNC:
        for r in result if isinstance(result, (list, tuple)) else [result]:
            if isinstance(r, NDArray):
                r.wait_to_read()
    return result


def invoke_fn(fn, inputs: Sequence[NDArray], kwargs=None):
    """Invoke an ad-hoc pure function as if it were an op (used by __getitem__
    and contrib paths). Dispatches on input type: with Symbol inputs the
    function is spliced into the graph as one inline-OpDef node
    (symbol.invoke_fn), so F-generic hybrid_forward code using this escape
    hatch stays symbolically traceable."""
    from ..symbol.symbol import Symbol, invoke_fn as _sym_invoke_fn

    if any(isinstance(x, Symbol) for x in inputs):
        return _sym_invoke_fn(fn, inputs, kwargs)
    opdef = OpDef("<lambda>", fn, num_outputs=1)
    return invoke(opdef, inputs, kwargs or {})


def _wrap_result(result, inputs):
    like = next((x for x in inputs if isinstance(x, NDArray)), None)
    if isinstance(result, (list, tuple)):
        return tuple(_wrap(r, like) for r in result)
    return _wrap(result, like)


# ---------------------------------------------------------------------------
# Creation / io
# ---------------------------------------------------------------------------

def array(source_array, ctx=None, dtype=None) -> NDArray:
    """Create an NDArray. Reference dtype rule: np.ndarray sources keep their
    dtype; python lists/scalars default to float32."""
    if dtype is None and not isinstance(source_array, (np.ndarray, jax.Array, NDArray)):
        dtype = np.float32
    return NDArray(source_array, ctx=ctx or current_context(), dtype=dtype)


def from_jax(arr: jax.Array) -> NDArray:
    return NDArray(arr)


def empty(shape, ctx=None, dtype=None) -> NDArray:
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=None, **kw) -> NDArray:
    from ..base import dtype_name

    return invoke("_zeros", [], {"shape": _tup(shape), "dtype": dtype_name(dtype or "float32"),
                                 "ctx": None}) .as_in_context(ctx or current_context())


def ones(shape, ctx=None, dtype=None, **kw) -> NDArray:
    from ..base import dtype_name

    return invoke("_ones", [], {"shape": _tup(shape), "dtype": dtype_name(dtype or "float32"),
                                "ctx": None}).as_in_context(ctx or current_context())


def full(shape, val, ctx=None, dtype=None, **kw) -> NDArray:
    from ..base import dtype_name

    return invoke("_full", [], {"shape": _tup(shape), "value": val,
                                "dtype": dtype_name(dtype or "float32"),
                                "ctx": None}).as_in_context(ctx or current_context())


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype="float32") -> NDArray:
    return invoke("_arange", [], {"start": start, "stop": stop, "step": step,
                                  "repeat": repeat, "dtype": dtype,
                                  "ctx": None}).as_in_context(ctx or current_context())


def _tup(shape):
    return (shape,) if isinstance(shape, int) else tuple(shape)


def concat(*data, dim=1):
    return invoke("Concat", list(data), {"dim": dim})


def stack(*data, axis=0):
    return invoke("stack", list(data), {"axis": axis})


def waitall():
    """Block until all launched work is done (reference MXNDArrayWaitAll)."""
    jax.effects_barrier()


# ---------------------------------------------------------------------------
# save / load — reference NDArray serialization API (MXNDArraySave/Load).
# Format: the reference binary list container (see ndarray/serialization.py);
# load() also accepts the npz container earlier TPU builds wrote.
# ---------------------------------------------------------------------------

def save(fname: str, data) -> None:
    from .serialization import save_nd

    if isinstance(data, NDArray):
        keys, arrays = [], [data]
    elif isinstance(data, dict):
        keys = list(data.keys())
        arrays = [data[k] for k in keys]
    elif isinstance(data, (list, tuple)):
        keys, arrays = [], list(data)
    else:
        raise TypeError(f"cannot save {type(data)}")
    # ONE batched device→host gather for the whole set (not a blocking
    # asnumpy per array) — checkpoints of many-parameter models sync once
    host = jax.device_get([a._data for a in arrays])
    save_nd(fname, [np.asarray(h) for h in host], keys)


def load(fname: str):
    from .serialization import is_binary_nd, load_nd

    path = _npz_path(fname)
    with open(path, "rb") as f:
        head = f.read(8)
    if is_binary_nd(head):
        out = load_nd(path)
        if isinstance(out, dict):
            return {k: NDArray(v) for k, v in out.items()}
        return [NDArray(v) for v in out]
    with np.load(path, allow_pickle=False) as z:  # legacy npz container
        keys = list(z.keys())
        if keys == ["__single__"]:
            return [NDArray(z["__single__"])]
        if all(k.startswith("__list_") for k in keys):
            return [NDArray(z[f"__list_{i}__"]) for i in range(len(keys))]
        return {k: NDArray(z[k]) for k in keys}


def _npz_path(fname):
    import os

    if os.path.exists(fname):
        return fname
    if os.path.exists(fname + ".npz"):
        return fname + ".npz"
    return fname

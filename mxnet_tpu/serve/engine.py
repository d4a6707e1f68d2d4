"""Compiled inference executor — the device half of ``mxnet_tpu.serve``.

Reference: the MXNet Model Server ran inference through threaded CachedOp
executors (``python/mxnet/gluon/block.py`` CachedOp + mms's batching handler
— TBV, SURVEY.md §1). TPU redesign: one **donation-free ``jax.jit``
program per bucketed input shape**, parameters device-resident and passed
as *traced arguments* — so a hot parameter reload swaps arrays without a
single retrace, and the compiled-program count is bounded by construction:

- **Shape bucketing**: a request batch of ``n`` rows is padded up to the
  smallest configured bucket ≥ n (pad rows are zeros; outputs are sliced
  back to ``n`` — rows are independent in eval mode, BatchNorm uses its
  moving stats, so the valid rows are bitwise what an unpadded run with the
  same program would produce). Ragged traffic therefore compiles at most
  ``len(buckets) × distinct feature signatures`` programs, ever.
- **Cache-key accounting** mirrors ``optimizer/fused.py``: every program is
  keyed explicitly (input avals), ``compile_log`` records one entry per
  compilation, and the TraceLinter's ``serve-retrace-churn`` rule
  (``analysis/trace.py``) turns that log into a *proof* that the bound
  holds — a key compiled twice, or more programs than buckets admit, is a
  linted defect, not a hunch.
- **Hot reload**: ``reload()`` validates the new parameter set against the
  current avals (a shape/dtype drift would silently double the program
  count) and swaps the whole device-resident set atomically under a lock.
  In-flight executions hold the snapshot they started with — a request sees
  *old or new* parameters, never a mix.

Telemetry (docs/OBSERVABILITY.md): ``serve.execute`` spans per batch with
bucket/compile attribution, ``serve.compile_seconds`` vs
``serve.execute_seconds`` histograms, ``dispatch.*`` counters feeding
``profiler.count_dispatches()`` so tests can assert the program bound.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import copytrack, obs, progcache
from ..base import MXNetError

__all__ = ["InferenceEngine", "ServeError", "RequestRejected",
           "DeadlineExceeded", "Draining", "default_buckets"]


class ServeError(MXNetError):
    """Base error of the serving subsystem."""


class RequestRejected(ServeError):
    """Load shed: the request was refused before execution (HTTP-429
    analog) — queue over watermark, or the server is not accepting."""


class DeadlineExceeded(ServeError):
    """The request's deadline expired before (or while) it could run; it
    was shed, not executed."""


class Draining(ServeError):
    """The endpoint is draining for shutdown and refuses new work."""


def _to_device(v, sharding=None):
    """NDArray/numpy → device array (load-time AND reload-time parameter
    placement share this one helper so they can never diverge). With a
    ``sharding`` the value is committed to the engine's mesh slice —
    tensor-parallel params land shard-resident per device, never gathered
    on one."""
    import jax

    from ..ndarray import NDArray

    if isinstance(v, NDArray) and v._data is not None:
        if sharding is None:
            return v._data
        return jax.device_put(v._data, sharding)
    arr = np.ascontiguousarray(np.asarray(v))
    return jax.device_put(arr) if sharding is None \
        else jax.device_put(arr, sharding)


def _shape_of(v) -> tuple:
    s = getattr(v, "shape", None)
    if s is None:
        s = np.asarray(v).shape
    return tuple(int(d) for d in s)


def _sig_of(arrays) -> tuple:
    """THE program signature of a (padded) batch — ``infer``'s accounting
    key and ``warmup``'s already-compiled filter both derive through this
    one function, so the two can never silently drift apart (a mismatch
    would make every warmup re-run full inferences instead of returning
    0 on the second call)."""
    return tuple((tuple(a.shape), str(a.dtype)) for a in arrays)


def default_buckets(max_batch_size: int) -> List[int]:
    """Power-of-two batch buckets up to ``max_batch_size`` (which is always
    included, power of two or not): 32 → [1, 2, 4, 8, 16, 32]."""
    max_batch_size = int(max_batch_size)
    if max_batch_size < 1:
        raise ValueError("max_batch_size must be >= 1")
    out = []
    b = 1
    while b < max_batch_size:
        out.append(b)
        b *= 2
    out.append(max_batch_size)
    return out


class _ParamSet:
    """One immutable generation of device-resident parameters. Executions
    snapshot the reference once, so a concurrent reload can never hand a
    program half-old half-new arrays."""

    __slots__ = ("version", "arg_vals", "aux_vals")

    def __init__(self, version: int, arg_vals: tuple, aux_vals: tuple):
        self.version = version
        self.arg_vals = arg_vals
        self.aux_vals = aux_vals


class InferenceEngine:
    """Serve a trained symbolic graph as compiled, bucketed inference.

    Parameters
    ----------
    symbol : Symbol
        The inference graph (a trained Module's symbol, a gluon export's
        embedded trace, or a ``quantize_model`` int8 rewrite).
    arg_params / aux_params : dict[str, array]
        Trained parameters (NDArray or numpy). Graph arguments that are
        neither data nor parameters (e.g. ``softmax_label`` on a training
        head) are bound to zeros per bucket — they don't affect eval-mode
        outputs.
    data_names : sequence of str
        Which graph arguments are request inputs, in request order.
    max_batch_size : int
        Largest bucket; requests bigger than this are chunked.
    buckets : sequence of int, optional
        Explicit batch buckets (sorted, deduped). Default:
        ``default_buckets(max_batch_size)``.
    lint : "off" | "warn" | "error"
        Pre-flight ``Symbol.lint`` at load time; "error" refuses to serve a
        graph with error-severity findings (a bad graph should fail at
        deploy, not on the first customer request).
    progcache_dir : str, optional
        Directory of a persistent AOT program cache for THIS engine
        (``mxnet_tpu/progcache.py``) — e.g. an artifact's shipped
        ``programs/`` payload. Default: the process-global cache
        (``MXNET_PROGCACHE_DIR`` / ``MXNET_PROGCACHE=1``), or no
        persistence. With a cache, a bucket whose program was compiled by
        ANY earlier process (same graph, avals, platform, code) warms by
        deserializing the stored executable — the ``compile_log`` entry
        records ``cache_hit: True`` and zero fresh XLA compilation
        happens; the loaded program is the same machine code, so the
        bitwise serve-vs-predict contract is untouched.
    mesh : jax.sharding.Mesh, optional
        Shard the engine over a device mesh (typically one replica group's
        slice — ``parallel.mesh_slices``): parameters are committed
        shard-resident per device by the ``rules`` table, every bucket's
        program compiles over the mesh (XLA inserts the tensor-parallel
        collectives), and batches shard over a ``dp`` axis when the mesh
        has one (``data_spec``). The compiled-program bound, the
        compile_log accounting, atomic hot reload, and the
        bitwise-vs-``predict`` contract *per shard config* are all
        unchanged — the mesh only changes where arrays live.
    rules : parallel.ShardingRules, optional
        Parameter-name → PartitionSpec table (default: everything
        replicated). Specs naming axes the mesh lacks, or not dividing a
        dim, prune to replicated — one table serves every mesh shape.
    data_spec : PartitionSpec, optional
        Spec for request batches (default ``P("dp")``, pruned per bucket
        shape; a pure-``tp`` slice replicates the batch).
    """

    def __init__(self, symbol, arg_params, aux_params=None, *,
                 data_names: Sequence[str] = ("data",),
                 max_batch_size: int = 32,
                 buckets: Optional[Sequence[int]] = None,
                 lint: str = "warn",
                 pad_value: float = 0.0,
                 mesh=None, rules=None, data_spec=None,
                 progcache_dir: Optional[str] = None):
        import jax

        from ..executor import _build_graph_fn

        self.symbol = symbol
        self._data_names = list(data_names)
        if buckets is None:
            buckets = default_buckets(max_batch_size)
        self.buckets: List[int] = sorted(set(int(b) for b in buckets))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"invalid buckets {buckets!r}")
        self.max_batch_size = self.buckets[-1]
        self._pad_value = float(pad_value)

        arg_params = dict(arg_params or {})
        aux_params = dict(aux_params or {})
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        missing_data = [n for n in self._data_names if n not in arg_names]
        if missing_data:
            raise ServeError(
                f"data_names {missing_data} are not arguments of the graph "
                f"(arguments: {arg_names})")
        self._param_names = [n for n in arg_names
                             if n not in self._data_names and n in arg_params]
        # training-head leftovers (labels): zero-filled per bucket — they
        # must not force the client to ship dummy tensors over the wire.
        # ONLY label-like names qualify: zero-filling an arbitrary missing
        # weight (a name-mismatched or truncated checkpoint) would serve
        # garbage silently, the exact bug class the aux check below rejects
        self._free_names = [n for n in arg_names
                            if n not in self._data_names
                            and n not in arg_params]
        not_label = [n for n in self._free_names if "label" not in n]
        if not_label:
            raise ServeError(
                f"graph arguments {not_label} are neither inputs nor in "
                "arg_params — a zero-filled weight would serve wrong "
                "predictions silently; fix the checkpoint/param_map, or "
                "list them in data_names if they are real inputs")
        self._aux_names = list(aux_names)
        missing_aux = [n for n in aux_names if n not in aux_params]
        if missing_aux:
            raise ServeError(
                f"aux states {missing_aux} missing from aux_params — an "
                "untrained BatchNorm served with default stats is a silent "
                "accuracy bug; export the full checkpoint")

        # -- pre-flight static analysis (model-load, not first-request) ----
        self.lint_report = None
        if lint not in ("off", "warn", "error"):
            raise ValueError(f"lint must be 'off'|'warn'|'error', got {lint!r}")
        if lint != "off":
            self.lint_report = symbol.lint()
            if lint == "error":
                self.lint_report.raise_if_errors()
            elif self.lint_report:
                import warnings

                warnings.warn("serve model-load lint: "
                              + self.lint_report.format(), stacklevel=2)

        # -- mesh sharding (tensor-parallel serving) ----------------------
        # the mesh-dependent placement is all resolved HERE, once: a dict
        # name → NamedSharding for params (rules table, pruned per shape),
        # replicated for aux/free/rng, batch spec per bucket at infer time.
        # reload goes through the same dict, so a new generation can never
        # land with a different layout than the programs compiled for.
        self.mesh = mesh
        self._param_sh: Dict[str, object] = {}
        self._replicated_sh = None
        self._data_spec = data_spec
        self._data_sh_cache: Dict[tuple, object] = {}
        if mesh is not None:
            from ..parallel.sharding import (ShardingRules, replicated)

            rules = rules or ShardingRules()
            self._rules = rules
            self._replicated_sh = replicated(mesh)
            for n in self._param_names:
                self._param_sh[n] = rules.sharding_for(
                    n, mesh, _shape_of(arg_params[n]))

        # -- device-resident parameters -----------------------------------
        self._lock = threading.Lock()
        self._staged: Optional[_ParamSet] = None  # prepared, not yet serving
        self._params = _ParamSet(
            0,
            tuple(_to_device(arg_params[n], self._param_sh.get(n))
                  for n in self._param_names),
            tuple(_to_device(aux_params[n], self._replicated_sh)
                  for n in self._aux_names))
        self._param_avals = tuple(
            (tuple(v.shape), str(v.dtype)) for v in self._params.arg_vals)
        self._aux_avals = tuple(
            (tuple(v.shape), str(v.dtype)) for v in self._params.aux_vals)

        # -- the compiled program (one jax.jit entry per input signature) --
        # The traced function mirrors Executor._get_fn's ``wrapped``
        # EXACTLY (same arg_vals/aux_vals list layout, same (outs, new_aux)
        # return): identical jaxpr → identical HLO → the engine's bucket-B
        # program is bit-for-bit the executable ``Module.predict`` runs at
        # batch B. That is what makes the flagship bitwise-equality
        # contract (serve output == direct predict output) hold by
        # construction instead of by luck — XLA does not promise identical
        # ulps across *different* programs, only across runs of the same
        # one.
        arg_order = {n: i for i, n in enumerate(arg_names)}
        _, _, fn, _ = _build_graph_fn(symbol, train=False)
        self._param_slots = [arg_order[n] for n in self._param_names]
        self._free_slots = [arg_order[n] for n in self._free_names]
        self._data_slots = [arg_order[n] for n in self._data_names]
        self._n_args = len(arg_names)

        def wrapped(rng_key, arg_vals, aux_vals):
            import jax.random as jr

            from .. import random as _random

            if hasattr(jr, "wrap_key_data") and \
                    getattr(rng_key, "dtype", None) == jax.numpy.uint32:
                rng_key = jr.wrap_key_data(rng_key)
            with _random.trace_key_scope(rng_key):
                return fn(arg_vals, aux_vals)

        self._jitted = jax.jit(wrapped)
        import jax.random as jr

        key = jr.PRNGKey(0)  # eval mode draws nothing; fixed = deterministic
        self._rng_data = jr.key_data(key) if hasattr(jr, "key_data") else key
        if self._replicated_sh is not None:
            # every program input must be COMMITTED to the engine's mesh
            # slice: an uncommitted array defaults to device 0, which may
            # not even be in this slice
            self._rng_data = jax.device_put(self._rng_data,
                                            self._replicated_sh)

        # explicit program accounting (the fused-update cache-key idiom):
        # one entry per distinct input signature ever compiled. The
        # TraceLinter serve-retrace-churn rule audits this log.
        self._programs: Dict[tuple, int] = {}   # sig -> execution count
        # counters mutate from concurrent warmup threads (+= is not atomic
        # once XLA releases the GIL mid-infer); compile_log appends are
        self._stat_lock = threading.Lock()
        self.compile_log: List[dict] = []
        self._free_cache: Dict[tuple, tuple] = {}
        self.exec_count = 0
        # what progcache.build made of a signature: ONE executable,
        # analyzed (flops/bytes/HBM into compile_log) and then executed
        self._aot: Dict[tuple, object] = {}      # sig -> compiled executable

        # persistent AOT program cache (mxnet_tpu/progcache.py): explicit
        # dir (an artifact's programs/ payload) beats the process-global
        # env-armed cache. Key statics = everything that determines the
        # traced program short of the batch signature — the graph itself,
        # argument layout, pad value, and mesh placement; progcache adds
        # the platform/topology/version fingerprint per entry.
        self._progcache = (progcache.ProgramCache(progcache_dir)
                           if progcache_dir else progcache.cache())
        self._key_statics = self._compute_key_statics()
        self.cache_hits = 0

    # ------------------------------------------------------------------
    # properties / stats
    # ------------------------------------------------------------------
    @property
    def version(self) -> int:
        """Monotonic parameter generation (bumped by :meth:`reload`)."""
        return self._params.version

    @property
    def num_programs(self) -> int:
        """Distinct compiled programs so far (the bounded quantity)."""
        return len(self._programs)

    @property
    def data_names(self) -> List[str]:
        return list(self._data_names)

    def _mesh_ctx(self):
        """Trace-time scope: model code (ring attention etc.) discovers the
        engine's mesh slice via ``parallel.current_mesh()``. No-op when the
        engine is unsharded."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from ..parallel.mesh import mesh_scope

        return mesh_scope(self.mesh)

    def _compute_key_statics(self):
        """The serve-program statics fed to ``progcache.program_key``:
        graph json (hashed), argument layout, avals, pad value, and — for
        a sharded engine — the mesh axes + concrete device ids (a program
        compiled for one slice must never load onto another)."""
        mesh_desc = None
        if self.mesh is not None:
            mesh_desc = (tuple(self.mesh.axis_names),
                         tuple(self.mesh.devices.shape),
                         tuple(int(d.id) for d in self.mesh.devices.flat),
                         repr(self._data_spec))
        return (self.symbol.tojson().encode("utf-8"),
                tuple(self._data_names), tuple(self._param_names),
                tuple(self._aux_names), tuple(self._free_names),
                self._param_avals, self._aux_avals, self._pad_value,
                mesh_desc)

    def _program_key(self, sig, bucket: int):
        """One :class:`~mxnet_tpu.progcache.ProgramKey` per signature —
        the SAME derivation the device-plane cost registry and the
        persistent cache file names use (progcache.program_key)."""
        return progcache.program_key("serve", f"bucket{bucket}",
                                     (self._key_statics, sig))

    def stats(self) -> dict:
        staged = self._staged
        out = {
            "version": self.version,
            "staged_version": staged.version if staged is not None else None,
            "buckets": list(self.buckets),
            "num_programs": self.num_programs,
            "executions": self.exec_count,
            "programs": {repr(k): v for k, v in self._programs.items()},
            "compiles": len(self.compile_log),
            "cache_hits": self.cache_hits,
        }
        if self._progcache is not None:
            out["progcache"] = dict(self._progcache.stats,
                                    dir=self._progcache.root)
        if self.mesh is not None:
            from ..parallel.mesh import mesh_axes

            out["mesh"] = mesh_axes(self.mesh)
            out["mesh_devices"] = int(self.mesh.devices.size)
            out["sharded_params"] = sum(
                1 for sh in self._param_sh.values()
                if getattr(sh, "spec", None) and any(
                    ax is not None for ax in sh.spec))
        return out

    # ------------------------------------------------------------------
    # bucketing
    # ------------------------------------------------------------------
    def bucket_for(self, n: int) -> Optional[int]:
        """Smallest bucket ≥ n, or None when n exceeds the largest (the
        caller chunks)."""
        for b in self.buckets:
            if b >= n:
                return b
        return None

    def _free_vals(self, batch: int, data_shapes) -> tuple:
        """Zero tensors for non-data, non-param graph arguments (labels),
        shaped by shape inference at this bucket. Cached per signature."""
        key = (batch, tuple(data_shapes))
        vals = self._free_cache.get(key)
        if vals is None:
            import jax.numpy as jnp

            if self._free_names:
                from ..symbol.symbol import infer_shapes

                shapes = dict(zip(self._data_names, data_shapes))
                inferred, _ = infer_shapes(self.symbol, shapes)
                missing = [n for n in self._free_names if n not in inferred]
                if missing:
                    raise ServeError(
                        f"cannot infer shapes for unbound arguments "
                        f"{missing}; pass them as arg_params or data_names")
                vals = tuple(jnp.zeros(inferred[n], jnp.float32)
                             for n in self._free_names)
                if self._replicated_sh is not None:
                    import jax

                    vals = tuple(jax.device_put(v, self._replicated_sh)
                                 for v in vals)
            else:
                vals = ()
            self._free_cache[key] = vals
        return vals

    def _data_sharding(self, shape):
        """Batch placement for one (padded) request array: the ``data_spec``
        pruned against this mesh and shape — sharded over ``dp`` when the
        bucket divides, replicated otherwise (a pure-``tp`` replica group
        always replicates the batch; the weights are what is sharded).
        Cached per shape (the _free_cache idiom): shapes are bounded by
        the bucket list, and rebuilding the pruned NamedSharding per
        request would be pure repeated work on the hot path."""
        sh = self._data_sh_cache.get(shape)
        if sh is None:
            from jax.sharding import PartitionSpec as P

            from ..parallel.sharding import batch_sharding

            spec = self._data_spec if self._data_spec is not None \
                else P("dp")
            sh = batch_sharding(self.mesh, spec, shape)
            self._data_sh_cache[shape] = sh
        return sh

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------
    def infer(self, inputs, n_valid: Optional[int] = None
              ) -> Tuple[List[np.ndarray], int]:
        """Run one (possibly padded) batch. ``inputs``: one array per data
        name, equal leading dim. Returns ``(outputs, param_version)`` with
        outputs as host numpy sliced back to ``n_valid`` rows.

        Batches larger than the top bucket are chunked internally (each
        chunk still hits a bucketed program); the version is taken from the
        first chunk's snapshot — chunks of one oversized request could in
        principle straddle a reload, which is the documented cost of
        sending a request bigger than max_batch_size.
        """
        import jax

        from .. import profiler

        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        if len(inputs) != len(self._data_names):
            raise ServeError(
                f"expected {len(self._data_names)} input(s) "
                f"({self._data_names}), got {len(inputs)}")
        arrays = [np.ascontiguousarray(np.asarray(x)) for x in inputs]
        n = int(arrays[0].shape[0]) if arrays[0].ndim else 1
        for a in arrays[1:]:
            if int(a.shape[0]) != n:
                raise ServeError("inputs disagree on batch dimension: "
                                 f"{[x.shape for x in arrays]}")
        if n == 0:
            raise ServeError("empty request (0 rows)")
        if n_valid is None:
            n_valid = n
        bucket = self.bucket_for(n)
        if bucket is None:
            # chunk an oversized batch through the top bucket
            top = self.max_batch_size
            pieces: List[List[np.ndarray]] = []
            version = None
            for lo in range(0, n, top):
                outs, v = self.infer([a[lo:lo + top] for a in arrays])
                version = v if version is None else version
                pieces.append(outs)
            merged = [np.concatenate([p[i] for p in pieces], axis=0)
                      for i in range(len(pieces[0]))]
            return [m[:n_valid] for m in merged], version

        pad = bucket - n
        if pad:
            arrays = [np.concatenate(
                [a, np.full((pad,) + a.shape[1:], self._pad_value, a.dtype)],
                axis=0) for a in arrays]
        sig = _sig_of(arrays)
        if self.mesh is not None:
            # commit the padded batch onto the mesh slice (dp-sharded when
            # the spec and bucket allow, replicated otherwise) — the sig is
            # taken from the host shapes above, so sharding never changes
            # the program-accounting key
            arrays = [jax.device_put(a, self._data_sharding(a.shape))
                      for a in arrays]
        free_vals = self._free_vals(bucket, [tuple(a.shape) for a in arrays])
        snapshot = self._params  # atomic: old-or-new, never mixed

        if profiler.counting_dispatches():
            profiler.count_dispatch("compiled")
            profiler.count_dispatch("h2d", len(arrays))
        arg_vals: List = [None] * self._n_args
        for slot, v in zip(self._param_slots, snapshot.arg_vals):
            arg_vals[slot] = v
        for slot, v in zip(self._free_slots, free_vals):
            arg_vals[slot] = v
        for slot, v in zip(self._data_slots, arrays):
            arg_vals[slot] = v
        rec = obs.enabled()
        t0 = time.monotonic() if rec else 0.0
        is_compile = sig not in self._programs
        cache_hit = False
        if is_compile:
            entry = {
                "sig": sig, "bucket": bucket,
                "param_avals": self._param_avals,
                "version_at_compile": snapshot.version,
                "cache_hit": False,
            }
            pc = self._progcache
            if pc is not None or rec:
                # one build per signature (a cache hit deserializes the
                # SAME machine code an earlier process compiled): cost and
                # memory analysis into the compile_log entry, the
                # executable into the sig cache (params stay traced
                # arguments — reload still swaps arrays without touching
                # the program)
                with self._mesh_ctx():
                    self._aot[sig], built = progcache.build(
                        self._jitted,
                        (self._rng_data, arg_vals, list(snapshot.aux_vals)),
                        key=self._program_key(sig, bucket), cache=pc,
                        meta={"bucket": bucket})
                entry.update(built)
                cache_hit = built["cache_hit"]
            self.compile_log.append(entry)
            if cache_hit:
                with self._stat_lock:
                    self.cache_hits += 1
        fn = self._aot.get(sig, self._jitted)
        with obs.trace.span("serve.execute", bucket=bucket, rows=n_valid,
                            compile=is_compile, cache_hit=cache_hit,
                            version=snapshot.version):
            with self._mesh_ctx():
                outs, _new_aux = fn(self._rng_data, arg_vals,
                                    list(snapshot.aux_vals))
            # materialize on host: the wire sends numpy, and an unwaited
            # future would let the execute span under-report real latency
            # (intentional sync: THE accounted d2h hop — copytrack counts
            # it so the wire_hop bench can subtract execute time)
            copytrack.TRACKER.host_sync("serve.engine.device_get")
            host = jax.device_get(list(outs))  # lint: disable=host-sync-on-hot-path
        if profiler.counting_dispatches():
            profiler.count_dispatch("d2h", len(host))
        if rec:
            dt = time.monotonic() - t0
            if is_compile and not cache_hit:
                obs.inc("serve.compile")
                obs.observe("serve.compile_seconds", dt)
            elif cache_hit:
                # a deserialize is not an XLA compile — count it apart so
                # "zero fresh compilations on warm start" is checkable;
                # and dt here includes the disk read + CRC + load, so it
                # stays out of the steady-state execute histogram too
                obs.inc("serve.cache_hit")
                obs.observe("serve.deserialize_seconds", dt)
            else:
                obs.observe("serve.execute_seconds", dt)
            obs.inc("serve.rows_executed", n_valid)
            obs.inc("serve.rows_padding", bucket - n_valid)
            obs.device.sample()  # live-HBM counter track, per batch
        with self._stat_lock:
            self._programs[sig] = self._programs.get(sig, 0) + 1
            self.exec_count += 1
        return ([np.asarray(o)[:n_valid] if np.ndim(o) else np.asarray(o)
                 for o in host], snapshot.version)

    def predict(self, *inputs):
        """Convenience single-call inference: numpy in, numpy out (one
        array, or a list when the graph has multiple outputs)."""
        outs, _version = self.infer(list(inputs))
        return outs[0] if len(outs) == 1 else outs

    def warmup(self, *feature_shapes, dtype=np.float32,
               concurrency: Optional[int] = None) -> int:
        """Pre-compile every bucket for the given per-row feature shape(s)
        (one tuple per data input; call once per distinct signature).
        Returns the number of programs compiled. Servers call this before
        flipping readiness so the first customer request never eats an XLA
        compile.

        Buckets warm **concurrently** (a thread pool over per-bucket
        compiles — XLA releases the GIL while it optimizes, so distinct
        buckets' compilations genuinely overlap; cache-hit deserialization
        runs at the same parallelism). ``concurrency`` caps the pool
        (``MXNET_SERVE_WARMUP_THREADS`` overrides the default of
        min(buckets, cores); 1 restores the serial path)."""
        shapes = list(feature_shapes) or [()]
        if len(shapes) != len(self._data_names):
            raise ServeError(
                f"warmup needs one feature shape per data input "
                f"({len(self._data_names)}), got {len(shapes)}")
        before = self.num_programs
        todo = [b for b in self.buckets
                if _sig_of([np.zeros((b,) + tuple(s), dtype)
                            for s in shapes]) not in self._programs]
        if concurrency is None:
            import os as _os

            from ..obs._env import env_int

            concurrency = env_int(
                "MXNET_SERVE_WARMUP_THREADS",
                min(len(todo) or 1, max(1, _os.cpu_count() or 2)))

        def _one(b):
            self.infer([np.zeros((b,) + tuple(s), dtype) for s in shapes])

        if concurrency <= 1 or len(todo) <= 1:
            for b in todo:
                _one(b)
        else:
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(
                    max_workers=min(concurrency, len(todo)),
                    thread_name_prefix="mxnet-serve-warmup") as pool:
                # list() re-raises the first worker's exception here,
                # matching the serial path's failure surface
                list(pool.map(_one, todo))
        return self.num_programs - before

    def save_programs(self, directory: str, keep: Optional[int] = None,
                      durable: bool = True) -> int:
        """Export this engine's compiled executables into ``directory`` as
        a persistent program-cache payload (the artifact ``programs/``
        convention ``serve.load`` auto-discovers — ``serve.ship_programs``
        wraps this with descriptor bookkeeping). Signatures that ran on
        the plain jit path (no cache, ``obs`` off) are built from their
        recorded signature so every warmed bucket ships. Returns the
        number of entries written."""
        pc = progcache.ProgramCache(directory, keep=keep or 0,
                                    durable=durable)
        snapshot = self._params
        logged = {e["sig"]: e for e in self.compile_log}
        for sig in list(self._programs):
            bucket = int(sig[0][0][0])
            pk = self._program_key(sig, bucket)
            compiled = self._aot.get(sig)
            if compiled is None:
                # same trace scope as infer's build: model code (ring
                # attention etc.) discovers the mesh slice at trace time —
                # an unscoped retrace would ship (and install) the
                # non-mesh variant of the program
                with self._mesh_ctx():
                    self._aot[sig], _ = progcache.build(
                        self._jitted, self._args_for_sig(sig, snapshot),
                        key=pk, cache=pc, meta={"bucket": bucket})
            else:
                cost = {k: logged[sig][k] for k in progcache.COST_FIELDS
                        if k in logged[sig]}
                pc.put(pk, compiled, meta=dict(cost, bucket=bucket))
        return pc.stats["write"]

    def _args_for_sig(self, sig, snapshot) -> tuple:
        """Rebuild example program arguments from a recorded signature
        (zero-filled batches — only avals matter to ``lower``)."""
        import jax

        arrays = [np.zeros(shape, dtype) for shape, dtype in sig]
        if self.mesh is not None:
            arrays = [jax.device_put(a, self._data_sharding(a.shape))
                      for a in arrays]
        free_vals = self._free_vals(int(sig[0][0][0]),
                                    [tuple(a.shape) for a in arrays])
        arg_vals: List = [None] * self._n_args
        for slot, v in zip(self._param_slots, snapshot.arg_vals):
            arg_vals[slot] = v
        for slot, v in zip(self._free_slots, free_vals):
            arg_vals[slot] = v
        for slot, v in zip(self._data_slots, arrays):
            arg_vals[slot] = v
        return (self._rng_data, arg_vals, list(snapshot.aux_vals))

    # ------------------------------------------------------------------
    # hot reload
    # ------------------------------------------------------------------
    def _validated_param_set(self, arg_params, aux_params):
        """Shared reload validation: names, shapes, and dtypes must match
        the serving set — a drifted checkpoint would silently recompile
        every bucket (and is almost always a deploy mistake). Returns the
        device-resident ``(new_args, new_aux)`` tuples."""
        arg_params = dict(arg_params or {})
        aux_params = dict(aux_params or {})
        missing = [n for n in self._param_names if n not in arg_params]
        missing += [n for n in self._aux_names if n not in aux_params]
        if missing:
            raise ServeError(f"reload missing parameters: {missing}")
        # the new generation lands with the SAME shardings the serving set
        # was placed with (the dict resolved at construction): the compiled
        # programs' layouts are part of the engine contract, not of any one
        # parameter generation
        new_args = tuple(_to_device(arg_params[n], self._param_sh.get(n))
                         for n in self._param_names)
        new_aux = tuple(_to_device(aux_params[n], self._replicated_sh)
                        for n in self._aux_names)
        for names, vals, avals in (
                (self._param_names, new_args, self._param_avals),
                (self._aux_names, new_aux, self._aux_avals)):
            for name, v, (shape, dtype) in zip(names, vals, avals):
                got = (tuple(v.shape), str(v.dtype))
                if got != (shape, dtype):
                    raise ServeError(
                        f"reload aval mismatch for {name!r}: serving "
                        f"{(shape, dtype)}, new checkpoint {got} — this "
                        "would retrace every bucket; deploy a new engine "
                        "for a changed architecture")
        return new_args, new_aux

    def prepare_reload(self, arg_params, aux_params=None, *,
                       version: Optional[int] = None) -> int:
        """Phase one of a two-phase reload: do ALL fallible work now —
        validate against the serving avals, place the new generation on
        device — and stage it without flipping. :meth:`commit_reload` is
        then a pure pointer swap that only process death can stop, which is
        what makes a *fleet-wide* flip atomic (serve/fleet.py): every
        replica prepares, then every live replica's commit is infallible.

        ``version`` pins the staged generation number (the fleet stamps its
        own coherent version across replicas); default is current + 1.
        Returns the staged version."""
        new_args, new_aux = self._validated_param_set(arg_params, aux_params)
        with self._lock:
            v = int(version) if version is not None \
                else self._params.version + 1
            self._staged = _ParamSet(v, new_args, new_aux)
        obs.event("serve.reload_prepared", version=v)
        return v

    def commit_reload(self) -> int:
        """Phase two: flip the staged generation live (one reference swap;
        in-flight executions keep the snapshot they started with). Raises
        when nothing is staged. Returns the now-serving version."""
        with self._lock:
            if self._staged is None:
                raise ServeError("no prepared reload to commit")
            self._params, self._staged = self._staged, None
            version = self._params.version
        obs.inc("serve.reloads")
        obs.event("serve.reload", version=version)
        return version

    def abort_reload(self) -> None:
        """Discard a staged generation (two-phase rollback; idempotent)."""
        with self._lock:
            self._staged = None

    def reload(self, arg_params, aux_params=None, *,
               version: Optional[int] = None) -> int:
        """Swap in a new parameter generation without dropping in-flight
        work (single-replica path). One lock acquisition, and the staged
        slot is untouched — a legacy reload racing a two-phase fleet flip
        can neither clobber the staged generation nor be half-applied.
        Returns the new version."""
        new_args, new_aux = self._validated_param_set(arg_params, aux_params)
        with self._lock:
            v = int(version) if version is not None \
                else self._params.version + 1
            self._params = _ParamSet(v, new_args, new_aux)
        obs.inc("serve.reloads")
        obs.event("serve.reload", version=v)
        return v

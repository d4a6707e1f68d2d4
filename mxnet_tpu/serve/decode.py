"""Autoregressive decode engine: paged KV cache + continuous batching.

The generation counterpart of ``serve/engine.py``. Two halves:

:class:`DecodeEngine` — the device half. Exactly TWO compiled program
shapes serve every generation:

- a **bucketed prefill program** (one trace per prompt bucket; buckets
  are powers of two in *positions*, always multiples of the page size):
  full causal forward over one padded prompt (the layers under one
  ``lax.scan`` over their stacked weights: one compiled layer body, a
  program twenty times smaller to compile, cache and load than 24
  unrolled), every layer's K/V written in place into the page pool at the
  sequence's page ids, first token sampled on-device;
- a **single decode-step program** (one trace, period): one new position
  for every slot of the fixed continuous batch — embed, per-layer
  paged-KV write + paged attention (ops/flash_attention.decode_attention),
  LM head, on-device greedy/temperature sampling.

The engine takes the **model by interface** (``DecodeEngine``'s docstring):
what a cache row is, the prefill and step bodies and the paged read are the
model's; pages, programs, accounting and the scheduler are shared by every
model (``models.transformer.TransformerDecodeModel`` — K and V of every head
side by side, float32; ``models.mla_moe.MLAMoEDecodeModel`` — one latent
row and no head axis, bfloat16).

Growing a sequence never changes a program shape: the KV pool is one
fixed array ``(pages, layers, page_size) + model.cache_row`` of
``model.cache_dtype`` and growth is a host-side page-table edit
(serve/kvcache.py) — the engine.py pad-and-slice idiom applied to the
time axis. The pool is read and written where it lies: the programs
donate it, write single rows (step) or whole pages (prefill) in place,
and hand the attention paths the whole array plus a static layer index;
between a layer's write and its attention no operation slices, copies or
lays out again any part of it, and on a TPU it rests in the layout the
Mosaic kernel reads (``_row_major``). ``stats()["step_program"]`` is the
compiler's account of that (``temp_bytes``, ``bytes_accessed``).

Program accounting mirrors InferenceEngine exactly: ``compile_log``
entries, progcache get/put so a scaled-out replica deserializes instead
of compiling (``decode.cache_hit`` vs ``decode.compile``), and
analysis/trace.py::check_decode_engine proves the
``len(prompt_buckets) + 1`` program bound.

:class:`DecodeScheduler` — the host half, beside serve/batcher.py but
token-granular: requests **join and leave the running decode batch at
step boundaries** instead of waiting for a drain. Priority lanes and the
batcher's shed discipline (queue watermark → 429, dead-on-arrival and
mid-generation deadline → DeadlineExceeded, draining → Draining) carry
over; page exhaustion sheds the newest admission rather than stalling
the batch. Per-step ``decode.occupancy`` gauge, ``decode.kv_pages_used``
from the pool, per-token spans onto the request's trace context.

Wire integration: serve/server.py streams tokens per
``OP_INFER_STREAM`` (wire.py codes 44-47); ``ServeClient.generate()``
and ``Router.generate`` consume the same iterator protocol this module's
``DecodeScheduler.generate`` exposes.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from .. import copytrack, obs, tsan
from ..obs import context as obs_context
from ..obs._env import env_float, env_int
from .engine import DeadlineExceeded, Draining, RequestRejected, ServeError
from .kvcache import SCRATCH_PAGE, PagePool, PagesExhausted, pages_for

__all__ = ["DecodeEngine", "DecodeScheduler", "StreamHandle",
           "default_decode_buckets"]


def default_decode_buckets(max_prompt: int, page_size: int) -> List[int]:
    """Power-of-two prompt buckets, every one a multiple of the page size
    (so a bucketed prefill always fills whole pages): page 16, max 100 →
    [16, 32, 64, 112]."""
    max_prompt = int(max_prompt)
    page_size = int(page_size)
    if max_prompt < 1:
        raise ValueError("max_prompt must be >= 1")
    cap = pages_for(max_prompt, page_size) * page_size
    out = []
    b = page_size
    while b < cap:
        out.append(b)
        b *= 2
    out.append(cap)
    return out


class DecodeEngine:
    """Paged-KV generation engine around a decode model.

    Parameters
    ----------
    lm : decode model, TransformerLM or dict
        A **decode model**: an object with ``cfg`` (a dict with
        ``max_length``), ``params`` (a tree of device arrays, passed to the
        programs as their first argument), ``layers``, ``cache_row`` and
        ``cache_dtype`` (the pool is ``(pages, layers, page_size) +
        cache_row`` of that dtype), ``counters`` (full names, as
        ``"moe.held"``, of the int32 values its bodies return beside the
        logits; may be empty), and three pure
        functions: ``prefill(params, tokens (1, S), length) -> (last logits
        (V,), rows (layers, S) + cache_row, counters or None)``,
        ``step(params, tokens (B,), positions (B,), live (B,), attend) ->
        (logits (B, V), counters or None)`` where ``attend(layer, query,
        row)`` writes ``row`` (B,) + cache_row at the step's positions and
        returns ``attention(query, pool, layer, page_tables, lengths)``, and
        that ``attention`` itself, the paged read of one layer.
        Anything else is taken for a ``TransformerLM`` (an initialized
        block, or its config dict when ``params`` is given) and wrapped in
        ``models.transformer.TransformerDecodeModel``.
    params : dict, optional
        Pre-extracted param dict (host numpy) when ``lm`` is a config.
    slots : int
        Width of the continuous decode batch — THE shape of the single
        decode-step program. Default ``MXNET_DECODE_SLOTS`` (8).
    page_size : int
        KV positions per page. Default ``MXNET_DECODE_PAGE_SIZE`` (16).
    num_pages : int
        Pool size (page 0 is reserved scratch). Default
        ``MXNET_DECODE_PAGES`` (64).
    prompt_buckets : list of int, optional
        Prefill pad targets; defaults to ``default_decode_buckets`` over
        the model's max_length (capped at the pool's capacity).
    progcache_dir : str, optional
        Explicit persistent program cache; defaults to the process-wide
        ``progcache.cache()`` (``MXNET_PROGCACHE=1``).
    """

    def __init__(self, lm, params=None, *, slots: Optional[int] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 prompt_buckets: Optional[List[int]] = None,
                 progcache_dir: Optional[str] = None):
        if hasattr(lm, "prefill") and hasattr(lm, "step"):
            self.model = lm
        else:
            from ..models.transformer import TransformerDecodeModel

            self.model = TransformerDecodeModel(lm, params)
        self.cfg = self.model.cfg
        self.slots = int(slots if slots is not None
                         else env_int("MXNET_DECODE_SLOTS", 8))
        self.page_size = int(page_size if page_size is not None
                             else env_int("MXNET_DECODE_PAGE_SIZE", 16))
        self.num_pages = int(num_pages if num_pages is not None
                             else env_int("MXNET_DECODE_PAGES", 64))
        self.max_length = int(self.cfg["max_length"])
        # page-table width of the step program: enough for a full-context
        # sequence, but never more than the pool could back
        self.max_pages = min(pages_for(self.max_length, self.page_size),
                             self.num_pages - 1)
        max_prompt = min(self.max_length,
                         (self.num_pages - 1) * self.page_size)
        if prompt_buckets is None:
            prompt_buckets = default_decode_buckets(max_prompt,
                                                    self.page_size)
        buckets = sorted({int(b) for b in prompt_buckets})
        for b in buckets:
            if b % self.page_size or b < 1 or b > max_prompt:
                raise ValueError(
                    f"prompt bucket {b} must be a positive multiple of "
                    f"page_size={self.page_size} and <= {max_prompt}")
        self.buckets = buckets
        self.pool = PagePool(self.num_pages, self.page_size)

        import jax
        import jax.numpy as jnp

        self._params = self.model.params
        self._param_avals = tuple(
            (tuple(a.shape), str(a.dtype))
            for a in jax.tree_util.tree_leaves(self._params))
        row, dtype = tuple(self.model.cache_row), self.model.cache_dtype
        pool_shape = (self.num_pages, self.model.layers, self.page_size) + row
        self.cache_row_bytes = int(np.prod(row)) * jnp.dtype(dtype).itemsize
        if jax.default_backend() == "tpu":
            pool = self._row_major(
                pool_shape,
                jax.tree_util.tree_leaves(self._params)[0].sharding)
            # donating the pool buffer makes the KV writes in-place on TPU;
            # CPU/GPU test backends would only warn about it
            donate = (1,)
        else:
            pool, donate = None, ()
        self.kv = jax.jit(lambda: jnp.zeros(pool_shape, dtype),
                          out_shardings=pool)()
        self._prefill_jit, self._step_jit = self._jit_programs(pool, donate)
        # what the model's bodies counted in the last program call, by
        # name; fetched with the tokens, in the same transfer
        self.last_counters: Dict[str, int] = {}

        # program accounting — mirrors InferenceEngine so the TraceLinter
        # and the coldstart idiom read both the same way
        self._programs: Dict[tuple, int] = {}
        self._aot: Dict[tuple, object] = {}
        self._sig_key: Dict[tuple, object] = {}
        self.compile_log: List[dict] = []
        self.cache_hits = 0
        self.exec_count = 0
        self._stat_lock = tsan.lock("serve.decode.stats")

        from .. import progcache as _progcache

        self._progcache = (_progcache.ProgramCache(progcache_dir)
                           if progcache_dir else _progcache.cache())
        self._key_statics = (
            type(self.model).__name__, tuple(sorted(self.cfg.items())),
            self.slots, self.page_size, self.num_pages, self.max_pages,
            tuple(self.buckets), self._param_avals)

    # -- pure device programs ------------------------------------------

    @staticmethod
    def _row_major(shape, sharding):
        """The layout the pool rests in on a TPU: row-major, which is what
        the Mosaic call reads. Left to itself the TPU client lays an array
        out by whichever axis order pads least (the page axis minor-most
        for some widths), and every program would then convert the whole
        pool on its way in and again on its way out."""
        from jax.experimental.layout import Format, Layout

        return Format(Layout(tuple(range(len(shape)))), sharding)

    def _jit_programs(self, pool, donate):
        """(prefill, step) jitted with the pool argument and result held
        to ``pool`` (a ``Format``, or None for the backend's default)."""
        import jax

        def jit(fn, n_args):
            return jax.jit(fn, donate_argnums=donate,
                           in_shardings=(None, pool) + (None,) * (n_args - 2),
                           out_shardings=(pool, None))

        return jit(self._prefill_fn, 7), jit(self._step_fn, 8)

    def _prefill_fn(self, params, kv, tokens, length, page_ids, seed, temp):
        """One padded prompt (1, S) → KV pages written, first token.
        S is the bucket (multiple of page_size); ``page_ids``
        (S // page_size,) are the sequence's pages in position order.
        Pad positions scatter garbage rows — masked by ``length`` until
        each slot is overwritten by a decode step."""
        import jax
        import jax.numpy as jnp

        from ..models.transformer import sample_token

        last, rows, counters = self.model.prefill(params, tokens, length)
        n = tokens.shape[1] // self.page_size

        # (L, S) + row → (L, n, page) + row, then page by page —
        # every layer's rows of the page in one update — in place at the
        # sequence's page ids. (One scatter would say the same; but for
        # head counts off the 8-row tile XLA's TPU scatter wants the pool
        # in a layout of its own, and converts the whole pool there and
        # back.)
        rows = rows.reshape((self.model.layers, n, self.page_size)
                            + rows.shape[2:])

        def write_page(j, kv):
            page = jax.lax.dynamic_slice_in_dim(rows, j, 1, axis=1)
            return jax.lax.dynamic_update_slice(
                kv, jnp.swapaxes(page, 0, 1),
                (page_ids[j],) + (0,) * (kv.ndim - 1))

        kv = jax.lax.fori_loop(0, n, write_page, kv)
        tok = sample_token(last[None], jax.random.PRNGKey(seed), temp)
        return kv, (tok[0], counters)

    def _step_fn(self, params, kv, tokens, positions, page_tables, lengths,
                 seed, temps):
        """One token for every slot. tokens/positions/lengths (B,),
        page_tables (B, max_pages). Inactive slots carry length 0 and a
        scratch page table — their writes land on the scratch page and
        their outputs are garbage the host discards."""
        import jax
        import jax.numpy as jnp

        from ..models.transformer import sample_token

        rows = jnp.arange(self.slots)
        pids = page_tables[rows, positions // self.page_size]
        offs = positions % self.page_size

        def attend(layer, query, row):
            nonlocal kv
            kv = kv.at[pids, layer, offs].set(row)
            return self.model.attention(query, kv, layer, page_tables,
                                        lengths)

        logits, counters = self.model.step(params, tokens, positions,
                                           lengths > 0, attend)
        toks = sample_token(logits, jax.random.PRNGKey(seed), temps)
        return kv, (toks, counters)

    # -- program accounting (the engine.py compile path, decode-keyed) --

    def _program_key(self, sig, label: str):
        pk = self._sig_key.get(sig)
        if pk is None:
            from .. import progcache as _progcache

            pk = _progcache.program_key("decode", label,
                                        (self._key_statics, sig))
            self._sig_key[sig] = pk
        return pk

    def _execute(self, kind: str, label: str, jitted, args):
        """Run one program call with full accounting: compile_log entry +
        progcache get/put on a fresh signature, ``decode.*`` metrics, and
        the pool array swap. Returns the sampled token(s) on host."""
        import jax

        sig = (kind,) + tuple(
            (tuple(np.shape(a)), str(np.asarray(a).dtype)) for a in args)
        is_compile = sig not in self._programs
        cache_hit = False
        call_args = (self._params, self.kv) + tuple(args)
        if is_compile:
            entry = {"sig": sig, "kind": kind, "label": label,
                     "param_avals": self._param_avals}
            pc = self._progcache
            pk = None
            if pc is not None:
                pk = self._program_key(sig, label)
                entry["program_key"] = pk.digest
                cached = pc.get(pk)
                if cached is not None:
                    cache_hit = True
                    self._aot[sig] = cached.executable
                    cost = obs.device.adopt_cached_cost(pk, cached.meta)
                    if cost:
                        entry.update(cost)
            entry["cache_hit"] = cache_hit
            if not cache_hit:
                # always through the AOT path: the one compile is measured
                # (stats()["step_program"]) and run, observed or not
                if obs.device.active():
                    compiled, cost = obs.device.capture(
                        jitted, call_args, site="decode", label=label,
                        key=pk)
                else:
                    from .. import progcache as _progcache

                    compiled = _progcache.aot_compile(jitted, call_args)
                    cost = obs.device.analyze_compiled(compiled)
                if compiled is not None:
                    self._aot[sig] = compiled
                    if pc is not None:
                        pc.put(pk, compiled,
                               meta=dict(cost or {}, kind=kind))
                if cost:
                    entry.update(cost)
            self.compile_log.append(entry)
            if cache_hit:
                with self._stat_lock:
                    self.cache_hits += 1
        fn = self._aot.get(sig, jitted)
        with obs.trace.span("decode.execute", kind=kind, label=label,
                            compile=is_compile, cache_hit=cache_hit):
            # argument upload and launch: returns before the device is done
            with obs.trace.span("decode.dispatch"):
                kv, out = fn(*call_args)
            self.kv = kv
            # the step's sampled tokens ARE the wire payload — this d2h is
            # the one accounted sync of the decode hot path
            copytrack.TRACKER.host_sync("serve.decode.device_get")
            # the wait for the device's last operation, then the copy back:
            # device-idle time under this span is the host not yet awake
            with obs.trace.span("decode.device_get"):
                toks, counters = jax.device_get(out)  # lint: disable=host-sync-on-hot-path
            host = np.asarray(toks)
        self.last_counters = ({} if counters is None else dict(
            zip(self.model.counters, (int(c) for c in counters))))
        # an operator's "which call recompiled, which deserialized" alarm,
        # one increment per first call of a signature (the decode.execute
        # span carries compile, cache_hit and the duration)
        if is_compile and not cache_hit:
            obs.inc("decode.compile")
        elif cache_hit:
            obs.inc("decode.cache_hit")
        with self._stat_lock:
            self._programs[sig] = self._programs.get(sig, 0) + 1
            self.exec_count += 1
        return host

    # -- host-facing calls ---------------------------------------------

    def bucket_for(self, prompt_len: int) -> int:
        for b in self.buckets:
            if b >= prompt_len:
                return b
        raise RequestRejected(
            f"prompt length {prompt_len} exceeds max bucket "
            f"{self.buckets[-1]}")

    def prefill(self, tokens: np.ndarray, page_ids: List[int], *,
                temperature: float = 0.0, seed: int = 0) -> int:
        """Prefill one prompt into its pages; returns the first sampled
        token. ``tokens`` is the unpadded 1-D prompt; ``page_ids`` must
        cover its bucket (``bucket_for(len) // page_size`` pages)."""
        tokens = np.asarray(tokens, np.uint32).astype(np.int32)
        n = int(tokens.shape[0])
        bucket = self.bucket_for(n)
        if len(page_ids) != bucket // self.page_size:
            raise ServeError(
                f"prefill needs {bucket // self.page_size} pages for "
                f"bucket {bucket}, got {len(page_ids)}")
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = tokens
        out = self._execute(
            "prefill", f"prefill{bucket}", self._prefill_jit,
            (padded, np.int32(n), np.asarray(page_ids, np.int32),
             np.uint32(seed), np.float32(temperature)))
        return int(out)

    def step(self, tokens, positions, page_tables, lengths, temps, *,
             seed: int = 0) -> np.ndarray:
        """One continuous-batch decode step; returns (slots,) int32
        sampled tokens (garbage at inactive rows, i.e. lengths == 0)."""
        return self._execute(
            "step", "step", self._step_jit,
            (np.asarray(tokens, np.int32), np.asarray(positions, np.int32),
             np.asarray(page_tables, np.int32),
             np.asarray(lengths, np.int32), np.uint32(seed),
             np.asarray(temps, np.float32)))

    def warmup(self) -> int:
        """Compile (or progcache-load) every prefill bucket plus the step
        program before traffic. Warmup calls write only the reserved
        scratch page. Returns the number of fresh XLA compiles."""
        before = sum(1 for e in self.compile_log if not e["cache_hit"])
        scratch_tables = np.full((self.slots, self.max_pages), SCRATCH_PAGE,
                                 np.int32)
        for b in self.buckets:
            self.prefill(np.zeros((b,), np.int32),
                         [SCRATCH_PAGE] * (b // self.page_size))
        self.step(np.zeros((self.slots,), np.int32),
                  np.zeros((self.slots,), np.int32), scratch_tables,
                  np.zeros((self.slots,), np.int32),
                  np.zeros((self.slots,), np.float32))
        return sum(1 for e in self.compile_log if not e["cache_hit"]) - before

    def stats(self) -> dict:
        with self._stat_lock:
            out = {
                "slots": self.slots,
                "page_size": self.page_size,
                "cache_row_bytes": self.cache_row_bytes,
                "buckets": list(self.buckets),
                "num_programs": len(self._programs),
                "executions": self.exec_count,
                "compiles": len(self.compile_log),
                "cache_hits": self.cache_hits,
                "programs": {repr(k): v for k, v in self._programs.items()},
            }
            step = next((e for e in self.compile_log
                         if e["kind"] == "step"), None)
        # the compiler's own account of the one step program (None until
        # it is built): temporaries far under one layer's share of the pool
        # say that no program slices, copies or lays the pool out again
        out["step_program"] = step and {
            k: step.get(k, 0) for k in ("temp_bytes", "bytes_accessed")}
        out["pool"] = self.pool.stats()
        if self._progcache is not None:
            out["progcache"] = dict(self._progcache.stats,
                                    dir=self._progcache.root)
        return out


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------


class StreamHandle:
    """Client half of one generation: a bounded event queue the scheduler
    feeds and ``generate`` drains. Events: ("token", tok, index),
    ("end", reason, n_tokens), ("error", exc). The queue is sized so the
    scheduler can always emit a full generation without blocking —
    backpressure past that cancels the stream instead of stalling the
    shared decode batch."""

    def __init__(self, capacity: int):
        self._q: "queue.Queue" = queue.Queue(maxsize=capacity)
        self._cancelled = threading.Event()

    def cancel(self) -> None:
        """Ask the scheduler to retire this generation at the next step
        boundary (its pages are reclaimed there)."""
        self._cancelled.set()

    def cancelled(self) -> bool:
        return self._cancelled.is_set()

    def _emit(self, ev) -> bool:
        try:
            self._q.put_nowait(ev)
            return True
        except queue.Full:
            return False

    def get(self, timeout: float):
        return self._q.get(timeout=timeout)


class _Gen:
    """One generation's scheduler-side state."""

    __slots__ = ("seq", "tokens", "prompt_len", "max_new", "deadline",
                 "priority", "temperature", "ctx", "handle", "produced",
                 "last_token", "t_submit", "t_admit", "seed")

    def __init__(self, seq, tokens, max_new, deadline, priority,
                 temperature, handle, seed):
        self.seq = seq
        self.tokens = tokens
        self.prompt_len = int(tokens.shape[0])
        self.max_new = max_new
        self.deadline = deadline
        self.priority = priority
        self.temperature = temperature
        self.ctx = obs_context.current()
        self.handle = handle
        self.produced = 0
        self.last_token = -1
        self.t_submit = time.monotonic()
        self.t_admit = 0.0
        self.seed = seed


class DecodeScheduler:
    """Token-level continuous batching over a :class:`DecodeEngine`.

    A single scheduler thread owns the engine: each loop iteration is one
    ``step()`` — admit queued requests into free slots (prefill at the
    step boundary), run ONE decode-step program over every active slot,
    distribute the sampled tokens, retire finished/cancelled/expired
    generations and free their pages. Requests therefore join and leave
    the running batch between steps, never mid-program.
    """

    def __init__(self, engine: DecodeEngine, *, max_queue: int = 64,
                 lanes: int = 2, default_timeout: Optional[float] = None,
                 eos_id: Optional[int] = None,
                 max_new_tokens: Optional[int] = None):
        self.engine = engine
        self.max_queue = int(max_queue)
        self.default_timeout = float(
            default_timeout if default_timeout is not None
            else env_float("MXNET_DECODE_TIMEOUT", 30.0))
        self.eos_id = eos_id
        self.max_new_tokens = int(
            max_new_tokens if max_new_tokens is not None
            else env_int("MXNET_DECODE_MAX_NEW", 64))
        self._cv = tsan.condition("serve.decode.cv")
        self._lanes: List[List[_Gen]] = [[] for _ in range(int(lanes))]
        self._slots: List[Optional[_Gen]] = [None] * engine.slots
        self._running = True
        self._draining = False
        self._seq = 0
        # shed discipline — the batcher.py aggregate/by-reason invariant:
        # self.shed == sum(shed_by_reason.values())
        self.shed = 0
        self.shed_by_reason = {"queue_full": 0, "deadline": 0,
                               "draining": 0, "pages": 0,
                               "backpressure": 0}
        self.submitted = 0
        self.completed = 0
        self.cancelled = 0
        self.steps = 0
        self.tokens_out = 0
        self.counted: Dict[str, int] = {}   # the model's counters, summed
        self._occupancy = 0.0
        self.stopped_clean = True
        self._thread = threading.Thread(target=self._loop,
                                        name="mxnet-decode-sched",
                                        daemon=True)
        self._thread.start()

    # -- admission ------------------------------------------------------

    def _qsize(self) -> int:
        return sum(len(l) for l in self._lanes)

    def _active(self) -> int:
        return sum(1 for g in self._slots if g is not None)

    def _shed(self, why: str, exc: ServeError):
        self.shed += 1
        self.shed_by_reason[why] += 1
        obs.inc(f"decode.shed_{why}")
        obs.tail.note(shed=why)
        raise exc

    def submit(self, tokens, *, max_new_tokens: Optional[int] = None,
               deadline_ms: Optional[float] = None, priority: int = 1,
               temperature: float = 0.0,
               seed: int = 0) -> StreamHandle:
        """Queue one generation; returns its :class:`StreamHandle`.
        Sheds synchronously (batcher discipline) when the queue is over
        watermark, the scheduler drains, or the deadline is already
        dead on arrival."""
        arr = np.ascontiguousarray(np.asarray(tokens, np.int64)
                                   .astype(np.int32)).reshape(-1)
        if arr.shape[0] < 1:
            raise RequestRejected("empty prompt")
        self.engine.bucket_for(arr.shape[0])  # rejects over-long prompts
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else self.max_new_tokens)
        max_new = max(1, min(max_new, self.engine.max_length
                             - arr.shape[0]))
        deadline = (time.monotonic() + deadline_ms / 1000.0
                    if deadline_ms is not None else None)
        lane = max(0, min(int(priority), len(self._lanes) - 1))
        handle = StreamHandle(capacity=max_new + 2)
        with self._cv:
            if not self._running or self._draining:
                self._shed("draining", Draining("decode scheduler draining"))
            if self._qsize() >= self.max_queue:
                self._shed("queue_full", RequestRejected(
                    f"decode queue over watermark ({self.max_queue})"))
            if deadline is not None and time.monotonic() >= deadline:
                self._shed("deadline", DeadlineExceeded(
                    "deadline expired before admission"))
            self._seq += 1
            g = _Gen(self._seq, arr, max_new, deadline, lane, temperature,
                     handle, seed)
            self._lanes[lane].append(g)
            self.submitted += 1
            depth = self._qsize()
            self._cv.notify_all()
        obs.set_gauge("decode.queue_depth", depth)
        return handle

    def generate(self, tokens, *, max_new_tokens: Optional[int] = None,
                 deadline_ms: Optional[float] = None, priority: int = 1,
                 temperature: float = 0.0, seed: int = 0):
        """Yield tokens as the scheduler produces them. Closing the
        generator mid-stream cancels the generation — its KV pages are
        reclaimed at the next step boundary. Raises the batcher's typed
        errors (RequestRejected / DeadlineExceeded / Draining) — possibly
        mid-stream."""
        h = self.submit(tokens, max_new_tokens=max_new_tokens,
                        deadline_ms=deadline_ms, priority=priority,
                        temperature=temperature, seed=seed)
        budget = (deadline_ms / 1000.0 + 5.0 if deadline_ms is not None
                  else self.default_timeout)
        t_end = time.monotonic() + budget
        try:
            while True:
                try:
                    ev = h.get(timeout=max(0.01, t_end - time.monotonic()))
                except queue.Empty:
                    raise ServeError(
                        "decode stream stalled (scheduler wedged?)")
                if ev[0] == "token":
                    yield ev[1]
                elif ev[0] == "end":
                    return
                else:
                    raise ev[1]
        finally:
            h.cancel()
            with self._cv:
                self._cv.notify_all()

    # -- the scheduler loop --------------------------------------------

    def _loop(self):
        try:
            while True:
                with self._cv:
                    while (self._running and self._qsize() == 0
                           and self._active() == 0):
                        with obs.trace.span("decode.idle_wait"):
                            self._cv.wait(1.0)
                    if not self._running:
                        return
                self.step()
        finally:
            # whatever ends this thread, nothing may keep pages: retire
            # every resident generation and flush the queue
            self._abort_all(ServeError("decode scheduler stopped"))

    def step(self) -> int:
        """One continuous-batch step: admit → decode → distribute →
        retire. Returns the number of tokens produced. This is the
        decode data plane's hot root (analysis/dataplane.py)."""
        with obs.trace.span("decode.turn") as turn:
            joined, active, left = self._turn()
            turn.set(joined=joined, active=active, left=left)
        return active

    def _turn(self):
        """The body of :meth:`step`; returns (joined, active, left)."""
        joined = self._admit(time.monotonic())
        active = [(i, g) for i, g in enumerate(self._slots)
                  if g is not None]
        if not active:
            return joined, 0, 0
        eng = self.engine
        with obs.trace.span("decode.build", active=len(active)):
            tokens = np.zeros((eng.slots,), np.int32)
            positions = np.zeros((eng.slots,), np.int32)
            lengths = np.zeros((eng.slots,), np.int32)
            temps = np.zeros((eng.slots,), np.float32)
            tables = np.full((eng.slots, eng.max_pages), SCRATCH_PAGE,
                             np.int32)
            stepping = []
            for i, g in active:
                pos = g.prompt_len + g.produced - 1
                try:
                    table = self._ensure_pages(g, pos)
                except PagesExhausted as e:
                    # shedding a RUNNING stream, not a queued one: freeing
                    # its pages is what lets the rest of the batch keep
                    # stepping
                    self.shed += 1
                    self.shed_by_reason["pages"] += 1
                    obs.inc("decode.shed_pages")
                    self._retire(i, g, "pages", error=e)
                    continue
                tokens[i] = g.last_token
                positions[i] = pos
                lengths[i] = pos + 1
                temps[i] = g.temperature
                tables[i, :len(table)] = table
                stepping.append((i, g))
        if not stepping:
            return joined, 0, 0
        t0 = time.monotonic()
        out = eng.step(tokens, positions, tables, lengths, temps,
                       seed=self._step_seed())
        dt = time.monotonic() - t0
        left = 0
        now = time.monotonic()
        with obs.trace.span("decode.distribute") as distribute:
            for i, g in stepping:
                tok = int(out[i])
                g.last_token = tok
                g.produced += 1
                self.tokens_out += 1
                if g.ctx is not None and g.ctx.sampled:
                    obs.trace.complete("decode.token", t0, dt, ctx=g.ctx,
                                       index=g.produced, slot=i)
                if not g.handle._emit(("token", tok, g.produced)):
                    self._retire(i, g, "backpressure",
                                 error=RequestRejected(
                                     "stream consumer too slow (token "
                                     "buffer full)"))
                    left += 1
                    continue
                if self._done(g, tok, now):
                    left += 1
            distribute.set(left=left)
        self.steps += 1
        occ = len(stepping) / eng.slots
        self._occupancy = (occ if self.steps == 1
                           else 0.7 * self._occupancy + 0.3 * occ)
        obs.set_gauge("decode.occupancy", self._occupancy)
        obs.set_gauge("decode.cache_row_bytes",
                      getattr(eng, "cache_row_bytes", 0))
        obs.trace.complete("decode.step", t0, dt, active=len(stepping),
                           joined=joined, left=left, **self._counted())
        return joined, len(stepping), left

    def _counted(self) -> dict:
        """What the model counted in the engine's last program call (an
        expert layer's ``moe.*``; nothing for a model that counts nothing),
        as span attributes — and added to the counters of the same names
        (``*_max``: a gauge) and to ``stats()["counted"]``."""
        counted = getattr(self.engine, "last_counters", None) or {}
        for name, v in counted.items():
            if name.endswith("_max"):
                obs.set_gauge(name, v)
                self.counted[name] = max(self.counted.get(name, 0), v)
            else:
                obs.inc(name, v)
                self.counted[name] = self.counted.get(name, 0) + v
        return counted

    def _step_seed(self) -> int:
        # deterministic per step-count: replays reproduce token-for-token
        return (self.steps * 1000003 + 12345) & 0x7FFFFFFF

    def _admit(self, now: float) -> int:
        """Move queued generations into free slots (prefill at the step
        boundary). Page exhaustion leaves the request queued."""
        admitted = []
        with obs.trace.span("decode.admit") as admit, self._cv:
            free = [i for i, g in enumerate(self._slots) if g is None]
            for lane in self._lanes:
                while lane and free:
                    g = lane[0]
                    if g.handle.cancelled():
                        lane.pop(0)
                        self.cancelled += 1
                        g.handle._emit(("end", "cancelled", 0))
                        continue
                    if g.deadline is not None and now >= g.deadline:
                        lane.pop(0)
                        self.shed += 1
                        self.shed_by_reason["deadline"] += 1
                        obs.inc("decode.shed_deadline")
                        g.handle._emit(("error", DeadlineExceeded(
                            "deadline expired in decode queue")))
                        continue
                    bucket = self.engine.bucket_for(g.prompt_len)
                    try:
                        self.engine.pool.alloc(
                            g.seq, bucket // self.engine.page_size)
                    except PagesExhausted:
                        # stays queued: pages free as running streams end
                        free = []
                        break
                    lane.pop(0)
                    slot = free.pop(0)
                    self._slots[slot] = g
                    admitted.append((g, bucket))
            admit.set(admitted=len(admitted))
        for g, bucket in admitted:
            g.t_admit = time.monotonic()
            obs.trace.complete("decode.queue_wait", g.t_submit,
                              g.t_admit - g.t_submit, ctx=g.ctx,
                              priority=g.priority)
            with obs.trace.span("decode.prefill", bucket=bucket,
                                prompt_len=g.prompt_len) as prefill:
                tok = self.engine.prefill(
                    g.tokens, self.engine.pool.table(g.seq),
                    temperature=g.temperature, seed=g.seed)
                prefill.set(**self._counted())
            g.last_token = tok
            g.produced = 1
            self.tokens_out += 1
            if not g.handle._emit(("token", tok, 1)):
                idx = self._slots.index(g)
                self._retire(idx, g, "backpressure",
                             error=RequestRejected(
                                 "stream consumer too slow"))
                continue
            self._done(g, tok, time.monotonic())
        return len(admitted)

    def _ensure_pages(self, g: _Gen, pos: int) -> List[int]:
        """Grow ``g``'s page table to cover position ``pos`` (at most one
        page per step — step granularity by construction)."""
        pool = self.engine.pool
        table = pool.table(g.seq)
        while len(table) * pool.page_size <= pos:
            pool.alloc(g.seq, 1)
            table = pool.table(g.seq)
        return table

    def _done(self, g: _Gen, tok: int, now: float) -> bool:
        """Post-token retirement checks, in precedence order."""
        idx = self._slots.index(g)
        if self.eos_id is not None and tok == self.eos_id:
            self._retire(idx, g, "eos")
            return True
        if g.produced >= g.max_new:
            self._retire(idx, g, "length")
            return True
        if g.prompt_len + g.produced >= self.engine.max_length:
            self._retire(idx, g, "overflow")
            return True
        if g.deadline is not None and now >= g.deadline:
            self.shed_by_reason["deadline"] += 1
            self.shed += 1
            obs.inc("decode.shed_deadline")
            self._retire(idx, g, "deadline", error=DeadlineExceeded(
                f"deadline expired after {g.produced} tokens"))
            return True
        if g.handle.cancelled():
            self._retire(idx, g, "cancelled")
            return True
        return False

    def _retire(self, slot: int, g: _Gen, reason: str,
                error: Optional[ServeError] = None):
        """Leave the batch: free pages, emit the terminal event, complete
        the request span. EVERY exit path funnels here — the page-leak
        guarantee lives in this one place."""
        self._slots[slot] = None
        self.engine.pool.free(g.seq)
        if reason == "cancelled":
            self.cancelled += 1
        else:
            self.completed += 1
        if error is not None:
            g.handle._emit(("error", error))
        else:
            g.handle._emit(("end", reason, g.produced))
        obs.inc("decode.finished")
        obs.trace.complete(
            "decode.generate", g.t_admit or g.t_submit,
            time.monotonic() - (g.t_admit or g.t_submit), ctx=g.ctx,
            tokens=g.produced, outcome=reason)
        with self._cv:
            self._cv.notify_all()

    def _abort_all(self, exc: ServeError):
        with self._cv:
            queued = [g for lane in self._lanes for g in lane]
            for lane in self._lanes:
                del lane[:]
        for i, g in enumerate(list(self._slots)):
            if g is not None:
                self._retire(i, g, "aborted", error=exc)
        for g in queued:
            g.handle._emit(("error", exc))

    # -- lifecycle ------------------------------------------------------

    def drain(self, timeout: float = 30.0) -> bool:
        """Refuse new work, let running generations finish. True when
        queue and batch emptied within ``timeout``."""
        deadline = time.monotonic() + timeout
        with self._cv:
            self._draining = True
            self._cv.notify_all()
            while self._qsize() or self._active():
                rem = deadline - time.monotonic()
                if rem <= 0:
                    return False
                self._cv.wait(min(rem, 0.1))
        return True

    def close(self, timeout: float = 5.0):
        """Stop the scheduler thread; resident generations get a
        structured abort and their pages are reclaimed."""
        with self._cv:
            if not self._running:
                return
            self._running = False
            self._draining = True
            self._cv.notify_all()
        self._thread.join(timeout)
        if self._thread.is_alive():
            self.stopped_clean = False
            obs.inc("decode.scheduler_thread_leaked")

    def ready(self) -> bool:
        return self._running and not self._draining

    @property
    def version(self) -> int:
        return 0

    def stats(self) -> dict:
        with self._cv:
            out = {
                "submitted": self.submitted,
                "completed": self.completed,
                "cancelled": self.cancelled,
                "shed": self.shed,
                "shed_by_reason": dict(self.shed_by_reason),
                "steps": self.steps,
                "tokens_out": self.tokens_out,
                "queued": self._qsize(),
                "active": self._active(),
                "occupancy": self._occupancy,
                "draining": self._draining,
                "counted": dict(self.counted),
            }
        out["engine"] = self.engine.stats()
        return out

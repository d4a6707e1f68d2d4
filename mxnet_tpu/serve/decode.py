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
  sequence's page ids, first token sampled on-device. For a model that can
  **continue a prompt from a position** (``prefill_from``, below) there is
  ONE such program instead, of the smallest bucket's size: a prompt goes
  through it a *piece* at a time, the scheduler launching one piece a turn
  in front of that turn's decode step, so that no stream waits behind more
  of another caller's prompt than a piece;
- a **single decode-step program** (one trace, period): one new position
  for every slot of the fixed continuous batch — embed, per-layer
  paged-KV write + paged attention (ops/flash_attention.decode_attention),
  LM head, on-device greedy/temperature sampling.

Each slot's last sampled token stays **on the device**: a ``(slots,)``
int32 vector that every program takes and returns (the step whole, a
prefill with its slot's first token written), so no call waits for a token
to come back to the host in order to send it up again, and a call is
**launched** (``launch_step``/``launch_prefill``: one packed int32 array
uploaded, then the program queued) apart from where its result is **read**
(``read``). ``step``/``prefill`` are the two together.

The engine takes the **model by interface** (``DecodeEngine``'s docstring):
what a cache row is, the prefill and step bodies and the paged read are the
model's; pages, programs, accounting and the scheduler are shared by every
model (``models.transformer.TransformerDecodeModel`` — K and V of every head
side by side, float32; ``models.mla_moe.MLAMoEDecodeModel`` — one latent
row and no head axis, bfloat16; ``models.gdn_moe.GDNMoEDecodeModel`` — a
flat grouped-KV row in a quarter of its layers, and in the others a
fixed-size recurrent state per sequence).

**Two kinds of cache.** Beside its rows a model may declare **per-slot
state** (``model.state``: name -> (shape per slot, dtype)): what a layer
keeps per sequence whatever its length. The engine holds one array a name,
``(slots + 1,) + shape`` (the last slot scratch), resident and donated with
the pool; a prefill returns its sequence's state and the engine writes it
over its slot's — overwrites, never adds: whatever the slot's last owner or
a step launched ahead for it left there is gone —; the step is handed every
slot's with ``live`` and returns them, a slot that is not live untouched.
Only the model's ``paged_layers`` have a layer of the pool.

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

Program accounting mirrors InferenceEngine: every program is built by
``progcache.build`` (its ``compile_log`` entry, cache get/put so a
scaled-out replica deserializes instead of compiling —
``decode.cache_hit`` vs ``decode.compile``), and
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

import collections
import queue
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from .. import copytrack, obs, progcache, tsan
from ..obs import context as obs_context
from ..obs._env import env_float, env_int
from .engine import DeadlineExceeded, Draining, RequestRejected, ServeError
from .kvcache import SCRATCH_PAGE, PagePool, PagesExhausted, pages_for

__all__ = ["DecodeEngine", "DecodeScheduler", "StreamHandle",
           "default_decode_buckets"]

# the step's packed rows: position, length, temperature bits, page table
_POS, _LEN, _TEMP, _TABLE = 0, 1, 2, 3
# a prefill's packed header; the page ids follow, then the padded prompt
_P_LEN, _P_SLOT, _P_SEED, _P_TEMP, _P_PAGES = 0, 1, 2, 3, 4
# a piece's: the piece's first position after them, then the sequence's whole
# page table and the piece's tokens
_P_START, _P_TABLE = 4, 5


def _bits(value, dtype) -> int:
    """``value`` as ``dtype`` (float32, uint32), its 32 bits as an int32."""
    return int(np.array(value, dtype).view(np.int32))


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
        ``cache_dtype`` (the pool is ``(pages, paged layers, page_size) +
        cache_row`` of that dtype; ``paged_layers``, how many of the layers
        keep rows there, is ``layers`` unless the model says), optionally
        ``state`` (module docstring; a model with none compiles to the
        programs it had before state existed), ``counters`` (full names, as
        ``"moe.held"``, of the int32 values its bodies return beside the
        logits; may be empty), and three pure
        functions: ``prefill(params, tokens (1, S), length) -> (last logits
        (V,), rows (paged layers, S) + cache_row, counters or None)``,
        ``step(params, tokens (B,), positions (B,), live (B,), attend) ->
        (logits (B, V), counters or None)`` where ``attend(paged layer,
        query, row)`` writes ``row`` (B,) + cache_row at the step's positions
        and returns ``attention(query, pool, layer, page_tables, lengths)``,
        and that ``attention`` itself, the paged read of one layer. With
        ``state``, ``prefill`` returns the sequence's state (name -> shape
        per slot) as a fourth value, and ``step`` takes the engine's arrays
        as a sixth argument and returns them as a third value.
        Optionally a fourth function, ``prefill_from(params, tokens (1, C),
        start, length, prior, state)``: ``prefill`` for the positions
        ``start .. start + C - 1`` (``start`` () int32, a multiple of C; live
        where ``< length``, the prompt's whole length) of a prompt whose
        earlier pieces have been through it — ``state`` the sequence's own
        state (name -> shape per slot) as the piece before left it, to be
        taken for zeros where ``start`` is 0 whatever it holds (``{}`` for a
        model that declares none), and ``prior(paged layer)`` the rows of
        positions ``0 .. max_prompt - 1`` of that layer as the pool has them
        through the sequence's page table (those ``< start`` are the earlier
        pieces'; the rest is not to be read). It returns what ``prefill``
        returns, the state always; the logits mean something in the last
        piece only. The engine of such a model feeds EVERY prompt in pieces
        of its smallest bucket (``prefill_piece``): ``buckets`` is then that
        one size, a prompt is padded to a multiple of it, and the largest
        bucket given stays as ``max_prompt``, the longest prompt admitted.
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
        the model's max_length (capped at the pool's capacity). For a model
        with ``prefill_from``: the smallest is the piece, the largest the
        longest prompt, and all are multiples of the smallest.
    progcache_dir : str, optional
        Explicit persistent program cache; defaults to the process-wide
        ``progcache.cache()`` (``MXNET_PROGCACHE=1``).

    **What the programs take.** Both take five arguments, four of them
    resident on the device — ``params``, the pool ``kv`` and the per-slot
    ``state`` (both donated; ``state`` is empty for a model that declares
    none), and ``last``, the ``(slots,)`` int32 vector of every slot's last
    sampled token — and ONE int32 array from the host, so a call is one
    upload:

    - the step's ``(slots + 1, 3 + max_pages)``: a row per slot — position,
      length (0: the slot is idle), the temperature's float32 bits, its
      page table — and the sampling seed's bits first in the row after them
      (``blank_step``);
    - a prefill's ``(4 + S // page_size + S,)`` for bucket ``S``: prompt
      length, slot, the seed's and the temperature's bits, the page ids,
      the padded prompt;
    - a piece's ``(5 + max_prompt // page_size + C,)``: the same four, then
      ``start``, the sequence's whole page table (scratch behind its pages)
      and the piece's tokens.

    Both return ``kv``, ``state``, the new ``last`` (the step's tokens; a prefill's
    first token written at its slot) and what the host fetches in one
    transfer: the sampled tokens (a prefill's one) followed by the model's
    counters.
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
        # a model that can continue a prompt (``prefill_from``) is fed in
        # pieces of the smallest bucket: the one pad target there is then
        self.prefill_piece = (buckets[0] if hasattr(self.model, "prefill_from")
                              else None)
        self.max_prompt = buckets[-1]       # the longest prompt admitted
        if self.prefill_piece:
            if any(b % buckets[0] for b in buckets):
                raise ValueError(
                    f"prompt buckets {buckets} of a model fed in pieces must "
                    f"be multiples of the smallest")
            buckets = buckets[:1]
        self.buckets = buckets
        self.pool = PagePool(self.num_pages, self.page_size)

        import jax
        import jax.numpy as jnp

        self._params = self.model.params
        self._param_avals = tuple(
            (tuple(a.shape), str(a.dtype))
            for a in jax.tree_util.tree_leaves(self._params))
        row, dtype = tuple(self.model.cache_row), self.model.cache_dtype
        self.paged_layers = int(getattr(self.model, "paged_layers",
                                        self.model.layers))
        pool_shape = (self.num_pages, self.paged_layers, self.page_size) + row
        self.cache_row_bytes = int(np.prod(row)) * jnp.dtype(dtype).itemsize
        # per-slot state beside the pages: one more slot than the batch has,
        # the last scratch (where a step's idle slots read and write)
        declared = {name: ((self.slots + 1,) + tuple(shape), jnp.dtype(dt))
                    for name, (shape, dt) in
                    (getattr(self.model, "state", None) or {}).items()}
        self.state_bytes = sum(int(np.prod(shape[1:])) * dt.itemsize
                               for shape, dt in declared.values())
        if jax.default_backend() == "tpu":
            sharding = jax.tree_util.tree_leaves(self._params)[0].sharding
            pool = self._row_major(pool_shape, sharding)
            held = {name: self._row_major(shape, sharding)
                    for name, (shape, _) in declared.items()}
            # donating the pool buffer makes the KV writes in-place on TPU;
            # CPU/GPU test backends would only warn about it
            donate = (1, 2)
        else:
            pool, held, donate = None, {name: None for name in declared}, ()
        self.kv = jax.jit(lambda: jnp.zeros(pool_shape, dtype),
                          out_shardings=pool)()
        self.state = {name: jax.jit(lambda s=shape, d=dt: jnp.zeros(s, d),
                                    out_shardings=held[name])()
                      for name, (shape, dt) in declared.items()}
        if held and pool is not None:
            # the programs take each array in the layout it HAS: a zeros
            # program loaded from the compile cache has been seen to give
            # the client's own choice, not the row-major one asked for
            # (an array that is not whole tiles wide: PR 29 saw it of the
            # pool, PR 38 of a state array); a model shapes its state in
            # whole tiles so that the two are the same
            held = {name: a.format for name, a in self.state.items()}
        self._prefill_jit, self._step_jit = self._jit_programs(pool, held,
                                                               donate)
        # every slot's last sampled token, fed back on the device
        self.last = jnp.zeros((self.slots,), jnp.int32)
        self._blank_step = np.full((self.slots + 1, _TABLE + self.max_pages),
                                   SCRATCH_PAGE, np.int32)
        self._blank_step[:, :_TABLE] = 0
        # what the model's bodies counted in the last program call read,
        # by name; fetched with the tokens, in the same transfer
        self.last_counters: Dict[str, int] = {}

        # program accounting — mirrors InferenceEngine so the TraceLinter
        # and the coldstart idiom read both the same way
        self._programs: Dict[tuple, int] = {}
        self._aot: Dict[tuple, object] = {}
        self.compile_log: List[dict] = []
        self.cache_hits = 0
        self.exec_count = 0
        self._stat_lock = tsan.lock("serve.decode.stats")

        self._progcache = (progcache.ProgramCache(progcache_dir)
                           if progcache_dir else progcache.cache())
        self._key_statics = (
            type(self.model).__name__, tuple(sorted(self.cfg.items())),
            self.slots, self.page_size, self.num_pages, self.max_pages,
            tuple(self.buckets), self._param_avals)
        if self.prefill_piece:
            self._key_statics += ("pieces", self.max_prompt)
        if declared:
            self._key_statics += (self.paged_layers, tuple(
                (name, shape, str(dt)) for name, (shape, dt)
                in sorted(declared.items())))

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

    def _jit_programs(self, pool, held, donate):
        """(prefill, step) jitted with the pool argument and result held
        to ``pool`` and the state's to ``held`` (name -> ``Format``; None
        for the backend's default)."""
        import jax

        def jit(fn):
            return jax.jit(fn, donate_argnums=donate,
                           in_shardings=(None, pool, held, None, None),
                           out_shardings=(pool, held, None))

        return (jit(self._piece_fn if self.prefill_piece
                    else self._prefill_fn), jit(self._step_fn))

    def _fetched(self, toks, counters):
        """What the host reads of a call, as one int32 vector: the sampled
        tokens, then the model's counters."""
        import jax.numpy as jnp

        if counters is None:
            return toks
        return jnp.concatenate([toks, jnp.stack(counters).astype(jnp.int32)])

    def _prefill_fn(self, params, kv, state, last, packed):
        """One padded prompt → KV pages written, its state written over its
        slot's, first token (also written into ``last`` at the prompt's
        slot). ``packed`` (class docstring)
        holds the prompt padded to its bucket S (multiple of page_size) and
        the sequence's S // page_size pages in position order. Pad
        positions scatter garbage rows — masked by ``length`` until each
        slot is overwritten by a decode step."""
        import jax

        from ..models.transformer import sample_token

        n = (packed.shape[0] - _P_PAGES) // (self.page_size + 1)
        length, slot, seed, temp = self._header(packed)
        page_ids = packed[_P_PAGES:_P_PAGES + n]
        tokens = packed[_P_PAGES + n:][None]
        logits, rows, counters, *own = self.model.prefill(params, tokens,
                                                          length)
        if state:   # the sequence's own, over whatever its slot held
            state = self._write_state(state, own[0], slot)
        kv = self._write_pages(kv, rows, page_ids)
        tok = sample_token(logits[None], jax.random.PRNGKey(seed), temp)
        return kv, state, (last.at[slot].set(tok[0]),
                           self._fetched(tok, counters))

    @staticmethod
    def _header(packed):
        """(length, slot, seed, temperature) of a prefill's packed array."""
        import jax
        import jax.numpy as jnp

        return (packed[_P_LEN], packed[_P_SLOT],
                jax.lax.bitcast_convert_type(packed[_P_SEED], jnp.uint32),
                jax.lax.bitcast_convert_type(packed[_P_TEMP], jnp.float32))

    @staticmethod
    def _write_state(state, own, slot):
        """Every state array with the sequence's ``own`` over ``slot``'s."""
        import jax

        return {name: jax.lax.dynamic_update_slice(
            held, own[name][None].astype(held.dtype),
            (slot,) + (0,) * (held.ndim - 1))
            for name, held in state.items()}

    def _write_pages(self, kv, rows, page_ids):
        """``rows`` (L, S) + row → (L, n, page) + row, then page by page —
        every layer's rows of the page in one update — in place at
        ``page_ids`` (n). (One scatter would say the same; but for head
        counts off the 8-row tile XLA's TPU scatter wants the pool in a
        layout of its own, and converts the whole pool there and back.)"""
        import jax
        import jax.numpy as jnp

        n = page_ids.shape[0]
        rows = rows.reshape((self.paged_layers, n, self.page_size)
                            + rows.shape[2:])

        def write_page(j, kv):
            page = jax.lax.dynamic_slice_in_dim(rows, j, 1, axis=1)
            return jax.lax.dynamic_update_slice(
                kv, jnp.swapaxes(page, 0, 1),
                (page_ids[j],) + (0,) * (kv.ndim - 1))

        return jax.lax.fori_loop(0, n, write_page, kv)

    def _piece_fn(self, params, kv, state, last, packed):
        """One piece of a prompt, for a model with ``prefill_from``:
        ``packed`` (class docstring) holds the piece's first position, the
        sequence's page table and the piece's tokens. The slot's state is
        read, handed to the model and written back; the rows of the pieces
        before are read through the page table, the piece's own written into
        its pages; the last piece's token goes into ``last`` at the slot."""
        import jax
        import jax.numpy as jnp

        from ..models.transformer import sample_token

        piece, page = self.prefill_piece, self.page_size
        n = self.max_prompt // page
        length, slot, seed, temp = self._header(packed)
        start = packed[_P_START]
        table = packed[_P_TABLE:_P_TABLE + n]
        tokens = packed[_P_TABLE + n:][None]

        def prior(layer):   # positions 0 .. max_prompt - 1 of one layer
            return _pages(kv, table, layer).reshape((n * page,) + kv.shape[3:])

        own = {name: jax.lax.dynamic_index_in_dim(held, slot, keepdims=False)
               for name, held in state.items()}
        logits, rows, counters, own = self.model.prefill_from(
            params, tokens, start, length, prior, own)
        state = self._write_state(state, own, slot)
        kv = self._write_pages(kv, rows, jax.lax.dynamic_slice_in_dim(
            table, start // page, piece // page))
        tok = sample_token(logits[None], jax.random.PRNGKey(seed), temp)
        return kv, state, (
            last.at[slot].set(jnp.where(start + piece >= length, tok[0],
                                        last[slot])),
            self._fetched(tok, counters))

    def _step_fn(self, params, kv, state, last, packed):
        """One token for every slot: ``last`` (B,) are the tokens to embed,
        ``packed`` (class docstring) the positions, lengths, temperatures
        and page tables (B, max_pages). Inactive slots carry length 0 and a
        scratch page table — their writes land on the scratch page and
        their outputs are garbage the host discards."""
        import jax
        import jax.numpy as jnp

        from ..models.transformer import sample_token

        positions = packed[:self.slots, _POS]
        lengths = packed[:self.slots, _LEN]
        temps = jax.lax.bitcast_convert_type(packed[:self.slots, _TEMP],
                                             jnp.float32)
        page_tables = packed[:self.slots, _TABLE:]
        seed = jax.lax.bitcast_convert_type(packed[self.slots, 0],
                                            jnp.uint32)
        rows = jnp.arange(self.slots)
        pids = page_tables[rows, positions // self.page_size]
        offs = positions % self.page_size

        def attend(layer, query, row):
            nonlocal kv
            kv = kv.at[pids, layer, offs].set(row)
            return self.model.attention(query, kv, layer, page_tables,
                                        lengths)

        if state:
            logits, counters, state = self.model.step(
                params, last, positions, lengths > 0, attend, state)
        else:
            logits, counters = self.model.step(params, last, positions,
                                               lengths > 0, attend)
        toks = sample_token(logits, jax.random.PRNGKey(seed), temps)
        return kv, state, (toks, self._fetched(toks, counters))

    # -- program accounting (the engine.py compile path, decode-keyed) --

    def _launch(self, kind: str, label: str, jitted, packed) -> tuple:
        """Queue one program call with full accounting: a fresh signature
        built through ``progcache.build``, ``decode.*`` metrics, and
        the swap of the pool and the last-token vector for the call's
        (not yet computed) results. Returns what :meth:`read` takes."""
        sig = (kind, (tuple(packed.shape), str(packed.dtype)))
        is_compile = sig not in self._programs
        cache_hit = False
        call_args = (self._params, self.kv, self.state, self.last, packed)
        if is_compile:
            # always ahead of time: the one compile is measured
            # (stats()["step_program"]) and run, observed or not
            self._aot[sig], built = progcache.build(
                jitted, call_args, cache=self._progcache,
                key=progcache.program_key("decode", label,
                                          (self._key_statics, sig)),
                meta={"kind": kind})
            cache_hit = built["cache_hit"]
            self.compile_log.append({
                "sig": sig, "kind": kind, "label": label,
                "param_avals": self._param_avals, **built})
            if cache_hit:
                with self._stat_lock:
                    self.cache_hits += 1
        fn = self._aot[sig]
        with obs.trace.span("decode.execute", kind=kind, label=label,
                            compile=is_compile, cache_hit=cache_hit):
            # the one upload and the launch: returns before the device is
            # done, and before it has begun if a call is still running
            with obs.trace.span("decode.dispatch"):
                self.kv, self.state, (self.last, fetched) = fn(*call_args)
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
        return kind, label, fetched

    def read(self, launched: tuple):
        """Wait for a launched call and fetch what it sampled: ``(tokens,
        counters)`` — a step's (slots,) int32 array or a prefill's int, and
        what the model's bodies counted in that call, by name (also left in
        ``last_counters``)."""
        import jax

        kind, label, fetched = launched
        # the sampled tokens ARE the wire payload — this d2h is the one
        # accounted sync of the decode hot path
        copytrack.TRACKER.host_sync("serve.decode.device_get")
        with obs.trace.span("decode.execute", kind=kind, label=label):
            # the wait for the call's last operation, then the copy back:
            # device-idle time under this span is the host not yet awake
            with obs.trace.span("decode.device_get"):
                host = jax.device_get(fetched)  # lint: disable=host-sync-on-hot-path
        n = len(host) - len(self.model.counters)
        self.last_counters = dict(zip(self.model.counters,
                                      (int(c) for c in host[n:])))
        return ((host[:n] if kind == "step" else int(host[0])),
                self.last_counters)

    # -- host-facing calls ---------------------------------------------

    def bucket_for(self, prompt_len: int) -> int:
        """The positions a prompt of ``prompt_len`` is padded to (and needs
        pages for): its bucket, or for a model fed in pieces the next
        multiple of the piece."""
        if prompt_len > self.max_prompt:
            raise RequestRejected(
                f"prompt length {prompt_len} exceeds max bucket "
                f"{self.max_prompt}")
        if self.prefill_piece:
            return -(-prompt_len // self.prefill_piece) * self.prefill_piece
        return next(b for b in self.buckets if b >= prompt_len)

    def launch_prefill(self, tokens: np.ndarray, page_ids: List[int], *,
                       temperature: float = 0.0, seed: int = 0,
                       slot: int = 0, start: int = 0) -> tuple:
        """Queue the prefill of one prompt into its pages; :meth:`read`
        gives its first sampled token, which the program also writes into
        the last-token vector at ``slot``. ``tokens`` is the unpadded 1-D
        prompt; ``page_ids`` must cover its bucket (``bucket_for(len) //
        page_size`` pages). For a model fed in pieces (``prefill_piece``)
        ONE call queues ONE piece, the positions from ``start`` (a multiple
        of the piece; the pieces of a prompt in order, into the same slot),
        and only the last piece's token means anything."""
        tokens = np.asarray(tokens, np.uint32).astype(np.int32)
        n = int(tokens.shape[0])
        bucket = self.bucket_for(n)
        pages = bucket // self.page_size
        if len(page_ids) != pages:
            raise ServeError(
                f"prefill needs {pages} pages for bucket {bucket}, got "
                f"{len(page_ids)}")
        header = (n, slot, _bits(seed, np.uint32),
                  _bits(temperature, np.float32))
        piece = self.prefill_piece
        if start and not piece:
            raise ServeError("this engine's model is prefilled whole")
        if piece:
            if start % piece or not 0 <= start < bucket:
                raise ServeError(f"no piece of {piece} starts at {start} in "
                                 f"a prompt padded to {bucket}")
            table = _P_TABLE + self.max_prompt // self.page_size
            packed = np.zeros((table + piece,), np.int32)
            packed[:_P_TABLE] = header + (start,)
            packed[_P_TABLE:table] = SCRATCH_PAGE
            packed[_P_TABLE:_P_TABLE + pages] = page_ids
            part = tokens[start:start + piece]
            packed[table:table + len(part)] = part
            return self._launch("prefill", f"prefill{piece}",
                                self._prefill_jit, packed)
        packed = np.zeros((_P_PAGES + pages + bucket,), np.int32)
        packed[:_P_PAGES] = header
        packed[_P_PAGES:_P_PAGES + pages] = page_ids
        packed[_P_PAGES + pages:_P_PAGES + pages + n] = tokens
        return self._launch("prefill", f"prefill{bucket}", self._prefill_jit,
                            packed)

    def prefill(self, tokens: np.ndarray, page_ids: List[int], *,
                temperature: float = 0.0, seed: int = 0,
                slot: int = 0) -> int:
        """:meth:`launch_prefill` and :meth:`read` together: prefill one
        prompt into its pages — every piece of it, for a model fed in
        pieces — and return the first sampled token."""
        piece = self.prefill_piece
        for start in (range(0, self.bucket_for(len(tokens)), piece) if piece
                      else (0,)):
            tok = self.read(self.launch_prefill(
                tokens, page_ids, temperature=temperature, seed=seed,
                slot=slot, start=start))[0]
        return tok

    def blank_step(self) -> np.ndarray:
        """A step's packed argument with every slot idle (length 0, its
        page table all scratch) and seed 0, for the caller to fill: row
        ``i`` is slot ``i``'s position, length, temperature bits and page
        table; ``[slots, 0]`` the sampling seed's bits."""
        return self._blank_step.copy()

    def launch_step(self, packed: np.ndarray) -> tuple:
        """Queue one continuous-batch decode step over the tokens the
        device holds; :meth:`read` gives the (slots,) sampled tokens."""
        return self._launch("step", "step", self._step_jit, packed)

    def step(self, tokens, positions, page_tables, lengths, temps, *,
             seed: int = 0) -> np.ndarray:
        """One decode step from ``tokens`` given by the host, waited for;
        returns (slots,) int32 sampled tokens (garbage at inactive rows,
        i.e. lengths == 0)."""
        packed = self.blank_step()
        rows = packed[:self.slots]
        rows[:, _POS] = positions
        rows[:, _LEN] = lengths
        rows[:, _TEMP] = np.asarray(temps, np.float32).view(np.int32)
        rows[:, _TABLE:] = page_tables
        packed[self.slots, 0] = _bits(seed, np.uint32)
        self.last = np.asarray(tokens, np.int32)
        return self.read(self.launch_step(packed))[0]

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
                "paged_layers": self.paged_layers,
                # what a slot holds beside its pages, whatever its length
                "state_bytes": self.state_bytes,
                "state": {name: {"shape": list(a.shape),
                                 "dtype": str(a.dtype)}
                          for name, a in self.state.items()},
                "buckets": list(self.buckets),
                # the positions of one prefill call where a prompt is fed
                # in pieces (then the one bucket), else None
                "prefill_piece": self.prefill_piece,
                "max_prompt": self.max_prompt,
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
        # what that step's paged reads cost in grid steps, from the shapes
        # its kernel sees: the model's to say (None for one with no
        # per-head pool, or whose step gathers through XLA)
        describe = getattr(self.model, "paged_kernel", None)
        out["paged_kernel"] = describe(
            self.kv, self.slots, self.max_pages) if (
                step and describe) else None
        # the row tile the held experts' grouped products run with, a step
        # and each prompt bucket (``ops.moe.row_tile``): the model's to say
        # (None for one with no routed experts)
        describe = getattr(self.model, "moe_row_tile", None)
        out["moe_row_tile"] = describe and {
            "step": describe(self.slots),
            "prefill": {b: describe(b) for b in self.buckets}}
        # the form the delta rule takes over a prompt or a piece of one
        # (``gdn_prefill``, the kernel, or ``xla``): the model's to say
        # (None for one with no such layers)
        describe = getattr(self.model, "delta_rule", None)
        out["delta_rule"] = describe and describe()
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
    shared decode batch. ``seq`` is the scheduler's number of the request
    (the identifier its spans share), ``ctx`` its trace context; ``arrived``
    is when the event ``get`` returned last came back from the device, on
    the scheduler's clock (a token's; 0.0 for an ending)."""

    def __init__(self, capacity: int):
        self._q: "queue.Queue" = queue.Queue(maxsize=capacity)
        self._cancelled = threading.Event()
        self.seq = 0
        self.ctx = None
        self.arrived = 0.0

    def cancel(self) -> None:
        """Ask the scheduler to retire this generation at the next step
        boundary (its pages are reclaimed there)."""
        self._cancelled.set()

    def cancelled(self) -> bool:
        return self._cancelled.is_set()

    def _emit(self, ev, arrived: float = 0.0) -> bool:
        try:
            self._q.put_nowait((ev, arrived))
            return True
        except queue.Full:
            return False

    def get(self, timeout: float):
        ev, self.arrived = self._q.get(timeout=timeout)
        return ev


class _StreamTimes:
    """What the way back cost one stream, kept by its consumer while
    telemetry is on: per token the *hand-over* (its arrival on the
    scheduler's clock -> ``get`` returning it on the consumer's thread: the
    rest of the distribute loop, the queue, the wake-up, the interpreter
    lock) and the *consume* (the ``yield`` -> the consumer asking for the
    next: for ``ServeServer`` the frame and the send). One ``decode.stream``
    span at the stream's end, from the first token seen to the last
    consumed."""

    __slots__ = ("t_first", "t_last", "tokens", "handover", "handover_max",
                 "first_handover", "consume", "consume_max")

    def __init__(self, t_first: float):
        self.t_first = self.t_last = t_first
        self.tokens = 0
        self.handover = self.handover_max = 0.0
        self.consume = self.consume_max = 0.0
        self.first_handover = None

    def seen(self, index: int, arrived: float, got: float):
        wait = got - arrived
        self.tokens += 1
        self.handover += wait
        if wait > self.handover_max:
            self.handover_max = wait
        if index == 1:
            self.first_handover = wait
        self.t_last = got

    def consumed(self, got: float, back: float):
        took = back - got
        self.consume += took
        if took > self.consume_max:
            self.consume_max = took
        self.t_last = back

    def record(self, handle: StreamHandle):
        attrs = {"seq": handle.seq, "tokens": self.tokens,
                 "handover_us": _us(self.handover),
                 "handover_max_us": _us(self.handover_max),
                 "consume_us": _us(self.consume),
                 "consume_max_us": _us(self.consume_max)}
        if self.first_handover is not None:
            attrs["first_handover_us"] = _us(self.first_handover)
        obs.trace.complete("decode.stream", self.t_first,
                           self.t_last - self.t_first, ctx=handle.ctx,
                           **attrs)


def _us(seconds: float) -> int:
    return int(round(seconds * 1e6))


class _Gen:
    """One generation's scheduler-side state. ``launched`` counts the
    tokens asked of the device (the prefill's and each step's), ``produced``
    those that have come back and gone out; ``fed`` the prompt's positions
    handed to the device so far, where it goes in pieces."""

    __slots__ = ("seq", "tokens", "prompt_len", "max_new", "deadline",
                 "priority", "temperature", "temp_bits", "ctx", "handle",
                 "slot", "launched", "produced", "retired", "t_submit",
                 "t_admit", "seed", "fed", "ahead", "t_prefill")

    def __init__(self, seq, tokens, max_new, deadline, priority,
                 temperature, handle, seed):
        self.seq = seq
        self.tokens = tokens
        self.prompt_len = int(tokens.shape[0])
        self.max_new = max_new
        self.deadline = deadline
        self.priority = priority
        self.temperature = temperature
        self.temp_bits = _bits(temperature, np.float32)
        self.ctx = obs_context.current()
        self.handle = handle
        self.slot = -1
        self.launched = 0
        self.fed = 0
        self.produced = 0
        self.retired = False
        self.t_submit = time.monotonic()
        self.t_admit = 0.0
        self.t_prefill = 0.0    # the launch of its first prefill call
        self.ahead = 0          # prompts prefilling before it at admission
        self.seed = seed


class _InFlight:
    """One launched program call whose result the scheduler has yet to
    read, with the generations it yields a token for (``who``: a prefill's
    one — none for a piece that is not its prompt's last —, a step's
    several) and its span's attributes."""

    __slots__ = ("kind", "launched", "t_launch", "who", "attrs")

    def __init__(self, kind, launched, t_launch, who, **attrs):
        self.kind = kind
        self.launched = launched
        self.t_launch = t_launch
        self.who = who
        self.attrs = attrs


class DecodeScheduler:
    """Token-level continuous batching over a :class:`DecodeEngine`.

    A single scheduler thread owns the engine: each loop iteration is one
    ``step()`` — admit queued requests into free slots (their prefills
    launched at the step boundary), launch ONE decode-step program over
    every active slot, then read what was launched before it — the last
    turn's step, this turn's prefills — and distribute those tokens,
    retiring finished/cancelled/expired generations and freeing their
    pages. Requests therefore join and leave the running batch between
    steps, never mid-program.

    **A prompt in pieces.** Where the engine feeds its model a prompt in
    pieces (``engine.prefill_piece``: the model can continue a prompt), an
    admitted generation — slot and ALL of its prompt's pages taken, as ever —
    waits in a FIFO of *prefilling* generations, and a turn launches at most
    ONE piece, of the oldest, in front of its step: no stream waits behind
    more than a piece. A prefilling slot rides the steps idle; its last
    piece makes it a decoding slot. Cancel and deadline are looked at before
    each piece.

    **The host's turn runs under the device's step**: step *n+1* is
    launched before step *n*'s tokens are read, because nothing it is built
    from needs them — the tokens are fed back on the device, and a slot's
    position is its prompt length plus the tokens *launched* for it. What
    only a read token tells (``eos_id``), and what is looked at when one is
    handed over (cancel, deadline, back-pressure), is learned one step
    late: that slot's step in flight is speculative and its token dropped
    (``decode.dropped_speculative``). Its KV write lands in a page the
    stream still owns, or in rows that ``lengths`` masks for the page's
    next owner; and the device runs programs in launch order, so a later
    prefill into a freed page writes after it.

    The engine behind it is a :class:`DecodeEngine`, or anything with its
    ``slots``/``max_pages``/``max_length``/``pool``/``bucket_for``,
    ``blank_step``, ``launch_prefill``, ``launch_step`` and ``read`` (and,
    with a ``prefill_piece``, a ``launch_prefill`` that takes ``start``).
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
        self.steps = 0              # steps read
        self.steps_launched = 0
        self.launched_ahead = 0     # ... while another step was in flight
        self.dropped_speculative = 0
        self.tokens_out = 0
        self.admitted = 0
        self.prefill_pieces = 0     # prefill calls: one an admission, or
        #                             one a piece where prompts go in pieces
        self._piece = getattr(engine, "prefill_piece", None)
        # admitted, their prompts not yet all on the device; oldest first
        self._prefilling: collections.deque = collections.deque()
        self.counted: Dict[str, int] = {}   # the model's counters, summed
        # launched and not yet read, oldest first; between turns at most
        # the one step launched last
        self._inflight: collections.deque = collections.deque()
        self._t_arrived = 0.0       # when the last result read came back
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

    def _count_shed(self, why: str):
        self.shed += 1
        self.shed_by_reason[why] += 1
        obs.inc(f"decode.shed_{why}")

    def _shed(self, why: str, exc: ServeError):
        self._count_shed(why)
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
            handle.seq, handle.ctx = g.seq, g.ctx
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
        times = None    # a _StreamTimes from the first token seen with
        #                 telemetry on (a window may open on a running stream)
        try:
            while True:
                try:
                    ev = h.get(timeout=max(0.01, t_end - time.monotonic()))
                except queue.Empty:
                    raise ServeError(
                        "decode stream stalled (scheduler wedged?)")
                if ev[0] == "token":
                    if not obs.trace._ENABLED:
                        yield ev[1]
                        continue
                    got = time.monotonic()
                    if times is None:
                        times = _StreamTimes(got)
                    times.seen(ev[2], h.arrived, got)
                    yield ev[1]
                    times.consumed(got, time.monotonic())
                elif ev[0] == "end":
                    return
                else:
                    raise ev[1]
        finally:
            if times is not None:
                times.record(h)
            h.cancel()
            with self._cv:
                self._cv.notify_all()

    # -- the scheduler loop --------------------------------------------

    def _loop(self):
        try:
            while True:
                with self._cv:
                    while (self._running and self._qsize() == 0
                           and self._active() == 0 and not self._inflight):
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
        """One continuous-batch turn: admit → launch one piece of a prompt
        (where prompts go in pieces) → launch the next step → read what was
        launched before it → distribute → retire. Returns the
        number of slots the launched step covers. This is the decode data
        plane's hot root (analysis/dataplane.py)."""
        with obs.trace.span("decode.turn") as turn:
            joined, active, left = self._turn()
            turn.set(joined=joined, active=active, left=left)
        return active

    def _turn(self):
        """The body of :meth:`step`; returns (joined, active, left)."""
        joined = self._admit(time.monotonic())
        if self._prefilling:
            self._feed()
        active = self._launch_step(joined)
        # everything older than the step just launched: the last turn's
        # step first, then this turn's prefills (or its one piece)
        left = 0
        while len(self._inflight) > (1 if active else 0):
            left += self._receive(self._inflight[0])
            self._inflight.popleft()    # after it: drain() waits on this
        return joined, active, left

    def _launch_step(self, joined: int) -> int:
        """Build and launch one decode step over every slot that has a
        token still to ask for; returns how many. Nothing here waits for a
        token: a slot's position follows from the count of tokens launched
        for it."""
        # (a slot whose prompt is still going in rides the step idle)
        active = [g for g in self._slots if g is not None and g.launched]
        if not active:
            return 0
        eng = self.engine
        with obs.trace.span("decode.build", active=len(active)):
            packed = eng.blank_step()
            stepping = []
            for g in active:
                pos = g.prompt_len + g.launched - 1
                try:
                    table = self._ensure_pages(g, pos)
                except PagesExhausted as e:
                    # shedding a RUNNING stream, not a queued one: freeing
                    # its pages is what lets the rest of the batch keep
                    # stepping
                    self._count_shed("pages")
                    self._retire(g, "pages", error=e)
                    continue
                row = packed[g.slot]
                row[_POS] = pos
                row[_LEN] = pos + 1
                row[_TEMP] = g.temp_bits
                row[_TABLE:_TABLE + len(table)] = table
                stepping.append(g)
            packed[eng.slots, 0] = self._step_seed()
        if not stepping:
            return 0
        # what this step's caches cost in bytes: rows read grow with the
        # live contexts, state read and written does not
        cache = {
            "cache.paged_bytes": int(packed[:eng.slots, _LEN].sum())
            * getattr(eng, "cache_row_bytes", 0)
            * getattr(eng, "paged_layers", 0),
            "cache.state_bytes": 2 * getattr(eng, "state_bytes", 0)
            * len(stepping)}
        ahead = int(any(f.kind == "step" for f in self._inflight))
        t_launch = time.monotonic()
        launched = eng.launch_step(packed)
        for g in stepping:
            g.launched += 1
            self._release_if_last(g)
        self.steps_launched += 1
        self.launched_ahead += ahead
        obs.inc("decode.launched_ahead", ahead)
        self._inflight.append(_InFlight("step", launched, t_launch, stepping,
                                        joined=joined, ahead=ahead, **cache))
        return len(stepping)

    def _receive(self, flight: _InFlight) -> int:
        """Read one launched call and hand its tokens over; returns how
        many generations left the batch on them. The spans say *what the
        call cost the streams*: from the later of its launch and the
        arrival of the result before it, to its own arrival."""
        out, counted = self.engine.read(flight.launched)
        now = time.monotonic()
        t0 = max(flight.t_launch, self._t_arrived)
        self._t_arrived = now
        attrs = dict(flight.attrs, **self._count(counted))
        if flight.kind == "prefill":
            obs.trace.complete("decode.prefill", flight.t_launch,
                               now - flight.t_launch, **attrs)
            for g in flight.who:    # the call that held its prompt's end
                self._first_token(g, now, attrs["pieces"])
            return sum(self._token(g, out, now) for g in flight.who)
        left = 0
        with obs.trace.span("decode.distribute") as distribute:
            for g in flight.who:
                left += self._token(g, int(out[g.slot]), now, t0)
            distribute.set(left=left)
        self.steps += 1
        occ = len(flight.who) / self.engine.slots
        self._occupancy = (occ if self.steps == 1
                           else 0.7 * self._occupancy + 0.3 * occ)
        obs.set_gauge("decode.occupancy", self._occupancy)
        obs.set_gauge("decode.cache_row_bytes",
                      getattr(self.engine, "cache_row_bytes", 0))
        obs.set_gauge("decode.state_bytes",
                      getattr(self.engine, "state_bytes", 0))
        obs.set_gauge("decode.paged_layers",
                      getattr(self.engine, "paged_layers", 0))
        obs.trace.complete("decode.step", t0, now - t0,
                           active=len(flight.who), left=left, **attrs)
        return left

    def _token(self, g: _Gen, tok: int, now: float,
               t0: Optional[float] = None) -> bool:
        """One token of ``g`` has come back (``t0``: since when its step
        was what the stream waited for; None for a prefill's). True if
        ``g`` leaves the batch on it."""
        if g.retired:
            # asked for before the host knew that the stream had ended
            self.dropped_speculative += 1
            obs.inc("decode.dropped_speculative")
            return False
        g.produced += 1
        self.tokens_out += 1
        if t0 is not None and g.ctx is not None and g.ctx.sampled:
            obs.trace.complete("decode.token", t0, now - t0, ctx=g.ctx,
                               index=g.produced, slot=g.slot)
        if not g.handle._emit(("token", tok, g.produced), now):
            self._retire(g, "backpressure", error=RequestRejected(
                "stream consumer too slow (token buffer full)"))
            return True
        return self._done(g, tok, now)

    def _first_token(self, g: _Gen, now: float, pieces: int):
        """``decode.first_token``: submit -> the arrival of ``g``'s first
        token on the scheduler's clock, with its three parts in µs: the
        ``decode.queue_wait`` span, the ``decode.prefill_wait`` span (0 for
        a prompt prefilled whole) and from the first prefill call's launch
        to the last one's arrival, the steps run between pieces included."""
        if not obs.trace._ENABLED or g.retired:
            return
        obs.trace.complete(
            "decode.first_token", g.t_submit, now - g.t_submit, ctx=g.ctx,
            seq=g.seq, prompt_len=g.prompt_len, pieces=pieces,
            queue_wait_us=_us(g.t_admit - g.t_submit),
            prefill_wait_us=_us(g.t_prefill - g.t_admit),
            prefill_us=_us(now - g.t_prefill))

    def _count(self, counted: dict) -> dict:
        """What the model counted in the call just read (an expert layer's
        ``moe.*``; nothing for a model that counts nothing), as span
        attributes — and added to the counters of the same names (``*_max``:
        a gauge) and to ``stats()["counted"]``."""
        for name, v in counted.items():
            if name.endswith("_max"):
                obs.set_gauge(name, v)
                self.counted[name] = max(self.counted.get(name, 0), v)
            else:
                obs.inc(name, v)
                self.counted[name] = self.counted.get(name, 0) + v
        return counted

    def _step_seed(self) -> int:
        # deterministic per count of steps launched: replays reproduce
        # token-for-token
        return (self.steps_launched * 1000003 + 12345) & 0x7FFFFFFF

    def _admit(self, now: float) -> int:
        """Move queued generations into free slots and launch their
        prefills (at the step boundary). Page exhaustion leaves the
        request queued."""
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
                        self._count_shed("deadline")
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
                    g.slot = free.pop(0)
                    self._slots[g.slot] = g
                    admitted.append((g, bucket))
            admit.set(admitted=len(admitted))
        for g, bucket in admitted:
            g.t_admit = time.monotonic()
            obs.trace.complete("decode.queue_wait", g.t_submit,
                              g.t_admit - g.t_submit, ctx=g.ctx,
                              seq=g.seq, priority=g.priority)
            if self._piece:
                g.ahead = len(self._prefilling)
                self._prefilling.append(g)
            else:
                self._launch_prefill(g, bucket, g.t_admit)
        if admitted:
            self.admitted += len(admitted)
            obs.inc("decode.admitted", len(admitted))
        return len(admitted)

    def _launch_prefill(self, g: _Gen, bucket: int, t_launch: float):
        """Launch ``g``'s prompt, or where prompts go in pieces the next
        piece of it (``bucket``: the positions of the call); the call that
        holds the prompt's end makes ``g`` a decoding slot."""
        start = g.fed
        if not start:
            g.t_prefill = t_launch
            if self._piece:     # its stay behind other prompts' pieces
                obs.trace.complete("decode.prefill_wait", g.t_admit,
                                   t_launch - g.t_admit, ctx=g.ctx,
                                   seq=g.seq, ahead=g.ahead)
        where = {"start": start} if self._piece else {}
        launched = self.engine.launch_prefill(
            g.tokens, self.engine.pool.table(g.seq),
            temperature=g.temperature, seed=g.seed, slot=g.slot, **where)
        g.fed = start + bucket
        self.prefill_pieces += 1
        obs.inc("decode.prefill_pieces")
        last = g.fed >= g.prompt_len
        if last:
            g.launched = 1
            self._release_if_last(g)
        self._inflight.append(_InFlight(
            "prefill", launched, t_launch, [g] if last else [], seq=g.seq,
            bucket=bucket, prompt_len=g.prompt_len, start=start,
            pieces=-(-g.prompt_len // bucket)))

    def _feed(self):
        """One piece of the oldest prefilling generation that is still
        wanted; cancel and deadline are looked at here, a piece apart, as a
        decoding stream's are a token apart."""
        while self._prefilling:
            g = self._prefilling[0]
            now = time.monotonic()
            if g.handle.cancelled() and not g.retired:
                self._retire(g, "cancelled")
            elif (g.deadline is not None and now >= g.deadline
                  and not g.retired):
                self._count_shed("deadline")
                self._retire(g, "deadline", error=DeadlineExceeded(
                    f"deadline expired {g.fed} positions into the prompt"))
            if not g.retired:
                self._launch_prefill(g, self._piece, now)
                if g.launched:      # that was its last piece
                    self._prefilling.popleft()
                return
            self._prefilling.popleft()

    def _release_if_last(self, g: _Gen):
        """A stream's last token by ``max_new_tokens`` or the model's length
        is known as it is launched: its slot and pages go back then, not a
        turn later when the token is read, so the next request's prefill
        is queued right behind that step (the device runs in launch order:
        nothing writes a freed page before the step has read it)."""
        if (g.launched >= g.max_new
                or g.prompt_len + g.launched >= self.engine.max_length):
            self._release(g)

    def _release(self, g: _Gen):
        """Give back ``g``'s slot and pages, once. EVERY exit path funnels
        here — the page-leak guarantee lives in this one place."""
        if self._slots[g.slot] is g:
            self._slots[g.slot] = None
            self.engine.pool.free(g.seq)

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
        if self.eos_id is not None and tok == self.eos_id:
            self._retire(g, "eos")
            return True
        if g.produced >= g.max_new:
            self._retire(g, "length")
            return True
        if g.prompt_len + g.produced >= self.engine.max_length:
            self._retire(g, "overflow")
            return True
        if g.deadline is not None and now >= g.deadline:
            self._count_shed("deadline")
            self._retire(g, "deadline", error=DeadlineExceeded(
                f"deadline expired after {g.produced} tokens"))
            return True
        if g.handle.cancelled():
            self._retire(g, "cancelled")
            return True
        return False

    def _retire(self, g: _Gen, reason: str,
                error: Optional[ServeError] = None):
        """Leave the batch: slot and pages back (``_release``, if its last
        launch has not done so already), the terminal event, the request
        span. A token of ``g`` still in flight is dropped when it
        arrives."""
        g.retired = True
        self._release(g)
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
            seq=g.seq, tokens=g.produced, outcome=reason)
        with self._cv:
            self._cv.notify_all()

    def _abort_all(self, exc: ServeError):
        with self._cv:
            queued = [g for lane in self._lanes for g in lane]
            for lane in self._lanes:
                del lane[:]
        # resident, or released with its last token still on its way
        inflight = [g for f in self._inflight for g in f.who]
        self._inflight.clear()
        self._prefilling.clear()
        for g in [g for g in self._slots if g is not None] + inflight:
            if not g.retired:
                self._retire(g, "aborted", error=exc)
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
            while self._qsize() or self._active() or self._inflight:
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
                "steps_launched": self.steps_launched,
                "launched_ahead": self.launched_ahead,
                "launched_ahead_share": (self.launched_ahead
                                         / max(1, self.steps_launched)),
                "dropped_speculative": self.dropped_speculative,
                "tokens_out": self.tokens_out,
                "admitted": self.admitted,
                # prefill calls, and the positions of one where prompts go
                # in pieces (None: a prompt is one call)
                "prefill_pieces": self.prefill_pieces,
                "prefill_piece": self._piece,
                "queued": self._qsize(),
                "active": self._active(),
                "occupancy": self._occupancy,
                "draining": self._draining,
                "counted": dict(self.counted),
            }
        out["engine"] = self.engine.stats()
        return out


# -- the pages of one layer through a page table. At the end of the module, and
# called from ONE line of ``_piece_fn``, so that every program traced through
# this file keeps the lines it is cached under.

# The largest window (a page of one layer) that XLA:TPU's gather takes whole:
# for a larger one it splits the OPERAND, the whole pool, into column halves
# ("mini-gather-slice": two copies of half the pool each, a piece; seen in the
# compiled program of a 256 x 2,560 B page, PR 46). 256 x 2,048 B is taken.
_GATHER_WINDOW_BYTES = 512 * 1024


def _pages(kv, table, layer):
    """``kv[table, layer]``: (n, page_size) + row for a page table of n ids;
    in half pages where a whole one is over ``_GATHER_WINDOW_BYTES``."""
    import jax.numpy as jnp

    page = kv.shape[2]
    window = int(np.prod(kv.shape[2:])) * kv.dtype.itemsize
    if page % 2 or window <= _GATHER_WINDOW_BYTES:
        return kv[table, layer]
    halves = kv.reshape(kv.shape[:2] + (2, page // 2) + kv.shape[3:])
    return halves[table[:, None], layer, jnp.arange(2)[None, :]]

"""Host-side asynchronous parameter server (dist_async transport).

Reference: ps-lite (``3rdparty/ps-lite``: ZMQ van, KVServer message loop,
server-side optimizer — TBV, SURVEY.md §3.4). TPU-native plan keeps this
**host-side over DCN** (north star): TPU workers push grads from host buffers,
the server applies the optimizer on arrival (no barrier — async), workers pull
fresh weights.

Transport: length-prefixed msgpack-free binary framing over TCP sockets
(stdlib only; the reference uses ZMQ which is not in this image). The server
runs one thread per connection + a lock per key, matching the reference's
per-key serialized updates. A C++ implementation of the same wire protocol
lives in native/ps (same framing), used when built.

Wire format (little-endian):
  u32 total_len | u8 opcode | u16 key_len | key bytes | payload
  opcodes: 0=INIT 1=PUSH 2=PULL 3=SET_OPT 4=BARRIER 5=SHUTDOWN
  (6-9 sparse/seq variants; 16-20 elastic membership — see elastic.py;
  32-42 are the serving plane's range, serve/server.py)
  payload for INIT/PUSH: u8 ndim | u32*ndim shape | u8 dtype_code | raw bytes
  reply for PULL: same array framing; others: u8 status

Elastic training (docs/ROBUSTNESS.md "Elastic training"): with worker
heartbeats flowing, every barrier and epoch rendezvous is scoped to the
LIVE membership — a SIGKILL'd worker is declared dead after K missed
heartbeats and collective waits release over the survivors instead of
timing out. With ``snapshot_dir`` set the server also periodically
snapshots weights / optimizer state / the seq-dedup table through the
checkpoint/ atomic+CRC machinery and warm-restarts from the newest valid
snapshot, so a SIGKILL'd server comes back with exactly-once semantics
intact (clients retry with capped backoff; replayed pushes dedupe).
"""
from __future__ import annotations

import contextlib
import json
import os
import pickle
import socket
import struct
import threading
import time
from typing import Dict, Optional

import numpy as np

from .. import copytrack, obs, tsan
from ..obs import context as obs_context
from ..base import CODE_TO_DTYPE, DTYPE_TO_CODE, get_env
from ..wire import PS_WIRE
from . import elastic as elastic_mod
from .elastic import (ELASTIC_OP_NAMES, OP_CLOCK, OP_CLOCK_PULL, OP_EPOCH,
                      OP_HB, OP_JOIN, OP_LEAVE, OP_PULL_STALE, OP_REDUCE,
                      OP_REDUCE_SCOPED, ST_ERROR, ST_OK, ST_QUARANTINED,
                      ST_STALE)

# opcode constants come from the declarative registry (mxnet_tpu/wire.py):
# codes, names, and exactly-once metadata live in ONE table that the
# protocol linter cross-checks against this module's dispatch
(OP_INIT, OP_PUSH, OP_PULL, OP_SET_OPT, OP_BARRIER, OP_SHUTDOWN,
 OP_PUSH_SPARSE, OP_PULL_SPARSE, OP_PUSH_SEQ, OP_PUSH_SPARSE_SEQ,
 OP_TELEMETRY, OP_STATS) = \
    PS_WIRE.codes("init", "push", "pull", "set_opt", "barrier", "shutdown",
                  "push_sparse", "pull_sparse", "push_seq",
                  "push_sparse_seq", "telemetry", "stats")

# opcode → canonical name (telemetry labels; mxnet_tpu.chaos.rpc mirrors
# it) — includes the elastic range, which this server also dispatches
OP_NAMES = dict(PS_WIRE.names())

# one rule table fault-injects both planes (the serve/server.py idiom) —
# the full PS table, so the fleet-telemetry/stats ops are targetable too
from ..chaos import rpc as _chaos_rpc  # noqa: E402

_chaos_rpc.OP_NAMES.update(OP_NAMES)


def _pack_array(arr: np.ndarray) -> bytes:
    code = DTYPE_TO_CODE[arr.dtype.name]
    head = struct.pack("<B", arr.ndim) + struct.pack(f"<{arr.ndim}I", *arr.shape) \
        + struct.pack("<B", code)
    copytrack.TRACKER.serialized(arr.nbytes)
    copytrack.TRACKER.copied(arr.nbytes)
    # one copy of the array bytes into the frame is today's wire
    # contract; memoryview scatter-gather framing is ROADMAP item 4 —
    # copytrack counts this copy so the rewrite's gain is measurable
    return head + arr.tobytes()  # lint: disable=redundant-buffer-copy


def _unpack_array(buf: memoryview) -> np.ndarray:
    ndim = struct.unpack_from("<B", buf, 0)[0]
    shape = struct.unpack_from(f"<{ndim}I", buf, 1)
    code = struct.unpack_from("<B", buf, 1 + 4 * ndim)[0]
    if code == 16:  # 2-bit compressed gradient (see kvstore/compression.py)
        from .compression import dequantize_2bit

        size = int(np.prod(shape)) if ndim else 1
        off = 6 + 4 * ndim
        if len(buf) < off or len(buf) - off < (size + 3) // 4:
            raise ConnectionError("truncated 2-bit payload")  # drops the conn
        (threshold,) = struct.unpack_from("<f", buf, 2 + 4 * ndim)
        packed = np.frombuffer(buf, dtype=np.uint8, offset=off)
        return dequantize_2bit(packed, threshold, size).reshape(shape)
    dtype = np.dtype(CODE_TO_DTYPE[code])
    data = np.frombuffer(buf, dtype=dtype, offset=2 + 4 * ndim)
    copytrack.TRACKER.copied(data.nbytes)
    return data.reshape(shape).copy()


def _array_nbytes(buf: memoryview) -> int:
    """Byte length of one packed array at the head of ``buf`` (so two arrays
    can ride one payload — the sparse wire format: indices then rows)."""
    ndim = struct.unpack_from("<B", buf, 0)[0]
    shape = struct.unpack_from(f"<{ndim}I", buf, 1)
    code = struct.unpack_from("<B", buf, 1 + 4 * ndim)[0]
    size = 1
    for s in shape:
        size *= s
    itemsize = np.dtype(CODE_TO_DTYPE[code]).itemsize
    return 2 + 4 * ndim + size * itemsize


def _pack_sparse(indices: np.ndarray, rows: np.ndarray) -> bytes:
    return (_pack_array(np.ascontiguousarray(indices, np.int32))
            + _pack_array(np.ascontiguousarray(rows)))


def _unpack_sparse(buf: memoryview):
    n = _array_nbytes(buf)
    return _unpack_array(buf[:n]), _unpack_array(buf[n:])


def _pack_arrays(arrays) -> bytes:
    """N arrays on one payload: u8 count, then each in the array framing
    above (the sparse wire generalized — mxnet_tpu.serve's multi-input
    requests and multi-output replies ride this)."""
    if len(arrays) > 255:
        raise ValueError(f"too many arrays for one frame ({len(arrays)})")
    buf = struct.pack("<B", len(arrays)) + b"".join(
        _pack_array(np.ascontiguousarray(a)) for a in arrays)
    copytrack.TRACKER.copied(len(buf) - 1)  # the gather join re-copies
    return buf


def _unpack_arrays(buf: memoryview):
    (count,) = struct.unpack_from("<B", buf, 0)
    out, off = [], 1
    for _ in range(count):
        n = _array_nbytes(buf[off:])
        out.append(_unpack_array(buf[off:off + n]))
        off += n
    return out, off


def _send_msg(sock: socket.socket, opcode: int, key: str = "", payload=b""):
    """Frame and send one message. ``payload`` is ``bytes``/``memoryview``
    or a list of buffer parts — parts go straight to ``sendmsg`` without
    ever being concatenated (the scatter-gather send the data-plane lint
    demands: the old ``sendall(header + body)`` re-copied every message)."""
    kb = key.encode()
    parts = list(payload) if isinstance(payload, (list, tuple)) \
        else [payload]
    plen = sum(len(p) for p in parts)
    head = struct.pack("<IBH", 3 + len(kb) + plen, opcode, len(kb)) + kb
    _send_parts(sock, [head] + parts)


def _send_parts(sock, parts) -> None:
    """sendall() for a list of buffers, scatter-gather: no concatenation,
    resumes correctly after a partial ``sendmsg``."""
    views = [memoryview(p) for p in parts if len(p)]
    if not hasattr(sock, "sendmsg"):  # test/chaos socket doubles
        copytrack.TRACKER.copied(sum(len(v) for v in views))
        sock.sendall(b"".join(views))
        return
    while views:
        sent = sock.sendmsg(views)
        while sent:
            if sent >= len(views[0]):
                sent -= len(views[0])
                views.pop(0)
            else:
                views[0] = views[0][sent:]
                sent = 0


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    chunks = []
    while n:
        c = sock.recv(min(n, 1 << 20))
        if not c:
            raise ConnectionError("peer closed")
        chunks.append(c)
        n -= len(c)
    if len(chunks) == 1:
        return chunks[0]  # single-chunk receive: join would be a no-op
    buf = b"".join(chunks)
    copytrack.TRACKER.copied(len(buf))  # multi-chunk reassembly copy
    return buf


def _recv_msg(sock: socket.socket):
    (ln,) = struct.unpack("<I", _recv_exact(sock, 4))
    body = memoryview(_recv_exact(sock, ln))
    opcode, klen = struct.unpack_from("<BH", body, 0)
    key = bytes(body[3:3 + klen]).decode()
    payload = body[3 + klen:]
    return opcode, key, payload


class PSServer:
    """The server process: aggregates pushes and runs the optimizer per key.

    async mode (reference dist_async): every push immediately applies
    ``updater(key, grad, weight)`` under the key's lock — no worker barrier.
    """

    def __init__(self, host="0.0.0.0", port=9091, num_workers=1,
                 barrier_timeout=60.0, snapshot_dir=None,
                 snapshot_period=None, hb_interval=None, miss_k=None,
                 async_staleness=None):
        self._weights: Dict[str, np.ndarray] = {}
        self._locks: Dict[str, threading.Lock] = {}
        self._updater = None
        self._optimizer = None
        self._opt_spec: Optional[str] = None
        self._global_lock = tsan.lock("ps.global")
        from collections import OrderedDict

        self._num_workers = num_workers
        # elastic membership plane: created lazily at the first OP_JOIN so a
        # classic fleet (no heartbeats) pays nothing — not even the liveness
        # thread. The config is captured now for the lazy construction.
        self._elastic: Optional[elastic_mod.ElasticState] = None
        self._elastic_cfg = (hb_interval, miss_k)
        self._elastic_lock = tsan.lock("ps.elastic")
        # durable-state plane (docs/ROBUSTNESS.md "Elastic training"):
        # periodic snapshots through checkpoint/'s atomic+CRC manager, warm
        # restart from the newest valid one
        self._snapshot_dir = snapshot_dir or get_env(
            "MXNET_PS_SNAPSHOT_DIR", None)
        self._snapshot_period = float(
            snapshot_period if snapshot_period is not None
            else get_env("MXNET_PS_SNAPSHOT_PERIOD_S", 5.0, float))
        self._snap_mgr = None
        self._snap_step = 0
        self._snap_thread: Optional[threading.Thread] = None
        self._snap_lock = tsan.lock("ps.snapshot")
        self._wal: Optional[elastic_mod.PushWAL] = None
        # (client_id, key) -> last applied seq; LRU-bounded so client churn
        # (each process draws a fresh id) cannot grow the map forever.
        # Own lock: handlers for DIFFERENT keys share this dict, so the
        # per-key weight locks are not enough (mirrors the C++ seq_mu_).
        self._applied_seq: "OrderedDict" = OrderedDict()
        # key-indexed mirror of _applied_seq (same lock), so a durable
        # snapshot can copy ONE key's entries under that key's lock
        # instead of rescanning the 64k-entry LRU per key
        self._seq_by_key: Dict[str, Dict[int, int]] = {}
        self._seq_lock = tsan.lock("ps.seq")
        self._barrier_timeout = barrier_timeout  # straggler window (seconds)
        self._barrier_count = 0
        self._barrier_gen = 0
        # idempotent barrier (docs/ROBUSTNESS.md): clients send a
        # (client_id, barrier_epoch) token; the arrival SET dedups a
        # retransmit within the round, and the released LRU acks a
        # retransmit that arrives after the round completed.
        self._barrier_arrived: Dict = {}
        self._barrier_stamps: Dict = {}  # token -> arrival monotonic (the
        # per-rank barrier-wait attribution reads these at release)
        self._barrier_released: "OrderedDict" = OrderedDict()
        self._barrier_cv = tsan.condition("ps.barrier")
        # training-fleet telemetry plane (obs/fleetstats.py): cached
        # per-worker parts piggybacked on heartbeats + the straggler
        # detector over them; exactly-once OP_TELEMETRY drains via the
        # collection-token LRU (the serve-plane idiom)
        from ..obs import fleetstats as _fleetstats

        self.fleet = _fleetstats.FleetAggregator(
            member_ranks=self._live_ranks)
        self._hot_keys = _fleetstats.HotKeyTable()
        self._telemetry_tokens: "OrderedDict" = OrderedDict()
        self._telemetry_lock = tsan.lock("ps.telemetry")
        # bounded-staleness async plane (docs/ROBUSTNESS.md "Asynchronous
        # training"): per-rank committed clocks (rank -> last COMPLETED
        # step), the cid->rank table that attributes them, and the
        # per-rank staleness widening the straggler policy grants.
        # Initialized BEFORE _init_durability(): snapshot restore
        # (install_server_state) max-merges straight into these tables.
        # Lock order: _clock_cv may take el.cv (floor computation), never
        # the reverse — membership callbacks fire outside el.cv.
        self._clock: Dict[int, int] = {}
        self._clock_rank: Dict[int, int] = {}
        self._staleness_widen: Dict[int, int] = {}
        self._clock_cv = tsan.condition("ps.clock")
        if async_staleness is None:
            env = get_env("MXNET_ASYNC_STALENESS", None)
            async_staleness = int(env) if env is not None else None
        self._async_staleness = async_staleness
        self._async_widen_step = get_env("MXNET_ASYNC_WIDEN", 2, int)
        self._async_max_staleness = get_env(
            "MXNET_ASYNC_MAX_STALENESS", 16, int)
        if self._async_staleness is not None:
            # actuation (ROADMAP open item 2): straggler verdicts change
            # fleet behavior instead of only being reported. Registered
            # only in async mode so sync fleets keep PR 15 behavior.
            self.fleet.on_straggler(self._policy_on_straggler)
        self._started = time.monotonic()
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(64)
        self.port = self._sock.getsockname()[1]
        self._stop = threading.Event()
        self._threads = []
        self._conns = []
        self._warm_thread: Optional[threading.Thread] = None
        if self._snapshot_dir:
            self._init_durability()

    def serve_forever(self):
        while not self._stop.is_set():
            try:
                self._sock.settimeout(0.5)
                conn, _ = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            self._conns.append(conn)
            t = threading.Thread(target=self._handle, args=(conn,), daemon=True)
            t.start()
            self._threads = [th for th in self._threads if th.is_alive()]
            self._threads.append(t)

    def start(self):
        t = threading.Thread(target=self.serve_forever, daemon=True)
        t.start()
        return t

    # ------------------------------------------------------------------
    # elastic membership + durable state
    # ------------------------------------------------------------------
    def _elastic_state(self) -> elastic_mod.ElasticState:
        """The membership plane, created at the first OP_JOIN. The change
        callback pokes the barrier condvar so a declared death releases a
        waiting (now survivor-complete) barrier immediately."""
        with self._elastic_lock:
            if self._elastic is None:
                hb, miss = self._elastic_cfg
                self._elastic = elastic_mod.ElasticState(
                    hb_interval=hb, miss_k=miss,
                    on_change=[self._on_membership_change],
                    on_prune=[self.fleet.forget])
            return self._elastic

    def _on_membership_change(self):
        with self._barrier_cv:
            self._release_barrier_locked()
            self._barrier_cv.notify_all()
        # a declared death moves the committed-clock floor (dead ranks
        # stop holding it down) — staleness-gated pulls must re-check
        with self._clock_cv:
            self._clock_cv.notify_all()

    def _live_ranks(self):
        """Active members' ranks — the fleet aggregator's membership view
        (judging a window waits for every LIVE rank's report; dead/left
        ranks stop counting)."""
        el = self._elastic
        if el is None:
            return None
        with el.cv:
            return [m.rank for m in el.active_members()]

    def _required_workers(self) -> int:
        """Barrier quorum: the LIVE membership once anyone heartbeats, the
        static launch-time worker count otherwise (classic fleets)."""
        el = self._elastic
        if el is not None:
            with el.cv:
                if el.has_members():
                    return max(1, el.active_count())
        return self._num_workers

    # ------------------------------------------------------------------
    # bounded-staleness async clock plane (docs/ROBUSTNESS.md
    # "Asynchronous training")
    # ------------------------------------------------------------------
    def _clock_floor_locked(self) -> int:
        """Caller holds ``_clock_cv``. The committed-clock floor: min
        committed step over LIVE ranks — a live rank that has not
        committed yet floors at 0, so fast ranks cannot run away before
        the fleet's first commits land. Dead/left ranks drop out the
        moment liveness declares them (membership changes notify
        ``_clock_cv`` for exactly this). Without a membership plane every
        rank that ever committed counts."""
        live = self._live_ranks()
        if live:
            return min(self._clock.get(r, 0) for r in live)
        if not self._clock:
            return 0
        return min(self._clock.values())

    def _clock_bounds_locked(self):
        """Caller holds ``_clock_cv``: (floor, max clock, policy widen)."""
        floor = self._clock_floor_locked()
        maxc = max(self._clock.values()) if self._clock else 0
        widen = max(self._staleness_widen.values(), default=0)
        return floor, maxc, widen

    def _advance_clock(self, cid: int, rank: int, step: int) -> bool:
        """Commit "rank FINISHED step ``step``" — max-merge (a retried or
        reordered frame can never roll a clock back) and wake every
        staleness-gated pull. The advance rides the WAL (kind 4) before
        the caller acks, so a SIGKILLed server warm-restarts
        mid-async-storm with the clock table intact — the exactly-once
        contract extends to clocks."""
        with self._clock_cv:
            advanced = step > self._clock.get(rank, -1)
            if advanced:
                self._clock[rank] = step
                self._clock_cv.notify_all()
            self._clock_rank[cid] = rank
        if advanced and self._wal is not None:
            # append OUTSIDE _clock_cv: the fsync must not serialize the
            # gated-pull wakeups; still durable before the ack
            self._wal.append(4, cid, step, str(rank), b"")
        return advanced

    def _policy_on_straggler(self, verdict: dict):
        """``on_straggler`` actuation (async mode only — PR 15 built the
        sensor, this closes the loop): a compute-blamed straggler WIDENS
        the fleet's staleness bound (fast ranks run further ahead instead
        of stalling at the gate), a data_wait-blamed one triggers a shard
        recut (the pathological shard rotates off the rank at the next
        epoch boundary), and a recovery withdraws the widening. Runs on
        the heartbeat handler thread — exception containment lives in
        ``FleetAggregator._judge``, and this hook must return promptly
        (the SLOMonitor callback contract)."""
        kind = verdict.get("kind")
        rank = verdict.get("rank")
        if rank is None:
            return
        if kind == "recovered":
            with self._clock_cv:
                narrowed = self._staleness_widen.pop(rank, None)
                if narrowed is not None:
                    self._clock_cv.notify_all()
            if narrowed is not None:
                obs.event("train.async.staleness_narrowed", rank=rank,
                          was=narrowed)
            return
        if kind != "straggler":
            return
        blame = verdict.get("blame")
        if blame == "data_wait" and self._elastic is not None:
            self._elastic.request_recut()
            obs.event("train.async.shard_recut", rank=rank, blame=blame)
            return
        base = self._async_staleness or 0
        with self._clock_cv:
            cur = self._staleness_widen.get(rank, 0)
            new = min(cur + self._async_widen_step,
                      max(0, self._async_max_staleness - base))
            if new != cur:
                self._staleness_widen[rank] = new
                self._clock_cv.notify_all()
        if new != cur:
            obs.inc("train.async.staleness_widened")
            obs.event("train.async.staleness_widened", rank=rank,
                      widen=new, blame=blame or "compute")

    def _init_durability(self):
        from ..checkpoint.manager import CheckpointManager

        self._snap_mgr = CheckpointManager(self._snapshot_dir, prefix="ps",
                                           keep_last=3, async_write=False)
        state = self._snap_mgr.load_latest()
        if state is not None and state.meta.get("kind") == "ps_server":
            if state.meta.get("generation") is not None:
                self._elastic_state()  # restore generation monotonicity
            elastic_mod.install_server_state(self, state)
            self._snap_step = (self._snap_mgr.latest_step() or 0) + 1
        # replay acked-but-unsnapshotted pushes through the seq-dedup path
        # (anything the snapshot already covers skips itself), THEN open a
        # fresh log — zero lost, zero double-applied across the restart.
        # Two passes: key births (kind 2) first, then pushes in order —
        # the live handlers append birth and first-push records on
        # DIFFERENT locks, so a concurrent worker's acked push can land in
        # the log ahead of the key's birth record; a single ordered pass
        # would silently drop that acked push at `key not in weights`
        self._wal = elastic_mod.PushWAL(self._snapshot_dir)
        pushes = []

        def _births_first(kind, cid, seq, key, payload):
            if kind == 2:
                self._replay_push(kind, cid, seq, key, payload)
            else:
                pushes.append((kind, cid, seq, key, payload))

        replayed = self._wal.replay(_births_first)
        for rec in pushes:
            self._replay_push(*rec)
        if replayed:
            obs.event("elastic.ps_wal_replayed", records=replayed)
        self._wal.rotate(self._snap_step)
        if self._snapshot_period > 0:
            self._snap_thread = threading.Thread(
                target=self._snapshot_loop, daemon=True,
                name="mxtpu-ps-snapshot")
            self._snap_thread.start()

    def _replay_push(self, kind: int, cid: int, seq: int, key: str,
                     payload: bytes):
        """WAL replay: the OP_PUSH_SEQ / OP_PUSH_SPARSE_SEQ apply path
        minus the wire — dedup by (cid, seq), apply, record. Kind 2 is a
        key-birth record (OP_INIT): first-wins, like the live handler."""
        if kind == 2:
            with self._global_lock:
                if key not in self._weights:
                    self._weights[key] = _unpack_array(memoryview(payload))
                    self._locks[key] = tsan.lock("ps.key")
            return
        if kind == 3:  # optimizer spec (OP_SET_OPT), in order vs pushes
            spec = bytes(payload).decode("ascii", errors="replace")
            if spec != (self._opt_spec or "") or self._updater is None:
                # an unchanged spec from a WAL file overlapping the
                # snapshot must NOT rebuild the Updater — that would wipe
                # the snapshot-restored slots (momentum etc.)
                self._set_optimizer_bytes(bytes(payload), warm=False)
            return
        if kind == 4:  # committed-clock advance (OP_CLOCK): key is the
            # decimal rank, seq the step — max-merge, so replaying a
            # record older than the snapshot-restored clock is a no-op
            # and a clock can never roll back across a warm restart
            try:
                rank = int(key)
            except ValueError:
                return
            with self._clock_cv:
                if seq > self._clock.get(rank, -1):
                    self._clock[rank] = seq
                self._clock_rank[cid] = rank
            return
        if key not in self._weights:
            return
        buf = memoryview(payload)
        with self._locks[key]:
            with self._seq_lock:
                fresh = self._applied_seq.get((cid, key), -1) < seq
            if not fresh:
                return
            if kind == 0:
                grad = _unpack_array(buf)
                if self._updater is not None:
                    self._apply(key, grad, self._weights[key])
                else:
                    self._weights[key] = self._weights[key] + grad
            else:
                if not self._apply_sparse(key, buf, locked=True):
                    return
            with self._seq_lock:
                self._record_seq(cid, key, seq)

    def _snapshot_loop(self):
        while not self._stop.wait(self._snapshot_period):
            try:
                self.snapshot_now()
            except Exception:  # noqa: BLE001 — a failed snapshot must not
                obs.inc("elastic.ps_snapshot_errors")  # kill the server

    def snapshot_now(self):
        """Write one durable snapshot (atomic commit, CRC manifest). Safe
        to call concurrently with request handling: per-key consistency is
        taken under the same locks the push path applies under."""
        if self._snap_mgr is None:
            return
        with self._snap_lock:  # serialize: periodic vs explicit callers
            state = elastic_mod.capture_server_state(self)
            step, self._snap_step = self._snap_step, self._snap_step + 1
            with obs.trace.span("elastic.ps_snapshot", step=step):
                self._snap_mgr.save(state, step, block=True)
            if self._wal is not None:
                # pushes newer than this snapshot land in the fresh log;
                # older logs are covered by the snapshot and GC'd
                self._wal.rotate(step + 1)
            obs.inc("elastic.ps_snapshots")

    def stop(self):
        self._stop.set()
        if self._elastic is not None:
            self._elastic.close()
        if self._wal is not None:
            self._wal.close()
        try:
            self._sock.close()
        except OSError:
            pass
        # snapshot: _handle threads concurrently .remove() from _conns and
        # iterating the live list could skip a neighbor of a removed entry
        for c in list(self._conns):  # sever live sessions too — a stopped
            try:                     # server must look dead, not half-alive
                c.close()
            except OSError:
                pass
        # reap worker threads: handlers exit once their sockets are severed,
        # the snapshot loop and warm thread see _stop / finish their bounded
        # work. Leaks are counted, not waited out — stop() must be prompt.
        me = threading.current_thread()  # OP_SHUTDOWN stops from a handler
        reap = [t for t in self._threads if t is not me]
        if self._snap_thread is not None and self._snap_thread is not me:
            reap.append(self._snap_thread)
        if self._warm_thread is not None:
            reap.append(self._warm_thread)
        deadline = time.monotonic() + 1.0  # ONE budget for the whole reap
        leaked = 0
        for t in reap:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
            if t.is_alive():
                leaked += 1
        if leaked:
            obs.inc("kvstore.server.threads_leaked", leaked)
            obs.event("kvstore.server.threads_leaked", count=leaked)

    # ------------------------------------------------------------------
    def _handle(self, conn: socket.socket):
        try:
            self._handle_loop(conn)
        finally:  # prune: reconnect-retrying clients make churn routine
            try:
                conn.close()
            except OSError:
                pass
            try:
                self._conns.remove(conn)
            except ValueError:
                pass

    def _handle_loop(self, conn: socket.socket):
        try:
            while True:
                opcode, key, payload = _recv_msg(conn)
                # strip wire trace context BEFORE any key lookup — a
                # context-bearing key must hit the same weight/lock/seq
                # tables as its plain form (old-format frames: no
                # separator, nothing stripped)
                key, wctx = obs_context.extract_key(key)
                rec = obs.enabled()
                t0 = time.monotonic() if rec else 0.0
                if rec:
                    obs.inc("kvstore.server.bytes_received", len(payload))
                try:
                    # server-side span joins the worker's trace, so a PS
                    # RPC shows both halves (client wait vs server apply)
                    # on the merged timeline
                    with obs_context.use(wctx), \
                            obs.trace.span(
                                "kvstore.server.rpc",
                                op=OP_NAMES.get(opcode, str(opcode)),
                                key=key):
                        alive = self._handle_one(conn, opcode, key, payload)
                finally:
                    if rec:
                        # per-RPC service time, server side (lock wait +
                        # optimizer apply + reply serialization)
                        obs.observe(
                            "kvstore.server.rpc."
                            f"{OP_NAMES.get(opcode, str(opcode))}_seconds",
                            time.monotonic() - t0)
                if not alive:
                    return
        except (ConnectionError, OSError):
            return

    def _handle_one(self, conn: socket.socket, opcode: int, key: str,
                    payload) -> bool:
        """Serve one framed request; False only after OP_SHUTDOWN."""
        if opcode == OP_INIT:
            arr = _unpack_array(payload)
            with self._global_lock:
                created = key not in self._weights
                if created:
                    self._weights[key] = arr
                    self._locks[key] = tsan.lock("ps.key")
            if created and self._wal is not None:
                # key birth rides the WAL (kind 2, one small fsynced
                # append) so a warm restart never sees a push for a key it
                # doesn't know — without paying a full-state snapshot per
                # key, let alone per re-init from every non-winning worker
                self._wal.append(2, 0, 0, key, bytes(payload))
            _send_msg(conn, OP_INIT, key, b"\x00")
        elif opcode == OP_PUSH:
            grad = _unpack_array(payload)
            with self._locks[key]:
                if self._updater is not None:
                    w = self._weights[key]
                    self._apply(key, grad, w)
                else:
                    self._weights[key] = self._weights[key] + grad
            _send_msg(conn, OP_PUSH, key, b"\x00")
        elif opcode == OP_PUSH_SEQ:
            # exactly-once push: payload prefixed with (client_id,
            # seq); a retried frame whose seq was already applied is
            # acked without re-applying — fixes the at-least-once
            # double-apply the plain PUSH retry path has
            if key not in self._weights or len(payload) < 16:
                _send_msg(conn, OP_PUSH_SEQ, key, b"\x01")
                return True
            cid, seq = struct.unpack_from("<QQ", payload, 0)
            grad = _unpack_array(payload[16:])
            from ..chaos.proc import kill_point

            rec = obs.enabled()
            t_apply = t_wal = 0.0
            with self._locks[key]:
                with self._seq_lock:
                    fresh = self._applied_seq.get((cid, key), -1) < seq
                if fresh:
                    t0 = time.monotonic() if rec else 0.0
                    if self._updater is not None:
                        self._apply(key, grad, self._weights[key])
                    else:
                        self._weights[key] = self._weights[key] + grad
                    if rec:
                        t_apply = time.monotonic() - t0
                    # record only AFTER a successful apply, so a
                    # failed apply doesn't burn the seq
                    with self._seq_lock:
                        self._record_seq(cid, key, seq)
                    if self._wal is not None:
                        # durable BEFORE the ack: an acked push may never
                        # be resent, so it must survive a SIGKILL here
                        t0 = time.monotonic() if rec else 0.0
                        self._wal.append(0, cid, seq, key,
                                         bytes(payload[16:]))
                        if rec:
                            t_wal = time.monotonic() - t0
            if rec and fresh:
                # reduce-plane attribution (docs/OBSERVABILITY.md
                # "Training-fleet telemetry"): optimizer-apply vs
                # WAL-append+fsync split per applied push, plus the
                # bounded top-N hot-key table train_report renders
                obs.observe("kvstore.server.push.apply_seconds", t_apply)
                if self._wal is not None:
                    obs.observe("kvstore.server.push.wal_seconds", t_wal)
                self._hot_keys.record(key, len(payload) - 16, t_apply)
            # chaos: die with the update applied+recorded but unacked —
            # the client MUST retry and the retry MUST dedupe, across a
            # warm restart when snapshots are on (docs/ROBUSTNESS.md)
            kill_point("ps:post_apply")
            kill_point("ps:pre_reply")
            _send_msg(conn, OP_PUSH_SEQ, key, b"\x00")
        elif opcode == OP_PULL:
            with self._locks.get(key, self._global_lock):
                arr = self._weights[key]
            rec = obs.enabled()
            t0 = time.monotonic() if rec else 0.0
            _send_msg(conn, OP_PULL, key, _pack_array(arr))
            if rec:
                # the serialize half of the per-RPC split (pushes reply
                # one status byte; pulls pay the array encode + send)
                obs.observe("kvstore.server.pull.serialize_seconds",
                            time.monotonic() - t0)
        elif opcode == OP_PUSH_SPARSE:
            # reference kvstore_dist.h sparse PSKV: only touched rows
            # cross the wire; the server applies a row-sparse update.
            # Same validation contract as the C++ twin: bad key /
            # out-of-range or negative index → \x01, never corruption
            ok = self._apply_sparse(key, payload)
            _send_msg(conn, OP_PUSH_SPARSE, key,
                      b"\x00" if ok else b"\x01")
        elif opcode == OP_PUSH_SPARSE_SEQ:
            # sparse twin of OP_PUSH_SEQ: (client_id, seq) prefix
            # dedups a retried frame so the row update applies
            # exactly once even when the ack was lost
            if key not in self._weights or len(payload) < 16:
                _send_msg(conn, OP_PUSH_SPARSE_SEQ, key, b"\x01")
                return True
            cid, seq = struct.unpack_from("<QQ", payload, 0)
            ok = True
            with self._locks[key]:
                with self._seq_lock:
                    fresh = self._applied_seq.get((cid, key), -1) < seq
                if fresh:
                    ok = self._apply_sparse(key, payload[16:],
                                            locked=True)
                    if ok:  # a rejected frame must not burn the seq
                        with self._seq_lock:
                            self._record_seq(cid, key, seq)
                        if self._wal is not None:
                            self._wal.append(1, cid, seq, key,
                                             bytes(payload[16:]))
            _send_msg(conn, OP_PUSH_SPARSE_SEQ, key,
                      b"\x00" if ok else b"\x01")
        elif opcode == OP_PULL_SPARSE:
            reply = b""  # empty = failure, matching the C++ twin
            if key in self._weights:
                idx = _unpack_array(payload).astype(np.int64)
                w = self._weights[key]
                if (idx.ndim == 1 and idx.size > 0
                        and 0 <= idx.min()
                        and idx.max() < w.shape[0]):
                    with self._locks.get(key, self._global_lock):
                        reply = _pack_array(
                            np.ascontiguousarray(w[idx]))
            _send_msg(conn, OP_PULL_SPARSE, key, reply)
        elif opcode == OP_SET_OPT:
            self._set_optimizer_bytes(bytes(payload))
            if self._wal is not None and self._opt_spec:
                # the spec must survive a restart — as one small WAL
                # record, not an inline full-state snapshot that could
                # stall this RPC past the client timeout on large models
                self._wal.append(3, 0, 0, "",
                                 self._opt_spec.encode("ascii"))
            _send_msg(conn, OP_SET_OPT, key, b"\x00")
        elif opcode == OP_BARRIER:
            ok, detail = self._barrier(payload)
            _send_msg(conn, OP_BARRIER, key,
                      b"\x00" if ok else b"\x01" + detail)
        elif opcode == OP_HB:
            # empty payload = connection-liveness ping (the client's
            # ping-before-reuse path) — replies without touching membership
            part_blob = cid = None
            if len(payload) >= 16:
                cid, _rank = struct.unpack_from("<QQ", payload, 0)
                if len(payload) > 16:
                    part_blob = payload[16:]
                st, gen, count = self._elastic_state().heartbeat(cid)
            elif self._elastic is not None:
                with self._elastic.cv:
                    st, gen, count = (ST_OK, self._elastic.generation,
                                      self._elastic.active_count())
            else:
                st, gen, count = ST_OK, 0, 0
            _send_msg(conn, OP_HB, key, struct.pack("<BQI", st, gen, count))
            if part_blob is not None:
                # training-fleet telemetry part piggybacked on the
                # heartbeat (obs/fleetstats.py): windowed step-phase
                # summaries + the rank's drained spans — ingested AFTER
                # last_hb was refreshed and the beat acked, so detector
                # judging and on_straggler policy hooks can never turn a
                # received heartbeat into a missed one (hooks must still
                # return promptly — the SLOMonitor callback contract)
                self.fleet.add_part(cid, part_blob)
        elif opcode == OP_JOIN:
            cid, rank = struct.unpack_from("<QQ", payload, 0)
            state, gen, epoch, part, nparts, count = \
                self._elastic_state().join(cid, rank)
            st = {"active": ST_OK, "quarantined": ST_QUARANTINED}.get(
                state, ST_STALE)
            _send_msg(conn, OP_JOIN, key,
                      struct.pack("<BQQIII", st, gen, epoch, part, nparts,
                                  count))
        elif opcode == OP_REDUCE:
            if self._elastic is None or len(payload) < 24:
                _send_msg(conn, OP_REDUCE, key,
                          struct.pack("<BQI", ST_ERROR, 0, 0))
                return True
            cid, round_id, wait = struct.unpack_from("<QQd", payload, 0)
            arr = _unpack_array(payload[24:])
            st, gen, n, result = self._elastic.reduce(
                cid, key, round_id, arr,
                timeout=max(1.0, min(float(wait), 3600.0)))
            head = struct.pack("<BQI", st, gen, n)
            _send_msg(conn, OP_REDUCE, key,
                      head + (_pack_array(result) if st == ST_OK else b""))
        elif opcode == OP_EPOCH:
            if self._elastic is None or len(payload) < 24:
                _send_msg(conn, OP_EPOCH, key,
                          struct.pack("<BQQIII", ST_ERROR, 0, 0, 0, 1, 0))
                return True
            cid, epoch, wait = struct.unpack_from("<QQd", payload, 0)
            st, gen, nxt, part, nparts, count = self._elastic.epoch_end(
                cid, epoch, timeout=max(1.0, min(float(wait), 3600.0)))
            _send_msg(conn, OP_EPOCH, key,
                      struct.pack("<BQQIII", st, gen, nxt, part, nparts,
                                  count))
        elif opcode == OP_LEAVE:
            if self._elastic is not None and len(payload) >= 8:
                (cid,) = struct.unpack_from("<Q", payload, 0)
                self._elastic.leave(cid)
            _send_msg(conn, OP_LEAVE, key, b"\x00")
        elif opcode == OP_CLOCK:
            # async committed-clock push: "rank r finished step t";
            # max-merge + kind-4 WAL record via _advance_clock. Reply
            # carries the fleet clock bounds so every step's commit
            # doubles as the worker's view refresh (floor for the gate,
            # max for lr compensation) — no extra RPC per step.
            if len(payload) < 24:
                _send_msg(conn, OP_CLOCK, key,
                          struct.pack("<BQQI", ST_ERROR, 0, 0, 0))
                return True
            cid, rank, step = struct.unpack_from("<QQQ", payload, 0)
            self._advance_clock(cid, int(rank), int(step))
            with self._clock_cv:
                floor, maxc, widen = self._clock_bounds_locked()
            _send_msg(conn, OP_CLOCK, key,
                      struct.pack("<BQQI", ST_OK, floor, maxc, widen))
        elif opcode == OP_CLOCK_PULL:
            # read-only committed-clock table dump — tests assert
            # exactly-once clock recovery with it; retries harmless
            with self._clock_cv:
                floor = self._clock_floor_locked()
                table = sorted(self._clock.items())
            _send_msg(conn, OP_CLOCK_PULL, key,
                      struct.pack("<BQI", ST_OK, floor, len(table))
                      + b"".join(struct.pack("<QQ", r, c)
                                 for r, c in table))
        elif opcode == OP_PULL_STALE:
            # staleness-gated pull (stale-synchronous-parallel): the
            # puller declares its own committed clock and blocks while it
            # would run more than s_eff steps ahead of the fleet's
            # committed-clock floor (s_eff = requested bound + policy
            # widening). The wait bound rides IN the request (the
            # OP_REDUCE discipline) so the server answers ST_ERROR before
            # the client socket timeout instead of dropping the
            # connection.
            if len(payload) < 40 or key not in self._weights:
                _send_msg(conn, OP_PULL_STALE, key,
                          struct.pack("<BQQ", ST_ERROR, 0, 0))
                return True
            cid, rank, step, stale, wait = struct.unpack_from(
                "<QQQQd", payload, 0)
            deadline = time.monotonic() + max(0.0, min(float(wait), 3600.0))
            st, blocked = ST_OK, False
            with self._clock_cv:
                while True:
                    floor, maxc, widen = self._clock_bounds_locked()
                    if step <= floor + stale + widen:
                        break
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        st = ST_ERROR
                        obs.inc("kvstore.async.gate_timeouts")
                        break
                    blocked = True
                    self._clock_cv.wait(timeout=remaining)
            if blocked:
                obs.inc("kvstore.async.gate_blocks")
            if st != ST_OK:
                _send_msg(conn, OP_PULL_STALE, key,
                          struct.pack("<BQQ", st, floor, maxc))
                return True
            with self._locks.get(key, self._global_lock):
                arr = self._weights[key]
            _send_msg(conn, OP_PULL_STALE, key,
                      [struct.pack("<BQQ", ST_OK, floor, maxc),
                       _pack_array(arr)])
        elif opcode == OP_REDUCE_SCOPED:
            # scoped reduce: completes at an explicit contributor count
            # instead of the full live membership — the group-local and
            # cross-group stages of hierarchical reduction ride this
            if self._elastic is None or len(payload) < 28:
                _send_msg(conn, OP_REDUCE_SCOPED, key,
                          struct.pack("<BQI", ST_ERROR, 0, 0))
                return True
            cid, round_id, wait, expected = struct.unpack_from(
                "<QQdI", payload, 0)
            arr = _unpack_array(payload[28:])
            st, gen, n, result = self._elastic.reduce(
                cid, key, round_id, arr,
                timeout=max(1.0, min(float(wait), 3600.0)),
                expected=int(expected))
            head = struct.pack("<BQI", st, gen, n)
            _send_msg(conn, OP_REDUCE_SCOPED, key,
                      head + (_pack_array(result) if st == ST_OK else b""))
        elif opcode == OP_TELEMETRY:
            # training-fleet telemetry pull: this server's own part (its
            # kvstore.server.rpc lanes + STATS) plus every cached worker
            # part. Draining is destructive and the client retries lost
            # replies, so a collection token re-serves the cached reply
            # instead of draining (and losing) a second batch — the
            # serve-plane OP_TELEMETRY idiom.
            try:
                spec = json.loads(bytes(payload).decode("utf-8")) \
                    if len(payload) else {}
                token = spec.get("token")
                drain = bool(spec.get("drain", True))
                if token is None:
                    blob = json.dumps(self.telemetry(drain=drain),
                                      default=float).encode("utf-8")
                else:
                    # lookup AND drain under ONE lock hold: a retried
                    # token racing the original's in-flight drain would
                    # otherwise miss the cache and drain a second batch —
                    # the first batch then sits under the token, never
                    # re-requested (exactly the loss the token prevents).
                    # The drain is CPU-only (ring + dicts), so holding
                    # the lock serializes rare operator pulls, not RPCs.
                    with self._telemetry_lock:
                        blob = self._telemetry_tokens.get(token)
                        if blob is None:
                            blob = json.dumps(
                                self.telemetry(drain=drain),
                                default=float).encode("utf-8")
                            self._telemetry_tokens[token] = blob
                            while len(self._telemetry_tokens) > 16:
                                self._telemetry_tokens.popitem(last=False)
                _send_msg(conn, OP_TELEMETRY, key, b"\x00" + blob)
            except Exception as e:  # noqa: BLE001 — wire-reported
                obs.inc("kvstore.telemetry_errors")
                _send_msg(conn, OP_TELEMETRY, key,
                          b"\x01" + f"{type(e).__name__}: {e}".encode(
                              "utf-8", "replace"))
        elif opcode == OP_STATS:
            # read-only stats snapshot (membership liveness, straggler
            # verdicts, hot keys, metrics under "metrics" — the serve
            # plane's STATS schema); {"metrics": false} skips the
            # registry snapshot for cheap polls
            try:
                include = True
                if len(payload):
                    try:
                        spec = json.loads(bytes(payload).decode("utf-8"))
                        include = bool(spec.get("metrics", True))
                    except ValueError:
                        pass
                blob = json.dumps(self.stats(include_metrics=include),
                                  default=str).encode("utf-8")
                _send_msg(conn, OP_STATS, key, b"\x00" + blob)
            except Exception as e:  # noqa: BLE001 — wire-reported
                obs.inc("kvstore.stats_errors")
                _send_msg(conn, OP_STATS, key,
                          b"\x01" + f"{type(e).__name__}: {e}".encode(
                              "utf-8", "replace"))
        elif opcode == OP_SHUTDOWN:
            if self._snap_mgr is not None:
                try:
                    self.snapshot_now()  # parting durable state
                except Exception:  # noqa: BLE001
                    pass
            _send_msg(conn, OP_SHUTDOWN, key, b"\x00")
            self.stop()
            return False
        return True

    # ------------------------------------------------------------------
    # stats + telemetry surfaces (the serve-plane schema on the PS plane)
    # ------------------------------------------------------------------
    def stats(self, include_metrics: bool = True) -> dict:
        """Structured server state: key count, membership liveness, the
        training-fleet section (per-rank windows + straggler verdicts),
        the bounded hot-key table, and — ``include_metrics`` — the full
        registry snapshot under ``"metrics"`` (ONE schema for every
        numeric runtime signal, the serve-plane STATS discipline)."""
        out = {"pid": os.getpid(),
               "uptime_seconds": round(time.monotonic() - self._started, 3),
               "keys": len(self._weights),
               "num_workers": self._num_workers}
        el = self._elastic
        if el is not None:
            with el.cv:
                out["generation"] = el.generation
                out["epoch"] = el.epoch
                out["active_workers"] = el.active_count()
            out["membership"] = [
                {"rank": rank, "client_id": str(cid), "state": state,
                 "last_hb_age_s": age}
                for rank, cid, state, age in el.liveness_table()]
        out["fleet"] = self.fleet.stats()
        out["hot_keys"] = self._hot_keys.snapshot()
        with self._clock_cv:
            if self._clock or self._async_staleness is not None:
                floor, maxc, widen = self._clock_bounds_locked()
                out["async"] = {
                    "staleness": self._async_staleness,
                    "clock_floor": floor, "clock_max": maxc,
                    "widen": widen,
                    "clocks": {str(r): c
                               for r, c in sorted(self._clock.items())},
                    "staleness_widen": {
                        str(r): w for r, w
                        in sorted(self._staleness_widen.items())}}
        if include_metrics:
            out["metrics"] = obs.metrics.snapshot()
        return out

    def telemetry(self, drain: bool = True) -> dict:
        """``{"parts": [...]}`` — the OP_TELEMETRY document: this
        process's part first (role ``ps_server``, STATS attached so one
        pull carries the straggler verdicts and hot keys), then every
        cached worker part (role ``rank<r>``) with its windows, spans,
        and clock anchor — the rank lanes of the merged timeline."""
        st = self.stats(include_metrics=False)
        part = obs.telemetry_part(drain=drain, role="ps_server")
        part["stats"] = st
        return {"parts": [part] + self.fleet.parts(drain=drain)}

    def _record_seq(self, cid, key, seq):
        """Caller holds ``self._seq_lock``. LRU-bounded (client churn)."""
        self._applied_seq[(cid, key)] = seq
        self._applied_seq.move_to_end((cid, key))
        self._seq_by_key.setdefault(key, {})[cid] = seq
        while len(self._applied_seq) > 65536:
            (ocid, okey), _oseq = self._applied_seq.popitem(last=False)
            per_key = self._seq_by_key.get(okey)
            if per_key is not None:
                per_key.pop(ocid, None)
                if not per_key:
                    del self._seq_by_key[okey]

    def _apply_sparse(self, key, payload, locked=False) -> bool:
        """Validate + apply a row-sparse push. Returns False (never corrupts)
        on bad key / shape mismatch / out-of-range or negative index."""
        if key not in self._weights:
            return False
        idx, rows = _unpack_sparse(payload)
        idx = idx.astype(np.int64)
        w = self._weights[key]
        if not (idx.ndim == 1 and rows.shape[:1] == idx.shape
                and rows.shape[1:] == w.shape[1:] and idx.size > 0
                and 0 <= idx.min() and idx.max() < w.shape[0]):
            return False
        lock = self._locks[key] if not locked else contextlib.nullcontext()
        with lock:
            if self._updater is not None:
                grad = np.zeros_like(w)
                np.add.at(grad, idx, rows.astype(w.dtype))
                self._apply(key, grad, w)
            else:
                np.add.at(w, idx, rows.astype(w.dtype))
        return True

    def _release_barrier_locked(self) -> bool:
        """Caller holds ``_barrier_cv``. Releases the round when the quorum
        — the LIVE membership under elasticity, the static worker count
        otherwise — has arrived. Called on arrival AND on membership
        change, so a declared death releases a survivor-complete round.

        Membership-scoped release compares the live cid SET against the
        arrived token cids (the reduce/epoch discipline), not a raw count:
        a member that arrived and then died must not stand in for a live
        member that never reached the barrier."""
        el = self._elastic
        required_cids = None
        if el is not None:
            with el.cv:
                if el.has_members():
                    required_cids = {m.cid for m in el.active_members()}
        if required_cids is None:
            if self._barrier_count < self._num_workers:
                return False
        else:
            arrived = {tok[0] for tok, g in self._barrier_arrived.items()
                       if g == self._barrier_gen}
            # (tokenless legacy arrivals carry no identity and only count
            # in the static-quorum mode above)
            if not arrived or not required_cids.issubset(arrived):
                return False
        if obs.enabled() and el is not None and self._barrier_stamps:
            # barrier wait-by-rank (reduce-plane attribution): how long
            # each arrived rank stood at this rendezvous — the rank with
            # ~zero wait is the one everyone else waited on
            now = time.monotonic()
            with el.cv:
                rank_of = {m.cid: m.rank for m in el.members.values()}
            for tok, t0 in self._barrier_stamps.items():
                r = rank_of.get(tok[0])
                if r is not None:
                    obs.observe(f"kvstore.barrier_wait.rank{r}_seconds",
                                now - t0)
        self._barrier_count = 0
        self._barrier_gen += 1
        for tok in self._barrier_arrived:
            self._barrier_released[tok] = True
        self._barrier_arrived.clear()
        self._barrier_stamps.clear()
        while len(self._barrier_released) > 65536:
            self._barrier_released.popitem(last=False)
        self._barrier_cv.notify_all()
        return True

    def _barrier_timeout_detail(self) -> bytes:
        """Structured straggler report: exactly which ranks are missing and
        how stale their heartbeats are (unknowable without the membership
        plane — then only the arrived/expected counts are reported). Rides
        after the \\x01 status byte; also emitted as a
        ``kvstore.barrier_timeout`` obs event."""
        detail = {"expected": self._required_workers(),
                  "arrived": self._barrier_count}
        if self._elastic is not None:
            arrived_cids = {tok[0] for tok, g in self._barrier_arrived.items()
                            if g == self._barrier_gen}
            missing = [
                {"rank": rank, "client_id": cid, "state": state,
                 "last_heartbeat_age_s": age}
                for rank, cid, state, age in self._elastic.liveness_table()
                if state == "active" and cid not in arrived_cids]
            detail["missing"] = sorted(missing, key=lambda m: m["rank"])
        obs.event("kvstore.barrier_timeout", **{
            k: v for k, v in detail.items() if k != "missing"},
            missing_ranks=[m["rank"] for m in detail.get("missing", [])])
        obs.inc("kvstore.barrier_timeouts")
        try:
            return json.dumps(detail).encode()
        except (TypeError, ValueError):
            return b"{}"

    def _barrier(self, payload):
        """Membership-scoped rendezvous; a straggler timeout rolls its
        arrival back instead of poisoning the next round, and reports a
        structured straggler detail (returns ``(ok, detail_bytes)``).

        Idempotent when the client sends a (client_id, barrier_epoch) token
        (16-byte payload): a retransmit while the round gathers is counted
        once (arrival keyed by token), and a retransmit that lands after the
        round released — the lost-reply case — is acked immediately from the
        released LRU instead of entering the next round. Tokenless legacy
        frames fall back to plain arrival counting.

        With the elastic membership plane active the quorum is the LIVE
        member count: a worker SIGKILL'd mid-epoch is declared dead after K
        missed heartbeats and the round releases over the survivors —
        the barrier is scoped to the membership generation, not a static
        worker count.
        """
        token = (struct.unpack_from("<QQ", payload, 0)
                 if len(payload) >= 16 else None)
        # membership-scoped quorum needs membership-checked ARRIVALS too: a
        # zombie (declared dead, still running) counting toward the live
        # quorum would release a round a live member never reached —
        # reject it structurally, like OP_REDUCE's ST_STALE. Tokenless
        # legacy frames carry no identity and keep counting (no members →
        # static quorum → unchanged behavior).
        if token is not None and self._elastic is not None:
            with self._elastic.cv:
                if self._elastic.has_members():
                    m = self._elastic.members.get(token[0])
                    if m is None or m.state != "active":
                        obs.inc("elastic.stale_rejected")
                        return False, json.dumps(
                            {"stale_member": True,
                             "client_id": token[0]}).encode()
        ok, detail = True, b""
        with self._barrier_cv:
            counted = True
            if token is not None:
                if token in self._barrier_released:
                    return True, b""  # round completed; just re-ack
                if token in self._barrier_arrived:
                    # retransmit while the round is still gathering: wait for
                    # the release the original arrival is counted toward
                    gen = self._barrier_arrived[token]
                    counted = False
                else:
                    gen = self._barrier_gen
                    self._barrier_arrived[token] = gen
                    self._barrier_stamps[token] = time.monotonic()
                    self._barrier_count += 1
            else:
                gen = self._barrier_gen
                self._barrier_count += 1
            if not (counted and self._release_barrier_locked()):
                deadline = time.monotonic() + self._barrier_timeout
                while self._barrier_gen == gen:
                    # re-check on every wake: a membership change may have
                    # shrunk the quorum to the already-arrived set
                    if self._release_barrier_locked():
                        break
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        detail = self._barrier_timeout_detail()
                        # roll back only an arrival THIS handler counted; a
                        # timed-out retransmit must not erase the original's
                        if counted:
                            self._barrier_count = max(
                                0, self._barrier_count - 1)
                            if token is not None:
                                self._barrier_arrived.pop(token, None)
                                self._barrier_stamps.pop(token, None)
                        ok = False
                        break
                    self._barrier_cv.wait(timeout=remaining)
        return ok, detail

    def _set_optimizer_bytes(self, blob: bytes, warm: bool = True):
        """SET_OPT payload is text: ``name key=val key=val …`` — a format the
        C++ server (native/ps/ps_server.cc) parses too. Legacy pickle blobs
        still accepted. ``warm=False`` skips the background XLA pre-warm
        (the warm-restart path re-installs the optimizer before serving)."""
        from ..optimizer import Updater, create as opt_create

        try:
            text = blob.decode("ascii")
            parts = text.split()
            name, kwargs = parts[0], {}
            for kv in parts[1:]:
                k, _, v = kv.partition("=")
                kwargs[k] = float(v)
            self._opt_spec = text
        except (UnicodeDecodeError, ValueError, IndexError):
            # legacy SET_OPT blobs: a tiny {name, kwargs} dict set once at
            # init — never an array payload, never per-request
            spec = pickle.loads(blob)  # lint: disable=pickle-on-wire
            name, kwargs = spec["name"], spec["kwargs"]
            # normalize to the text form so a durable snapshot can always
            # re-install it (capture_server_state persists _opt_spec)
            self._opt_spec = name + " " + " ".join(
                f"{k}={v}" for k, v in kwargs.items())
        opt = opt_create(name, **kwargs)
        self._optimizer = opt
        self._updater = Updater(opt)
        if not warm:
            return
        # Pre-warm the XLA executables for every known weight shape with a
        # THROWAWAY updater, in the background (warming inside this RPC
        # handler would stall SET_OPT past the client timeout): the first
        # real push must not eat multi-second compiles inside a client's
        # RPC window (the cause of the retry-double-apply flake this fixes
        # together with OP_PUSH_SEQ).

        with self._global_lock:  # OP_INIT mutates _weights concurrently
            snapshot = [(k, w.copy()) for k, w in self._weights.items()]

        def _warm(shapes=snapshot):
            try:
                from ..ndarray import array

                warm = Updater(opt_create(name, **kwargs))
                for k, w in shapes:
                    warm(k, array(np.zeros_like(w)), array(w))
            except Exception:
                pass  # warmup is best-effort

        # tracked (not fire-and-forget): stop() joins it with a bounded
        # timeout so a mid-compile warmup can't outlive the server silently
        self._warm_thread = threading.Thread(target=_warm, daemon=True,
                                             name="mxtpu-ps-warm")
        self._warm_thread.start()

    def _apply(self, key, grad, weight_np):
        """Run the fused optimizer update on host numpy via the framework ops
        (the server machine may have no TPU; jax-cpu executes)."""
        from ..ndarray import array

        w = array(weight_np)
        g = array(grad)
        self._updater(key, g, w)
        # intentional sync: PS weights are host-resident numpy by design
        # (the server's optimizer IS host compute, not a wire stall)
        self._weights[key] = w.asnumpy()  # lint: disable=host-sync-on-hot-path


def main():
    import argparse

    # The PS is host-side by design (reference ps-lite servers are CPU
    # processes): pin jax to cpu BEFORE any NDArray is created, or the
    # optimizer's first _apply would initialize the accelerator backend —
    # taking a chip the workers need (a chip belongs to one process), or
    # blocking on one they already hold. MXNET_PS_PLATFORM overrides.
    import jax

    jax.config.update("jax_platforms",
                      os.environ.get("MXNET_PS_PLATFORM", "cpu"))

    ap = argparse.ArgumentParser(description="mxnet_tpu async parameter server")
    ap.add_argument("--port", type=int, default=9091)
    ap.add_argument("--num-workers", type=int, default=1)
    ap.add_argument("--snapshot-dir", default=None,
                    help="durable-state directory (atomic+CRC snapshots; "
                    "warm restart picks up the newest valid one). Falls "
                    "back to MXNET_PS_SNAPSHOT_DIR")
    ap.add_argument("--snapshot-period", type=float, default=None,
                    help="seconds between snapshots "
                    "(MXNET_PS_SNAPSHOT_PERIOD_S, default 5)")
    args = ap.parse_args()
    srv = PSServer(port=args.port, num_workers=args.num_workers,
                   snapshot_dir=args.snapshot_dir,
                   snapshot_period=args.snapshot_period)
    print(f"PSServer listening on :{srv.port}", flush=True)
    srv.serve_forever()


if __name__ == "__main__":
    main()

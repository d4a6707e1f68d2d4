"""Threaded socket front end for ``mxnet_tpu.serve``.

Reference: MXNet Model Server's HTTP front end over CachedOp workers (TBV,
SURVEY.md §1). This build reuses the parameter-server wire format
(``kvstore/ps_server.py``: length-prefixed binary framing, the same
``_pack_array`` array encoding) on a disjoint opcode range, so one set of
framing/chaos/telemetry tooling covers both the training and serving
planes.

Wire protocol (little-endian, see ``kvstore/ps_server.py`` for framing):

  INFER  request : f64 deadline_ms (0 = none) | u8 priority | packed arrays
  INFER  reply   : u8 status | (ok: u32 param_version | packed arrays)
                               (err: utf-8 message)
  HEALTH reply   : u8 0 — process liveness only
  READY  reply   : u8 status — 0 ready / DRAINING / NOT_READY
  RELOAD request : utf-8 json {"path": ..., "epoch": ..., "prefix": ...}
  RELOAD reply   : u8 status | (ok: u32 new_version; err: message)
  STATS  reply   : u8 0 | utf-8 json (engine + batcher + server stats)
  DRAIN  request : u8 stop_after (0/1)
  DRAIN  reply   : u8 0 once queued + in-flight work finished
  TELEMETRY request : utf-8 json {"drain": bool (default true),
                   "format": "json"|"prometheus",
                   "openmetrics": bool (default true; false = strict
                   text format 0.0.4, no exemplars/EOF — for textfile
                   collectors)} (empty = defaults).
  TELEMETRY reply: u8 status | utf-8 blob — json: {"parts": [telemetry
                   part, ...]} (obs.telemetry_part schema: pid, role,
                   wall_epoch clock anchor, drained span ring, metrics
                   snapshot; a FleetServer returns one part per live
                   replica plus its own). prometheus: text exposition
                   (obs/export.py), pid/role-labeled — the HTTP-free
                   scrape endpoint.

Distributed tracing (docs/OBSERVABILITY.md): every request frame's key
field may carry a ``\\x1f``-suffixed W3C traceparent (obs/context.py).
``_handle_loop`` strips it FIRST — old-format frames have no suffix and
parse unchanged; a bare INFER gets a fresh sampled-or-not root, so the
replica's spans are one timeline either way. Replies never carry context.
  PREPARE_RELOAD : utf-8 json {"path", "epoch", "prefix", "version",
                   "token": [cid, epoch]} — phase one of the fleet-atomic
                   reload (serve/fleet.py): load + validate + stage, do NOT
                   flip. reply u8 status | (ok: u32 staged_version)
  COMMIT_RELOAD  : u64 cid | u64 epoch (the prepare's token). Flips the
                   staged set — a pure pointer swap, infallible short of
                   process death. Exactly-once: a retried COMMIT whose ack
                   was lost re-acks from the token LRU without re-flipping
                   (the kvstore (client_id, seq) dedup idiom). reply
                   u8 status | (ok: u32 version)
  ABORT_RELOAD   : u64 cid | u64 epoch — discard the staged set (idempotent)

Graceful degradation contract (tested in tests/test_serve.py):

- a deadline-expired or shed request gets an explicit status, never a
  hang;
- ``drain()`` flips readiness, finishes in-flight work, then (optionally)
  stops the listener — a rolling restart loses zero accepted requests;
- hot reload swaps parameters atomically (engine contract): every reply
  carries the parameter version it was computed with;
- chaos (``MXNET_CHAOS_RPC`` on the client, ``MXNET_CHAOS_KILL`` at the
  ``serve:pre_reply`` / ``serve:post_recv`` kill points here) exercises
  the retry/failover paths deterministically.
"""
from __future__ import annotations

import json
import os
import socket
import struct
import threading
import time
from typing import Optional

import numpy as np

from .. import obs, tsan
from ..obs import context as obs_context
from ..chaos import rpc as _chaos_rpc
from ..chaos.proc import kill_point
from ..kvstore.ps_server import (_pack_arrays, _recv_msg, _send_msg,
                                 _unpack_arrays)
from .batcher import DynamicBatcher
from .engine import (DeadlineExceeded, Draining, InferenceEngine,
                     RequestRejected, ServeError)

__all__ = ["ServeServer", "OP_INFER", "OP_HEALTH", "OP_READY", "OP_RELOAD",
           "OP_STATS", "OP_DRAIN", "OP_SHUTDOWN", "OP_PREPARE_RELOAD",
           "OP_COMMIT_RELOAD", "OP_ABORT_RELOAD", "OP_TELEMETRY", "OP_DUMP",
           "OP_INFER_STREAM", "OP_STREAM_TOKEN", "OP_STREAM_END",
           "OP_STREAM_ERROR", "SERVE_OP_NAMES", "STATUS_OK",
           "STATUS_REJECTED", "STATUS_DEADLINE", "STATUS_BAD_REQUEST",
           "STATUS_DRAINING", "STATUS_INTERNAL", "STATUS_NOT_READY"]

# serve opcode range: disjoint from the kvstore PS opcodes by
# construction — both planes declare their rows in mxnet_tpu/wire.py and
# the registry raises on any collision at import; the protocol linter
# cross-checks this module's dispatch against the same table
from ..wire import SERVE_WIRE

(OP_INFER, OP_HEALTH, OP_READY, OP_RELOAD, OP_STATS, OP_DRAIN,
 OP_SHUTDOWN, OP_PREPARE_RELOAD, OP_COMMIT_RELOAD,
 OP_ABORT_RELOAD, OP_TELEMETRY, OP_DUMP, OP_INFER_STREAM,
 OP_STREAM_TOKEN, OP_STREAM_END, OP_STREAM_ERROR) = SERVE_WIRE.codes(
    "infer", "health", "ready", "reload", "stats", "drain",
    "serve_shutdown", "prepare_reload", "commit_reload", "abort_reload",
    "telemetry", "dump", "infer_stream", "stream_token", "stream_end",
    "stream_error")

SERVE_OP_NAMES = dict(SERVE_WIRE.names())

# single source of truth for chaos rule names: MXNET_CHAOS_RPC rules match
# these ops the moment the serving plane is imported (the client imports
# this module, so on_send always sees registered names)
_chaos_rpc.OP_NAMES.update(SERVE_OP_NAMES)

(STATUS_OK, STATUS_REJECTED, STATUS_DEADLINE, STATUS_BAD_REQUEST,
 STATUS_DRAINING, STATUS_INTERNAL, STATUS_NOT_READY) = range(7)

_INFER_HDR = struct.Struct("<dB")  # deadline_ms (0 = none), priority
# INFER_STREAM request: deadline_ms (0 = none), priority,
# max_new_tokens (0 = server default), temperature — then packed arrays
# (one 1-D int32 prompt). Reply is a chunk sequence on the same
# connection: STREAM_TOKEN (u32 token | u32 index) per token, closed by
# STREAM_END (u8 status | u32 n_tokens) or STREAM_ERROR (_err_payload).
_STREAM_HDR = struct.Struct("<dBIf")
_TOKEN_FRAME = struct.Struct("<II")


def _err_payload(status: int, msg: str) -> bytes:
    return struct.pack("<B", status) + msg.encode("utf-8", "replace")


class ServeServer:
    """A concurrent inference endpoint over an :class:`InferenceEngine`.

    One accept loop + one thread per connection (the PSServer pattern);
    every connection handler funnels INFERs into the shared
    :class:`DynamicBatcher`, so concurrency turns into batch occupancy
    instead of lock contention on the device.
    """

    def __init__(self, engine: Optional[InferenceEngine] = None,
                 host: str = "127.0.0.1", port: int = 0, *,
                 batcher: Optional[DynamicBatcher] = None,
                 decode=None,
                 max_linger_ms: float = 2.0, max_queue: int = 256,
                 lanes: int = 2, default_timeout: float = 30.0):
        self._engine = engine
        if batcher is not None:
            self._batcher = batcher
        elif engine is not None:
            self._batcher = DynamicBatcher(
                engine, max_linger_ms=max_linger_ms, max_queue=max_queue,
                lanes=lanes)
        else:
            self._batcher = None
        # streaming generation source (OP_INFER_STREAM): a
        # decode.DecodeScheduler, or — on a FleetServer — absent, in which
        # case the Router batcher's own generate() relays replica streams
        self._decode = decode
        self._default_timeout = float(default_timeout)
        self._draining = False
        self._started = time.monotonic()
        self._shed_draining = 0  # server-level sheds (pre-batcher)
        # two-phase reload bookkeeping: staged token + committed-token LRU
        # (the kvstore exactly-once idiom — a retried COMMIT re-acks, never
        # re-flips); one lock serializes prepare/commit/abort
        self._reload_lock = tsan.lock("serve.server.reload")
        self._staged_token = None
        from collections import OrderedDict
        self._committed_tokens: "OrderedDict" = OrderedDict()
        # exactly-once telemetry drains: draining the span ring is
        # destructive, and the client's RPC layer retries lost replies —
        # a retried collection token re-serves the cached reply instead
        # of draining again (the kvstore (client_id, seq) idiom; without
        # this, every retry would silently lose the first drain's spans)
        self._telemetry_tokens: "OrderedDict" = OrderedDict()
        self._telemetry_lock = tsan.lock("serve.server.telemetry")
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(128)
        self.port = self._sock.getsockname()[1]
        self._stop = threading.Event()
        self._threads = []
        self._conns = []

    # ------------------------------------------------------------------
    # lifecycle (PSServer idiom)
    # ------------------------------------------------------------------
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
            t = threading.Thread(target=self._handle, args=(conn,),
                                 daemon=True)
            t.start()
            self._threads = [th for th in self._threads if th.is_alive()]
            self._threads.append(t)

    def start(self):
        t = threading.Thread(target=self.serve_forever, daemon=True,
                             name="mxnet-tpu-serve-accept")
        t.start()
        return t

    def stop(self):
        self._stop.set()
        self._close_listener()
        # snapshot: handler threads concurrently .remove() from _conns, and
        # iterating the live list would skip (and leave open) neighbors of
        # a removed entry — a stopped server must look dead to EVERY client
        for c in list(self._conns):
            try:
                c.close()
            except OSError:
                pass
        # reap handler threads (they exit once their sockets are severed);
        # OP_SHUTDOWN stops from inside a handler — never join yourself
        me = threading.current_thread()
        deadline = time.monotonic() + 1.0  # ONE budget for the whole reap
        leaked = 0
        for t in [t for t in self._threads if t is not me]:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
            if t.is_alive():
                leaked += 1
        if leaked:
            obs.inc("serve.handler_threads_leaked", leaked)
            obs.event("serve.handler_threads_leaked", count=leaked)
        if self._batcher is not None:
            self._batcher.close(timeout=5)
        if self._decode is not None:
            self._decode.close(timeout=5)

    def abort(self):
        """Crash-style stop: sever the listener and every live connection
        WITHOUT draining queued or in-flight work — to a client this is
        indistinguishable from the process being SIGKILLed, which is
        exactly what the fleet tests need from an in-process replica
        (serve/fleet.py LocalReplica.kill)."""
        self._stop.set()
        self._close_listener()
        for c in list(self._conns):
            try:
                c.close()
            except OSError:
                pass

    def _close_listener(self):
        # shutdown() before close(): close alone does NOT wake the accept
        # loop blocked inside its 0.5s poll, and while that thread holds
        # the fd the kernel keeps the listener ALIVE — new connects land
        # in a zombie backlog and only see RST when the poll tick fires,
        # so "this port is dead" took up to half a second to become true
        # (the fleet router's dead-replica attempts randomly lost their
        # 250ms hedge window to it). shutdown resets the backlog and
        # raises the blocked accept immediately.
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass

    def drain(self, stop: bool = False, timeout: float = 30.0) -> bool:
        """Graceful shutdown, phase one: flip readiness off, let queued and
        in-flight requests finish, refuse new ones. ``stop=True`` closes
        the listener afterwards (phase two)."""
        self._draining = True
        obs.event("serve.drain", stop=stop)
        ok = True
        if self._batcher is not None:
            ok = self._batcher.drain(timeout=timeout)
        if self._decode is not None:
            ok = self._decode.drain(timeout=timeout) and ok
        if stop:
            self.stop()
        return ok

    def reload(self, path: str, epoch: Optional[int] = None,
               prefix: str = "ckpt") -> int:
        """Hot-swap parameters from a newer on-disk artifact (same graph).
        In-flight requests keep the generation they started with.
        Serialized against the two-phase prepare/commit path so a legacy
        RELOAD can't interleave with a fleet flip."""
        if self._engine is None:
            raise ServeError("no engine loaded")
        from . import load_params

        arg, aux = load_params(path, epoch=epoch, prefix=prefix)
        with self._reload_lock:
            return self._engine.reload(arg, aux)

    def prepare_reload(self, path: str, epoch: Optional[int] = None,
                       prefix: str = "ckpt", *,
                       version: Optional[int] = None, token=None) -> int:
        """Phase one of the fleet-atomic reload: load, validate, and stage
        the new generation without flipping (all fallible work happens
        here; the commit left is a pure pointer swap)."""
        if self._engine is None:
            raise ServeError("no engine loaded")
        from . import load_params

        arg, aux = load_params(path, epoch=epoch, prefix=prefix)
        with self._reload_lock:
            staged = self._engine.prepare_reload(arg, aux, version=version)
            self._staged_token = tuple(token) if token is not None else None
        return staged

    def commit_reload(self, token=None) -> int:
        """Phase two: flip the staged generation. Exactly-once under
        retries — a token seen in the committed LRU re-acks with the
        version it flipped to, without flipping again."""
        if self._engine is None:
            raise ServeError("no engine loaded")
        tok = tuple(token) if token is not None else None
        with self._reload_lock:
            if tok is not None and tok in self._committed_tokens:
                return self._committed_tokens[tok]  # retried frame: re-ack
            if tok is not None and self._staged_token not in (None, tok):
                raise ServeError(
                    f"commit token {tok} does not match staged "
                    f"{self._staged_token}")
            version = self._engine.commit_reload()
            self._staged_token = None
            if tok is not None:
                self._committed_tokens[tok] = version
                while len(self._committed_tokens) > 4096:
                    self._committed_tokens.popitem(last=False)
        return version

    def abort_reload(self, token=None) -> None:
        """Discard a staged generation (idempotent rollback)."""
        if self._engine is None:
            return
        tok = tuple(token) if token is not None else None
        with self._reload_lock:
            if tok is None or self._staged_token in (None, tok):
                self._engine.abort_reload()
                self._staged_token = None

    def stats(self, include_metrics: bool = True) -> dict:
        out = {"uptime_seconds": round(time.monotonic() - self._started, 3),
               "draining": self._draining,
               "connections": len(self._conns),
               "sheds": {"draining": self._shed_draining},
               "pid": os.getpid()}
        if include_metrics:
            # ONE schema for every numeric runtime signal: the full
            # registry snapshot rides STATS, so load generators,
            # fleet_report and the SLO monitor read the same counters the
            # process records — no ad-hoc parallel bookkeeping. (The
            # telemetry path passes False: its part already carries the
            # snapshot, a second copy would just double the payload.)
            out["metrics"] = obs.metrics.snapshot()
        if self._engine is not None:
            out["engine"] = self._engine.stats()
        if self._batcher is not None:
            out["batcher"] = self._batcher.stats()
        if self._decode is not None:
            out["decode"] = self._decode.stats()
        return out

    def telemetry(self, drain: bool = True,
                  retained: Optional[list] = None) -> dict:
        """This process's telemetry contribution (``OP_TELEMETRY``): span
        ring (drained by default — repeated collections are increments),
        metrics snapshot, clock anchor. A FleetServer overrides this to
        pull and append every live replica's parts.

        ``retained`` is the tail-retention verdict list riding the
        request (obs/tail.py): pending traces named in it promote into
        the ring BEFORE the drain, so a downstream hop's held spans leave
        with the collection that carried their verdict; everything past
        the hold window expires in the same pass."""
        if retained:
            obs.tail.resolve(retained)
        # stats first: anything stats() mirrors into gauges must land in
        # the snapshot telemetry_part() takes
        st = self.stats(include_metrics=False)
        part = obs.telemetry_part(drain=drain, role="server")
        part["stats"] = st
        return {"parts": [part]}

    # ------------------------------------------------------------------
    # connection handling
    # ------------------------------------------------------------------
    def _handle(self, conn: socket.socket):
        try:
            self._handle_loop(conn)
        finally:
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
                kill_point("serve:post_recv")  # chaos: die with work read
                # strip wire trace context BEFORE anything looks at the
                # key (old-format frames: no separator, no context); a
                # context-less INFER becomes a new sampled-or-not root, so
                # replica spans trace either way ("absent = new root")
                key, wctx = obs_context.extract_key(key)
                rec = obs.enabled()
                root_here = False
                if wctx is None and rec and opcode in (OP_INFER,
                                                       OP_INFER_STREAM):
                    wctx = obs_context.new_root()
                    root_here = True
                t0 = time.monotonic() if rec else 0.0
                opname = SERVE_OP_NAMES.get(opcode, str(opcode))
                try:
                    with obs_context.use(wctx), \
                            obs.trace.span("serve.rpc", op=opname):
                        alive = self._handle_one(conn, opcode, key, payload)
                finally:
                    if rec:
                        obs.observe(f"serve.rpc.{opname}_seconds",
                                    time.monotonic() - t0)
                    # tail retention: a server-side root's verdict
                    # happens HERE — latency + the outcome _do_infer
                    # noted (shed/deadline/error rode the reply to
                    # the client; the same verdict decides whether
                    # the trace survives). When the CLIENT owns the
                    # root, the reply status byte carries the outcome —
                    # but hedge/breaker flags noted by the router on
                    # THIS thread never reach the client, so
                    # finish_remote applies the policy to the flags
                    # locally (retaining the fleet-side spans) and, like
                    # finish_root, always clears this thread's notes so
                    # they cannot leak into the next request on this
                    # connection — even ones taken while telemetry was
                    # off.
                    if root_here:
                        obs.tail.finish_root(wctx, time.monotonic() - t0)
                    else:
                        obs.tail.finish_remote(wctx,
                                               time.monotonic() - t0)
                if not alive:
                    return
        except (ConnectionError, OSError):
            return

    def _reply(self, conn, opcode: int, payload):
        kill_point("serve:pre_reply")  # chaos: server dies before the ack
        _send_msg(conn, opcode, "", payload)

    def _handle_one(self, conn, opcode: int, key: str, payload) -> bool:
        if opcode == OP_INFER:
            self._reply(conn, OP_INFER, self._do_infer(payload))
        elif opcode == OP_INFER_STREAM:
            return self._do_infer_stream(conn, payload)
        elif opcode == OP_HEALTH:
            # liveness only: answering at all is the signal
            self._reply(conn, OP_HEALTH, struct.pack("<B", STATUS_OK))
        elif opcode == OP_READY:
            # the fleet front (serve/fleet.py FleetServer) has no engine:
            # the Router IS the batcher, and its ready() gates on live
            # replicas instead of a loaded model
            # a decode-only replica (no batch engine) is ready while its
            # scheduler accepts work
            src = self._batcher if self._batcher is not None \
                else self._decode
            if src is None or (self._engine is None
                               and not hasattr(src, "ready")):
                status = STATUS_NOT_READY
            elif self._draining:
                status = STATUS_DRAINING
            elif self._engine is None and not src.ready():
                status = STATUS_NOT_READY
            else:
                status = STATUS_OK
            # the serving param version rides along (u32 appended — old
            # clients read byte 0 only), so a fleet router can gate a
            # replica on version coherence from one probe
            if self._engine is not None:
                version = self._engine.version
            else:
                version = int(getattr(src, "version", 0) or 0)
            self._reply(conn, OP_READY,
                        struct.pack("<BI", status, version))
        elif opcode == OP_RELOAD:
            try:
                spec = json.loads(bytes(payload).decode("utf-8"))
                version = self.reload(spec["path"],
                                      epoch=spec.get("epoch"),
                                      prefix=spec.get("prefix", "ckpt"))
                self._reply(conn, OP_RELOAD,
                            struct.pack("<BI", STATUS_OK, version))
            except Exception as e:  # noqa: BLE001 — wire-reported
                obs.inc("serve.reload_errors")
                self._reply(conn, OP_RELOAD, _err_payload(
                    STATUS_INTERNAL, f"{type(e).__name__}: {e}"))
        elif opcode == OP_PREPARE_RELOAD:
            try:
                spec = json.loads(bytes(payload).decode("utf-8"))
                staged = self.prepare_reload(
                    spec["path"], epoch=spec.get("epoch"),
                    prefix=spec.get("prefix", "ckpt"),
                    version=spec.get("version"), token=spec.get("token"))
                self._reply(conn, OP_PREPARE_RELOAD,
                            struct.pack("<BI", STATUS_OK, staged))
            except Exception as e:  # noqa: BLE001 — wire-reported
                obs.inc("serve.reload_errors")
                self._reply(conn, OP_PREPARE_RELOAD, _err_payload(
                    STATUS_INTERNAL, f"{type(e).__name__}: {e}"))
        elif opcode == OP_COMMIT_RELOAD:
            try:
                token = struct.unpack_from("<QQ", payload, 0) \
                    if len(payload) >= 16 else None
                kill_point("serve:pre_commit")  # chaos: die mid-phase-2
                version = self.commit_reload(token)
                self._reply(conn, OP_COMMIT_RELOAD,
                            struct.pack("<BI", STATUS_OK, version))
            except Exception as e:  # noqa: BLE001 — wire-reported
                obs.inc("serve.reload_errors")
                self._reply(conn, OP_COMMIT_RELOAD, _err_payload(
                    STATUS_INTERNAL, f"{type(e).__name__}: {e}"))
        elif opcode == OP_ABORT_RELOAD:
            token = struct.unpack_from("<QQ", payload, 0) \
                if len(payload) >= 16 else None
            self.abort_reload(token)
            self._reply(conn, OP_ABORT_RELOAD, struct.pack("<B", STATUS_OK))
        elif opcode == OP_STATS:
            # optional json payload {"metrics": false} skips the registry
            # snapshot — the fleet supervisor polls replica queue-depth/
            # occupancy every probe interval and must not pay a full
            # snapshot per poll (empty payload = legacy full stats)
            include = True
            if len(payload):
                try:
                    spec = json.loads(bytes(payload).decode("utf-8"))
                    include = bool(spec.get("metrics", True))
                except ValueError:
                    pass
            blob = json.dumps(self.stats(include_metrics=include),
                              default=str).encode("utf-8")
            self._reply(conn, OP_STATS, struct.pack("<B", STATUS_OK) + blob)
        elif opcode == OP_TELEMETRY:
            try:
                spec = json.loads(bytes(payload).decode("utf-8")) \
                    if len(payload) else {}
                token = spec.get("token")
                blob = None
                if token is not None:
                    with self._telemetry_lock:
                        blob = self._telemetry_tokens.get(token)
                if blob is None:
                    tel = self.telemetry(drain=bool(spec.get("drain", True)),
                                         retained=spec.get("retained"))
                    if spec.get("format") == "prometheus":
                        from ..obs.export import parts_to_prometheus

                        blob = parts_to_prometheus(
                            tel["parts"],
                            openmetrics=bool(spec.get("openmetrics", True)),
                        ).encode("utf-8")
                    else:
                        blob = json.dumps(tel, default=float).encode("utf-8")
                    if token is not None:
                        with self._telemetry_lock:
                            self._telemetry_tokens[token] = blob
                            while len(self._telemetry_tokens) > 4:
                                self._telemetry_tokens.popitem(last=False)
                self._reply(conn, OP_TELEMETRY,
                            struct.pack("<B", STATUS_OK) + blob)
            except Exception as e:  # noqa: BLE001 — wire-reported
                obs.inc("serve.telemetry_errors")
                self._reply(conn, OP_TELEMETRY, _err_payload(
                    STATUS_INTERNAL, f"{type(e).__name__}: {e}"))
        elif opcode == OP_DUMP:
            # flight-recorder snapshot (obs/blackbox.py): the bundle is
            # built from the always-on ring — nothing drains, so retries
            # are harmless and no dedup token is needed
            try:
                spec = json.loads(bytes(payload).decode("utf-8")) \
                    if len(payload) else {}
                from ..obs import blackbox

                reason = str(spec.get("reason", "wire"))
                doc = blackbox.bundle(reason=reason)
                if spec.get("write") and blackbox.enabled():
                    # persist the SAME document the reply carries (a
                    # second bundle_dict here would snapshot a later,
                    # different ring)
                    doc["path"] = blackbox.dump(reason=reason, doc=doc)
                blob = json.dumps(doc, default=str).encode("utf-8")
                self._reply(conn, OP_DUMP,
                            struct.pack("<B", STATUS_OK) + blob)
            except Exception as e:  # noqa: BLE001 — wire-reported
                obs.inc("serve.dump_errors")
                self._reply(conn, OP_DUMP, _err_payload(
                    STATUS_INTERNAL, f"{type(e).__name__}: {e}"))
        elif opcode == OP_DRAIN:
            stop = bool(payload and payload[0])
            drained = self.drain(stop=False)
            self._reply(conn, OP_DRAIN, struct.pack(
                "<B", STATUS_OK if drained else STATUS_INTERNAL))
            if stop:
                self.stop()
                return False
        elif opcode == OP_SHUTDOWN:
            self._reply(conn, OP_SHUTDOWN, struct.pack("<B", STATUS_OK))
            self.stop()
            return False
        else:
            self._reply(conn, opcode,
                        _err_payload(STATUS_BAD_REQUEST,
                                     f"unknown opcode {opcode}"))
        return True

    def _do_infer_stream(self, conn, payload) -> bool:
        """Relay one generation as a chunked reply sequence. The token
        source is uniform: ``DecodeScheduler.generate`` on a replica,
        ``Router.generate`` on a fleet front — both yield ints and raise
        the typed serve errors, possibly mid-stream. Returns False (drop
        the connection) only when the CLIENT died mid-stream — the
        generator's close() cancels the generation so its KV pages are
        reclaimed at the next step boundary."""
        src = self._decode if self._decode is not None else self._batcher
        gen_fn = getattr(src, "generate", None)
        if gen_fn is None:
            self._reply(conn, OP_STREAM_ERROR, _err_payload(
                STATUS_NOT_READY, "no decode path loaded"))
            return True
        if self._draining:
            self._shed_draining += 1
            obs.inc("serve.shed_draining")
            obs.tail.note("shed")
            self._reply(conn, OP_STREAM_ERROR, _err_payload(
                STATUS_DRAINING, "endpoint draining"))
            return True
        try:
            deadline_ms, priority, max_new, temp = \
                _STREAM_HDR.unpack_from(payload, 0)
            arrays, _ = _unpack_arrays(payload[_STREAM_HDR.size:])
            tokens = np.asarray(arrays[0]).reshape(-1)
        except (struct.error, IndexError, KeyError, ValueError) as e:
            self._reply(conn, OP_STREAM_ERROR, _err_payload(
                STATUS_BAD_REQUEST, f"malformed INFER_STREAM frame: {e}"))
            return True
        gen = gen_fn(tokens,
                     max_new_tokens=int(max_new) or None,
                     deadline_ms=deadline_ms or None,
                     priority=int(priority),
                     temperature=float(temp))
        n = 0
        try:
            try:
                for tok in gen:
                    n += 1
                    # chaos: die with tokens streamed but the generation
                    # still resident — the page-reclaim proof's kill point
                    kill_point("serve:mid_stream")
                    _send_msg(conn, OP_STREAM_TOKEN, "",
                              _TOKEN_FRAME.pack(int(tok) & 0xFFFFFFFF, n))
                _send_msg(conn, OP_STREAM_END, "",
                          struct.pack("<BI", STATUS_OK, n))
            except RequestRejected as e:
                obs.tail.note("shed")
                _send_msg(conn, OP_STREAM_ERROR, "",
                          _err_payload(STATUS_REJECTED, str(e)))
            except DeadlineExceeded as e:
                obs.tail.note("deadline")
                _send_msg(conn, OP_STREAM_ERROR, "",
                          _err_payload(STATUS_DEADLINE, str(e)))
            except Draining as e:
                obs.tail.note("shed")
                _send_msg(conn, OP_STREAM_ERROR, "",
                          _err_payload(STATUS_DRAINING, str(e)))
            except ServeError as e:
                obs.tail.note("error")
                _send_msg(conn, OP_STREAM_ERROR, "",
                          _err_payload(STATUS_INTERNAL, str(e)))
        except (ConnectionError, OSError):
            # the CLIENT vanished mid-stream: nothing to reply to — just
            # make sure the generation leaves the batch
            obs.inc("serve.stream_client_lost")
            return False
        finally:
            gen.close()
            if n:
                obs.inc("serve.stream_tokens", n)
        return True

    def _do_infer(self, payload):
        if self._batcher is None:
            return _err_payload(STATUS_NOT_READY, "no model loaded")
        if self._draining:
            self._shed_draining += 1
            obs.inc("serve.shed_draining")
            return _err_payload(STATUS_DRAINING, "endpoint draining")
        try:
            deadline_ms, priority = _INFER_HDR.unpack_from(payload, 0)
            arrays, _ = _unpack_arrays(payload[_INFER_HDR.size:])
        except (struct.error, IndexError, KeyError, ValueError) as e:
            return _err_payload(STATUS_BAD_REQUEST,
                                f"malformed INFER frame: {e}")
        try:
            fut = self._batcher.submit(arrays,
                                       deadline_ms=deadline_ms or None,
                                       priority=int(priority))
            wait = (deadline_ms / 1e3) if deadline_ms \
                else self._default_timeout
            outs, version = fut.result(timeout=wait + 1.0)
        except RequestRejected as e:
            obs.tail.note("shed")
            return _err_payload(STATUS_REJECTED, str(e))
        except DeadlineExceeded as e:
            obs.tail.note("deadline")
            # DEADLINE means "your deadline passed, the work was shed"; a
            # deadline-LESS request timing out the server-side wait is an
            # internal condition (the work may still execute), not an SLO
            # miss the client never asked for
            if not deadline_ms:
                return _err_payload(
                    STATUS_INTERNAL,
                    f"server wait exceeded {self._default_timeout}s: {e}")
            return _err_payload(STATUS_DEADLINE, str(e))
        except Draining as e:
            obs.tail.note("shed")
            return _err_payload(STATUS_DRAINING, str(e))
        except ServeError as e:
            obs.tail.note("error")
            return _err_payload(STATUS_INTERNAL, str(e))
        with obs.trace.span("serve.serialize", outputs=len(outs)):
            # status header and packed arrays travel as separate parts:
            # _send_msg scatter-gathers them, so the reply is never
            # re-copied into one contiguous buffer (data-plane lint)
            reply = [struct.pack("<BI", STATUS_OK, version),
                     _pack_arrays([np.ascontiguousarray(o) for o in outs])]
        # chaos: die with the answer computed but unsent — the INFER-specific
        # twin of serve:pre_reply (which also fires on probe replies, so a
        # fleet test could never target "kill mid-INFER-reply" with it)
        kill_point("serve:infer_pre_reply")
        return reply


def main():  # pragma: no cover - CLI shim
    import argparse

    import jax

    # serving may legitimately target the accelerator; MXNET_SERVE_PLATFORM
    # pins it (the PS server's MXNET_PS_PLATFORM idiom)
    plat = os.environ.get("MXNET_SERVE_PLATFORM")
    if plat:
        jax.config.update("jax_platforms", plat)

    ap = argparse.ArgumentParser(description="mxnet_tpu serving endpoint")
    ap.add_argument("model", help="artifact path (Module checkpoint prefix, "
                    "gluon export path, or checkpoint directory)")
    ap.add_argument("--epoch", type=int, default=None)
    ap.add_argument("--port", type=int, default=9191)
    ap.add_argument("--max-batch-size", type=int, default=32)
    ap.add_argument("--max-linger-ms", type=float, default=2.0)
    ap.add_argument("--max-queue", type=int, default=256)
    ap.add_argument("--warmup-shape", type=str, default=None,
                    help="comma-separated per-row feature shape to "
                         "pre-compile every bucket for, e.g. 3,224,224")
    ap.add_argument("--progcache-dir", type=str, default=None,
                    help="persistent AOT program-cache directory "
                         "(mxnet_tpu/progcache.py); overrides "
                         "MXNET_PROGCACHE_DIR — warmup deserializes "
                         "previously compiled bucket programs instead of "
                         "recompiling them")
    ap.add_argument("--tp", type=int, default=0,
                    help="tensor-parallel shard the engine over the first "
                         "N local devices (mesh axis 'tp'; sharding specs "
                         "come from the model's rule table when serving "
                         "in-process — the CLI path replicates params)")
    args = ap.parse_args()

    from . import load

    if args.progcache_dir:
        from .. import progcache

        progcache.configure(args.progcache_dir)

    engine_kw = {}
    if args.tp:
        from ..parallel import make_mesh

        engine_kw["mesh"] = make_mesh({"tp": args.tp})
    engine = load(args.model, epoch=args.epoch,
                  max_batch_size=args.max_batch_size, **engine_kw)
    if args.warmup_shape:
        feat = tuple(int(d) for d in args.warmup_shape.split(",") if d)
        engine.warmup(feat)
    srv = ServeServer(engine, port=args.port,
                      max_linger_ms=args.max_linger_ms,
                      max_queue=args.max_queue)
    print(f"ServeServer listening on :{srv.port}", flush=True)
    srv.serve_forever()


if __name__ == "__main__":  # pragma: no cover
    main()

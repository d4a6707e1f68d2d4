"""Data-plane copy/sync accounting (``MXNET_COPYTRACK=1``) — the runtime
twin of ``mxnet_tpu.analysis.dataplane``.

The static pass proves *where* array bytes can be copied or a host sync
can happen on a hot path; this module measures *how much*, per process,
at the choke points every request transits:

- wire framing (``kvstore/ps_server.py`` ``_pack_array``/``_pack_arrays``/
  ``_send_msg``/``_recv_exact``/``_unpack_array``) — serialize calls and
  the bytes each redundant buffer copy moves;
- batcher assembly (``serve/batcher.py`` per-batch ``np.concatenate``);
- device boundary (``serve/engine.py`` ``device_get``/
  ``block_until_ready`` host syncs, h2d pad/put copies).

Counters: ``wire.bytes_copied`` (every byte moved by a host-side buffer
copy), ``wire.serialize_calls`` / ``wire.serialize_bytes`` (array→wire
packs), ``hotpath.host_syncs`` (device→host materialization points, by
site). They feed two consumers:

- ``copytrack.snapshot()`` — always available while enabled; a caller
  divides deltas by its request count to get bytes copied, serialize
  calls and host syncs per request (``tests/test_dataplane_lint.py``
  holds the counts of one INFER hop);
- the ``mxnet_tpu.obs`` metrics registry (same counter names) when
  telemetry is ALSO on — so the numbers ride STATS replies, Prometheus
  exposition, and merged fleet timelines for free.

Zero-overhead-when-off contract (the ``tsan.py`` idiom): every
instrumented site calls ``copytrack.TRACKER.<method>(...)``. When
``MXNET_COPYTRACK`` is unset, ``TRACKER`` is the no-op singleton
``NULL`` — one attribute lookup plus an empty method call, no locks, no
env reads, no branches. Tests assert ``TRACKER is NULL`` stays true
after exercising the serve path with the flag off.
"""
from __future__ import annotations

import threading
from typing import Dict

from .base import get_env

__all__ = ["enabled", "enable", "disable", "reset", "snapshot",
           "TRACKER", "NULL"]


class _NullTracker:
    """No-op singleton bound to ``TRACKER`` while tracking is off."""

    __slots__ = ()
    enabled = False

    def copied(self, nbytes):
        pass

    def serialized(self, nbytes, calls=1):
        pass

    def host_sync(self, site=""):
        pass

    def snapshot(self) -> Dict[str, float]:
        return {}


class _Tracker:
    """Live counters; one lock, increments only (hot-path friendly)."""

    __slots__ = ("_mu", "bytes_copied", "serialize_calls",
                 "serialize_bytes", "host_syncs", "sync_sites")
    enabled = True

    def __init__(self):
        self._mu = threading.Lock()
        self.bytes_copied = 0
        self.serialize_calls = 0
        self.serialize_bytes = 0
        self.host_syncs = 0
        self.sync_sites: Dict[str, int] = {}

    def copied(self, nbytes) -> None:
        n = int(nbytes)
        with self._mu:
            self.bytes_copied += n
        _obs_inc("wire.bytes_copied", n)

    def serialized(self, nbytes, calls=1) -> None:
        n = int(nbytes)
        with self._mu:
            self.serialize_calls += calls
            self.serialize_bytes += n
        _obs_inc("wire.serialize_calls", calls)
        _obs_inc("wire.serialize_bytes", n)

    def host_sync(self, site="") -> None:
        with self._mu:
            self.host_syncs += 1
            if site:
                self.sync_sites[site] = self.sync_sites.get(site, 0) + 1
        _obs_inc("hotpath.host_syncs", 1)

    def snapshot(self) -> Dict[str, float]:
        with self._mu:
            return {
                "wire.bytes_copied": self.bytes_copied,
                "wire.serialize_calls": self.serialize_calls,
                "wire.serialize_bytes": self.serialize_bytes,
                "hotpath.host_syncs": self.host_syncs,
                "hotpath.sync_sites": dict(self.sync_sites),
            }


def _obs_inc(name: str, n: int) -> None:
    # forward into the metrics registry so STATS/Prometheus surface the
    # counters when telemetry is on; obs.inc is itself no-op-when-off
    from . import obs

    obs.inc(name, n)


NULL = _NullTracker()
TRACKER = NULL  # rebound by enable()/disable(); call sites read it live


def enabled() -> bool:
    return TRACKER is not NULL


def enable() -> "_Tracker":
    """Swap in a live tracker (idempotent; keeps existing counters)."""
    global TRACKER
    if TRACKER is NULL:
        TRACKER = _Tracker()
    return TRACKER


def disable() -> None:
    global TRACKER
    TRACKER = NULL


def reset() -> None:
    """Zero the counters without changing the enabled state."""
    global TRACKER
    if TRACKER is not NULL:
        TRACKER = _Tracker()


def snapshot() -> Dict[str, float]:
    """Current counters (``{}`` while disabled)."""
    return TRACKER.snapshot()


if get_env("MXNET_COPYTRACK", False, bool):
    enable()

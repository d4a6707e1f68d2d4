"""Platform-outage injection: simulate an accelerator backend that hangs.

``jax.devices()`` can block forever without raising (a chip held by another
process is one way). ``mxnet_tpu.platform`` wraps those choke points in a
watchdog; this injector makes the failure reproducible on demand so the
degradation path (bounded exit + parseable ``platform_unavailable``
artifact) is a tested contract, not a hope.

``MXNET_CHAOS_PLATFORM_HANG`` names the guard points to hang:

- ``1`` / ``all`` / ``*`` — every guarded platform call blocks;
- a comma list (e.g. ``jax.devices,device_put``) — only those points.

The hook runs *inside* the watchdog's worker thread and blocks it forever
(a daemon thread, so it dies with the process) — byte-for-byte the shape of
the real hang: the caller sees no exception, no return, nothing, until
the watchdog fires. Like every injector in this package it is one env
lookup when disabled.
"""
from __future__ import annotations

import os
import time
from typing import Optional, Set

__all__ = ["hang_points", "hang_if_injected"]

_ALL = {"1", "all", "*", "true", "yes"}


def hang_points() -> Optional[Set[str]]:
    """Parsed ``MXNET_CHAOS_PLATFORM_HANG``: None when off, ``{"*"}`` for
    every point, else the set of guard-point names to hang. Parsed per
    call — subprocess tests flip the env var at runtime."""
    spec = os.environ.get("MXNET_CHAOS_PLATFORM_HANG", "").strip()
    if not spec:
        return None
    if spec.lower() in _ALL:
        return {"*"}
    return {p.strip() for p in spec.split(",") if p.strip()}


def hang_if_injected(point: str) -> None:
    """Block forever if chaos targets this guard point (called from inside
    the platform watchdog's worker thread)."""
    pts = hang_points()
    if pts is None or ("*" not in pts and point not in pts):
        return
    from .. import obs

    obs.event("chaos.platform_hang", point=point)
    while True:  # the real hang never returns either
        time.sleep(3600)

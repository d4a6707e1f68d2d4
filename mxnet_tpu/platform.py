"""Guarded platform entry points — bounded access to the accelerator.

``jax.devices()`` initializes the backend on first use, and a backend can
block there without raising: a chip held by another process is the case on
this hardware (a chip belongs to one process at a time, and the second one
fails *or hangs*). A driver that hangs at enumeration produces no result
and no error either. The reference stack never had this failure mode
(ps-lite treats a dead peer as a timeout); a TPU-native framework has to
build the equivalent discipline at the PJRT boundary.

This module is that boundary. Every first touch of the platform —
enumeration, backend init, the first ``device_put`` — goes through a
**watchdog**: the call runs in a daemon worker thread, the caller waits at
most ``timeout`` seconds, and a hang becomes a raised
:class:`PlatformUnavailable` carrying a machine-parseable artifact. Drivers
then degrade instead of hanging:

- ``devices_or_exit()`` prints ONE JSON line (schema
  ``mxnet_tpu.platform_error/1``) and exits non-zero in bounded time — the
  driver's capture records a *valid* "platform_unavailable" artifact;
- ``__graft_entry__.dryrun_multichip`` goes to the virtual CPU mesh (the
  child needs no chip) and says so on every line it prints;
- the serving fleet keeps serving on the replicas that still answer.

Chaos twin: ``MXNET_CHAOS_PLATFORM_HANG`` (``chaos/platform.py``) blocks
the worker thread exactly like a hung backend, so the bounded-exit contract
is asserted by tests, not assumed.

``MXNET_PLATFORM_TIMEOUT`` overrides the default watchdog budget
(seconds); per-call ``timeout=`` wins over both.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Any, Callable, List, Optional

from .base import MXNetError

__all__ = ["PlatformUnavailable", "call_with_watchdog", "devices",
           "devices_or_exit", "device_put", "emit_artifact",
           "virtual_cpu_env", "ARTIFACT_SCHEMA", "default_timeout"]

ARTIFACT_SCHEMA = "mxnet_tpu.platform_error/1"


def default_timeout() -> float:
    """Watchdog budget in seconds (``MXNET_PLATFORM_TIMEOUT``, default 90
    — comfortably under the 120 s bound every driver must exit within)."""
    return float(os.environ.get("MXNET_PLATFORM_TIMEOUT", 90))


class PlatformUnavailable(MXNetError):
    """A guarded platform call hung past its watchdog (``kind =
    "platform_unavailable"`` — the hung-backend signature) or raised
    during backend init (``kind = "platform_init_failed"`` — a real
    plugin/config failure that must not be triaged as the known hang)."""

    def __init__(self, what: str, detail: str, *, kind: str,
                 timeout_s: float, elapsed_s: float):
        super().__init__(f"{kind}: {what}: {detail}")
        self.what = what
        self.detail = detail
        self.kind = kind
        self.timeout_s = timeout_s
        self.elapsed_s = elapsed_s

    def artifact(self, **extra: Any) -> dict:
        """The machine-parseable error record every driver emits — one
        schema, so the capture harness greps for a single shape."""
        out = {
            "schema": ARTIFACT_SCHEMA,
            "error": self.kind,
            "what": self.what,
            "detail": self.detail[:300],
            "timeout_s": round(self.timeout_s, 1),
            "elapsed_s": round(self.elapsed_s, 1),
            "pid": os.getpid(),
            "argv0": os.path.basename(sys.argv[0]) if sys.argv else "",
        }
        if self.kind == "platform_unavailable":
            out["hint"] = ("accelerator backend unresponsive — is the "
                           "chip held by another process? A platform "
                           "condition, not a framework failure")
        out.update(extra)
        return out


def call_with_watchdog(fn: Callable[[], Any], *, what: str,
                       timeout: Optional[float] = None) -> Any:
    """Run ``fn()`` in a daemon worker thread, waiting at most ``timeout``
    seconds. A hang raises :class:`PlatformUnavailable` (the worker thread
    is abandoned — it blocks inside the backend and dies with the process,
    which is the only safe treatment PJRT offers); an exception from ``fn``
    is re-raised as ``platform_init_failed`` with the original message."""
    from .chaos.platform import hang_if_injected

    budget = default_timeout() if timeout is None else float(timeout)
    result: List[Any] = []
    error: List[BaseException] = []

    def _run():
        try:
            hang_if_injected(what)  # chaos: the blocking enumeration hook
            result.append(fn())
        except BaseException as e:  # noqa: BLE001 — reported via the artifact
            error.append(e)

    t0 = time.monotonic()
    worker = threading.Thread(target=_run, daemon=True,
                              name=f"mxtpu-platform-watchdog[{what}]")
    worker.start()
    worker.join(timeout=budget)
    elapsed = time.monotonic() - t0
    if worker.is_alive():
        raise PlatformUnavailable(
            what, f"no response within {budget:g}s watchdog",
            kind="platform_unavailable", timeout_s=budget, elapsed_s=elapsed)
    if error:
        e = error[0]
        raise PlatformUnavailable(
            what, f"{type(e).__name__}: {e}", kind="platform_init_failed",
            timeout_s=budget, elapsed_s=elapsed) from e
    return result[0]


def devices(timeout: Optional[float] = None, backend: Optional[str] = None):
    """``jax.devices()`` under the watchdog — the single most
    hang-prone call in the repo (it initializes the backend on first use,
    which is where a chip another process holds can block forever)."""
    import jax

    return call_with_watchdog(
        lambda: jax.devices(backend) if backend else jax.devices(),
        what="jax.devices", timeout=timeout)


def device_put(x, device=None, timeout: Optional[float] = None):
    """First-touch-guarded ``jax.device_put``: probes in drivers route their
    opening upload through this so a backend that enumerates but no longer
    moves bytes still fails in bounded time. Steady-state transfers after a
    successful first touch stay unguarded (per-call watchdog threads would
    distort the numbers being measured)."""
    import jax

    return call_with_watchdog(lambda: jax.device_put(x, device),
                              what="device_put", timeout=timeout)


def emit_artifact(err: PlatformUnavailable, stream=None, **extra) -> dict:
    """Print the one-line JSON platform-error artifact (flushed — the
    process is usually about to exit) and return it."""
    art = err.artifact(**extra)
    print(json.dumps(art), file=stream or sys.stdout, flush=True)
    return art


def devices_or_exit(what: str = "", timeout: Optional[float] = None,
                    exit_code: int = 1, **extra):
    """Driver preamble: return the device list, or emit the parseable
    platform-error artifact and exit — a hung backend costs one watchdog
    budget, never a hung run. ``what`` names the driver in the artifact
    (defaults to argv[0])."""
    try:
        return devices(timeout=timeout)
    except PlatformUnavailable as e:
        if what:
            extra.setdefault("driver", what)
        emit_artifact(e, **extra)
        sys.exit(exit_code)


def virtual_cpu_env(n_devices: int, base: Optional[dict] = None) -> dict:
    """Child-process environment for an n-device virtual CPU mesh — the
    legal fallback when the real platform is unreachable (the CPU child
    needs no chip). The same recipe tests/conftest.py uses. Strips the
    platform-hang chaos injector: it simulates an *accelerator* fault, and
    the CPU child never touches the accelerator."""
    import re

    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}").strip()
    env.pop("MXNET_CHAOS_PLATFORM_HANG", None)
    return env

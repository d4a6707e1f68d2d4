"""Device context: ``mx.cpu()``, ``mx.gpu()``, ``mx.tpu()``.

Re-design of the reference ``python/mxnet/context.py`` + ``include/mxnet/base.h``
``Context{dev_type, dev_id}`` (paths TBV — mount empty, SURVEY.md §0) for TPU:

- A ``Context`` names a *logical* device and resolves to a ``jax.Device``.
- ``mx.tpu(i)`` is the new first-class accelerator context (SURVEY.md §2.3
  "add mx.tpu(i) here").
- ``mx.gpu(i)`` **aliases to the accelerator** when no real GPU exists, so
  reference training scripts written against ``mx.gpu()`` run unmodified on a
  TPU pod (BASELINE.json north star).
- An accelerator context never resolves to a CPU device behind the caller's
  back. Only a process *configured* for CPU (``JAX_PLATFORMS=cpu``, or
  ``jax.config.update("jax_platforms", "cpu")`` as tests/conftest.py does)
  aliases ``mx.tpu(i)``/``mx.gpu(i)`` to its CPU devices, so CI runs device
  scripts unchanged; anywhere else a missing accelerator raises.
- There is no storage manager / stream pool here: PJRT owns device memory and
  XLA owns streams (reference L0 `src/storage/` is subsumed — SURVEY.md §2.1).
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context", "num_gpus", "num_tpus"]


class Context:
    """A logical device. Usable as a context manager to set the default device."""

    # dev_type int codes kept for checkpoint/string compat with the reference.
    devtype2num = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
    devnum2type = {v: k for k, v in devtype2num.items()}

    _default = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        if device_type not in self.devtype2num:
            raise ValueError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- identity ----------------------------------------------------------
    @property
    def device_typeid(self) -> int:
        return self.devtype2num[self.device_type]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    # -- JAX resolution ----------------------------------------------------
    def jax_device(self) -> jax.Device:
        """Resolve to a concrete jax.Device.

        gpu/tpu both resolve to the process's accelerator devices; gpu is an
        alias kept so reference scripts (`ctx=mx.gpu(0)`) run unmodified.
        """
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = _cpu_devices()
        else:
            devs = _accel_devices()
            if not devs:
                if not _configured_for_cpu():
                    raise RuntimeError(
                        f"{self}: jax found no accelerator (backend "
                        f"{jax.default_backend()!r}) and this process was "
                        "not configured for CPU — set JAX_PLATFORMS=cpu to "
                        "run accelerator contexts on the host on purpose")
                devs = _cpu_devices()
        return devs[self.device_id % len(devs)]

    # -- default-context scope --------------------------------------------
    def __enter__(self):
        stack = getattr(Context._default, "stack", None)
        if stack is None:
            stack = Context._default.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._default.stack.pop()

    @classmethod
    def default_ctx(cls) -> "Context":
        stack = getattr(cls._default, "stack", None)
        if stack:
            return stack[-1]
        global _DEFAULT
        if _DEFAULT is None:
            # Resolved on first use, NOT at import: touching jax.devices()
            # at import time would initialize the XLA backend and break the
            # create-kvstore-before-arrays contract jax.distributed needs.
            if _accel_devices():
                _DEFAULT = Context("tpu", 0)
            else:
                if not _configured_for_cpu():
                    import warnings

                    warnings.warn(
                        "mxnet_tpu: jax found no accelerator and fell back "
                        f"to {jax.default_backend()!r}; the default context "
                        "is cpu(0). Set JAX_PLATFORMS=cpu to say so on "
                        "purpose.", RuntimeWarning, stacklevel=3)
                _DEFAULT = Context("cpu", 0)
        return _DEFAULT


def _cpu_devices():
    # local_devices, not devices(): under jax.distributed a Context must name
    # a process-addressable device (reference: each worker owns its own GPUs)
    if jax.default_backend() != "cpu":
        return jax.local_devices(backend="cpu")
    return jax.local_devices()


def _configured_for_cpu() -> bool:
    """Was this process told to run on the CPU (as opposed to jax failing
    to take a chip and falling back to it)?"""
    return jax.config.jax_platforms == "cpu"


_ACCEL_CACHE: Optional[list] = None


def _accel_devices():
    global _ACCEL_CACHE
    if _ACCEL_CACHE is None:
        devs = jax.local_devices()
        _ACCEL_CACHE = [d for d in devs if d.platform not in ("cpu",)]
    return _ACCEL_CACHE


_DEFAULT: Optional[Context] = None  # lazily resolved by default_ctx()


def cpu(device_id: int = 0) -> Context:
    """CPU context (reference mx.cpu())."""
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    """Pinned-host-memory context. On PJRT this is plain host memory."""
    return Context("cpu_pinned", device_id)


def gpu(device_id: int = 0) -> Context:
    """Accelerator context; alias of tpu() on TPU machines (script compat)."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    """TPU context — the native accelerator context of this framework."""
    return Context("tpu", device_id)


def num_gpus() -> int:
    """Number of accelerator devices (reference mx.context.num_gpus())."""
    return len(_accel_devices())


def num_tpus() -> int:
    return len(_accel_devices())


def current_context() -> Context:
    """The innermost `with ctx:` scope, else the process default (cpu or tpu)."""
    return Context.default_ctx()



"""Base utilities: dtype handling, env-var config, error types.

TPU-native re-design of the reference's ``python/mxnet/base.py`` (ctypes FFI
bootstrap; reference path TBV — mount empty at survey time, see SURVEY.md §0).
There is no C ABI here: the "backend" is JAX/XLA over PJRT, so this module only
carries the pieces of base.py that still make sense — dtype tables, the
``MXNET_*`` env-var config layer (SURVEY.md §5.6 tier 1), and exception types.
"""
from __future__ import annotations

import os
from typing import Any

import numpy as np

__all__ = [
    "MXNetError",
    "mx_real_t",
    "string_types",
    "numeric_types",
    "integer_types",
    "get_env",
    "set_env",
    "dtype_np",
    "dtype_name",
    "capped_backoff",
    "configure_socket_keepalive",
    "checkout_path",
]


def checkout_path(name: str) -> str:
    """``<checkout root>/<name>``: the fixed home of this checkout's caches
    (``.jax_cache``, ``.mxnet_progcache`` — both git-ignored). Cache keys
    embed their directory, so the path must not depend on the user, the
    pid, or a temp name."""
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), name)


def capped_backoff(attempt: int, base_interval: float,
                   max_interval: float) -> float:
    """Capped exponential backoff with full-range jitter: attempt 0 →
    ~base_interval, doubling up to max_interval, scaled by a uniform draw
    in [0.5, 1.0]. The ONE retry-delay policy shared by the PS client and
    the serving plane (client reconnects, replica-pool restarts): jitter
    decorrelates a fleet hammering a restarting peer, and sharing the
    helper keeps the two planes from ever drifting apart."""
    import random

    delay = min(float(max_interval), float(base_interval) * (2.0 ** attempt))
    return delay * (0.5 + random.random() / 2.0)


def configure_socket_keepalive(sock, idle: int = 30, interval: int = 5,
                               count: int = 3) -> None:
    """Enable TCP keepalive on ``sock`` (half-open-connection detection).

    The ONE keepalive policy shared by the PS client, the serve client, and
    the elastic heartbeater: a peer that vanished without a FIN (SIGKILL'd
    VM, dropped link) is detected by the kernel after
    ``idle + interval*count`` seconds instead of whenever the OS default
    (often hours) gives up. The per-platform TCP_KEEP* constants are probed
    — missing ones just fall back to the system defaults; any OSError is
    swallowed because keepalive is an optimization, never a correctness
    requirement (the RPC layers still carry their own timeouts)."""
    import socket as _socket

    try:
        sock.setsockopt(_socket.SOL_SOCKET, _socket.SO_KEEPALIVE, 1)
        for opt, val in (("TCP_KEEPIDLE", idle), ("TCP_KEEPINTVL", interval),
                         ("TCP_KEEPCNT", count)):
            if hasattr(_socket, opt):
                sock.setsockopt(_socket.IPPROTO_TCP,
                                getattr(_socket, opt), val)
    except OSError:
        pass


class MXNetError(RuntimeError):
    """Error raised by the framework (parity with reference ``MXNetError``)."""


class GraphAnalysisError(MXNetError, ValueError):
    """Structured graph-analysis failure with node attribution.

    Raised by shape/type inference and ``bind(lint="error")`` instead of an
    opaque tracer exception: ``node``/``op``/``input_shapes`` name exactly
    where the graph broke. Subclasses ValueError so callers that caught the
    old ad-hoc inference ValueErrors keep working.
    """

    def __init__(self, message, node=None, op=None, rule_id=None,
                 input_shapes=None, findings=None):
        super().__init__(message)
        self.node = node
        self.op = op
        self.rule_id = rule_id
        self.input_shapes = input_shapes
        self.findings = findings or []


# Default real type, matching the reference's mshadow default_real_t = float32.
mx_real_t = np.float32

string_types = (str,)
numeric_types = (float, int, np.generic)
integer_types = (int, np.integer)

# dtype name <-> numpy dtype table. The reference keeps int codes in
# include/mxnet/base.h (mshadow TypeFlag); here names are canonical and the
# int codes are kept only for checkpoint-format compat (ndarray save/load).
_DTYPE_NAME_TO_NP = {
    "float32": np.float32,
    "float64": np.float64,
    "float16": np.float16,
    "bfloat16": None,  # filled lazily from ml_dtypes to avoid hard dep at import
    "uint8": np.uint8,
    "int32": np.int32,
    "int8": np.int8,
    "int64": np.int64,
    "bool": np.bool_,
    "int16": np.int16,
    "uint16": np.uint16,
    "uint32": np.uint32,
    "uint64": np.uint64,
}

# mshadow TypeFlag int codes (reference include/mxnet/base.h, TBV) — used by the
# binary .params format so checkpoints stay loadable across frameworks.
DTYPE_TO_CODE = {
    "float32": 0,
    "float64": 1,
    "float16": 2,
    "uint8": 3,
    "int32": 4,
    "int8": 5,
    "int64": 6,
    "bool": 7,
    "int16": 8,
    "uint16": 9,
    "uint32": 10,
    "uint64": 11,
    "bfloat16": 12,
}
CODE_TO_DTYPE = {v: k for k, v in DTYPE_TO_CODE.items()}


def _bfloat16():
    import ml_dtypes

    return ml_dtypes.bfloat16


def dtype_np(dtype: Any):
    """Normalize a user-facing dtype (str/np.dtype/type/None) to a numpy dtype."""
    if dtype is None:
        return np.dtype(mx_real_t)
    if isinstance(dtype, str):
        if dtype == "bfloat16":
            return np.dtype(_bfloat16())
        if dtype not in _DTYPE_NAME_TO_NP:
            raise TypeError(f"unknown dtype {dtype!r}")
        return np.dtype(_DTYPE_NAME_TO_NP[dtype])
    return np.dtype(dtype)


def dtype_name(dtype: Any) -> str:
    """Canonical string name for a dtype."""
    return np.dtype(dtype).name if not isinstance(dtype, str) else dtype


# ---------------------------------------------------------------------------
# Env-var config layer (reference: dmlc::GetEnv over MXNET_* — SURVEY.md §5.6).
# Reads accept both the historical MXNET_ prefix and no prefix.
# ---------------------------------------------------------------------------

def get_env(name: str, default=None, typ=str):
    """Read an ``MXNET_*`` config env var with type coercion.

    Mirrors the reference's dmlc::GetEnv tier of its 3-tier config system.
    """
    raw = os.environ.get(name)
    if raw is None and not name.startswith("MXNET_"):
        raw = os.environ.get("MXNET_" + name)
    if raw is None:
        return default
    if typ is bool:
        return raw.lower() not in ("0", "false", "off", "")
    return typ(raw)


def set_env(name: str, value) -> None:
    os.environ[name] = str(value)

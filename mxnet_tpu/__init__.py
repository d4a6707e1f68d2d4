"""mxnet_tpu — a TPU-native deep-learning framework with the capabilities of
Apache-MXNet-1.x-class systems (the reference, parkchanyong/mxnet).

Built from scratch on JAX/XLA/Pallas/pjit over PJRT. See SURVEY.md for the
layer map of the reference and README.md for the architecture of this build.

Import as ``import mxnet_tpu as mx`` — the namespace mirrors the reference's
``import mxnet as mx`` surface: ``mx.nd``, ``mx.sym``, ``mx.autograd``,
``mx.gluon``, ``mx.cpu()/mx.gpu()/mx.tpu()``, ``mx.kv``, ``mx.io``, …
"""
from __future__ import annotations

__version__ = "0.1.0"

import jax as _jax

# fp32 matmuls are true fp32 (reference parity: cuBLAS fp32 GEMM). The fast
# MXU path is bf16 *inputs* (AMP / bf16 params), which is single-pass
# regardless of this setting — so perf work happens in dtype policy, not here.
# Override via MXNET_MATMUL_PRECISION=default|high|highest.
import os as _os

_jax.config.update("jax_default_matmul_precision",
                   _os.environ.get("MXNET_MATMUL_PRECISION", "highest"))

from .base import MXNetError, get_env  # noqa: F401
from .base import checkout_path as _checkout_path


def _default_compile_cache_dir(environ=_os.environ):
    """Where this package points XLA's persistent compilation cache, or
    None when it must not: ``JAX_COMPILATION_CACHE_DIR`` is jax's own knob
    and whoever sets it (a chip machine that keeps a cache between runs)
    owns the location. Otherwise ONE fixed path in the checkout — the
    directory is part of the cache key, so a path that moves (a home
    directory, a pid, a temp name) never hits."""
    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return _checkout_path(".jax_cache")


_cache_dir = _default_compile_cache_dir()
if _cache_dir is not None:
    _jax.config.update("jax_compilation_cache_dir", _cache_dir)

from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context, num_gpus, num_tpus  # noqa: F401
from . import ops  # noqa: F401  (registers the operator library)
from . import ndarray  # noqa: F401
from . import ndarray as nd  # noqa: F401
from .ndarray import NDArray  # noqa: F401
from . import autograd  # noqa: F401
from . import random  # noqa: F401


def __getattr__(name):
    # Lazy submodule loading keeps `import mxnet_tpu` fast and cycle-free.
    import importlib

    lazy = {
        "sym": ".symbol",
        "symbol": ".symbol",
        "gluon": ".gluon",
        "optimizer": ".optimizer",
        "lr_scheduler": ".optimizer.lr_scheduler",
        "metric": ".metric",
        "initializer": ".initializer",
        "init": ".initializer",
        "io": ".io",
        "recordio": ".io.recordio",
        "image": ".image",
        "kvstore": ".kvstore",
        "kv": ".kvstore",
        "module": ".module",
        "mod": ".module",
        "rnn": ".rnn",
        "callback": ".callback",
        "profiler": ".profiler",
        "model": ".model",
        "runtime": ".runtime",
        "registry": ".registry",
        "test_utils": ".test_utils",
        "executor": ".executor",
        "amp": ".amp",
        "parallel": ".parallel",
        "models": ".models",
        "contrib": ".contrib",
        "util": ".util",
        "np": ".numpy",
        "npx": ".numpy_extension",
        "operator": ".operator",
        "monitor": ".monitor",
        "mon": ".monitor",
        "obs": ".obs",
        "platform": ".platform",
        "serve": ".serve",
        "native": ".native",
        "viz": ".visualization",
        "visualization": ".visualization",
        "engine": ".engine",
        "attribute": ".attribute",
        "subgraph": ".subgraph",
        "name": ".name",
    }
    if name in lazy:
        mod = importlib.import_module(lazy[name], __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'mxnet_tpu' has no attribute {name!r}")

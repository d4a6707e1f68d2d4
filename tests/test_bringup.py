"""Bring-up contracts (PR 21): where the compile cache goes, what an
accelerator context resolves to, and that ``chip_smoke.py`` refuses a CPU."""
import os
import subprocess
import sys

import pytest

import mxnet_tpu as mx
from mxnet_tpu import context, progcache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_dir_is_the_envs_or_one_fixed_path(monkeypatch):
    # jax's own variable set: this package names no directory at all
    assert mx._default_compile_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": "/x"}) is None
    # unset: one path inside the checkout — the directory is part of the
    # cache key, so nothing that moves (home, pid, temp name) may be in it

    def dirs():
        return mx._default_compile_cache_dir({}), progcache.default_dir()

    before = dirs()
    assert before == (os.path.join(REPO, ".jax_cache"),
                      os.path.join(REPO, ".mxnet_progcache"))
    monkeypatch.setenv("HOME", "/elsewhere")
    monkeypatch.setenv("XDG_CACHE_HOME", "/elsewhere/.cache")
    monkeypatch.setenv("TMPDIR", "/elsewhere/tmp")
    assert dirs() == before


def test_accelerator_context_needs_an_accelerator(monkeypatch):
    # the suite is configured for CPU (conftest): tpu/gpu alias to it
    assert mx.tpu(0).jax_device().platform == "cpu"
    assert mx.gpu(1).jax_device().platform == "cpu"
    # a process that merely FELL BACK to the CPU must not alias silently
    monkeypatch.setattr(context, "_configured_for_cpu", lambda: False)
    with pytest.raises(RuntimeError, match="no accelerator"):
        mx.tpu(0).jax_device()
    assert mx.cpu(0).jax_device().platform == "cpu"


def test_chip_smoke_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert "not 'tpu'" in r.stderr
    assert '"ok"' not in r.stdout  # no result line

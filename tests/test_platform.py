"""Guarded platform entry points (``mxnet_tpu.platform``) + the
platform-hang chaos injector (docs/ROBUSTNESS.md "Platform outages").

``jax.devices()`` can block forever without raising — a chip held by
another process is one way — and a driver that hangs there ships no result
and no error. The contract under test here: with the hang injector active
(``MXNET_CHAOS_PLATFORM_HANG`` — byte-for-byte the real hang's shape, the
call never returns), every guarded call raises
:class:`PlatformUnavailable` within its watchdog budget, and every driver
(``__graft_entry__.py``, the ``tools/`` probes) exits
non-zero with ONE parseable platform-error JSON line instead of hanging.
"""
import json
import os
import subprocess
import sys
import time

import pytest

from mxnet_tpu import platform as mxplatform
from mxnet_tpu.chaos import platform as chaos_platform

pytestmark = pytest.mark.chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_leaked_injector(monkeypatch):
    monkeypatch.delenv("MXNET_CHAOS_PLATFORM_HANG", raising=False)
    yield


# ---------------------------------------------------------------------------
# watchdog semantics
# ---------------------------------------------------------------------------

def test_watchdog_passes_result_through():
    assert mxplatform.call_with_watchdog(lambda: 42, what="t",
                                         timeout=5) == 42


def test_watchdog_timeout_raises_bounded():
    t0 = time.monotonic()
    with pytest.raises(mxplatform.PlatformUnavailable) as ei:
        mxplatform.call_with_watchdog(lambda: time.sleep(30), what="hang",
                                      timeout=0.2)
    assert time.monotonic() - t0 < 5.0
    err = ei.value
    assert err.kind == "platform_unavailable"
    assert err.timeout_s == 0.2
    art = err.artifact()
    assert art["schema"] == mxplatform.ARTIFACT_SCHEMA
    assert art["error"] == "platform_unavailable"
    json.dumps(art)  # must be wire-serializable


def test_watchdog_init_raise_is_distinct():
    """A RAISE during backend init is a real failure (plugin/config) and
    must never be triaged as a hang."""

    def boom():
        raise RuntimeError("plugin exploded")

    with pytest.raises(mxplatform.PlatformUnavailable) as ei:
        mxplatform.call_with_watchdog(boom, what="init", timeout=5)
    assert ei.value.kind == "platform_init_failed"
    assert "plugin exploded" in ei.value.detail
    assert "hint" not in ei.value.artifact()  # the hang hint would mislead


def test_devices_normal_path():
    devs = mxplatform.devices(timeout=60)
    assert len(devs) >= 1


# ---------------------------------------------------------------------------
# the platform-hang injector
# ---------------------------------------------------------------------------

def test_hang_points_parse(monkeypatch):
    assert chaos_platform.hang_points() is None
    monkeypatch.setenv("MXNET_CHAOS_PLATFORM_HANG", "1")
    assert chaos_platform.hang_points() == {"*"}
    monkeypatch.setenv("MXNET_CHAOS_PLATFORM_HANG", "jax.devices, device_put")
    assert chaos_platform.hang_points() == {"jax.devices", "device_put"}


def test_platform_hang_bounds_devices(monkeypatch):
    """With the injector on, devices() must fail within the watchdog budget
    carrying the platform_unavailable artifact — exactly what every driver
    does with a real hang."""
    monkeypatch.setenv("MXNET_CHAOS_PLATFORM_HANG", "1")
    t0 = time.monotonic()
    with pytest.raises(mxplatform.PlatformUnavailable) as ei:
        mxplatform.devices(timeout=0.3)
    assert time.monotonic() - t0 < 5.0
    assert ei.value.kind == "platform_unavailable"
    assert ei.value.what == "jax.devices"


def test_platform_hang_named_point_only(monkeypatch):
    monkeypatch.setenv("MXNET_CHAOS_PLATFORM_HANG", "device_put")
    # un-targeted point passes straight through
    assert len(mxplatform.devices(timeout=30)) >= 1


def test_virtual_cpu_env_strips_injector(monkeypatch):
    monkeypatch.setenv("MXNET_CHAOS_PLATFORM_HANG", "1")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=2 --foo")
    env = mxplatform.virtual_cpu_env(4)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
    assert "--xla_force_host_platform_device_count=2" not in env["XLA_FLAGS"]
    assert "MXNET_CHAOS_PLATFORM_HANG" not in env  # CPU child needs no chip


# ---------------------------------------------------------------------------
# driver bounded-exit contract (subprocess — the real degradation path)
# ---------------------------------------------------------------------------

def _run_hung_driver(cmd, budget=60.0):
    env = dict(os.environ)
    env["MXNET_CHAOS_PLATFORM_HANG"] = "1"
    env["MXNET_PLATFORM_TIMEOUT"] = "2"
    env.pop("JAX_PLATFORMS", None)  # drivers must not need a cpu pin to exit
    t0 = time.monotonic()
    out = subprocess.run(cmd, cwd=REPO, env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True, timeout=budget)
    wall = time.monotonic() - t0
    return out.returncode, out.stdout, wall


def _parse_artifact(stdout):
    arts = []
    for line in stdout.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            d = json.loads(line)
        except ValueError:
            continue
        if isinstance(d, dict):
            arts.append(d)
    assert arts, f"no JSON artifact line in driver output:\n{stdout[-2000:]}"
    return arts


def test_tools_probe_exits_with_artifact_under_hang():
    rc, out, wall = _run_hung_driver(
        [sys.executable, os.path.join(REPO, "tools", "tune_flash.py")])
    assert rc == 1
    assert wall < 60
    (art,) = _parse_artifact(out)
    assert art["schema"] == mxplatform.ARTIFACT_SCHEMA
    assert art["error"] == "platform_unavailable"
    assert art["driver"] == "tools/tune_flash.py"


def test_graft_entry_main_exits_with_artifact_under_hang():
    rc, out, wall = _run_hung_driver(
        [sys.executable, os.path.join(REPO, "__graft_entry__.py")])
    assert rc == 1
    assert wall < 60
    arts = _parse_artifact(out)
    assert any(a.get("error") == "platform_unavailable" for a in arts)


@pytest.mark.slow
def test_graft_dryrun_falls_back_to_cpu_mesh_under_hang():
    """With the accelerator backend hung, the multichip dry run emits the
    outage artifact AND still produces valid results on the virtual CPU
    mesh (the child needs no chip) — labelled as such."""
    env = dict(os.environ)
    env["MXNET_CHAOS_PLATFORM_HANG"] = "1"
    env["MXNET_PLATFORM_TIMEOUT"] = "2"
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__; __graft_entry__.dryrun_multichip(2)"],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-2000:]
    arts = _parse_artifact(out.stdout)
    assert any(a.get("error") == "platform_unavailable" for a in arts)
    assert "3/3 combos OK [platform=cpu devices=2]" in out.stdout

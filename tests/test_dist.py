"""Multi-process distributed kvstore tests: 3 real worker processes on
localhost through tools/launch.py (reference nightly dist kvstore tests +
dmlc local tracker — SURVEY.md §3.4/§4)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "dist_worker.py")
LAUNCH = os.path.join(REPO, "tools", "launch.py")


def _run_launcher(extra_args, mode, timeout=240, env_extra=None):
    env = dict(os.environ)
    # children get exactly one CPU device each (parent conftest forces 8)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    cmd = [sys.executable, LAUNCH, *extra_args,
           sys.executable, WORKER, mode]
    proc = subprocess.run(cmd, env=env, cwd=REPO, timeout=timeout,
                          stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                          text=True)
    assert proc.returncode == 0, f"launcher rc={proc.returncode}\n{proc.stdout[-4000:]}"
    return proc.stdout


def test_dist_sync_three_workers():
    out = _run_launcher(["-n", "3"], "dist_sync")
    assert out.count("OK") == 3, out[-2000:]


def test_dist_sync_four_workers():
    """n=4 known-value run (VERDICT r3 item 6: dist testing stopped at 3
    processes; the reference nightly runs more — dist_sync_kvstore.py TBV).
    Covers dense sum, row_sparse, 2-bit compression, optimizer-on-store."""
    out = _run_launcher(["-n", "4"], "dist_sync", timeout=360)
    assert out.count("OK") == 4, out[-2000:]


@pytest.mark.elastic
def test_dist_sync_elastic_three_workers():
    """The full dist_sync known-value suite (rank-0-wins init, exact dense
    sums, push merge, 2-bit compressed fused collective, row_sparse,
    optimizer-on-store) over the elastic PS-reduce transport — the
    generation-scoped allreduce must be EXACT, not approximately right."""
    out = _run_launcher(["-n", "3", "-e"], "dist_sync",
                        env_extra={"MXNET_ELASTIC": "1"})
    assert out.count("OK") == 3, out[-2000:]


@pytest.mark.elastic
def test_dist_sync_elastic_four_workers():
    out = _run_launcher(["-n", "4", "-e"], "dist_sync", timeout=360,
                        env_extra={"MXNET_ELASTIC": "1"})
    assert out.count("OK") == 4, out[-2000:]


def test_dist_async_four_workers_native_ps():
    ps_bin = os.path.join(REPO, "native", "build", "mxtpu_ps_server")
    if not os.path.exists(ps_bin):
        pytest.skip("native PS server not built")
    out = _run_launcher(["-n", "4", "-s", "1"], "dist_async", timeout=360)
    assert out.count("OK") == 4, out[-2000:]


def test_dist_async_three_workers_native_ps():
    ps_bin = os.path.join(REPO, "native", "build", "mxtpu_ps_server")
    if not os.path.exists(ps_bin):
        pytest.skip("native PS server not built")
    out = _run_launcher(["-n", "3", "-s", "1"], "dist_async")
    assert out.count("OK") == 3, out[-2000:]


def test_dist_async_python_ps(tmp_path, monkeypatch):
    """Same known-value run against the python twin server."""
    ps_bin = os.path.join(REPO, "native", "build", "mxtpu_ps_server")
    hidden = str(tmp_path / "mxtpu_ps_server")
    if os.path.exists(ps_bin):
        os.rename(ps_bin, hidden)
    try:
        out = _run_launcher(["-n", "2", "-s", "1"], "dist_async")
        assert out.count("OK") == 2, out[-2000:]
    finally:
        if os.path.exists(hidden):
            os.rename(hidden, ps_bin)

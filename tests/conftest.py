"""Test config: force CPU with 8 virtual devices so multi-chip sharding logic
is exercised without TPU hardware (SURVEY.md §4: the reference's analog is the
dmlc local tracker forking a PS cluster on localhost).

``jax.config.update("jax_platforms", "cpu")`` below makes the suite a CPU
suite however pytest was started; ``JAX_PLATFORMS=cpu`` in the environment
(the tier-1 command sets it) does the same for the subprocesses tests spawn.
"""
import os

os.environ.setdefault("MXNET_TEST_ON_CPU", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded from tier-1")
    config.addinivalue_line(
        "markers", "lint: static-analysis tests; run standalone via "
        "`pytest -m lint` or `make lint-tests`")
    config.addinivalue_line(
        "markers", "chaos: fault-injection tests (docs/ROBUSTNESS.md); run "
        "via `pytest -m chaos` or `make chaos`. Fast chaos tests stay in "
        "tier-1; subprocess SIGKILL ones are also marked slow")
    config.addinivalue_line(
        "markers", "perf: dispatch-count / perf-guarantee smoke tests "
        "(docs/PERFORMANCE.md); run via `pytest -m perf` or `make perf`")
    config.addinivalue_line(
        "markers", "obs: runtime telemetry tests — span tracer, metrics "
        "registry, instrumented step (docs/OBSERVABILITY.md); run via "
        "`pytest -m obs` or `make obs`")
    config.addinivalue_line(
        "markers", "serve: inference-serving tests — compiled engine, "
        "dynamic batcher, socket endpoint (docs/SERVING.md); run via "
        "`pytest -m serve` or `make serve`")
    config.addinivalue_line(
        "markers", "health: training-health plane tests — divergence "
        "sentinel, NaN provenance, checkpoint auto-rollback "
        "(docs/OBSERVABILITY.md \"Training health\"); run via "
        "`pytest -m health` or `make health`")
    config.addinivalue_line(
        "markers", "elastic: elastic-training tests — worker membership/"
        "heartbeats, generation-scoped barriers, PS durability, "
        "checkpointed rejoin (docs/ROBUSTNESS.md \"Elastic training\"); "
        "run via `pytest -m elastic` or `make elastic`")
    config.addinivalue_line(
        "markers", "blackbox: black-box plane tests — tail-based trace "
        "retention, continuous stack profiler, crash flight recorder "
        "(docs/OBSERVABILITY.md); run via `pytest -m blackbox` or "
        "`make prof`")
    config.addinivalue_line(
        "markers", "serve_mesh: mesh-sharded serving + elastic autoscale "
        "tests on the 8-virtual-device CPU mesh — tensor-parallel engines, "
        "replica groups on mesh slices, quarantine→activate joins "
        "(docs/SERVING.md \"Mesh-sharded serving\"); run via "
        "`pytest -m serve_mesh` or `make serve_mesh`")
    config.addinivalue_line(
        "markers", "train_obs: training-fleet telemetry tests — per-rank "
        "step attribution, straggler detection/blame, PS telemetry "
        "opcode, reduce-plane accounting (docs/OBSERVABILITY.md "
        "\"Training-fleet telemetry\"); run via `pytest -m train_obs` or "
        "`make train-obs`")
    config.addinivalue_line(
        "markers", "progcache: persistent AOT program-cache tests — "
        "shared key derivation, hit/miss/reject structure, cache-hit "
        "bitwise parity, replica restart warm-from-disk "
        "(docs/PERFORMANCE.md \"Program cache and cold start\"); run via "
        "`pytest -m progcache` or `make progcache`")
    config.addinivalue_line(
        "markers", "async: bounded-staleness async-training tests — "
        "committed clocks, the staleness-gated pull, straggler-verdict "
        "actuation (widen/recut), hierarchical reduction, async vs sync "
        "convergence (docs/ROBUSTNESS.md \"Asynchronous training\"); run "
        "via `pytest -m async` or `make async`")
    config.addinivalue_line(
        "markers", "dataplane: data-plane lint tests — hot-path copy/"
        "sync/allocation rules, resource lifetime, env-registry drift, "
        "and the MXNET_COPYTRACK runtime twin (docs/ANALYSIS.md "
        "\"Data-plane lint\"); run via `pytest -m dataplane` or "
        "`make copytrack`")
    config.addinivalue_line(
        "markers", "decode: autoregressive decode-engine tests — paged "
        "KV cache alloc/free/leak, the two-program compile bound, "
        "continuous-batch join/leave, streaming wire roundtrip, "
        "progcache-warm replica (docs/SERVING.md \"Autoregressive "
        "decode\"); run via `pytest -m decode` or `make decode`")


@pytest.fixture(autouse=True)
def _seed():
    """Reference with_seed() decorator analog: seed numpy + framework RNG per
    test; repro a failure by exporting MXNET_TEST_SEED."""
    seed = int(os.environ.get("MXNET_TEST_SEED", "0")) or np.random.randint(0, 2**31)
    np.random.seed(seed)
    import mxnet_tpu as mx

    mx.random.seed(seed)
    yield

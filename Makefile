# Developer entry points. The analyzer targets are what CI / future PRs
# should run before binding anything (docs/ANALYSIS.md); `make chaos` is the
# fault-injection suite (docs/ROBUSTNESS.md).

PYTHON ?= python
export JAX_PLATFORMS ?= cpu

.PHONY: lint lint-tests test test-fast chaos chaos-serve elastic async perf obs health serve serve_mesh tsan prof progcache train-obs copytrack decode

# repo self-lint: framework invariants + the concurrency-correctness pass
# (lock-order cycles, blocking-under-lock, CV/thread discipline, wire
# protocol registry checks) over mxnet_tpu/ source — fails on any
# unwaived finding (docs/ANALYSIS.md "Concurrency lint")
lint:
	$(PYTHON) tools/lint_repo.py mxnet_tpu

# runtime concurrency sanitizer (docs/ANALYSIS.md "Concurrency lint"):
# re-run the serve-fleet SIGKILL and elastic-rejoin chaos suites with the
# instrumented locks on and the deadlock watchdog armed — every chaos run
# doubles as a lock-order sanitizer run
tsan:
	MXNET_TSAN=1 MXNET_TSAN_STALL_S=30 $(PYTHON) -m pytest tests/test_tsan.py tests/test_fleet.py -q -p no:cacheprovider
	MXNET_TSAN=1 MXNET_TSAN_STALL_S=30 $(PYTHON) -m pytest tests/test_elastic.py -q -p no:cacheprovider

# data-plane sanitizer (docs/ANALYSIS.md "Data-plane lint"): the dataplane
# lint test subset with the MXNET_COPYTRACK runtime twin exercised e2e
# (bytes copied / serialize calls / host syncs per request of one INFER hop)
copytrack:
	$(PYTHON) -m pytest tests/ -q -m dataplane -p no:cacheprovider

# the static-analysis test subset (graph/trace/sharding/repo lint)
lint-tests:
	$(PYTHON) -m pytest tests/ -q -m lint -p no:cacheprovider

# tier-1: everything but slow
test:
	$(PYTHON) -m pytest tests/ -q -m 'not slow' -p no:cacheprovider

test-fast: lint
	$(PYTHON) -m pytest tests/test_analysis.py tests/test_repo_lint.py -q -p no:cacheprovider

# fault-injection suite: SIGKILL/resume bitwise-resume proof, RPC drop/dup
# exactly-once checks, CRC corruption fallback (docs/ROBUSTNESS.md)
chaos:
	$(PYTHON) -m pytest tests/ -q -m chaos -p no:cacheprovider

# serving-fleet + platform-outage chaos (docs/ROBUSTNESS.md "Serving
# fleet"): the full fleet/platform suite incl. the slow SIGKILL flagship
chaos-serve:
	$(PYTHON) -m pytest tests/test_fleet.py tests/test_platform.py -q -p no:cacheprovider

# elastic-training suite (docs/ROBUSTNESS.md "Elastic training"): worker
# membership/heartbeats, generation-scoped barriers released over
# survivors, PS snapshot+WAL durability, checkpointed rejoin — incl. the
# slow flagships (1-of-3 worker SIGKILL mid-epoch; PS SIGKILL mid-push)
elastic:
	$(PYTHON) -m pytest tests/ -q -m elastic -p no:cacheprovider

# bounded-staleness async training (docs/ROBUSTNESS.md "Asynchronous
# training"): committed-clock protocol + gated pull, straggler-verdict
# actuation (staleness widen / shard recut), hierarchical reduction,
# async exactly-once across a PS SIGKILL, sync-vs-async convergence
async:
	$(PYTHON) -m pytest tests/ -q -m async -p no:cacheprovider

# dispatch-overhead guarantees (docs/PERFORMANCE.md): the perf-marked tests
# assert a Trainer.step updates all params in <=2 compiled programs
# (profiler.count_dispatches), and the device plane's memory gates
perf:
	$(PYTHON) -m pytest tests/ -q -m perf -p no:cacheprovider

# runtime telemetry suite (docs/OBSERVABILITY.md): span tracer, metrics
# registry, instrumented step phases, chaos-event tagging, PLUS the
# distributed plane — trace-context propagation over both wires, the
# OP_TELEMETRY collection plane, Prometheus exposition, SLO math, and the
# cross-process chaos flagship (2 ProcReplicas, one SIGKILLed, one merged
# timeline)
obs:
	$(PYTHON) -m pytest tests/ -q -m obs -p no:cacheprovider

# black-box plane (docs/OBSERVABILITY.md "Tail sampling" / "Continuous
# profiling" / "Flight recorder"): tail-based retention policy units +
# cross-process verdict plumbing, the sampling profiler, crash flight
# recorder + DUMP opcode, torn-tail tolerance
prof:
	$(PYTHON) -m pytest tests/ -q -m blackbox -p no:cacheprovider

# training-health plane (docs/OBSERVABILITY.md "Training health"): sentinel
# detector units, the dispatch-bound proof (stats cost 0 extra program
# executions), the NaN-provenance blame pass, the chaos flagship (injected
# NaN -> breach -> blame -> auto-rollback -> bitwise-identical replay)
health:
	$(PYTHON) -m pytest tests/ -q -m health -p no:cacheprovider

# training-fleet telemetry plane (docs/OBSERVABILITY.md "Training-fleet
# telemetry"): detector pure-function units, heartbeat-piggybacked parts,
# PS OP_TELEMETRY exactly-once, merged rank timeline with a corpse lane,
# hot-key boundedness, the chaos-slow flagship
train-obs:
	$(PYTHON) -m pytest tests/ -q -m train_obs -p no:cacheprovider

# persistent AOT program cache (docs/PERFORMANCE.md "Program cache and
# cold start"): key-derivation/hit/miss/reject units, bitwise parity of
# cache-hit vs fresh-compile execution, fused-update dispatch bound on
# hits, ProcReplica restart-warms-from-disk chaos leg, keep-last-N GC
progcache:
	$(PYTHON) -m pytest tests/ -q -m progcache -p no:cacheprovider

# serving suite: compiled engine program bound, SLO scheduler, endpoint
# lifecycle + chaos degradation (docs/SERVING.md)
serve:
	$(PYTHON) -m pytest tests/ -q -m serve -p no:cacheprovider

# autoregressive decode engine (docs/SERVING.md "Autoregressive decode"):
# paged-KV alloc/free/leak units, the two-program compile bound proof,
# continuous-batch join/leave, the streaming wire roundtrip with chaos
# drop/dup and the mid-stream kill, progcache-warm replica
decode:
	$(PYTHON) -m pytest tests/ -q -m decode -p no:cacheprovider

# mesh-sharded serving + elastic autoscale suite on the 8-device CPU mesh:
# tensor-parallel engines, replica groups on mesh slices, quarantine→
# activate joins, drain-then-leave, autoscaler policy/controller
# (docs/SERVING.md "Mesh-sharded serving and elastic autoscaling")
serve_mesh:
	$(PYTHON) -m pytest tests/ -q -m serve_mesh -p no:cacheprovider

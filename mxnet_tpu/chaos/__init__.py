"""Deterministic fault injection for robustness testing (``pytest -m chaos``).

Two injector families, both env-gated so production code paths cost one dict
lookup when chaos is off:

- :mod:`mxnet_tpu.chaos.rpc` — drop / delay / duplicate parameter-server
  RPCs at exact occurrence counts (``MXNET_CHAOS_RPC`` or programmatic
  rules). Hooks live in ``kvstore/ps_client.py``.
- :mod:`mxnet_tpu.chaos.proc` — SIGKILL the current process at named code
  points (``MXNET_CHAOS_KILL``, e.g. the checkpoint writer mid-rename or a
  serve replica's ``serve:pre_reply``), and helpers to run a training
  subprocess and kill it at a chosen step. The serving fleet
  (``serve/fleet.py``) forwards ``MXNET_CHAOS_KILL_REPLICA<i>`` to replica
  *i* as its ``MXNET_CHAOS_KILL``, so one env var SIGKILLs exactly one
  member of a fleet at a named point.
- :mod:`mxnet_tpu.chaos.platform` — hang the guarded platform entry points
  (``MXNET_CHAOS_PLATFORM_HANG``) the way a hung accelerator backend does, so
  every driver's bounded-exit + platform-error-artifact path is testable.
- :mod:`mxnet_tpu.chaos.nan` — poison a named tensor with NaN at a counted
  occurrence of it entering an Executor forward (``MXNET_CHAOS_NAN``), so
  the training-health plane's detection → provenance → auto-rollback chain
  (obs/health.py) is deterministically testable end to end.
- :mod:`mxnet_tpu.chaos.slow` — delay a named rank's step phase at counted
  occurrences (``MXNET_CHAOS_SLOW``), so the training-fleet straggler
  detector (obs/fleetstats.py) is chaos-proven: the flagged rank and the
  blamed phase must match the injection.

Determinism is the point: a chaos test that flakes is worse than no test.
Every injector fires on a counted occurrence of a named event, never on a
timer or a random draw.
"""
from __future__ import annotations

from . import nan, platform, proc, rpc, slow

__all__ = ["rpc", "proc", "platform", "nan", "slow"]

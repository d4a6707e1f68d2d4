#!/usr/bin/env python
"""Distributed job launcher (reference ``tools/launch.py`` analog).

Reference counterpart: dmlc-core's local/ssh/mpi trackers spawning scheduler +
servers + workers (expected path ``tools/launch.py`` per SURVEY.md §3.4; the
reference mount was empty this round). TPU-native redesign: there is no
scheduler process — ``dist_sync`` workers rendezvous through
``jax.distributed`` (Gloo/ICI collectives), and ``dist_async`` workers talk
to one parameter-server process (the native C++ server when built, else the
python twin).

Usage (local launcher, the multi-host ssh/mpi modes delegate to the cluster
scheduler on TPU pods — see docstring bottom):

    python tools/launch.py -n 4 python train.py --kv-store dist_sync
    python tools/launch.py -n 4 -s 1 python train.py --kv-store dist_async

Env contract exported to each worker (reference DMLC vars):
    DMLC_ROLE=worker  DMLC_NUM_WORKER=<n>  DMLC_WORKER_ID=<rank>
    MXNET_COORDINATOR=<host:port>            (dist_sync rendezvous)
    MXNET_PS_ADDR / MXNET_PS_PORT            (dist_async, when -s > 0)

On TPU pods the equivalent of ssh/mpi launch is the platform's own
multi-host runner (each host runs the same program; jax.distributed picks up
the topology), so --launcher ssh/mpi intentionally raises here.

One TPU host is NOT a target for ``-n > 1``: every worker gets the same
environment, so they would all ask for the same chips, and a chip belongs to
one process — the first worker takes it and the rest fail or hang at start-up.
On such a host one process drives all of its chips as a mesh
(``parallel.make_mesh`` + ``ShardedTrainer``); the local launcher is for
CPU workers (``JAX_PLATFORMS=cpu``, what the tests use) and for the
host-side parameter server.
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _start_ps_server(port: int, num_workers: int, elastic: bool = False,
                     async_staleness=None):
    """Prefer the native C++ server; fall back to the python twin. Elastic
    mode needs the python server — the membership/heartbeat opcodes (16-20,
    kvstore/elastic.py) are not in the C++ twin — and so does
    bounded-staleness async mode (the clock/gated-pull opcodes 23-25)."""
    native = os.path.join(_repo_root(), "native", "build", "mxtpu_ps_server")
    env = dict(os.environ)
    if async_staleness is not None:
        env["MXNET_ASYNC_STALENESS"] = str(async_staleness)
    if os.path.exists(native) and not elastic and async_staleness is None:
        cmd = [native, "--port", str(port), "--num-workers", str(num_workers)]
    else:
        cmd = [sys.executable, "-m", "mxnet_tpu.kvstore.ps_server",
               "--port", str(port), "--num-workers", str(num_workers)]
        # the child must import mxnet_tpu regardless of the caller's cwd
        # (the serve ProcReplica idiom)
        env["PYTHONPATH"] = _repo_root() + os.pathsep + env.get(
            "PYTHONPATH", "")
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    deadline = time.time() + 60
    lines = []
    while time.time() < deadline:  # skip warning chatter before the banner
        line = proc.stdout.readline()
        if not line:
            break
        lines.append(line)
        if "listening" in line:
            return proc
    proc.kill()
    raise RuntimeError(f"ps server failed to start: {''.join(lines)!r}")


def launch_local(num_workers: int, num_servers: int, command: list,
                 env_extra=None, elastic: bool = False,
                 async_staleness=None) -> int:
    """Spawn everything on localhost; returns the first nonzero worker rc."""
    base_env = dict(os.environ)
    base_env.update(env_extra or {})
    elastic = elastic or base_env.get("MXNET_ELASTIC", "") not in ("", "0")
    base_env["DMLC_NUM_WORKER"] = str(num_workers)
    base_env["DMLC_NUM_SERVER"] = str(num_servers)
    if elastic:
        # elastic dist_sync (docs/ROBUSTNESS.md "Elastic training") rides
        # the PS wire for membership + generation-scoped reductions: a PS
        # process is required even for sync mode
        base_env["MXNET_ELASTIC"] = "1"
        num_servers = max(1, num_servers)
    if async_staleness is not None:
        # bounded-staleness dist_async (docs/ROBUSTNESS.md "Asynchronous
        # training"): needs the python PS (clock opcodes) — like --elastic
        base_env["MXNET_ASYNC_STALENESS"] = str(int(async_staleness))
        num_servers = max(1, num_servers)

    ps_proc = None
    if num_servers > 0:
        ps_port = _free_port()
        ps_proc = _start_ps_server(ps_port, num_workers, elastic=elastic,
                                   async_staleness=async_staleness)
        base_env["MXNET_PS_ADDR"] = "127.0.0.1"
        base_env["MXNET_PS_PORT"] = str(ps_port)
    else:
        base_env["MXNET_COORDINATOR"] = f"127.0.0.1:{_free_port()}"

    workers = []
    for rank in range(num_workers):
        env = dict(base_env)
        env["DMLC_ROLE"] = "worker"
        env["DMLC_WORKER_ID"] = str(rank)
        workers.append(subprocess.Popen(command, env=env))

    rc = 0
    try:
        for w in workers:
            w.wait()
            rc = rc or w.returncode
    except KeyboardInterrupt:
        for w in workers:
            w.send_signal(signal.SIGINT)
        rc = 130
    finally:
        if ps_proc is not None:
            ps_proc.terminate()
            try:
                ps_proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                ps_proc.kill()
    return rc


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="launch a distributed mxnet_tpu job",
        usage="launch.py [-h] -n NUM_WORKERS [-s NUM_SERVERS] command ...")
    p.add_argument("-n", "--num-workers", type=int, required=True)
    p.add_argument("-s", "--num-servers", type=int, default=0,
                   help="PS processes (dist_async); 0 = collective dist_sync")
    p.add_argument("-e", "--elastic", action="store_true",
                   help="elastic training: PS-backed generation-scoped "
                   "sync, worker heartbeats, survivable barriers "
                   "(docs/ROBUSTNESS.md); implies a python PS process")
    p.add_argument("--async-staleness", type=int, default=None,
                   metavar="N",
                   help="bounded-staleness dist_async: workers more than "
                   "N steps ahead of the fleet's committed-clock floor "
                   "block at pull (docs/ROBUSTNESS.md \"Asynchronous "
                   "training\"); implies a python PS process")
    p.add_argument("--launcher", default="local",
                   choices=["local", "ssh", "mpi", "yarn", "sge"])
    p.add_argument("command", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    if args.launcher != "local":
        raise SystemExit(
            f"--launcher {args.launcher}: on TPU pods use the platform "
            "multi-host runner (every host runs the same program and "
            "jax.distributed discovers the topology); only 'local' spawns "
            "processes from here")
    if not args.command:
        p.error("no command given")
    return launch_local(args.num_workers, args.num_servers, args.command,
                        elastic=args.elastic,
                        async_staleness=args.async_staleness)


if __name__ == "__main__":
    sys.exit(main())

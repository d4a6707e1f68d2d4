"""The readings that ``correct``'s limits are set from, on the chip at the
cell's own size: ``python3 benchmark/controls.py --workload <cell> --seeds
1 2 3 [--seconds s]``. For each seed one JSON line of the runner's
``control()``: what the reference computed in a lower precision reads where
the program should be (and, for a served cell, what a short window of the
program itself reads). The benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import jax

    for seed in args.seeds:
        jax.clear_caches()   # a loaded program keeps its scratch reserved
        run = harness.start(argparse.Namespace(
            workload=args.workload, seed=seed, seconds=args.seconds, trace=0,
            rehearse=args.rehearse))
        runner = importlib.import_module(
            f"benchmark.runners.{run.workload['runner']}")
        print(json.dumps({"cell": run.cell, "seed": seed,
                          **runner.control(run)}), flush=True)


if __name__ == "__main__":
    sys.exit(main())

"""Run one cell of the benchmark once:
``python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

One new process: load, make the weights from the seed, warm up the cell's
own shapes, measure for ``--seconds``, check the timed path's output against
the plain reference, print the contract's one JSON line last, exit.

Everything that belongs to one cell, configuration or per-layer metric is a
file found by the name in ``BENCHMARK.json`` (see ``README.md``); this
module holds no list of them. It exits non-zero, printing no result, unless
jax's backend is ``tpu`` with the chips the cell asks for. ``--rehearse``
(the harness's own flag, for the sandbox and the tests) instead drives the
same code on the CPU at the tiny sizes the files give under ``rehearsal``,
and prints no metric at all: a CPU run never carries a device number.
"""
from __future__ import annotations

import time

T0 = time.monotonic()   # process start, as near as Python can say

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


class Run:
    """What a runner is given: the cell's files, the arguments, the devices,
    and the clocks that ``setup_s`` is made from."""

    def __init__(self, args, manifest):
        self.cell = args.workload
        self.seed, self.seconds = args.seed, args.seconds
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.workload = _load("workloads", f"{self.cell}.json")
        self.config = _load("configs", f"{self.workload['config']}.json")
        self.model = dict(self.config["model"])
        if self.rehearse:   # tiny sizes, same code
            self.model.update(self.config["rehearsal"])
            for section, tiny in self.workload["rehearsal"].items():
                self.workload[section].update(tiny)
        self.traffic = self.workload["traffic"]
        self.manifest = manifest
        self.devices = None          # set once jax is up
        self.programs_built = 0      # compiled or loaded from the cache
        self.reference_s = 0.0       # spent in the reference: not set-up
        self.window_start = self.setup_s = None
        self.program_peak = None     # memory peak before a reference that
        #                              runs after the program (serving)
        self.scratch = os.path.join(HERE, ".out", self.cell)

    def count_program(self, event, _secs, **_kw):
        if event == COMPILE_EVENT:
            self.programs_built += 1

    def open_window(self):
        """Called by the runner as the measured window starts; returns the
        number of programs built so far, for the runner to compare at the
        end (nothing may compile inside the window)."""
        self.window_start = time.monotonic()
        self.setup_s = self.window_start - T0 - self.reference_s
        return self.programs_built

    def program_done(self):
        """Called by a runner whose reference runs after the program's state
        is freed: ``memory_peak_bytes`` is the peak up to here."""
        self.program_peak = _memory_peak(self.devices)

    def log(self, message):
        """A line with the seconds since process start and, once jax is
        up, the fullest device's memory peak so far in GB."""
        peak = _memory_peak(self.devices) / 1e9 if self.devices else 0.0
        print(f"[{time.monotonic() - T0:7.2f}s {peak:5.2f}GB] {message}",
              flush=True)


def _devices(run):
    import jax

    backend = jax.default_backend()
    if run.rehearse:
        if backend != "cpu":
            sys.exit("--rehearse is for the CPU sandbox; run the cell "
                     "itself on the chip")
    elif backend != "tpu":
        sys.exit(f"jax backend is {backend!r}, not 'tpu': the benchmark "
                 "measures the chip or nothing")
    devices = jax.devices()
    if len(devices) < run.workload["chips"]:
        sys.exit(f"cell {run.cell} needs {run.workload['chips']} chip(s), "
                 f"jax found {len(devices)}")
    return devices


def _memory_peak(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _cell_metrics(entries, cell):
    return [m for m in entries if cell in m.get("workloads", [cell])]


def start(args):
    """The Run for these arguments, with jax up: the compile cache placed
    and counted, the devices checked."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    run = Run(args, manifest)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)

    import jax

    import mxnet_tpu  # noqa: F401  (places the compile cache in the checkout)

    # programs that compile in under a second are cached too: every run of
    # every later check is a new process and would build them again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.monitoring.register_event_duration_secs_listener(run.count_program)
    run.devices = _devices(run)
    dev = run.devices[0]
    cache_dir = jax.config.jax_compilation_cache_dir
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    run.log(f"{dev.platform} / {dev.device_kind} x {len(run.devices)}; "
            f"compile cache {cache_dir}: {entries} entries at start")
    return run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    run = start(args)
    runner = importlib.import_module(
        f"benchmark.runners.{run.workload['runner']}")
    result = runner.run(run)
    manifest, dev = run.manifest, run.devices[0]

    run.log(f"set-up {run.setup_s:.2f}s (reference {run.reference_s:.2f}s "
            f"kept apart); programs built in the window: "
            f"{result['programs_in_window']}")
    correct = bool(result["correct"]) and result["programs_in_window"] == 0
    values = dict(result["metrics"], setup_s=run.setup_s)
    metrics = {}
    if run.rehearse:
        pass   # a CPU run carries no device metric
    elif run.trace:
        for m in _cell_metrics(manifest["per_layer"], run.cell):
            spec = _load("layer_metrics", f"{m['name']}.json")
            reader = importlib.import_module(
                f"benchmark.readers.{spec['reader']}")
            value = reader.read(result["observations"], spec.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in _cell_metrics(manifest["end_to_end"], run.cell):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": (run.program_peak if run.program_peak
                                    is not None
                                    else _memory_peak(run.devices))}
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": device}
    if run.trace and not run.rehearse:   # to the contract, or no line at all
        from benchmark import reduce_trace   # here: no line above moves
        device["busy_s"], device["window_s"], line["breakdown"] = (
            reduce_trace.last_line(result["observations"].get("trace"),
                                   run.log))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

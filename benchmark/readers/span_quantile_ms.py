"""The ``args.q`` quantile by rank (no interpolation: ``readers/percentile.py``
takes it) of the durations, in ms, of the program's ``obs.trace`` spans named
``args.span`` that the runner collected over the window. None where the
program recorded no such span."""
from benchmark.readers import percentile


def read(obs, args):
    durations = [s["dur"] * 1e3 for s in obs.get("spans", [])
                 if s["name"] == args["span"]]
    return percentile.read({"durations": durations},
                           {"observation": "durations", "q": args["q"]})

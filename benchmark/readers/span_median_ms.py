"""Median duration, in ms, of the program's ``obs.trace`` spans named
``args.span`` that the runner collected over the window."""
import statistics


def read(obs, args):
    durations = [s["dur"] * 1e3 for s in obs.get("spans", [])
                 if s["name"] == args["span"]]
    return statistics.median(durations) if durations else None

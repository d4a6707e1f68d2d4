"""Median of a list the runner observed: ``args.observation`` names it."""
import statistics


def read(obs, args):
    values = obs.get(args["observation"])
    return statistics.median(values) if values else None

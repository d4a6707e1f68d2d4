"""The ``args.q`` quantile by rank (no interpolation, as the runners take
their tails) of a list the runner observed: ``args.observation`` names it."""
import math


def read(obs, args):
    values = sorted(obs.get(args["observation"]) or [])
    if not values:
        return None
    return values[min(len(values) - 1,
                      math.ceil(args["q"] * len(values)) - 1)]

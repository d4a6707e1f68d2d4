"""100 x the sum of attribute ``args.attr`` over the spans named
``args.span`` / (their count x the observation ``args.capacity``): the
share of a fixed capacity that each span's work filled."""


def read(obs, args):
    spans = [s for s in obs.get("spans", []) if s["name"] == args["span"]]
    if not spans:
        return None
    filled = sum(s["args"][args["attr"]] for s in spans)
    return 100.0 * filled / (len(spans) * obs[args["capacity"]])

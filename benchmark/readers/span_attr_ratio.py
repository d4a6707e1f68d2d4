"""``args.scale`` x attribute ``args.num`` / attribute ``args.den`` over the
spans whose name is in ``args.spans`` and that carry both: the ratio of the
two sums (``args.reduce`` = ``"sum"``) or the median of the spans' own
ratios (``"median"``). ``args.scale`` is a number, or the name of an
observation. None where no span carries the attributes (a program that does
not count them) or the denominator is 0."""
import statistics


def read(obs, args):
    pairs = [(s["args"][args["num"]], s["args"][args["den"]])
             for s in obs.get("spans", []) if s["name"] in args["spans"]
             and args["num"] in s.get("args", {})
             and args["den"] in s.get("args", {})]
    pairs = [(n, d) for n, d in pairs if d > 0]
    if not pairs:
        return None
    scale = args.get("scale", 1.0)
    scale = obs[scale] if isinstance(scale, str) else scale
    if args["reduce"] == "median":
        return scale * statistics.median(n / d for n, d in pairs)
    return scale * sum(n for n, _ in pairs) / sum(d for _, d in pairs)

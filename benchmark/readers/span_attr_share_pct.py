"""100 x the sum of attribute ``args.part`` / the sum of the attributes
``args.whole`` (a list; ``part`` is usually one of them), over the spans
whose name is in ``args.spans`` and that carry every one of them. None where
no span carries them (a program that does not count them) or the whole is
0."""


def read(obs, args):
    rows = [s["args"] for s in obs.get("spans", [])
            if s["name"] in args["spans"]
            and all(a in s.get("args", {}) for a in args["whole"])
            and args["part"] in s.get("args", {})]
    whole = sum(r[a] for r in rows for a in args["whole"])
    if not rows or whole <= 0:
        return None
    return 100.0 * sum(r[args["part"]] for r in rows) / whole

"""Median, in ms, of the self time of the spans named ``args.span``: each
one's duration less the summed durations of the spans named ``args.less``
on the same thread that start inside it. A ``less`` span whose ``span`` was
not recorded (it began before the window) counts nowhere."""
import statistics


def read(obs, args):
    spans = obs.get("spans", [])
    inner = [s for s in spans if s["name"] == args["less"]]
    selves = []
    for outer in (s for s in spans if s["name"] == args["span"]):
        end = outer["ts"] + outer["dur"]
        covered = sum(s["dur"] for s in inner if s["tid"] == outer["tid"]
                      and outer["ts"] <= s["ts"] < end)
        selves.append((outer["dur"] - covered) * 1e3)
    return statistics.median(selves) if selves else None

"""100 x (1 - device busy / traced window), from the reduced device trace."""


def read(obs, args):
    trace = obs.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])

"""Device time of the engine's scan per round, in ms: the scan's whole
runs in the trace over the rounds they hold."""


def read(run):
    t = run.trace
    if not t or t["rounds"] <= 0:
        return None
    return 1e3 * t["engine_s"] / t["rounds"]

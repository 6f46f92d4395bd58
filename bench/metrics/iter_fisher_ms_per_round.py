"""Device time of the Iter-Fisher kernels (compensation and
lambda-statistics) per engine round, in ms."""


def read(run):
    t = run.trace
    if not t or t["rounds"] <= 0 or t["kernel_calls"] == 0:
        return None
    return 1e3 * t["kernel_s"] / t["rounds"]

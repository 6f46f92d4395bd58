"""Device time per engine round, in ms, of the scan's leaf ops under no
``ferret.*`` scope: the scan's carry copies, which XLA adds without
metadata (``other_s`` of ``bench/trace_scopes.py``)."""


def read(run):
    t = run.trace
    if not t or t["rounds"] <= 0 or "other_s" not in t:
        return None
    return 1e3 * t["other_s"] / t["rounds"]

"""Device time of the engine's state per engine round, in ms: the leaf ops
under the program's scopes ``ferret.push``, ``.delta_gather``,
``.compensate``, ``.optimizer`` and ``.delta_ring`` (``state_s`` of
``bench/trace_scopes.py``)."""


def read(run):
    t = run.trace
    if not t or t["rounds"] <= 0 or "state_s" not in t:
        return None
    return 1e3 * t["state_s"] / t["rounds"]

"""Device time of the model per engine round, in ms: the leaf ops under
the program's scopes ``ferret.forward`` and ``ferret.penalty``, forward
and backward (``model_s`` of ``bench/trace_scopes.py``)."""


def read(run):
    t = run.trace
    if not t or t["rounds"] <= 0 or "model_s" not in t:
        return None
    return 1e3 * t["model_s"] / t["rounds"]

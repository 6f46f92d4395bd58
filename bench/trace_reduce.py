"""From a profiler trace of the window to device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX. On each device plane (``/device:TPU:<n>``) the line ``XLA Ops`` holds
one event per executed operation and ``XLA Modules`` one per executed
program; host planes hold the host's own events (dispatch, the harness's
annotations). All share one clock.

The traced window runs from the start of the first whole run of the
engine's scan to the end of the last one, so that it holds only whole
segments (a caller whose trace starts inside a run skips that run).
Within it:

- busy: the union of the operation intervals, per device, averaged;
- engine: the scan's run time, and how many rounds those runs hold;
- kernels: time of the operations the caller marks as kernels;
- device ops: time by operation, counting only ops that hold no other;
- gaps: the longest stretches with no operation on device 0, each named
  by the host event that overlaps it most (the shortest such on a tie).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Callable, Dict, List, Optional, Tuple

Interval = Tuple[float, float]


def latest_xplane(directory: str) -> str:
    files = glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(files, key=os.path.getmtime)


def _events(line, is_kernel: Optional[Callable[[str], bool]] = None) -> List[dict]:
    """Events of a line. An op's name is the whole HLO instruction; it is
    cut to the instruction's own name (and its custom-call target), and
    ``is_kernel`` is asked of the whole of it."""
    out = []
    for e in line.events:
        name = e.name
        ev = {"name": name, "start": float(e.start_ns), "end": float(e.end_ns)}
        if is_kernel is not None:
            ev["kernel"] = is_kernel(name)
            head = name.split(" = ", 1)[0]
            target = re.search(r'custom_call_target="([^"]+)"', name)
            ev["name"] = f"{head} [{target.group(1)}]" if target else head
        out.append(ev)
    return out


def load(path: str, is_kernel: Callable[[str], bool]) -> dict:
    """{"devices": [{"ops": [...], "modules": [...]}], "host": [...]}, times in ns."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if re.match(r"^/device:(TPU|GPU):\d+$", plane.name):
            lines = {line.name: line for line in plane.lines}
            devices.append({
                "name": plane.name,
                "ops": _events(lines["XLA Ops"], is_kernel) if "XLA Ops" in lines else [],
                "modules": _events(lines["XLA Modules"]) if "XLA Modules" in lines else [],
            })
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    devices.sort(key=lambda d: int(d["name"].rsplit(":", 1)[1]))
    return {"devices": devices, "host": host}


def union_length(intervals: List[Interval]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def _clip(events: List[dict], t0: float, t1: float) -> List[Interval]:
    return [(max(e["start"], t0), min(e["end"], t1)) for e in events
            if e["end"] > t0 and e["start"] < t1]


def _leaves(events: List[dict]) -> List[dict]:
    """The ops that hold no other op (a loop or a conditional holds the
    ops it runs, on the same line)."""
    evs = sorted(events, key=lambda e: (e["start"], -e["end"]))
    return [e for e, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt["start"] >= e["end"]]


def _gaps(ops: List[Interval], t0: float, t1: float) -> List[Interval]:
    out, end = [], t0
    for a, b in sorted(ops):
        if a > end:
            out.append((end, a))
        end = max(end, b)
    if t1 > end:
        out.append((end, t1))
    return out


def _name_gap(gap: Interval, host: List[dict]) -> str:
    best, key = "no host event", (0.0, 0.0)
    for e in host:
        ov = min(gap[1], e["end"]) - max(gap[0], e["start"])
        if ov <= 0:
            continue
        k = (ov, -(e["end"] - e["start"]))
        if k > key:
            best, key = e["name"], k
    return best


def summarize(
    trace: dict, is_engine: Callable[[str], bool], rounds_per_run: int,
    skip_runs: int = 0, top: int = 10,
) -> Optional[dict]:
    """Device numbers of the traced window, or None when the trace holds
    no whole run of the engine."""
    devs = trace["devices"]
    if not devs:
        return None
    engine = sorted((m for m in devs[0]["modules"] if is_engine(m["name"])),
                    key=lambda m: m["start"])[skip_runs:]
    if not engine:
        return None
    t0 = min(m["start"] for m in engine)
    t1 = max(m["end"] for m in engine)
    window = t1 - t0
    busy = [union_length(_clip(d["ops"], t0, t1)) for d in devs]
    ops0 = [e for e in devs[0]["ops"] if e["end"] > t0 and e["start"] < t1]
    by_name: Dict[str, float] = {}
    for e in _leaves(ops0):
        by_name[e["name"]] = by_name.get(e["name"], 0.0) + (e["end"] - e["start"])
    kernels = [e for e in ops0 if e["kernel"]]
    gaps = sorted(_gaps(_clip(ops0, t0, t1), t0, t1), key=lambda g: g[0] - g[1])[:top]
    ns = 1e-9
    return {
        "window_s": window * ns,
        "busy_s": sum(busy) / len(busy) * ns,
        "engine_s": sum(m["end"] - m["start"] for m in engine) * ns,
        "engine_runs": len(engine),
        "rounds": len(engine) * rounds_per_run,
        "kernel_s": sum(e["end"] - e["start"] for e in kernels) * ns,
        "kernel_calls": len(kernels),
        "device_ops": [[n, t * ns] for n, t in
                       sorted(by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_name_gap(g, trace["host"]), (g[1] - g[0]) * ns] for g in gaps],
    }

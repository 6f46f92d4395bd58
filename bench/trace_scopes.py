"""Where the engine's device time goes, by the program's own names.

    python3 bench/trace_scopes.py <trace dir | .xplane.pb> [--rounds-per-run 32] [--skip-runs 0]

The program names its layers (``src/repro/core/spans.py``): the engine
round's ops carry ``jax.named_scope`` paths (``ferret.forward``,
``ferret.push``, ...), and the segment loops open ``ferret.*`` host spans
on the profiler's clock. ``jax.profiler.ProfileData`` does not expose an
op's path, so this module reads the ``.xplane.pb`` itself with a plain
protobuf wire reader (field numbers of ``tsl/profiler/protobuf/xplane.proto``):
on a device plane each ``XLA Ops`` event's metadata holds the op's
``tf_op`` stat, its framework path; a fusion carries its root
instruction's path.

Over the same traced window as ``trace_reduce.summarize`` (the engine's
whole runs, less ``skip_runs`` at the head), it gives:

- ``scopes``: device time of the engine's leaf ops by ``ferret.*`` scope,
  the backward apart (``forward.bwd``: the forward's scope under
  ``transpose(jvp(...))``); ``other`` for ops under none;
- ``model_s`` (forward and penalty, both ways), ``state_s`` (push, Δθ
  gather, compensation, optimizer, Δθ ring) and ``other_s``, and the
  ``rounds`` they hold;
- ``kernels``: time and calls by Pallas kernel name;
- ``boundary_s``: from the end of segment k's ``ferret.fetch`` to the end
  of segment k+1's ``ferret.dispatch``, per boundary in the window;
- ``switch_stall_s``: for each ``ferret.replan`` in the window, from the
  end of the engine run before it to the start of the next;
- ``gaps``: each stretch of 1 ms or more with no op on device 0, with the
  ``ferret.*`` host spans overlapping it.

With ``--trace 1``, ``bench/run.py`` puts ``scopes``, ``model_s``,
``state_s``, ``other_s``, ``kernels``, ``boundary_s`` and
``switch_stall_s`` on the run's trace, where a metric's reader finds a
scope or a kernel by its name.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import struct
import sys
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import trace_reduce

# tsl/profiler/protobuf/xplane.proto
XSPACE_PLANES = 1
XPLANE_NAME, XPLANE_LINES, XPLANE_EVENT_METADATA, XPLANE_STAT_METADATA = 2, 3, 4, 5
XLINE_NAME, XLINE_TIMESTAMP_NS, XLINE_EVENTS = 2, 3, 4
XEVENT_METADATA_ID, XEVENT_OFFSET_PS, XEVENT_DURATION_PS, XEVENT_STATS = 1, 2, 3, 4
XSTAT_METADATA_ID = 1
XSTAT_VALUES = {2: "double", 3: "uint64", 4: "int64", 5: "str", 6: "bytes", 7: "ref"}
XEVENT_METADATA_NAME, XEVENT_METADATA_STATS = 2, 5
XSTAT_METADATA_NAME = 2
MAP_KEY, MAP_VALUE = 1, 2

SCOPE = re.compile(r"(transpose\()?(?:jvp\()?ferret\.([a-z_]+)")
MODEL = ("forward", "forward.bwd", "penalty", "penalty.bwd")
STATE = ("push", "delta_gather", "compensate", "optimizer", "delta_ring")
KERNEL = re.compile(r'kernel_metadata=\{\s*"kernel"\s*:\s*"([^"]+)"')
GAP_MIN_NS = 1e6


# ---------------------------------------------------------------------------
# protobuf wire format
# ---------------------------------------------------------------------------


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, start: int = 0, end: Optional[int] = None) -> Iterator[tuple]:
    """(field number, value) of one message; a length-delimited value is
    its (start, end) in ``buf``, a fixed64 its 8 raw bytes."""
    i, end = start, len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, value


def _str(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _stat(buf: bytes, span, stat_names: Dict[int, str]) -> Tuple[str, object]:
    name, value = None, None
    for f, v in _fields(buf, *span):
        if f == XSTAT_METADATA_ID:
            name = stat_names.get(v, str(v))
        elif f in XSTAT_VALUES:
            kind = XSTAT_VALUES[f]
            if kind == "double":
                value = struct.unpack("<d", v)[0]
            elif kind in ("str", "bytes"):
                value = _str(buf, v)
            elif kind == "ref":
                value = stat_names.get(v, str(v))
            elif kind == "int64" and v >= 1 << 63:
                value = v - (1 << 64)
            else:
                value = v
    return name, value


def _map_entry(buf: bytes, span) -> Tuple[int, Optional[Tuple[int, int]]]:
    key, value = 0, None
    for f, v in _fields(buf, *span):
        if f == MAP_KEY:
            key = v
        elif f == MAP_VALUE:
            value = v
    return key, value


def _plane(buf: bytes, span, want_line: Callable[[str], bool],
           want_event: Callable[[str], bool]) -> dict:
    name, lines, ev_meta, stat_meta = "", [], [], []
    for f, v in _fields(buf, *span):
        if f == XPLANE_NAME:
            name = _str(buf, v)
        elif f == XPLANE_LINES:
            lines.append(v)
        elif f == XPLANE_EVENT_METADATA:
            ev_meta.append(v)
        elif f == XPLANE_STAT_METADATA:
            stat_meta.append(v)
    stat_names: Dict[int, str] = {}
    for entry in stat_meta:
        key, value = _map_entry(buf, entry)
        for f, v in _fields(buf, *value) if value else ():
            if f == XSTAT_METADATA_NAME:
                stat_names[key] = _str(buf, v)
    meta: Dict[int, dict] = {}
    for entry in ev_meta:
        key, value = _map_entry(buf, entry)
        if value is None:
            continue
        md = {"name": "", "stats": {}}
        for f, v in _fields(buf, *value):
            if f == XEVENT_METADATA_NAME:
                md["name"] = _str(buf, v)
            elif f == XEVENT_METADATA_STATS:
                k, sv = _stat(buf, v, stat_names)
                md["stats"][k] = sv
        if want_event(md["name"]):
            meta[key] = md
    out_lines = []
    for span_ in lines:
        lname, ts, events = "", 0, []
        for f, v in _fields(buf, *span_):
            if f == XLINE_NAME:
                lname = _str(buf, v)
            elif f == XLINE_TIMESTAMP_NS:
                ts = v
            elif f == XLINE_EVENTS:
                events.append(v)
        if not want_line(lname):
            continue
        evs = []
        for ev in events:
            mid = off = dur = 0
            stats = []
            for f, v in _fields(buf, *ev):
                if f == XEVENT_METADATA_ID:
                    mid = v
                elif f == XEVENT_OFFSET_PS:
                    off = v
                elif f == XEVENT_DURATION_PS:
                    dur = v
                elif f == XEVENT_STATS:
                    stats.append(v)
            md = meta.get(mid)
            if md is None:
                continue
            # whole ns, as jax.profiler.ProfileData gives them
            start = float(ts + off // 1000)
            e = {"name": md["name"], "start": start, "end": start + dur // 1000,
                 "meta": md["stats"]}
            if stats:
                e["stats"] = dict(_stat(buf, s, stat_names) for s in stats)
            evs.append(e)
        out_lines.append({"name": lname, "events": evs})
    return {"name": name, "lines": out_lines}


def read(path: str) -> dict:
    """{"devices": [{"name", "ops", "modules"}], "host": [...]}: device 0
    first; an op keeps its ``tf_op`` path and its HLO text; host events
    are the ``ferret.*`` spans only. Times in ns, on the trace's clock."""
    with open(path, "rb") as f:
        buf = f.read()
    devices, host = [], []
    for field, span in _fields(buf):
        if field != XSPACE_PLANES:
            continue
        name = ""
        for f, v in _fields(buf, *span):
            if f == XPLANE_NAME:
                name = _str(buf, v)
                break
        if re.match(r"^/device:(TPU|GPU):\d+$", name):
            plane = _plane(buf, span, lambda ln: ln in ("XLA Ops", "XLA Modules"),
                           lambda n: True)
            lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
            ops = [{"name": e["name"], "start": e["start"], "end": e["end"],
                    "tf_op": str(e["meta"].get("tf_op", ""))}
                   for e in lines.get("XLA Ops", [])]
            modules = [{"name": e["name"], "start": e["start"], "end": e["end"]}
                       for e in lines.get("XLA Modules", [])]
            devices.append({"name": name, "ops": ops, "modules": modules})
        elif name.startswith("/host:"):
            plane = _plane(buf, span, lambda ln: True, lambda n: n.startswith("ferret."))
            for ln in plane["lines"]:
                for e in ln["events"]:
                    host.append({"name": e["name"], "start": e["start"], "end": e["end"],
                                 "step": (e.get("stats") or {}).get("step_num"),
                                 "thread": ln["name"]})
    devices.sort(key=lambda d: int(d["name"].rsplit(":", 1)[1]))
    host.sort(key=lambda e: e["start"])
    return {"devices": devices, "host": host}


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------


def scope_of(tf_op: str) -> str:
    """The op's ``ferret.*`` scope (``forward.bwd`` for the backward of
    the forward), or ``other``."""
    m = SCOPE.search(tf_op)
    if m is None:
        return "other"
    return m.group(2) + (".bwd" if m.group(1) else "")


def kernel_of(hlo: str) -> Optional[str]:
    m = KERNEL.search(hlo)
    return m.group(1) if m else None


def _spans(host: List[dict], name: str, t0: float, t1: float) -> List[dict]:
    return [e for e in host if e["name"] == name and e["end"] > t0 and e["start"] < t1]


def summarize(trace: dict, is_engine: Callable[[str], bool], rounds_per_run: int,
              skip_runs: int = 0) -> Optional[dict]:
    devs = trace["devices"]
    if not devs:
        return None
    runs = sorted((m for m in devs[0]["modules"] if is_engine(m["name"])),
                  key=lambda m: m["start"])
    engine = runs[skip_runs:]
    if not engine:
        return None
    t0, t1 = engine[0]["start"], max(m["end"] for m in engine)
    ops0 = [e for e in devs[0]["ops"] if e["end"] > t0 and e["start"] < t1]
    in_engine = [e for e in trace_reduce._leaves(ops0)
                 if any(m["start"] <= e["start"] and e["end"] <= m["end"] for m in engine)]
    ns = 1e-9
    by_scope: Dict[str, float] = {}
    kernels: Dict[str, List[float]] = {}
    for e in in_engine:
        d = e["end"] - e["start"]
        key = scope_of(e["tf_op"])
        by_scope[key] = by_scope.get(key, 0.0) + d
        k = kernel_of(e["name"])
        if k is not None:
            kernels.setdefault(k, [0.0, 0])
            kernels[k][0] += d * ns
            kernels[k][1] += 1
    host = trace["host"]
    fetches = _spans(host, "ferret.fetch", t0, t1)
    dispatches = [e for e in host if e["name"] == "ferret.dispatch"]
    boundary = []
    for f in fetches:
        nxt = [d for d in dispatches if d["start"] >= f["end"]]
        if nxt and nxt[0]["end"] <= t1:
            boundary.append((nxt[0]["end"] - f["end"]) * ns)
    stalls = []
    for r in _spans(host, "ferret.replan", t0, t1):
        before = [m for m in runs if m["end"] <= r["start"]]
        after = [m for m in runs if m["start"] >= r["end"]]
        if before and after:
            stalls.append((after[0]["start"] - before[-1]["end"]) * ns)
    gaps = []
    for a, b in trace_reduce._gaps(trace_reduce._clip(ops0, t0, t1), t0, t1):
        if b - a < GAP_MIN_NS:
            continue
        over = {}
        for e in host:
            ov = min(b, e["end"]) - max(a, e["start"])
            if ov > 0 and e["name"] != "ferret.segment":
                over[e["name"]] = over.get(e["name"], 0.0) + ov * ns
        gaps.append({"at_s": (a - t0) * ns, "length_s": (b - a) * ns,
                     "spans": sorted(([n, s] for n, s in over.items()), key=lambda x: -x[1])})
    total = lambda keys: sum(by_scope.get(k, 0.0) for k in keys) * ns  # noqa: E731
    return {
        "rounds": len(engine) * rounds_per_run,
        "engine_s": sum(m["end"] - m["start"] for m in engine) * ns,
        "engine_leaf_s": sum(by_scope.values()) * ns,
        "scopes": sorted(([k, v * ns] for k, v in by_scope.items()), key=lambda x: -x[1]),
        "model_s": total(MODEL),
        "state_s": total(STATE),
        "other_s": total(("other",)),
        "kernels": sorted(([k, s, n] for k, (s, n) in kernels.items()), key=lambda x: -x[1]),
        "boundary_s": boundary,
        "switch_stall_s": stalls,
        "gaps": gaps,
        "host_s": {n: sum(e["end"] - e["start"] for e in _spans(host, n, t0, t1)) * ns
                   for n in sorted({e["name"] for e in host})},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace")
    ap.add_argument("--rounds-per-run", type=int, default=32)
    ap.add_argument("--skip-runs", type=int, default=0)
    args = ap.parse_args(argv)
    path = args.trace
    if os.path.isdir(path):
        path = trace_reduce.latest_xplane(path)
    import drive

    s = summarize(read(path), drive.is_engine_module, args.rounds_per_run, args.skip_runs)
    print(json.dumps(s))
    return 0 if s is not None else 1


if __name__ == "__main__":
    sys.exit(main())

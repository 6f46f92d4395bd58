"""The reader of the program's names in a profiler trace."""

from __future__ import annotations

import gzip
import importlib.util
from pathlib import Path

import pytest

import drive
import trace_reduce as tr
import trace_scopes as ts

DATA = Path(__file__).parent / "data"
SUMMARY_KEYS = {"window_s", "busy_s", "engine_s", "engine_runs", "rounds", "kernel_s",
                "kernel_calls", "device_ops", "idle_gaps"}


def _unpack(tmp_path, name: str) -> str:
    path = tmp_path / name.replace(".gz", "")
    path.write_bytes(gzip.decompress((DATA / name).read_bytes()))
    return str(path)


def _xplane_pb2():
    """The installed ``xplane.proto`` module, loaded from its file (its
    package, TensorFlow, is not imported), or None."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        return None
    path = Path(list(spec.submodule_search_locations)[0]) / "tsl" / "profiler" / "protobuf" \
        / "xplane_pb2.py"
    if not path.exists():
        return None
    mspec = importlib.util.spec_from_file_location("xplane_pb2_for_test", path)
    mod = importlib.util.module_from_spec(mspec)
    mspec.loader.exec_module(mod)
    return mod


def test_field_numbers_are_the_installed_protos():
    pb = _xplane_pb2()
    if pb is None:
        pytest.skip("no xplane.proto module installed")
    num = {m: {f.name: f.number for f in getattr(pb, m).DESCRIPTOR.fields}
           for m in ("XSpace", "XPlane", "XLine", "XEvent", "XStat", "XEventMetadata",
                     "XStatMetadata")}
    assert num["XSpace"]["planes"] == ts.XSPACE_PLANES
    assert (num["XPlane"]["name"], num["XPlane"]["lines"], num["XPlane"]["event_metadata"],
            num["XPlane"]["stat_metadata"]) == (ts.XPLANE_NAME, ts.XPLANE_LINES,
                                                ts.XPLANE_EVENT_METADATA, ts.XPLANE_STAT_METADATA)
    assert (num["XLine"]["name"], num["XLine"]["timestamp_ns"], num["XLine"]["events"]) == \
        (ts.XLINE_NAME, ts.XLINE_TIMESTAMP_NS, ts.XLINE_EVENTS)
    assert (num["XEvent"]["metadata_id"], num["XEvent"]["offset_ps"],
            num["XEvent"]["duration_ps"], num["XEvent"]["stats"]) == \
        (ts.XEVENT_METADATA_ID, ts.XEVENT_OFFSET_PS, ts.XEVENT_DURATION_PS, ts.XEVENT_STATS)
    assert num["XStat"]["metadata_id"] == ts.XSTAT_METADATA_ID
    assert {num["XStat"][f"{k}_value"]: k for k in ts.XSTAT_VALUES.values()} == ts.XSTAT_VALUES
    assert (num["XEventMetadata"]["name"], num["XEventMetadata"]["stats"]) == \
        (ts.XEVENT_METADATA_NAME, ts.XEVENT_METADATA_STATS)
    assert num["XStatMetadata"]["name"] == ts.XSTAT_METADATA_NAME


def test_a_hand_made_space(tmp_path):
    """An op whose path is a string stat and one whose path is a reference
    to a stat name; a host span with its step; events of other names and
    lines left out."""
    pb = _xplane_pb2()
    if pb is None:
        pytest.skip("no xplane.proto module installed")
    space = pb.XSpace()
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[1].name = "tf_op"
    dev.stat_metadata[2].name = "jit(_scan)/while/body/ferret.push/add:"
    for mid, name in ((10, "%fusion.1 = f32[] add()"), (11, "%copy.2 = f32[] copy()"),
                      (12, "jit__scan(1)")):
        dev.event_metadata[mid].name = name
    dev.event_metadata[10].stats.add(metadata_id=1, str_value="jit(_scan)/ferret.optimizer/mul:")
    dev.event_metadata[11].stats.add(metadata_id=1, ref_value=2)
    ops = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    ops.events.add(metadata_id=10, offset_ps=2_000_000, duration_ps=3_500)
    ops.events.add(metadata_id=11, offset_ps=9_000_000, duration_ps=1_000_000)
    dev.lines.add(name="XLA Modules", timestamp_ns=1000).events.add(
        metadata_id=12, offset_ps=1_000_000, duration_ps=10_000_000)
    dev.lines.add(name="Steps", timestamp_ns=0).events.add(metadata_id=12, offset_ps=0)
    host = space.planes.add(name="/host:CPU")
    host.stat_metadata[1].name = "step_num"
    host.event_metadata[1].name = "ferret.segment"
    host.event_metadata[2].name = "$threading.py:1018 _bootstrap"
    line = host.lines.add(name="python3", timestamp_ns=500)
    line.events.add(metadata_id=1, offset_ps=100_000, duration_ps=7_000_000).stats.add(
        metadata_id=1, int64_value=3)
    line.events.add(metadata_id=2, offset_ps=0, duration_ps=9_000_000)
    path = tmp_path / "space.xplane.pb"
    path.write_bytes(space.SerializeToString())

    got = ts.read(str(path))
    (d,) = got["devices"]
    assert [(o["name"], o["start"], o["end"], o["tf_op"]) for o in d["ops"]] == [
        ("%fusion.1 = f32[] add()", 3000.0, 3003.0, "jit(_scan)/ferret.optimizer/mul:"),
        ("%copy.2 = f32[] copy()", 10000.0, 11000.0, "jit(_scan)/while/body/ferret.push/add:"),
    ]
    assert [(m["name"], m["start"], m["end"]) for m in d["modules"]] == [
        ("jit__scan(1)", 2000.0, 12000.0)]
    assert [(h["name"], h["start"], h["end"], h["step"]) for h in got["host"]] == [
        ("ferret.segment", 600.0, 7600.0, 3)]
    s = ts.summarize(got, drive.is_engine_module, 4)
    assert s["rounds"] == 4
    assert dict(s["scopes"]) == pytest.approx({"push": 1000e-9, "optimizer": 3e-9})
    assert (s["model_s"], s["other_s"]) == (0.0, 0.0)
    assert s["state_s"] == pytest.approx(1003e-9)


@pytest.mark.parametrize("path, scope", [
    ("jit(_scan)/while/body/closed_call/cond/branch_1_fun/jvp(ferret.forward)/dot_general:",
     "forward"),
    ("jit(_scan)/while/body/transpose(jvp(ferret.forward))/dot_general:", "forward.bwd"),
    ("jit(_scan)/while/body/cond/branch_1_fun/ferret.compensate/iter_fisher_stats/x:",
     "compensate"),
    ("jit(_scan)/while/body/closed_call/cond/branch_1_fun/pallas_call:", "other"),
    ("", "other"),
])
def test_scope_of_a_path(path, scope):
    assert ts.scope_of(path) == scope


def test_the_recorded_chip_trace(tmp_path):
    """The trace that ``test_trace_reduce`` reads (recorded before the
    program named its layers): the reader gives the paths the profiler
    recorded, on ProfileData's clock, and ``trace_reduce`` reads it as
    before."""
    path = _unpack(tmp_path, "small.xplane.pb.gz")
    got = ts.read(path)
    ref = tr.load(path, drive.is_kernel_op)
    for key in ("ops", "modules"):
        assert [(e["start"], e["end"]) for e in got["devices"][0][key]] == \
            [(e["start"], e["end"]) for e in ref["devices"][0][key]]
    paths = {e["name"].split(" = ", 1)[0]: e["tf_op"] for e in got["devices"][0]["ops"]}
    assert paths["%branch_1_fun.4"] == \
        "jit(_scan)/while/body/closed_call/cond/branch_1_fun/cond/branch_1_fun/pallas_call:"
    assert paths["%pad.4"] == ("jit(_scan)/while/body/closed_call/cond/branch_1_fun/cond/"
                               "branch_1_fun/dynamic_update_slice:")
    s = tr.summarize(ref, drive.is_engine_module, 8, skip_runs=1)
    assert set(s) == SUMMARY_KEYS
    scoped = ts.summarize(got, drive.is_engine_module, 8, skip_runs=1)
    assert scoped["engine_s"] == pytest.approx(s["engine_s"], rel=1e-12)
    assert scoped["scopes"] == [["other", scoped["engine_leaf_s"]]]


def test_the_scoped_chip_trace(tmp_path):
    """A trace recorded on one TPU v5e by ``record_trace.py`` once the
    program named its layers: the engine at d_model 256, 2 layers, P=2,
    8-round segments, the trace begun inside the run of segment 1."""
    path = _unpack(tmp_path, "small_scoped.xplane.pb.gz")
    got = ts.read(path)
    ref = tr.load(path, drive.is_kernel_op)
    s = ts.summarize(got, drive.is_engine_module, 8, skip_runs=1)
    base = tr.summarize(ref, drive.is_engine_module, 8, skip_runs=1)
    assert set(base) == SUMMARY_KEYS and base["engine_runs"] == 1
    # model + state + other is the engine run's leaf-op time, as read by
    # ProfileData, and all but a few percent of the run
    (run,) = [m for m in ref["devices"][0]["modules"] if drive.is_engine_module(m["name"])][1:]
    ops = [e for e in ref["devices"][0]["ops"]
           if e["end"] > run["start"] and e["start"] < run["end"]]
    leaf_s = 1e-9 * sum(e["end"] - e["start"] for e in tr._leaves(ops))
    assert s["model_s"] + s["state_s"] + s["other_s"] == pytest.approx(leaf_s, rel=1e-9)
    assert s["engine_s"] == pytest.approx(base["engine_s"], rel=1e-12)
    assert leaf_s > 0.95 * s["engine_s"]
    assert {k for k, _ in s["scopes"]} == {"forward", "forward.bwd", "push", "delta_gather",
                                           "compensate", "optimizer", "delta_ring", "other"}
    # the kernels by name: each of 2 stages, every round
    kernels = {k: (t, n) for k, t, n in s["kernels"]}
    assert {k: n for k, (_, n) in kernels.items()} == {"iter_fisher_compensate": 16,
                                                       "iter_fisher_stats": 16}
    assert sum(t for t, _ in kernels.values()) == pytest.approx(base["kernel_s"], rel=1e-9)
    assert base["device_ops"][0][0] == "%iter_fisher_stats.2 [tpu_custom_call]"
    # the host spans of the segment the window holds, in order, on the
    # device's clock: the run starts after its dispatch and ends before
    # its fetch does
    seg = [h for h in got["host"] if h["name"] == "ferret.segment" and h["step"] == 2]
    (seg,) = seg
    inner = [h["name"] for h in got["host"]
             if seg["start"] <= h["start"] and h["end"] <= seg["end"]
             and h["name"] not in ("ferret.segment", "ferret.feeder.wait", "ferret.feeder.prepare")]
    assert inner == ["ferret.take", "ferret.schedule", "ferret.upload", "ferret.dispatch",
                     "ferret.fetch"]
    (dispatch,) = [h for h in got["host"] if h["name"] == "ferret.dispatch"
                   and seg["start"] <= h["start"] <= seg["end"]]
    (fetch,) = [h for h in got["host"] if h["name"] == "ferret.fetch"
                and seg["start"] <= h["start"] <= seg["end"]]
    assert dispatch["start"] < run["start"] and run["end"] <= fetch["end"]
    # the boundary from segment 1's results to segment 2's dispatch lies
    # before the window, inside the run the window skips
    assert s["boundary_s"] == []
    whole = ts.summarize(got, drive.is_engine_module, 8, skip_runs=0)
    (prev_fetch,) = [h for h in got["host"] if h["name"] == "ferret.fetch"
                     and h["end"] < seg["start"]]
    assert whole["boundary_s"] == [pytest.approx(1e-9 * (dispatch["end"] - prev_fetch["end"]))]

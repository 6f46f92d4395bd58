"""The reduction from a profiler trace to device numbers."""

from __future__ import annotations

import pytest

import trace_reduce as tr


def _ev(name, start, end, kernel=False):
    return {"name": name, "start": float(start), "end": float(end), "kernel": kernel}


def test_union_and_window_on_a_hand_made_trace():
    # two engine runs [100, 200] and [250, 400] ns; ops inside, one kernel
    trace = {
        "devices": [{
            "name": "/device:TPU:0",
            "modules": [_ev("jit__scan(1)", 100, 200), _ev("jit__scan(1)", 250, 400),
                        _ev("jit_other", 0, 50)],
            "ops": [_ev("while.1", 100, 190), _ev("fusion.1", 100, 150),
                    _ev("fusion.2", 150, 190), _ev("f.3", 260, 300, kernel=True),
                    _ev("fusion.1", 300, 390), _ev("copy", 10, 40)],
        }],
        "host": [_ev("segment boundary", 180, 270), _ev("process", 0, 1000)],
    }
    s = tr.summarize(trace, lambda n: n.startswith("jit__scan"), 32)
    ns = 1e-9
    assert s["window_s"] == 300 * ns
    assert s["busy_s"] == (90 + 40 + 90) * ns
    assert s["engine_runs"] == 2 and s["rounds"] == 64
    assert s["engine_s"] == 250 * ns
    assert s["kernel_calls"] == 1 and s["kernel_s"] == 40 * ns
    # the loop that holds fusion.1 and fusion.2 is not an op of its own
    assert s["device_ops"][0] == ["fusion.1", 140 * ns]
    assert "while.1" not in [n for n, _ in s["device_ops"]]
    # the longest gap, 190..260, is named by the host event covering it
    assert s["idle_gaps"][0] == ["segment boundary", 70 * ns]


def test_no_engine_run_reads_nothing():
    trace = {"devices": [{"name": "/device:TPU:0", "modules": [], "ops": []}], "host": []}
    assert tr.summarize(trace, lambda n: True, 32) is None
    assert tr.summarize({"devices": [], "host": []}, lambda n: True, 32) is None


def test_union_length():
    assert tr.union_length([(0, 10), (5, 15), (20, 30), (25, 26)]) == 25
    assert tr.union_length([]) == 0


def test_a_recorded_chip_trace(tmp_path):
    """A trace recorded on one TPU v5e: the engine at a small width
    (d_model 256, 2 layers, P=2), 8-round segments, the trace begun inside
    a run (so the first is skipped). It was recorded before the program
    named its kernels: its Mosaic kernels carry no name, so none counts
    as an Iter-Fisher kernel, and read as every ``tpu_custom_call`` they
    are the compensation and lambda-statistics kernels of each stage."""
    import gzip
    from pathlib import Path

    import drive

    raw = gzip.decompress((Path(__file__).parent / "data" / "small.xplane.pb.gz").read_bytes())
    path = tmp_path / "small.xplane.pb"
    path.write_bytes(raw)
    s = tr.summarize(tr.load(str(path), drive.is_kernel_op), drive.is_engine_module, 8,
                     skip_runs=1)
    assert s["engine_runs"] == 1 and s["rounds"] == 8
    assert s["kernel_calls"] == 0 and s["kernel_s"] == 0
    assert s["window_s"] == pytest.approx(0.004587297, rel=1e-6)
    assert s["busy_s"] == pytest.approx(0.004579986, rel=1e-6)
    assert s["device_ops"][0][0].endswith("[tpu_custom_call]")
    mosaic = tr.summarize(tr.load(str(path), lambda hlo: "tpu_custom_call" in hlo),
                          drive.is_engine_module, 8, skip_runs=1)
    # compensation and lambda-statistics kernels, for each of 2 stages, every round
    assert mosaic["kernel_calls"] == 2 * 2 * 8
    assert mosaic["kernel_s"] == pytest.approx(0.000760428, rel=1e-6)


@pytest.mark.parametrize("metadata, iter_fisher", [
    ('kernel_metadata={\n"kernel":"iter_fisher_stats"\n}', True),
    ('kernel_metadata={"kernel": "iter_fisher_compensate"}', True),
    ('kernel_metadata={\n"kernel":"flash_attention_fwd"\n}', False),
    ("kernel_metadata={}", False),
])
def test_kernels_by_name(metadata, iter_fisher):
    """Only a kernel the program names ``iter_fisher_*`` is Iter-Fisher's:
    a kernel of another name has a roofline of its own to read."""
    import drive

    hlo = ('%iter_fisher_stats.2 = (f32[8,128]{1,0}) custom-call(f32[8,128]{1,0} %p), '
           f'custom_call_target="tpu_custom_call", {metadata}')
    assert drive.is_kernel_op(hlo) is iter_fisher

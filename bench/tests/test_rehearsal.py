"""A whole run of the harness on the CPU at a small size, with the look
for a chip skipped: cells, configurations, blocks, traffic and metrics
that exist only as new files are found by name, a sound run is correct,
and a run whose timed path is broken underneath is not."""

from __future__ import annotations

import filecmp
import gzip
import json
from pathlib import Path

import pytest

import run as run_lib
import trace_reduce
from conftest import BENCH, PEAKS, ROOT, cpu_devices


def run_cell(capsys, root, workload, *, seed=11, seconds=1, trace=0) -> dict:
    rc = run_lib.main(["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
                       "--trace", str(trace)], root=root, devices_for=cpu_devices, peaks=PEAKS)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_new_files_are_found_by_name(smoke_root, capsys):
    out = run_cell(capsys, smoke_root, "smoke.stream", seed=3_000_000_019)
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"stream_tokens_per_s", "peak_hbm_gib", "setup_s"}
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                             "memory_peak_bytes": 1 << 20}
    assert list(out)[-1] == "checks"
    traced = run_cell(capsys, smoke_root, "smoke.stream", trace=1)
    assert traced["metrics"]["window_rounds"]["value"] > 0
    assert "stream_tokens_per_s" not in traced["metrics"]


def test_a_block_added_as_files(smoke_root, capsys, monkeypatch, tmp_path):
    """The routed block (top-2 of 4 experts, 3 windowed layers to 1 full,
    head_dim 32 at d_model 64) runs to ``correct`` from files and entries
    added to the checkout, every file of the benchmark left as it was.
    Traced, the metric added with it reads the kernels by name. A CPU's
    trace holds no device, so the trace recorded on one TPU v5e (the
    engine in 8-round segments, as here) stands in for the profiler's."""
    for path in BENCH.rglob("*"):
        rel = path.relative_to(BENCH)
        if path.is_file() and rel.parts[0] != "tests" and "__pycache__" not in rel.parts:
            assert filecmp.cmp(path, smoke_root / "bench" / rel, shallow=False), rel
    before = json.loads((ROOT / "BENCHMARK.json").read_text())
    after = json.loads((smoke_root / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads", "end_to_end"):
        assert after[key][:len(before[key])] == before[key]
    for old, new in zip(before["per_layer"], after["per_layer"]):
        assert dict(new, workloads=old["workloads"]) == old
        assert new["workloads"][:len(old["workloads"])] == old["workloads"]

    out = run_cell(capsys, smoke_root, "routed.stream", seed=2_900_000_015)
    assert out["correct"] is True, out["checks"]
    assert set(out["checks"]) == {"loss0_gap", "loss_gap", "plan_departures"}
    assert set(out["metrics"]) == {"stream_tokens_per_s", "peak_hbm_gib", "setup_s"}

    recorded = tmp_path / "small_scoped.xplane.pb"
    recorded.write_bytes(gzip.decompress(
        (Path(__file__).parent / "data" / "small_scoped.xplane.pb.gz").read_bytes()))
    monkeypatch.setattr(trace_reduce, "latest_xplane", lambda directory: str(recorded))
    traced = run_cell(capsys, smoke_root, "routed.stream", trace=1)
    assert traced["correct"] is True, traced["checks"]
    metrics = {k: v["value"] for k, v in traced["metrics"].items()}
    # the recorded trace's 2 named kernels on each of its 2 stages, every round
    assert metrics["kernel_launches_per_round"] == 4.0
    assert all(metrics[f"engine_{k}_ms_per_round"] > 0 for k in ("model", "state", "other"))
    assert sum(metrics[f"engine_{k}_ms_per_round"] for k in ("model", "state", "other")) \
        <= metrics["engine_round_ms"]


def test_budget_switch_keeps_every_round(smoke_root, capsys):
    out = run_cell(capsys, smoke_root, "smoke.switch")
    checks = out["checks"]
    assert out["correct"] is True, checks
    assert set(checks) == {"loss0_gap", "loss_gap", "low_loss_gap", "back_loss_gap",
                           "change_gap", "low_change_gap", "plan_departures", "rounds_lost",
                           "tiling_gaps"}
    assert checks["rounds_lost"]["value"] == 0
    assert checks["tiling_gaps"]["value"] == 0
    assert "replan_ms" not in out["metrics"]  # a per-layer metric
    traced = run_cell(capsys, smoke_root, "smoke.switch", trace=1)
    assert traced["metrics"]["remap_ms"]["value"] > 0


def _frozen(monkeypatch):
    """A step that returns its state unchanged."""
    from repro.optim import optimizers

    real = optimizers.adamw

    def adamw(**kw):
        opt = real(**kw)
        return optimizers.Optimizer(init=opt.init, update=lambda p, g, s: (p, s))

    monkeypatch.setattr(optimizers, "adamw", adamw)


def _half_batch(monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    from repro.models import layers

    real = layers.cross_entropy_loss

    def half(logits, labels, mask=None):
        h = logits.shape[0] // 2
        return real(logits[:h], labels[:h], None if mask is None else mask[:h])

    monkeypatch.setattr(layers, "cross_entropy_loss", half)


@pytest.mark.parametrize("fault", [_frozen, _half_batch], ids=["frozen", "half_batch"])
@pytest.mark.parametrize("workload", ["smoke.stream", "smoke.switch", "routed.stream"])
def test_broken_timed_path_is_not_correct(smoke_root, capsys, monkeypatch, fault, workload):
    fault(monkeypatch)
    out = run_cell(capsys, smoke_root, workload)
    assert out["correct"] is False
    assert out["checks"]["loss_gap"]["value"] > out["checks"]["loss_gap"]["limit"]


def test_refuses_without_a_chip(smoke_root, capsys):
    with pytest.raises(SystemExit) as e:
        run_lib.main(["--workload", "smoke.stream", "--seed", "1", "--seconds", "1"],
                     root=smoke_root)
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""

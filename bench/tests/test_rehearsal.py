"""A whole run of the harness on the CPU at a small size, with the look
for a chip skipped: cells, configurations, traffic and metrics that exist
only as new files are found by name, a sound run is correct, and a run
whose timed path is broken underneath is not."""

from __future__ import annotations

import json

import pytest

import run as run_lib
from conftest import PEAKS, cpu_devices


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
@pytest.mark.parametrize("workload", ["smoke.stream", "smoke.switch"])
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

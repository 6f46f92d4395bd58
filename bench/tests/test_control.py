"""The control and the faults fail the comparison that a sound run passes
(CPU, small size; the chip readings at the cells' own sizes are in
PERF.md)."""

from __future__ import annotations

import json

import pytest

import calibrate
from conftest import ROUTED_LIMITS, SMOKE_LIMITS, cpu_devices


@pytest.mark.parametrize("workload", ["smoke.stream", "smoke.switch", "routed.stream"])
def test_control_and_faults_read_above_the_limit(smoke_root, capsys, workload):
    assert calibrate.main(["--workload", workload, "--seeds", "5", "6", "--control"],
                          root=smoke_root, devices_for=cpu_devices) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    stated = ROUTED_LIMITS if workload == "routed.stream" else SMOKE_LIMITS
    limits = {k: stated[k] for k in out["program_max"]}
    for k, limit in limits.items():
        assert out["program_max"][k] < limit, k
    for name in ("control", "frozen", "half_batch"):
        assert any(out[f"{name}_min"][k] > limit for k, limit in limits.items()), name

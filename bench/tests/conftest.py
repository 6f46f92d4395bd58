"""Shared set-up of the benchmark's own tests (CPU, small shapes).

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

``smoke_root`` builds a throwaway checkout: the repository's
``BENCHMARK.json`` and ``bench/`` with a small configuration, two small
traffic mixes, their two cells and a new per-layer metric added as new
files and entries only, and ``src`` linked in. Also added so: a block the
decoder reference cannot state (``blocks/routed.py``: routed experts,
windowed and full layers, its own head size) as its reference, a
configuration, a cell and a metric that reads the kernels by name.
"""

from __future__ import annotations

import importlib.util
import json
import shutil
import sys
from pathlib import Path
from types import ModuleType

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

SMOKE_MODEL = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2, "d_ff": 128,
               "vocab_size": 256, "window": None, "rope_theta": 10000.0, "norm_eps": 1e-6,
               "param_dtype": "float32", "compute_dtype": "bfloat16"}
# limits for this size, from CPU readings of seeds 1-6: the program reads
# at most 0.0047 (loss0_gap), 0.014 (loss_gap, smoke.stream), 0.0033 and
# 0.0019 (the switch's two loss windows), 0.0026 and 0.0019 (its two
# changes); the fp8 control at least 0.020, 0.028, 0.011, 0.013, 0.039
# and 0.044; the faults at least 0.13 on the later rounds and 1.0 frozen
SMOKE_LIMITS = {"loss0_gap": 0.012, "loss_gap": 0.03, "low_loss_gap": 0.007,
                "back_loss_gap": 0.007, "change_gap": 0.012, "low_change_gap": 0.012}
SMOKE_HIGH = {"bounds": [0, 1, 2], "workers": 3, "active": [0, 1, 2], "omit": [0, 0]}
# at this size no budget moves the partition: 0.4 of the plan's memory
# keeps the bounds and one worker of three, whose first stage omits every
# other backward; the schedule restarts
SMOKE_LOW = {"bounds": [0, 1, 2], "workers": 3, "active": [2], "omit": [1, 0]}
SMOKE_CHECK = {"first_rounds": 6, "loss_windows": {"low_loss_gap": [8, 14],
                                                    "back_loss_gap": [24, 30]},
               "changes": {"change_gap": 8, "low_change_gap": 16}}
# the routed block at a small size: top-2 of 4 experts with a capacity
# factor at which the program drops no token (capacity = seq), 3 windowed
# layers to 1 full, head_dim 32 at d_model 64 with 4 heads
ROUTED_MODEL = {"num_layers": 4, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
                "head_dim": 32, "d_ff": 128, "vocab_size": 256, "window": None,
                "local_global_ratio": 3, "local_window": 8, "num_experts": 4,
                "experts_per_token": 2, "moe_capacity_factor": 2.0, "rope_theta": 10000.0,
                "norm_eps": 1e-6, "param_dtype": "float32", "compute_dtype": "bfloat16"}
ROUTED_CONFIG = {"name": "routed", "registry_name": "mixtral-8x22b", "reference": "routed",
                 "model": ROUTED_MODEL}
# and at Mellum-2-12B-A2.5B's published widths (an eighth of its vocabulary)
WIDE_ROUTED_MODEL = dict(ROUTED_MODEL, d_model=2304, num_heads=32, num_kv_heads=4, head_dim=128,
                         d_ff=896, vocab_size=12288, local_window=1024, num_experts=64,
                         experts_per_token=8, moe_capacity_factor=8.0)
ROUTED_PLAN = {"bounds": [0, 1, 2, 3, 4], "workers": 3, "active": [0, 1, 2], "omit": [0, 0, 0, 0]}
ROUTED_LIMITS = {"loss0_gap": 0.012, "loss_gap": 0.03}
KERNEL_METRIC = '''"""Launches of the named kernels per engine round (a metric added as a file
of its own that reads the kernels by name)."""


def read(run):
    t = run.trace
    if not t or t["rounds"] <= 0 or not t.get("kernels"):
        return None
    return sum(calls for _, _, calls in t["kernels"]) / t["rounds"]
'''
NEW_METRIC = '''"""Rounds in the window (a metric added as a file of its own)."""


def read(run):
    return float(run.window_rounds)
'''


class FakeDevice:
    """A CPU device that reports a peak, as a TPU's allocator does."""

    def __init__(self, device):
        self.platform = device.platform
        self.device_kind = device.device_kind

    def memory_stats(self):
        return {"peak_bytes_in_use": 1 << 20}


def cpu_devices(chips: int):
    import jax

    return [FakeDevice(d) for d in jax.devices()[:chips]]


PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}


def load_block(name: str) -> ModuleType:
    """A reference kept with these tests (``blocks/<name>.py``)."""
    spec = importlib.util.spec_from_file_location(f"block_{name}", BENCH / "tests" / "blocks"
                                                  / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def smoke_root(tmp_path: Path) -> Path:
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "src").symlink_to(ROOT / "src")
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())

    config = {"name": "smoke", "registry_name": "musicgen-medium", "reference": "decoder",
              "model": SMOKE_MODEL}
    (root / "bench" / "configs" / "smoke.json").write_text(json.dumps(config))
    for name in ("stream", "budget-switch"):
        tr = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
        tr.update(batch=2, seq=16, segment_rounds=8)
        if "budget" in tr:
            tr["budget"].update(low_fraction=0.4, first_high_rounds=8, phase_rounds=16)
            tr["check"] = SMOKE_CHECK
        (root / "bench" / "traffic" / f"{name}-smoke.json").write_text(json.dumps(tr))
    limits = {k: SMOKE_LIMITS[k] for k in ("loss0_gap", "loss_gap")}
    (root / "bench" / "cells" / "smoke.stream.json").write_text(
        json.dumps({"plan": SMOKE_HIGH, "limits": limits}))
    (root / "bench" / "cells" / "smoke.switch.json").write_text(
        json.dumps({"plan": SMOKE_HIGH, "low_plan": SMOKE_LOW, "limits": SMOKE_LIMITS}))
    (root / "bench" / "metrics" / "window_rounds.py").write_text(NEW_METRIC)
    shutil.copy(BENCH / "tests" / "blocks" / "routed.py", root / "bench" / "references")
    (root / "bench" / "configs" / "routed.json").write_text(json.dumps(ROUTED_CONFIG))
    (root / "bench" / "cells" / "routed.stream.json").write_text(
        json.dumps({"plan": ROUTED_PLAN, "limits": ROUTED_LIMITS}))
    (root / "bench" / "metrics" / "kernel_launches_per_round.py").write_text(KERNEL_METRIC)

    doc["configs"] += [
        {"name": "smoke", "source": "test", "file": "bench/configs/smoke.json", "reduced": [],
         "why": "test"},
        {"name": "routed", "source": "test", "file": "bench/configs/routed.json", "reduced": [],
         "why": "test"},
    ]
    doc["workloads"] += [
        {"name": "smoke.stream", "config": "smoke", "traffic": "stream-smoke", "chips": 1,
         "why": "test"},
        {"name": "smoke.switch", "config": "smoke", "traffic": "budget-switch-smoke", "chips": 1,
         "why": "test"},
        {"name": "routed.stream", "config": "routed", "traffic": "stream-smoke", "chips": 1,
         "why": "test"},
    ]
    twins = {"musicgen-medium.stream": ["smoke.stream", "routed.stream"],
             "musicgen-medium.budget-switch": ["smoke.switch"]}
    for m in doc["per_layer"]:
        m["workloads"] += [t for w in m["workloads"] for t in twins.get(w, [])]
    doc["per_layer"].append({"name": "kernel_launches_per_round", "unit": "launches",
                             "better": "lower", "source": "device_trace", "layer": "kernels",
                             "moves": "stream_tokens_per_s", "workloads": ["routed.stream"]})
    doc["per_layer"].append({"name": "window_rounds", "unit": "rounds", "better": "higher",
                             "source": "host_clock", "layer": "session / runner",
                             "moves": "stream_tokens_per_s",
                             "workloads": ["smoke.stream", "smoke.switch"]})
    # the switch's own metrics (their chip cell is not in BENCHMARK.json yet)
    for name, layer in (("replan_ms", "planner"), ("remap_ms", "state remap")):
        doc["per_layer"].append({"name": name, "unit": "ms", "better": "lower",
                                 "source": "program_span", "layer": layer,
                                 "moves": "stream_tokens_per_s", "workloads": ["smoke.switch"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return root

"""Bytes the Iter-Fisher kernels need, from parameter counts; and the
chip's peaks.

These are the yardstick's own counts, not the program's: a kernel's bytes
are the HBM reads and writes its algorithm needs, each array once. A
block's model FLOPs and its parameters per stage are its reference's
(``train_flops``, ``stage_sizes``), which count the passes and weights
the configuration requires, nothing recomputed.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import List

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str, path: Path = PEAKS) -> dict:
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table["devices"][device_kind]


def iter_fisher_bytes(n: int, K: int) -> float:
    """fp32 bytes of one stage-update of the two Iter-Fisher kernels over
    n parameters with a K-deep Δθ history: compensation reads g and the K
    Δθ rows and writes g; the λ-statistics pass reads g, Δθ, v_r, v_a and
    writes v_r, v_a."""
    return 4.0 * n * (K + 2) + 4.0 * n * 6


def iter_fisher_bytes_per_round(stage_sizes: List[int]) -> float:
    """Every stage applies one update per round once the pipeline is full;
    the Δθ history is as deep as there are stages."""
    K = len(stage_sizes)
    return sum(iter_fisher_bytes(n, K) for n in stage_sizes)

"""Operations and bytes the work needs, from shapes; and the chip's peaks.

These are the yardstick's own counts, not the program's: a model FLOP is
one of the forward and backward passes the configuration requires
(nothing recomputed), and a kernel's bytes are the HBM reads and writes
its algorithm needs, each array once.
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


def decoder_train_flops(model: dict, rows: int, seq: int) -> float:
    """Forward + backward FLOPs of one step of the decoder on rows x seq
    tokens: 6 per weight per token for every matmul weight (attention,
    MLP, LM head; the embedding is a gather), plus 12 per (layer, head
    dim, key) for the full score and value products, as the step
    computes them."""
    d, ff, V, L = model["d_model"], model["d_ff"], model["vocab_size"], model["num_layers"]
    nh, kvh = model["num_heads"], model["num_kv_heads"]
    hd = d // nh
    attn = d * nh * hd * 2 + d * kvh * hd * 2
    mlp = 3 * d * ff
    weights = L * (attn + mlp) + d * V
    tokens = rows * seq
    return 6.0 * weights * tokens + 12.0 * L * nh * hd * seq * tokens


def stage_sizes(model: dict, bounds: List[int]) -> List[int]:
    """Parameters held by each pipeline stage: the embedding on the first,
    the final norm and LM head on the last, the layers between bounds."""
    d, ff, V = model["d_model"], model["d_ff"], model["vocab_size"]
    nh, kvh = model["num_heads"], model["num_kv_heads"]
    hd = d // nh
    layer = 2 * d + d * nh * hd * 2 + d * kvh * hd * 2 + 3 * d * ff
    sizes = []
    for j in range(len(bounds) - 1):
        n = (bounds[j + 1] - bounds[j]) * layer
        if j == 0:
            n += V * d
        if j == len(bounds) - 2:
            n += d + d * V
        sizes.append(n)
    return sizes


def iter_fisher_bytes(n: int, K: int) -> float:
    """fp32 bytes of one stage-update of the two Iter-Fisher kernels over
    n parameters with a K-deep Δθ history: compensation reads g and the K
    Δθ rows and writes g; the λ-statistics pass reads g, Δθ, v_r, v_a and
    writes v_r, v_a."""
    return 4.0 * n * (K + 2) + 4.0 * n * 6


def iter_fisher_bytes_per_round(model: dict, bounds: List[int]) -> float:
    """Every stage applies one update per round once the pipeline is full."""
    K = max(len(bounds) - 1, 1)
    return sum(iter_fisher_bytes(n, K) for n in stage_sizes(model, bounds))

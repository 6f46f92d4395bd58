"""Roofline-term extraction from compiled XLA artifacts.

``compiled.cost_analysis()`` gives HLO FLOPs and bytes, but collective
traffic is not in there — we parse the (post-SPMD-partitioning) HLO text
and sum wire bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute, with ring-algorithm multipliers and
replica-group sizes taken from the instruction attributes.

Hardware constants come from ``repro.core.chips`` for the mesh's device
kind (TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM, 50 GB/s/link ICI).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

from repro.core.chips import TPU_V5E, ChipSpec, chip_spec

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)


def _shape_bytes(shape_str: str) -> int:
    """Sum bytes over every 'dtype[dims]' group in a result-type string."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str, default: int) -> int:
    m = re.search(r"replica_groups=\{\{([0-9,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    # iota v2 format [num_groups, group_size]
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    return default


@dataclasses.dataclass
class CollectiveStats:
    counts: Dict[str, int]
    wire_bytes: Dict[str, float]  # per-device bytes over ICI links

    @property
    def total_bytes(self) -> float:
        return sum(self.wire_bytes.values())


def parse_collectives(hlo_text: str, default_group: int) -> CollectiveStats:
    counts: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    wire: Dict[str, float] = {c: 0.0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if "fused_computation" in stripped:
            continue
        for coll in _COLLECTIVES:
            # match op invocations, incl. async '-start' forms; skip '-done'
            if re.search(rf"= .* {coll}(-start)?\(", stripped) is None:
                continue
            # result type(s): between '=' and the op name
            m = re.search(rf"=\s*(.*?)\s*{coll}(-start)?\(", stripped)
            if not m:
                continue
            out_bytes = _shape_bytes(m.group(1))
            g = _group_size(stripped, default_group)
            if g <= 1:
                continue
            if coll == "all-reduce":
                # ring: reduce-scatter + all-gather ≈ 2·(g-1)/g · size
                b = 2.0 * (g - 1) / g * out_bytes
            elif coll == "all-gather":
                b = (g - 1) / g * out_bytes  # output is the gathered size
            elif coll == "reduce-scatter":
                b = (g - 1) * out_bytes  # output is the scattered shard
            elif coll == "all-to-all":
                b = (g - 1) / g * out_bytes
            else:  # collective-permute: point-to-point
                b = float(out_bytes)
            counts[coll] += 1
            wire[coll] += b
            break
    return CollectiveStats(counts=counts, wire_bytes=wire)


@dataclasses.dataclass
class RooflineTerms:
    """All terms in seconds, per the §Roofline definition."""

    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    chips: int
    chip: ChipSpec = TPU_V5E

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / self.chip.peak_flops_bf16

    @property
    def t_memory(self) -> float:
        return self.bytes_per_device / self.chip.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.collective_bytes_per_device / self.chip.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """Ideal-overlap roofline: the dominant term bounds the step."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    def as_dict(self) -> dict:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "chips": self.chips,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
        }


def analytic_memory_bytes(
    cfg,
    shape,
    chips: int,
    model_shard: int,
    microbatch: int,
    cache_bytes: int = 0,
) -> float:
    """Per-device HBM-traffic *model* (lower bound).

    The compiled `bytes accessed` on the CPU backend sums every
    instruction's operands pre-fusion and overestimates TPU HBM traffic by
    10-30× (measured: danube train reports 2.75 TB/dev where weights+acts
    +optimizer round to ~70 GB). This model counts the unavoidable traffic:
      - optimizer: params+m+v read & write once per step,
      - weights: each fwd/remat/bwd pass streams the (TP-resident,
        FSDP-gathered) weights once (gather write + read ⇒ ×2),
      - activations: the ~6.5 B/(token·layer·d) residual stream written
        and read once,
      - decode/prefill: the KV/SSM cache read (+ write for decode).
    """
    pb = 2 if cfg.param_dtype == "bfloat16" else 4
    p_count = cfg.param_count()
    p_total = p_count * pb
    w_gathered = p_total / max(model_shard, 1)

    if shape.kind == "train":
        opt = p_count / chips * (pb + 8) * 2.0  # read+write of p, m, v
        passes = 3.0 * microbatch  # fwd + remat-fwd + bwd per microbatch
        weights = passes * w_gathered * 2.0
        tok_dev = shape.batch * shape.seq / max(chips / model_shard, 1)
        acts = 2.0 * 6.5 * cfg.num_layers * cfg.d_model * tok_dev
        return opt + weights + acts
    if shape.kind == "prefill":
        tok_dev = shape.batch * shape.seq / max(chips / model_shard, 1)
        acts = 2.0 * 2.0 * cfg.num_layers * cfg.d_model * tok_dev  # write+read, fwd only
        return w_gathered * 2.0 + acts + cache_bytes / chips
    # decode: weights once + cache r/w
    return w_gathered + 2.0 * cache_bytes / chips


def roofline_from_compiled(compiled, mesh, hlo_text: Optional[str] = None) -> RooflineTerms:
    chips = mesh.devices.size
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    colls = parse_collectives(text, default_group=chips)
    return RooflineTerms(
        flops_per_device=flops,
        bytes_per_device=byts,
        collective_bytes_per_device=colls.total_bytes,
        chips=chips,
        chip=chip_spec(mesh.devices.flat[0]),
    ), colls

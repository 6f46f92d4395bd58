"""Per-chip hardware constants, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" — per chip 197 TFLOP/s bf16,
16 GB of HBM at 819 GB/s, and 1,600 Gbit/s of chip-to-chip interconnect
over four links (50 GB/s each).

The planner's analytic roofline, the HLO roofline terms and the elastic
cluster view all read this table. A TPU whose kind is not listed is an
error, never a guess. Non-TPU devices (the CPU that tests and dry runs use)
plan against the v5e row: it is the chip this code targets.
"""

from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    device_kind: str
    peak_flops_bf16: float  # FLOP/s
    hbm_bytes: int
    hbm_bw: float  # B/s
    ici_bw: float  # B/s per link


TPU_V5E = ChipSpec(
    device_kind="TPU v5 lite",
    peak_flops_bf16=197e12,
    hbm_bytes=16 * 2**30,
    hbm_bw=819e9,
    ici_bw=50e9,
)

CHIPS: Dict[str, ChipSpec] = {c.device_kind: c for c in (TPU_V5E,)}


def chip_spec(device=None) -> ChipSpec:
    """The spec of ``device`` (default: the first device jax sees)."""
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform != "tpu":
        return TPU_V5E
    try:
        return CHIPS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no hardware constants for TPU kind {device.device_kind!r}; "
            f"known kinds: {sorted(CHIPS)} (repro.core.chips)"
        ) from None

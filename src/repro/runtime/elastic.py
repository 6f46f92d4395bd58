"""Elastic scaling: resource changes re-enter the paper's own planner.

A device/host loss (or gain) changes two planner inputs:
  1. the per-chip memory budget share M, and
  2. the per-layer profile (per-chip t^f/t^b scale with the TP degree).

Ferret's bi-level planner (Alg. 2+3) was built to answer exactly the
question "best pipeline under memory budget M", so elasticity is a
re-plan + checkpoint-restore: no bespoke rebalancing logic. This is the
paper's memory-adaptivity claim operationalized as fault tolerance.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from repro.core import planner as planner_lib
from repro.core.chips import chip_spec
from repro.core.profiler import ModelProfile, profile_for
from repro.models.config import ModelConfig


def _is_topology(cluster) -> bool:
    from repro.runtime.topology import DeviceTopology

    return isinstance(cluster, DeviceTopology)


class DeviceLossError(RuntimeError):
    """A device/host dropped out mid-run.

    Unlike a NaN or a timeout, this is not retryable in place: the lost
    capacity is gone, so the supervisor escalates straight to the elastic
    planner (shrink-replan) instead of burning its retry budget.

    ``lost_devices`` sizes the topology shrink the handler performs: the
    elastic trainer rebuilds its ``DeviceTopology`` over
    ``device_count - lost_devices`` survivors and replans from there.
    """

    def __init__(self, *args, lost_devices: int = 1):
        super().__init__(*args)
        self.lost_devices = int(lost_devices)


@dataclasses.dataclass
class ClusterSpec:
    chips: int
    hbm_per_chip: int = dataclasses.field(default_factory=lambda: chip_spec().hbm_bytes)

    @property
    def total_hbm(self) -> int:
        return self.chips * self.hbm_per_chip

    @classmethod
    def from_topology(cls, topology) -> "ClusterSpec":
        """A cluster view of a discovered ``DeviceTopology`` — the legacy
        scalar bridge for callers that still budget from chip totals."""
        return cls(
            chips=topology.device_count,
            hbm_per_chip=topology.memory_per_device,
        )


class ElasticPlanner:
    """Re-plans the pipeline when the cluster shrinks or grows."""

    def __init__(
        self,
        model_cfg: ModelConfig,
        batch: int,
        seq: int,
        decay_c: float = 1.0,
        memory_fraction: float = 0.9,  # budget headroom for runtime buffers
        max_workers: Optional[int] = 8,
    ):
        self.model_cfg = model_cfg
        self.batch = batch
        self.seq = seq
        self.decay_c = decay_c
        self.memory_fraction = memory_fraction
        self.max_workers = max_workers

    def profile_for(self, cluster) -> ModelProfile:
        """Store-aware Alg. 3 ``profile(θ)``: a persisted on-device
        measurement for this geometry (scaled to the cluster's shape) when
        one exists, the analytic roofline otherwise — so a topology-shrink
        replan after ``Supervisor.on_fatal`` runs from real numbers.

        ``cluster`` is a legacy ``ClusterSpec`` (TP/FSDP-style per-chip
        division over ``chips``) or a discovered ``DeviceTopology``
        (data-parallel scaling: times and activations divide by the data
        axis, weights replicate — ``profile.bridge.for_topology``).
        """
        if _is_topology(cluster):
            from repro.profile.bridge import for_topology

            base = profile_for(self.model_cfg, self.batch, self.seq)
            return for_topology(base, cluster)
        return profile_for(
            self.model_cfg, self.batch, self.seq, chips=cluster.chips
        )

    def replan(self, cluster) -> planner_lib.Plan:
        profile = self.profile_for(cluster)
        t_d = planner_lib.default_data_interval(profile)
        return planner_lib.plan(
            profile,
            t_d,
            self.budget_for(cluster),
            c=self.decay_c,
            max_workers=self.max_workers,
            topology=cluster if _is_topology(cluster) else None,
        )

    def degradation(self, before: planner_lib.Plan, after: planner_lib.Plan) -> float:
        """Fractional adaptation-rate loss from the resource change."""
        if before.rate <= 0:
            return 0.0
        return max(0.0, 1.0 - after.rate / before.rate)

    def budget_for(self, cluster) -> float:
        """The memory budget M the planner gets for this cluster shape.

        A ``DeviceTopology`` budgets *per device* (data-parallel replicas
        hold the whole pipeline, only the model axis multiplies memory);
        the legacy ``ClusterSpec`` keeps its scalar-total semantics.
        """
        if _is_topology(cluster):
            return cluster.plan_budget(self.memory_fraction)
        return self.memory_fraction * cluster.total_hbm

"""Shared fixtures. NOTE: no XLA_FLAGS here — tests see 1 CPU device;
multi-device sharding tests spawn subprocesses that set the flag first."""

import dataclasses
import os
import tempfile

import jax
import pytest

# Hermetic profile store: planner/dispatch defaults must come from code,
# never from whatever ~/.cache/repro/profile happens to hold on this
# machine. Set before any repro import resolves the store root. Tests
# that exercise the store point REPRO_PROFILE_DIR at their own tmp_path
# (and reset_default_stores()/clear_tuned_cache() around it).
os.environ.setdefault(
    "REPRO_PROFILE_DIR", tempfile.mkdtemp(prefix="repro-test-profile-")
)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def smoke_cfg(arch: str, **overrides):
    from repro.models.registry import get_config

    cfg = get_config(arch, smoke=True)
    defaults = dict(compute_dtype="float32", moe_capacity_factor=8.0)
    defaults.update(overrides)
    return dataclasses.replace(cfg, **defaults)

"""End-to-end behaviour tests for the full Ferret system."""

import dataclasses
import json
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from conftest import smoke_cfg
from repro.core.compensation import CompensationConfig
from repro.core.ferret import FerretConfig, FerretTrainer, sequential_oracle_run
from repro.models import transformer as T
from repro.ocl.streams import StreamConfig, make_stream


def _learnable_stream(vocab=32, length=150, seq=16, batch=2, seed=0):
    return make_stream(
        StreamConfig(kind="iid", modality="tokens", length=length, batch=batch,
                     vocab=vocab, seq=seq, seed=seed)
    )


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = smoke_cfg("h2o-danube-1.8b", num_layers=4, vocab_size=32)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    stream = _learnable_stream()
    return cfg, params, stream


def test_ferret_trainer_learns_and_respects_budget(tiny_setup):
    cfg, params, stream = tiny_setup
    fc = FerretConfig(
        budget_bytes=float("inf"), lr=5e-3, max_workers=3, max_stages=4,
        compensation=CompensationConfig(method="iter_fisher", eta_lambda=1e-4),
    )
    tr = FerretTrainer(cfg, fc, batch=2, seq=16)
    res = tr.run_stream(params, stream)
    assert np.isfinite(res.losses).all()
    # the model learns: mean loss over the last quarter < first quarter
    q = len(res.losses) // 4
    assert res.losses[-q:].mean() < res.losses[:q].mean()
    assert res.admitted_frac == 1.0

    # constrained run: planner memory within budget, rate not higher than M+
    budget = tr.plan.memory * 0.3
    fc2 = dataclasses.replace(fc, budget_bytes=budget)
    tr2 = FerretTrainer(cfg, fc2, batch=2, seq=16)
    assert tr2.plan.memory <= budget * (1 + 1e-9)
    assert tr2.plan.rate <= tr.plan.rate * (1 + 1e-9)


def test_ferret_tracks_oracle_on_stationary_stream(tiny_setup):
    """Ferret_M+ online accuracy should be within a few points of Oracle
    (paper Table 1's qualitative claim)."""
    cfg, params, stream = tiny_setup
    orc = sequential_oracle_run(cfg, params, stream, lr=5e-3)
    fc = FerretConfig(budget_bytes=float("inf"), lr=5e-3, max_workers=3, max_stages=4,
                      compensation=CompensationConfig(method="iter_fisher", eta_lambda=1e-4))
    res = FerretTrainer(cfg, fc, batch=2, seq=16).run_stream(params, stream)
    oacc_oracle = float(orc["acc"].mean())
    assert res.online_acc > 0.5 * oacc_oracle


def test_compensation_improves_async_accuracy(tiny_setup):
    """Iter-Fisher ≥ no-compensation on the same async pipeline (Table 4)."""
    cfg, params, _ = tiny_setup
    stream = _learnable_stream(length=240, seed=3)
    accs = {}
    for method in ("none", "iter_fisher"):
        fc = FerretConfig(
            budget_bytes=float("inf"), lr=1e-2, max_workers=2, max_stages=4,
            compensation=CompensationConfig(method=method, eta_lambda=0.0, lam0=0.2),
        )
        res = FerretTrainer(cfg, fc, batch=2, seq=16).run_stream(params, stream)
        accs[method] = res.online_acc
    # allow tiny noise, but compensation must not be significantly worse
    assert accs["iter_fisher"] >= accs["none"] - 0.01


SUBPROCESS_SHARDING = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, jax, jax.numpy as jnp, dataclasses
    from repro.models.registry import get_config
    from repro.models import transformer as T
    from repro.configs.common import InputShape, input_specs
    from repro.launch import shardings as sh
    from repro.launch.steps import make_train_step, make_decode_step
    from repro.optim.optimizers import adamw

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    maxes = dict(zip(mesh.axis_names, mesh.devices.shape))
    cfg = get_config("{arch}", smoke=True)
    shape = InputShape("t", "{kind}", 64, 8)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    pspecs = T.param_pspecs(cfg, maxes, data_axes=("data",))
    p_sh = sh.named(mesh, pspecs)
    batch_s = input_specs(cfg, shape)
    b_sh = sh.named(mesh, sh.batch_pspecs(cfg, shape, maxes, ("data",), "model"))
    with jax.set_mesh(mesh):
        if "{kind}" == "train":
            opt = adamw(1e-3)
            opt_s = jax.eval_shape(opt.init, params)
            o_sh = sh.named(mesh, sh.opt_pspecs(pspecs, opt_s))
            step = make_train_step(cfg, opt, remat=False)
            c = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh)).lower(
                jax.eval_shape(lambda: params), opt_s, batch_s).compile()
        else:
            cache_s = jax.eval_shape(lambda: T.init_cache(cfg, shape.batch, shape.seq))
            c_specs = sh.cache_pspecs(cfg, cache_s, maxes, ("data",), "model")
            c_sh = sh.named(mesh, c_specs)
            step = make_decode_step(cfg)
            c = jax.jit(step, in_shardings=(p_sh, c_sh, b_sh)).lower(
                jax.eval_shape(lambda: params), cache_s, batch_s).compile()
    print(json.dumps({{"ok": True, "flops": c.cost_analysis().get("flops", 0)}}))
    """
)


@pytest.mark.parametrize("arch,kind", [
    ("h2o-danube-1.8b", "train"),
    ("mamba2-780m", "train"),
    ("mixtral-8x22b", "train"),
    ("gemma3-12b", "decode"),
    ("hymba-1.5b", "decode"),
])
def test_sharded_lowering_on_8_device_mesh(arch, kind):
    """Multi-device GSPMD lowering of smoke configs (subprocess so the
    device-count flag never leaks into other tests)."""
    import os
    code = SUBPROCESS_SHARDING.format(arch=arch, kind=kind)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["ok"]


def test_train_driver_plain_mode_smoke(tmp_path):
    """launch.train plain mode: runs, checkpoints, restarts."""
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cmd = [
        sys.executable, "-m", "repro.launch.train", "--arch", "mamba2-780m",
        "--smoke", "--mode", "plain", "--steps", "6", "--batch", "2", "--seq", "16",
        "--ckpt-dir", str(tmp_path), "--ckpt-every", "2",
    ]
    p1 = subprocess.run(cmd, capture_output=True, text=True, timeout=600, cwd=root, env=env)
    assert p1.returncode == 0, p1.stderr[-2000:]
    # second run restores from the checkpoint and continues to 8
    cmd[cmd.index("6")] = "8"
    p2 = subprocess.run(cmd, capture_output=True, text=True, timeout=600, cwd=root, env=env)
    assert p2.returncode == 0, p2.stderr[-2000:]
    assert "restored from checkpoint" in p2.stdout


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_is_placed_from_outside(env_dir, tmp_path, monkeypatch):
    """Entry points keep JAX's cache where JAX_COMPILATION_CACHE_DIR says,
    setting nothing in code; unset, at the checkout's fixed .jax_cache/."""
    from pathlib import Path

    from repro.launch.compile_cache import enable_compile_cache

    prev = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env_dir))
    try:
        got = enable_compile_cache()
        if env_dir is None:
            want = Path(__file__).resolve().parents[1] / ".jax_cache"
            assert got == str(want) == jax.config.jax_compilation_cache_dir
        else:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)

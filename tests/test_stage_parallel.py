"""Spatial shard_map pipeline: wavefront forward/backward equivalences.

Runs in a subprocess with 8 host devices (keeps the main test process on
1 device)."""

import json
import os
import subprocess
import sys
import textwrap

CODE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.registry import get_config
    from repro.models import transformer as T
    from repro.core.stage_parallel import spatial_pipeline_logits, spatial_pipeline_loss

    cfg = dataclasses.replace(get_config("h2o-danube-1.8b", smoke=True),
                              compute_dtype="float32", num_layers=8, vocab_size=64)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    mesh = jax.make_mesh((4,), ("stage",))
    M, b, s = 3, 2, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (M, b, s + 1), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :, :-1], "labels": toks[:, :, 1:]}
    with jax.set_mesh(mesh):
        logits = spatial_pipeline_logits(cfg, params, batch, mesh, num_stages=4)
    for m in range(M):
        ref, _ = T.forward(cfg, params, {"tokens": batch["tokens"][m]})
        np.testing.assert_allclose(np.asarray(logits[m]), np.asarray(ref), rtol=2e-4, atol=2e-4)
    with jax.set_mesh(mesh):
        g_sp = jax.grad(lambda p: spatial_pipeline_loss(cfg, p, batch, mesh, 4))(params)
    def plain_loss(p):
        tot = 0.0
        for m in range(M):
            tot = tot + T.loss_fn(cfg, p, {"tokens": batch["tokens"][m],
                                           "labels": batch["labels"][m]})[0]
        return tot / M
    g_ref = jax.grad(plain_loss)(params)
    for a, b_ in zip(jax.tree.leaves(g_sp), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=3e-3, atol=3e-4)
    print(json.dumps({"ok": True}))
    """
)


def test_spatial_pipeline_matches_sequential():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", CODE], capture_output=True, text=True,
        timeout=600, cwd=root, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1])["ok"]

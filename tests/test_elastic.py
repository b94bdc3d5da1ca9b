"""Elastic scaling integration: checkpoint on one mesh layout, restore onto
another (the 1000-node failover path), in a forced-8-device subprocess."""
import os
import subprocess
import sys
import textwrap

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import configs as cfgs
    from repro.checkpoint import restore_state, save_state
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import init_state, make_train_step, state_shardings
    from repro.optim import AdamWConfig
    from repro.runtime import plan_remesh, build_mesh

    cfg = cfgs.get_config("qwen1.5-0.5b", smoke=True)
    opt_cfg = AdamWConfig(lr=1e-3)

    # --- train one step on a (4, 2) mesh, checkpoint ---
    mesh_a = make_mesh((4, 2), ("data", "model"))
    with mesh_a, shd.use_rules(shd.default_rules(mesh_a), mesh_a):
        ns_a = state_shardings(cfg, mesh_a, 2)
        step = jax.jit(make_train_step(cfg, opt_cfg, total_steps=10),
                       in_shardings=(ns_a, None), out_shardings=(ns_a, None))
        state = jax.device_put(init_state(cfg, opt_cfg, jax.random.PRNGKey(0)),
                               ns_a)
        key = jax.random.PRNGKey(1)
        toks = jax.random.randint(key, (8, 32), 0, cfg.vocab, jnp.int32)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
        state, m1 = step(state, batch)
        save_state(state, "/tmp/elastic_ckpt", 1)

    # --- "lose" 4 devices: re-mesh to (2, 2) and restore ---
    plan = plan_remesh(4, model=2)
    assert plan == ((2, 2), ("data", "model")), plan
    mesh_b = build_mesh(plan, devices=jax.devices()[:4])
    with mesh_b, shd.use_rules(shd.default_rules(mesh_b), mesh_b):
        ns_b = state_shardings(cfg, mesh_b, 2)
        like = jax.eval_shape(
            lambda: init_state(cfg, opt_cfg, jax.random.PRNGKey(0)))
        restored = restore_state(like, "/tmp/elastic_ckpt", 1, shardings=ns_b)
        # same logical state, new physical layout
        w_old = np.asarray(jax.device_get(
            jax.tree.leaves(state["params"])[0]), np.float32)
        w_new = np.asarray(jax.device_get(
            jax.tree.leaves(restored["params"])[0]), np.float32)
        np.testing.assert_array_equal(w_old, w_new)
        # and training continues on the smaller mesh
        step_b = jax.jit(make_train_step(cfg, opt_cfg, total_steps=10),
                         in_shardings=(ns_b, None), out_shardings=(ns_b, None))
        restored, m2 = step_b(restored, batch)
        assert np.isfinite(float(m2["loss"]))
        assert int(restored["opt"]["step"]) == 2
    print("ELASTIC_OK", float(m1["loss"]), float(m2["loss"]))
""")


def test_checkpoint_restores_across_mesh_shapes():
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": os.environ.get("HOME", ""),
             "JAX_PLATFORMS": "cpu"},
        cwd=REPO_ROOT)
    assert "ELASTIC_OK" in r.stdout, r.stderr[-2000:]

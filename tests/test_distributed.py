"""Sharding rules, spec resolution, and a real multi-device train step
(8 forced host devices in a subprocess, since device count locks at init)."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd
from repro import configs as cfgs
from repro.models import transformer as T

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_resolve_rules():
    mesh = jax.make_mesh((1,), ("data",))
    with shd.use_rules({"fsdp": "data", "tp": "model",
                        "dp": ("data",), "sp": "model"}, mesh):
        assert shd.resolve(P("fsdp", "tp")) == P("data", "model")
        assert shd.resolve(P("dp", None)) == P(("data",), None)
        assert shd.resolve(P(None)) == P(None)
        assert shd.resolve(P("unknown")) == P(None)


def test_constrain_noop_outside_mesh():
    x = jax.numpy.ones((4, 4))
    np.testing.assert_array_equal(np.asarray(shd.constrain(x, "dp", None)),
                                  np.asarray(x))


@pytest.mark.parametrize("arch", ["yi-9b", "olmoe-1b-7b", "mamba2-2.7b",
                                  "jamba-1.5-large-398b"])
def test_param_specs_match_param_tree(arch):
    """Spec pytree must be congruent with the param pytree and rank-correct."""
    cfg = cfgs.get_config(arch, smoke=True)
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    specs = T.param_specs(cfg, tp=2)
    jax.tree.map(lambda p, s: None, params, specs,
                 is_leaf=lambda x: isinstance(x, P))  # structure congruence
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    for p, s in zip(flat_p, flat_s):
        assert len(tuple(s)) <= p.ndim, (p.shape, s)


def test_cache_specs_match_cache_tree():
    cfg = cfgs.get_config("jamba-1.5-large-398b", smoke=True)
    cache = jax.eval_shape(lambda: T.init_cache(cfg, 4, 32))
    specs = T.cache_specs(cfg, tp=2)
    jax.tree.map(lambda c, s: None, cache, specs,
                 is_leaf=lambda x: isinstance(x, P))


MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import configs as cfgs
    from repro.distributed import sharding as shd
    from repro.launch.steps import init_state, make_train_step, state_shardings
    from repro.launch.mesh import make_mesh

    cfg = cfgs.get_config("{arch}", smoke=True)
    mesh = make_mesh((4, 2), ("data", "model"))
    with mesh:
        with shd.use_rules(shd.default_rules(mesh), mesh):
            from repro.optim import AdamWConfig
            opt_cfg = AdamWConfig(lr=1e-3)
            state_ns = state_shardings(cfg, mesh, 2)
            step = jax.jit(make_train_step(cfg, opt_cfg, total_steps=10),
                           in_shardings=(state_ns, None),
                           out_shardings=(state_ns, None), donate_argnums=(0,))
            state = jax.device_put(
                init_state(cfg, opt_cfg, jax.random.PRNGKey(0)), state_ns)
            key = jax.random.PRNGKey(1)
            toks = jax.random.randint(key, (8, 32), 0, cfg.vocab, jnp.int32)
            batch = {{"tokens": toks, "labels": jnp.roll(toks, -1, 1)}}
            if cfg.family in ("vlm", "encoder"):
                batch = {{"embeds": jax.random.normal(
                    key, (8, 32, cfg.d_model), jnp.bfloat16),
                    "labels": batch["labels"]}}
            l0 = None
            for _ in range(3):
                state, m = step(state, batch)
                loss = float(m["loss"])
                assert np.isfinite(loss), loss
                l0 = loss if l0 is None else l0
            print("MULTIDEV_OK", l0, loss)
""")


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "olmoe-1b-7b"])
def test_train_step_on_8_devices(arch):
    """Real data+tensor parallel train step on 8 forced host devices."""
    r = subprocess.run(
        [sys.executable, "-c", MULTIDEV_SCRIPT.format(arch=arch)],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", ""), "JAX_PLATFORMS": "cpu"},
        cwd=REPO_ROOT)
    assert "MULTIDEV_OK" in r.stdout, r.stderr[-2000:]

"""Seeded inputs and weights, made on the device.

Everything a run feeds the program comes from ``--seed``: the conv weights
and biases (one jitted call for the whole network, pruned by magnitude at
each layer's stated sparsity), the FC seed, and the images.  The seed is
passed as key data, so the compiled makers are shared by every seed and
stay in the persistent compilation cache.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import reference

# Stream ids folded into the run's key: one per kind of input.
_WEIGHTS, _IMAGES, _FC = 1, 2, 3


def key_data(seed: int) -> np.ndarray:
    """The threefry key data of a seed of up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is not a whole number below 2**64")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def fc_seed(seed: int) -> int:
    """The seed of the FC weights, derived from the run's seed."""
    return int(np.random.default_rng([int(seed) & (2**63 - 1), _FC])
               .integers(0, 2**31))


def prune(w, sparsity: float):
    """Zero the ``round(sparsity * size)`` entries of least magnitude."""
    if sparsity <= 0:
        return w
    flat = jnp.sort(jnp.abs(w).reshape(-1))
    drop = int(round(sparsity * flat.size))
    return jnp.where(jnp.abs(w) > flat[drop - 1], w, 0.0)


@functools.lru_cache(maxsize=None)
def _weight_maker(config_json: str):
    import json
    config = json.loads(config_json)
    table = reference.conv_table(config)
    bias_std = float(config["bias_std"])

    @jax.jit
    def make(kd):
        key = jax.random.fold_in(jax.random.wrap_key_data(kd), _WEIGHTS)
        out = {}
        for i, cv in enumerate(table):
            kw, kb = jax.random.split(jax.random.fold_in(key, i))
            shape = (cv["out"], cv["c"], cv["k"], cv["k"])
            std = (2.0 / (cv["c"] * cv["k"] * cv["k"])) ** 0.5
            w = jax.random.normal(kw, shape, jnp.float32) * std
            b = jax.random.normal(kb, (cv["out"],), jnp.float32) * bias_std
            out[cv["name"]] = (prune(w, cv["sparsity"]), b)
        return out

    return make


def make_weights(config: Dict[str, Any], seed: int
                 ) -> Dict[str, Tuple[jax.Array, jax.Array]]:
    """``{conv name: (w (M, C, K, K), b (M,))}`` on the default device."""
    import json
    return _weight_maker(json.dumps(config, sort_keys=True))(
        jnp.asarray(key_data(seed)))


@functools.partial(jax.jit, static_argnums=(1,))
def _images(kd, shape):
    key = jax.random.fold_in(jax.random.wrap_key_data(kd), _IMAGES)
    return jax.random.normal(key, shape, jnp.float32)


def make_images(seed: int, n: int, c: int, h: int, w: int) -> jax.Array:
    """``n`` distinct (C, H, W) images with N(0, 1) pixels, on the device."""
    return _images(jnp.asarray(key_data(seed)), (n, c, h, w))

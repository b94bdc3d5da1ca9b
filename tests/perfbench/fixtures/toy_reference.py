"""Plain reference of a toy architecture that the shared CNN reference
(``perfbench/references/cnn.py``) cannot parse: ``stages``, each a 3x3
pad-1 conv with bias and ReLU followed by a 2x2 stride-2 average pool, then
one FC layer of ``classes`` outputs.  It lives with the tests alone, to
show that an architecture's benchmark side is its own files: a
configuration that names this module runs through the harness unchanged.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perfbench import weights as wts

PRECISIONS = ("highest", "high", "bfloat16")


def conv_table(config):
    c, h, out = config["channels"], config["image"], []
    for s in config["stages"]:
        out.append({"name": s["name"], "out": s["out"], "k": 3, "stride": 1,
                    "pad": 1, "sparsity": s["sparsity"], "c": c, "h": h,
                    "w": h, "e": h, "f": h, "res": False})
        c, h = s["out"], h // 2
    return out


def fc_layers(config):
    last = conv_table(config)[-1]
    return [{"name": "fc", "in_f": last["out"] * (last["e"] // 2) ** 2,
             "out_f": config["classes"], "relu": False}]


@functools.lru_cache(maxsize=None)
def _weight_maker(config_json):
    config = json.loads(config_json)
    table = conv_table(config)

    @jax.jit
    def make(kd):
        key, out = wts.weights_key(kd), {}
        for i, cv in enumerate(table):
            kw, kb = jax.random.split(jax.random.fold_in(key, i))
            w = jax.random.normal(kw, (cv["out"], cv["c"], 3, 3)) * (
                2.0 / (9 * cv["c"])) ** 0.5
            b = jax.random.normal(kb, (cv["out"],)) * config["bias_std"]
            out[cv["name"]] = (wts.prune(w, cv["sparsity"]), b)
        return out

    return make


def make_weights(config, seed):
    return _weight_maker(json.dumps(config, sort_keys=True))(
        jnp.asarray(wts.key_data(seed)))


def fc_weights(config, fc_seed):
    rng = np.random.default_rng(int(fc_seed))
    return [rng.standard_normal((fc["in_f"], fc["out_f"])).astype(np.float32)
            * np.float32((1.0 / fc["in_f"]) ** 0.5)
            for fc in fc_layers(config)]


def forward(config, weights, fc_ws, x, precision="highest"):
    dt = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    prec = {"highest": lax.Precision.HIGHEST, "high": lax.Precision.HIGH,
            "bfloat16": lax.Precision.DEFAULT}[precision]
    v = x.astype(dt)
    for cv in conv_table(config):
        w, b = weights[cv["name"]]
        v = lax.conv_general_dilated(
            v, w.astype(dt), (1, 1), ((1, 1), (1, 1)),
            dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=prec,
            preferred_element_type=dt)
        v = jnp.maximum(v + b.astype(dt)[None, :, None, None], 0)
        v = lax.reduce_window(v, jnp.array(0, dt), lax.add, (1, 1, 2, 2),
                              (1, 1, 2, 2), "VALID") / 4
    (fc_w,) = fc_ws
    v = jnp.matmul(v.reshape(v.shape[0], -1), fc_w.astype(dt),
                   precision=prec, preferred_element_type=dt)
    return v.astype(jnp.float32)

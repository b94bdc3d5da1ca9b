"""Plain reference of the benchmark's CNNs, built from a configuration file.

The configuration's ``layers`` list is the network as it is run: ``conv``,
``maxpool``, ``bottleneck`` (ResNet v1: 1x1 with the stride, 3x3, 1x1, plus
an identity or 1x1 projection shortcut, then ReLU), ``inception`` (four
branches concatenated on channels), ``gap`` and ``fc`` (the NCHW map
flattened, times the FC matrix, then a ReLU where the layer says
``"relu": true``).  Every conv is followed by its bias and a ReLU, except
the last 1x1 of a bottleneck and its projection, whose sum takes the ReLU.
A ``conv`` layer may state ``groups`` (Caffe's ``group``): its output
channels fall into that many groups, each of which reads only its own
share of the input channels.

Straightforward ``jax.numpy``/``lax`` in NCHW, nothing of the program
under test imported.  ``precision="highest"`` is float32 at
``lax.Precision.HIGHEST`` (the reference); ``precision="high"`` is float32
at ``lax.Precision.HIGH`` (three bfloat16 passes) and
``precision="bfloat16"`` holds every array in bfloat16: the controls, one
precision below float32 at ``highest`` and at the TPU's default.

The module interface is described in ``perfbench/references/__init__.py``.
"""
from __future__ import annotations

import functools
import json
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perfbench import weights as wts

PRECISIONS = ("highest", "high", "bfloat16")


def out_size(h: int, k: int, stride: int, pad: int) -> int:
    return (h + 2 * pad - k) // stride + 1


def _conv(name, out, k, stride=1, pad=0, sparsity=0.0, groups=1):
    return {"name": name, "out": out, "k": k, "stride": stride, "pad": pad,
            "sparsity": sparsity, "groups": groups}


def _layer_conv(layer: Dict[str, Any]) -> Dict[str, Any]:
    return _conv(layer["conv"], layer["out"], layer["k"], layer["stride"],
                 layer["pad"], layer["sparsity"], layer.get("groups", 1))


def bottleneck_convs(b: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    n, sp = b["bottleneck"], b["sparsity"]
    convs = {
        "a": _conv(f"{n}/1x1a", b["mid"], 1, b["stride"], 0, sp),
        "b": _conv(f"{n}/3x3", b["mid"], 3, 1, 1, sp),
        "c": _conv(f"{n}/1x1b", b["out"], 1, 1, 0, sp),
    }
    if b["project"]:
        convs["proj"] = _conv(f"{n}/proj", b["out"], 1, b["stride"], 0, 0.0)
    return convs


def inception_convs(m: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    n, sp = m["inception"], m["sparsity"]
    return {
        "1x1": _conv(f"{n}/1x1", m["c1"], 1, sparsity=sp),
        "3x3_reduce": _conv(f"{n}/3x3_reduce", m["c3r"], 1, sparsity=sp),
        "3x3": _conv(f"{n}/3x3", m["c3"], 3, 1, 1, sp),
        "5x5_reduce": _conv(f"{n}/5x5_reduce", m["c5r"], 1, sparsity=sp),
        "5x5": _conv(f"{n}/5x5", m["c5"], 5, 1, 2, sp),
        "pool_proj": _conv(f"{n}/pool_proj", m["pool_proj"], 1, sparsity=sp),
    }


def _tables(config: Dict[str, Any]
            ) -> Tuple[List[Dict[str, Any]], List[Dict[str, Any]]]:
    """The convs and the FC layers of the network, in forward order."""
    c, h = config["channels"], config["image"]
    out: List[Dict[str, Any]] = []
    fcs: List[Dict[str, Any]] = []

    def add(cv, c_in, h_in, res=False):
        e = out_size(h_in, cv["k"], cv["stride"], cv["pad"])
        out.append(dict(cv, c=c_in, h=h_in, w=h_in, e=e, f=e, res=res))
        return cv["out"], e

    for layer in config["layers"]:
        if "conv" in layer:
            c, h = add(_layer_conv(layer), c, h)
        elif "maxpool" in layer:
            h = out_size(h, layer["maxpool"], layer["stride"], layer["pad"])
        elif "bottleneck" in layer:
            cv = bottleneck_convs(layer)
            c1, h1 = add(cv["a"], c, h)
            c2, h2 = add(cv["b"], c1, h1)
            if "proj" in cv:
                add(cv["proj"], c, h)
            c, h = add(cv["c"], c2, h2, res=True)
        elif "inception" in layer:
            cv = inception_convs(layer)
            c1, _ = add(cv["1x1"], c, h)
            cr, _ = add(cv["3x3_reduce"], c, h)
            c3, _ = add(cv["3x3"], cr, h)
            cr5, _ = add(cv["5x5_reduce"], c, h)
            c5, _ = add(cv["5x5"], cr5, h)
            cp, _ = add(cv["pool_proj"], c, h)
            c = c1 + c3 + c5 + cp
        elif "gap" in layer:
            h = 1
        elif "fc" in layer:
            fcs.append({"name": layer["fc"], "in_f": c * h * h,
                        "out_f": layer["out"],
                        "relu": layer.get("relu", False)})
            c, h = layer["out"], 1
        else:
            raise ValueError(f"unknown layer {layer}")
    return out, fcs


def conv_table(config: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Every conv of the network in forward order, with its input geometry
    (``c``, ``h``, ``w``), its output size (``e``, ``f``), its ``groups``,
    and ``res``: whether a shortcut is added to its output before the
    ReLU."""
    return _tables(config)[0]


def fc_layers(config: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The FC layers in forward order: name, fan-in (the flattened map
    before it), outputs, and whether a ReLU follows."""
    return _tables(config)[1]


@functools.lru_cache(maxsize=None)
def _weight_maker(config_json: str):
    config = json.loads(config_json)
    table = conv_table(config)
    bias_std = float(config["bias_std"])

    @jax.jit
    def make(kd):
        key = wts.weights_key(kd)
        out = {}
        for i, cv in enumerate(table):
            kw, kb = jax.random.split(jax.random.fold_in(key, i))
            fan_in = cv["c"] // cv["groups"] * cv["k"] * cv["k"]
            shape = (cv["out"], cv["c"] // cv["groups"], cv["k"], cv["k"])
            std = (2.0 / fan_in) ** 0.5
            w = jax.random.normal(kw, shape, jnp.float32) * std
            b = jax.random.normal(kb, (cv["out"],), jnp.float32) * bias_std
            out[cv["name"]] = (wts.prune(w, cv["sparsity"]), b)
        return out

    return make


def make_weights(config: Dict[str, Any], seed: int
                 ) -> Dict[str, Tuple[jax.Array, jax.Array]]:
    """``{conv name: (w (M, C/groups, K, K), b (M,))}`` on the default
    device, in one jitted call: conv i draws from the weights stream folded
    with i, N(0, 2/fan_in) pruned by magnitude at its sparsity, bias
    N(0, bias_std)."""
    return _weight_maker(json.dumps(config, sort_keys=True))(
        jnp.asarray(wts.key_data(seed)))


def fc_weights(config: Dict[str, Any], fc_seed: int) -> List[np.ndarray]:
    """The FC weights the configuration states, in forward order: each
    N(0, 1/in_f), drawn on the host one after the other from
    ``numpy.random.default_rng(fc_seed)`` as an (in_f, out_f) matrix."""
    rng = np.random.default_rng(int(fc_seed))
    return [rng.standard_normal((fc["in_f"], fc["out_f"])).astype(np.float32)
            * np.float32((1.0 / fc["in_f"]) ** 0.5)
            for fc in fc_layers(config)]


def forward(config: Dict[str, Any], weights: Dict[str, Any], fc_ws, x,
            precision: str = "highest"):
    """Logits (N, classes) of the network on images ``x`` (N, C, H, W).

    ``weights`` maps each conv name to ``(w, b)``, w of shape
    (M, C/groups, K, K); ``fc_ws`` holds the (in_f, out_f) FC matrices in forward order.
    Traceable: jit it with the weights as arguments.
    """
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    dt = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    prec = {"highest": lax.Precision.HIGHEST, "high": lax.Precision.HIGH,
            "bfloat16": lax.Precision.DEFAULT}[precision]

    def conv(cv, v, relu=True, shortcut=None):
        w, b = weights[cv["name"]]
        y = lax.conv_general_dilated(
            v, w.astype(dt), (cv["stride"], cv["stride"]),
            ((cv["pad"], cv["pad"]), (cv["pad"], cv["pad"])),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=cv["groups"], precision=prec,
            preferred_element_type=dt)
        y = y + b.astype(dt)[None, :, None, None]
        if shortcut is not None:
            y = y + shortcut
        return jnp.maximum(y, 0) if relu else y

    def maxpool(v, k, stride, pad):
        return lax.reduce_window(
            v, jnp.array(-jnp.inf, dt), lax.max, (1, 1, k, k),
            (1, 1, stride, stride), ((0, 0), (0, 0), (pad, pad), (pad, pad)))

    v = x.astype(dt)
    fcs = iter(fc_ws)
    for layer in config["layers"]:
        if "conv" in layer:
            v = conv(_layer_conv(layer), v, relu=layer.get("relu", True))
        elif "maxpool" in layer:
            v = maxpool(v, layer["maxpool"], layer["stride"], layer["pad"])
        elif "bottleneck" in layer:
            cv = bottleneck_convs(layer)
            y = conv(cv["b"], conv(cv["a"], v))
            sc = conv(cv["proj"], v, relu=False) if "proj" in cv else v
            v = conv(cv["c"], y, shortcut=sc)
        elif "inception" in layer:
            cv = inception_convs(layer)
            v = jnp.concatenate([
                conv(cv["1x1"], v),
                conv(cv["3x3"], conv(cv["3x3_reduce"], v)),
                conv(cv["5x5"], conv(cv["5x5_reduce"], v)),
                conv(cv["pool_proj"], maxpool(v, 3, 1, 1)),
            ], axis=1)
        elif "gap" in layer:
            v = v.mean(axis=(2, 3), keepdims=True)
        elif "fc" in layer:
            v = jnp.matmul(v.reshape(v.shape[0], -1), next(fcs).astype(dt),
                           precision=prec, preferred_element_type=dt)
            if layer.get("relu", False):
                v = jnp.maximum(v, 0)
    return v.astype(jnp.float32)

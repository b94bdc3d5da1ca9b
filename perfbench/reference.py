"""Plain reference of the benchmark's CNNs, built from a configuration file.

The configuration's ``layers`` list is the network as it is run: ``conv``,
``maxpool``, ``bottleneck`` (ResNet v1: 1x1 with the stride, 3x3, 1x1, plus
an identity or 1x1 projection shortcut, then ReLU), ``inception`` (four
branches concatenated on channels), ``gap`` and ``fc``.  Every conv is
followed by its bias and a ReLU, except the last 1x1 of a bottleneck and
its projection, whose sum takes the ReLU.

Straightforward ``jax.numpy``/``lax`` in NCHW, nothing of the program
under test imported.  ``precision="highest"`` is float32 at
``lax.Precision.HIGHEST`` (the reference); ``precision="high"`` is float32
at ``lax.Precision.HIGH`` (three bfloat16 passes) and
``precision="bfloat16"`` holds every array in bfloat16: the controls, one
precision below float32 at ``highest`` and at the TPU's default.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax.numpy as jnp
import numpy as np
from jax import lax

PRECISIONS = ("highest", "high", "bfloat16")


def out_size(h: int, k: int, stride: int, pad: int) -> int:
    return (h + 2 * pad - k) // stride + 1


def _conv(name, out, k, stride=1, pad=0, sparsity=0.0):
    return {"name": name, "out": out, "k": k, "stride": stride, "pad": pad,
            "sparsity": sparsity}


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


def conv_table(config: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Every conv of the network in forward order, with its input geometry
    (``c``, ``h``, ``w``), its output size (``e``, ``f``), and ``res``:
    whether a shortcut is added to its output before the ReLU."""
    c, h = config["channels"], config["image"]
    out: List[Dict[str, Any]] = []

    def add(cv, c_in, h_in, res=False):
        e = out_size(h_in, cv["k"], cv["stride"], cv["pad"])
        out.append(dict(cv, c=c_in, h=h_in, w=h_in, e=e, f=e, res=res))
        return cv["out"], e

    for layer in config["layers"]:
        if "conv" in layer:
            c, h = add(_conv(layer["conv"], layer["out"], layer["k"],
                             layer["stride"], layer["pad"],
                             layer["sparsity"]), c, h)
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
            pass
        else:
            raise ValueError(f"unknown layer {layer}")
    return out


def fc_layer(config: Dict[str, Any]) -> Dict[str, Any]:
    """The FC layer: its name, fan-in (channels after global pooling) and
    outputs."""
    c = config["channels"]
    for layer in config["layers"]:
        if "conv" in layer:
            c = layer["out"]
        elif "bottleneck" in layer:
            c = layer["out"]
        elif "inception" in layer:
            c = (layer["c1"] + layer["c3"] + layer["c5"]
                 + layer["pool_proj"])
        elif "fc" in layer:
            return {"name": layer["fc"], "in_f": c, "out_f": layer["out"]}
    raise ValueError("configuration has no fc layer")


def fc_weight(config: Dict[str, Any], fc_seed: int) -> np.ndarray:
    """The FC weights the configuration states: N(0, 1/in_f), drawn on the
    host by ``numpy.random.default_rng(fc_seed)`` as an (in_f, out_f)
    matrix."""
    fc = fc_layer(config)
    rng = np.random.default_rng(int(fc_seed))
    return (rng.standard_normal((fc["in_f"], fc["out_f"])).astype(np.float32)
            * np.float32((1.0 / fc["in_f"]) ** 0.5))


def forward(config: Dict[str, Any], weights: Dict[str, Any], fc_w, x,
            precision: str = "highest"):
    """Logits (N, classes) of the network on images ``x`` (N, C, H, W).

    ``weights`` maps each conv name to ``(w, b)``, w of shape (M, C, K, K);
    ``fc_w`` is the (in_f, out_f) FC matrix.  Traceable: jit it with the
    weights as arguments.
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
            dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=prec,
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
    for layer in config["layers"]:
        if "conv" in layer:
            v = conv(_conv(layer["conv"], layer["out"], layer["k"],
                           layer["stride"], layer["pad"]), v,
                     relu=layer.get("relu", True))
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
            v = jnp.matmul(v.reshape(v.shape[0], -1), fc_w.astype(dt),
                           precision=prec, preferred_element_type=dt)
    return v.astype(jnp.float32)

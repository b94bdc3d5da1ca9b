"""The system under test, as the benchmark drives it.

Everything the benchmark imports from the program goes through here: the
network table and its lowering, the parameter dict that ``CnnEngine`` and
``RobustCnnServer`` bind, the engine, the server and the program's own
telemetry.  The weights are the benchmark's (``perfbench.weights``); the
program builds its sparse formats from them as ``init_cnn`` would.
"""
from __future__ import annotations

import os
import sys
from typing import Any, Dict, List, Tuple

import numpy as np

from perfbench import reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def import_program():
    """Put the checkout's ``src`` on the path and return ``repro``."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro
    return repro


def network(config: Dict[str, Any]) -> Tuple[List[Any], Any]:
    """The program's layer spec for the configuration, and its lowering at
    the configuration's input, checked against the configuration: every
    conv's name, channels, kernel, stride, padding, input size and
    sparsity must agree, so the program runs the network the file states.
    """
    import_program()
    from repro.engine import lower
    from repro.models import cnn

    net = cnn.NETWORKS[config["program_network"]]()
    c, h = config["channels"], config["image"]
    program = lower(net, (c, h, h))
    want = {cv["name"]: cv for cv in reference.conv_table(config)}
    have = {op.name: op for op in program.conv_ops}
    if set(want) != set(have):
        raise SystemExit(
            f"program network {config['program_network']!r} has convs "
            f"{sorted(set(have) ^ set(want))} that the configuration "
            "does not, or lacks them")
    for name, cv in want.items():
        op = have[name]
        got = (op.m, op.c, op.h, op.k, op.stride, op.pad, op.e,
               float(op.sparsity), op.res is not None)
        exp = (cv["out"], cv["c"], cv["h"], cv["k"], cv["stride"], cv["pad"],
               cv["e"], float(cv["sparsity"]), cv["res"])
        if got != exp:
            raise SystemExit(f"conv {name}: program runs {got}, the "
                             f"configuration states {exp}")
    fc = reference.fc_layer(config)
    fcs = [(op.name, op.in_f, op.out_f) for op in program.fc_ops]
    if fcs != [(fc["name"], fc["in_f"], fc["out_f"])]:
        raise SystemExit(f"program FC layers {fcs} differ from {fc}")
    return net, program


def params(config: Dict[str, Any], weights: Dict[str, Any], fc_seed: int
           ) -> Dict[str, Any]:
    """The parameter dict the program binds, from the benchmark's weights:
    dense ``w`` and ``b`` on the device, and for each pruned conv the ELL
    banks the program builds from ``w`` (as ``init_cnn`` does)."""
    import_program()
    from repro.core.sparse_format import ell_from_dense, ell_from_dense_conv

    out: Dict[str, Any] = {}
    for cv in reference.conv_table(config):
        w, b = weights[cv["name"]]
        entry = {"w": w, "b": b}
        if cv["sparsity"] > 0:
            wh = np.asarray(w)
            entry["ell"] = ell_from_dense_conv(wh)
            entry["ell2d"] = ell_from_dense(wh.reshape(wh.shape[0], -1))
        out[cv["name"]] = entry
    out["_fc_rng"] = int(fc_seed)
    return out


def engine(program, prm):
    import_program()
    from repro.engine import CnnEngine
    return CnnEngine(program, prm)


def narrow_plan(plan: Dict[str, Any], value_dtype: str) -> Dict[str, Any]:
    """The same plan with every Pallas-kernel layer's weight values stored
    in ``value_dtype`` (the program's own quantised path)."""
    import dataclasses
    return {name: (dataclasses.replace(pe, value_dtype=value_dtype)
                   if pe.method in ("pallas", "bsr") else pe)
            for name, pe in plan.items()}


def methods(report) -> Dict[str, List[str]]:
    """Layers per executed method, from an ``ExecutionReport``."""
    out: Dict[str, List[str]] = {}
    for o in report.ops:
        out.setdefault(o.method_executed, []).append(o.name)
    return out


KERNEL_METHODS = ("pallas", "bsr")


def build(config: Dict[str, Any], seed: int):
    """Weights from the seed, the program's network and the parameter dict
    it binds: ``(weights, fc_seed, net, program, params)``."""
    from perfbench import weights as wts

    w = wts.make_weights(config, seed)
    fcs = wts.fc_seed(seed)
    net, program = network(config)
    return w, fcs, net, program, params(config, w, fcs)

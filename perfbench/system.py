"""The system under test, as the benchmark drives it.

Everything the benchmark imports from the program goes through here: the
network table and its lowering, the parameter dict that ``CnnEngine`` and
``RobustCnnServer`` bind, the engine, the server and the program's own
telemetry.  The weights are the benchmark's, drawn by the configuration's
reference module (``perfbench.references``), whose conv and FC tables the
program's network is checked against; the program builds its sparse
formats from them as ``init_cnn`` would.
"""
from __future__ import annotations

import os
import sys
from typing import Any, Dict, List, Tuple

import jax.numpy as jnp
import numpy as np

from perfbench import references

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
    the configuration's input, checked against the configuration's
    reference: every conv's name, channels, kernel, stride, padding, input
    size, sparsity and shortcut, and every FC layer's name, fan-in, outputs
    and ReLU, in order, must agree, so the program runs the network the
    file states.  A grouped conv of the reference is one conv over all its
    input channels in the program (``params`` gives it block-diagonal
    weights)."""
    import_program()
    from repro.engine import lower
    from repro.models import cnn

    ref = references.load(config)
    net = cnn.NETWORKS[config["program_network"]]()
    c, h = config["channels"], config["image"]
    program = lower(net, (c, h, h))
    want = {cv["name"]: cv for cv in ref.conv_table(config)}
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
    relu_of = {op.src for op in program.ops if type(op).__name__ == "ReluOp"}
    got_fc = [(op.name, op.in_f, op.out_f, op.out in relu_of)
              for op in program.fc_ops]
    exp_fc = [(fc["name"], fc["in_f"], fc["out_f"], fc["relu"])
              for fc in ref.fc_layers(config)]
    if got_fc != exp_fc:
        raise SystemExit(f"program FC layers {got_fc} differ from the "
                         f"configuration's {exp_fc}")
    return net, program


def block_diagonal(w, groups: int):
    """A grouped conv's weights (M, C/g, K, K) as the (M, C, K, K) weights
    of one conv over all C input channels: output group i reads input
    group i alone, every other weight is zero."""
    if groups == 1:
        return w
    m, cg = w.shape[0] // groups, w.shape[1]
    return jnp.concatenate(
        [jnp.pad(w[i * m:(i + 1) * m],
                 ((0, 0), (i * cg, (groups - 1 - i) * cg), (0, 0), (0, 0)))
         for i in range(groups)])


def params(config: Dict[str, Any], weights: Dict[str, Any], fc_seed: int
           ) -> Dict[str, Any]:
    """The parameter dict the program binds, from the benchmark's weights:
    dense ``w`` and ``b`` on the device (a grouped conv's ``w``
    block-diagonal), for each pruned conv the ELL banks the program builds
    from ``w`` (as ``init_cnn`` does), and the FC seed under ``_fc_rng``,
    from which the program draws the FC weights as the reference's
    ``fc_weights`` does."""
    import_program()
    from repro.core.sparse_format import ell_from_dense, ell_from_dense_conv

    out: Dict[str, Any] = {}
    for cv in references.load(config).conv_table(config):
        w, b = weights[cv["name"]]
        entry = {"w": block_diagonal(w, cv.get("groups", 1)), "b": b}
        if cv["sparsity"] > 0:
            wh = np.asarray(entry["w"])
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

    w = references.load(config).make_weights(config, seed)
    fcs = wts.fc_seed(seed)
    net, program = network(config)
    return w, fcs, net, program, params(config, w, fcs)

"""Operations and bytes a forward requires, from geometry and nonzeros.

Counts depend on the layer's shapes and on how many weights are nonzero,
never on the format, tiling or kernel that runs the layer, so a layer
counts the same whichever method implements it.

* A pruned conv requires ``2 * nnz * E * F`` operations per image (one
  multiply and one add per nonzero weight and output position), a dense
  conv ``2 * M * C * K * K * E * F``, the FC layer ``2 * in_f * out_f``.
  Pools, adds and concatenations are not counted.
* A conv's bytes are its input map read once, its output map written once,
  the fused shortcut read once where the layer has one, and its nonzero
  weight values, all at the configuration's dtype.  Indices and padding of
  any sparse format are not required and not counted.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable

import numpy as np

from perfbench import reference

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def conv_counts(cv: Dict[str, Any], nnz: int, batch: int,
                dtype: str = "float32") -> Dict[str, float]:
    """``{"flops", "bytes"}`` one conv requires for ``batch`` images;
    ``nnz`` is its number of nonzero weights (all of them when dense)."""
    item = DTYPE_BYTES[dtype]
    out_px = cv["e"] * cv["f"]
    flops = 2.0 * nnz * out_px * batch
    in_map = cv["c"] * cv["h"] * cv["w"]
    out_map = cv["out"] * out_px
    maps = in_map + out_map * (2 if cv["res"] else 1)
    return {"flops": flops, "bytes": float(item * (batch * maps + nnz))}


def nonzeros(weights: Dict[str, Any]) -> Dict[str, int]:
    """Nonzero weights of each conv, from ``{name: (w, b)}``."""
    return {name: int(np.count_nonzero(np.asarray(w)))
            for name, (w, _) in weights.items()}


def forward_flops(config: Dict[str, Any], nnz: Dict[str, int],
                  batch: int) -> float:
    """Operations one forward of ``batch`` images requires: every conv at
    its nonzeros (a dense conv's nonzeros are all its weights), plus the
    FC layer, which runs dense."""
    table = reference.conv_table(config)
    fc = reference.fc_layer(config)
    total = sum(conv_counts(cv, nnz[cv["name"]], batch)["flops"]
                for cv in table)
    return total + 2.0 * fc["in_f"] * fc["out_f"] * batch


def least_seconds(counts: Iterable[Dict[str, float]], flops_per_s: float,
                  bytes_per_s: float) -> float:
    """Sum over layers of each layer's least time on the chip: the larger
    of its operations over peak FLOP/s and its bytes over HBM bandwidth."""
    return sum(max(c["flops"] / flops_per_s, c["bytes"] / bytes_per_s)
               for c in counts)


"""The comparison that decides ``correct``.

Every answer the timed path produced in the window (one row of logits per
image) is compared with the plain reference's logits for the same image.
The number compared is the worst row's error relative to that row's
largest reference logit::

    max_err = max over rows i of  max_j |y_ij - ref_ij| / max_j |ref_ij|

A row that is missing, not finite or of the wrong shape reads infinity.
The limit of each cell lives in its cell file, with the readings it was set
from in ``PERF.md``.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence

import jax
import numpy as np

from perfbench import references


def row_errors(y: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per row: ``max |y - ref| / max |ref|``; inf where ``y`` is not
    finite."""
    y = np.asarray(y, np.float64)
    ref = np.asarray(ref, np.float64)
    if y.shape != ref.shape:
        return np.full(ref.shape[0], np.inf)
    err = np.abs(y - ref).max(axis=1) / np.abs(ref).max(axis=1)
    err[~np.isfinite(y).all(axis=1)] = np.inf
    return err


def reference_logits(config, weights, fc_ws, images, block: int = 32,
                     precision: str = "highest") -> np.ndarray:
    """The logits of the configuration's reference for ``images``
    (N, C, H, W), ``block`` rows at a time, under ``jax.jit`` with the
    weights as arguments."""
    forward = references.load(config).forward
    fn = jax.jit(lambda w, f, x: forward(config, w, f, x, precision))
    fc_ws = jax.device_put(fc_ws)
    out = [np.asarray(fn(weights, fc_ws, images[i:i + block]))
           for i in range(0, images.shape[0], block)]
    return np.concatenate(out)


def passes(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(k in numbers and math.isfinite(numbers[k])
               and numbers[k] <= limits[k] for k in limits)


def worst(errors: Sequence[np.ndarray]) -> float:
    errs = [np.asarray(e) for e in errors if np.size(e)]
    return float(max(e.max() for e in errs)) if errs else math.inf

"""The paper's CNN benchmark models: AlexNet, GoogLeNet (v1), ResNet-50.

Each network is a table of layer specs; convolutions execute through a
selectable method (paper Table/Figs 8-11):

  "dense"      -- XLA dense conv on zero-filled weights   (CUBLAS analogue)
  "lowered"    -- im2col + ELL(CSR) SpMM                  (CUSPARSE analogue)
  "csr-direct" -- Escoin direct sparse conv, pure-JAX scan
  "pallas"     -- Escoin direct sparse conv, Pallas kernel (interpret on CPU)
                  with the bias/ReLU/shortcut epilogue fused in-kernel and
                  the halo DMA double-buffered whenever it fits VMEM
  "bsr"        -- block-sparse (BCSR) direct conv on the MXU: blocked
                  weight tiles contracted against on-chip-gathered im2col
                  patch tiles — dense-unit throughput for moderately-sparse
                  layers (Pallas kernel, interpret on CPU)
  "auto"       -- per-layer dispatch through a tuned plan from repro.tuning
                  (the paper's kernel customization, measurement-driven);
                  plan entries carry the full schedule: method, (tm, te)
                  tiling, pad_to, fused epilogue, pipelined staging,
                  nnz-balanced channel packing, and the BCSR block shape

Execution goes through the compile-once graph engine (``repro.engine``):
the nested spec is lowered exactly once into a flat typed op program —
``init_cnn``, ``cnn_forward`` and ``conv_layer_shapes`` all delegate to
that single lowering pass instead of each re-walking the spec — and
``cnn_forward`` runs the program through a ``CnnEngine`` with a cached
``jax.jit`` per (method, input geometry).

Per-layer sparsities default to the Deep-Compression-era profile the paper's
SkimCaffe models carry (first conv kept dense — pruning conv1 hurts accuracy,
and the paper's models likewise keep some layers dense).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.engine import CnnEngine, METHODS, init_conv_params, lower
# Layer-spec vocabulary (historical home: this module; canonical home:
# repro.engine.spec — re-exported so existing callers keep working).
from repro.engine.spec import FC, Concat, Conv, Pool, Relu, Residual  # noqa: F401

CONV_METHODS = METHODS


# --------------------------------------------------------------------------
# network tables
# --------------------------------------------------------------------------

def alexnet() -> List[Any]:
    # Paper Table 3: 5 CONV layers, 4 sparse (conv1 dense).  Caffe AlexNet.
    return [
        Conv("conv1", 96, 11, 4, 0, sparsity=0.0), Relu(), Pool("max", 3, 2),
        Conv("conv2", 256, 5, 1, 2, sparsity=0.62), Relu(), Pool("max", 3, 2),
        Conv("conv3", 384, 3, 1, 1, sparsity=0.65), Relu(),
        Conv("conv4", 384, 3, 1, 1, sparsity=0.63), Relu(),
        Conv("conv5", 256, 3, 1, 1, sparsity=0.63), Relu(), Pool("max", 3, 2),
        FC("fc6", 4096, 0.91), Relu(), FC("fc7", 4096, 0.91),
        Relu(), FC("fc8", 1000, 0.75),
    ]


def _inception(name: str, c1: int, c3r: int, c3: int, c5r: int, c5: int,
               pp: int, sp: float) -> Concat:
    return Concat(branches=(
        (Conv(f"{name}/1x1", c1, 1, sparsity=sp), Relu()),
        (Conv(f"{name}/3x3_reduce", c3r, 1, sparsity=sp), Relu(),
         Conv(f"{name}/3x3", c3, 3, 1, 1, sparsity=sp), Relu()),
        (Conv(f"{name}/5x5_reduce", c5r, 1, sparsity=sp), Relu(),
         Conv(f"{name}/5x5", c5, 5, 1, 2, sparsity=sp), Relu()),
        (Pool("max", 3, 1, 1),
         Conv(f"{name}/pool_proj", pp, 1, sparsity=sp), Relu()),
    ))


def googlenet() -> List[Any]:
    # GoogLeNet v1 (57 CONV); the paper prunes 19 of them — we mark the 3x3/5x5
    # convs of the later inception modules sparse, reduces + early layers dense.
    s = 0.7
    return [
        Conv("conv1", 64, 7, 2, 3, sparsity=0.0), Relu(), Pool("max", 3, 2, 1),
        Conv("conv2_reduce", 64, 1, sparsity=0.0), Relu(),
        Conv("conv2", 192, 3, 1, 1, sparsity=0.62), Relu(), Pool("max", 3, 2, 1),
        _inception("3a", 64, 96, 128, 16, 32, 32, 0.0),
        _inception("3b", 128, 128, 192, 32, 96, 64, s),
        Pool("max", 3, 2, 1),
        _inception("4a", 192, 96, 208, 16, 48, 64, s),
        _inception("4b", 160, 112, 224, 24, 64, 64, s),
        _inception("4c", 128, 128, 256, 24, 64, 64, s),
        _inception("4d", 112, 144, 288, 32, 64, 64, s),
        _inception("4e", 256, 160, 320, 32, 128, 128, s),
        Pool("max", 3, 2, 1),
        _inception("5a", 256, 160, 320, 32, 128, 128, s),
        _inception("5b", 384, 192, 384, 48, 128, 128, s),
        Pool("gap"),
        FC("fc", 1000, 0.8),
    ]


def _bottleneck(name: str, mid: int, out: int, stride: int, sp: float,
                project: bool) -> Residual:
    body = (
        Conv(f"{name}/1x1a", mid, 1, stride, 0, sparsity=sp), Relu(),
        Conv(f"{name}/3x3", mid, 3, 1, 1, sparsity=sp), Relu(),
        Conv(f"{name}/1x1b", out, 1, sparsity=sp),
    )
    proj = Conv(f"{name}/proj", out, 1, stride, 0, sparsity=0.0) if project else None
    return Residual(body=body, proj=proj)


def resnet50() -> List[Any]:
    # 53 CONV layers; the paper's model has 16 sparse CONV layers — we prune
    # the 3x3 convs of stages 2-4 (16 of them), matching that count.
    layers: List[Any] = [
        Conv("conv1", 64, 7, 2, 3, sparsity=0.0), Relu(), Pool("max", 3, 2, 1)]
    stages = [("res2", 64, 256, 3, 0.0), ("res3", 128, 512, 4, 0.7),
              ("res4", 256, 1024, 6, 0.7), ("res5", 512, 2048, 3, 0.7)]
    for sname, mid, out, blocks, sp in stages:
        for b in range(blocks):
            stride = 2 if (b == 0 and sname != "res2") else 1
            layers.append(_bottleneck(f"{sname}{chr(97 + b)}", mid, out, stride,
                                      sp, project=(b == 0)))
            layers.append(Relu())
    layers += [Pool("gap"), FC("fc", 1000, 0.8)]
    return layers


NETWORKS = {"alexnet": alexnet, "googlenet": googlenet, "resnet50": resnet50}


# --------------------------------------------------------------------------
# engine delegation: one lowering pass feeds init, forward, and shape tables
# --------------------------------------------------------------------------

def _lowered(net: Sequence[Any], in_c: int, h: int, w: int):
    """Lower a spec once per (net, input geometry); memoized."""
    key = (tuple(net), in_c, h, w)
    prog = _PROGRAMS.get(key)
    if prog is None:
        prog = lower(net, (in_c, h, w))
        if len(_PROGRAMS) > 64:
            _PROGRAMS.clear()
        _PROGRAMS[key] = prog
    return prog


_PROGRAMS: Dict[Any, Any] = {}


def _params_fingerprint(params: Dict[str, Any]) -> Tuple[Any, ...]:
    """Identity snapshot of every parameter leaf.

    jax arrays are immutable, so any update — replacing a weight, or
    ``apply_plan_to_params`` adding ``ell_auto`` formats — rebinds a dict
    entry to a *new* object.  Fingerprinting leaf identities lets the
    engine memo detect such updates and rebind instead of replaying a jit
    that baked the old arrays in as constants (the legacy eager executor
    re-read params every call; compiled replay must not silently diverge
    from that).
    """
    out = []
    for name, entry in params.items():
        if isinstance(entry, dict):
            out.append((name, tuple((k, id(v)) for k, v in entry.items())))
        else:
            out.append((name, id(entry)))
    return tuple(out)


def engine_for(net: Sequence[Any], params: Dict[str, Any],
               in_shape: Tuple[int, int, int],
               plan: Optional[Dict[str, Any]] = None) -> CnnEngine:
    """A bound :class:`~repro.engine.CnnEngine` for (net, params, geometry).

    Engines are memoized on the lowered program plus the *identity* of
    ``params``/``plan`` — and a fingerprint of the parameter leaves, so a
    params update after a forward binds a fresh engine — letting repeated
    ``cnn_forward`` calls reuse each engine's per-(method, shape) compiled
    executables.
    """
    c, h, w = (int(d) for d in in_shape)
    program = _lowered(net, c, h, w)
    key = (id(program), id(params), id(plan))
    fp = _params_fingerprint(params)
    hit = _ENGINES.get(key)
    # id() can be recycled after gc: verify the cached engine still binds
    # the same live objects (and the same parameter leaves) before reusing.
    if (hit is not None and hit[1] == fp):
        eng = hit[0]
        if eng.program is program and eng.params is params and eng.plan is plan:
            return eng
    if len(_ENGINES) > 64:
        _ENGINES.clear()
    eng = CnnEngine(program, params, plan)
    _ENGINES[key] = (eng, fp)
    return eng


_ENGINES: Dict[Any, Tuple[CnnEngine, Tuple[Any, ...]]] = {}


def init_cnn(net: Sequence[Any], in_c: int, rng: np.random.Generator,
             image: int = 224) -> Dict[str, Any]:
    """Random pruned weights for every layer (magnitude pruning at each
    layer's configured sparsity), plus precomputed Escoin formats.

    Delegates to the engine's single lowering pass — the conv table drives
    RNG draws in the historical spec-walk order, so weights are
    bit-identical to the pre-engine walker's.
    """
    return init_conv_params(_lowered(net, in_c, image, image), rng)


def cnn_forward(net: Sequence[Any], params: Dict[str, Any], x: jax.Array,
                method: str = "dense",
                plan: Optional[Dict[str, Any]] = None) -> jax.Array:
    """Run the whole network; FC layers run dense (paper measures CONV).

    ``method="auto"`` dispatches each conv through its tuned plan entry
    (``repro.tuning``).  With no plan supplied, a roofline-mode plan is
    computed on the fly from the input geometry (no measurement needed).
    Execution is the engine's cached-jit program replay; FC weights come
    from the engine bind (never created inside a trace).
    """
    engine = engine_for(net, params, x.shape[1:], plan)
    return engine(x, method)


def conv_layer_shapes(net: Sequence[Any], in_c: int, image: int,
                      ) -> List[Tuple[Conv, Tuple[int, int, int]]]:
    """Static (layer, (C, H, W)) input-shape table for benchmarks."""
    return list(_lowered(net, in_c, image, image).conv_table)

"""Both sparse conv kernels compile for a TPU v5e, at real ResNet-50 layers.

Interpret mode runs every Pallas kernel on the CPU without asking Mosaic,
the TPU kernel compiler, whether it can lower it.  These tests compile the
kernels for a *described* v5e chip — the TPU compiler is installed, no chip
is needed — at the geometries of the 224-px ResNet-50 forward: the res3
3x3 (28x28, stride 1), the res3a 1x1a that strides 56x56 down to 28x28, the
res5 3x3 at 7x7, and a res3 1x1b with its fused shortcut; f32 and int8
value streams.  Each compiled program must hold the kernel's custom call.
The ELL kernel's pointwise schedule (1x1, stride 1) compiles at res5 1x1b
with its shortcut and at res4 1x1a, the layers whose input block fills
the VMEM budget.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import bcsr_conv_from_dense, ell_from_dense_conv
from repro.core.pruning import magnitude_prune
from repro.core.sparse_format import quantize_values
from repro.kernels.bsr_conv.ops import bsr_conv, resolve_bsr_schedule
from repro.kernels.sparse_conv.ops import resolve_schedule, sparse_conv

pytestmark = pytest.mark.pallas

BATCH = 8

# (name, C, H, M, R, stride, pad, fused residual): ResNet-50 at 224 px.
LAYERS = {
    "res3_3x3": (128, 28, 128, 3, 1, 1, False),
    "res3a_1x1a_s2": (256, 56, 128, 1, 2, 0, False),
    "res5_3x3": (512, 7, 512, 3, 1, 1, False),
    "res3_1x1b_res": (128, 28, 512, 1, 1, 0, True),
    "res5_1x1b_res": (512, 7, 2048, 1, 1, 0, True),
    "res4_1x1a": (1024, 14, 256, 1, 1, 0, False),
}
CASES = [("res3_3x3", "float32"), ("res3_3x3", "int8"),
         ("res3a_1x1a_s2", "float32"), ("res5_3x3", "float32"),
         ("res3_1x1b_res", "float32")]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # The TPU compiler logs under /tmp unless told otherwise.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep these tests out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _layer(name, seed=0):
    c, h, m, r, stride, pad, res = LAYERS[name]
    rng = np.random.default_rng(seed)
    w = np.asarray(magnitude_prune(jnp.asarray(
        rng.standard_normal((m, c, r, r)).astype(np.float32)), 0.7))
    e = (h + 2 * pad - r) // stride + 1
    return w, dict(stride=stride, padding=pad), (c, h, m, r, e, res)


def _compile(one_chip, fn, *args):
    spec = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    text = jax.jit(fn).lower(*spec).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("layer,value_dtype", CASES)
def test_ell_kernel_compiles_for_v5e(layer, value_dtype, one_chip,
                                     no_persistent_cache):
    w, conv, (c, h, m, r, e, res) = _layer(layer)
    ell = ell_from_dense_conv(w)
    if value_dtype != "float32":
        ell = quantize_values(ell, value_dtype)
    sched, reason = resolve_schedule(m, c, e, e, ell.k, r, r, conv["stride"],
                                     fuse_res=res, value_dtype=value_dtype)
    assert reason is None, reason
    x = np.zeros((BATCH, c, h, h), np.float32)
    bias = np.zeros((m,), np.float32)
    shortcut = np.zeros((BATCH, m, e, e), np.float32) if res else None

    def fwd(x, ell, bias, shortcut):
        return sparse_conv(x, ell, bias=bias, fuse_relu=True,
                           residual=shortcut, **conv)

    _compile(one_chip, fwd, x, ell, bias, shortcut)


# (layer, value dtype, batch, the pointwise pixel tile): at batch 32 res5
# 1x1b takes 16 rows, the whole budget with its shortcut; res4 1x1a (1024
# input channels) takes 8 rows at any batch.
POINTWISE_CASES = [("res5_1x1b_res", "float32", 32, 16),
                   ("res4_1x1a", "float32", 32, 8),
                   ("res4_1x1a", "int8", 8, 8)]


@pytest.mark.parametrize("layer,value_dtype,batch,tp", POINTWISE_CASES)
def test_ell_pointwise_compiles_for_v5e(layer, value_dtype, batch, tp,
                                        one_chip, no_persistent_cache):
    w, conv, (c, h, m, r, e, res) = _layer(layer)
    ell = ell_from_dense_conv(w)
    if value_dtype != "float32":
        ell = quantize_values(ell, value_dtype)
    sched, reason = resolve_schedule(m, c, e, e, ell.k, r, r, 1, n=batch,
                                     tm=128, fuse_res=res,
                                     value_dtype=value_dtype)
    assert reason is None and sched.tp == tp, (sched, reason)
    x = np.zeros((batch, c, h, h), np.float32)
    bias = np.zeros((m,), np.float32)
    shortcut = np.zeros((batch, m, e, e), np.float32) if res else None

    def fwd(x, ell, bias, shortcut):
        return sparse_conv(x, ell, bias=bias, fuse_relu=True,
                           residual=shortcut, tm=128, **conv)

    _compile(one_chip, fwd, x, ell, bias, shortcut)


@pytest.mark.parametrize("layer,value_dtype", CASES)
def test_bsr_kernel_compiles_for_v5e(layer, value_dtype, one_chip,
                                     no_persistent_cache):
    w, conv, (c, h, m, r, e, res) = _layer(layer)
    bc = bcsr_conv_from_dense(w, block=(8, 128))
    if value_dtype != "float32":
        bc = quantize_values(bc, value_dtype)
    gbm, kb, bm, bn = bc.blocks.shape
    sched, reason = resolve_bsr_schedule(c, e, e, r, r, conv["stride"], bm,
                                         bn, gbm, kb, fuse_res=res,
                                         value_dtype=value_dtype)
    assert reason is None, reason
    x = np.zeros((BATCH, c, h, h), np.float32)
    bias = np.zeros((m,), np.float32)
    shortcut = np.zeros((BATCH, m, e, e), np.float32) if res else None

    def fwd(x, bc, bias, shortcut):
        return bsr_conv(x, bc, bias=bias, fuse_relu=True, residual=shortcut,
                        **conv)

    _compile(one_chip, fwd, x, bc, bias, shortcut)


@pytest.mark.parametrize("method,kernel,r", [
    ("pallas", "sparse_conv_pallas", 3), ("bsr", "bsr_conv_pallas", 3),
    ("pallas", "sparse_conv_pallas", 1)])
def test_engine_layer_scopes_keep_kernel_names(method, kernel, r, one_chip,
                                               no_persistent_cache):
    """Each engine op runs under a named scope of its layer: the compiled
    program's metadata carries the layer, while the kernel's custom call
    keeps the name a profiler trace reports it by (``<kernel>.<n>``) — the
    ELL kernel's pointwise schedule (a 1x1 ``c1``) included."""
    import re

    from repro.engine import CnnEngine, lower
    from repro.models import cnn

    net = [cnn.Conv("c0", 128, 3, 1, 1, sparsity=0.0), cnn.Relu(),
           cnn.Conv("c1", 128, r, 1, r // 2, sparsity=0.7), cnn.Relu(),
           cnn.Pool("gap"), cnn.FC("fc", 10)]
    params = cnn.init_cnn(net, 3, np.random.default_rng(0), 28)
    engine = CnnEngine(lower(net, (3, 28, 28)), params)
    engine.interpret = False
    x = jax.ShapeDtypeStruct((BATCH, 3, 28, 28), jnp.float32,
                             sharding=one_chip)
    lowered = engine.lowered(x, method)
    assert kernel in lowered.as_text()
    text = lowered.compile().as_text()
    calls = re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', text, re.M)
    assert len(calls) == 1 and re.fullmatch(kernel + r"\.\d+", calls[0])
    assert re.search(r'op_name="[^"]*/c1/jit\(' + kernel, text)

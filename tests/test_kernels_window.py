"""The staged halo window both sparse conv kernels read, and what it costs.

On a v5e, a DMA that copies a partial sublane tile into a VMEM buffer
never signals its semaphore: the kernel hangs in its wait.  Neither
interpret mode nor a compile for a described chip notices.  So these tests
pin the staged layout (``kernels/window.py``) and the VMEM working sets
(``kernels/budget.py``) to byte counts worked out by hand at real
ResNet-50 layers, and trace every kernel the three networks can launch to
check that what it stages is whole sublane tiles.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.engine import lower
from repro.kernels import budget
from repro.kernels.bsr_conv.kernel import bsr_conv_pallas
from repro.kernels.bsr_conv.ops import BLOCK_CANDIDATES, bsr_tile_candidates
from repro.kernels.sparse_conv.kernel import sparse_conv_pallas
from repro.kernels.sparse_conv.ops import tile_candidates
from repro.kernels.window import halo_extent, stage_shape, sublane_tile
from repro.models import cnn
from repro.tuning.planner import geometry_of_op

pytestmark = pytest.mark.pallas


@pytest.mark.parametrize("args,expect", [
    # res3 3x3, whole map: 30 halo rows -> 32; 28 + 2 columns -> one tile.
    (dict(c=128, r=3, s=3, stride=1, te=28, f=28), (128, 32, 128)),
    # res3 3x3, 8-row tiles: 10 halo rows -> 16.
    (dict(c=128, r=3, s=3, stride=1, te=8, f=28), (128, 16, 128)),
    # res3a 1x1a, stride 2 from 56x56: one phase plane per channel (S=1),
    # 55 halo rows -> 56.
    (dict(c=256, r=1, s=1, stride=2, te=28, f=28), (256, 56, 128)),
    # a 3x3 at stride 2: two phase planes per channel, 17 rows -> 24, and
    # 28 + 1 phase-plane columns.
    (dict(c=64, r=3, s=3, stride=2, te=8, f=28), (128, 24, 128)),
    # res5 3x3 on bf16 input: 9 halo rows -> 16 (16-row bf16 tiles).
    (dict(c=512, r=3, s=3, stride=1, te=7, f=7, itemsize=2), (512, 16, 128)),
])
def test_stage_shape_pins_resnet50_layers(args, expect):
    assert stage_shape(**args) == expect


def test_ell_vmem_bytes_pins_res3_3x3():
    """res3 3x3 (C=128, 28x28, stride 1) at tm=8, te=28."""
    args = dict(c=128, f=28, r=3, s=3, stride=1, tm=8, te=28)
    halo = 128 * 32 * 128 * 4       # 128 planes of 32 x 128, f32
    out = 2 * 8 * 32 * 128 * 4      # 8 channels of 28 x 28 (32 x 128), x2
    assert budget.ell_vmem_bytes(**args) == halo + out == 2359296
    assert budget.ell_vmem_bytes(**args, pipeline=True) == 2 * halo + out
    assert budget.ell_vmem_bytes(**args, fuse_res=True) == halo + 2 * out


def test_ell_vmem_bytes_pins_res3a_1x1a_stride2():
    """res3a 1x1a (C=256, 56x56 -> 28x28, stride 2) at tm=8, te=8."""
    halo = 256 * 16 * 128 * 4       # 15 halo rows -> 16, f32
    out = 2 * 8 * 8 * 128 * 4       # 8 channels of 8 x 28 (8 x 128), x2
    assert budget.ell_vmem_bytes(256, 28, 1, 1, 2, 8, 8) == halo + out


def test_bsr_vmem_bytes_pins_res3_3x3():
    """res3 3x3 at the (8, 128) block, te=28: the staged block, the
    (128, 28, 28) patch scratch and its flat f32 (128, 784) operand, the
    double-buffered weight, out and bias tiles."""
    args = dict(c=128, r=3, s=3, stride=1, bm=8, bn=128, te=28, f=28)
    halo = 128 * 32 * 128 * 4
    patch = 128 * 32 * 128 * 4 + 128 * 896 * 4
    weight = 2 * 8 * 128 * 4
    out = 2 * 8 * 896 * 4
    bias = 2 * 8 * 128 * 4
    total = halo + patch + weight + out + bias
    assert budget.bsr_vmem_bytes(**args) == total == 4726784
    # int8 weights: an (8, 128) int8 tile pads to 32 sublanes, the same
    # bytes; the scale tile adds a second double-buffered (8, 1) f32 tile.
    assert budget.bsr_vmem_bytes(**args, value_itemsize=1,
                                 quantized=True) == total + bias
    # the fused residual: an f32 (8, 784) tile, double-buffered
    assert budget.bsr_vmem_bytes(**args, fuse_res=True) == total + out


def _staged_block(fn, *shapes):
    """The VMEM staging buffer a kernel allocates, read from its traced
    jaxpr (nothing runs): the first scratch operand of its pallas_call."""
    jaxpr = jax.make_jaxpr(fn)(*shapes).jaxpr
    todo = [jaxpr]
    while todo:
        for eqn in todo.pop().eqns:
            if eqn.primitive.name == "pallas_call":
                n_scratch = eqn.params["grid_mapping"].num_scratch_operands
                refs = eqn.params["jaxpr"].invars[-n_scratch:]
                return refs[0].aval.inner_aval
            for p in eqn.params.values():
                inner = getattr(p, "jaxpr", p)
                if hasattr(inner, "eqns"):
                    todo.append(inner)
    raise AssertionError("no pallas_call in the traced function")


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("net", sorted(cnn.NETWORKS))
def test_kernels_stage_whole_sublane_tiles(net, dtype):
    """Every row tiling the candidate spaces can emit for a sparse conv of
    the three networks at 224 px — ELL blocking and pipelined, BCSR at
    every block shape — stages a block whose rows are whole sublane tiles
    of the input dtype and cover the halo band."""
    program = lower(cnn.NETWORKS[net](), (3, 224, 224))
    dt = jnp.dtype(dtype)
    sub = sublane_tile(dt.itemsize)
    seen = set()
    for op in program.conv_ops:
        if op.sparsity <= 0:
            continue
        g = geometry_of_op(op)
        k = g.k_est(8)
        xpad = _sds((1, g.c, g.hp, g.wp), dt)
        row = _sds((g.m,))
        tiles = [("pallas", tm, te, pipe) for pipe in (False, True)
                 for tm, te in tile_candidates(g.m, g.c, g.e, g.f, k, g.r,
                                               g.s, g.stride, pipeline=pipe)]
        tiles += [("bsr", bm, te, bn) for bm, bn in BLOCK_CANDIDATES
                  for te in bsr_tile_candidates(g.c, g.e, g.f, g.r, g.s,
                                                g.stride, bm, bn,
                                                itemsize=dt.itemsize)]
        for kind, a, te, b in tiles:
            key = (kind, g.c, g.hp, g.wp, g.r, g.stride, te, b)
            if key in seen:
                continue
            seen.add(key)
            geo = dict(rs=g.r * g.s, s=g.s, e=g.e, f=g.f, stride=g.stride,
                       te=te)
            if kind == "pallas":
                block = _staged_block(
                    lambda x, v, i, n, bias, tm=a, pipe=b: sparse_conv_pallas(
                        x, v, i, n, bias, tm=tm, k=k, pipeline=pipe, **geo),
                    xpad, _sds((g.m, k)), _sds((g.m, k), jnp.int32),
                    _sds((g.m,), jnp.int32), row)
            else:
                gbm = -(-g.m // a)
                block = _staged_block(
                    lambda x, w, col, nb, bias: bsr_conv_pallas(
                        x, w, col, nb, bias, **geo),
                    xpad, _sds((gbm, 1, a, b)), _sds((gbm, 1), jnp.int32),
                    _sds((gbm,), jnp.int32), _sds((gbm, a)))
            rows = block.shape[-2]
            band = halo_extent(min(te, g.e), g.stride, g.r)
            assert rows % sub == 0 and rows >= band, (
                f"{net} {op.name} {kind}: stages {rows} rows for a "
                f"{band}-row band ({dtype}, {sub}-row tiles)")
    assert seen

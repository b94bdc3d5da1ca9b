"""BCSR MXU conv kernel: interpret-mode parity grids vs the dense oracle,
the blocked structural mirror (bit-identity), and the ELL Pallas path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (bcsr_conv_from_dense, block_prune_conv,
                        ell_from_dense_conv, magnitude_prune)
from repro.core.direct_conv import direct_sparse_conv, out_spatial
from repro.kernels.bsr_conv import ops
from repro.kernels.bsr_conv.ops import (bsr_conv, bsr_smem_fits,
                                        bsr_tile_candidates, bsr_tiling_fits)
from repro.kernels.bsr_conv.ref import (bsr_conv_blocked_ref, bsr_conv_ref)
from repro.kernels.sparse_conv.ops import sparse_conv

pytestmark = pytest.mark.pallas


def _row_tile(e, f):
    """The smallest ragged row tile the TPU can block for an E x F output —
    the kernel's flat (bm, TE*F) out block needs TE*F to be a multiple of
    128 lanes — or all of E when no smaller one exists."""
    return next((t for t in range(1, e) if (t * f) % 128 == 0), e)


def _case(seed, n, c, h, w, m, r, sp, block, *, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((n, c, h, w)), dtype=dtype)
    wt = np.asarray(block_prune_conv(
        jnp.asarray(rng.standard_normal((m, c, r, r)).astype(np.float32)),
        sp, block))
    return rng, x, wt


# ---------------------------------------------------------------------------
# parity grid: stride x padding x residual x bf16 x edge tiles x block sizes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("pad", [0, 1])
@pytest.mark.parametrize("residual", [False, True])
@pytest.mark.parametrize("block", [(4, 8), (8, 16)])
def test_bsr_parity_grid(stride, pad, residual, block):
    """The full grid with edge tiles (te deliberately not dividing E where
    a blockable one exists) and a non-dividing M (channel padding path),
    against the dense oracle — and bit-identical to the blocked structural
    mirror on the untiled schedule."""
    n, c, h, w, m, r = 2, 4, 13, 18, 12, 3
    seed = 5000 + 1000 * stride + 100 * pad + 10 * residual + block[0]
    rng, x, wt = _case(seed, n, c, h, w, m, r, 0.6, block)
    bc = bcsr_conv_from_dense(wt, block=block)
    assert bc.gbm * block[0] >= m
    bias = jnp.asarray(rng.standard_normal((m,)).astype(np.float32))
    e, f = out_spatial(h, w, r, r, stride, pad)
    res = (jnp.asarray(rng.standard_normal((n, m, e, f)).astype(np.float32))
           if residual else None)
    got = bsr_conv(x, bc, stride=stride, padding=pad, te=_row_tile(e, f),
                   bias=bias, fuse_relu=True, residual=res, interpret=True)
    ref = bsr_conv_ref(x, jnp.asarray(wt), stride=stride, padding=pad)
    ref = jax.nn.relu(ref + bias[None, :, None, None]
                      + (res.astype(jnp.float32) if res is not None else 0.0))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=1e-5, atol=1e-5)
    # Bit-identity anchor: the untiled kernel is the exact op sequence of
    # the blocked mirror (same patch gathers, same per-KB dot_general
    # accumulation order, same f32 epilogue).
    got_untiled = bsr_conv(x, bc, stride=stride, padding=pad,
                           bias=bias, fuse_relu=True, residual=res,
                           interpret=True)
    mirror = bsr_conv_blocked_ref(x, bc, stride=stride, padding=pad,
                                  bias=bias, fuse_relu=True, residual=res)
    np.testing.assert_array_equal(np.asarray(got_untiled, np.float32),
                                  np.asarray(mirror, np.float32))


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("block", [(4, 8), (8, 32)])
def test_bsr_parity_bf16(stride, block):
    """bf16 inputs/weights with f32 accumulation: tolerance is the bf16
    rounding of the conv itself (the contraction is f32)."""
    n, c, h, w, m, r, pad = 1, 4, 12, 12, 8, 3, 1
    rng, x, wt = _case(7000 + stride + block[1], n, c, h, w, m, r, 0.6,
                       block, dtype=jnp.bfloat16)
    bc = bcsr_conv_from_dense(wt.astype(np.float32), block=block)
    bc = dataclasses.replace(bc, blocks=bc.blocks.astype(jnp.bfloat16))
    got = bsr_conv(x, bc, stride=stride, padding=pad, interpret=True)
    assert got.dtype == jnp.bfloat16
    ref = bsr_conv_ref(x, jnp.asarray(wt), stride=stride, padding=pad)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("stride", [1, 2])
def test_bsr_matches_ell_pallas_and_direct(stride):
    """Cross-method agreement on one geometry: the BCSR MXU path, the ELL
    Pallas path, and the pure-JAX direct path all compute the same conv."""
    n, c, h, w, m, r, pad = 2, 4, 12, 10, 8, 3, 1
    rng, x, wt = _case(7100 + stride, n, c, h, w, m, r, 0.5, (4, 8))
    bc = bcsr_conv_from_dense(wt, block=(4, 8))
    ell = ell_from_dense_conv(wt)
    y_bsr = bsr_conv(x, bc, stride=stride, padding=pad, interpret=True)
    y_ell = sparse_conv(x, ell, stride=stride, padding=pad, interpret=True)
    y_dir = direct_sparse_conv(x, ell, stride=stride, padding=pad)
    np.testing.assert_allclose(np.asarray(y_bsr), np.asarray(y_ell),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(y_bsr), np.asarray(y_dir),
                               rtol=2e-5, atol=2e-5)


def test_bsr_unstructured_weights_still_correct():
    """Magnitude-pruned (unstructured) weights keep nearly every tile but
    must stay exactly correct — block sparsity is a performance transform."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((1, 3, 10, 10)).astype(np.float32))
    wt = np.asarray(magnitude_prune(
        jnp.asarray(rng.standard_normal((8, 3, 3, 3)).astype(np.float32)), 0.7))
    bc = bcsr_conv_from_dense(wt, block=(4, 8))
    got = bsr_conv(x, bc, padding=1, interpret=True)
    ref = bsr_conv_ref(x, jnp.asarray(wt), padding=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# fallbacks + feasibility
# ---------------------------------------------------------------------------

def test_bsr_vmem_infeasible_falls_back(monkeypatch):
    """When no row tiling fits VMEM, bsr_conv must fall back to the
    dense-reconstruction path — with the epilogue still applied — instead
    of launching the kernel."""
    rng, x, wt = _case(13, 1, 4, 10, 10, 8, 3, 0.5, (4, 8))
    bc = bcsr_conv_from_dense(wt, block=(4, 8))
    bias = jnp.asarray(rng.standard_normal((8,)).astype(np.float32))
    monkeypatch.setattr(ops, "VMEM_BUDGET", 1024)
    assert bsr_tile_candidates(4, 10, 10, 3, 3, 1, 4, 8) == []

    def _boom(*a, **kw):
        raise AssertionError("over-budget kernel launch")

    monkeypatch.setattr(ops, "bsr_conv_pallas", _boom)
    got = bsr_conv(x, bc, padding=1, bias=bias, fuse_relu=True,
                   interpret=True)
    ref = bsr_conv_ref(x, jnp.asarray(wt), padding=1)
    ref = jax.nn.relu(ref + bias[None, :, None, None])
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_bsr_stale_infeasible_tiling_falls_back(monkeypatch):
    """A pinned row tile from a stale tuned plan that busts VMEM
    must fall back, never launch over budget."""
    rng, x, wt = _case(17, 1, 4, 16, 16, 8, 3, 0.5, (4, 8))
    bc = bcsr_conv_from_dense(wt, block=(4, 8))
    # Budget below the untiled working set but above nothing in particular:
    # the pinned (16, 16) tiling must be rejected up front.
    monkeypatch.setattr(ops, "VMEM_BUDGET", 1024)
    assert not bsr_tiling_fits(4, 3, 3, 1, 4, 8, 16, 16)

    def _boom(*a, **kw):
        raise AssertionError("over-budget kernel launch")

    monkeypatch.setattr(ops, "bsr_conv_pallas", _boom)
    got = bsr_conv(x, bc, padding=1, te=16, interpret=True)
    ref = bsr_conv_ref(x, jnp.asarray(wt), padding=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_bsr_smem_infeasible_falls_back(monkeypatch):
    """A block table bigger than SMEM must route to the fallback."""
    rng, x, wt = _case(19, 1, 4, 8, 8, 8, 3, 0.5, (4, 8))
    bc = bcsr_conv_from_dense(wt, block=(4, 8))
    monkeypatch.setattr(ops, "SMEM_BUDGET", 4)
    assert not bsr_smem_fits(bc.gbm, bc.kb)

    def _boom(*a, **kw):
        raise AssertionError("SMEM-infeasible kernel launch")

    monkeypatch.setattr(ops, "bsr_conv_pallas", _boom)
    got = bsr_conv(x, bc, padding=1, interpret=True)
    ref = bsr_conv_ref(x, jnp.asarray(wt), padding=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_bsr_fully_pruned_bank():
    """An all-zero bank keeps one inert tile per block-row (KB clamps to 1)
    and produces exact zeros through the kernel."""
    wt = np.zeros((8, 4, 3, 3), np.float32)
    bc = bcsr_conv_from_dense(wt, block=(4, 8))
    assert bc.kb == 1
    assert int(np.asarray(bc.nblocks).sum()) == 0
    rng = np.random.default_rng(23)
    x = jnp.asarray(rng.standard_normal((1, 4, 8, 8)).astype(np.float32))
    got = bsr_conv(x, bc, padding=1, interpret=True)
    assert got.shape == (1, 8, 8, 8)
    np.testing.assert_array_equal(np.asarray(got), 0.0)


def test_bsr_tiling_fits_accounts_residual_tile():
    """Reserving the fused-residual input tile can rule out tilings that
    fit without it."""
    args = dict(c=8, r=3, s=3, stride=1, bm=8, bn=64, te=64, f=64)
    # 8 staged planes of 66 rows (72) x 66 columns (128); the (64, 64, 64)
    # patch scratch (64 x 128 planes) and its flat f32 (64, 4096) operand;
    # double-buffered (8, 64) weight, f32 (8, 4096) out and (8, 1) bias
    # tiles — all f32, at their (8, 128)-tile-padded sizes.
    x_bytes = 8 * 72 * 128 * 4
    patch = 64 * 64 * 128 * 4 + 64 * 4096 * 4
    w_bytes = 2 * 8 * 128 * 4
    out = 2 * 8 * 4096 * 4
    bias = 2 * 8 * 128 * 4
    import repro.kernels.bsr_conv.ops as bops
    orig = bops.VMEM_BUDGET
    try:
        bops.VMEM_BUDGET = x_bytes + patch + w_bytes + out + bias
        assert bsr_tiling_fits(**args)
        assert not bsr_tiling_fits(**args, fuse_res=True)
    finally:
        bops.VMEM_BUDGET = orig

# ---------------------------------------------------------------------------
# quantised value streams: int8 / fp8 banks, scale after the MXU contraction
# ---------------------------------------------------------------------------

from repro.core.sparse_format import QUANT_DTYPES, quantize_values  # noqa: E402


@pytest.mark.parametrize("value_dtype", sorted(QUANT_DTYPES))
@pytest.mark.parametrize("stride", [1, 2])
def test_bsr_quantised_bit_identical_to_blocked_mirror(value_dtype, stride):
    """A quantised bank through the untiled kernel is bit-identical to the
    blocked structural mirror — narrow blocks feed the contraction, the
    per-channel f32 scales multiply each KB-step's contribution, the
    accumulator stays f32 — the tiled schedule agrees to fp tolerance, and
    both land within quantisation tolerance of the dense oracle."""
    n, c, h, w, m, r, pad = 2, 4, 13, 11, 12, 3, 1
    seed = 8800 + 100 * stride + len(value_dtype)
    rng, x, wt = _case(seed, n, c, h, w, m, r, 0.6, (4, 8))
    q = quantize_values(bcsr_conv_from_dense(wt, block=(4, 8)), value_dtype)
    assert q.value_dtype == value_dtype
    bias = jnp.asarray(rng.standard_normal((m,)).astype(np.float32))
    e, f = out_spatial(h, w, r, r, stride, pad)
    res = jnp.asarray(rng.standard_normal((n, m, e, f)).astype(np.float32))
    kw = dict(stride=stride, padding=pad, bias=bias, fuse_relu=True,
              residual=res)
    got = bsr_conv(x, q, interpret=True, **kw)
    mirror = bsr_conv_blocked_ref(x, q, **kw)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(mirror, np.float32))
    got_tiled = bsr_conv(x, q, te=_row_tile(e, f), interpret=True,
                         **kw)
    np.testing.assert_allclose(np.asarray(got_tiled), np.asarray(got),
                               rtol=1e-5, atol=1e-5)
    ref = bsr_conv_ref(x, jnp.asarray(wt), stride=stride, padding=pad)
    ref = np.asarray(jax.nn.relu(ref + bias[None, :, None, None] + res))
    rel = np.linalg.norm(np.asarray(got, np.float32) - ref) / np.linalg.norm(ref)
    assert rel < 0.05, rel

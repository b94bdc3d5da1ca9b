"""Pallas direct-sparse-conv kernel: interpret-mode sweeps vs the jnp oracle,
including the fused epilogue (bias / ReLU / residual in-kernel)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import balance_ell_conv, ell_from_dense_conv, magnitude_prune
from repro.core.direct_conv import out_spatial
from repro.kernels import budget as kbudget
from repro.kernels.sparse_conv import ops
from repro.kernels.sparse_conv.kernel import sparse_conv_pallas
from repro.kernels.sparse_conv.ops import (choose_tiles, choose_tm,
                                           smem_fits, sparse_conv,
                                           tile_candidates, tm_candidates)
from repro.kernels.sparse_conv.ref import sparse_conv_ref

pytestmark = pytest.mark.pallas


def _row_tile(e):
    """A blockable row tile that leaves a ragged edge tile where E allows:
    the TPU blocks rows in multiples of 8 (or all of E) and never tiles
    columns."""
    return 8 if e > 8 else e

CASES = [
    # (N, C, H, W, M, R, pad, sparsity)
    (1, 3, 10, 10, 8, 3, 0, 0.7),
    (2, 8, 12, 12, 16, 3, 1, 0.9),
    (1, 4, 9, 9, 8, 5, 2, 0.8),
    (2, 16, 8, 8, 32, 1, 0, 0.85),   # 1x1
    (1, 2, 7, 11, 4, 3, 1, 0.5),     # non-square input
    (1, 6, 14, 14, 12, 3, 1, 0.0),   # fully dense weights via sparse path
]


@pytest.mark.parametrize("case", CASES)
def test_kernel_matches_oracle(case):
    n, c, h, w, m, r, pad, sp = case
    rng = np.random.default_rng(abs(hash(case)) % 2**31)
    x = jnp.asarray(rng.standard_normal((n, c, h, w)).astype(np.float32))
    wt = rng.standard_normal((m, c, r, r)).astype(np.float32)
    if sp > 0:
        wt = np.asarray(magnitude_prune(jnp.asarray(wt), sp))
    ell = ell_from_dense_conv(wt)
    got = sparse_conv(x, ell, padding=pad, interpret=True)
    ref = sparse_conv_ref(x, jnp.asarray(wt), padding=pad)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_dtypes(dtype):
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((2, 4, 10, 10)), dtype=dtype)
    wt = np.asarray(magnitude_prune(
        jnp.asarray(rng.standard_normal((8, 4, 3, 3)).astype(np.float32)), 0.8))
    ell = ell_from_dense_conv(wt.astype(np.float32))
    import dataclasses
    ell = dataclasses.replace(ell, value=ell.value.astype(dtype))
    got = sparse_conv(x, ell, padding=1, interpret=True)
    ref = sparse_conv_ref(x, jnp.asarray(wt), padding=1)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("tm", [1, 2, 4, 8])
def test_kernel_channel_tiles(tm, monkeypatch):
    """Every legal channel-tile size (8, 16, 32, 64 of M=64 — multiples of
    the TPU's 8-row block) produces identical results through the
    kernel."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((1, 4, 8, 8)).astype(np.float32))
    wt = np.asarray(magnitude_prune(
        jnp.asarray(rng.standard_normal((64, 4, 3, 3)).astype(np.float32)), 0.7))
    ell = ell_from_dense_conv(wt)
    launches = []
    real = ops.sparse_conv_pallas
    monkeypatch.setattr(
        ops, "sparse_conv_pallas",
        lambda *a, **kw: launches.append(kw) or real(*a, **kw))
    got = sparse_conv(x, ell, tm=8 * tm, interpret=True)
    assert launches and launches[0]["tm"] == 8 * tm
    ref = sparse_conv_ref(x, jnp.asarray(wt))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_strided_runs_in_kernel(monkeypatch):
    """stride > 1 now runs through the Pallas kernel (no pure-JAX fallback)."""
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.standard_normal((1, 3, 16, 16)).astype(np.float32))
    wt = np.asarray(magnitude_prune(
        jnp.asarray(rng.standard_normal((8, 3, 3, 3)).astype(np.float32)), 0.7))
    ell = ell_from_dense_conv(wt)
    launches = []
    real = ops.sparse_conv_pallas
    monkeypatch.setattr(
        ops, "sparse_conv_pallas",
        lambda *a, **kw: launches.append(kw) or real(*a, **kw))
    got = sparse_conv(x, ell, stride=2, padding=1, interpret=True)
    ref = sparse_conv_ref(x, jnp.asarray(wt), stride=2, padding=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert launches and launches[0]["stride"] == 2


# ---------------------------------------------------------------------------
# spatial tiling: stride x padding grid, edge tiles, large feature maps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stride", [1, 2, 4])
@pytest.mark.parametrize("pad", [0, 1, 2])
def test_strided_tiled_parity(stride, pad):
    """(stride, padding) grid through the row-tiled kernel with edge tiles:
    te deliberately does not divide E where E allows it."""
    n, c, h, w, m, r = 2, 3, 15, 13, 8, 3
    rng = np.random.default_rng(100 * stride + pad)
    x = jnp.asarray(rng.standard_normal((n, c, h, w)).astype(np.float32))
    wt = np.asarray(magnitude_prune(
        jnp.asarray(rng.standard_normal((m, c, r, r)).astype(np.float32)), 0.7))
    ell = ell_from_dense_conv(wt)
    e, f = out_spatial(h, w, r, r, stride, pad)
    got = sparse_conv(x, ell, stride=stride, padding=pad,
                      tm=8, te=_row_tile(e), interpret=True)
    ref = sparse_conv_ref(x, jnp.asarray(wt), stride=stride, padding=pad)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("stride", [2, 4])
def test_strided_bf16(stride):
    rng = np.random.default_rng(23 + stride)
    x = jnp.asarray(rng.standard_normal((1, 4, 12, 12)), dtype=jnp.bfloat16)
    wt = np.asarray(magnitude_prune(
        jnp.asarray(rng.standard_normal((8, 4, 3, 3)).astype(np.float32)), 0.8))
    ell = ell_from_dense_conv(wt)
    import dataclasses
    ell = dataclasses.replace(ell, value=ell.value.astype(jnp.bfloat16))
    got = sparse_conv(x, ell, stride=stride, padding=1, interpret=True)
    ref = sparse_conv_ref(x, jnp.asarray(wt), stride=stride, padding=1)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_large_feature_map_spatially_tiled():
    """A feature map whose whole padded image busts the VMEM budget still
    runs through the Pallas kernel via spatial tiling — the old kernel
    refused it (and the [1]-fallback bug would have launched over budget)."""
    n, c, h, w, m, r, pad = 1, 96, 192, 192, 8, 3, 1
    hp = wp = h + 2 * pad
    assert c * kbudget.vmem_tile_bytes(hp, wp, 4) > ops.VMEM_BUDGET
    rng = np.random.default_rng(31)
    x = jnp.asarray(rng.standard_normal((n, c, h, w)).astype(np.float32))
    wt = np.asarray(magnitude_prune(
        jnp.asarray(rng.standard_normal((m, c, r, r)).astype(np.float32)), 0.95))
    ell = ell_from_dense_conv(wt)
    e, f = out_spatial(h, w, r, r, 1, pad)
    # regression: the untiled ladder must report infeasible, not [1]
    assert tm_candidates(m, c, hp, wp, e, f, ell.k) == []
    tiles = choose_tiles(m, c, e, f, ell.k, r, r, 1)
    assert tiles is not None and tiles[1] < e
    got = sparse_conv(x, ell, padding=pad, interpret=True)
    ref = sparse_conv_ref(x, jnp.asarray(wt), padding=pad)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=1e-4, atol=1e-4)


def test_tm_candidates_over_budget_returns_empty():
    """Regression: tm_candidates used to return [1] even when TM=1 busts
    the VMEM budget, launching an over-budget kernel."""
    assert tm_candidates(m=8, c=2048, hp=64, wp=64, e=62, f=62, k=64) == []


def test_off_ladder_tm_honored(monkeypatch):
    """A pinned tm that divides M but is not on the default ladder (e.g. 24
    for M=48) must still launch the kernel, not silently fall back."""
    rng = np.random.default_rng(41)
    x = jnp.asarray(rng.standard_normal((1, 3, 8, 8)).astype(np.float32))
    wt = np.asarray(magnitude_prune(
        jnp.asarray(rng.standard_normal((48, 3, 3, 3)).astype(np.float32)), 0.7))
    ell = ell_from_dense_conv(wt)
    launches = []
    real = ops.sparse_conv_pallas
    monkeypatch.setattr(
        ops, "sparse_conv_pallas",
        lambda *a, **kw: launches.append(kw) or real(*a, **kw))
    got = sparse_conv(x, ell, tm=24, padding=1, interpret=True)
    ref = sparse_conv_ref(x, jnp.asarray(wt), padding=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert launches and launches[0]["tm"] == 24


def test_vmem_infeasible_falls_back_to_direct(monkeypatch):
    """When no (tm, te) tiling fits VMEM, sparse_conv must fall back to
    the pure-JAX direct path instead of launching the kernel."""
    rng = np.random.default_rng(37)
    x = jnp.asarray(rng.standard_normal((1, 4, 10, 10)).astype(np.float32))
    wt = np.asarray(magnitude_prune(
        jnp.asarray(rng.standard_normal((8, 4, 3, 3)).astype(np.float32)), 0.7))
    ell = ell_from_dense_conv(wt)
    monkeypatch.setattr(ops, "_VMEM_BUDGET", 1024)
    assert tile_candidates(8, 4, 8, 8, ell.k, 3, 3, 1) == []

    def _boom(*a, **kw):
        raise AssertionError("over-budget kernel launch")

    monkeypatch.setattr(ops, "sparse_conv_pallas", _boom)
    got = sparse_conv(x, ell, padding=1, interpret=True)
    ref = sparse_conv_ref(x, jnp.asarray(wt), padding=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# double-buffered halo DMA pipeline: parity vs the blocking schedule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("residual", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pipelined_matches_blocking(stride, residual, dtype):
    """Interpret-mode parity grid: the double-buffered schedule must be
    *bit-identical* to the single-buffer one (same FMA order, different
    staging only) across stride x residual x dtype, with edge tiles (te
    deliberately not dividing E) so the prefetch crosses ragged cells."""
    import dataclasses
    n, c, h, w, m, r, pad = 2, 4, 13, 11, 8, 3, 1
    rng = np.random.default_rng(9000 + 100 * stride + 10 * residual
                                + (dtype == jnp.bfloat16))
    x = jnp.asarray(rng.standard_normal((n, c, h, w)), dtype=dtype)
    wt = np.asarray(magnitude_prune(
        jnp.asarray(rng.standard_normal((m, c, r, r)).astype(np.float32)), 0.7))
    ell = ell_from_dense_conv(wt)
    if dtype == jnp.bfloat16:
        ell = dataclasses.replace(ell, value=ell.value.astype(dtype))
    bias = jnp.asarray(rng.standard_normal((m,)).astype(np.float32))
    e, f = out_spatial(h, w, r, r, stride, pad)
    res = (jnp.asarray(rng.standard_normal((n, m, e, f)).astype(np.float32),
                       dtype=dtype) if residual else None)
    kw = dict(stride=stride, padding=pad, tm=8, te=_row_tile(e),
              bias=bias, fuse_relu=True, residual=res, interpret=True)
    y_block = sparse_conv(x, ell, pipeline=False, **kw)
    y_pipe = sparse_conv(x, ell, pipeline=True, **kw)
    np.testing.assert_array_equal(np.asarray(y_block, np.float32),
                                  np.asarray(y_pipe, np.float32))
    ref = sparse_conv_ref(x, jnp.asarray(wt), stride=stride, padding=pad)
    ref = ref.astype(jnp.float32) + bias[None, :, None, None]
    if res is not None:
        ref = ref + res.astype(jnp.float32)
    ref = jax.nn.relu(ref)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(y_pipe, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_pipeline_auto_enabled_when_it_fits(monkeypatch):
    """pipeline=None (default) must launch the double-buffered schedule
    whenever the second halo buffer fits the VMEM budget."""
    rng = np.random.default_rng(43)
    x = jnp.asarray(rng.standard_normal((1, 4, 10, 10)).astype(np.float32))
    wt = np.asarray(magnitude_prune(
        jnp.asarray(rng.standard_normal((8, 4, 3, 3)).astype(np.float32)), 0.7))
    ell = ell_from_dense_conv(wt)
    launches = []
    real = ops.sparse_conv_pallas
    monkeypatch.setattr(
        ops, "sparse_conv_pallas",
        lambda *a, **kw: launches.append(kw) or real(*a, **kw))
    got = sparse_conv(x, ell, padding=1, interpret=True)
    ref = sparse_conv_ref(x, jnp.asarray(wt), padding=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert launches and launches[0]["pipeline"] is True


def test_pipeline_drops_to_single_buffer_when_double_halo_busts(monkeypatch):
    """A requested pipeline=True whose second halo block busts VMEM must
    run the single-buffer blocking kernel — not the pure-JAX fallback."""
    rng = np.random.default_rng(47)
    x = jnp.asarray(rng.standard_normal((1, 4, 16, 16)).astype(np.float32))
    wt = np.asarray(magnitude_prune(
        jnp.asarray(rng.standard_normal((8, 4, 3, 3)).astype(np.float32)), 0.7))
    ell = ell_from_dense_conv(wt)
    e = f = 16
    tm, te = 8, 16
    # Budget: exactly one halo block — 4 planes of 18 rows (24, whole
    # 8-row sublane tiles) by 18 columns (one 128-lane tile), f32 — plus
    # the double-buffered f32 out tile, 8 channels of 16 x 16 (16 x 128
    # padded).  No room for a second halo buffer.
    budget = 4 * 24 * 128 * 4 + 2 * 8 * 16 * 128 * 4
    monkeypatch.setattr(ops, "_VMEM_BUDGET", budget)
    assert ops.tiling_fits(8, 4, e, f, ell.k, 3, 3, 1, tm, te)
    assert not ops.tiling_fits(8, 4, e, f, ell.k, 3, 3, 1, tm, te,
                               pipeline=True)
    launches = []
    real = ops.sparse_conv_pallas
    monkeypatch.setattr(
        ops, "sparse_conv_pallas",
        lambda *a, **kw: launches.append(kw) or real(*a, **kw))
    got = sparse_conv(x, ell, padding=1, tm=tm, te=te, pipeline=True,
                      interpret=True)
    ref = sparse_conv_ref(x, jnp.asarray(wt), padding=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert launches and launches[0]["pipeline"] is False


# ---------------------------------------------------------------------------
# nnz-balanced channel packing: permuted bank is invisible to callers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stride", [1, 2])
def test_balanced_bank_output_bit_identical(stride):
    """A permuted (nnz-balanced) ELL bank must produce *bit-identical*
    output to the natural-order bank: row contents (and therefore each
    row's f32 accumulation order) are untouched, only row order changes and
    the inverse permutation restores it."""
    n, c, h, w, m, r, pad = 2, 4, 12, 12, 16, 3, 1
    rng = np.random.default_rng(6000 + stride)
    x = jnp.asarray(rng.standard_normal((n, c, h, w)).astype(np.float32))
    wt = np.asarray(magnitude_prune(
        jnp.asarray(rng.standard_normal((m, c, r, r)).astype(np.float32)), 0.7))
    ell = ell_from_dense_conv(wt)
    bal = balance_ell_conv(ell)
    # the permutation actually balances: nnz descending
    nnz = np.asarray(bal.nnz)
    assert (np.diff(nnz) <= 0).all()
    assert sorted(np.asarray(bal.perm).tolist()) == list(range(m))
    bias = jnp.asarray(rng.standard_normal((m,)).astype(np.float32))
    e, f = out_spatial(h, w, r, r, stride, pad)
    res = jnp.asarray(rng.standard_normal((n, m, e, f)).astype(np.float32))
    kw = dict(stride=stride, padding=pad, bias=bias, fuse_relu=True,
              residual=res, interpret=True)
    y_nat = sparse_conv(x, ell, **kw)
    y_bal = sparse_conv(x, bal, **kw)
    np.testing.assert_array_equal(np.asarray(y_nat), np.asarray(y_bal))


def test_balanced_bank_fallback_unpermutes(monkeypatch):
    """The pure-JAX fallback must also restore natural channel order for a
    permuted bank (and apply the epilogue on the restored order)."""
    rng = np.random.default_rng(61)
    x = jnp.asarray(rng.standard_normal((1, 4, 10, 10)).astype(np.float32))
    wt = np.asarray(magnitude_prune(
        jnp.asarray(rng.standard_normal((8, 4, 3, 3)).astype(np.float32)), 0.7))
    bal = balance_ell_conv(ell_from_dense_conv(wt))
    bias = jnp.asarray(rng.standard_normal((8,)).astype(np.float32))
    monkeypatch.setattr(ops, "_VMEM_BUDGET", 1024)

    def _boom(*a, **kw):
        raise AssertionError("over-budget kernel launch")

    monkeypatch.setattr(ops, "sparse_conv_pallas", _boom)
    got = sparse_conv(x, bal, padding=1, bias=bias, fuse_relu=True,
                      interpret=True)
    ref = sparse_conv_ref(x, jnp.asarray(wt), padding=1)
    ref = jax.nn.relu(ref.astype(jnp.float32) + bias[None, :, None, None])
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# regressions: SMEM accounting, non-dividing channel tiles
# ---------------------------------------------------------------------------

def test_smem_fits_budgets_nnz_row():
    """Regression: smem_fits must account every SMEM operand — the
    double-buffered (TM, K) index and value tiles *and* the three
    scalar-prefetched rows, the int32 nnz row among them.  Pick (m, k)
    where the tiles plus two rows fit but adding the nnz row overshoots:
    a check that forgot it would say yes and overshoot SMEM."""
    budget = ops.SMEM_BUDGET
    m = 8192
    row = kbudget.smem_array_bytes(1, m, 4)
    # k a multiple of 128, so each (8, k) f32/int32 tile is exactly 32k B.
    k = (budget - 2 * row) // (4 * 8 * 4 * 128) * 128
    tiles = 2 * 2 * 8 * k * 4
    assert tiles + 2 * row <= budget < tiles + 3 * row
    assert not smem_fits(m, k)
    assert smem_fits(m, (budget - 3 * row) // (4 * 8 * 4 * 128) * 128)


def test_non_dividing_tm_raises_value_error():
    """The kernel wrapper must reject a non-dividing channel tile with a
    ValueError naming the geometry — an assert would vanish under
    ``python -O`` and silently mis-tile."""
    rng = np.random.default_rng(53)
    wt = np.asarray(magnitude_prune(
        jnp.asarray(rng.standard_normal((8, 3, 3, 3)).astype(np.float32)), 0.7))
    ell = ell_from_dense_conv(wt)
    xpad = jnp.asarray(rng.standard_normal((1, 3, 10, 10)).astype(np.float32))
    with pytest.raises(ValueError, match=r"tm=3 does not divide M=8"):
        sparse_conv_pallas(
            xpad, ell.value, ops.pack_indices(ell), ell.nnz,
            jnp.zeros((8,), jnp.float32), tm=3, k=ell.k, rs=9, s=3,
            e=8, f=8, interpret=True)


def test_stale_plan_non_dividing_tm_falls_back(monkeypatch):
    """Regression: a stale tuned plan carrying a tm that no longer divides M
    (e.g. the layer was re-pruned to a different channel count) must fall
    back to the pure-JAX path — never reach the kernel, even with asserts
    stripped (``python -O``)."""
    rng = np.random.default_rng(59)
    x = jnp.asarray(rng.standard_normal((1, 4, 10, 10)).astype(np.float32))
    wt = np.asarray(magnitude_prune(
        jnp.asarray(rng.standard_normal((8, 4, 3, 3)).astype(np.float32)), 0.7))
    ell = ell_from_dense_conv(wt)

    def _boom(*a, **kw):
        raise AssertionError("non-dividing tm reached the kernel")

    monkeypatch.setattr(ops, "sparse_conv_pallas", _boom)
    # fully-specified stale tiling: tm=3 does not divide m=8
    got = sparse_conv(x, ell, padding=1, tm=3, te=8, interpret=True)
    ref = sparse_conv_ref(x, jnp.asarray(wt), padding=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# fused epilogue: conv+bias+ReLU (+residual) vs the unfused dense oracle
# ---------------------------------------------------------------------------

def _epilogue_case(seed, n, c, h, w, m, r, *, dtype=jnp.float32, sp=0.7):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((n, c, h, w)), dtype=dtype)
    wt = np.asarray(magnitude_prune(
        jnp.asarray(rng.standard_normal((m, c, r, r)).astype(np.float32)), sp))
    bias = jnp.asarray(rng.standard_normal((m,)).astype(np.float32))
    return rng, x, wt, bias


def _unfused_oracle(x, wt, bias, *, stride, pad, residual=None):
    y = sparse_conv_ref(x, jnp.asarray(wt), stride=stride, padding=pad)
    y = y.astype(jnp.float32) + bias[None, :, None, None]
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    return jax.nn.relu(y)


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("residual", [False, True])
def test_fused_epilogue_parity(stride, residual):
    """Fused conv+bias+ReLU (and +residual) vs the unfused dense oracle,
    with edge tiles: te deliberately does not divide E where E allows."""
    n, c, h, w, m, r, pad = 2, 4, 13, 11, 8, 3, 1
    rng, x, wt, bias = _epilogue_case(1000 + 10 * stride + residual,
                                      n, c, h, w, m, r)
    ell = ell_from_dense_conv(wt)
    e, f = out_spatial(h, w, r, r, stride, pad)
    res = (jnp.asarray(rng.standard_normal((n, m, e, f)).astype(np.float32))
           if residual else None)
    got = sparse_conv(x, ell, stride=stride, padding=pad, tm=8,
                      te=_row_tile(e), bias=bias, fuse_relu=True,
                      residual=res, interpret=True)
    ref = _unfused_oracle(x, wt, bias, stride=stride, pad=pad, residual=res)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("residual", [False, True])
def test_fused_epilogue_parity_bf16(stride, residual):
    """bf16 inputs through the fused epilogue: the epilogue runs on the f32
    accumulator, so tolerance is the bf16 rounding of the conv itself."""
    import dataclasses
    n, c, h, w, m, r, pad = 1, 4, 12, 12, 8, 3, 1
    rng, x, wt, bias = _epilogue_case(2000 + 10 * stride + residual,
                                      n, c, h, w, m, r, dtype=jnp.bfloat16,
                                      sp=0.8)
    ell = ell_from_dense_conv(wt)
    ell = dataclasses.replace(ell, value=ell.value.astype(jnp.bfloat16))
    e, f = out_spatial(h, w, r, r, stride, pad)
    res = (jnp.asarray(rng.standard_normal((n, m, e, f)), dtype=jnp.bfloat16)
           if residual else None)
    got = sparse_conv(x, ell, stride=stride, padding=pad,
                      bias=bias, fuse_relu=True, residual=res, interpret=True)
    ref = _unfused_oracle(x, wt, bias, stride=stride, pad=pad, residual=res)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_fused_epilogue_fallback_applies_epilogue(monkeypatch):
    """When no VMEM-feasible tiling exists, the fallback must still apply
    the full epilogue (bias + residual + ReLU), not just the conv."""
    n, c, h, w, m, r, pad = 1, 4, 10, 10, 8, 3, 1
    rng, x, wt, bias = _epilogue_case(3000, n, c, h, w, m, r)
    ell = ell_from_dense_conv(wt)
    e, f = out_spatial(h, w, r, r, 1, pad)
    res = jnp.asarray(rng.standard_normal((n, m, e, f)).astype(np.float32))
    monkeypatch.setattr(ops, "_VMEM_BUDGET", 1024)

    def _boom(*a, **kw):
        raise AssertionError("over-budget kernel launch")

    monkeypatch.setattr(ops, "sparse_conv_pallas", _boom)
    got = sparse_conv(x, ell, padding=pad, bias=bias, fuse_relu=True,
                      residual=res, interpret=True)
    ref = _unfused_oracle(x, wt, bias, stride=1, pad=pad, residual=res)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_fused_residual_tightens_vmem_feasibility(monkeypatch):
    """Reserving the residual input tile can rule out tilings that fit
    without it — tiling_fits must account the extra block."""
    from repro.kernels.sparse_conv.ops import tiling_fits
    args = dict(m=8, c=8, e=64, f=64, k=16, r=3, s=3, stride=1, tm=8, te=64)
    # Budget sized to fit the input block — 8 planes of 66 rows (72, whole
    # 8-row sublane tiles) by 66 columns (one 128-lane tile), f32 — and the
    # double-buffered f32 out tile, 8 channels of 64 x 64 (64 x 128
    # padded), but not the residual tile of the same size.
    monkeypatch.setattr(ops, "_VMEM_BUDGET",
                        8 * 72 * 128 * 4 + 2 * 8 * 64 * 128 * 4)
    assert tiling_fits(**args)
    assert not tiling_fits(**args, fuse_res=True)


def test_choose_tm_fits_budget():
    tm = choose_tm(m=256, c=96, hp=31, wp=31, e=27, f=27, k=256)
    assert 256 % tm == 0
    assert (96 * kbudget.vmem_tile_bytes(31, 31, 4)
            + 2 * tm * kbudget.vmem_tile_bytes(27, 27, 4)) <= 12 * 2**20


@pytest.mark.parametrize("pad_to", [1, 4, 8])
def test_fully_pruned_bank(pad_to):
    """Regression: an all-zero filter bank must keep K >= pad_to >= 1 and
    produce an all-zero output through the Pallas path (no 0-width arrays)."""
    wt = np.zeros((8, 4, 3, 3), np.float32)
    ell = ell_from_dense_conv(wt, pad_to=pad_to)
    assert ell.k >= max(1, pad_to)
    assert int(np.asarray(ell.nnz).sum()) == 0
    rng = np.random.default_rng(17)
    x = jnp.asarray(rng.standard_normal((1, 4, 8, 8)).astype(np.float32))
    got = sparse_conv(x, ell, padding=1, interpret=True)
    assert got.shape == (1, 8, 8, 8)
    np.testing.assert_array_equal(np.asarray(got), 0.0)


def test_degenerate_pad_to_clamped():
    """pad_to < 1 is clamped instead of crashing with ZeroDivisionError."""
    wt = np.zeros((4, 2, 3, 3), np.float32)
    assert ell_from_dense_conv(wt, pad_to=0).k >= 1


def test_empty_bank_rejected():
    with pytest.raises(ValueError):
        ell_from_dense_conv(np.zeros((0, 2, 3, 3), np.float32))

# ---------------------------------------------------------------------------
# quantised value streams: int8 / fp8 banks, in-kernel dequantisation
# ---------------------------------------------------------------------------

from repro.core.sparse_format import (QUANT_DTYPES, dequantize,  # noqa: E402
                                      quantize_values)


@pytest.mark.parametrize("value_dtype", sorted(QUANT_DTYPES))
@pytest.mark.parametrize("pipeline", [False, True])
@pytest.mark.parametrize("stride", [1, 2])
def test_quantised_bank_bit_identical_to_dequantised(value_dtype, pipeline,
                                                     stride):
    """The kernel's in-register dequantisation (scale at the FMA, f32
    accumulator) performs the exact multiply dequantize() does host-side,
    so a quantised bank through either schedule is bit-identical to the
    f32 kernel run on the dequantised bank — and within quantisation
    tolerance of the dense oracle.  Edge tiles (te not dividing E) and the
    fused epilogue ride along."""
    n, c, h, w, m, r, pad = 2, 4, 13, 11, 8, 3, 1
    rng = np.random.default_rng(31000 + 100 * stride + 10 * pipeline
                                + len(value_dtype))
    x = jnp.asarray(rng.standard_normal((n, c, h, w)).astype(np.float32))
    wt = np.asarray(magnitude_prune(
        jnp.asarray(rng.standard_normal((m, c, r, r)).astype(np.float32)), 0.7))
    q = quantize_values(ell_from_dense_conv(wt), value_dtype)
    assert q.value_dtype == value_dtype
    bias = jnp.asarray(rng.standard_normal((m,)).astype(np.float32))
    e, f = out_spatial(h, w, r, r, stride, pad)
    res = jnp.asarray(rng.standard_normal((n, m, e, f)).astype(np.float32))
    kw = dict(stride=stride, padding=pad, tm=8, te=_row_tile(e),
              bias=bias, fuse_relu=True, residual=res, pipeline=pipeline,
              interpret=True)
    y_q = sparse_conv(x, q, **kw)
    y_f32 = sparse_conv(x, dequantize(q), **kw)
    np.testing.assert_array_equal(np.asarray(y_q), np.asarray(y_f32))
    ref = sparse_conv_ref(x, jnp.asarray(wt), stride=stride, padding=pad)
    ref = np.asarray(jax.nn.relu(ref + bias[None, :, None, None] + res))
    rel = (np.linalg.norm(np.asarray(y_q) - ref) / np.linalg.norm(ref))
    assert rel < 0.05, rel


def test_quantised_balanced_bank_parity():
    """Quantisation composes with row balancing: scales follow the
    permuted rows, and the permuted quantised bank stays bit-identical to
    the f32 kernel on its dequantised twin."""
    rng = np.random.default_rng(31999)
    x = jnp.asarray(rng.standard_normal((1, 4, 10, 10)).astype(np.float32))
    wt = np.asarray(magnitude_prune(
        jnp.asarray(rng.standard_normal((8, 4, 3, 3)).astype(np.float32)), 0.8))
    bal = balance_ell_conv(ell_from_dense_conv(wt))
    q = quantize_values(bal, "int8")
    assert q.perm is not None
    y_q = sparse_conv(x, q, padding=1, interpret=True)
    y_f32 = sparse_conv(x, dequantize(q), padding=1, interpret=True)
    np.testing.assert_array_equal(np.asarray(y_q), np.asarray(y_f32))
    ref = np.asarray(sparse_conv_ref(x, jnp.asarray(wt), padding=1))
    rel = np.linalg.norm(np.asarray(y_q) - ref) / np.linalg.norm(ref)
    assert rel < 0.05, rel

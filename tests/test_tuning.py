"""Kernel-customization autotuner: space validity, cache round-trip,
and method="auto" numerical equivalence (interpret mode, CPU)."""
import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.sparse_conv.ops import (SMEM_BUDGET, VMEM_BUDGET,
                                           choose_tm,
                                           tiling_fits, tm_candidates)
from repro.models import cnn
from repro.tuning import (Candidate, ConvGeometry, PlanCache, PlanEntry,
                          apply_plan_to_params, enumerate_candidates,
                          layer_key, plan_network, roofline_estimate)


def _geom(**kw):
    base = dict(name="l", m=64, c=32, h=14, w=14, r=3, s=3, stride=1, pad=1,
                sparsity=0.7, batch=2)
    base.update(kw)
    return ConvGeometry(**base)


# ---------------------------------------------------------------------------
# candidate space
# ---------------------------------------------------------------------------

def _assert_pallas_fits(g, cands):
    """Every pallas candidate's (tm, te) halo'd working set fits VMEM —
    fused candidates accounting the residual input tile, pipelined ones the
    second halo scratch buffer — and its (TM, K) index and value tiles plus
    the scalar-prefetched rows fit SMEM."""
    from repro.kernels.budget import ell_smem_bytes, ell_vmem_bytes
    assert any(c.method == "pallas" for c in cands)
    for cd in cands:
        if cd.method != "pallas":
            continue
        assert g.m % cd.tm == 0
        assert cd.te is not None
        k = g.k_est(cd.pad_to)
        fuse_res = cd.fuse and g.residual
        assert ell_vmem_bytes(g.c, g.f, g.r, g.s, g.stride, cd.tm, cd.te,
                              fuse_res=fuse_res,
                              pipeline=cd.pipeline) <= VMEM_BUDGET
        assert tiling_fits(g.m, g.c, g.e, g.f, k, g.r, g.s, g.stride,
                           cd.tm, cd.te, fuse_res=fuse_res,
                           pipeline=cd.pipeline)
        assert ell_smem_bytes(g.m, k, tm=cd.tm) <= SMEM_BUDGET


def test_candidates_tiles_divide_m_and_fit_budgets():
    g = _geom()
    _assert_pallas_fits(g, enumerate_candidates(g))


def test_dense_layer_space_is_dense_only():
    assert enumerate_candidates(_geom(sparsity=0.0)) == [Candidate("dense")]


def test_strided_layer_has_pallas():
    """Strided layers are pallas-eligible now — the kernel strides in-kernel."""
    g = _geom(stride=2)
    _assert_pallas_fits(g, enumerate_candidates(g))


def test_large_map_layer_gets_spatially_tiled_pallas():
    """A layer whose whole padded image busts VMEM still gets pallas
    candidates — spatially tiled ones, all within budget."""
    g = _geom(m=8, c=96, h=192, w=192, pad=1, sparsity=0.95)
    assert g.c * g.hp * g.wp * 4 > VMEM_BUDGET
    cands = enumerate_candidates(g)
    _assert_pallas_fits(g, cands)
    assert all(cd.te < g.e for cd in cands if cd.method == "pallas")


def test_smem_heavy_layer_has_no_pallas():
    # Even the smallest channel tile's (8, K) index + value tiles are far
    # over the SMEM budget: near-dense rows of a 4096-channel 3x3 bank.
    g = _geom(m=64, c=4096, sparsity=0.05)
    assert all(c.method != "pallas" for c in enumerate_candidates(g))


def test_choose_tm_is_first_candidate():
    args = dict(m=256, c=96, hp=31, wp=31, e=27, f=27, k=256)
    assert choose_tm(**args) == tm_candidates(**args)[0]


def test_roofline_orders_sparse_below_dense():
    """The execution-unit split (VPU for per-nonzero FMA loops, MXU for
    dense/bsr contractions) moves the dense-vs-direct crossover: at 95%
    sparsity the direct method's bound still beats dense, but at a
    moderate 70% the VPU-priced scan loses to the MXU-fed dense conv on a
    compute-heavy geometry — the gap the bsr method exists to close."""
    g_hi = _geom(m=256, c=256, h=28, w=28, sparsity=0.95)
    t_dense = roofline_estimate(g_hi, Candidate("dense"))
    t_direct = roofline_estimate(g_hi, Candidate("csr-direct", pad_to=8))
    assert t_direct < t_dense
    g_mid = _geom(m=256, c=256, h=28, w=28, sparsity=0.7)
    assert (roofline_estimate(g_mid, Candidate("csr-direct", pad_to=8))
            > roofline_estimate(g_mid, Candidate("dense")))


def test_roofline_pallas_tm_amortises_input():
    g = _geom()
    t1 = roofline_estimate(g, Candidate("pallas", tm=1, pad_to=8))
    t64 = roofline_estimate(g, Candidate("pallas", tm=64, pad_to=8))
    assert t64 <= t1


def test_roofline_pallas_spatial_tiling_costs_halo():
    """Smaller spatial tiles re-fetch halo rows: the untiled schedule must
    score no worse than a tiled one on a memory-bound geometry that fits."""
    g = _geom()
    t_full = roofline_estimate(g, Candidate("pallas", tm=8, pad_to=8))
    t_tiled = roofline_estimate(g, Candidate("pallas", tm=8, pad_to=8, te=8))
    assert t_full <= t_tiled


# ---------------------------------------------------------------------------
# fuse axis (in-kernel epilogue)
# ---------------------------------------------------------------------------

def test_candidates_include_fused_variants():
    g = _geom(relu=True)
    cands = enumerate_candidates(g)
    fused = [c for c in cands if c.method == "pallas" and c.fuse]
    unfused = [c for c in cands if c.method == "pallas" and not c.fuse]
    assert fused and unfused
    _assert_pallas_fits(g, cands)


def test_candidates_fused_residual_fit_vmem():
    g = _geom(relu=True, residual=True)
    _assert_pallas_fits(g, enumerate_candidates(g))


def test_roofline_credits_fused_epilogue():
    """The fused epilogue removes full output-tensor passes, so on a
    memory-bound geometry the fused candidate must score strictly better."""
    g = _geom(relu=True, residual=True)
    base = dict(tm=8, pad_to=8)
    t_unfused = roofline_estimate(g, Candidate("pallas", **base))
    t_fused = roofline_estimate(g, Candidate("pallas", **base, fuse=True))
    assert t_fused < t_unfused


def test_layer_key_separates_epilogues():
    """Same geometry, different fused epilogue -> different cache keys, so
    fused and unfused variants never share a measurement."""
    plain = layer_key(_geom(), "cpu")
    relu = layer_key(_geom(relu=True), "cpu")
    tail = layer_key(_geom(relu=True, residual=True), "cpu")
    assert len({plain, relu, tail}) == 3


def test_plan_program_dedups_on_op_geometry():
    """Repeated identical bottlenecks are scored once per run (even with no
    persistent cache), while the fused-tail conv — same shape as a plain
    conv+ReLU elsewhere — gets its own entry."""
    from repro.engine import lower
    from repro.tuning import plan_program

    body = lambda i: cnn.Residual(body=(                       # noqa: E731
        cnn.Conv(f"b{i}/1x1a", 16, 1, sparsity=0.7), cnn.Relu(),
        cnn.Conv(f"b{i}/1x1b", 16, 1, sparsity=0.7)))
    net = [cnn.Conv("stem", 16, 3, 1, 1, sparsity=0.0), cnn.Relu(),
           body(0), cnn.Relu(), body(1), cnn.Relu()]
    program = lower(net, (3, 12, 12))
    calls = []
    import repro.tuning.planner as planner_mod
    orig = planner_mod.plan_layer

    def spy(g, **kw):
        calls.append(g.name)
        return orig(g, **kw)

    planner_mod.plan_layer, plan = spy, None
    try:
        plan = planner_mod.plan_program(program, batch=1, mode="roofline",
                                        backend="cpu")
    finally:
        planner_mod.plan_layer = orig
    # 4 sparse convs, but only 2 distinct (geometry, epilogue) keys:
    # the relu'd 1x1a and the shortcut-fused 1x1b tail
    assert len(plan) == 5
    assert len(calls) == 2
    assert plan["b0/1x1a"] == plan["b1/1x1a"]
    assert plan["b0/1x1b"] == plan["b1/1x1b"]


# ---------------------------------------------------------------------------
# pipeline axis (double-buffered halo DMA) + permute axis (balanced banks)
# ---------------------------------------------------------------------------

def test_candidates_include_pipeline_and_permute_variants():
    g = _geom()
    cands = [c for c in enumerate_candidates(g) if c.method == "pallas"]
    assert any(c.pipeline for c in cands)
    assert any(not c.pipeline for c in cands)
    assert any(c.permute for c in cands)
    assert any(not c.permute for c in cands)
    _assert_pallas_fits(g, cands)


def test_pipelined_tilings_reserve_second_halo_buffer(monkeypatch):
    """A tiling whose single halo block fits but whose doubled block busts
    VMEM must be blocking-only in the candidate space."""
    import repro.kernels.sparse_conv.ops as ops
    args = dict(m=8, c=8, e=64, f=64, k=16, r=3, s=3, stride=1, tm=8, te=64)
    # One halo block: 8 planes of 66 rows (72, whole 8-row sublane tiles)
    # by 66 columns (one 128-lane tile), f32; plus the double-buffered f32
    # out tile, 8 channels of 64 x 64 (64 x 128 padded).
    halo = 8 * 72 * 128 * 4
    out = 2 * 8 * 64 * 128 * 4
    monkeypatch.setattr(ops, "_VMEM_BUDGET", halo + out)
    assert tiling_fits(**args)
    assert not tiling_fits(**args, pipeline=True)


def test_roofline_credits_pipelined_staging():
    """Double-buffered staging overlaps the halo copies with compute: on a
    staging-heavy tiling the pipelined candidate must score no worse, and
    its exposed staged-input stall must be strictly smaller."""
    from repro.tuning import staging_stall_s

    g = _geom()
    base = dict(tm=8, pad_to=8, te=8)
    blocking = Candidate("pallas", **base)
    pipelined = Candidate("pallas", **base, pipeline=True)
    assert roofline_estimate(g, pipelined) <= roofline_estimate(g, blocking)
    assert staging_stall_s(g, pipelined) < staging_stall_s(g, blocking)


def test_roofline_charges_permute_gather_only():
    """The kernel's per-row nnz loop makes tile compute permutation-
    invariant (rows run sequentially on the TPU grid), so the roofline must
    NOT fabricate a compute credit for balanced banks: the permute
    candidate pays exactly its inverse-permutation gather and scores no
    better analytically — any unrolled-loop scheduling benefit is wall-mode
    territory."""
    from repro.tuning import permute_bytes

    g = _geom()
    base = dict(tm=8, pad_to=8)
    t_nat = roofline_estimate(g, Candidate("pallas", **base))
    t_perm = roofline_estimate(g, Candidate("pallas", **base, permute=True))
    assert permute_bytes(g, True) > permute_bytes(g, False) == 0.0
    assert t_perm >= t_nat
    # memory-bound geometry: the gather round-trip is visible
    assert t_perm > t_nat


def test_plan_entry_carries_pipeline_and_permute():
    pe = PlanEntry(method="pallas", tm=8, te=8, pad_to=8,
                   pipeline=True, permute=True)
    assert pe.candidate.pipeline and pe.candidate.permute
    d = pe.to_dict()
    assert d["pipeline"] is True and d["permute"] is True
    assert PlanEntry.from_dict(d) == pe


# ---------------------------------------------------------------------------
# bsr axis (BCSR MXU conv): block-shape candidates + MXU-vs-VPU crossover
# ---------------------------------------------------------------------------

def test_candidates_include_bsr_block_shapes():
    """Sparse layers get bsr candidates across the block ladder, each with
    a VMEM-feasible spatial tiling and fused/unfused variants; tm, pad_to
    and the pallas-only schedule flags stay unset on them."""
    from repro.kernels.bsr_conv.ops import bsr_tiling_fits

    g = _geom()
    cands = [c for c in enumerate_candidates(g) if c.method == "bsr"]
    assert cands
    assert {(c.block_m, c.block_n) for c in cands} >= {(8, 128), (16, 128)}
    assert any(c.fuse for c in cands) and any(not c.fuse for c in cands)
    for cd in cands:
        assert cd.tm is None and cd.pad_to is None
        assert not cd.pipeline and not cd.permute
        assert cd.te is not None
        assert bsr_tiling_fits(g.c, g.r, g.s, g.stride, cd.block_m,
                               cd.block_n, cd.te, g.f,
                               fuse_res=cd.fuse and g.residual)


def test_roofline_bsr_beats_vpu_on_moderate_sparsity():
    """The crossover the bsr path exists for: on a compute-heavy layer at
    moderate (~62%) sparsity, the MXU-priced bsr bound must beat the best
    VPU-priced ELL pallas bound and the dense bound — while at extreme
    sparsity the per-nonzero ELL loop does so little work it wins back."""
    g = _geom(m=192, c=64, h=56, w=56, sparsity=0.62, batch=1)
    cands = enumerate_candidates(g)
    t_bsr = min(roofline_estimate(g, c) for c in cands if c.method == "bsr")
    t_ell = min(roofline_estimate(g, c) for c in cands if c.method == "pallas")
    t_dense = roofline_estimate(g, Candidate("dense"))
    assert t_bsr < t_ell and t_bsr < t_dense
    g_hi = _geom(m=192, c=64, h=56, w=56, sparsity=0.98, batch=1)
    cands_hi = enumerate_candidates(g_hi)
    t_bsr_hi = min(roofline_estimate(g_hi, c)
                   for c in cands_hi if c.method == "bsr")
    t_ell_hi = min(roofline_estimate(g_hi, c)
                   for c in cands_hi if c.method == "pallas")
    assert t_ell_hi < t_bsr_hi


def test_roofline_bsr_bigger_bm_amortises_gather():
    """The tile-gather-vs-systolic tradeoff: with identical spatial tiling
    and kept-block fraction, a taller block (bigger bm) amortises the VPU
    patch gather over more MXU rows, so its compute term is no worse."""
    from repro.tuning.measure import _bsr_terms

    g = _geom(m=256, c=256, h=28, w=28, sparsity=0.6)
    t8, _, _ = _bsr_terms(g, Candidate("bsr", te=28, block_m=8, block_n=128))
    t64, _, _ = _bsr_terms(g, Candidate("bsr", te=28, block_m=64, block_n=128))
    assert t64 <= t8


def test_plan_entry_carries_block_shape():
    pe = PlanEntry(method="bsr", te=16, fuse=True,
                   block_m=32, block_n=128)
    assert pe.candidate.block_m == 32 and pe.candidate.block_n == 128
    d = pe.to_dict()
    assert d["block_m"] == 32 and d["block_n"] == 128
    assert PlanEntry.from_dict(d) == pe


def test_auto_executes_bsr_plan():
    """A plan entry pinning the bsr method — block shape, spatial tiling,
    fused epilogue — must execute through method="auto" (interpret mode)
    and match the dense oracle, both with the bank prebuilt by
    apply_plan_to_params and blocked at trace time without it."""
    net = [cnn.Conv("c0", 8, 3, 1, 1, sparsity=0.0), cnn.Relu(),
           cnn.Conv("c1", 16, 3, 1, 1, sparsity=0.7), cnn.Relu()]
    rng = np.random.default_rng(31)
    params = cnn.init_cnn(net, 3, rng, 10)
    x = jnp.asarray(rng.standard_normal((1, 3, 10, 10)).astype(np.float32))
    plan = {"c0": PlanEntry(method="dense"),
            "c1": PlanEntry(method="bsr", te=6, fuse=True,
                            block_m=8, block_n=32)}
    y_dense = cnn.cnn_forward(net, params, x, method="dense")
    # without apply_plan_to_params: the engine blocks the bank at trace time
    y_auto = cnn.cnn_forward(net, params, x, method="auto", plan=plan)
    np.testing.assert_allclose(np.asarray(y_auto), np.asarray(y_dense),
                               rtol=1e-4, atol=1e-4)
    # with it: the prebuilt bcsr_auto bank is used
    apply_plan_to_params(params, plan)
    assert params["c1"]["bcsr_auto"].block == (8, 32)
    y_auto2 = cnn.cnn_forward(net, params, x, method="auto", plan=plan)
    np.testing.assert_allclose(np.asarray(y_auto2), np.asarray(y_dense),
                               rtol=1e-4, atol=1e-4)


def test_roofline_with_weights_recosts_bsr_from_actual_bank():
    """Regression: the geometry-only bsr estimate assumes block-structured
    pruning, but an unstructured magnitude-pruned bank keeps nearly every
    tile.  Weights-aware roofline planning must price bsr at the true
    kept-block count: the estimate-vs-honest bound gap must show, and on a
    high-sparsity layer — where the cheap per-nonzero ELL loop is the real
    winner — the winner must flip off the MXU path the estimate picked."""
    from repro.core import block_prune_conv, magnitude_prune
    from repro.tuning import plan_layer
    from repro.tuning.measure import bcsr_true_kept

    g = ConvGeometry(name="l", m=256, c=256, h=14, w=14, r=3, s=3, stride=1,
                     pad=1, sparsity=0.9, batch=1)
    rng = np.random.default_rng(37)
    w = np.asarray(magnitude_prune(jnp.asarray(
        rng.standard_normal((256, 256, 3, 3)).astype(np.float32)), 0.9))
    # unstructured pruning keeps essentially every (8, 128) tile
    gbn = -(-256 * 9 // 128)
    assert bcsr_true_kept(w, 8, 128) > 0.9 * gbn
    # the estimate prices bsr at ~10% of the tiles and picks it...
    assert plan_layer(g, mode="roofline", backend="cpu").method == "bsr"
    # ...the true near-dense bank costs more, and the winner flips
    cand = Candidate("bsr", te=14, block_m=8, block_n=128)
    assert (roofline_estimate(g, cand, w_dense=w)
            > roofline_estimate(g, cand))
    assert plan_layer(g, mode="roofline", w_dense=w,
                      backend="cpu").method != "bsr"
    # a genuinely block-pruned bank keeps the MXU pick
    wb = np.asarray(block_prune_conv(jnp.asarray(
        rng.standard_normal((256, 256, 3, 3)).astype(np.float32)),
        0.9, (8, 128)))
    assert plan_layer(g, mode="roofline", w_dense=wb,
                      backend="cpu").method == "bsr"


def test_weights_aware_plan_reads_legacy_untagged_entries(monkeypatch):
    """Regression: weights-aware plans key on layer_key + a weight-structure
    tag, but pre-tag caches (v1-v4 migrations, weight-free v5 runs) are
    untagged.  A non-bsr legacy winner must be inherited without
    re-scoring — only bsr entries are structure-sensitive and must be
    re-scored under the tagged key."""
    from repro.core import magnitude_prune
    from repro.engine import lower
    import repro.tuning.planner as planner_mod
    from repro.tuning import plan_program

    # tiny geometry: the weight-free roofline winner here is not bsr
    net = [cnn.Conv("c1", 8, 3, 1, 1, sparsity=0.7), cnn.Relu()]
    program = lower(net, (3, 10, 10))
    cache = PlanCache()
    plan0 = plan_program(program, batch=1, mode="roofline", cache=cache,
                         backend="cpu")
    assert plan0["c1"].method != "bsr"
    legacy_keys = set(cache.entries)
    assert not any("_bk" in k for k in legacy_keys)

    rng = np.random.default_rng(43)
    params = cnn.init_cnn(net, 3, rng, 10)
    calls = []
    orig = planner_mod.plan_layer
    monkeypatch.setattr(planner_mod, "plan_layer",
                        lambda g, **kw: calls.append(g.name) or orig(g, **kw))
    plan1 = plan_program(program, batch=1, mode="roofline", cache=cache,
                         params=params, backend="cpu")
    # the untagged non-bsr entry was inherited: zero re-scoring, same plan
    assert calls == []
    assert plan1["c1"] == plan0["c1"]
    assert set(cache.entries) == legacy_keys

    # a legacy *bsr* entry must NOT be inherited across structures: plant
    # one at the untagged key of a geometry whose estimate picks bsr
    net2 = [cnn.Conv("c2", 192, 3, 1, 1, sparsity=0.62), cnn.Relu()]
    program2 = lower(net2, (64, 56, 56))
    cache2 = PlanCache()
    plan2 = plan_program(program2, batch=1, mode="roofline", cache=cache2,
                         backend="cpu")
    assert plan2["c2"].method == "bsr"
    calls.clear()
    params2 = {"c2": {"w": jnp.asarray(np.asarray(magnitude_prune(jnp.asarray(
        np.random.default_rng(0).standard_normal(
            (192, 64, 3, 3)).astype(np.float32)), 0.62))),
        "b": jnp.zeros((192,), jnp.float32)}}
    plan_program(program2, batch=1, mode="roofline", cache=cache2,
                 params=params2, backend="cpu")
    assert calls == ["c2"]  # re-scored under the structure-tagged key
    assert any("_bk" in k for k in cache2.entries)


def test_auto_plan_uses_bound_params_for_bsr_costing(monkeypatch):
    """The engine's self-tuned roofline plan must pass its bound params so
    bsr costing sees the actual bank structure."""
    from repro.engine import CnnEngine, lower
    import repro.tuning.planner as planner_mod

    net = [cnn.Conv("c1", 8, 3, 1, 1, sparsity=0.7), cnn.Relu()]
    rng = np.random.default_rng(41)
    params = cnn.init_cnn(net, 3, rng, 10)
    engine = CnnEngine(lower(net, (3, 10, 10)), params)
    seen = {}
    orig = planner_mod.plan_program

    def spy(program, **kw):
        seen["params"] = kw.get("params")
        return orig(program, **kw)

    monkeypatch.setattr(planner_mod, "plan_program", spy)
    engine._auto_plan(1)
    assert seen["params"] is params


def test_wall_mode_excludes_bsr_off_tpu():
    """Like the ELL pallas kernel, the bsr kernel is interpret-mode off-TPU
    — wall-timing it would measure Python, so it is not measurable."""
    from repro.tuning import measurable

    assert not measurable(Candidate("bsr", block_m=8, block_n=128), "cpu")
    assert measurable(Candidate("bsr", block_m=8, block_n=128), "tpu")
    assert measurable(Candidate("csr-direct", pad_to=8), "cpu")


# ---------------------------------------------------------------------------
# cache / planner round-trip
# ---------------------------------------------------------------------------

def test_sparsity_bucketing_shares_keys():
    a = layer_key(_geom(sparsity=0.69), "cpu")
    b = layer_key(_geom(sparsity=0.71), "cpu")
    c = layer_key(_geom(sparsity=0.50), "cpu")
    assert a == b != c


def test_plan_cache_roundtrip(tmp_path):
    path = str(tmp_path / "plans" / "cache.json")
    net = cnn.alexnet()
    cache = PlanCache(path)
    plan = plan_network(net, 3, 99, batch=1, mode="roofline", cache=cache,
                        backend="cpu")
    assert len(cache) > 0
    # tune -> serialize -> reload -> identical plan, with zero re-tuning
    # (a miss would write the file again; compare entries directly).
    reloaded = PlanCache(path)
    assert reloaded.entries == cache.entries
    replan = plan_network(net, 3, 99, batch=1, mode="roofline",
                          cache=reloaded, backend="cpu")
    assert replan == plan
    # every sparse layer got a tuned sparse method under roofline scoring
    for layer, _ in cnn.conv_layer_shapes(net, 3, 99):
        pe = plan[layer.name]
        assert isinstance(pe, PlanEntry)
        if layer.sparsity == 0:
            assert pe.method == "dense"


def test_plan_cache_version_guard(tmp_path):
    """An unknown schema version warns and falls back to an empty cache by
    default (a stale cache must not take a deploy down); strict load keeps
    the historical ValueError for tooling that wants to localise it."""
    from repro.tuning.cache import PlanCacheWarning

    path = tmp_path / "bad.json"
    path.write_text('{"version": 999, "entries": {}}')
    with pytest.warns(PlanCacheWarning, match="version 999"):
        cache = PlanCache(str(path))
    assert len(cache) == 0
    with pytest.raises(ValueError, match="version 999"):
        PlanCache().load(str(path), strict=True)


@pytest.mark.parametrize("text, match", [
    ('{"version": 5, "entries": {', "Expecting"),      # truncated mid-write
    ("not json at all", "Expecting"),                  # corrupt
    ("[1, 2, 3]", "not a JSON object"),                # wrong document shape
    ('{"version": 5, "entries": [1]}', "not an object"),  # wrong entries shape
])
def test_plan_cache_mangled_file_falls_back_empty(tmp_path, text, match):
    """Corrupt/truncated cache files emit a diagnostic and fall back to an
    empty cache instead of raising mid-deploy; strict load raises."""
    from repro.tuning.cache import PlanCacheWarning

    path = tmp_path / "mangled.json"
    path.write_text(text)
    with pytest.warns(PlanCacheWarning, match=match):
        cache = PlanCache(str(path))
    assert len(cache) == 0
    with pytest.raises((ValueError, json.JSONDecodeError)):
        PlanCache().load(str(path), strict=True)


def test_plan_cache_malformed_entry_dropped(tmp_path):
    """A single malformed entry is dropped with a warning; healthy siblings
    survive the load."""
    from repro.tuning.cache import CACHE_VERSION, PlanCacheWarning

    path = tmp_path / "partial.json"
    path.write_text(json.dumps({
        "version": CACHE_VERSION,
        "entries": {
            "good": {"method": "dense"},
            "bad": {"tm": 64},          # missing required "method"
            "worse": "not-a-dict",
        }}))
    with pytest.warns(PlanCacheWarning, match="dropped 2 malformed"):
        cache = PlanCache(str(path))
    assert set(cache.entries) == {"good"}
    assert cache.entries["good"].method == "dense"
    with pytest.raises(ValueError, match="malformed"):
        PlanCache().load(str(path), strict=True)


def test_plan_cache_load_errors_counter(tmp_path):
    """Non-strict load failures bump the tuning.cache.load_errors counter
    when telemetry is enabled."""
    from repro import telemetry
    from repro.tuning.cache import PlanCacheWarning

    path = tmp_path / "bad.json"
    path.write_text("garbage")
    with telemetry.enabled():
        before = telemetry.counter("tuning.cache.load_errors").value
        with pytest.warns(PlanCacheWarning):
            PlanCache(str(path))
        after = telemetry.counter("tuning.cache.load_errors").value
    assert after == before + 1


def test_plan_cache_v1_migration(tmp_path):
    """v1 documents (no te/tf, no fuse, no pipeline/permute) load via
    migration: entries get te=None — the untiled schedule the v1 kernel
    ran — fuse=False (the unfused epilogue) and pipeline=permute=False
    (blocking DMA, natural row order), and re-save as the current version."""
    import json

    from repro.tuning.cache import CACHE_VERSION

    path = tmp_path / "v1.json"
    path.write_text(json.dumps({
        "version": 1,
        "entries": {"k1": {"method": "pallas", "tm": 64, "pad_to": 8,
                           "est_s": 1e-5, "source": "roofline"}}}))
    cache = PlanCache(str(path))
    pe = cache.get("k1")
    assert pe == PlanEntry(method="pallas", tm=64, pad_to=8, te=None,
                           fuse=False, pipeline=False, permute=False,
                           est_s=1e-5, source="roofline")
    assert pe.candidate.te is None
    assert pe.candidate.fuse is False
    assert pe.candidate.pipeline is False and pe.candidate.permute is False
    assert pe.candidate.block_m is None and pe.candidate.block_n is None
    out = tmp_path / "v6.json"
    cache.save(str(out))
    doc = json.loads(out.read_text())
    assert doc["version"] == CACHE_VERSION == 6
    assert doc["entries"]["k1"]["te"] is None
    assert doc["entries"]["k1"]["fuse"] is False
    assert doc["entries"]["k1"]["value_dtype"] == "float32"
    assert doc["entries"]["k1"]["pipeline"] is False
    assert doc["entries"]["k1"]["permute"] is False
    assert doc["entries"]["k1"]["block_m"] is None
    # and the migrated file round-trips as current-version
    assert PlanCache(str(out)).get("k1") == pe


def test_plan_cache_v2_migration_roundtrip(tmp_path):
    """v2 documents (te/tf but no fuse/pipeline/permute) load via migration
    — entries get fuse=False (the unfused three-pass epilogue) and
    pipeline=permute=False (the v2 kernel's blocking single-buffer DMA) —
    and the re-saved v6 file round-trips identically."""
    import json

    from repro.tuning.cache import CACHE_VERSION

    path = tmp_path / "v2.json"
    path.write_text(json.dumps({
        "version": 2,
        "entries": {
            "kp": {"method": "pallas", "tm": 32, "te": 16, "tf": 16,
                   "pad_to": 4, "est_s": 2e-5, "source": "measured"},
            "kd": {"method": "dense", "est_s": 0.0, "source": "heuristic"},
        }}))
    cache = PlanCache(str(path))
    pe = cache.get("kp")
    assert pe == PlanEntry(method="pallas", tm=32, te=16, pad_to=4,
                           fuse=False, pipeline=False, permute=False,
                           est_s=2e-5, source="measured")
    assert cache.get("kd").fuse is False
    out = tmp_path / "migrated.json"
    cache.save(str(out))
    doc = json.loads(out.read_text())
    assert doc["version"] == CACHE_VERSION == 6
    assert doc["entries"]["kp"]["fuse"] is False
    assert doc["entries"]["kp"]["pipeline"] is False
    assert doc["entries"]["kp"]["value_dtype"] == "float32"
    reloaded = PlanCache(str(out))
    assert reloaded.entries == cache.entries


def test_plan_cache_v3_migration_roundtrip(tmp_path):
    """v3 documents (fuse but no pipeline/permute) load via migration —
    entries keep their fuse flag and get pipeline=permute=False, the
    blocking natural-order schedule every v3 kernel ran — and the re-saved
    v6 file round-trips identically."""
    import json

    from repro.tuning.cache import CACHE_VERSION

    path = tmp_path / "v3.json"
    path.write_text(json.dumps({
        "version": 3,
        "entries": {
            "kf": {"method": "pallas", "tm": 16, "te": 32, "tf": 32,
                   "pad_to": 8, "fuse": True, "est_s": 3e-5,
                   "source": "measured"},
            "kd": {"method": "csr-direct", "pad_to": 4, "est_s": 1e-4,
                   "source": "roofline"},
        }}))
    cache = PlanCache(str(path))
    pe = cache.get("kf")
    assert pe == PlanEntry(method="pallas", tm=16, te=32, pad_to=8,
                           fuse=True, pipeline=False, permute=False,
                           est_s=3e-5, source="measured")
    assert cache.get("kd").pipeline is False
    out = tmp_path / "migrated.json"
    cache.save(str(out))
    doc = json.loads(out.read_text())
    assert doc["version"] == CACHE_VERSION == 6
    assert doc["entries"]["kf"]["fuse"] is True
    assert doc["entries"]["kf"]["pipeline"] is False
    assert doc["entries"]["kf"]["permute"] is False
    assert PlanCache(str(out)).entries == cache.entries


def test_plan_cache_v4_migration_roundtrip(tmp_path):
    """v4 documents (pipeline/permute but no block shape) load via
    migration — entries keep their schedule flags and get block_m =
    block_n = None (no pre-v5 kernel ran blocked) — and the re-saved v6
    file round-trips identically."""
    import json

    from repro.tuning.cache import CACHE_VERSION

    path = tmp_path / "v4.json"
    path.write_text(json.dumps({
        "version": 4,
        "entries": {
            "kp": {"method": "pallas", "tm": 8, "te": 16, "tf": 16,
                   "pad_to": 8, "fuse": True, "pipeline": True,
                   "permute": True, "est_s": 4e-5, "source": "measured"},
        }}))
    cache = PlanCache(str(path))
    pe = cache.get("kp")
    assert pe == PlanEntry(method="pallas", tm=8, te=16, pad_to=8,
                           fuse=True, pipeline=True, permute=True,
                           block_m=None, block_n=None,
                           est_s=4e-5, source="measured")
    out = tmp_path / "migrated.json"
    cache.save(str(out))
    doc = json.loads(out.read_text())
    assert doc["version"] == CACHE_VERSION == 6
    assert doc["entries"]["kp"]["pipeline"] is True
    assert doc["entries"]["kp"]["block_m"] is None
    assert doc["entries"]["kp"]["value_dtype"] == "float32"
    assert PlanCache(str(out)).entries == cache.entries


def test_plan_cache_migration_chain_v1_to_v6(tmp_path):
    """The full migration chain: one fixture per historical schema (v1-v5)
    loads, defaults exactly the fields its kernels predate, re-persists as
    v6, and the v6 file round-trips bit-for-bit. Every pre-v6 entry streams
    f32 values, so migration pins value_dtype="float32"."""
    import json

    from repro.tuning.cache import CACHE_VERSION, MIGRATABLE_VERSIONS

    fixtures = {
        1: ({"method": "pallas", "tm": 64, "pad_to": 8},
            PlanEntry(method="pallas", tm=64, pad_to=8)),
        2: ({"method": "pallas", "tm": 32, "te": 16, "tf": 16, "pad_to": 4},
            PlanEntry(method="pallas", tm=32, te=16, pad_to=4)),
        3: ({"method": "pallas", "tm": 16, "te": 32, "tf": 32, "pad_to": 8,
             "fuse": True},
            PlanEntry(method="pallas", tm=16, te=32, pad_to=8,
                      fuse=True)),
        4: ({"method": "pallas", "tm": 8, "te": 16, "tf": 16, "pad_to": 8,
             "fuse": True, "pipeline": True, "permute": True},
            PlanEntry(method="pallas", tm=8, te=16, pad_to=8,
                      fuse=True, pipeline=True, permute=True)),
        5: ({"method": "bsr", "te": 16, "tf": 16, "fuse": True,
             "block_m": 8, "block_n": 128},
            PlanEntry(method="bsr", te=16, fuse=True,
                      block_m=8, block_n=128)),
    }
    assert set(fixtures) == set(MIGRATABLE_VERSIONS)
    for ver, (raw, expect) in fixtures.items():
        p = tmp_path / f"v{ver}.json"
        p.write_text(json.dumps({"version": ver, "entries": {"k": raw}}))
        cache = PlanCache(str(p))
        assert cache.get("k") == expect
        assert cache.get("k").value_dtype == "float32"
        if ver < 5:
            assert cache.get("k").block_m is None
        out = tmp_path / f"v{ver}-migrated.json"
        cache.save(str(out))
        doc = json.loads(out.read_text())
        assert doc["version"] == CACHE_VERSION == 6
        assert doc["entries"]["k"]["value_dtype"] == "float32"
        # the column tile is gone: every tile spans all F columns
        assert "tf" not in doc["entries"]["k"]
        assert PlanCache(str(out)).entries == cache.entries


def test_stale_v4_bsr_plan_falls_back_to_dense(tmp_path):
    """A pre-v5 plan entry claiming method="bsr" migrates with no block
    shape; the engine must treat it as stale and execute the dense path —
    numerically identical to method="dense" — instead of crashing."""
    import json

    path = tmp_path / "stale.json"
    path.write_text(json.dumps({
        "version": 4,
        "entries": {"k": {"method": "bsr", "te": 8, "tf": 8,
                          "est_s": 1e-5, "source": "roofline"}}}))
    pe = PlanCache(str(path)).get("k")
    assert pe.method == "bsr" and pe.block_m is None and pe.block_n is None
    net = [cnn.Conv("c1", 8, 3, 1, 1, sparsity=0.75), cnn.Relu()]
    rng = np.random.default_rng(29)
    params = cnn.init_cnn(net, 3, rng, 10)
    x = jnp.asarray(rng.standard_normal((1, 3, 10, 10)).astype(np.float32))
    apply_plan_to_params(params, {"c1": pe})
    assert "bcsr_auto" not in params["c1"]  # nothing to build from a stale entry
    y_auto = cnn.cnn_forward(net, params, x, method="auto", plan={"c1": pe})
    y_dense = cnn.cnn_forward(net, params, x, method="dense")
    np.testing.assert_array_equal(np.asarray(y_auto), np.asarray(y_dense))


def test_wall_mode_measures_and_picks(tmp_path):
    # Tiny single-layer net: wall mode must run and record a measured source.
    net = [cnn.Conv("c1", 8, 3, 1, 1, sparsity=0.7), cnn.Relu()]
    rng = np.random.default_rng(0)
    params = cnn.init_cnn(net, 4, rng, 8)
    plan = plan_network(net, 4, 8, batch=1, mode="wall", cache=PlanCache(),
                        params=params, iters=1, backend="cpu")
    assert plan["c1"].source == "measured"
    assert plan["c1"].method in ("dense", "lowered", "csr-direct")


# ---------------------------------------------------------------------------
# method="auto" numerical equivalence (paper layer slices, interpret mode)
# ---------------------------------------------------------------------------

def _slice(net_name, n_sparse=2, image=12):
    full = cnn.NETWORKS[net_name]()
    convs = [l for l, _ in cnn.conv_layer_shapes(full, 3, 224)]
    picked = ([next(l for l in convs if l.sparsity == 0)]
              + [l for l in convs if l.sparsity > 0][:n_sparse])
    out = []
    for l in picked:
        out.append(dataclasses.replace(
            l, out_c=max(8, min(32, l.out_c // 8)), stride=1))
        out.append(cnn.Relu())
    return out, image


@pytest.mark.parametrize("net_name", ["alexnet", "resnet50"])
def test_auto_matches_dense_on_slice(net_name):
    net, image = _slice(net_name)
    rng = np.random.default_rng(3)
    params = cnn.init_cnn(net, 3, rng, image)
    x = jnp.asarray(rng.standard_normal((1, 3, image, image)).astype(np.float32))
    plan = plan_network(net, 3, image, batch=1, mode="roofline",
                        cache=PlanCache(), backend="cpu")
    apply_plan_to_params(params, plan)
    y_auto = cnn.cnn_forward(net, params, x, method="auto", plan=plan)
    y_dense = cnn.cnn_forward(net, params, x, method="dense")
    np.testing.assert_allclose(np.asarray(y_auto), np.asarray(y_dense),
                               rtol=1e-4, atol=1e-4)


def test_auto_without_plan_self_tunes():
    net = [cnn.Conv("c0", 8, 3, 1, 1, sparsity=0.0), cnn.Relu(),
           cnn.Conv("c1", 8, 3, 1, 1, sparsity=0.75)]
    rng = np.random.default_rng(5)
    params = cnn.init_cnn(net, 3, rng, 8)
    x = jnp.asarray(rng.standard_normal((1, 3, 8, 8)).astype(np.float32))
    y_auto = cnn.cnn_forward(net, params, x, method="auto")
    y_dense = cnn.cnn_forward(net, params, x, method="dense")
    np.testing.assert_allclose(np.asarray(y_auto), np.asarray(y_dense),
                               rtol=1e-4, atol=1e-4)


def test_auto_executes_pipelined_permuted_plan():
    """A plan entry pinning the full v4 schedule — pallas, fused epilogue,
    double-buffered staging, nnz-balanced bank — must execute through
    method="auto" and match the dense oracle (interpret mode)."""
    net = [cnn.Conv("c0", 8, 3, 1, 1, sparsity=0.0), cnn.Relu(),
           cnn.Conv("c1", 8, 3, 1, 1, sparsity=0.75), cnn.Relu()]
    rng = np.random.default_rng(17)
    params = cnn.init_cnn(net, 3, rng, 10)
    x = jnp.asarray(rng.standard_normal((1, 3, 10, 10)).astype(np.float32))
    plan = {"c0": PlanEntry(method="dense"),
            "c1": PlanEntry(method="pallas", tm=4, te=6, pad_to=8,
                            fuse=True, pipeline=True, permute=True)}
    apply_plan_to_params(params, plan)
    assert params["c1"]["ell_auto"].perm is not None  # balanced bank built
    y_auto = cnn.cnn_forward(net, params, x, method="auto", plan=plan)
    y_dense = cnn.cnn_forward(net, params, x, method="dense")
    np.testing.assert_allclose(np.asarray(y_auto), np.asarray(y_dense),
                               rtol=1e-4, atol=1e-4)


def test_auto_balances_in_trace_without_apply_plan():
    """The same permuted plan executed *without* apply_plan_to_params: the
    engine must balance the natural-order bank in-trace (pure gathers)."""
    net = [cnn.Conv("c1", 8, 3, 1, 1, sparsity=0.75), cnn.Relu()]
    rng = np.random.default_rng(19)
    params = cnn.init_cnn(net, 3, rng, 10)
    x = jnp.asarray(rng.standard_normal((1, 3, 10, 10)).astype(np.float32))
    plan = {"c1": PlanEntry(method="pallas", tm=4, te=6, pad_to=8,
                            fuse=True, pipeline=True, permute=True)}
    y_auto = cnn.cnn_forward(net, params, x, method="auto", plan=plan)
    y_dense = cnn.cnn_forward(net, params, x, method="dense")
    np.testing.assert_allclose(np.asarray(y_auto), np.asarray(y_dense),
                               rtol=1e-4, atol=1e-4)


def test_apply_plan_rebuilds_formats():
    net = [cnn.Conv("c1", 8, 3, 1, 1, sparsity=0.8)]
    rng = np.random.default_rng(7)
    params = cnn.init_cnn(net, 4, rng, 8)
    plan = {"c1": PlanEntry(method="csr-direct", pad_to=4)}
    apply_plan_to_params(params, plan)
    assert params["c1"]["ell_auto"].k % 4 == 0
    plan2 = {"c1": PlanEntry(method="lowered", pad_to=16)}
    apply_plan_to_params(params, plan2)
    assert params["c1"]["ell2d_auto"].k % 16 == 0


# ---------------------------------------------------------------------------
# quantised value-dtype axis (v6): opt-in enumeration, roofline credit,
# backend capability filtering, cache round-trip
# ---------------------------------------------------------------------------

def test_candidate_space_default_is_f32_only():
    """Narrow value storage is lossy, so the default space must stay
    float32 — quantised candidates appear only on explicit opt-in, and
    then for the Pallas paths alone (dense/lowered/csr-direct have no
    narrow bank to stream)."""
    from repro.tuning.space import VALUE_DTYPES

    g = _geom()
    assert {c.value_dtype for c in enumerate_candidates(g)} == {"float32"}
    cands = enumerate_candidates(g, value_dtypes=VALUE_DTYPES)
    for method in ("pallas", "bsr"):
        assert ({c.value_dtype for c in cands if c.method == method}
                == set(VALUE_DTYPES))
    assert all(c.value_dtype == "float32" for c in cands
               if c.method not in ("pallas", "bsr"))
    _assert_pallas_fits(g, cands)


def test_allowed_value_dtypes_backend_policy():
    """fp8 needs TPU hardware casts; int8 and f32 run everywhere.  This is
    the single capability table the planner and the static verifier share,
    so they can never disagree about a plan's executability."""
    from repro.tuning.space import VALUE_DTYPES, allowed_value_dtypes

    assert allowed_value_dtypes("tpu") == VALUE_DTYPES
    for backend in ("cpu", "gpu"):
        got = allowed_value_dtypes(backend)
        assert "float8_e4m3fn" not in got
        assert "float32" in got and "int8" in got


def test_roofline_credits_quantised_value_stream():
    """Same schedule, narrower values: the roofline charges the int8
    variant strictly fewer HBM bytes than its f32 twin (smaller value
    stream + one f32 scale row) for both Pallas paths, and on a
    weight-bound geometry — a big bank over a tiny feature map — the time
    bound drops too."""
    from repro.tuning.measure import candidate_cost

    g = _geom(m=256, c=256, h=28, w=28, sparsity=0.9)
    pallas = Candidate("pallas", tm=8, pad_to=8)
    bsr = Candidate("bsr", block_m=8, block_n=128)
    for cand in (pallas, bsr):
        q = dataclasses.replace(cand, value_dtype="int8")
        assert (candidate_cost(g, q)["hbm_bytes"]
                < candidate_cost(g, cand)["hbm_bytes"])
    g_wb = _geom(m=512, c=512, h=7, w=7, sparsity=0.95, batch=1)
    for cand in (pallas, bsr):
        q = dataclasses.replace(cand, value_dtype="int8")
        assert roofline_estimate(g_wb, q) < roofline_estimate(g_wb, cand)


def test_plan_layer_quantize_opt_in():
    """plan_layer never pins a narrow dtype unless asked; with
    quantize=True the roofline prefers the smaller value stream on a
    memory-bound layer, and an off-TPU backend can never pin fp8."""
    from repro.tuning import plan_layer

    g = _geom(m=256, c=256, h=28, w=28, sparsity=0.9)
    assert plan_layer(g, mode="roofline", backend="cpu").value_dtype == "float32"
    pe = plan_layer(g, mode="roofline", backend="cpu", quantize=True)
    assert pe.method in ("pallas", "bsr")
    assert pe.value_dtype == "int8"   # cpu backend: fp8 filtered out
    pe_tpu = plan_layer(g, mode="roofline", backend="tpu", quantize=True)
    assert pe_tpu.value_dtype in ("int8", "float8_e4m3fn")


def test_plan_entry_value_dtype_roundtrip():
    """value_dtype survives the cache dict round-trip, and absent keys
    (v1-v5 documents) default to the f32 value stream."""
    pe = PlanEntry(method="bsr", te=16, block_m=8, block_n=128,
                   value_dtype="int8", est_s=1e-5, source="roofline")
    d = pe.to_dict()
    assert d["value_dtype"] == "int8"
    assert PlanEntry.from_dict(d) == pe
    legacy = {k: v for k, v in d.items() if k != "value_dtype"}
    assert PlanEntry.from_dict(legacy).value_dtype == "float32"

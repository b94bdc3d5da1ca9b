"""Compile-once graph engine: lowering structure, bit-for-bit equivalence
with the pre-engine spec walkers, and bind-time FC parameter creation.

The "legacy" reference implementations below are verbatim copies of the
historical ``models/cnn.py`` walkers (init_cnn.walk / cnn_forward.walk) —
the engine must reproduce their outputs *bit-for-bit* for the
dense/lowered/csr-direct methods on AlexNet/GoogLeNet/ResNet-50 smoke
shapes, per the refactor's acceptance contract.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.direct_conv import dense_conv, direct_sparse_conv
from repro.core.lowering import lowered_sparse_conv
from repro.core.pruning import magnitude_prune
from repro.core.sparse_format import ell_from_dense, ell_from_dense_conv
from repro.engine import (ConcatOp, ConvOp, FCOp, PoolOp,
                          ReluOp, ResidualAddOp, lower)
from repro.models import cnn

SMOKE = [("alexnet", 67), ("googlenet", 48), ("resnet50", 48)]


# ---------------------------------------------------------------------------
# legacy reference: the pre-engine walkers, verbatim
# ---------------------------------------------------------------------------

def legacy_init_cnn(net, in_c, rng, image=224):
    params = {}

    def walk(layers, c):
        for l in layers:
            if isinstance(l, cnn.Conv):
                w = (rng.standard_normal((l.out_c, c, l.k, l.k))
                     .astype(np.float32) * (2.0 / (c * l.k * l.k)) ** 0.5)
                if l.sparsity > 0:
                    w = np.asarray(magnitude_prune(jnp.asarray(w), l.sparsity))
                entry = {"w": jnp.asarray(w),
                         "b": jnp.zeros((l.out_c,), jnp.float32)}
                if l.sparsity > 0:
                    entry["ell"] = ell_from_dense_conv(w)
                    entry["ell2d"] = ell_from_dense(w.reshape(l.out_c, -1))
                params[l.name] = entry
                c = l.out_c
            elif isinstance(l, cnn.Concat):
                c = sum(walk(br, c) for br in l.branches)
            elif isinstance(l, cnn.Residual):
                cb = walk(l.body, c)
                if l.proj is not None:
                    walk((l.proj,), c)
                c = cb
        return c

    walk(net, in_c)
    params["_fc_rng"] = rng.integers(0, 2**31)
    return params


def _legacy_conv_apply(l, entry, x, method):
    if l.sparsity == 0 or method == "dense":
        y = dense_conv(x, entry["w"], stride=l.stride, padding=l.pad)
    elif method == "lowered":
        y = lowered_sparse_conv(x, entry["ell2d"], l.k, l.k,
                                stride=l.stride, padding=l.pad)
    elif method == "csr-direct":
        y = direct_sparse_conv(x, entry["ell"], stride=l.stride, padding=l.pad)
    else:
        raise ValueError(method)
    return y + entry["b"][None, :, None, None]


def _legacy_pool(l, x):
    if l.kind == "gap":
        return x.mean(axis=(2, 3), keepdims=True)
    init = -jnp.inf if l.kind == "max" else 0.0
    op = jax.lax.max if l.kind == "max" else jax.lax.add
    y = jax.lax.reduce_window(
        x, init, op, (1, 1, l.k, l.k), (1, 1, l.stride, l.stride),
        ((0, 0), (0, 0), (l.pad, l.pad), (l.pad, l.pad)))
    if l.kind == "avg":
        y = y / (l.k * l.k)
    return y


def legacy_cnn_forward(net, params, x, method="dense"):
    fc_rng = np.random.default_rng(int(params["_fc_rng"]))

    def walk(layers, x):
        for l in layers:
            if isinstance(l, cnn.Conv):
                x = _legacy_conv_apply(l, params[l.name], x, method)
            elif isinstance(l, cnn.Relu):
                x = jax.nn.relu(x)
            elif isinstance(l, cnn.Pool):
                x = _legacy_pool(l, x)
            elif isinstance(l, cnn.Concat):
                x = jnp.concatenate([walk(br, x) for br in l.branches], axis=1)
            elif isinstance(l, cnn.Residual):
                y = walk(l.body, x)
                sc = (_legacy_conv_apply(l.proj, params[l.proj.name], x, method)
                      if l.proj is not None else x)
                x = y + sc
            elif isinstance(l, cnn.FC):
                flat = x.reshape(x.shape[0], -1)
                key = f"{l.name}:{flat.shape[1]}"
                if key not in params:
                    params[key] = (
                        fc_rng.standard_normal((flat.shape[1], l.out_f))
                        .astype(np.float32) * (1.0 / flat.shape[1]) ** 0.5)
                x = flat @ params[key]
        return x

    return walk(net, x)


# ---------------------------------------------------------------------------
# lowering structure
# ---------------------------------------------------------------------------

def test_lowering_is_flat_and_fused():
    net = cnn.NETWORKS["alexnet"]()
    prog = lower(net, (3, 67, 67))
    kinds = {type(op) for op in prog.ops}
    assert kinds <= {ConvOp, PoolOp, FCOp, ReluOp, ConcatOp, ResidualAddOp}
    convs = prog.conv_ops
    assert len(convs) == 5
    # every AlexNet conv is followed by a ReLU -> fused at lowering time
    assert all(op.fuse_relu for op in convs)
    # the conv+ReLU pairs collapsed: only the two post-FC ReLUs remain
    assert sum(isinstance(op, ReluOp) for op in prog.ops) == 2
    # geometry statically resolved: conv1 stride-4 stem at 67px -> 15x15 out
    assert (convs[0].e, convs[0].f) == (15, 15)
    # FC fan-in resolved statically (no lazy flattened-dim discovery)
    assert prog.fc_ops[0].in_f == 256 * 1 * 1


def test_lowering_fuses_bottleneck_tail():
    net = cnn.NETWORKS["resnet50"]()
    prog = lower(net, (3, 64, 64))
    tails = [op for op in prog.conv_ops if op.res is not None]
    # one fused tail per bottleneck (3+4+6+3 = 16 blocks), shortcut + ReLU
    assert len(tails) == 16
    assert all(op.fuse_relu for op in tails)
    assert all(op.name.endswith("1x1b") for op in tails)
    # no standalone residual-add ops remain
    assert not any(isinstance(op, ResidualAddOp) for op in prog.ops)
    # the shortcut value is defined before the tail conv consumes it
    for tail in tails:
        defined = {0}
        for op in prog.ops:
            if op is tail:
                assert tail.res in defined
                break
            defined.add(op.out)


def test_conv_table_matches_legacy_walk_order():
    """conv_table drives init: it must visit convs in the historical order
    (Residual: body then proj) so RNG draws line up bit-for-bit."""
    net = cnn.NETWORKS["resnet50"]()
    prog = lower(net, (3, 64, 64))
    names = [l.name for l, _ in prog.conv_table]
    i_body = names.index("res2a/1x1b")
    i_proj = names.index("res2a/proj")
    assert i_body < i_proj  # body before proj, as the legacy walker did
    legacy = legacy_init_cnn(net, 3, np.random.default_rng(0), 64)
    assert [n for n in names] == [k for k in legacy if k != "_fc_rng"]


def test_shape_table_delegates_to_lowering():
    net = cnn.NETWORKS["googlenet"]()
    shapes = cnn.conv_layer_shapes(net, 3, 96)
    prog = lower(net, (3, 96, 96))
    assert shapes == list(prog.conv_table)
    # spot-check a known geometry: conv2 sees the pooled 24x24 map
    by_name = {l.name: s for l, s in shapes}
    assert by_name["conv2"] == (64, 24, 24)


# ---------------------------------------------------------------------------
# bit-for-bit equivalence with the pre-engine implementation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("net_name,image", SMOKE)
def test_init_matches_legacy_bitwise(net_name, image):
    net = cnn.NETWORKS[net_name]()
    new = cnn.init_cnn(net, 3, np.random.default_rng(0), image)
    old = legacy_init_cnn(net, 3, np.random.default_rng(0), image)
    assert int(new["_fc_rng"]) == int(old["_fc_rng"])
    assert set(old) == set(new)
    for k in old:
        if k == "_fc_rng":
            continue
        np.testing.assert_array_equal(np.asarray(old[k]["w"]),
                                      np.asarray(new[k]["w"]))


@pytest.mark.parametrize("net_name,image", SMOKE)
@pytest.mark.parametrize("method", ["dense", "lowered", "csr-direct"])
def test_forward_matches_legacy_bitwise(net_name, image, method):
    net = cnn.NETWORKS[net_name]()
    rng = np.random.default_rng(7)
    params = cnn.init_cnn(net, 3, rng, image)
    x = jnp.asarray(np.random.default_rng(11)
                    .standard_normal((1, 3, image, image)).astype(np.float32))
    old = np.asarray(jax.jit(functools.partial(
        legacy_cnn_forward, net, params, method=method))(x))
    new = np.asarray(cnn.cnn_forward(net, params, x, method))
    np.testing.assert_array_equal(old, new)


# ---------------------------------------------------------------------------
# FC params: created at bind, never inside a trace (satellite regression)
# ---------------------------------------------------------------------------

def _fc_net():
    return [cnn.Conv("c0", 4, 3, 1, 1, sparsity=0.0), cnn.Relu(),
            cnn.Pool("max", 2, 2), cnn.FC("fc1", 16), cnn.Relu(),
            cnn.FC("fc2", 8)]


def test_fc_weights_created_at_bind_not_in_params():
    """The engine never mutates params during a trace: FC weights live in
    the engine bind, keyed on (name, static fan-in)."""
    net = _fc_net()
    params = cnn.init_cnn(net, 3, np.random.default_rng(0), 8)
    keys_before = set(params)
    x = jnp.ones((2, 3, 8, 8), jnp.float32)
    y = cnn.cnn_forward(net, params, x)
    assert y.shape == (2, 8)
    assert set(params) == keys_before  # no lazily-injected FC entries
    eng = cnn.engine_for(net, params, (3, 8, 8))
    assert ("fc1", 4 * 4 * 4) in eng.fc_weights


def test_fc_traces_at_two_image_sizes_do_not_collide():
    """Two traces at different image sizes must not collide: each size's
    outputs are deterministic regardless of which size traced first.  (The
    historical lazy creation was order-dependent — whichever size ran first
    pinned the downstream FC draws for every later size.)"""
    net = _fc_net()
    params = cnn.init_cnn(net, 3, np.random.default_rng(0), 8)
    xa = jnp.ones((1, 3, 8, 8), jnp.float32)
    xb = jnp.ones((1, 3, 12, 12), jnp.float32)
    ya_first = np.asarray(cnn.cnn_forward(net, params, xa))
    yb_second = np.asarray(cnn.cnn_forward(net, params, xb))
    # fresh params, reversed call order: outputs must be unchanged
    params2 = cnn.init_cnn(net, 3, np.random.default_rng(0), 8)
    yb_first = np.asarray(cnn.cnn_forward(net, params2, xb))
    ya_second = np.asarray(cnn.cnn_forward(net, params2, xa))
    np.testing.assert_array_equal(ya_first, ya_second)
    np.testing.assert_array_equal(yb_second, yb_first)
    ea = cnn.engine_for(net, params, (3, 8, 8))
    eb = cnn.engine_for(net, params, (3, 12, 12))
    (ka,) = [k for k in ea.fc_weights if k[0] == "fc1"]
    (kb,) = [k for k in eb.fc_weights if k[0] == "fc1"]
    assert ka != kb  # different fan-ins -> different keys, no collision
    # and binds are reproducible: same params identity, same weights
    np.testing.assert_array_equal(
        ea.fc_weights[ka],
        cnn.engine_for(net, params2, (3, 8, 8)).fc_weights[ka])


# ---------------------------------------------------------------------------
# engine execution: cached jit + fused pallas agreement
# ---------------------------------------------------------------------------

def test_engine_caches_one_jit_per_method_and_shape():
    net = _fc_net()
    params = cnn.init_cnn(net, 3, np.random.default_rng(0), 8)
    eng = cnn.engine_for(net, params, (3, 8, 8))
    x = jnp.ones((1, 3, 8, 8), jnp.float32)
    eng(x, "dense")
    eng(x, "dense")
    assert len(eng._fns) == 1
    eng(x, "csr-direct")
    assert len(eng._fns) == 2
    eng(jnp.ones((2, 3, 8, 8), jnp.float32), "dense")
    assert len(eng._fns) == 3
    # repeated cnn_forward calls reuse the memoized engine
    assert cnn.engine_for(net, params, (3, 8, 8)) is eng


def test_params_update_rebinds_engine():
    """Replacing a weight (or apply_plan_to_params adding formats) after a
    forward must bind a fresh engine — not replay a jit that baked the old
    arrays in as constants."""
    net = [cnn.Conv("c0", 4, 3, 1, 1, sparsity=0.0), cnn.Relu()]
    params = cnn.init_cnn(net, 3, np.random.default_rng(0), 8)
    x = jnp.ones((1, 3, 8, 8), jnp.float32)
    y0 = np.asarray(cnn.cnn_forward(net, params, x))
    params["c0"]["w"] = params["c0"]["w"] * 2.0
    y1 = np.asarray(cnn.cnn_forward(net, params, x))
    np.testing.assert_array_equal(y1, 2.0 * y0)


@pytest.mark.parametrize("method", ["pallas", "auto"])
def test_engine_fused_methods_match_dense(method):
    """Fused in-kernel epilogue (bias/ReLU/bottleneck shortcut) agrees with
    the dense oracle end-to-end, including a projection residual block."""
    net = [cnn.Conv("c0", 8, 3, 2, 1, sparsity=0.0), cnn.Relu(),
           cnn.Residual(body=(cnn.Conv("r/1x1a", 8, 1, sparsity=0.7),
                              cnn.Relu(),
                              cnn.Conv("r/1x1b", 16, 1, sparsity=0.7)),
                        proj=cnn.Conv("r/proj", 16, 1, sparsity=0.0)),
           cnn.Relu()]
    rng = np.random.default_rng(3)
    params = cnn.init_cnn(net, 3, rng, 12)
    # non-zero biases so the fused bias add is actually exercised
    for name in ("c0", "r/1x1a", "r/1x1b", "r/proj"):
        m = params[name]["b"].shape[0]
        params[name]["b"] = jnp.asarray(
            rng.standard_normal((m,)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((2, 3, 12, 12)).astype(np.float32))
    ref = np.asarray(cnn.cnn_forward(net, params, x, "dense"))
    out = np.asarray(cnn.cnn_forward(net, params, x, method))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    unfused = np.asarray(cnn.engine_for(net, params, (3, 12, 12))(
        x, "pallas", fuse=False))
    np.testing.assert_allclose(unfused, ref, rtol=1e-5, atol=1e-5)


def test_trace_built_bcsr_bank_survives_retrace():
    """A BCSR bank blocked from the dense weights inside one trace is cached
    on the engine; a later trace (another method reusing the bank, or the
    AOT ``lowered`` retrace) must read concrete arrays, not a leaked
    tracer."""
    import dataclasses

    from repro.tuning import PlanCache, plan_program

    net = [cnn.Conv("c0", 8, 3, 1, 1, sparsity=0.0), cnn.Relu(),
           cnn.Conv("c1", 16, 3, 1, 1, sparsity=0.7), cnn.Relu()]
    params = cnn.init_cnn(net, 3, np.random.default_rng(0), 8)
    eng = cnn.engine_for(net, params, (3, 8, 8))
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (1, 3, 8, 8)).astype(np.float32))
    ref = np.asarray(eng(x, "dense"))
    y = np.asarray(eng(x, "bsr"))
    assert eng._bcc_cache
    for bank in eng._bcc_cache.values():
        assert not isinstance(bank.blocks, jax.core.Tracer)
    eng.lowered(x, "bsr").compile()
    bsr_plan = {"c1": dataclasses.replace(
        plan_program(eng.program, batch=1, mode="roofline",
                     cache=PlanCache(), backend="cpu")["c1"],
        method="bsr", block_m=8, block_n=128, te=None, tm=None)}
    y_auto = np.asarray(eng(x, "auto", plan_override=bsr_plan))
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(y_auto, ref, rtol=1e-4, atol=1e-4)

# ---------------------------------------------------------------------------
# quantised value streams: pinned plans execute narrow banks, stale plans
# fall back loudly
# ---------------------------------------------------------------------------

def _quant_micro():
    import dataclasses

    from repro.tuning import PlanCache, plan_program

    net = [cnn.Conv("c0", 8, 3, 1, 1, sparsity=0.0), cnn.Relu(),
           cnn.Conv("c1", 8, 3, 1, 1, sparsity=0.75), cnn.Relu(),
           cnn.Pool("gap"), cnn.FC("fc", 10)]
    rng = np.random.default_rng(0)
    program = lower(net, (3, 8, 8))
    params = cnn.init_cnn(net, 3, rng, 8)
    x = rng.standard_normal((1, 3, 8, 8)).astype(np.float32)
    plan = plan_program(program, batch=1, mode="roofline", cache=PlanCache(),
                        backend="cpu")
    qplan = {name: (dataclasses.replace(pe, value_dtype="int8")
                    if pe.method in ("pallas", "bsr") else pe)
             for name, pe in plan.items()}
    assert any(pe.value_dtype == "int8" for pe in qplan.values())
    return program, params, x, plan, qplan


def test_engine_int8_pinned_plan_executes_quantised():
    """An int8-pinned plan over host-quantised banks executes its planned
    kernels — zero fallbacks, the report rows carry the executed narrow
    dtype — and the output agrees with the f32 forward to quantisation
    tolerance."""
    from repro import telemetry
    from repro.engine import CnnEngine
    from repro.tuning import apply_plan_to_params

    program, params, x, plan, qplan = _quant_micro()
    qparams = apply_plan_to_params(params, qplan)
    engine = CnnEngine(program, qparams, qplan, strict=True)
    telemetry.reset()
    try:
        with telemetry.enabled():
            y_q = np.asarray(engine(x, "auto"))
            report = engine.last_report
    finally:
        telemetry.reset()
    assert report is not None and report.fallback_count == 0
    assert any(o.value_dtype == "int8" for o in report.ops)
    y_f = np.asarray(CnnEngine(program, params, None)(x, "dense"))
    rel = np.abs(y_q - y_f).max() / (np.abs(y_f).max() or 1.0)
    assert rel < 0.05, rel


def test_engine_int8_plan_quantises_f32_bank_in_trace():
    """A narrow plan bound over plain f32 banks quantises in-trace — same
    per-channel scales, baked into the jit — so the output is bit-identical
    to the host-side apply_plan_to_params route."""
    from repro.engine import CnnEngine
    from repro.tuning import apply_plan_to_params

    program, params, x, plan, qplan = _quant_micro()
    y_trace = np.asarray(CnnEngine(program, params, qplan)(x, "auto"))
    qparams = apply_plan_to_params(params, qplan)
    y_host = np.asarray(CnnEngine(program, qparams, qplan)(x, "auto"))
    np.testing.assert_array_equal(y_trace, y_host)


def test_engine_value_dtype_mismatch_falls_back_dense():
    """A migrated f32 plan executed against an already-quantised bank must
    NOT silently dequantise: the op falls back to dense with the
    ``value_dtype_mismatch`` reason (and so stays numerically exact)."""
    from repro import telemetry
    from repro.engine import CnnEngine
    from repro.tuning import apply_plan_to_params

    program, params, x, plan, qplan = _quant_micro()
    qparams = apply_plan_to_params(params, qplan)   # int8 banks...
    engine = CnnEngine(program, qparams, plan)      # ...but the f32 plan
    telemetry.reset()
    try:
        with telemetry.enabled():
            y = np.asarray(engine(x, "auto"))
            report = engine.last_report
    finally:
        telemetry.reset()
    assert report is not None and report.fallback_count > 0
    reasons = {o.fallback_reason for o in report.fallback_ops}
    assert reasons == {"value_dtype_mismatch"}
    # every mismatched op executed the exact dense path
    assert all(o.value_dtype == "float32" for o in report.ops)
    y_dense = np.asarray(CnnEngine(program, params, None)(x, "dense"))
    np.testing.assert_allclose(y, y_dense, rtol=1e-5, atol=1e-6)

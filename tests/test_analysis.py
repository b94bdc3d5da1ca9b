"""Pre-flight static verifier: rule packs, fixtures, CLI, strict bind.

Everything here is static Python over shapes, plan documents, and parsed
ASTs — no kernel launches, no jit compiles (the engine strict-bind test
binds but never executes)."""
import json
import os
import textwrap

import numpy as np
import pytest

from repro.analysis import Diagnostic, PreflightError, REASON_RULES
from repro.analysis import ast_lints, plan_rules, program_rules
from repro.analysis.checker import (ALL_RULES, DEFAULT_NETS,
                                    default_kernel_paths, default_plan_path,
                                    run_check)
from repro.analysis.cli import main as cli_main
from repro.engine import CnnEngine, init_conv_params, lower
from repro.engine.program import ConvOp, Program, ReluOp
from repro.models import cnn
from repro.tuning.cache import PlanEntry

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "plan_caches")


def rules_of(diags, severity=None):
    return {d.rule for d in diags
            if severity is None or d.severity == severity}


# ---------------------------------------------------------------------------
# diagnostics vocabulary
# ---------------------------------------------------------------------------

def test_every_fallback_reason_has_a_static_rule():
    """The verifier's core contract: each runtime fallback reason code has
    a static rule that would have caught it pre-flight."""
    from repro.telemetry.fallback import REASONS

    assert set(REASON_RULES) == set(REASONS)
    for rule in REASON_RULES.values():
        assert rule in ALL_RULES, rule


def test_diagnostic_severity_validated():
    with pytest.raises(ValueError):
        Diagnostic(rule="x", severity="fatal", message="m")


def test_rule_catalogue_ids_are_dotted_and_unique():
    for rule, (severity, doc) in ALL_RULES.items():
        pack, _, name = rule.partition(".")
        assert pack in ("sched", "plan", "prog", "lint") and name, rule
        assert severity in ("error", "warning", "info")
        assert doc


# ---------------------------------------------------------------------------
# plan-cache rules: known-bad fixtures -> exact rule ids
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fixture, rule, severity", [
    ("stale_v4_bsr.json", "plan.stale_bsr_no_block", "error"),
    ("nondividing_tm.json", "sched.nondividing_tm", "error"),
    ("vmem_busting_tiling.json", "sched.vmem_tiling", "error"),
    ("vmem_busting_pipeline.json", "sched.pipeline_demoted", "warning"),
    ("bad_key.json", "plan.key_unparsable", "error"),
    ("fp8_on_cpu.json", "sched.value_dtype", "error"),
    ("bad_value_dtype.json", "sched.value_dtype", "error"),
])
def test_known_bad_fixture(fixture, rule, severity):
    diags = plan_rules.check_plan_file(os.path.join(FIXTURES, fixture))
    assert rule in rules_of(diags, severity), [d.format() for d in diags]


def test_pipeline_fixture_demotes_but_does_not_error():
    """The VMEM-busting *pipelined* tiling fits unpipelined: the kernel
    silently runs the blocking schedule, so the finding is a warning, not
    a dispatch error."""
    diags = plan_rules.check_plan_file(
        os.path.join(FIXTURES, "vmem_busting_pipeline.json"))
    assert not rules_of(diags, "error")


def test_plan_rules_unreadable_and_schema(tmp_path):
    p = tmp_path / "corrupt.json"
    p.write_text("{not json")
    assert rules_of(plan_rules.check_plan_file(str(p))) == {"plan.unreadable"}
    p2 = tmp_path / "future.json"
    p2.write_text('{"version": 999, "entries": {}}')
    assert rules_of(plan_rules.check_plan_file(str(p2))) == {
        "plan.schema_version"}
    assert rules_of(plan_rules.check_plan_file(str(tmp_path / "absent.json")),
                    ) == {"plan.unreadable"}


def test_plan_rules_unknown_method_and_structure_tag(tmp_path):
    key = "m64_c32_h14w14_r3s3_st1_p1_n1_ep10_sp0.7_float32_cpu"
    p = tmp_path / "cache.json"
    p.write_text(json.dumps({"version": 5, "entries": {
        key: {"method": "winograd"},
        key + "_bk9.5": {"method": "dense"},
    }}))
    rules = rules_of(plan_rules.check_plan_file(str(p)), "error")
    assert "plan.unknown_method" in rules
    assert "plan.structure_tag" in rules


def test_plan_rules_geometry_mismatch(tmp_path):
    # Parses fine but 5x5 kernel cannot fit a 3x3 unpadded input.
    key = "m64_c32_h3w3_r5s5_st1_p0_n1_ep10_sp0.7_float32_cpu"
    p = tmp_path / "cache.json"
    p.write_text(json.dumps({"version": 5, "entries": {
        key: {"method": "dense"}}}))
    assert rules_of(plan_rules.check_plan_file(str(p)), "error") == {
        "plan.geometry_mismatch"}


def test_shipped_default_plans_are_clean():
    for net in DEFAULT_NETS:
        path = default_plan_path(net)
        assert path is not None, f"no shipped plan for {net}"
        diags = plan_rules.check_plan_file(path)
        assert not rules_of(diags, "error"), [d.format() for d in diags]


# ---------------------------------------------------------------------------
# program rules
# ---------------------------------------------------------------------------

def _conv(name, src, out, c, h, w, m, k, stride, pad, e, f, **kw):
    return ConvOp(name=name, src=src, out=out, c=c, h=h, w=w, m=m, k=k,
                  stride=stride, pad=pad, sparsity=0.7, e=e, f=f, **kw)


def test_program_rules_clean_on_real_nets():
    for net in DEFAULT_NETS:
        program = lower(cnn.NETWORKS[net](), (3, 224, 224))
        diags = program_rules.check_program(program, net=net)
        assert not diags, [d.format() for d in diags]


def test_program_rules_geometry_chain():
    op = _conv("c1", 0, 1, c=3, h=8, w=8, m=4, k=3, stride=1, pad=1,
               e=9, f=9)  # arithmetic says 8x8
    prog = Program(ops=(op,), out=1, in_shape=(3, 8, 8), conv_table=())
    assert "prog.geometry_chain" in rules_of(
        program_rules.check_program(prog), "error")


def test_program_rules_input_mismatch():
    op = _conv("c1", 0, 1, c=16, h=8, w=8, m=4, k=3, stride=1, pad=1,
               e=8, f=8)  # input is (3, 8, 8), not (16, 8, 8)
    prog = Program(ops=(op,), out=1, in_shape=(3, 8, 8), conv_table=())
    assert "prog.geometry_chain" in rules_of(
        program_rules.check_program(prog), "error")


def test_program_rules_ssa_and_out():
    op1 = _conv("c1", 0, 1, c=3, h=8, w=8, m=4, k=3, stride=1, pad=1,
                e=8, f=8)
    op2 = _conv("c2", 5, 2, c=4, h=8, w=8, m=4, k=3, stride=1, pad=1,
                e=8, f=8)  # src 5 never defined
    prog = Program(ops=(op1, op2), out=9, in_shape=(3, 8, 8), conv_table=())
    rules = rules_of(program_rules.check_program(prog), "error")
    assert "prog.ssa_form" in rules
    assert "prog.out_undefined" in rules


def test_program_rules_epilogue_signature():
    sc = _conv("proj", 0, 1, c=3, h=8, w=8, m=8, k=1, stride=1, pad=0,
               e=8, f=8)
    tail = _conv("tail", 0, 2, c=3, h=8, w=8, m=4, k=3, stride=1, pad=1,
                 e=8, f=8, res=1)  # shortcut is (8, 8, 8), conv out (4, 8, 8)
    prog = Program(ops=(sc, tail), out=2, in_shape=(3, 8, 8), conv_table=())
    assert "prog.epilogue_signature" in rules_of(
        program_rules.check_program(prog), "error")


def test_program_rules_unfused_relu_and_dead_value():
    op1 = _conv("c1", 0, 1, c=3, h=8, w=8, m=4, k=3, stride=1, pad=1,
                e=8, f=8)
    relu = ReluOp(src=1, out=2)
    dead = _conv("c2", 0, 3, c=3, h=8, w=8, m=4, k=3, stride=1, pad=1,
                 e=8, f=8)
    prog = Program(ops=(op1, relu, dead), out=2, in_shape=(3, 8, 8),
                   conv_table=())
    rules = rules_of(program_rules.check_program(prog), "warning")
    assert "prog.unfused_relu" in rules
    assert "prog.dead_value" in rules


# ---------------------------------------------------------------------------
# AST lints
# ---------------------------------------------------------------------------

def _lint(tmp_path, source):
    p = tmp_path / "kern.py"
    p.write_text(textwrap.dedent(source))
    return ast_lints.check_source(str(p))


def test_lint_traced_branch(tmp_path):
    diags = _lint(tmp_path, """
        def _kernel(x_ref, o_ref):
            i = pl.program_id(0)
            j = i * 2
            if j > 0:
                o_ref[0] = x_ref[0]
    """)
    assert rules_of(diags) == {"lint.traced_branch"}


def test_lint_traced_branch_on_ref_load(tmp_path):
    diags = _lint(tmp_path, """
        def _kernel(nnz_ref, o_ref):
            n = nnz_ref[0]
            while n > 0:
                n = n - 1
    """)
    assert "lint.traced_branch" in rules_of(diags)


def test_lint_static_branch_ok(tmp_path):
    diags = _lint(tmp_path, """
        def _kernel(x_ref, o_ref, *, pipeline: bool):
            i = pl.program_id(0)
            if pipeline:
                o_ref[0] = x_ref[i] * 2
            hi = i + 1 if pipeline else 0
    """)
    assert not diags


def test_lint_grid_alloc(tmp_path):
    diags = _lint(tmp_path, """
        def _kernel(x_ref, o_ref):
            def body(k, acc):
                t = jnp.zeros((8,), dtype=jnp.float32)
                return acc + t
            acc = lax.fori_loop(0, 4, body, jnp.zeros((8,), jnp.float32))
            o_ref[...] = acc
    """)
    assert rules_of(diags) == {"lint.grid_alloc"}


def test_lint_grid_alloc_outer_loop_ok(tmp_path):
    # Allocation in a loop body that itself runs fori_loop (the per-channel
    # accumulator pattern of the sparse conv kernel) is allowed.
    diags = _lint(tmp_path, """
        def _kernel(x_ref, o_ref):
            def channel(ml, _):
                def body(k, acc):
                    return acc + x_ref[ml, k]
                acc0 = jnp.zeros((8,), dtype=jnp.float32)
                o_ref[ml] = lax.fori_loop(0, 4, body, acc0)
                return 0
            lax.fori_loop(0, 8, channel, 0)
    """)
    assert not diags


def test_lint_accum_dtype(tmp_path):
    diags = _lint(tmp_path, """
        def _kernel(x_ref, o_ref):
            acc = jnp.zeros((8, 8))
            o_ref[...] = acc
    """)
    assert rules_of(diags) == {"lint.accum_dtype"}


def test_lint_accum_dtype_positional_and_like_ok(tmp_path):
    diags = _lint(tmp_path, """
        def _kernel(x_ref, o_ref):
            a = jnp.zeros((8,), jnp.float32)
            b = jnp.full((8,), -1e30, jnp.float32)
            c = jnp.zeros_like(o_ref)
            o_ref[...] = a + b + c
    """)
    assert not diags


def test_lint_dma_pairing(tmp_path):
    diags = _lint(tmp_path, """
        def _kernel(x_ref, o_ref, xblk_ref, sem):
            dma = pltpu.make_async_copy(x_ref, xblk_ref, sem)
            dma.start()
            o_ref[...] = xblk_ref[...]
    """)
    assert rules_of(diags) == {"lint.dma_pairing"}


def test_lint_dma_paired_ok(tmp_path):
    diags = _lint(tmp_path, """
        def _kernel(x_ref, o_ref, xblk_ref, sem):
            dma = pltpu.make_async_copy(x_ref, xblk_ref, sem)
            dma.start()
            dma.wait()
            o_ref[...] = xblk_ref[...]
    """)
    assert not diags


def test_lint_skips_non_kernel_functions(tmp_path):
    diags = _lint(tmp_path, """
        def wrapper(x, w):
            if x.sum() > 0:
                return jnp.zeros((8,))
            return x
    """)
    assert not diags


def test_repo_kernel_sources_pass_lints():
    """The shipped Pallas kernels satisfy their own hygiene rules."""
    paths = default_kernel_paths()
    assert paths
    diags = ast_lints.check_paths(paths)
    assert not diags, [d.format() for d in diags]


# ---------------------------------------------------------------------------
# full sweep + CLI
# ---------------------------------------------------------------------------

def test_run_check_all_nets_and_shipped_plans_zero_errors():
    """The acceptance gate: every net, its shipped default plan, and the
    kernel sources verify clean."""
    report = run_check()
    assert report.ok, [d.format() for d in report.errors]
    assert not report.warnings, [d.format() for d in report.warnings]
    assert any(c.startswith("net:") for c in report.checked)
    assert any(c.startswith("plan:") for c in report.checked)
    assert any(c.startswith("lint:") for c in report.checked)


def test_run_check_flags_bad_cache():
    report = run_check(
        nets=["alexnet"],
        plan_caches=[os.path.join(FIXTURES, "stale_v4_bsr.json")],
    )
    assert not report.ok
    assert "plan.stale_bsr_no_block" in rules_of(report.errors)


def test_cli_json_and_exit_codes(tmp_path, capsys):
    rc = cli_main(["check", "--net", "alexnet", "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0 and doc["ok"] is True
    assert doc["counts"]["error"] == 0
    rc = cli_main([
        "check", "--net", "alexnet", "--no-lints",
        "--plan-cache", os.path.join(FIXTURES, "nondividing_tm.json"),
    ])
    capsys.readouterr()
    assert rc == 1


def test_cli_rules_catalogue(capsys):
    assert cli_main(["rules"]) == 0
    out = capsys.readouterr().out
    for rule in ALL_RULES:
        assert rule in out


# ---------------------------------------------------------------------------
# engine strict mode
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def alexnet_bound():
    program = lower(cnn.NETWORKS["alexnet"](), (3, 224, 224))
    params = init_conv_params(program, np.random.default_rng(0))
    return program, params


def test_strict_bind_clean(alexnet_bound):
    program, params = alexnet_bound
    CnnEngine(program, params, strict=True)  # does not raise


def test_strict_bind_rejects_poisoned_plan(alexnet_bound):
    program, params = alexnet_bound
    name = next(op.name for op in program.conv_ops if op.sparsity > 0)
    plan = {name: PlanEntry(method="pallas", tm=7, pad_to=8, te=8)}
    with pytest.raises(PreflightError) as exc:
        CnnEngine(program, params, plan, strict=True)
    assert {d.rule for d in exc.value.diagnostics} == {
        "sched.nondividing_tm"}
    # Non-strict bind keeps the historical permissive behaviour.
    CnnEngine(program, params, plan)


def test_strict_bind_rejects_stale_bsr_plan(alexnet_bound):
    program, params = alexnet_bound
    name = next(op.name for op in program.conv_ops if op.sparsity > 0)
    plan = {name: PlanEntry(method="bsr")}
    with pytest.raises(PreflightError) as exc:
        CnnEngine(program, params, plan, strict=True)
    assert {d.rule for d in exc.value.diagnostics} == {
        "plan.stale_bsr_no_block"}

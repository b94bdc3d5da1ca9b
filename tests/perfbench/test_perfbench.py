"""CPU tests of the benchmark's own arithmetic and of its correctness check.

The benchmark (``perfbench/``) measures on a TPU; here it runs on the CPU
at tiny sizes, with the look for a chip skipped, to show that its counts,
its trace reduction and its arrival schedule are what they claim, and that
a run whose timed path is broken reads ``correct: false``.
"""
import copy
import json
import math
import os
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from perfbench import (arrivals, counts, harness, peaks, references,  # noqa: E402
                       system, trace)

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
FIXTURE = os.path.join(ROOT, "perfbench", "fixtures",
                       "resnet50-offline-b32.xspace.pbtxt")


def _config(name):
    return harness.load_json(os.path.join(ROOT, "perfbench", "configs",
                                          name + ".json"))


def _table(config):
    return references.load(config).conv_table(config)


def _conv(config, name):
    return next(cv for cv in _table(config) if cv["name"] == name)


# -- counts ----------------------------------------------------------------

@pytest.mark.parametrize("layer, nnz, flops, nbytes", [
    # res3a/3x3: 128 -> 128 channels, 3x3, 28x28 in and out, no shortcut.
    # flops = 2 * nnz * 28 * 28 * 32; bytes = 4 * (32 * (128*784 +
    # 128*784) + nnz).
    ("res3a/3x3", 44237, 2 * 44237 * 784 * 32,
     4 * (32 * (100352 + 100352) + 44237)),
    # res4b/1x1b: 256 -> 1024 channels, 1x1 at 14x14, fused shortcut: the
    # output map is written once and the shortcut read once.
    ("res4b/1x1b", 78643, 2 * 78643 * 196 * 32,
     4 * (32 * (256 * 196 + 2 * 1024 * 196) + 78643)),
])
def test_conv_counts_by_hand(layer, nnz, flops, nbytes):
    cv = _conv(_config("resnet50-224"), layer)
    got = counts.conv_counts(cv, nnz, batch=32)
    assert got == {"flops": float(flops), "bytes": float(nbytes)}


def test_forward_flops_match_the_network_tables():
    """ResNet-50 at 224 px: 7.71 GFLOP a dense image, about 3.85 once the
    res3-res5 convs keep 30% of their weights (FC included)."""
    cfg = _config("resnet50-224")
    table = _table(cfg)
    dense = {cv["name"]: cv["out"] * cv["c"] * cv["k"] ** 2 for cv in table}
    pruned = {k: v - round(_conv(cfg, k)["sparsity"] * v)
              for k, v in dense.items()}
    assert counts.forward_flops(cfg, dense, 1) == pytest.approx(7.71e9,
                                                                rel=2e-3)
    assert counts.forward_flops(cfg, pruned, 1) == pytest.approx(3.85e9,
                                                                 rel=5e-3)
    # conv1 alone: 64 x 3 x 7 x 7 weights at 112 x 112 outputs.
    assert counts.conv_counts(table[0], 9408, 1)["flops"] == 2 * 9408 * 12544


def test_mfu_cannot_pass_100_percent_under_the_dense_method():
    """The fastest a dense forward can run is its dense operations at the
    peak; mfu counts only the required (nonzero) operations, so at that
    speed it reads at most 100%."""
    read = harness.load_reader("mfu.offline")
    for name in ("resnet50-224", "googlenet-224"):
        cfg = _config(name)
        table = _table(cfg)
        dense = {cv["name"]: cv["out"] * cv["c"] * cv["k"] ** 2
                 for cv in table}
        pruned = {k: v - round(_conv(cfg, k)["sparsity"] * v)
                  for k, v in dense.items()}
        kind = "TPU v5 lite"
        fastest = (counts.forward_flops(cfg, dense, 32)
                   / peaks.peak(kind)["flops_per_s"])
        run = harness.Run(cell={}, config=cfg, traffic={}, params={}, seed=0,
                          seconds=1, traced=True, t_start=0.0,
                          device_kind=kind)
        run.window_s = 10 * fastest
        run.data.update(forwards=10, flops_per_forward=counts.forward_flops(
            cfg, pruned, 32))
        assert 0 < read(run) <= 100.0
        run.data["flops_per_forward"] = counts.forward_flops(cfg, dense, 32)
        assert read(run) == pytest.approx(100.0)


def test_kernel_roofline_takes_each_kernels_layers_and_events():
    """Each kernel's share counts the layers the report ran on it and the
    trace events named after it, and reads 100% when the kernel takes
    exactly its layers' least time."""
    import types

    cfg = _config("resnet50-224")
    table = _table(cfg)
    nnz = {cv["name"]: cv["out"] * cv["c"] * cv["k"] ** 2 for cv in table}
    method = {"res3a/3x3": "pallas", "res3b/3x3": "pallas",
              "res4b/1x1b": "bsr"}
    report = types.SimpleNamespace(ops=[
        types.SimpleNamespace(name=cv["name"],
                              method_executed=method.get(cv["name"], "dense"))
        for cv in table])
    kind = "TPU v5 lite"
    pk = peaks.peak(kind)
    least = {k: counts.least_seconds(
        [counts.conv_counts(cv, nnz[cv["name"]], 32) for cv in table
         if method.get(cv["name"]) == m], pk["flops_per_s"],
        pk["hbm_bytes_per_s"]) for k, m in (("sparse_conv", "pallas"),
                                            ("bsr_conv", "bsr"))}
    E = trace.Event
    # Two forwards; the ELL kernel takes twice its least time, the BCSR
    # kernel exactly its least time; XLA's ops do not count.
    ell_ns = 2 * least["sparse_conv"] * 1e9
    bsr_ns = least["bsr_conv"] * 1e9
    events, t = [], 0.0
    for _ in range(2):
        for name, dur in (("sparse_conv_pallas.3", ell_ns / 2),
                          ("sparse_conv_pallas.4", ell_ns / 2),
                          ("bsr_conv_pallas.1", bsr_ns),
                          ("convolution.7", 5e6)):
            events.append(E(name, t, t + dur))
            t += dur
    run = harness.Run(cell={}, config=cfg, traffic={}, params={}, seed=0,
                      seconds=1, traced=True, t_start=0.0, device_kind=kind)
    run.trace = trace.Trace(window=(0.0, t), devices=[events], host=[])
    run.data.update(forwards=2, batch=32, nnz=nnz, report=report)
    ell = harness.load_reader("sparse_conv_roofline.offline")(run)
    bsr = harness.load_reader("bsr_conv_roofline.offline")(run)
    assert ell == pytest.approx(50.0) and bsr == pytest.approx(100.0)
    # A kernel that no layer ran on, or that left no event, reads nothing.
    run.trace = trace.Trace(window=(0.0, t), devices=[[
        e for e in events if not e.name.startswith("bsr")]], host=[])
    assert harness.load_reader("bsr_conv_roofline.offline")(run) is None


def test_unknown_device_kind_is_an_error():
    assert peaks.peak("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("cpu")


# -- arrivals --------------------------------------------------------------

def test_arrival_schedule_rate_and_determinism():
    a = arrivals.poisson_schedule(12.0, 30.0, seed=2**33 + 5)
    b = arrivals.poisson_schedule(12.0, 30.0, seed=2**33 + 5)
    c = arrivals.poisson_schedule(12.0, 30.0, seed=7)
    assert len(a) == 360 and np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert np.all(np.diff(a) > 0) and a[0] > 0
    assert a[-1] == pytest.approx(30.0)
    # Every seed offers the same gaps, in another order.
    assert np.allclose(np.sort(np.diff(a, prepend=0.0)),
                       np.sort(np.diff(c, prepend=0.0)))
    gaps = np.diff(a, prepend=0.0)
    # Exponential: the coefficient of variation of the gaps is about 1.
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.1)


# -- trace reduction -------------------------------------------------------

def _fixture_trace():
    from jax.profiler import ProfileData
    with open(FIXTURE) as f:
        xspace = ProfileData.text_proto_to_serialized_xspace(f.read())
    return trace.from_profile(ProfileData.from_serialized_xspace(xspace))


def test_trace_reduction_on_a_recorded_chip_trace():
    """A slice of a traced ``resnet50-offline-b32`` run on a v5e: the
    reduction's numbers against the same numbers worked out directly from
    the events."""
    t = _fixture_trace()
    assert len(t.devices) == 1 and t.devices[0]
    lo, hi = t.window
    ops = sorted((e.start_ns, e.end_ns, e.name) for e in t.devices[0])
    # Busy time by a plain walk over the sorted intervals.
    busy, end = 0.0, lo
    for a, b, _ in ops:
        a, b = max(a, lo), min(b, hi)
        if b > end:
            busy += b - max(a, end)
            end = b
    assert trace.busy_s(t) == pytest.approx(busy / 1e9)
    assert trace.idle_share(t) == pytest.approx(1 - busy / (hi - lo))
    kernels = trace.kernel_s(t)
    assert set(kernels) <= {"sparse_conv", "bsr_conv"} and kernels
    for k, v in kernels.items():
        want = sum(min(b, hi) - max(a, lo) for a, b, n in ops
                   if n.startswith(k + "_pallas") and b > lo and a < hi)
        assert v == pytest.approx(want / 1e9)
    top = trace.top_ops(t)
    assert len(top) <= 10 and top == sorted(top, key=lambda kv: -kv[1])
    gaps = trace.idle_gaps(t)
    assert sum(v for _, v in gaps) == pytest.approx(
        (hi - lo - busy) / 1e9, rel=1e-6, abs=1e-9)
    assert all(k.startswith("perfbench.") or k == "host.other"
               for k, _ in gaps)


@pytest.mark.parametrize("raw, name", [
    ("%sparse_conv_pallas.34 = f32[32,2048,7,7]{3,2,1,0:T(8,128)} "
     "custom-call(s32[2048]{0:T(1024)S(1)} %copy-done.102)",
     "sparse_conv_pallas.34 f32[32,2048,7,7]"),
    ("%copy-start.7 = (bf16[64,3,7,7]{0,1,3,2:T(4,128)(2,1)S(1)}, u32[])",
     "copy-start.7"),
    ("jit__unknown(11045548304712153092)",
     "jit__unknown(11045548304712153092)"),
])
def test_op_names_from_hlo_text(raw, name):
    assert trace.op_name(raw) == name
    if name.startswith("sparse_conv_pallas"):
        assert trace.KERNEL_EVENT.match(name)["kernel"] == "sparse_conv"


def test_idle_gaps_go_to_the_shortest_covering_annotation():
    E = trace.Event
    t = trace.Trace(window=(0, 100),
                    devices=[[E("a", 10, 20), E("b", 15, 30),
                              E("sparse_conv_pallas.3", 50, 60)]],
                    host=[E("perfbench.fetch", 0, 40),
                          E("perfbench.dispatch", 35, 45)])
    assert trace.busy_s(t) == pytest.approx(30e-9)
    assert trace.kernel_s(t) == {"sparse_conv": pytest.approx(10e-9)}
    got = dict(trace.idle_gaps(t))
    assert got == {"host.other": pytest.approx(45e-9),
                   "perfbench.fetch": pytest.approx(15e-9),
                   "perfbench.dispatch": pytest.approx(10e-9)}


# -- the configurations and the program ------------------------------------

@pytest.mark.parametrize("name", ["resnet50-224", "googlenet-224",
                                  "alexnet-227"])
def test_configuration_is_the_network_the_program_runs(name):
    cfg = _config(name)
    _, program = system.network(cfg)
    ref = references.load(cfg)
    pruned = [cv for cv in ref.conv_table(cfg) if cv["sparsity"] > 0]
    assert len(pruned) == {"resnet50-224": 39, "googlenet-224": 49,
                           "alexnet-227": 4}[name]
    assert len(program.conv_ops) == len(ref.conv_table(cfg))
    fcs = [(fc["in_f"], fc["out_f"], fc["relu"]) for fc in ref.fc_layers(cfg)]
    assert fcs == {"resnet50-224": [(2048, 1000, False)],
                   "googlenet-224": [(1024, 1000, False)],
                   "alexnet-227": [(9216, 4096, True), (4096, 4096, True),
                                   (4096, 1000, False)]}[name]
    grouped = [(cv["name"], cv["groups"]) for cv in ref.conv_table(cfg)
               if cv["groups"] != 1]
    assert grouped == {"resnet50-224": [], "googlenet-224": [],
                       "alexnet-227": [("conv2", 2), ("conv4", 2),
                                       ("conv5", 2)]}[name]


def test_a_grouped_conv_is_a_block_diagonal_conv():
    """The program has no grouped conv: it is given Caffe's group-2 convs
    as one conv over all input channels with block-diagonal weights, which
    must compute what the grouped conv computes."""
    import jax
    from jax import lax

    kw, kx = jax.random.split(jax.random.PRNGKey(3))
    w = jax.random.normal(kw, (6, 2, 3, 3))          # 6 out, 2 groups of 2
    x = jax.random.normal(kx, (2, 4, 5, 5))
    full = system.block_diagonal(w, 2)
    assert full.shape == (6, 4, 3, 3)
    assert not full[:3, 2:].any() and not full[3:, :2].any()

    def conv(w, g):
        return lax.conv_general_dilated(
            x, w, (1, 1), ((1, 1), (1, 1)),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=g, precision=lax.Precision.HIGHEST)
    assert np.allclose(conv(full, 1), conv(w, 2), atol=1e-5)
    assert system.block_diagonal(w, 1) is w


def test_a_configuration_unlike_the_program_is_refused():
    """The program's AlexNet has a ReLU after fc6; a file that states none
    is not the network the program runs."""
    cfg = _config("alexnet-227")
    cfg["layers"] = [dict(layer, relu=False) if layer.get("fc") == "fc6"
                     else layer for layer in cfg["layers"]]
    with pytest.raises(SystemExit, match="FC layers"):
        system.network(cfg)


def test_no_result_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "resnet50-offline-b32", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode == 3 and p.stdout == ""


# -- a run with its timed path broken reads correct: false ------------------

def _dense_plans(monkeypatch):
    """Every conv planned dense, so a CPU run interprets no kernel."""
    from repro.tuning import planner
    from repro.tuning.cache import PlanEntry

    def plan_program(program, **_kw):
        return {op.name: PlanEntry(method="dense", source="roofline")
                for op in program.conv_ops}
    monkeypatch.setattr(planner, "plan_program", plan_program)


def _swap_rows(y):
    return y[::-1]


def _half_batch(y):
    # The first half, so that a batch the server fills only in part loses
    # a real request too.
    return y.at[:y.shape[0] // 2].set(0.0)


def _one_logit(y):
    row = y[0]
    return y.at[0, 0].add(0.05 * abs(row).max())


FAULTS = {"none": None, "answers_swapped": _swap_rows,
          "half_batch_left_out": _half_batch, "one_logit_moved": _one_logit}


def _break_timed_path(monkeypatch, fault):
    """Every forward of ``CnnEngine`` returns its logits with ``fault``."""
    system.import_program()
    from repro.engine import CnnEngine

    if FAULTS[fault] is not None:
        call = CnnEngine.__call__

        def broken(self, x, *a, **k):
            return FAULTS[fault](call(self, x, *a, **k))
        monkeypatch.setattr(CnnEngine, "__call__", broken)


# The smallest input each configuration allows: AlexNet's three pools
# collapse a map below 67 px.
SMALLEST_IMAGE = {"alexnet-227": 67}


def _small_run(cell, seconds):
    files = copy.deepcopy(harness.cell_files(cell, BENCH))
    files["config"]["image"] = SMALLEST_IMAGE.get(files["config"]["name"], 32)
    if files["traffic"]["kind"] == "closed_loop":
        files["traffic"].update(batch=2, pool_batches=2)
    else:
        files["traffic"].update(batch=2, pool_images=4, drain_s=30)
        files["params"]["rate_per_s"] = 6.0
    return harness.Run(seed=2**32 + 17, seconds=seconds, traced=False,
                       t_start=time.perf_counter(), device_kind="cpu",
                       **files)


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("cell, kind", [
    ("resnet50-offline-b32", "closed_loop"),
    ("resnet50-server-poisson", "open_loop"),
    ("alexnet-offline-b32", "closed_loop")])
def test_broken_timed_path_is_not_correct(monkeypatch, cell, kind, fault):
    import importlib

    _dense_plans(monkeypatch)
    _break_timed_path(monkeypatch, fault)
    run = _small_run(cell, seconds=0.5)
    importlib.import_module("perfbench.kinds." + kind).run(run)
    e2e, per = harness.cell_metrics(cell, BENCH)
    out = json.loads(json.dumps(harness.result(run, e2e, per)))
    assert out["correct"] is (fault == "none"), out["checks"]
    assert out["attempted"] > 0 and run.compiles_in_window == 0
    names = {m["name"] for m in e2e}
    assert set(out["metrics"]) == names and "setup_s" in names
    assert all(math.isfinite(out["metrics"][k]["value"]) for k in names)


# -- an architecture is its own files --------------------------------------

TOY = os.path.join(ROOT, "tests", "perfbench", "fixtures", "toy-avgpool.json")


@pytest.mark.parametrize("fault", list(FAULTS))
def test_an_architecture_is_its_own_files(monkeypatch, fault):
    """A configuration whose reference module lives only with the tests, in
    a layer vocabulary the shared CNN reference does not parse (stages of
    conv and average pool), runs through the offline kind, the counts and
    the comparison as they are.  The program's network for it stands in
    for the one a change to the program would add."""
    from perfbench.kinds import closed_loop
    from repro.models import cnn
    from repro.models.cnn import FC, Conv, Pool, Relu

    monkeypatch.setitem(cnn.NETWORKS, "toy_avgpool", lambda: [
        Conv("s1", 8, 3, 1, 1, sparsity=0.5), Relu(), Pool("avg", 2, 2),
        Conv("s2", 16, 3, 1, 1, sparsity=0.6), Relu(), Pool("avg", 2, 2),
        FC("fc", 10)])
    _dense_plans(monkeypatch)
    _break_timed_path(monkeypatch, fault)
    traffic = harness.load_json(os.path.join(ROOT, "perfbench", "traffic",
                                             "offline-b32.json"))
    run = harness.Run(
        cell={"name": "toy-offline-b2", "chips": 1},
        config=harness.load_json(TOY),
        traffic=dict(traffic, batch=2, pool_batches=2),
        params={"limits": {"max_err": 2e-5}}, seed=2**32 + 17, seconds=0.5,
        traced=False, t_start=time.perf_counter(), device_kind="cpu")
    closed_loop.run(run)
    e2e = [m for m in BENCH["end_to_end"]
           if m["name"] in ("images_per_s", "setup_s")]
    out = harness.result(run, e2e, [])
    assert out["correct"] is (fault == "none"), out["checks"]
    nnz = run.data["nnz"]
    assert 0 < nnz["s1"] < 8 * 3 * 9 and 0 < nnz["s2"] < 16 * 8 * 9
    # s1 at 16x16, s2 at 8x8, the FC 256 -> 10; a batch of 2.
    assert run.data["flops_per_forward"] == 2 * 2 * (
        nnz["s1"] * 256 + nnz["s2"] * 64 + 256 * 10)


# -- the control: the reference one precision down, in the program's place --

def _reference_in_the_programs_place(monkeypatch, config, precision):
    """``CnnEngine.__call__`` answers with the plain reference computed at
    ``precision`` from the engine's own bound weights."""
    import jax

    system.import_program()
    from repro.engine import CnnEngine

    forward = references.load(config).forward
    fwd = jax.jit(lambda w, f, x: forward(config, w, f, x, precision))

    def control(self, x, *_a, **_k):
        w = {name: (e["w"], e["b"]) for name, e in self.params.items()
             if isinstance(e, dict)}
        return fwd(w, list(self.fc_weights.values()), x)
    monkeypatch.setattr(CnnEngine, "__call__", control)


@pytest.mark.parametrize("precision", ["highest", "bfloat16"])
@pytest.mark.parametrize("cell, kind", [
    ("resnet50-offline-b32", "closed_loop"),
    ("resnet50-server-poisson", "open_loop")])
def test_control_in_the_programs_place(monkeypatch, cell, kind, precision):
    """The reference put in the program's place passes at its own
    precision and fails the cell's limit in bfloat16.  (The chip's control
    for float32 at ``highest`` is the reference at ``high``, three bfloat16
    passes; XLA's CPU backend does not round so, which is why its readings
    come from ``perfbench/calibrate.py`` on the chip, PERF.md.)"""
    import importlib

    _dense_plans(monkeypatch)
    run = _small_run(cell, seconds=0.5)
    _reference_in_the_programs_place(monkeypatch, run.config, precision)
    importlib.import_module("perfbench.kinds." + kind).run(run)
    e2e, per = harness.cell_metrics(cell, BENCH)
    out = harness.result(run, e2e, per)
    assert out["correct"] is (precision == "highest"), out["checks"]


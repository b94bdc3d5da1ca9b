"""Data pipeline determinism, checkpoint commit/restore/GC, fault-tolerance
runtime (straggler monitor, failure retry with restore)."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager, latest_step, restore_state, save_state
from repro.data import DataConfig, SyntheticLMDataset, make_loader
from repro.runtime import FailureDetector, StepRunner, StragglerMonitor, plan_remesh


# --------------------------- data pipeline ---------------------------------

def test_data_deterministic_by_step():
    cfg = DataConfig(seq_len=16, global_batch=4, vocab=100, seed=7)
    ds = SyntheticLMDataset(cfg)
    a, b = ds.batch_for(5), ds.batch_for(5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = ds.batch_for(6)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_data_host_sharding_disjoint():
    h0 = DataConfig(seq_len=8, global_batch=8, vocab=100, seed=1, n_hosts=2,
                    host_id=0)
    h1 = DataConfig(seq_len=8, global_batch=8, vocab=100, seed=1, n_hosts=2,
                    host_id=1)
    b0 = SyntheticLMDataset(h0).batch_for(3)
    b1 = SyntheticLMDataset(h1).batch_for(3)
    assert b0["tokens"].shape == (4, 8)
    assert not np.array_equal(b0["tokens"], b1["tokens"])


def test_loader_resume_mid_stream():
    cfg = DataConfig(seq_len=8, global_batch=2, vocab=50, seed=3)
    l1 = make_loader(cfg, start_step=0)
    seq1 = [next(l1)["tokens"] for _ in range(4)]
    l1.close()
    l2 = make_loader(cfg, start_step=2)  # restart-from-checkpoint semantics
    seq2 = [next(l2)["tokens"] for _ in range(2)]
    l2.close()
    np.testing.assert_array_equal(seq1[2], seq2[0])
    np.testing.assert_array_equal(seq1[3], seq2[1])


def test_labels_are_shifted_tokens():
    cfg = DataConfig(seq_len=8, global_batch=2, vocab=50)
    b = SyntheticLMDataset(cfg).batch_for(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


# --------------------------- checkpointing ---------------------------------

def _state():
    return {"params": {"w": jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3),
                       "b": jnp.ones((3,), jnp.float32)},
            "opt": {"step": jnp.int32(7)}}


def test_checkpoint_roundtrip(tmp_path):
    st = _state()
    save_state(st, str(tmp_path), 7)
    assert latest_step(str(tmp_path)) == 7
    like = jax.eval_shape(lambda: _state())
    back = restore_state(like, str(tmp_path), 7)
    np.testing.assert_array_equal(np.asarray(back["params"]["w"], np.float32),
                                  np.asarray(st["params"]["w"], np.float32))
    assert back["params"]["w"].dtype == jnp.bfloat16
    assert int(back["opt"]["step"]) == 7


def test_uncommitted_checkpoint_ignored(tmp_path):
    st = _state()
    save_state(st, str(tmp_path), 5)
    d = pathlib.Path(tmp_path) / "step_000009"
    d.mkdir()  # crashed mid-write: no COMMIT
    assert latest_step(str(tmp_path)) == 5


def test_manager_async_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    st = _state()
    for s in (10, 20, 30):
        mgr.save_async(st, s)
    mgr.wait()
    mgr._gc()
    steps = sorted(int(p.name.split("_")[1]) for p in tmp_path.iterdir())
    assert steps == [20, 30]
    back, step = mgr.restore_latest(jax.eval_shape(lambda: _state()))
    assert step == 30 and back is not None


# --------------------------- fault tolerance --------------------------------

def test_straggler_monitor_flags_outlier():
    mon = StragglerMonitor(warmup_steps=3)
    for _ in range(20):
        assert not mon.observe(1.0)
    assert mon.observe(5.0)


def test_straggler_monitor_host_lag():
    mon = StragglerMonitor()
    lag = mon.observe_hosts({0: 1.0, 1: 1.1, 2: 9.0, 3: 0.9})
    assert lag == [2]


def test_failure_detector_classification():
    det = FailureDetector(max_strikes=2)
    assert det.classify(RuntimeError("collective timeout DEADLINE_EXCEEDED")) \
        == "retryable"
    assert det.classify(ValueError("shape mismatch")) == "fatal"
    assert det.record(RuntimeError("UNAVAILABLE")) == "retryable"
    assert det.record(RuntimeError("UNAVAILABLE")) == "escalate"


def test_step_runner_restart_after_failure(tmp_path):
    """Induce a transient failure mid-run; the runner must restore the last
    committed checkpoint and converge to the same final state as an
    uninterrupted run (determinism across restarts)."""
    from repro.data import DataConfig, make_loader
    calls = {"n": 0, "failed": False}

    def flaky_step(state, batch):
        calls["n"] += 1
        if calls["n"] == 6 and not calls["failed"]:
            calls["failed"] = True
            raise RuntimeError("transient collective UNAVAILABLE")
        s = state["s"] + int(batch["tokens"].sum()) % 97
        return {"s": s}, {"loss": float(s)}

    dcfg = DataConfig(seq_len=4, global_batch=2, vocab=13, seed=0)
    mgr = CheckpointManager(str(tmp_path), keep=3)
    runner = StepRunner(flaky_step, mgr, lambda s: make_loader(dcfg, s),
                        ckpt_every=2)
    final, end = runner.run({"s": 0}, 0, 8)
    assert end == 8 and calls["failed"]

    # uninterrupted reference
    def clean_step(state, batch):
        return {"s": state["s"] + int(batch["tokens"].sum()) % 97}, {"loss": 0.0}
    mgr2 = CheckpointManager(str(tmp_path / "ref"), keep=3)
    runner2 = StepRunner(clean_step, mgr2, lambda s: make_loader(dcfg, s),
                         ckpt_every=100)
    ref, _ = runner2.run({"s": 0}, 0, 8)
    assert final["s"] == ref["s"]


def test_plan_remesh():
    assert plan_remesh(256, model=16) == ((16, 16), ("data", "model"))
    assert plan_remesh(200, model=16) == ((8, 16), ("data", "model"))
    assert plan_remesh(512, model=16, pod_axis=True) == (
        (2, 16, 16), ("pod", "data", "model"))
    assert plan_remesh(15, model=16) is None


# --------------------------- compile cache ---------------------------------

def test_compile_cache_dir_env_or_checkout(monkeypatch):
    """The env var's directory is left to JAX and no other is set; without
    it the cache goes to ``.jax_cache`` at the checkout root.  The config
    update is recorded, not applied: tests never turn the cache on."""
    from repro.runtime import compile_cache
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, val: updates.append((name, val)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    root = pathlib.Path(__file__).resolve().parents[1]
    assert compile_cache.enable_compile_cache() == str(root / ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", str(root / ".jax_cache"))]

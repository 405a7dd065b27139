"""Fault-tolerant loop: checkpoint/restart exactness, crash recovery,
straggler detection, CEU accounting, async checkpoints."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.api import OptimizerConfig, make_optimizer
from repro.data.synthetic import SyntheticLM, synthetic_batch
from repro.models.model import build_model
from repro.train import checkpoint as ckpt
from repro.train.fault_tolerance import Heartbeat, StragglerDetector, run_with_restart
from repro.train.loop import TrainLoop, TrainLoopConfig
from repro.train.train_state import TrainState


def _setup(tmp, stacked_state=False, **loop_kw):
    cfg = get_smoke("tinyllama-1.1b")
    model = build_model(cfg)
    tx = make_optimizer(OptimizerConfig(name="coap-adamw", learning_rate=1e-3,
                                        rank=8, t_update=4, lam=2, min_dim=16,
                                        stacked_state=stacked_state))
    data = SyntheticLM(vocab=cfg.vocab_size, order=1, noise=0.2)
    batch_fn = lambda step, host: data.batch(step, batch=4, seq=16, host=host)
    loop_cfg = TrainLoopConfig(ckpt_dir=os.path.join(tmp, "ckpt"),
                               metrics_path=os.path.join(tmp, "metrics.jsonl"),
                               **loop_kw)
    return TrainLoop(model, tx, batch_fn, loop_cfg), model, tx


@pytest.mark.parametrize("stacked", [False, True])
def test_checkpoint_restart_is_exact(tmp_path, stacked):
    """Train 8 steps straight vs 4 + restart + 4: final params identical —
    for per-leaf AND pre-stacked optimizer state (the restart restores a
    stacked TrainState through the codec-aware manifest)."""
    loopA, _, _ = _setup(str(tmp_path / "a"), total_steps=8, ckpt_every=100,
                         log_every=100, stacked_state=stacked)
    stateA = loopA.run()

    loopB1, _, _ = _setup(str(tmp_path / "b"), total_steps=4, ckpt_every=4,
                          log_every=100, stacked_state=stacked)
    loopB1.run()
    loopB2, _, _ = _setup(str(tmp_path / "b"), total_steps=8, ckpt_every=100,
                          log_every=100, stacked_state=stacked)
    stateB = loopB2.run()

    assert int(stateA.step) == int(stateB.step) == 8
    for a, b in zip(jax.tree_util.tree_leaves(stateA.params),
                    jax.tree_util.tree_leaves(stateB.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_crash_recovery_with_run_with_restart(tmp_path):
    """Induced crash at step 5 → auto-restart resumes from the checkpoint."""
    calls = []

    def attempt(i):
        crash = 5 if i == 0 else None
        loop, _, _ = _setup(str(tmp_path), total_steps=8, ckpt_every=2,
                            log_every=100, crash_at_step=crash)
        calls.append(i)
        return loop.run()

    state = run_with_restart(attempt, max_restarts=2)
    assert int(state.step) == 8
    assert calls == [0, 1]


def test_straggler_detector_flags_outliers():
    det = StragglerDetector(z_threshold=3.0, warmup=5)
    for _ in range(30):
        assert not det.observe(0.10 + np.random.default_rng(0).normal(0, 0.002))
    assert det.observe(0.50)  # 5x step time -> straggler
    assert det.flagged == 1
    assert not det.observe(0.10)


def test_heartbeat_roundtrip(tmp_path):
    hb = Heartbeat(str(tmp_path / "hb.json"), timeout=10.0)
    assert not hb.is_alive()
    hb.beat(3)
    assert hb.is_alive()


def test_checkpoint_atomicity_and_gc(tmp_path):
    state = {"w": jnp.arange(8, dtype=jnp.bfloat16), "c": jnp.asarray(3)}
    d = str(tmp_path)
    for s in [1, 2, 3, 4, 5]:
        ckpt.save(d, s, state, keep=2)
    assert ckpt.latest_step(d) == 5
    kept = sorted(x for x in os.listdir(d) if x.startswith("ckpt_"))
    assert len(kept) == 2
    template = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state
    )
    restored = ckpt.restore(d, template)
    np.testing.assert_array_equal(np.asarray(restored["w"], np.float32),
                                  np.asarray(state["w"], np.float32))
    assert restored["w"].dtype == jnp.bfloat16


def test_data_pipeline_deterministic_and_prefetches():
    from repro.data.pipeline import DataPipeline

    data = SyntheticLM(vocab=64, order=1)
    fn = lambda step, host: data.batch(step, 2, 8, host)
    p1 = DataPipeline(fn, start_step=0, host_index=0, host_count=1)
    got1 = [next(p1) for _ in range(4)]
    p1.close()
    p2 = DataPipeline(fn, start_step=0, host_index=0, host_count=1)
    got2 = [next(p2) for _ in range(4)]
    p2.close()
    for (s1, b1), (s2, b2) in zip(got1, got2):
        assert s1 == s2
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])


def test_synthetic_lm_ce_floor_reachable():
    """A tiny model should drive CE toward the known floor (sanity that the
    convergence benchmarks measure learning, not noise)."""
    data = SyntheticLM(vocab=32, order=1, noise=0.1)
    floor = data.ce_floor()
    assert 0.1 < floor < np.log(32)


def test_step_hlo_names_its_scopes(tmp_path):
    """The compiled step's op_names carry the scopes a device trace is
    attributed by: the model and its backward pass, the optimizer with
    each bucket's gather / refresh / update / scatter, the step metrics."""
    import re

    loop, model, tx = _setup(str(tmp_path), total_steps=1)
    state = jax.eval_shape(loop.init_or_restore)
    batch = jax.eval_shape(lambda: loop.batch_fn(0, 0))
    names = set(re.findall(r'op_name="([^"]*)"', loop.step_hlo_text(state, batch)))
    parts = {p for n in names for p in n.split("/")}
    assert {"jvp(model)", "transpose(jvp(model))", "optimizer", "step_metrics",
            "embed", "stack", "head"} <= parts
    subs = {}
    for n in names:
        m = re.search(r"/optimizer/(project:[0-9x]+:float32)/(\w+)/", n)
        if m:
            subs.setdefault(m.group(1), set()).add(m.group(2))
    assert any(s >= {"gather", "refresh", "update", "scatter"} for s in subs.values()), subs
    assert any(re.search(r"/optimizer/dense/dense:[0-9x]+:float32/update/", n)
               for n in names)


def test_step_hlo_text_reads_the_executable_that_ran(tmp_path):
    """After ``run``, the HLO text for the state it returned and a batch of
    its feed comes from the jitted step's cache: nothing compiles again."""
    loop, _, _ = _setup(str(tmp_path), total_steps=2)
    state = loop.run()
    compiles = []

    def listen(name, secs, **kw):
        if name.endswith("backend_compile_duration"):
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    text = loop.step_hlo_text(state, loop.batch_fn(2, 0))
    assert compiles == []
    assert "optimizer/" in text and "HloModule jit_step" in text


def test_loop_spans_keep_their_fields(tmp_path):
    """``loop/step`` rows keep their attributes and extent (what obs/calib
    reads); dispatch and wait nest in it; the feed, the metrics pull, the
    heartbeat and the log are spans of their own."""
    from repro.obs.trace import configure, read_trace

    path = str(tmp_path / "trace.jsonl")
    configure(path)
    try:
        loop, _, _ = _setup(str(tmp_path), total_steps=3, log_every=1,
                            heartbeat_path=str(tmp_path / "hb.json"))
        loop.run()
    finally:
        configure(None)
    rows = read_trace(path)
    steps = [r for r in rows if r["name"] == "loop/step"]
    assert [r["attrs"] for r in steps] == [{"step": 0, "compile": True},
                                           {"step": 1}, {"step": 2}]
    assert all(r["depth"] == 0 and r["parent"] is None for r in steps)
    for child in ("loop/dispatch", "loop/wait"):
        kids = [r for r in rows if r["name"] == child]
        assert len(kids) == 3
        assert all(r["parent"] == "loop/step" and r["depth"] == 1 for r in kids)
    for step, dispatch, wait in zip(
            steps, *[[r for r in rows if r["name"] == c]
                     for c in ("loop/dispatch", "loop/wait")]):
        assert step["ts"] <= dispatch["ts"] <= wait["ts"]
        assert wait["ts"] + wait["dur"] <= step["ts"] + step["dur"] + 1e-6
    for name in ("loop/batch", "loop/metrics_pull", "loop/heartbeat", "loop/log"):
        assert sum(r["name"] == name for r in rows) == 3, name

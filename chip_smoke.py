"""Smoke run of the training path on a TPU: kernels, two optimizers, a save.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the cross-chip path on four chips

One process drives the normal path (``get_config`` -> ``build_model`` ->
``make_optimizer`` -> ``TrainLoop`` -> ``make_train_step``) at LLaMA-1B's
published widths with random weights from a seed. Only the depth is cut,
to what one 16 GB v5e chip holds (``SMOKE_LAYERS``).

Phases, in order; any failure exits non-zero and prints no result line:

1. device check: the first device is a TPU, and the kernels dispatch to
   compiled Pallas (``REPRO_PALLAS`` unset or ``pallas``);
2. kernel parity: each main-path Pallas kernel against its
   ``kernels/ref.py`` oracle, on the chip; each compiled program must hold
   a ``tpu_custom_call``;
3. training: ``coap-adamw`` then ``8bit-coap-adamw`` at rank 512, through
   Eqn-6 refreshes and an Eqn-7 recalibration, with finite, falling
   losses and one checkpoint save.

``--chips 4`` runs only the cross-chip path instead: the sharded train
step on a (data 2, model 2) mesh against the same step on one chip, and
the compressed cross-pod step on a (pod 2, data 2) mesh against the
uncompressed step on the same mesh, each on its second step from a
shared state, in fp32.

Step times printed here are smoke figures, not a benchmark. ``[cache]``
lines give the persistent compile cache's hits, misses and written
entries per phase. The last line of standard output is one JSON object
naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import logging
import math
import os
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402

ARCH = "llama-1b"
FULL_LAYERS = 24
# The deepest LLaMA-1B whose coap-adamw step (batch 8 x 1024, rank 512,
# step not donated) compiles for one v5e to <= 14 GB of arguments,
# outputs and temporaries (``compiled.memory_analysis()``).
SMOKE_LAYERS = 7
BATCH, SEQ = 8, 1024
RANK = 512
# The cross-chip checks test layouts and collectives, not the rank. At
# rank 512 each SVD call site of the step takes tens of seconds of host
# compile on its own; at 128, a few.
CROSS_CHIP_RANK = 128
T_UPDATE, LAM = 4, 2  # Eqn-6 every 4 steps, Eqn-7 every 8 (per phase group)
STEPS = 10
LR = 1e-3
SEED = 0
OPTIMIZERS = ("coap-adamw", "8bit-coap-adamw")
CKPT_ROOT = os.path.join(ROOT, "artifacts", "chip_smoke")

# Kernel shapes: LLaMA-1B's projected (m, n) at rank 512, its up/gate as
# stored (n, m) for the int8 kernel, which reads them transposed and with a
# partial last row block, and an Eqn-6 shape whose VMEM plan builds the
# fused kernel.
FUSED_SHAPES = ((5461, 2048, 512), (2048, 2048, 512))
Q8_STORED_SHAPES = ((2048, 5461, 512),)
EQN6_SHAPE = (2048, 2048, 128)
# Tolerances of the interpret-mode tests in tests/test_kernels.py.
FUSED_TOL = dict(rtol=3e-5, atol=3e-5)
EQN6_TOL = dict(rtol=1e-4, atol=1e-6)


def log(msg: str) -> None:
    print(msg, flush=True)


class CacheLog(logging.Filter):
    """Persistent-cache hits, misses and refused writes, read from JAX's
    compiler log. Its debug records are counted here and go no further.

    ``install()`` attaches it; ``take()`` returns and clears what was seen
    since the last call."""

    _EVENTS = (("hit", "Persistent compilation cache hit for '"),
               ("miss", "PERSISTENT COMPILATION CACHE MISS for '"),
               ("unwritten", "Not writing persistent cache entry for '"))

    def __init__(self):
        super().__init__()
        self._seen = []

    def install(self) -> "CacheLog":
        logger = logging.getLogger("jax._src.compiler")
        logger.setLevel(logging.DEBUG)
        logger.addFilter(self)
        return self

    def filter(self, record: logging.LogRecord) -> bool:
        msg = record.getMessage()
        for kind, prefix in self._EVENTS:
            if msg.startswith(prefix):
                name, _, rest = msg[len(prefix):].partition("'")
                self._seen.append((kind, name, rest.partition("because ")[2]))
        return record.levelno >= logging.WARNING

    def take(self) -> dict:
        """{"hits": [names], "misses": n, "written": [names],
        "unwritten": {name: reason}} — compiles under JAX's minimum
        compile time for the cache are left out of ``unwritten``."""
        seen, self._seen = self._seen, []
        refused = {name: why for kind, name, why in seen
                   if kind == "unwritten"}
        misses = [name for kind, name, _ in seen if kind == "miss"]
        return {
            "hits": [name for kind, name, _ in seen if kind == "hit"],
            "misses": len(misses),
            "written": [name for name in misses if name not in refused],
            "unwritten": {name: why for name, why in refused.items()
                          if not why.startswith("it took <")},
        }


# ---------------------------------------------------------------------------
# 1. device check
# ---------------------------------------------------------------------------
def check_device(expected_count: int) -> dict:
    """Fail unless JAX sees ``expected_count`` TPU devices and the kernels
    dispatch to compiled Pallas. Never falls back to CPU or interpret."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {dev.platform!r}")
    if len(devices) < expected_count:
        raise SystemExit(
            f"need {expected_count} TPU devices, JAX sees {len(devices)}"
        )
    forced = os.environ.get("REPRO_PALLAS", "")
    if forced not in ("", "pallas"):
        raise SystemExit(f"REPRO_PALLAS={forced!r}: the chip run needs "
                         "compiled Pallas kernels")
    if ops._mode() != "pallas":
        raise SystemExit(f"kernels dispatch to {ops._mode()!r}, not pallas")
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    log(f"[device] {json.dumps(info)}")
    return info


# ---------------------------------------------------------------------------
# 2. kernel parity
# ---------------------------------------------------------------------------
def _rand(shape, seed, scale=1.0):
    return scale * jax.random.normal(jax.random.key(seed), shape, jnp.float32)


def _compiled(fn, *args):
    """Compile ``fn`` once, run it, and return (outputs, compiled text)."""
    compiled = jax.jit(fn).lower(*args).compile()
    return jax.block_until_ready(compiled(*args)), compiled.as_text()


def _max_violation(got, want, rtol, atol):
    """Largest |got - want| - (atol + rtol |want|); <= 0 means allclose."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) - (atol + rtol * np.abs(want))))


def _q8_row(name, got, want, text):
    """A parity row of the int8 kernel: codes within one step, scales and
    ΔW within ``FUSED_TOL``."""
    code_diff = max(
        int(np.max(np.abs(np.asarray(a, np.int32) - np.asarray(b, np.int32))))
        for a, b in ((got[0], want[0]), (got[2], want[2]))
    )
    worst = max(_max_violation(got[i], want[i], **FUSED_TOL)
                for i in (1, 3, 4))
    return dict(name=name, worst=worst, code_diff=code_diff,
                ok=worst <= 0 and code_diff <= 1,
                custom_call="tpu_custom_call" in text)


def kernel_parity(fused_shapes=FUSED_SHAPES, eqn6_shape=EQN6_SHAPE,
                  q8_stored_shapes=Q8_STORED_SHAPES, seed=SEED):
    """Each main-path kernel, through ``kernels/ops``, against its oracle.

    Returns one row per check: ``{name, ok, custom_call, ...}``. The oracle
    runs at ``highest`` matmul precision, so an fp32 product is fp32 on
    both sides. Raises AssertionError on a mismatch.
    """
    rows = []
    count = jnp.asarray(5, jnp.int32)
    for m, n, r in fused_shapes:
        g = _rand((m, n), seed)
        p = _rand((n, r), seed + 1, 1.0 / math.sqrt(r))
        m0 = _rand((m, r), seed + 2, 0.1)
        v0 = jnp.abs(_rand((m, r), seed + 3, 0.01))
        got, text = _compiled(ops.coap_fused_update_bp, g, p, m0, v0, count)
        want = jax.jit(ref.coap_fused_update_bp)(g, p, m0, v0, count)
        worst = max(_max_violation(a, b, **FUSED_TOL)
                    for a, b in zip(got, want))
        rows.append(dict(name=f"fused_bp {m}x{n} r{r}", worst=worst,
                         ok=worst <= 0,
                         custom_call="tpu_custom_call" in text))

        g8 = 0.1 * g
        mq, ms = ref.quantize_rowblock(0.5 * m0)
        vq, vs = ref.quantize_rowblock(v0)
        got, text = _compiled(ops.coap_fused_update_q8,
                              g8, p, mq, ms, vq, vs, count)
        want = jax.jit(ref.coap_fused_update_q8)(
            g8, p, mq, ms, vq, vs, count)
        rows.append(_q8_row(f"fused_q8 {m}x{n} r{r}", got, want, text))

    # The int8 kernel on a gradient stored (n, m): it reads G and writes
    # ΔW that way; the oracle takes G swapped to (m, n).
    for n, m, r in q8_stored_shapes:
        g8 = _rand((n, m), seed, 0.1)
        p = _rand((n, r), seed + 1, 1.0 / math.sqrt(r))
        mq, ms = ref.quantize_rowblock(_rand((m, r), seed + 2, 0.05))
        vq, vs = ref.quantize_rowblock(jnp.abs(_rand((m, r), seed + 3, 0.01)))
        got, text = _compiled(
            lambda *a: ops.coap_fused_update_q8(*a, transposed=True),
            g8, p, mq, ms, vq, vs, count)
        want = jax.jit(ref.coap_fused_update_q8)(
            g8.T, p, mq, ms, vq, vs, count)
        got = got[:4] + (got[4].T,)
        rows.append(_q8_row(f"fused_q8 stored {n}x{m} r{r}", got, want, text))

    m, n, r = eqn6_shape
    g = _rand((m, n), seed + 4, 0.1)
    p = _rand((n, r), seed + 5, 1.0 / math.sqrt(r))
    mp = _rand((m, r), seed + 6, 0.05)
    before = ops.eqn6_fallback_counts().get((m, n, r), 0)
    got, text = _compiled(
        lambda a, b, c: ops.eqn6_sgd_update(a, b, c, lr=0.1, steps=1),
        p, g, mp)
    fell_back = ops.eqn6_fallback_counts().get((m, n, r), 0) > before
    with jax.default_matmul_precision("highest"):
        want = jax.jit(
            lambda a, b, c: ref.eqn6_sgd_update(a, b, c, lr=0.1, steps=1)[0]
        )(p, g, mp)
    worst = _max_violation(got, want, **EQN6_TOL)
    rows.append(dict(name=f"eqn6 {m}x{n} r{r}", worst=worst,
                     ok=worst <= 0 and not fell_back, fell_back=fell_back,
                     custom_call="tpu_custom_call" in text))

    for row in rows:
        log(f"[kernel] {json.dumps(row)}")
    bad = [row["name"] for row in rows if not row["ok"]]
    assert not bad, f"kernel parity failed: {bad}"
    return rows


# ---------------------------------------------------------------------------
# 3. training
# ---------------------------------------------------------------------------
def smoke_config(n_layers: int = SMOKE_LAYERS):
    """LLaMA-1B at published widths, depth cut to ``n_layers``."""
    return dataclasses.replace(get_config(ARCH), n_layers=n_layers)


def refresh_schedule(params, rules, t_update, lam, steps):
    """{step: {"eqn6": [buckets], "recal": [buckets]}} for steps >= 1, from
    the phase allocation and predicates the jitted update uses."""
    from repro.core import stacked_state
    from repro.core.coap_adam import (
        ProjectedAdamConfig,
        _sched_preds,
        bucket_phases,
    )

    pcfg = ProjectedAdamConfig(rules=rules, t_update=t_update, lam=lam)
    layout = stacked_state.layout_for_tree(pcfg.rules.spec_for, params)
    phases = bucket_phases(pcfg, layout)
    out = {}
    for step in range(1, steps):
        ev = {"eqn6": [], "recal": []}
        for bi, phs in sorted(phases.items()):
            for ph in sorted(set(phs)):
                do_ref, do_recal = _sched_preds(step, ph, t_update, lam)
                if do_recal:
                    ev["recal"].append(bi)
                elif do_ref:
                    ev["eqn6"].append(bi)
        out[step] = ev
    return out


def train(cfg, optimizer, run_dir, *, save=False, rank=RANK, min_dim=128,
          steps=STEPS, batch=BATCH, seq=SEQ, t_update=T_UPDATE, lam=LAM,
          lr=LR, seed=SEED):
    """Train ``steps`` steps through TrainLoop and check the losses.

    ``run_dir`` is emptied first and holds the step trace; with ``save``
    it also receives the loop's one final checkpoint. Returns a dict of
    losses, timings, memory and Eqn-6 fallbacks."""
    from repro.core.api import OptimizerConfig, make_optimizer
    from repro.data.synthetic import SyntheticLM
    from repro.models.model import build_model
    from repro.obs import trace
    from repro.train import checkpoint as ckpt
    from repro.train.loop import TrainLoop, TrainLoopConfig

    model = build_model(cfg)
    ocfg = OptimizerConfig(
        name=optimizer, learning_rate=lr, rank=rank, min_dim=min_dim,
        t_update=t_update, lam=lam, weight_decay=0.0, seed=seed,
    )
    tx = make_optimizer(ocfg)
    data = SyntheticLM(vocab=cfg.vocab_size, order=2, noise=0.1, seed=seed)
    sched = refresh_schedule(model.abstract_params(), ocfg.rules(), t_update,
                             lam, steps)
    n_eqn6 = sum(1 for ev in sched.values() if ev["eqn6"])
    n_recal = sum(1 for ev in sched.values() if ev["recal"])
    assert n_eqn6 and n_recal, (
        f"{steps} steps at T_u={t_update}, lam={lam} schedule "
        f"{n_eqn6} Eqn-6 and {n_recal} Eqn-7 steps after the first")

    ckpt_dir = run_dir if save else None
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    trace_path = os.path.join(run_dir, "trace.jsonl")
    ops.reset_eqn6_fallbacks()
    trace.configure(trace_path)
    try:
        loop = TrainLoop(
            model, tx, lambda step, host: data.batch(step, batch, seq, host),
            TrainLoopConfig(total_steps=steps,
                            ckpt_dir=ckpt_dir, ckpt_every=0,
                            log_every=1, health_every=0),
            init_key=jax.random.key(seed),
        )
        state = loop.run()
    finally:
        trace.configure(None)
    losses = [row["loss"] for row in loop.logger.history]
    step_s = [row["dur"] for row in trace.read_trace(trace_path)
              if row.get("name") == "loop/step"]
    out = dict(
        optimizer=optimizer, n_layers=cfg.n_layers, rank=rank,
        batch=batch, seq=seq, steps=int(state.step),
        first_loss=losses[0], last_loss=losses[-1],
        # The first step traces and compiles; the rest are warm.
        first_step_s=step_s[0],
        median_step_s_smoke=statistics.median(step_s[1:]),
        eqn6_steps=n_eqn6, recal_steps=n_recal,
        eqn6_fallbacks={"x".join(map(str, k)): v for k, v in
                        sorted(ops.eqn6_fallback_counts().items())},
    )
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    if ckpt_dir:
        out["checkpoint_step"] = ckpt.latest_step(ckpt_dir)
    log(f"[train] {json.dumps(out)}")
    log(f"[train] {optimizer} losses {losses}")
    assert len(losses) == steps and all(math.isfinite(x) for x in losses), (
        f"{optimizer}: non-finite or missing losses {losses}")
    assert losses[-1] < losses[0], (
        f"{optimizer}: loss did not fall ({losses[0]} -> {losses[-1]})")
    if ckpt_dir:
        assert out["checkpoint_step"] == steps, (
            f"{optimizer}: no checkpoint at step {steps} in {ckpt_dir}")
    del state, loop
    gc.collect()
    return out


# ---------------------------------------------------------------------------
# --chips 4: the cross-chip path and what it is compared with
# ---------------------------------------------------------------------------
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def collective_counts(text: str) -> dict:
    """How often each collective kind is started in compiled HLO text."""
    return {kind: text.count(f" {kind}(") + text.count(f" {kind}-start(")
            for kind in COLLECTIVES}


def _batch(cfg, batch, seq, seed):
    key = jax.random.key(seed)
    return {"tokens": jax.random.randint(key, (batch, seq), 0,
                                         cfg.vocab_size),
            "labels": jax.random.randint(jax.random.fold_in(key, 1),
                                         (batch, seq), 0, cfg.vocab_size)}


def _on_mesh_counts() -> dict:
    """Kernel traces a mesh has sent to the jnp path so far."""
    from repro.obs.registry import get_registry

    prefix = "kernels/on_mesh/"
    return {k[len(prefix):]: v for k, v in
            sorted(get_registry().snapshot()["counters"].items())
            if k.startswith(prefix)}


# Parameter tolerance of the cross-chip checks: tests/test_distributed.py's
# for the compressed step.
STEP_TOL = dict(rtol=2e-3, atol=2e-5)


def _param_diff(want, got, lr):
    """max |got - want| over all parameters in units of ``lr``, and how
    many elements miss STEP_TOL."""
    worst, missed = 0.0, 0
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        a = np.asarray(a, np.float64)
        d = np.abs(np.asarray(b, np.float64) - a)
        worst = max(worst, float(d.max()))
        missed += int(np.sum(d > STEP_TOL["atol"]
                             + STEP_TOL["rtol"] * np.abs(a)))
    return dict(max_over_lr=worst / lr, missed=missed)


def _assert_params_close(want, got):
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(np.asarray(b, np.float32),
                                   np.asarray(a, np.float32), **STEP_TOL)


# Why the cross-chip checks compare the second step: on the first, Adam
# divides each entry of the projected gradient by its own magnitude plus
# eps, so an entry within rounding of zero gets an undetermined update of
# up to +-lr, which the back-projection spreads over a row of W. At
# LLaMA-1B widths thousands of elements then miss STEP_TOL between any two
# summation orders of the same step, on one device too. On the second
# step the second moment holds the first step's gradient as well, and the
# update is determined. Both steps start from the same state.
def _two_batches(cfg, batch, seq, seed):
    """The batches of the two steps. The second has the first's tokens
    and new labels, so every embedding row it touches has Adam moments
    from the first step."""
    first = _batch(cfg, batch, seq, seed + 1)
    return [first, dict(first,
                        labels=_batch(cfg, batch, seq, seed + 2)["labels"])]


def sharded_step_parity(cfg, *, rank=CROSS_CHIP_RANK, batch=BATCH, seq=SEQ,
                        seed=SEED, lr=LR):
    """The COAP train step with parameters laid out by ``param_specs`` on
    a (data 2, model 2) mesh against the same step on one device, in fp32
    at ``highest`` matmul precision, so the two differ only in the order
    of the partitioned program's sums.

    Both take the second step from the one-device state after the first;
    its loss must agree to rtol 2e-4 and its parameters to STEP_TOL. The
    first step from init is reported.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.api import OptimizerConfig, make_optimizer
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import build_model
    from repro.train.step import make_train_step
    from repro.train.train_state import TrainState

    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    model = build_model(cfg)
    tx = make_optimizer(OptimizerConfig(
        name="coap-adamw", learning_rate=lr, rank=rank, t_update=2, lam=2,
        seed=seed))
    step = jax.jit(make_train_step(model, tx))
    data = _two_batches(cfg, batch, seq, seed)
    dev0 = jax.devices()[0]
    mesh = make_test_mesh((2, 2), ("data", "model"))
    pshard = shd.tree_named(mesh, model.param_specs(mesh))
    rep = NamedSharding(mesh, P())
    bshard = shd.tree_named(mesh, shd.batch_specs(data[0], mesh))

    def sharded(state):
        return TrainState(step=jax.device_put(state.step, rep),
                          params=jax.device_put(state.params, pshard),
                          opt_state=jax.device_put(state.opt_state, rep))

    with jax.default_matmul_precision("highest"):
        # Host copies of the two start states keep one device to one
        # state at a time.
        state = jax.device_put(
            TrainState.create(model.init(jax.random.key(seed)), tx), dev0)
        init = jax.device_get(state)
        state, _ = step(state, jax.device_put(data[0], dev0))
        first = jax.device_get(state)
        state, metrics = step(state, jax.device_put(data[1], dev0))
        ref2, ref_loss = jax.device_get((state.params, metrics["loss"]))
        del state
        with jax.set_mesh(mesh):
            sdata = [jax.device_put(d, bshard) for d in data]
            s_init = sharded(init)
            compiled = jax.jit(step).lower(s_init, sdata[0]).compile()
            got1, _ = compiled(s_init, sdata[0])
            first_diff = _param_diff(first.params,
                                     jax.device_get(got1.params), lr)
            del got1, s_init
            got2, metrics = compiled(sharded(first), sdata[1])
    spans = max(len(x.sharding.device_set)
                for x in jax.tree_util.tree_leaves(got2.params))
    got2, loss = jax.device_get((got2.params, metrics["loss"]))
    out = dict(check="sharded_step", mesh=dict(mesh.shape),
               loss=float(loss), ref_loss=float(ref_loss),
               params=_param_diff(ref2, got2, lr),
               first_step_params=first_diff,
               param_devices=spans,
               collectives=collective_counts(compiled.as_text()),
               kernels_on_mesh=_on_mesh_counts())
    log(f"[chips] {json.dumps(out)}")
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-4)
    _assert_params_close(ref2, got2)
    assert spans == len(mesh.devices.flat), (
        f"sharded parameters span {spans} devices, mesh has "
        f"{len(mesh.devices.flat)}")
    return out


def compressed_step_parity(cfg, *, rank=CROSS_CHIP_RANK, batch=BATCH, seq=SEQ,
                           seed=SEED, lr=LR):
    """The compressed cross-pod step on a (pod 2, data 2) mesh against the
    same step uncompressed: the per-pod gradients all-reduced whole over
    'pod', then the core transform. Both run the same per-pod gradient
    program on the mesh, in fp32 at ``highest`` matmul precision, so they
    differ only in what ``compressed_update`` changes: it reduces the
    r-rank projection instead of G, and refreshes P in its own code.

    Both take the second step from the uncompressed state after the
    first; loss and parameters must agree to rtol 2e-4 and STEP_TOL. The
    first step from init is reported.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.coap_adam import (
        ProjectedAdamConfig,
        scale_by_projected_adam,
    )
    from repro.core.projector import ProjectionRules
    from repro.distributed.compression import make_compressed_train_step
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import build_model
    from repro.optim import apply_updates
    from repro.train.train_state import TrainState

    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    model = build_model(cfg)
    pcfg = ProjectedAdamConfig(rules=ProjectionRules(rank=rank),
                               t_update=2, lam=2, seed=seed)
    tx = scale_by_projected_adam(pcfg)
    data = _two_batches(cfg, batch, seq, seed)
    mesh = make_test_mesh((2, 2), ("pod", "data"))
    step_fn = make_compressed_train_step(model, pcfg, mesh, lr)

    def uncompressed_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, batch)[0])(params)
        grads, loss = jax.lax.pmean((grads, loss), "pod")
        upd, opt_state = tx.update(grads, opt_state, params)
        new = apply_updates(
            params, jax.tree_util.tree_map(lambda u: -lr * u, upd))
        return new, opt_state, loss

    uncompressed = jax.jit(jax.shard_map(
        uncompressed_step, mesh=mesh, in_specs=(P(), P(), P("pod")),
        out_specs=P(), check_vma=False, axis_names={"pod"}))

    with jax.default_matmul_precision("highest"), jax.set_mesh(mesh):
        rep = NamedSharding(mesh, P())
        params = jax.device_put(model.init(jax.random.key(seed)), rep)
        opt_state = jax.device_put(tx.init(params), rep)
        pods = [jax.device_put(d, NamedSharding(mesh, P("pod")))
                for d in data]

        def state(params, opt_state, step):
            return TrainState(step=jax.device_put(jnp.int32(step), rep),
                              params=params, opt_state=opt_state)

        compiled = jax.jit(step_fn).lower(
            state(params, opt_state, 0), pods[0]).compile()
        unc1 = uncompressed(params, opt_state, pods[0])
        com1, metrics = compiled(state(params, opt_state, 0), pods[0])
        first = dict(loss=float(metrics["loss"]), ref_loss=float(unc1[2]),
                     params=_param_diff(jax.device_get(unc1[0]),
                                        jax.device_get(com1.params), lr))
        del com1
        ref2, _, ref_loss = uncompressed(unc1[0], unc1[1], pods[1])
        com2, metrics = compiled(state(unc1[0], unc1[1], 1), pods[1])
        ref2, got2, loss, ref_loss = jax.device_get(
            (ref2, com2.params, metrics["loss"], ref_loss))
    out = dict(check="compressed_step", mesh=dict(mesh.shape),
               loss=float(loss), ref_loss=float(ref_loss),
               params=_param_diff(ref2, got2, lr), first_step=first,
               collectives=collective_counts(compiled.as_text()),
               kernels_on_mesh=_on_mesh_counts())
    log(f"[chips] {json.dumps(out)}")
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-4)
    _assert_params_close(ref2, got2)
    return out


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip path on four chips")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    log(f"[cache] {enable_compile_cache()}")
    cache = CacheLog().install()
    device = check_device(args.chips)
    if args.chips == 4:
        cfg = smoke_config(4)
        log(f"[chips] {ARCH} widths at {cfg.n_layers} of {FULL_LAYERS} "
            "layers")
        sharded_step_parity(cfg)
        compressed_step_parity(cfg)
        log(f"[cache] chips: {json.dumps(cache.take())}")
    else:
        rows = kernel_parity()
        log(f"[cache] kernels: {json.dumps(cache.take())}")
        missing = [row["name"] for row in rows if not row["custom_call"]]
        if missing:
            raise SystemExit(f"no tpu_custom_call compiled for {missing}")
        cfg = smoke_config()
        log(f"[train] {ARCH} widths, depth cut from {FULL_LAYERS} to "
            f"{cfg.n_layers} layers to fit one chip")
        for i, optimizer in enumerate(OPTIMIZERS):
            t0 = time.time()
            train(cfg, optimizer, os.path.join(CKPT_ROOT, optimizer),
                  save=i == 0)
            log(f"[train] {optimizer} phase {time.time() - t0:.1f} s")
            log(f"[cache] {optimizer}: {json.dumps(cache.take())}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

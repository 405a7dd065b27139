"""The fault-tolerant training loop (used by examples/ and launch/train.py).

Features: checkpoint/resume (atomic, elastic), heartbeat files, straggler
detection, CEU/PPL metrics, restart-exact data replay. Single-host here;
on a pod each host runs the same loop (SPMD) with its data shard.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.obs import health
from repro.obs.registry import get_registry
from repro.obs.trace import get_tracer
from repro.train import checkpoint as ckpt
from repro.train.fault_tolerance import (
    DrainPreemption,
    Heartbeat,
    StragglerDetector,
)
from repro.train.metrics import MetricsLogger
from repro.train.step import make_train_step
from repro.train.train_state import TrainState


@dataclasses.dataclass
class TrainLoopConfig:
    total_steps: int = 100
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    ckpt_keep: int = 3
    log_every: int = 10
    metrics_path: Optional[str] = None
    heartbeat_path: Optional[str] = None
    grad_accum: int = 1
    crash_at_step: Optional[int] = None  # fault-injection for tests
    # Seeded fault injection (train/faults.FaultInjector): kills, torn
    # checkpoint writes, heartbeat silence, slow steps. Owned by the
    # supervisor so one-shot faults survive across worker attempts.
    fault_injector: Optional[Any] = None
    # JSON dict saved with every checkpoint manifest (the elastic
    # supervisor stores the coap-plan/v1 artifact here).
    ckpt_meta: Optional[Dict] = None
    # Preemption-notice channel: a JSON file ({"deadline": unix_time})
    # whose appearance means "this allocation dies soon". The loop checks
    # it at the top of every step and DRAINS: checkpoint at the current
    # step, acknowledge (notice_path + ".ack"), raise DrainPreemption.
    # The supervisor owns the file's lifecycle (writes it, clears it
    # before relaunch).
    notice_path: Optional[str] = None
    # Wall-clock floor per step (seconds). Real fleets pace steps for
    # power/thermal smoothing; here it also makes process-supervision
    # races (notice vs kill vs heartbeat) testable on CPU where smoke
    # steps would otherwise finish in microseconds.
    min_step_s: float = 0.0
    # Optional refresh-group attribution for step spans: a callable
    # ``step -> [ {bucket, phase, size, frac, kind}, ... ]`` (see
    # ``obs.calib.planned_refresh_schedule``). The elastic supervisor
    # passes the planned schedule so a trace shows WHICH stagger groups
    # refreshed on each step — what the calibration fit keys on.
    refresh_schedule: Optional[Callable[[int], Any]] = None
    # Sampled projection-health cadence (``obs/health.observe_state``):
    # every N steps the loop reads the RESIDENT optimizer state (int8
    # codec stats, EF-sidecar norms) — never the gradient, so off-cadence
    # steps pay nothing and no step ever re-reads G. Refresh-boundary
    # metrics (energy/residual/overlap) are emitted from inside the
    # optimizer's own refresh branch, not from here. 0 disables.
    health_every: int = 25


class TrainLoop:
    def __init__(self, model, tx, batch_fn: Callable[[int, int], Dict],
                 cfg: TrainLoopConfig, init_key=None, initial_state=None):
        self.model = model
        self.tx = tx
        self.batch_fn = batch_fn
        self.cfg = cfg
        self.logger = MetricsLogger(cfg.metrics_path)
        self.straggler = StragglerDetector()
        self.heartbeat = (
            Heartbeat(cfg.heartbeat_path) if cfg.heartbeat_path else None
        )
        self._step_fn = jax.jit(make_train_step(model, tx,
                                                grad_accum=cfg.grad_accum))
        self._init_key = init_key if init_key is not None else jax.random.key(0)
        # A supervisor that already restored (and possibly migrated) the
        # state passes it here; init_or_restore then skips its own restore.
        self._initial_state = initial_state

    # -- state ---------------------------------------------------------------
    def init_or_restore(self) -> TrainState:
        cfg = self.cfg
        if self._initial_state is not None:
            return self._initial_state
        if cfg.ckpt_dir and ckpt.latest_step(cfg.ckpt_dir) is not None:
            template = jax.eval_shape(
                lambda: TrainState.create(
                    self.model.init(self._init_key), self.tx
                )
            )
            state = ckpt.restore(cfg.ckpt_dir, template)
            return state
        params = self.model.init(self._init_key)
        return TrainState.create(params, self.tx)

    def step_hlo_text(self, state, batch) -> str:
        """The optimized HLO text of the step compiled for these arguments
        (arrays or ``jax.ShapeDtypeStruct`` trees): its instruction names
        are the operations of a device trace, and each carries its
        ``op_name`` scope path (``model``, ``optimizer``, ...)."""
        return self._step_fn.lower(state, batch).compile().as_text()

    # -- drain ---------------------------------------------------------------
    def _notice_deadline(self, step: int) -> Optional[float]:
        """An active preemption notice's absolute deadline, or None. File
        channel first (process mode), then the in-process injector."""
        cfg = self.cfg
        if cfg.notice_path and os.path.exists(cfg.notice_path):
            try:
                with open(cfg.notice_path) as f:
                    return float(json.load(f).get("deadline", 0.0))
            except (json.JSONDecodeError, ValueError, OSError):
                return 0.0  # unreadable notice still means "leave now"
        inj = cfg.fault_injector
        if inj is not None and hasattr(inj, "due_notice"):
            d = inj.due_notice(step)
            if d is not None:
                return time.time() + d
        return None

    def _drain(self, state: TrainState, step: int, deadline: float):
        """Checkpoint at exactly ``step`` (every completed step survives),
        acknowledge the notice, and hand control back as a planned
        preemption. The next attempt resumes from ``step``: zero lost."""
        cfg = self.cfg
        get_registry().inc("loop/drain")
        if cfg.ckpt_dir:
            with get_tracer().span("loop/checkpoint", step=step,
                                   reason="drain"):
                ckpt.save(cfg.ckpt_dir, step, state, keep=cfg.ckpt_keep,
                          meta=cfg.ckpt_meta)
        if cfg.notice_path:
            ack = cfg.notice_path + ".ack"
            tmp = ack + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"step": step, "time": time.time()}, f)
            os.replace(tmp, ack)
        raise DrainPreemption(step, deadline)

    # -- main ----------------------------------------------------------------
    def run(self) -> TrainState:
        # The logger is closed in the finally: worker processes run one
        # loop per attempt, and leaked jsonl handles accumulate across
        # restarts otherwise. ``logger.history`` stays readable after.
        try:
            return self._run()
        finally:
            self.logger.close()

    def _run(self) -> TrainState:
        cfg = self.cfg
        tracer = get_tracer()
        reg = get_registry()
        reg.set_phase("train")
        state = self.init_or_restore()
        start = int(state.step)
        ceu_total = 0.0
        inj = cfg.fault_injector
        for step in range(start, cfg.total_steps):
            # One profiler step per iteration; the loop's own spans below
            # are host events inside it (obs/trace.py).
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                deadline = self._notice_deadline(step)
                if deadline is not None:
                    self._drain(state, step, deadline)
                if cfg.crash_at_step is not None and step == cfg.crash_at_step:
                    raise RuntimeError(f"induced crash at step {step}")
                if inj is not None:
                    inj.maybe_kill(step)
                with tracer.span("loop/batch"):
                    batch = self.batch_fn(step, 0)
                # Refresh-group attribution is computed host-side BEFORE the
                # step (a pure function of (plan, step)) so the span carries
                # exactly what the jitted update is about to do.
                span_attrs = {"step": step}
                if cfg.refresh_schedule is not None:
                    ev = cfg.refresh_schedule(step)
                    if ev:
                        span_attrs["refresh"] = ev
                if step == start:
                    # First execution of this loop instance traces + compiles.
                    span_attrs["compile"] = True
                t0 = time.time()
                # loop/step spans the dispatch and the wait, as it always has
                # (obs/calib reads it); the two children split it.
                with tracer.span("loop/step", **span_attrs):
                    with tracer.span("loop/dispatch"):
                        state, metrics = self._step_fn(state, batch)
                    with tracer.span("loop/wait"):
                        jax.block_until_ready(state.params)
                dt = time.time() - t0
                if cfg.min_step_s > 0 and dt < cfg.min_step_s:
                    time.sleep(cfg.min_step_s - dt)
                if inj is not None:
                    dt += inj.slow_delay(step)
                slow = self.straggler.observe(dt)
                if slow:
                    reg.inc("loop/straggler_step")
                with tracer.span("loop/metrics_pull"):
                    ceu_total += float(metrics["ceu"])
                if (
                    cfg.health_every
                    and health.get_monitor().enabled
                    and step % cfg.health_every == 0
                ):
                    health.observe_state(state.opt_state, step)
                if self.heartbeat and not (
                    inj is not None and inj.heartbeat_silent(step)
                ):
                    with tracer.span("loop/heartbeat"):
                        snap = reg.snapshot()
                        self.heartbeat.beat(
                            step,
                            extra={
                                "straggler_flagged": self.straggler.flagged,
                                "phase": reg.gauge("phase", "train"),
                                # The registry snapshot rides every beat:
                                # the supervisor (and fleet_status) reads a
                                # worker's counters AND health gauges with
                                # no extra channel.
                                "counters": snap["counters"],
                                "gauges": snap["gauges"],
                            },
                        )
                if step % cfg.log_every == 0 or step == cfg.total_steps - 1:
                    with tracer.span("loop/log"):
                        row = dict(metrics)
                        row["ceu_total"] = ceu_total
                        row["straggler"] = int(slow)
                        ntok = 0
                        b = batch.get("tokens", batch.get("embeds"))
                        if b is not None:
                            ntok = b.shape[0] * b.shape[1]
                        self.logger.log(step, row, tokens=ntok)
                if (
                    cfg.ckpt_dir
                    and cfg.ckpt_every
                    and (step + 1) % cfg.ckpt_every == 0
                ):
                    with tracer.span("loop/checkpoint", step=step + 1):
                        ckpt.save(cfg.ckpt_dir, step + 1, state,
                                  keep=cfg.ckpt_keep, meta=cfg.ckpt_meta)
                    reg.inc("ckpt/save")
                    if inj is not None:
                        inj.after_save(cfg.ckpt_dir, step + 1)
        if cfg.ckpt_dir:
            with tracer.span("loop/checkpoint", step=int(state.step),
                             reason="final"):
                ckpt.save(cfg.ckpt_dir, int(state.step), state,
                          keep=cfg.ckpt_keep, meta=cfg.ckpt_meta)
            reg.inc("ckpt/save")
            if inj is not None:
                inj.after_save(cfg.ckpt_dir, int(state.step))
        return state

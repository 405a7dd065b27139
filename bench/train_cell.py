"""A training cell: the program's normal path, driven from the seed.

Set-up builds one object, the program's ``TrainLoop`` (its compiled step
and its state), from weights the benchmark makes on the device from the
seed, and drives it through the warm-up: the first ``compared_steps``
steps one call of ``TrainLoop.run`` each (reading what the check
compares), then the rest. The window hands the same loop on and runs
whole steps through ``TrainLoop.run`` until ``--seconds`` have passed.

The feed is the traffic's Markov source (``bench/markov.py``), drawn for
``distinct_steps`` steps at set-up and put on the device once; step ``i``
takes batch ``i mod distinct_steps``.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import math
import shutil
import statistics
import tempfile
import time

import jax

from bench import check as chk
from bench import markov, weights
from bench.harness import BenchError


def _profile_options():
    """The profiler without its Python tracer, which would hook every
    Python call of the host loop and slow the traced steps."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


class TrainCell:
    def __init__(self, cell, arch: dict, seed: int, log=print):
        self.cell = cell
        self.model_ref = cell.reference  # the configuration's reference module
        self.arch = arch
        self.seed = seed
        self.log = log
        self.traffic = cell.traffic
        self.opt = dict(self.traffic["optimizer"])
        self.readings = {}
        self.marks = []  # (step, perf_counter) at each call of the feed
        self.window_steps = (0, 0)

    # ----------------------------------------------------------------- build
    def _build(self):
        from repro.configs import get_config
        from repro.core.api import OptimizerConfig, make_optimizer
        from repro.models.model import build_model

        cfg = dataclasses.replace(get_config(self.cell.config["registry_arch"]),
                                  **self.arch)
        model = build_model(cfg)
        o = self.opt
        tx = make_optimizer(OptimizerConfig(
            name=o["name"], learning_rate=o["lr"], weight_decay=0.0,
            b1=o["b1"], b2=o["b2"], eps=o["eps"], grad_clip=o["grad_clip"],
            rank=o["rank"], min_dim=o["min_dim"], t_update=o["t_update"],
            lam=o["lam"], seed=o["opt_seed"], eqn6_lr=o["eqn6_lr"],
            eqn6_steps=o["eqn6_steps"], stagger_groups=o["stagger_groups"]))
        return model, tx

    def _feed(self):
        t = self.traffic
        toks = markov.feed(t, self.arch["vocab_size"], self.seed)
        self.compared = [(toks[k, :, :-1], toks[k, :, 1:])
                         for k in range(t["compared_steps"])]
        dev = jax.device_put(toks)
        self.batches = [{"tokens": dev[i, :, :-1], "labels": dev[i, :, 1:]}
                        for i in range(t["distinct_steps"])]

    def batch_fn(self, step: int, host: int):
        """The loop's feed. The loop keeps the state it was handed
        (``initial_state``) for as long as ``run`` lasts; once it has asked
        for a batch it holds that state itself, so the reference is dropped
        here and the device holds one state, as it does when the loop makes
        its own."""
        self.marks.append((step, time.perf_counter()))
        self.loop._initial_state = None
        return self.batches[step % len(self.batches)]

    def _check_loop(self):
        """``TrainLoop`` takes no state and no step count once built; the
        harness sets the two fields that ``run`` reads. Were either renamed,
        setting it would make a new attribute and the loop would train from
        a state of its own: refuse that instead."""
        if "_initial_state" not in vars(self.loop) or not hasattr(self.loop.cfg,
                                                                  "total_steps"):
            raise RuntimeError("TrainLoop has no _initial_state or cfg.total_steps "
                               "for the harness to set")

    def _check_one_state(self):
        """The device holds one training state (and the feed), not two:
        what ``peak_hbm_gib`` measures."""
        state = sum(x.nbytes for x in jax.tree_util.tree_leaves(self.state))
        feed = sum(x.nbytes for x in jax.tree_util.tree_leaves(self.batches))
        live = sum(x.nbytes for x in jax.live_arrays())
        if live > 1.5 * state + 2 * feed + 2 ** 16:
            raise RuntimeError(f"{live} bytes live on the device against one state "
                               f"of {state} and a feed of {feed}")

    def setup(self, warmup: bool = True):
        """Build the loop (once), start it from this seed's weights and
        feed, read what the check compares in the first steps, and (with
        ``warmup``) run on to the window's first step."""
        from repro.train.loop import TrainLoop, TrainLoopConfig
        from repro.train.train_state import TrainState

        t = self.traffic
        if not hasattr(self, "loop"):
            model, self.tx = self._build()
            self.layout = self.model_ref.layout(self.arch)
            want = {chk.coap_adamw.path_of(kp): tuple(x.shape) for kp, x in
                    jax.tree_util.tree_flatten_with_path(model.abstract_params())[0]}
            if want != {p: tuple(s) for p, s in self.layout.items()}:
                raise BenchError(f"the program's parameters {want} differ from the "
                                 f"layout of {self.cell.config['reference']}: "
                                 f"{self.layout}")
            self.make_weights = weights.maker(self.layout)
            self.loop = TrainLoop(model, self.tx, self.batch_fn,
                                  TrainLoopConfig(total_steps=0,
                                                  grad_accum=t["grad_accum"]))
            self._check_loop()
        self._feed()
        self.marks = []
        self.loop.logger.history.clear()
        self.state = TrainState.create(self.make_weights(self.seed), self.tx)
        # The first compared steps one call each, so that each loss is
        # logged (the loop logs the last step of every call).
        k = t["compared_steps"]
        for step in range(k):
            self._run_to(step + 1)
            if step == 0:
                self._check_one_state()
                norms, dense = jax.device_get(chk.program_first_grads(
                    self.state.params, self.state.opt_state, b1=self.opt["b1"],
                    block=self.opt["quant_block"],
                    matrix_axes=self.model_ref.matrix_axes))
                self.readings["first_grad"] = chk._to_lists(norms)
                self.readings["first_dense"] = dense
        hist = {int(r["step"]): r["loss"] for r in self.loop.logger.history}
        self.readings["losses"] = [hist[s] for s in range(k)]
        change = jax.jit(functools.partial(chk.change_norms,
                                           matrix_axes=self.model_ref.matrix_axes))
        self.readings["change"] = chk._to_lists(jax.device_get(
            change(self.state.params, self.make_weights(self.seed))))
        if not warmup:
            return
        self._run_to(t["warmup_steps"])
        # Every step's time but the first's, which compiled.
        durations = [b - a for (_, a), (_, b) in zip(self.marks[1:], self.marks[2:])]
        self.est_step_s = statistics.median(durations) if durations else 1.0

    def _run_to(self, total: int):
        """Run the loop on to step ``total``; the state lives in
        ``self.state`` alone, so that no caller keeps the old one alive."""
        self.loop._initial_state, self.state = self.state, None
        self.loop.cfg.total_steps = total
        self.state = self.loop.run()

    def _step(self) -> int:
        return int(self.state.step)

    # ---------------------------------------------------------------- window
    def window(self, seconds: float, run, trace: bool, t0: float):
        """Whole steps until ``seconds`` have passed. With ``trace`` the
        profiler records the traffic's ``trace_steps`` steps at the end of
        the window; its start is inside the window, its write-out after."""
        from bench import trace_reduce

        t = self.traffic
        run.tokens_per_step = t["batch"] * t["grad_accum"] * t["seq"]
        run.flops_per_step = run.tokens_per_step * self.model_ref.flops_per_token(
            self.arch, t["seq"])
        run.shapes = dict(self.layout)
        traced = min(t["trace_steps"], max(1, math.ceil(seconds / self.est_step_s))) if trace else 0
        tmp = tempfile.mkdtemp() if trace else None
        first = self._step()
        self.marks = []
        t_start = time.perf_counter()
        run.setup_s = time.time() - t0

        def left():
            return seconds - (time.perf_counter() - t_start)

        try:
            while left() > traced * self.est_step_s:
                steps = max(1, math.ceil((left() - traced * self.est_step_s)
                                         / self.est_step_s))
                self._run_to(self._step() + steps)
            if trace:
                jax.profiler.start_trace(tmp, profiler_options=_profile_options())
                with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                    self._run_to(self._step() + traced)
                    jax.block_until_ready(self.state)
            while left() > 0:
                self._run_to(self._step() + max(1, math.ceil(left() / self.est_step_s)))
            jax.block_until_ready(self.state)
            t_end = time.perf_counter()
            reduced = None
            if trace:
                jax.profiler.stop_trace()
                reduced = trace_reduce.reduce_dir(tmp)
        finally:
            if tmp:
                shutil.rmtree(tmp, ignore_errors=True)
        self.window_steps = (first, self._step())
        times = [m for _, m in self.marks] + [t_end]
        times[0] = t_start
        run.step_s = [b - a for a, b in zip(times, times[1:])]
        run.window_s = t_end - t_start
        run.traced_steps = traced
        slow = sorted(range(len(run.step_s)), key=lambda i: -run.step_s[i])[:6]
        self.log(f"[window] steps {first}..{self._step() - 1}, "
                 f"{run.window_s:.3f} s, set-up {run.setup_s:.3f} s, "
                 f"traced {traced} steps; median step "
                 f"{statistics.median(run.step_s):.4f} s, longest "
                 + ", ".join(f"{first + i}: {run.step_s[i]:.4f}" for i in slow))
        return reduced

    def counters(self) -> dict:
        """The program's counters (``repro.obs.registry``) for the process."""
        from repro.obs.registry import get_registry

        return dict(get_registry().snapshot()["counters"])

    def attempted_failed(self):
        first, end = self.window_steps
        rows = [r for r in self.loop.logger.history if first <= r["step"] < end]
        bad = sum(1 for r in rows if not math.isfinite(r["loss"]))
        return end - first, bad

    def free(self):
        del self.loop, self.state, self.batches
        gc.collect()

    # ----------------------------------------------------------------- check
    def reference(self, log=print, **fault) -> dict:
        """The reference's readings on this run's first steps; ``fault``
        puts the control or a fault in its place (``bench/control.py``)."""
        return chk.reference_readings(self.make_weights, self.seed, self.compared,
                                      self.arch, self.opt, self.traffic["batch"],
                                      self.model_ref, log=log, **fault)

    def check(self, log=print) -> dict:
        t0 = time.perf_counter()
        ref = self.reference(log=log)
        worst = {}
        numbers = chk.gaps(self.readings, ref, self.model_ref.matrix_axes, worst)
        log(f"[check] reference {time.perf_counter() - t0:.3f} s; losses "
            f"program {self.readings['losses']} reference {ref['losses']}; "
            f"Eqn 6 moved P by (norm of the change over the norm) {ref['eqn6_moved']}; "
            f"worst matrices {worst}; left out of update: {chk.excluded(ref)}")
        return numbers

"""Record the scoped device trace that ``test_scopes.py`` reads.

    python3 bench/tests/record_scoped_trace.py <out_dir>

Runs on a TPU: ``record_trace.toy_loop``'s model and batch train with
``8bit-coap-adamw`` (rank 64) at T_u 16, so that the refreshes of its
eight staggered phase groups fall on every other step (at T_u 4 they fall
on every step, and no traced step would be off the schedule). Three
steps compile and initialize outside the trace; the next eight run inside
the ``bench/window`` span under the JAX profiler. Writes to ``<out_dir>``:

* ``toy_train_scoped.xplane.pb.gz``: the trace;
* ``toy_train_scoped.hlo.txt.gz``: the step's optimized HLO text, from
  ``TrainLoop.step_hlo_text`` on the loop that ran;
* ``toy_train_scoped.json``: the traced steps, the first one's number,
  the parameter shapes and the optimizer's ``rank``, ``min_dim``,
  ``t_update`` and ``phases``.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402

from bench.tests import record_trace, tiny  # noqa: E402

NAME = "toy_train_scoped"
WARMUP, TRACED = 3, 8
OPT = dict(name="8bit-coap-adamw", rank=64, min_dim=128, t_update=16, lam=2,
           stagger_groups=8)


def scoped_loop():
    from repro.core.api import OptimizerConfig, make_optimizer
    from repro.train.loop import TrainLoop

    toy = record_trace.toy_loop(OPT["name"])
    tx = make_optimizer(OptimizerConfig(
        name=OPT["name"], rank=OPT["rank"], min_dim=OPT["min_dim"],
        t_update=OPT["t_update"], lam=OPT["lam"],
        stagger_groups=OPT["stagger_groups"], learning_rate=1e-3))
    return TrainLoop(toy.model, tx, toy.batch_fn, toy.cfg)


def main(out_dir):
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    from repro.core.projector import path_str

    loop = scoped_loop()
    loop.cfg.total_steps = WARMUP
    state = loop.run()  # compile and initialize outside the trace
    loop._initial_state = state
    loop.cfg.total_steps = WARMUP + TRACED
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench/window"):
            state = jax.block_until_ready(loop.run())
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                         "*.xplane.pb"))
        os.makedirs(out_dir, exist_ok=True)
        with open(path, "rb") as f:
            data = f.read()
        with gzip.open(os.path.join(out_dir, NAME + ".xplane.pb.gz"), "wb") as f:
            f.write(data)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    batch = loop.batch_fn(0, 0)
    with gzip.open(os.path.join(out_dir, NAME + ".hlo.txt.gz"), "wt") as f:
        f.write(loop.step_hlo_text(state, batch))
    shapes = {path_str(kp): list(x.shape) for kp, x in
              jax.tree_util.tree_flatten_with_path(state.params)[0]}
    opt = {"rank": OPT["rank"], "min_dim": OPT["min_dim"], "t_update": OPT["t_update"],
           "phases": tiny.program_phases(shapes, OPT)}
    with open(os.path.join(out_dir, NAME + ".json"), "w") as f:
        json.dump({"traced_steps": TRACED, "first_step": WARMUP, "shapes": shapes,
                   "optimizer": opt}, f, indent=1)


if __name__ == "__main__":
    main(sys.argv[1])

"""The check's readings at a cell's own size: the program's, the
control's and the faults', for many seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 1 2 ... [--control-seeds 3] [--out <file>]

For each seed the program runs its first steps from the seed's weights
and feed (one compiled step for all seeds, no window) and is compared with
the reference by the cell's numbers (``bench/check.py``). For the first
``--control-seeds`` seeds the plain reference is also put in the
program's place in three ways:

* ``control``: every product's operands rounded to float8 in the forward
  pass (e4m3) and their gradients in the backward pass (e5m2), one absmax
  scale per tensor: one step below the bf16 the configuration computes
  in;
* ``half_batch``: each step sees the first half of its rows, the loss the
  mean over them;
* ``double``: one matrix (``DOUBLED`` of the configuration's reference
  module: every layer's query projection in a dense decoder) moves twice
  as far as the optimizer says, at every step;
* a step that returns its state unchanged reads 1 on ``update`` by
  construction and needs no run.

The program's readings set the lower ends of the cell's limits, the
others the upper ends (``PERF.md``). The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0
E5M2_MAX = 57344.0


def _round(x, dtype, top):
    """Round to ``dtype`` under one absmax scale per tensor."""
    scale = jnp.max(jnp.abs(x)) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(dtype).astype(x.dtype) * scale


@jax.custom_vjp
def fp8(x):
    """Float8 as fp8 training takes it: the operand rounded to e4m3 going
    forward, its gradient rounded to e5m2 coming back."""
    return _round(x, jnp.float8_e4m3fn, E4M3_MAX)


def _fp8_fwd(x):
    return fp8(x), None


def _fp8_bwd(_, g):
    return (_round(g, jnp.float8_e5m2, E5M2_MAX),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


VARIANTS = {
    "control": dict(rnd=fp8),
    "half_batch": dict(half_batch=True),
    "double": dict(double=True),
}


def _gaps(got, ref, matrix_axes):
    from bench import check

    worst = {}
    out = check.gaps(got, ref, matrix_axes, worst)
    out["worst"] = {k: f"{p}[{i}]" for k, (p, i) in worst.items()}
    return out


def readings(runner, variants=VARIANTS, log=None):
    """{"program": gaps, <variant>: gaps}: the program's first steps (read
    by ``runner.setup``) and each variant, against the reference."""
    import gc

    program = runner.readings
    runner.state = None  # the program's state goes before the reference runs
    gc.collect()
    ref = runner.reference(log=log)
    axes = runner.model_ref.matrix_axes
    out = {"program": _gaps(program, ref, axes), "eqn6_moved": ref["eqn6_moved"]}
    for name, kw in variants.items():
        out[name] = _gaps(runner.reference(log=log, **kw), ref, axes)
    return out


def main(argv=None):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    from bench import harness, train_cell

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="how many of the seeds also read the control and faults")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.check_device(cell.chips)
    harness.enable_cache()
    runner = train_cell.TrainCell(cell, harness.arch_fields(cell.config),
                                  args.seeds[0], log=lambda m: None)
    results = {}
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        runner.seed = seed
        runner.setup(warmup=False)
        results[seed] = readings(
            runner, VARIANTS if i < args.control_seeds else {},
            log=lambda m: print(m, flush=True))
        print(f"seed {seed} ({time.perf_counter() - t0:.1f} s): "
              f"{json.dumps(results[seed])}", flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "readings": results}, f, indent=1)


if __name__ == "__main__":
    main()

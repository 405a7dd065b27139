"""Record the small device trace that ``test_trace.py`` reads.

    python3 bench/tests/record_trace.py <out_dir>

Runs on a TPU: a toy InternLM2-shaped model (2 layers, d_model 256) trains
three steps with ``coap-adamw`` and three with ``8bit-coap-adamw`` (rank 64,
so both fused update kernels run), under the JAX profiler. The
``.xplane.pb`` file is copied to ``<out_dir>/toy_train.xplane.pb`` and a
summary of its planes, lines and event names is printed.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def toy_loop(optimizer):
    from repro.configs import get_config
    from repro.core.api import OptimizerConfig, make_optimizer
    from repro.models.model import build_model
    from repro.train.loop import TrainLoop, TrainLoopConfig

    cfg = dataclasses.replace(
        get_config("internlm2-1.8b"), n_layers=2, d_model=256, n_heads=4,
        n_kv_heads=2, head_dim=64, d_ff=512, vocab_size=1024)
    model = build_model(cfg)
    tx = make_optimizer(OptimizerConfig(name=optimizer, rank=64, t_update=4,
                                        lam=2, learning_rate=1e-3))
    key = jax.random.key(0)
    tokens = jax.random.randint(key, (4, 129), 0, cfg.vocab_size, jnp.int32)
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    return TrainLoop(model, tx, lambda step, host: batch,
                     TrainLoopConfig(total_steps=3, health_every=0))


def main(out_dir):
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    loops = [toy_loop(o) for o in ("coap-adamw", "8bit-coap-adamw")]
    states = [loop.run() for loop in loops]  # compile outside the trace
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        for loop, state in zip(loops, states):
            loop._initial_state = state
            loop.cfg.total_steps = int(state.step) + 3
            with jax.profiler.TraceAnnotation("bench/window"):
                jax.block_until_ready(loop.run())
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                         "*.xplane.pb"))
        os.makedirs(out_dir, exist_ok=True)
        dest = os.path.join(out_dir, "toy_train.xplane.pb")
        shutil.copy(path, dest)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    summarize(dest)


def summarize(path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            names = {}
            for ev in events:
                names.setdefault(ev.name, ev)
            for name, ev in list(names.items())[:12]:
                stats = {k: (str(v)[:80]) for k, v in ev.stats}
                print(f"    {name[:90]!r} start={ev.start_ns} "
                      f"dur={ev.duration_ns} stats={stats}")
            kernels = sorted(n for n in names if "kernel" in n.lower())
            if kernels:
                print(f"    kernel-named events: {kernels[:20]}")


if __name__ == "__main__":
    main(sys.argv[1])

"""A tiny cell for the CPU tests, added to a temporary copy of the
benchmark's files the way a later change adds one: a configuration file,
a traffic file, a limits file and two entries of ``BENCHMARK.json``, and,
where a test gives one, the reference module the configuration names."""
from __future__ import annotations

import copy
import gc
import json
import shutil
from pathlib import Path

from bench import harness, weights

PROBE = "bench/references/probe.py"

CELL = "tiny.coap"
# Set from CPU readings at this size over 8 compared steps, seven seeds
# each optimizer: the program reads at most loss 9.8e-3 (8-bit), grad
# 3.6e-3, update 5.4e-2 (8-bit), grad_diff 2.2e-2; the control at least
# grad 5.5e-2 and grad_diff 0.26, half the batch loss 4.6e-2, a matrix
# moved double update 1.0.
LIMITS = {
    "loss": {"limit": 2e-2},
    "grad": {"limit": 1e-2},
    "update": {"limit": 1e-1},
    "grad_diff": {"limit": 6e-2},
}


def program_phases(shapes: dict, opt: dict) -> dict:
    """{path: refresh phase} of every projected matrix, as the program's
    optimizer staggers them for these parameter shapes and settings."""
    import jax
    from repro.core import stacked_state
    from repro.core.coap_adam import ProjectedAdamConfig, bucket_phases
    from repro.core.projector import ProjectionRules

    abstract = weights.nest({p: jax.ShapeDtypeStruct(s, "float32")
                             for p, s in shapes.items()})
    rules = ProjectionRules(rank=opt["rank"], min_dim=opt["min_dim"])
    cfg = ProjectedAdamConfig(rules=rules, t_update=opt["t_update"], lam=opt["lam"],
                              stagger_groups=opt["stagger_groups"])
    layout = stacked_state.layout_for_tree(rules.spec_for, abstract)
    flat = jax.tree_util.tree_flatten_with_path(abstract)[0]
    out = {}
    for bi, phases in bucket_phases(cfg, layout).items():
        for i, phase in zip(layout.buckets[bi].indices, phases):
            out["/".join(str(k.key) for k in flat[i][0])] = phase
    return out


def make_root(tmp: Path, optimizer: str = "coap-adamw", limits=None,
              reference: str = None) -> Path:
    """A copy of BENCHMARK.json and bench/'s data with the tiny cell added.
    ``reference``, where given, is the source of a module written to
    ``PROBE`` in the copy, which the tiny configuration then names."""
    src = harness.ROOT
    root = tmp / "checkout"
    shutil.copytree(src / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((src / "BENCHMARK.json").read_text())
    conf = json.loads((src / "bench/configs/internlm2-1.8b.v5e1.json").read_text())
    conf["name"] = "tiny"
    conf["config"].update(hidden_size=64, intermediate_size=128,
                          num_attention_heads=4, num_key_value_heads=2,
                          num_hidden_layers=2, vocab_size=256)
    conf["arch_extra"] = {"head_dim": 16}
    traffic = json.loads((src / "bench/traffic/coap8.b8s1024.json").read_text())
    traffic.update(batch=4, seq=32, distinct_steps=8)
    traffic["optimizer"].update(name=optimizer, rank=16, min_dim=32,
                                quantize=optimizer.startswith("8bit-"))
    traffic["optimizer"]["phases"] = program_phases(
        harness.reference_module(conf).layout(harness.arch_fields(conf)),
        traffic["optimizer"])
    if reference is not None:
        (root / PROBE).write_text(reference)
        conf["reference"] = PROBE
    (root / "bench/configs/tiny.json").write_text(json.dumps(conf))
    (root / "bench/traffic/tiny.json").write_text(json.dumps(traffic))
    (root / f"bench/limits/{CELL}.json").write_text(json.dumps(limits or LIMITS))
    spec = copy.deepcopy(spec)
    spec["configs"].append({"name": "tiny", "source": "test", "file":
                            "bench/configs/tiny.json", "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny", "traffic": "tiny",
                              "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CPU_PEAKS = {"bf16_flop_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def run(root: Path, seed: int = 2 ** 31 + 7, seconds: float = 0.5) -> dict:
    """Drive one run of the tiny cell on the CPU, skipping the chip check.
    What an earlier test left to the garbage collector (a refused run's
    state, held in a traceback's cycle) goes first, so that the check of
    one state on the device counts this run's arrays alone."""
    gc.collect()
    cell = harness.load_cell(CELL, root)
    return harness.run_cell(cell, seed, seconds, trace=False, t0=0.0,
                            device=dict(CPU), peaks=dict(CPU_PEAKS),
                            log=lambda msg: None)

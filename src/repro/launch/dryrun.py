import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the very first lines: jax locks the device count on first init.

"""Multi-pod dry-run: AOT-lower + compile every (architecture × input shape ×
mesh) cell against the production meshes, proving the distribution config is
coherent — and extracting the roofline terms from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
        --shape train_4k [--multi-pod] [--optimizer coap-adamw] [--all]

Artifacts land in artifacts/dryrun/<arch>__<shape>__<mesh>[__tag].json and
are consumed by launch/roofline.py and EXPERIMENTS.md.
"""
import argparse
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, input_specs, supports_shape
from repro.configs.registry import ASSIGNED
from repro.core.api import OptimizerConfig, make_optimizer
from repro.distributed import sharding as shd
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models.model import build_model
from repro.train.step import make_train_step
from repro.train.train_state import TrainState, abstract_train_state

ARTIFACT_DIR = os.path.join("artifacts", "dryrun")

# Paper-faithful optimizer settings for the dry-run train cells (Table 5 /
# appendix Table 1: rank 512, T_u 40, λ 5 for ~1B; rank 1024 T_u 100 for 7B+).
def default_opt(cfg) -> OptimizerConfig:
    big = cfg.n_params() > 3e9
    return OptimizerConfig(
        name="coap-adamw",
        learning_rate=1e-2,
        rank=1024 if big else 512,
        t_update=100 if big else 40,
        lam=1 if big else 5,
        grad_clip=1.0,
    )


def generic_state_specs(tree, mesh):
    """Optimizer-state shardings (ZeRO-ish): largest dim over 'data',
    next over 'model' when divisible; small/1-D leaves replicated."""

    def one(x):
        if not hasattr(x, "shape") or len(x.shape) < 2:
            return P()
        spec = [None] * len(x.shape)
        order = sorted(range(len(x.shape)), key=lambda i: -x.shape[i])
        axes = ["data", "model"] if "data" in mesh.axis_names else ["model"]
        for dim_idx in order:
            if not axes:
                break
            ax = axes[0]
            if (
                x.shape[dim_idx] % mesh.shape[ax] == 0
                and x.shape[dim_idx] >= 2 * mesh.shape[ax]
            ):
                spec[dim_idx] = ax
                axes.pop(0)
        return P(*spec)

    return jax.tree_util.tree_map(one, tree)


def _named(mesh, tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               optimizer: str = "coap-adamw", rules=shd.PARAM_RULES,
               extra_opt: Optional[dict] = None,
               arch_overrides: Optional[dict] = None,
               grad_accum_override: Optional[int] = None,
               plan=None):
    """Returns (step_fn, in_shardings, abstract_args, mesh, meta)."""
    import dataclasses as _dc
    cfg = get_config(arch)
    if arch_overrides:
        cfg = _dc.replace(cfg, **arch_overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    meta = {"arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "optimizer": optimizer, "kind": shape.kind}

    batch_abs = input_specs(cfg, shape)
    batch_spec = shd.batch_specs(batch_abs, mesh,
                                 seq_shard=shape.global_batch == 1)

    if shape.kind == "train":
        ocfg = default_opt(cfg)
        ocfg.name = optimizer
        for k, v in (extra_opt or {}).items():
            setattr(ocfg, k, v)
        if plan is not None:
            # Budget-planned cell: the coap-plan/v1 artifact owns rules,
            # layout and per-bucket knobs; run-level knobs stay on ocfg.
            ocfg.plan = plan
            meta["plan_codec"] = plan.codec
            meta["plan_budget_bytes"] = plan.budget_bytes
        tx = make_optimizer(ocfg)
        state_abs = abstract_train_state(model, tx)
        pspecs = model.param_specs(mesh, rules)
        ospecs = generic_state_specs(state_abs.opt_state, mesh)
        state_spec = TrainState(step=P(), params=pspecs, opt_state=ospecs)
        # microbatch accumulation: big models can't hold a 1M-token
        # activation working set; production runs accumulate. Recorded in
        # the artifact so the roofline is per *full* step.
        n = cfg.n_params()
        grad_accum = 16 if n > 5e10 else (4 if n > 4e9 else 1)
        if grad_accum_override:
            grad_accum = grad_accum_override
        meta["grad_accum"] = grad_accum
        step = make_train_step(model, tx, grad_accum=grad_accum)
        in_shardings = (_named(mesh, state_spec), _named(mesh, batch_spec))
        args = (state_abs, batch_abs)
        if plan is not None:
            # Describe the PLANNED knobs, not default_opt's: t_update feeds
            # the roofline's refresh amortization, rank the artifact reader.
            meta["rank"] = sorted({
                b.spec.rank for b in plan.buckets if b.kind == "project"
            })
            meta["t_update"] = plan.globals_.t_update
        else:
            meta["rank"] = ocfg.rank
            meta["t_update"] = ocfg.t_update
        return step, in_shardings, args, mesh, meta

    pspecs = model.param_specs(mesh, rules)
    params_abs = model.abstract_params()
    if shape.kind == "prefill":
        def prefill_step(params, batch):
            logits, _, _ = model.logits(params, batch)
            return logits[:, -1:]  # serving returns last-token logits

        in_shardings = (_named(mesh, pspecs), _named(mesh, batch_spec))
        return prefill_step, in_shardings, (params_abs, batch_abs), mesh, meta

    # decode: one token against a seq_len-deep cache.
    # Serving layout: decode is weight-read-bound, so expert d_model shards
    # over 'data' (PARAM_RULES_SERVE) unlike the train layout.
    if rules is shd.PARAM_RULES:
        rules = shd.PARAM_RULES_SERVE
        pspecs = model.param_specs(mesh, rules)
    b = shape.global_batch
    cache_abs = model.cache_shapes(b, shape.seq_len)
    cspecs = model.cache_specs(mesh, b)

    def serve_step(params, caches, batch):
        return model.decode_step(params, caches, batch)

    in_shardings = (
        _named(mesh, pspecs), _named(mesh, cspecs), _named(mesh, batch_spec)
    )
    return serve_step, in_shardings, (params_abs, cache_abs, batch_abs), mesh, meta


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             optimizer: str = "coap-adamw", tag: str = "",
             rules=shd.PARAM_RULES, extra_opt: Optional[dict] = None,
             save: bool = True, arch_overrides: Optional[dict] = None,
             grad_accum_override: Optional[int] = None, plan=None,
             health_journal: Optional[str] = None) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = supports_shape(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    out_name = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    if not ok:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "skipped", "reason": why}
        _save(out_name, rec, save)
        return rec

    t0 = time.time()
    plan_rec = None
    if plan is not None and shape.kind == "train":
        # Exactness gate BEFORE any compile: the plan's predicted bytes
        # must equal accounting.abstract_state_bytes of the optimizer the
        # plan actually constructs (eval_shape — no allocation). A
        # mismatch fails the cell; a drifted byte model must never launch.
        from repro import plan as plan_mod

        try:
            vrep = plan_mod.verify(
                plan, build_model(cfg).abstract_params(),
                learning_rate=default_opt(cfg).learning_rate,
            )
        except plan_mod.PlanMismatchError as e:
            rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                   "status": "error",
                   "error": f"PlanMismatchError: {e}"}
            _save(out_name, rec, save)
            return rec
        plan_rec = {
            "predicted_state_bytes": vrep["predicted_total"],
            "accounted_state_bytes": vrep["accounted_total"],
            "match": vrep["match"],
            "eqn6_fallback_buckets_predicted": vrep["eqn6_fallback_buckets"],
        }

    step, in_shardings, args, mesh, meta = build_cell(
        arch, shape_name, multi_pod, optimizer, rules, extra_opt,
        arch_overrides, grad_accum_override, plan,
    )
    if arch_overrides:
        meta["arch_overrides"] = {k: str(v) for k, v in arch_overrides.items()}
    try:
        from repro.kernels import ops as kops

        kops.reset_eqn6_fallbacks()
        with jax.set_mesh(mesh):
            jitted = jax.jit(step, in_shardings=in_shardings)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
        analysis = hlo_analysis.analyze(hlo, n_devices=len(mesh.devices.flat))
        rec = dict(meta)
        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "n_devices": int(len(mesh.devices.flat)),
            # call-graph cost model (scan bodies x trip count; see
            # hlo_analysis.py) — xla_* fields keep XLA's single-pass
            # aggregate for reference.
            "flops_per_device": analysis["flops"],
            "flops_cond_per_device": analysis["flops_cond"],
            "bytes_per_device": analysis["hbm_bytes"],
            "bytes_cond_per_device": analysis["hbm_bytes_cond"],
            "collective_bytes": {
                "steady": analysis["collective_bytes"],
                "conditional": analysis["collective_bytes_cond"],
                "by_op": analysis["collective_by_op"],
                "by_op_cond": analysis["collective_by_op_cond"],
            },
            "xla_flops": cost.get("flops", 0.0),
            "xla_bytes": cost.get("bytes accessed", 0.0),
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "code_bytes": mem.generated_code_size_in_bytes,
            },
            "hlo_lines": hlo.count("\n"),
            "n_params": cfg.n_params(),
            "n_active_params": cfg.n_active_params(),
            "seq_len": shape.seq_len,
            "global_batch": shape.global_batch,
            # Counted fused-Eqn-6 fallback telemetry (per traced (m, n, r),
            # kernels/ops): plans that land a bucket on the slow unfused
            # refresh are visible here, not just as a one-shot warning.
            "eqn6_fallbacks": _live_eqn6_fallbacks(),
            # Process-wide obs registry snapshot (counters + gauges) —
            # anything any subsystem counted while building this cell.
            "registry": _registry_snapshot(),
        })
        if plan_rec is not None:
            rec["plan"] = plan_rec
        if health_journal:
            # Embed the analyzed verdicts of a prior run's health journal
            # so the dryrun artifact carries BOTH the predicted cost of
            # this cell and the observed numerics of the run it models.
            from repro.obs.health import analyze_journal

            rec["health"] = analyze_journal(health_journal).to_dict()
    except Exception as e:  # noqa: BLE001 — a failed cell is a bug report
        rec = dict(meta)
        rec.update({"status": "error", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:]})
    _save(out_name, rec, save)
    return rec


def _live_eqn6_fallbacks() -> dict:
    # THE telemetry formatter (shared with repro.plan.validate) — one
    # definition of the '(m, n, r)' artifact key shape.
    from repro.plan.validate import live_eqn6_fallbacks

    return live_eqn6_fallbacks()


def _registry_snapshot() -> dict:
    from repro.obs.registry import get_registry

    return get_registry().snapshot()


def _save(name: str, rec: dict, save: bool):
    if not save:
        return
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    with open(os.path.join(ARTIFACT_DIR, name + ".json"), "w") as f:
        json.dump(rec, f, indent=1)


OPTIMIZED_OVERRIDES = {
    # Beyond-paper performance defaults (EXPERIMENTS.md §Perf): flash-kernel
    # attention, shard_map local-EP MoE dispatch, absorbed MLA decode,
    # pure-bf16 elementwise.
    "attn_impl": "flash",
    "bf16_elementwise": True,
}


def optimized_overrides(arch: str) -> dict:
    cfg = get_config(arch)
    out = dict(OPTIMIZED_OVERRIDES)
    if cfg.n_experts:
        out["moe_impl"] = "local_ep"
    if cfg.mla:
        out["mla_absorbed_decode"] = True
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--optimizer", default="coap-adamw")
    ap.add_argument("--plan", default="",
                    help="coap-plan/v1 artifact: drive the train cells from "
                         "the planned knobs and cross-check predicted vs "
                         "accounted state bytes before compiling")
    ap.add_argument("--health", default="",
                    help="health.jsonl journal from a prior run: embed its "
                         "analyzed coap-health/v1 verdicts in each cell "
                         "artifact")
    ap.add_argument("--tag", default="")
    ap.add_argument("--optimized", action="store_true",
                    help="apply the §Perf beyond-paper overrides")
    ap.add_argument("--all", action="store_true",
                    help="every assigned arch x shape on the chosen mesh(es)")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    if args.optimized and not args.tag:
        args.tag = "opt"
    plan = None
    if args.plan:
        from repro.plan.artifact import load_plan

        plan = load_plan(args.plan)
        if not args.tag:
            args.tag = "plan"
        if plan.arch and not args.arch:
            args.arch = plan.arch

    archs = ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results = []
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                mesh_name = "2x16x16" if mp else "16x16"
                out = f"{arch}__{shape}__{mesh_name}" + (
                    f"__{args.tag}" if args.tag else "")
                path = os.path.join(ARTIFACT_DIR, out + ".json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        rec = json.load(f)
                    if rec.get("status") in ("ok", "skipped"):
                        print(f"[cached] {out}: {rec['status']}")
                        results.append(rec)
                        continue
                t0 = time.time()
                overrides = optimized_overrides(arch) if args.optimized else None
                if plan is not None and plan.arch and plan.arch != arch:
                    print(f"[skip] {out}: plan is for {plan.arch}")
                    continue
                rec = run_cell(arch, shape, mp, args.optimizer, args.tag,
                               arch_overrides=overrides, plan=plan,
                               health_journal=args.health or None)
                dt = time.time() - t0
                status = rec["status"]
                extra = rec.get("reason", rec.get("error", ""))[:90]
                print(f"[{dt:6.1f}s] {out}: {status} {extra}", flush=True)
                results.append(rec)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\n== dry-run summary: {n_ok} ok, {n_skip} skipped, {n_err} errors ==")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

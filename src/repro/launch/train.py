"""Production training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --optimizer coap-adamw --steps 200 --smoke            # CPU-size run

  ... --watch --devices 8 --hbm-per-device 40GB \
      --shrink-to 4 --shrink-at 100                # elastic supervisor

On a real pod every host runs this same script (SPMD); here the --smoke flag
selects the reduced config so the full loop (data pipeline, checkpointing,
straggler watchdog, heartbeats, metrics) is exercised end-to-end on CPU.

``--watch`` runs the preemption-native elastic supervisor
(``train/elastic.py``): each attempt replans against the current topology
(``plan.solve_for_topology``), restores the newest checkpoint that passes
its crc32 integrity checks, migrates the optimizer state into the new
plan's layout (``stacked_state.migrate``) if the plan changed, and resumes.
Restart policy is a sliding crash budget (``--max-crashes`` per
``--crash-window`` seconds) plus exponential backoff with seeded jitter.
``--inject-kills`` / ``--inject-torn`` / ``--inject-slow`` drive the seeded
fault injector (``train/faults.py``) through the REAL supervise → kill →
replan → relaunch path, so the failure handling is exercised, not assumed.
"""
from __future__ import annotations

import argparse
import json
import os

from repro.configs import get_config, get_smoke
from repro.core.api import OptimizerConfig, make_optimizer
from repro.data.synthetic import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.optim import warmup_cosine_schedule
from repro.train.fault_tolerance import run_with_restart
from repro.train.loop import TrainLoop, TrainLoopConfig


def _watch(args, cfg, model, data):
    """Elastic supervisor mode (see train/elastic.py). With ``--process``
    the worker is a SPAWNED process (``launch/worker.py``) the supervisor
    can really SIGKILL, supervised purely through the heartbeat file."""
    from repro.launch.plan import parse_budget
    from repro.train.elastic import (
        ElasticConfig,
        ElasticSupervisor,
        ProcessSupervisor,
        Topology,
    )
    from repro.train.faults import FaultInjector, FaultSchedule

    hbm = parse_budget(args.hbm_per_device)
    if hbm is None:
        raise SystemExit("--watch needs an explicit --hbm-per-device budget")
    topology = [Topology(args.devices, hbm)]
    if args.shrink_to:
        topology.append(
            Topology(args.shrink_to, hbm, from_step=args.shrink_at)
        )
    injector = None
    if (args.inject_kills or args.inject_torn or args.inject_slow
            or args.inject_notices):
        sched = FaultSchedule.generate(
            seed=args.fault_seed, total_steps=args.steps,
            n_kills=args.inject_kills, n_torn=args.inject_torn,
            n_slow=args.inject_slow, n_notices=args.inject_notices,
            notice_deadline_s=args.notice_deadline,
        )
        print(f"[watch] fault schedule: {sched}")
        injector = FaultInjector(sched, seed=args.fault_seed)

    ecfg = ElasticConfig(
        ckpt_dir=args.ckpt_dir,
        total_steps=args.steps,
        topology=tuple(topology),
        solve_kw=dict(min_dim=16 if args.smoke else 128,
                      t_update=args.t_update, lam=args.lam),
        ckpt_every=args.ckpt_every,
        log_every=10,
        metrics_path=args.metrics,
        heartbeat_path=os.path.join(args.ckpt_dir, "heartbeat.json"),
        grad_accum=args.grad_accum,
        max_crashes=args.max_crashes,
        crash_window_s=args.crash_window,
        backoff_base=args.backoff_base,
        backoff_cap=args.backoff_cap,
        seed=args.fault_seed,
        heartbeat_interval_s=args.heartbeat_interval,
        heartbeat_timeout_s=args.heartbeat_timeout,
        resume_horizon_steps=args.resume_horizon,
        fleet_dir=args.fleet_dir or None,
        host_id=args.host_id,
    )

    if args.process:
        spec = dict(
            arch=args.arch, smoke=bool(args.smoke),
            optimizer=args.optimizer, lr=args.lr,
            batch=args.batch, seq=args.seq,
        )
        psup = ProcessSupervisor(spec, ecfg, fault_injector=injector)
        done = psup.run()
        for ev in psup.events:
            print(f"[watch] {ev}")
        print(f"done at step {done.get('step')}; "
              f"loss={done.get('loss'):.4f}; "
              f"ce_floor={data.ce_floor():.4f}")
        return

    sup = ElasticSupervisor(
        model,
        lambda step, host: data.batch(step, args.batch, args.seq, host),
        ecfg,
        ocfg=OptimizerConfig(name=args.optimizer, learning_rate=args.lr),
        fault_injector=injector,
    )
    state = sup.run()
    for ev in sup.events:
        print(f"[watch] {ev}")
    if sup.last_resume:
        print(f"[watch] last resume: {json.dumps(sup.last_resume)}")
    print(f"done at step {int(state.step)}; ce_floor={data.ce_floor():.4f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--optimizer", default="coap-adamw")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--rank", type=int, default=16)
    ap.add_argument("--t-update", type=int, default=40)
    ap.add_argument("--lam", type=int, default=5)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU)")
    ap.add_argument("--ckpt-dir", default="artifacts/train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--metrics", default="artifacts/train_metrics.jsonl")
    ap.add_argument("--max-restarts", type=int, default=3)
    # -- elastic supervisor mode -------------------------------------------
    ap.add_argument("--watch", action="store_true",
                    help="elastic supervisor: replan/migrate/resume on crash")
    ap.add_argument("--devices", type=int, default=1,
                    help="[watch] initial device count")
    ap.add_argument("--hbm-per-device", default="auto",
                    help="[watch] per-device HBM budget, e.g. 40GB / 512MiB")
    ap.add_argument("--shrink-to", type=int, default=0,
                    help="[watch] device count after --shrink-at (0 = never)")
    ap.add_argument("--shrink-at", type=int, default=0,
                    help="[watch] step at which the topology shrinks")
    ap.add_argument("--inject-kills", type=int, default=0,
                    help="[watch] seeded injected preemptions")
    ap.add_argument("--inject-torn", type=int, default=0,
                    help="[watch] seeded torn checkpoint writes")
    ap.add_argument("--inject-slow", type=int, default=0,
                    help="[watch] seeded straggler steps")
    ap.add_argument("--inject-notices", type=int, default=0,
                    help="[watch] seeded preemption NOTICES (drain before "
                         "the kill; requires --process or notice polling)")
    ap.add_argument("--notice-deadline", type=float, default=5.0,
                    help="[watch] seconds of warning a notice gives")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--process", action="store_true",
                    help="[watch] out-of-process workers: spawn "
                         "launch/worker.py per attempt, supervise via the "
                         "heartbeat file, SIGKILL for real")
    ap.add_argument("--heartbeat-interval", type=float, default=0.0,
                    help="[watch] worker-side heartbeat refresher period "
                         "(0 = beat only at step boundaries)")
    ap.add_argument("--heartbeat-timeout", type=float, default=300.0,
                    help="[watch] heartbeat age after which the worker "
                         "reads as stale")
    ap.add_argument("--resume-horizon", type=int, default=0,
                    help="[watch] >0: resume-latency-aware replans, "
                         "amortizing migrate+recompile over this many steps")
    ap.add_argument("--fleet-dir", default="",
                    help="[watch] shared dir for multi-supervisor plan "
                         "consensus (train/fleet.py)")
    ap.add_argument("--host-id", default="host-0",
                    help="[watch] this supervisor's fleet member id")
    ap.add_argument("--max-crashes", type=int, default=10,
                    help="[watch] crash budget: N crashes per window")
    ap.add_argument("--crash-window", type=float, default=600.0,
                    help="[watch] crash-budget window, seconds")
    ap.add_argument("--backoff-base", type=float, default=1.0,
                    help="[watch] restart backoff base, seconds (0 = none)")
    ap.add_argument("--backoff-cap", type=float, default=30.0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    data = SyntheticLM(vocab=cfg.vocab_size, order=2, noise=0.1)

    if args.watch:
        _watch(args, cfg, model, data)
        return

    lr = warmup_cosine_schedule(args.lr, max(10, args.steps // 20), args.steps)
    tx = make_optimizer(OptimizerConfig(
        name=args.optimizer, learning_rate=lr, rank=args.rank,
        t_update=args.t_update, lam=args.lam,
        min_dim=16 if args.smoke else 128, weight_decay=0.0,
    ))
    loop_cfg = TrainLoopConfig(
        total_steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, metrics_path=args.metrics,
        heartbeat_path=os.path.join(args.ckpt_dir, "heartbeat.json"),
        grad_accum=args.grad_accum, log_every=10,
    )

    def attempt(i):
        if i:
            print(f"[restart {i}] resuming from newest checkpoint")
        loop = TrainLoop(
            model, tx,
            lambda step, host: data.batch(step, args.batch, args.seq, host),
            loop_cfg,
        )
        return loop.run()

    state = run_with_restart(attempt, max_restarts=args.max_restarts,
                             on_restart=lambda i, e: print(f"crash: {e}"))
    print(f"done at step {int(state.step)}; "
          f"ce_floor={data.ce_floor():.4f}")


if __name__ == "__main__":
    main()

"""Out-of-process elastic worker: ONE attempt of the replan → migrate →
resume loop, run as its own OS process so the supervisor can really
``SIGKILL`` it (``train/elastic.ProcessSupervisor`` is the parent).

  PYTHONPATH=src python -m repro.launch.worker --spec <ckpt_dir>/worker_spec.json

The spec file carries the model/data recipe plus the serialized
``ElasticConfig`` — everything the worker needs lives in the checkpoint
directory, the one piece of shared state a preemptible fleet already has.
The attempt index arrives via ``REPRO_WORKER_ATTEMPT`` (set by the
supervisor at spawn).

Exit protocol (the supervisor never *trusts* exit codes for liveness —
death is declared on heartbeat evidence alone — but cooperative exits
carry meaning):

  * ``0``  — run complete; ``DONE.json`` written atomically next to the
    spec with the final step and loss.
  * ``75`` (``EXIT_DRAINED``, EX_TEMPFAIL) — a preemption notice was
    honored: checkpoint saved at the current step, ack written, leaving
    before the deadline. The supervisor relaunches immediately without
    charging the crash budget.
  * anything else — crash; the supervisor's crash budget + backoff apply.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", required=True,
                    help="worker_spec.json written by ProcessSupervisor")
    args = ap.parse_args(argv)

    with open(args.spec) as f:
        spec = json.load(f)
    attempt = int(os.environ.get("REPRO_WORKER_ATTEMPT", "0"))

    # Import after arg parsing so --help stays instant.
    import contextlib

    from repro.configs import get_config, get_smoke
    from repro.core.api import OptimizerConfig
    from repro.data.synthetic import SyntheticLM
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.model import build_model
    from repro.train.elastic import (
        EXIT_DRAINED,
        ElasticSupervisor,
        elastic_config_from_dict,
    )
    from repro.obs.registry import get_registry
    from repro.obs.trace import configure as trace_configure
    from repro.obs.trace import get_tracer
    from repro.train.fault_tolerance import DrainPreemption, Heartbeat

    enable_compile_cache()
    ecfg = elastic_config_from_dict(spec["elastic"])
    if ecfg.trace_path:
        trace_configure(ecfg.trace_path, host=ecfg.host_id)
    tracer = get_tracer()
    reg = get_registry()
    reg.set_phase("boot")

    # Liveness = process-liveness for the ENTIRE worker lifetime: the
    # refresher must outlive run_attempt (which runs its own) because the
    # model build before it and the final-loss compile + DONE write after
    # it are long non-stepping phases too — a stale-kill there would
    # declare a healthy worker dead mid-completion.
    hb_guard = contextlib.nullcontext()
    if ecfg.heartbeat_path and ecfg.heartbeat_interval_s > 0:
        hb_guard = Heartbeat(
            ecfg.heartbeat_path, timeout=ecfg.heartbeat_timeout_s
        ).auto(ecfg.heartbeat_interval_s)

    arch = spec.get("arch", "tinyllama-1.1b")
    cfg = get_smoke(arch) if spec.get("smoke", True) else get_config(arch)
    with tracer.span("worker/build", attempt=attempt, arch=arch):
        model = build_model(cfg)
    data = SyntheticLM(
        vocab=cfg.vocab_size,
        order=int(spec.get("data_order", 2)),
        noise=float(spec.get("data_noise", 0.1)),
    )
    batch = int(spec.get("batch", 8))
    seq = int(spec.get("seq", 64))

    sup = ElasticSupervisor(
        model,
        lambda step, host: data.batch(step, batch, seq, host),
        ecfg,
        ocfg=OptimizerConfig(
            name=spec.get("optimizer", "coap-adamw"),
            learning_rate=float(spec.get("lr", 3e-3)),
        ),
        # Injected faults that belong IN the worker (torn writes,
        # straggler slowdowns) could be plumbed here; process-level kills
        # and notices are the parent's job.
        fault_injector=None,
    )
    with hb_guard:
        try:
            state = sup.run_attempt(attempt)
        except DrainPreemption:
            return EXIT_DRAINED

        reg.set_phase("final_eval")
        with tracer.span("worker/final_eval", attempt=attempt):
            final_loss, _ = model.loss(
                state.params, data.batch(ecfg.total_steps + 1, batch, seq, 0)
            )
        done_path = os.path.join(ecfg.ckpt_dir, "DONE.json")
        tmp = f"{done_path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(
                {"step": int(state.step), "loss": float(final_loss),
                 "attempt": attempt}, f,
            )
        os.replace(tmp, done_path)
    return 0


if __name__ == "__main__":
    sys.exit(main())

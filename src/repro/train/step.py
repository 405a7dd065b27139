"""Train-step factory: loss → grads → optimizer, with microbatch gradient
accumulation and optional cross-pod projected-gradient compression."""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.optim import apply_updates
from repro.train.train_state import TrainState


def make_train_step(
    model,
    tx,
    grad_accum: int = 1,
    donate: bool = True,
) -> Callable:
    """Returns step(state, batch) -> (state, metrics).

    grad_accum > 1 splits the per-device batch into microbatches and
    accumulates gradients through a lax.scan (bounds activation memory; the
    standard remat+accum combination for the train_4k cells).
    """

    def loss_fn(params, batch):
        # The backward pass of these operations is named
        # "transpose(jvp(model))/..." in the compiled step's op_name.
        with jax.named_scope("model"):
            loss, metrics = model.loss(params, batch)
        return loss, metrics

    def compute_grads(params, batch):
        if grad_accum == 1:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
            return loss, metrics, grads

        def split(x):
            b = x.shape[0]
            if x.ndim >= 2 and x.shape[0] == 3:  # mrope positions (3,B,T)
                return jnp.moveaxis(
                    x.reshape(3, grad_accum, -1, *x.shape[2:]), 1, 0
                )
            return x.reshape(grad_accum, b // grad_accum, *x.shape[1:])

        micro = jax.tree_util.tree_map(split, batch)

        def body(carry, mb):
            acc, loss_sum = carry
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mb
            )
            acc = jax.tree_util.tree_map(jnp.add, acc, grads)
            return (acc, loss_sum + loss), None

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        (gsum, loss_sum), _ = jax.lax.scan(
            body, (zeros, jnp.zeros([], jnp.float32)), micro
        )
        grads = jax.tree_util.tree_map(lambda g: g / grad_accum, gsum)
        loss = loss_sum / grad_accum
        return loss, {"ce": loss}, grads

    def step(state: TrainState, batch) -> tuple:
        loss, metrics, grads = compute_grads(state.params, batch)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, state.opt_state, state.params)
            params = apply_updates(state.params, updates)
        with jax.named_scope("step_metrics"):
            gnorm = jnp.sqrt(
                sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree_util.tree_leaves(grads)
                )
            )
            # CEU (paper Fig 3): Σ‖ΔW‖₁ of the applied update
            ceu = sum(
                jnp.sum(jnp.abs(u.astype(jnp.float32)))
                for u in jax.tree_util.tree_leaves(updates)
            )
        out_metrics = {"loss": loss, "grad_norm": gnorm, "ceu": ceu}
        for k, v in metrics.items():
            out_metrics.setdefault(k, v)
        return (
            TrainState(step=state.step + 1, params=params, opt_state=opt_state),
            out_metrics,
        )

    return step


def make_eval_step(model) -> Callable:
    def eval_step(params, batch) -> Dict[str, jnp.ndarray]:
        loss, metrics = model.loss(params, batch)
        return {"loss": loss, "ppl": jnp.exp(metrics["ce"]), **metrics}

    return eval_step

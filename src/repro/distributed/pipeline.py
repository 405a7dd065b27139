"""GPipe-style pipeline parallelism over a mesh axis (optional mode).

For latency-bound cross-pod deployments the `pod` axis can run as a
pipeline instead of pure DP: layers are split into `n_stages` contiguous
groups, microbatches stream through stages, and activations hop stage→stage
with `jax.lax.ppermute`. Implemented with shard_map manual over the stage
axis; the classic GPipe schedule (fill, steady state, drain) is expressed
as a lax.fori_loop over ``n_micro + n_stages - 1`` ticks — every stage
computes on every tick (idle ticks process garbage that is masked out),
which is the standard SPMD formulation.

This module is self-contained (takes any per-stage apply function) and is
validated on an 8-host-device mesh in tests/test_pipeline.py: pipeline
output == sequential stack output, for 2 and 4 stages.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def split_stage_params(stacked_params: Any, n_stages: int):
    """(L, ...) stacked layer params -> (n_stages, L/n_stages, ...)."""

    def split(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape(n_stages, l // n_stages, *x.shape[1:])

    return jax.tree_util.tree_map(split, stacked_params)


def pipeline_apply(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params: Any,  # pytree with leading (n_stages, ...) axis
    x: jnp.ndarray,  # (n_micro, micro_batch, ...) microbatched input
    *,
    mesh,
    axis: str = "pod",
) -> jnp.ndarray:
    """Runs x through n_stages sequential stages living on `axis`.

    stage_fn(params_for_stage, h) -> h  applies one stage's layer group.
    Returns (n_micro, micro_batch, ...) outputs (same layout as x).
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    assert n_micro >= 1

    def per_stage(params_s, x_all):
        # params_s: this stage's params; shard_map leaves the manual axis as
        # a local size-1 leading dim — strip it.
        params_s = jax.tree_util.tree_map(lambda v: v[0], params_s)
        # x_all: full (n_micro, mb, ...) input, replicated; only stage 0
        # reads it.
        stage = jax.lax.axis_index(axis)
        mb_shape = x_all.shape[1:]
        n_ticks = n_micro + n_stages - 1

        def tick(t, carry):
            buf, outputs = carry
            # stage 0 ingests microbatch t (or garbage past the end)
            idx = jnp.minimum(t, n_micro - 1)
            fresh = jax.lax.dynamic_index_in_dim(x_all, idx, 0, False)
            h_in = jnp.where(stage == 0, fresh, buf)
            h_out = stage_fn(params_s, h_in)
            # last stage emits microbatch (t - n_stages + 1)
            out_idx = t - (n_stages - 1)
            write = (stage == n_stages - 1) & (out_idx >= 0)
            safe_idx = jnp.clip(out_idx, 0, n_micro - 1)
            cur = jax.lax.dynamic_index_in_dim(outputs, safe_idx, 0, False)
            upd = jnp.where(write, h_out, cur)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, upd, safe_idx, 0
            )
            # shift activations to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(h_out, axis, perm)
            return buf, outputs

        buf0 = jnp.zeros(mb_shape, x_all.dtype)
        outs0 = jnp.zeros((n_micro,) + mb_shape, x_all.dtype)
        _, outputs = jax.lax.fori_loop(0, n_ticks, tick, (buf0, outs0))
        # outputs live on the last stage; broadcast so out_specs can be P()
        outputs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs)),
            axis,
        )
        return outputs

    # Manual over the whole mesh (JAX requires specs to resolve every
    # axis); non-pipeline axes are replicated, every shard computes the
    # same schedule.
    in_specs = (P(axis), P())
    out_specs = P()
    return jax.shard_map(
        per_stage, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(stage_params, x)

"""Fused RMSNorm kernel — the per-token normalization on the serving path.

Grid over row-tiles of the flattened (tokens, d_model) activations; each
program normalizes ``bm`` rows in VMEM (reduce + rsqrt + scale in one pass,
fp32 math, input-dtype output). d_model up to 8192 fits comfortably:
bm=256 rows × 8192 × 4B = 8MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, s_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "interpret", "bm"))
def rmsnorm_pallas(x, scale, eps=1e-6, interpret=False, bm=256):
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    bm_eff = min(bm, rows)
    pad = (-rows) % bm_eff
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    grid = (x2.shape[0] // bm_eff,)
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        name="rmsnorm_pallas",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_eff, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bm_eff, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
    )(x2, scale)
    return out[:rows].reshape(orig_shape)

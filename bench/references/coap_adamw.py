"""Plain reference of AdamW with COAP (Algorithm 1 of arXiv:2412.00071).

Written from the paper and the optimizer's stated settings, in float32 with
float32 products; nothing is taken from the program under test.

The settings (a traffic file's ``optimizer`` entry) are those of the cells:
gradients clipped to a global norm, then per matrix either

* projected, where the smaller side is at least ``min_dim``, the rank
  ``min(rank, smaller side)`` is below it, and the path names no embedding,
  norm, scale or bias: the matrix is taken with its larger side first
  (transposed where needed), ``P`` (n, r) starts as a Gaussian with
  variance ``1/r`` and is set at the first step by the low-cost SVD of
  Eqn 7 (``QR(G P)``, then the right singular vectors of ``Qᵀ G``);
  Adam's moments live on ``G P`` and the update is ``Δ Pᵀ``;
* or dense Adam on the whole matrix.

``P`` is refreshed on Algorithm 1's schedule, each projected matrix at
its own phase (the optimizer's ``phases``, a setting of the cell): at the
step whose 0-based count ``c`` has ``(c + phase) % T_u == 0``, by the low-
cost SVD of Eqn 7 where also ``(c + phase) % (λ T_u) == 0`` (and for every
matrix at ``c == 0``), else by ``eqn6_steps`` steps of SGD at ``eqn6_lr``
on Eqn 6's objective, ``MSE(G P Pᵀ, G) · (1 − CosSim(M Pᵀ, G))`` with the
row-wise cosine averaged over the rows and ``M`` the stored first moment,
differentiated by ``jax.grad``. The new ``P`` projects that step's
gradient; the moments are not carried into the new subspace.

The first ``P`` is drawn as the optimizer draws it: ``key(opt_seed)``
folded with the matrix's index in the flattened parameter tree. That is
the optimizer's seed, a setting of the cell, not a value the program made.

With ``quantize`` the moments are stored as int8 with absmax scales: the
projected moments per row in blocks of ``block`` along the rank, the dense
ones in blocks of ``block`` of the flattened array; the bias-corrected
step is clipped to ``±delta_clip`` before it is applied.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
EXCLUDE = (r"embed", r"norm", r"scale", r"bias", r"\bpos\b")


def path_of(key_path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in key_path)


def classify(path: str, shape, rank: int, min_dim: int):
    """("project", transpose, r) or ("dense", False, 0)."""
    if any(re.search(p, path.lower()) for p in EXCLUDE) or len(shape) < 2:
        return ("dense", False, 0)
    m, n = shape[-2], shape[-1]
    r = min(rank, m, n)
    if min(m, n) < min_dim or r >= min(m, n):
        return ("dense", False, 0)
    return ("project", m < n, r)


def leaf_kinds(params, opt):
    """[(path, shape, kind)] in flattened-tree order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return [(path_of(kp), tuple(x.shape),
             classify(path_of(kp), x.shape, opt["rank"], opt["min_dim"]))
            for kp, x in flat]


# ---------------------------------------------------------------- int8 codec
def _absmax_codes(blocks):
    scale = jnp.max(jnp.abs(blocks), axis=-1) / 127.0
    inv = jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    q = jnp.clip(jnp.round(blocks * inv[..., None]), -127, 127)
    return q, scale


def roundtrip_rows(x, block):
    """Store and load along the last axis in blocks of ``block``."""
    r = x.shape[-1]
    nblk = -(-r // block)
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, nblk * block - r)])
    q, s = _absmax_codes(xp.reshape(x.shape[:-1] + (nblk, block)))
    return (q * s[..., None]).reshape(xp.shape)[..., :r]


def roundtrip_flat(x, block):
    """Store and load the flattened array in blocks of ``block``."""
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % block
    q, s = _absmax_codes(jnp.pad(flat, (0, pad)).reshape(-1, block))
    return (q * s[:, None]).reshape(-1)[: flat.shape[0]].reshape(x.shape)


# ----------------------------------------------------------------- the steps
def lowcost_svd(g, p):
    """Eqn 7: P' = right singular vectors of QR(G P)ᵀ G. g: (..., m, n)."""
    y = jnp.einsum("...mn,...nr->...mr", g, p, precision=HIGHEST)
    q, _ = jnp.linalg.qr(y)
    b = jnp.einsum("...mr,...mn->...rn", q, g, precision=HIGHEST)
    _, _, zt = jnp.linalg.svd(b, full_matrices=False)
    return jnp.swapaxes(zt, -1, -2)


def eqn6_objective(p, g, m):
    """Eqn 6 for one matrix: g (m, n), p (n, r), m (m, r)."""
    gp = jnp.einsum("mn,nr->mr", g, p, precision=HIGHEST)
    g_hat = jnp.einsum("mr,nr->mn", gp, p, precision=HIGHEST)
    m_hat = jnp.einsum("mr,nr->mn", m, p, precision=HIGHEST)
    mse = jnp.mean(jnp.square(g_hat - g))
    cos = jnp.mean(jnp.sum(m_hat * g, axis=-1)
                   / (jnp.linalg.norm(m_hat, axis=-1) * jnp.linalg.norm(g, axis=-1)))
    return mse * (1.0 - cos)


def eqn6_sgd(g, p, m, lr: float, steps: int):
    """``steps`` SGD steps on Eqn 6, each matrix of a stack in turn."""
    def one(args):
        g1, p1, m1 = args
        for _ in range(steps):
            p1 = p1 - lr * jax.grad(eqn6_objective)(p1, g1, m1)
        return p1

    def flat(x):
        return x.reshape((-1,) + x.shape[-2:])

    return jax.lax.map(one, (flat(g), flat(p), flat(m))).reshape(p.shape)


def refreshes(count: int, opt) -> tuple:
    """((path, "eqn7" | "eqn6"), ...): the projected matrices whose P is
    set at the step of 0-based ``count``."""
    t_u, period = opt["t_update"], opt["lam"] * opt["t_update"]
    out = []
    for path, phase in sorted(opt["phases"].items()):
        if count == 0 or (count + phase) % period == 0:
            out.append((path, "eqn7"))
        elif (count + phase) % t_u == 0:
            out.append((path, "eqn6"))
    return tuple(out)


def init_state(params, opt):
    """Per leaf: {"p": P0 or None, "m": zeros, "v": zeros}."""
    key = jax.random.key(opt["opt_seed"])
    state = []
    for idx, (path, shape, (kind, tr, r)) in enumerate(leaf_kinds(params, opt)):
        if kind == "project":
            m, n = (shape[-1], shape[-2]) if tr else (shape[-2], shape[-1])
            p0 = jax.random.normal(jax.random.fold_in(key, idx),
                                   shape[:-2] + (n, r), jnp.float32)
            p0 = p0 / jnp.sqrt(jnp.float32(r))
            mshape = shape[:-2] + (m, r)
        else:
            p0, mshape = None, shape
        state.append({"p": p0, "m": jnp.zeros(mshape, jnp.float32),
                      "v": jnp.zeros(mshape, jnp.float32)})
    return state


def step(params, grads, state, t, refresh: tuple, opt):
    """One optimizer step; ``t`` is the 1-based step for bias correction and
    ``refresh`` (``refreshes``) the matrices whose P is set first.
    Returns (params, state)."""
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["lr"]
    quant = opt["quantize"]
    block = opt["quant_block"]
    flat_p, tdef = jax.tree_util.tree_flatten(params)
    flat_g = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in flat_g))
    clip = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-16))
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    kinds = leaf_kinds(params, opt)
    refresh = dict(refresh)
    new_p, new_state = [], []
    for w, g, st, (path, _, (kind, tr, _)) in zip(flat_p, flat_g, state, kinds):
        g = g * clip
        if kind == "project":
            gc = jnp.swapaxes(g, -1, -2) if tr else g
            how = refresh.get(path)
            if how == "eqn7":
                p = lowcost_svd(gc, st["p"])
            elif how == "eqn6":
                p = eqn6_sgd(gc, st["p"], st["m"], opt["eqn6_lr"], opt["eqn6_steps"])
            else:
                p = st["p"]
            x = jnp.einsum("...mn,...nr->...mr", gc, p, precision=HIGHEST)
        else:
            p, x = None, g
        m = b1 * st["m"] + (1.0 - b1) * x
        v = b2 * st["v"] + (1.0 - b2) * jnp.square(x)
        delta = (m / c1) / (jnp.sqrt(v / c2) + eps)
        if quant:
            delta = jnp.clip(delta, -opt["delta_clip"], opt["delta_clip"])
            codec = roundtrip_rows if kind == "project" else roundtrip_flat
            m, v = codec(m, block), codec(v, block)
        if kind == "project":
            u = jnp.einsum("...mr,...nr->...mn", delta, p, precision=HIGHEST)
            u = jnp.swapaxes(u, -1, -2) if tr else u
        else:
            u = delta
        new_p.append(w - lr * u)
        new_state.append({"p": p, "m": m, "v": v})
    return jax.tree_util.tree_unflatten(tdef, new_p), new_state

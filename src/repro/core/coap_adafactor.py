"""Algorithm 2: Adafactor with COAP (and GaLore/Flora strategy variants).

Projected leaves hold: ``P (n,r)``, first moment ``M_proj (m,r)``, and the
*factored* second moment of the projected gradient: ``R (m,)``, ``C (r,)``
with the paper's β₂ schedule ``β₂ = 1 − t^{−γ}``. Per Algorithm 2:

    R_t = β₂R + (1−β₂)·Sum(G_proj², −1)
    C_t = β₂C + (1−β₂)·Sum(G_proj², −2)
    V̂_t = sqrt(Mean(R_t) / (R_t C_t))          # note: reciprocal-sqrt form
    ΔW_proj = β₁·M + (1−β₁)·η·V̂ ⊙ G_proj
    W ← W − ΔW_proj Pᵀ

FAITHFULNESS NOTE: Algorithm 2 as printed also contains the line
``M_t ← β₁M + (1−β₁)G_proj`` which is unit-inconsistent with the ΔW line
(it would subtract an *unscaled* gradient EMA from W). We implement the
self-consistent reading — M accumulates the scaled update, i.e.
``M_t = ΔW_proj`` (momentum-on-update, as in Adafactor-with-momentum) — and
expose ``interpretation='literal'`` for the verbatim text. The consistent
reading reproduces the paper's convergence behaviour in our small-scale
benchmarks; the literal one diverges for any η < 1, corroborating the typo
(see DESIGN.md §8).

Because learning rate is *inside* ΔW here, this transformation is terminal:
chain it with ``scale(-1)`` only (no extra lr scaling).

The update runs on the SAME bucket+phase hot-path machinery as the Adam
variant (``coap_adam.update_fn``): congruent projected leaves compute as
one stacked launch per bucket (``stacked_state=True`` additionally STORES
them pre-stacked — no gather/scatter copies), refreshes follow the shared
staggered schedule (``bucket_phases`` — the same allocation the elastic
supervisor and the cross-pod compression path derive cadence from), and a
plan's per-bucket overrides apply through ``_bucket_cfg``. Dense buckets
vmap the per-leaf Adafactor step (the factored-iff-ndim≥2 branch is a
static per-leaf property, preserved under vmap). Both storage modes share
the bucketed compute, so they stay bit-identical by construction
(``tests/test_stacked_state.py::test_stacked_adafactor_matches_per_leaf_bitwise``).

CONV NOTE. Algorithm 2 has no Tucker-2 path: every non-projected leaf —
conv ``(O,I,K1,K2)`` kernels included — takes the dense Adafactor path.
``_af_classify`` therefore maps conv specs to ``BUCKET_DENSE``, never to
the ``stacked-bucket/v2`` conv bucket class the Adam transform uses: the
adafactor layout has no conv buckets and no tail, and the v1→v2 codec bump
(which only changed where KIND_CONV leaves live under the DEFAULT
classification) does not alter its bucket assignment
(``tests/test_conv_bucketing.py::test_adafactor_layout_unaffected_by_v2``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import correlation, projector, recalibrate
from repro.core import stacked_state
from repro.core.coap_adam import (
    ProjectedAdamConfig,
    _array_loader,
    _bucket_cfg,
    _maybe_transplant,
    _refresh_p,
    bucket_phases,
)
from repro.core.projector import (
    KIND_DENSE,
    KIND_PROJECT,
    ProjectionRules,
    path_str,
)
from repro.obs import health
from repro.optim.transform import GradientTransformation, chain, scale

_EPS = 1e-30


class ProjFactorLeaf(NamedTuple):
    p: Any  # (..., n, r)
    m: Any  # (..., M, r)
    row: Any  # (..., M)
    col: Any  # (..., r)


class DenseFactorLeaf(NamedTuple):
    row: Any
    col: Any
    nu: Any  # unfactored fallback for <2-D


class ProjectedAdafactorState(NamedTuple):
    count: jnp.ndarray
    leaves: Any


@dataclasses.dataclass(frozen=True)
class ProjectedAdafactorConfig(ProjectedAdamConfig):
    gamma: float = 0.8  # β₂ decay-rate exponent
    learning_rate: float = 1e-4  # η lives inside ΔW (Algorithm 2)
    interpretation: str = "consistent"  # 'consistent' | 'literal'


def _af_classify(spec) -> str:
    """Adafactor has no conv path: everything non-projected is dense."""
    if spec.kind == KIND_PROJECT:
        return stacked_state.BUCKET_PROJECT
    return stacked_state.BUCKET_DENSE


def _af_layout(cfg, flat) -> stacked_state.StackedLayout:
    return stacked_state.layout_for_flat(
        cfg.rules.spec_for, flat, classify=_af_classify
    )


def scale_by_projected_adafactor(cfg: ProjectedAdafactorConfig) -> GradientTransformation:
    def init_fn(params):
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        key = jax.random.key(cfg.seed)
        leaves = []
        for idx, (kp, leaf) in enumerate(flat):
            spec = cfg.rules.spec_for(path_str(kp), leaf.shape)
            if spec.kind == KIND_PROJECT:
                p0 = projector.init_p(
                    jax.random.fold_in(key, idx), leaf.shape, spec, jnp.float32
                )
                msh = projector.moment_shape(leaf.shape, spec)
                leaves.append(
                    ProjFactorLeaf(
                        p=p0,
                        m=jnp.zeros(msh, jnp.float32),
                        row=jnp.zeros(msh[:-1], jnp.float32),
                        col=jnp.zeros(msh[:-2] + msh[-1:], jnp.float32),
                    )
                )
            else:
                # Dense leaves: classic Adafactor (factored iff ndim >= 2).
                if leaf.ndim >= 2:
                    leaves.append(
                        DenseFactorLeaf(
                            row=jnp.zeros(leaf.shape[:-1], jnp.float32),
                            col=jnp.zeros(leaf.shape[:-2] + leaf.shape[-1:], jnp.float32),
                            nu=jnp.zeros((1,), jnp.float32),
                        )
                    )
                else:
                    leaves.append(
                        DenseFactorLeaf(
                            row=jnp.zeros((1,), jnp.float32),
                            col=jnp.zeros((1,), jnp.float32),
                            nu=jnp.zeros(leaf.shape, jnp.float32),
                        )
                    )
        if cfg.stacked_state:
            return ProjectedAdafactorState(
                count=jnp.zeros([], jnp.int32),
                leaves=stacked_state.encode(_af_layout(cfg, flat), leaves),
            )
        return ProjectedAdafactorState(
            count=jnp.zeros([], jnp.int32),
            leaves=jax.tree_util.tree_unflatten(treedef, leaves),
        )

    def _vhat(row, col):
        """V̂ = sqrt(Mean(R)/(R C)) — the reciprocal-sqrt normalizer."""
        mean_r = jnp.mean(row, axis=-1, keepdims=True)
        denom = row[..., :, None] * col[..., None, :] + _EPS
        return jnp.sqrt(mean_r[..., None] / denom)

    def _update_proj_bucket(bcfg, leaf: ProjFactorLeaf, g, spec, count, t,
                            idx_arr, b2, phases):
        """Algorithm 2 for a stacked bucket of congruent projected leaves
        (leading (B,) axis everywhere; B == 1 for singleton buckets).
        ``bcfg`` is the bucket-effective config (plan overrides applied);
        ``phases`` staggers the refresh cadence exactly as in the Adam
        variant — same ``_refresh_p`` group dispatch, same transplant
        group structure."""
        gc = projector.to_canonical(g, spec).astype(jnp.float32)
        p_old = leaf.p

        def m_loader(sl=slice(None)):
            return leaf.m[sl].astype(jnp.float32)

        new_p, refreshed = _refresh_p(
            bcfg, spec, p_old, _array_loader(gc), m_loader, count, idx_arr,
            phases,
        )
        # Projection-health emit (obs/health): refresh-boundary metrics
        # (captured energy, Eqn-6 residual, subspace overlap) ride the
        # refresh branch that already holds G — zero extra HBM reads of G
        # on non-refresh steps, and a trace-time no-op when the monitor is
        # disabled (bit-identical compiled program).
        health.emit_refresh_matrix(
            health.bucket_label("project", g.shape[1:], g.dtype),
            lambda: gc, p_old, new_p, refreshed, count,
        )
        m = _maybe_transplant(
            bcfg, leaf.m, p_old, new_p, refreshed, phases, count
        )
        g_proj = projector.project(gc, new_p)
        g2 = jnp.square(g_proj)
        new_row = b2 * leaf.row + (1.0 - b2) * jnp.sum(g2, axis=-1)
        new_col = b2 * leaf.col + (1.0 - b2) * jnp.sum(g2, axis=-2)
        vhat = _vhat(new_row, new_col)
        if bcfg.interpretation == "literal":
            new_m = bcfg.b1 * m + (1.0 - bcfg.b1) * g_proj
            delta = bcfg.b1 * new_m + (1.0 - bcfg.b1) * bcfg.learning_rate * vhat * g_proj
        else:
            delta = bcfg.b1 * m + (1.0 - bcfg.b1) * bcfg.learning_rate * vhat * g_proj
            new_m = delta  # momentum over scaled updates (consistent units)
        upd_c = projector.backproject(delta, new_p)
        upd = projector.from_canonical(upd_c, spec) * bcfg.update_scale
        return upd.astype(g.dtype), ProjFactorLeaf(
            p=new_p, m=new_m, row=new_row, col=new_col
        )

    def _update_dense(leaf: DenseFactorLeaf, g, t, b2):
        g32 = g.astype(jnp.float32)
        g2 = jnp.square(g32) + _EPS
        if g.ndim >= 2:
            new_row = b2 * leaf.row + (1.0 - b2) * jnp.sum(g2, axis=-1)
            new_col = b2 * leaf.col + (1.0 - b2) * jnp.sum(g2, axis=-2)
            vhat = _vhat(new_row, new_col)
            upd = cfg.learning_rate * vhat * g32
            new_leaf = DenseFactorLeaf(row=new_row, col=new_col, nu=leaf.nu)
        else:
            new_nu = b2 * leaf.nu + (1.0 - b2) * g2
            upd = cfg.learning_rate * g32 / jnp.sqrt(new_nu + _EPS)
            new_leaf = DenseFactorLeaf(row=leaf.row, col=leaf.col, nu=new_nu)
        return upd.astype(g.dtype), new_leaf

    def update_fn(updates, state, params=None):
        del params
        count = state.count
        t = count + 1
        b2 = 1.0 - (t.astype(jnp.float32)) ** (-cfg.gamma)
        flat_u, treedef = jax.tree_util.tree_flatten_with_path(updates)
        n_leaves = len(flat_u)

        # THE bucket assignment (shared with the stacked-state codec, the
        # checkpoint/accounting stack and the elastic supervisor) — under
        # the adafactor classification: project buckets + dense buckets,
        # never conv, never tail (module docstring CONV NOTE).
        layout = _af_layout(cfg, flat_u)

        if cfg.stacked_state:
            prev = state.leaves
            if (
                not isinstance(prev, stacked_state.StackedLeaves)
                or prev.layout.signature() != layout.signature()
            ):
                raise ValueError(
                    "stacked adafactor state does not match the gradient "
                    "tree (rules / model structure changed since init?)"
                )
            flat_s = None
        else:
            prev = None
            flat_s = treedef.flatten_up_to(state.leaves)

        bucket_cfgs = [_bucket_cfg(cfg, info) for info in layout.buckets]
        # Per-leaf refresh phases: THE staggered allocation, shared with
        # the Adam variant and every schedule consumer.
        phase_by_bucket = bucket_phases(cfg, layout)

        new_updates = [None] * n_leaves
        new_buckets = [None] * len(layout.buckets)
        new_flat = [None] * n_leaves  # per-leaf mode only

        for bi, info in enumerate(layout.buckets):
            is_proj = info.kind == stacked_state.BUCKET_PROJECT
            bcfg = bucket_cfgs[bi]
            phases = phase_by_bucket.get(bi)
            if cfg.bucket_leaves:
                slot_groups = [tuple(range(len(info.indices)))]
            else:  # per-leaf A/B mode (stacked_state forbids this)
                slot_groups = [(k,) for k in range(len(info.indices))]
            for slots in slot_groups:
                idxs = [info.indices[k] for k in slots]
                g_stack = jnp.stack([flat_u[i][1] for i in idxs])
                if cfg.stacked_state:
                    # Hot-path win: the bucket state is ALREADY stacked —
                    # no stack copy in, no scatter copy out.
                    leaf_stack = prev.buckets[bi]
                else:
                    leaf_stack = jax.tree_util.tree_map(
                        lambda *xs: jnp.stack(xs),
                        *[flat_s[i] for i in idxs],
                    )
                if is_proj:
                    u_stack, nl_stack = _update_proj_bucket(
                        bcfg, leaf_stack, g_stack, info.spec, count, t,
                        jnp.asarray(idxs, jnp.int32), b2,
                        tuple(phases[k] for k in slots),
                    )
                else:
                    # The factored-iff-ndim>=2 branch is static per leaf
                    # shape; vmap keeps it per-element while batching the
                    # congruent bucket into one launch.
                    u_stack, nl_stack = jax.vmap(
                        lambda lf, gg: _update_dense(lf, gg, t, b2)
                    )(leaf_stack, g_stack)
                for b, i in enumerate(idxs):
                    new_updates[i] = u_stack[b]
                    if not cfg.stacked_state:
                        new_flat[i] = jax.tree_util.tree_map(
                            lambda x: x[b], nl_stack
                        )
                if cfg.stacked_state:
                    new_buckets[bi] = nl_stack

        if cfg.stacked_state:
            leaves_out = stacked_state.StackedLeaves(
                new_buckets, prev.tail, prev.layout
            )
        else:
            leaves_out = jax.tree_util.tree_unflatten(treedef, new_flat)
        return (
            jax.tree_util.tree_unflatten(treedef, new_updates),
            ProjectedAdafactorState(count=count + 1, leaves=leaves_out),
        )

    return GradientTransformation(init_fn, update_fn)


def coap_adafactor(
    learning_rate: float,
    rules: ProjectionRules,
    *,
    strategy: str = "coap",
    b1: float = 0.9,
    gamma: float = 0.8,
    t_update: int = 200,
    lam: int = 5,
    eqn6_lr: float = 0.1,
    eqn6_steps: int = 1,
    seed: int = 0,
    update_scale: float = 1.0,
    stacked_state: bool = False,
    stagger: bool = True,
    stagger_groups: int = 8,
) -> GradientTransformation:
    """Adafactor+COAP per Algorithm 2 (η inside; terminal sign flip only)."""
    cfg = ProjectedAdafactorConfig(
        rules=rules,
        strategy=strategy,
        b1=b1,
        gamma=gamma,
        t_update=t_update,
        lam=lam,
        eqn6_lr=eqn6_lr,
        eqn6_steps=eqn6_steps,
        seed=seed,
        learning_rate=learning_rate,
        update_scale=update_scale,
        stacked_state=stacked_state,
        stagger=stagger,
        stagger_groups=stagger_groups,
    )
    return chain(scale_by_projected_adafactor(cfg), scale(-1.0))

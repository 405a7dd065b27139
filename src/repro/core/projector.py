"""Projection-shape policy and canonical project/backproject primitives.

Conventions (paper §3.1): for a weight ``W ∈ R^{m×n}`` with ``m ≥ n`` the
projection is on the right: ``P ∈ R^{n×r}``, ``G_proj = G P ∈ R^{m×r}`` —
moments live on the *large* side (matches the paper's memory accounting for
LLaMA-1B, −61% at rank 512). Weights with ``m < n`` are transposed into this
canonical orientation on entry and transposed back on exit — by the jnp
paths and the refresh; the fused update kernels read such a gradient as
stored (``transposed``) and copy nothing.

All primitives operate on the **last two axes** and broadcast over leading
axes. This is how scan-over-layers models (stacked ``(L, m, n)`` weights) and
per-expert MoE weights (``(L, E, m, n)``) get a projector per layer/expert
with a single einsum — the TPU-friendly equivalent of the paper's per-layer
Python loop.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

# Param kinds decided statically at init time.
KIND_PROJECT = "project"  # last-two-axes matrix (possibly stacked) -> low-rank
KIND_CONV = "conv"  # (O, I, K1, K2) conv kernel -> Tucker-2 (core/conv.py)
KIND_DENSE = "dense"  # full-rank Adam/Adafactor

HIGHEST = jax.lax.Precision.HIGHEST


class ProjSpec(NamedTuple):
    """Static per-leaf projection decision."""

    kind: str
    transpose: bool  # swap last two axes to make m >= n
    rank: int  # effective rank r (0 for dense)
    # Conv-only Tucker-2 ranks:
    rank_o: int = 0
    rank_i: int = 0


@dataclasses.dataclass(frozen=True)
class ProjectionRules:
    """Shape/path policy for which leaves get projected and at what rank.

    Either ``rank`` (fixed, clipped to min-dim) or ``rank_ratio`` (paper's
    ``c``: r = min(m, n) / c) must be set. ``min_dim`` guards tiny matrices
    (router heads, norms reshaped as 2-D, ...) from projection — they stay on
    full-rank Adam, matching GaLore/paper practice.
    """

    rank: Optional[int] = None
    rank_ratio: Optional[float] = None
    min_dim: int = 128
    # Paths matching any of these regexes are never projected (embeddings and
    # norms by default — the paper and GaLore keep them full-rank).
    exclude_patterns: Tuple[str, ...] = (r"embed", r"norm", r"scale", r"bias", r"\bpos\b")
    # Paths matching these are always treated as conv kernels.
    conv_patterns: Tuple[str, ...] = (r"conv",)
    project_conv: bool = True

    def __post_init__(self):
        if (self.rank is None) == (self.rank_ratio is None):
            raise ValueError("set exactly one of rank / rank_ratio")

    def rank_for(self, m: int, n: int) -> int:
        small = min(m, n)
        if self.rank is not None:
            return int(min(self.rank, small))
        return max(1, int(small // self.rank_ratio))

    def spec_for(self, path: str, shape: Sequence[int]) -> ProjSpec:
        shape = tuple(int(s) for s in shape)
        lpath = path.lower()
        if any(re.search(p, lpath) for p in self.exclude_patterns):
            return ProjSpec(KIND_DENSE, False, 0)
        is_conv = any(re.search(p, lpath) for p in self.conv_patterns) or (
            len(shape) == 4 and shape[-1] <= 7 and shape[-2] <= 7 and shape[0] > 7
        )
        if is_conv:
            if not self.project_conv:
                return ProjSpec(KIND_DENSE, False, 0)
            o, i = shape[0], shape[1]
            if min(o, i) < self.min_dim:
                return ProjSpec(KIND_DENSE, False, 0)
            ratio = self.rank_ratio if self.rank_ratio is not None else None
            if ratio is not None:
                # Tucker-2: split the rank ratio across the two modes (α per
                # Algorithm 3; total state compression ≈ α).
                import math

                ro = max(1, int(o / math.sqrt(ratio)))
                ri = max(1, int(i / math.sqrt(ratio)))
            else:
                ro = min(self.rank, o)
                ri = min(self.rank, i)
            return ProjSpec(KIND_CONV, False, 0, rank_o=ro, rank_i=ri)
        if len(shape) < 2:
            return ProjSpec(KIND_DENSE, False, 0)
        m, n = shape[-2], shape[-1]
        if min(m, n) < self.min_dim:
            return ProjSpec(KIND_DENSE, False, 0)
        r = self.rank_for(m, n)
        if r >= min(m, n):
            return ProjSpec(KIND_DENSE, False, 0)
        return ProjSpec(KIND_PROJECT, m < n, r)


@dataclasses.dataclass(frozen=True)
class PlannedRules(ProjectionRules):
    """Per-path spec overrides layered over a base :class:`ProjectionRules`.

    This is how a memory plan (``repro/plan``, ``coap-plan/v1``) drives the
    optimizer: the planner decides one :class:`ProjSpec` per bucket and pins
    it here for every member path; any path without an override falls back
    to the base policy. Overrides are EXACT path matches (the planner and
    the optimizer flatten the same tree, so paths agree by construction) and
    the tuple storage keeps the rules hashable — layouts built from planned
    rules stay valid jit-static aux data.
    """

    spec_overrides: Tuple[Tuple[str, ProjSpec], ...] = ()

    def __post_init__(self):
        super().__post_init__()
        object.__setattr__(self, "_spec_map", dict(self.spec_overrides))

    def spec_for(self, path: str, shape: Sequence[int]) -> ProjSpec:
        spec = self._spec_map.get(path)
        if spec is not None:
            return spec
        return super().spec_for(path, shape)


def path_str(key_path) -> str:
    """jax tree key-path -> 'a/b/0/c' string for regex policies."""
    parts = []
    for k in key_path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def to_canonical(g: jnp.ndarray, spec: ProjSpec) -> jnp.ndarray:
    """Transpose last two axes so that m >= n."""
    if spec.transpose:
        return jnp.swapaxes(g, -1, -2)
    return g


def from_canonical(g: jnp.ndarray, spec: ProjSpec) -> jnp.ndarray:
    if spec.transpose:
        return jnp.swapaxes(g, -1, -2)
    return g


def project(g_canon: jnp.ndarray, p: jnp.ndarray) -> jnp.ndarray:
    """``G_proj = G P`` over the last two axes: (...,m,n)@(...,n,r)->(...,m,r),
    at fp32 precision like the fused kernels."""
    return jnp.einsum("...mn,...nr->...mr", g_canon, p, precision=HIGHEST)


def backproject(u_proj: jnp.ndarray, p: jnp.ndarray) -> jnp.ndarray:
    """``ΔW = ΔW_proj Pᵀ``: (...,m,r)@(...,n,r)ᵀ -> (...,m,n)."""
    return jnp.einsum("...mr,...nr->...mn", u_proj, p, precision=HIGHEST)


def reconstruct(g_canon: jnp.ndarray, p: jnp.ndarray) -> jnp.ndarray:
    """``Ĝ = G P Pᵀ`` (paper Eqn 6 reconstruction operand)."""
    return backproject(project(g_canon, p), p)


def init_p(key: jax.Array, shape: Sequence[int], spec: ProjSpec, dtype=jnp.float32):
    """Random init for P (Algorithm 1 'Randomly Initialize'): orthonormal-ish
    Gaussian N(0, 1/r), batched over leading axes."""
    shape = tuple(shape)
    lead = shape[:-2]
    m, n = shape[-2], shape[-1]
    if spec.transpose:
        m, n = n, m
    p_shape = lead + (n, spec.rank)
    return jax.random.normal(key, p_shape, dtype) / jnp.sqrt(
        jnp.asarray(spec.rank, dtype)
    )


def moment_shape(shape: Sequence[int], spec: ProjSpec) -> Tuple[int, ...]:
    shape = tuple(shape)
    lead = shape[:-2]
    m, n = shape[-2], shape[-1]
    if spec.transpose:
        m, n = n, m
    return lead + (m, spec.rank)

"""Algorithm 1: Adam with COAP — plus GaLore/Flora strategy variants.

One GradientTransformation covers the whole family because the only
difference between COAP, GaLore and Flora is the projection-refresh rule:

  * ``coap``   — every ``T_u`` steps refresh P by Eqn-6 SGD; every
                 ``λ·T_u`` steps recalibrate by Eqn-7 low-cost SVD; at t=0
                 initialize by Eqn 7 from the first gradient (Algorithm 1).
  * ``galore`` — every ``T_u`` steps recompute P as the truncated SVD of the
                 current gradient (O(mn²)).
  * ``flora``  — resample a Gaussian P every ``T_u`` steps (paper: every
                 step, T_u=1) and transplant the first moment into the new
                 subspace.

Leaves are classified statically (see ``projector.ProjectionRules``):
2-D-matrix leaves (with arbitrary leading stack axes — scan-over-layers
weights ``(L,m,n)``, per-expert weights ``(L,E,m,n)``) are projected;
conv ``(O,I,K1,K2)`` kernels take the Tucker-2 path (Algorithm 3, in
``core/conv.py``); everything else gets dense Adam. Refreshes happen inside
the jitted step under ``lax.cond`` — no host round-trips (DESIGN.md §3).

Optimizer states are fp32 by default or block-wise int8 when
``quantize=True`` (8-bit COAP / 8-bit Adam baselines, via kernels/quant8).
Projected int8 moments use the ROW-BLOCK codec (shape-preserving int8 +
per-row-block scales; kernels/ref.py) so the whole quantized step — project,
dequant, moment EMA, requant, back-project — runs as ONE fused kernel with
no fp32 M/V or Δ_proj ever materialized in HBM. Dense and conv int8 states
keep the flat (nblocks, 256) codec.

``update_fn`` batches congruent leaves: all projected, conv or dense leaves
sharing a ``(shape, spec, dtype)`` signature are stacked along a new leading
axis and updated by a single (vmapped) kernel launch — a transformer's
dozens of per-layer matrices, or a vision tower's per-block conv kernels,
become a handful of dispatches per step instead of one per leaf. Bucketing
is numerics-neutral: every code path broadcasts over leading axes, and
flora's per-leaf RNG keys fold in the ORIGINAL flat leaf index, so bucketed
and per-leaf execution produce identical bits (``bucket_leaves=False``
keeps the per-leaf loop for A/B checks).

STAGGERED REFRESH (``stagger=True``, default): the paper-faithful schedule
refreshes EVERY projected leaf at ``count % T_u == 0`` — a synchronized
QR/SVD + Eqn-6 stall across the whole tree every ``T_u`` steps (the GaLore
cost cliff the paper's cheap refresh is meant to remove). With stagger on,
each leaf gets a deterministic phase offset and refreshes when
``(count + phase) % T_u == 0`` (recalibration likewise at
``(count + phase) % (λ·T_u) == 0``), so refresh work spreads nearly
uniformly over the interval and the worst step pays ~1/U of the
synchronized cost (U = total phase groups). Semantics preserved exactly:

  * every leaf still refreshes with period ``T_u`` and recalibrates with
    period ``λ·T_u`` — only the phase differs per leaf;
  * Eqn-7 initialization at t=0 runs for ALL leaves regardless of phase
    (Algorithm 1 line 3 — the first gradient seeds every P);
  * phases are a pure function of the bucket structure
    (``stagger_phases``), so they are identical across restarts and
    identical between bucketed and per-leaf execution;
  * within a congruent bucket, leaves are partitioned into at most
    ``stagger_groups`` contiguous phase groups; on a refresh step only the
    matching group's slice runs QR/SVD/Eqn-6 (``lax.switch`` over static
    slices), and the per-step fused update stays ONE launch per bucket.

``stagger=False`` restores the synchronized schedule bit-for-bit.
Flora's per-step resample (T_u=1) degenerates to a single phase-0 group and
is unchanged; with T_u>1 its resamples stagger for free. Conv (Tucker-2)
leaves are on the SAME staggered schedule since stacked-bucket/v2: each
conv bucket's phase units are allocated by ``stagger_phases`` right after
the projected buckets' (``layout.staggerable_bucket_sizes()``), and both
Tucker factors of a phase group refresh inside one ``lax.switch`` branch
(``conv.update_conv_bucket``).

PRE-STACKED STATE (``stacked_state=True``): with per-leaf state storage the
stack/scatter round-trip at the bucket boundary is real copy traffic every
step (XLA fuses some fp32 copies into kernel operands, but never the int8
state round-trip). Setting ``stacked_state=True`` stores the optimizer
state pre-stacked along the bucket axis (``core/stacked_state.py``): the
fused kernels and the staggered ``lax.switch`` refresh consume bucket
slices directly, and only the gradient stack and update scatter — pure
bf16/fp32 copies at the kernel boundary — remain on the hot path
(``benchmarks/overhead.run_state`` quantifies the removed traffic;
``BENCH_state.json``). State-tree/param-tree congruence is recovered on
demand through the stacked-state codec (``encode``/``decode``/
``leaf_view``/``manifest_entries``), which checkpointing, accounting and
the cross-pod compression path all understand — a checkpoint written in
either mode restores into the other. ``stacked_state=False`` (the default)
keeps today's per-leaf layout bit-for-bit, and the two modes produce
bit-identical updates and states — fp32, bf16 streaming, int8 codes and
flora RNG included (``tests/test_stacked_state.py``). Conv (Tucker-2)
leaves bucket and pre-stack like everything else under the
``stacked-bucket/v2`` codec (``tests/test_conv_bucketing.py``); a custom
``classify`` can still route leaves to the per-leaf residual tail.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import conv as conv_mod
from repro.core import correlation, projector, recalibrate
from repro.core import stacked_state
from repro.core.projector import (
    KIND_CONV,
    KIND_DENSE,
    KIND_PROJECT,
    ProjSpec,
    ProjectionRules,
    path_str,
)
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.obs import health
from repro.optim.transform import (
    GradientTransformation,
    add_decayed_weights,
    chain,
    scale_by_learning_rate,
)

STRATEGIES = ("coap", "galore", "flora")


class ProjLeaf(NamedTuple):
    """Low-rank leaf state: P (…,n,r); moments on the large side (…,m,r).

    Quantized moments are shape-preserving int8 under the row-block codec:
    ``m``/``v`` stay (…,m,r) int8 and ``*_scale`` are (…,m,ceil(r/block))
    fp32 — the layout the fused q8 kernel consumes tile-locally.

    ``ef`` is the int8-collective error-feedback accumulator (fp32, moment
    shape) used by the cross-pod ``sync_codes`` path; ``None`` (an empty
    pytree slot — zero bytes, zero checkpoint entries) unless the config
    enables ``sync_codes``. Single-pod updates carry it through untouched."""

    p: Any
    m: Any
    v: Any
    m_scale: Any  # codec scales; zeros((1,)) placeholders when fp32
    v_scale: Any
    ef: Any = None  # sync_codes error-feedback sidecar (distributed only)


class DenseLeaf(NamedTuple):
    mu: Any
    nu: Any
    mu_scale: Any
    nu_scale: Any


class ConvLeaf(NamedTuple):
    """Tucker-2 leaf (Algorithm 3): two factor projections + core moments."""

    p_o: Any  # (O, r_O)
    p_i: Any  # (I, r_I)
    m: Any  # (r_O, r_I, K1, K2)
    v: Any
    m_scale: Any
    v_scale: Any
    ef: Any = None  # sync_codes error-feedback sidecar (core shape; see ProjLeaf)


class ProjectedAdamState(NamedTuple):
    count: jnp.ndarray
    leaves: Any  # pytree congruent with params; leaf = Proj/Dense/ConvLeaf


@dataclasses.dataclass(frozen=True)
class LeafOverrides:
    """Per-leaf knob overrides a memory plan may pin (``None`` = inherit the
    global :class:`ProjectedAdamConfig` value). Rank overrides do NOT live
    here — they ride in the rules (``projector.PlannedRules``) because the
    rank is part of the ProjSpec and therefore of the bucket identity."""

    quantize: Optional[bool] = None
    t_update: Optional[int] = None
    stagger_groups: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class PlanOverrides:
    """Exact-path -> :class:`LeafOverrides` map (hashable; plan-driven).

    Congruence buckets group leaves by ``(spec, shape, dtype)``; storage
    codec and refresh cadence are bucket-level properties, so every path of
    a bucket must resolve to the SAME overrides — ``update_fn`` enforces
    this and raises on a mixed bucket (a plan assigns knobs per bucket, so
    this only triggers on hand-edited plans)."""

    entries: Tuple[Tuple[str, LeafOverrides], ...] = ()

    def __post_init__(self):
        object.__setattr__(self, "_map", dict(self.entries))

    def for_path(self, path: str) -> Optional[LeafOverrides]:
        return self._map.get(path)

    def any_quantized(self) -> bool:
        return any(ov.quantize for _, ov in self.entries)


@dataclasses.dataclass(frozen=True)
class ProjectedAdamConfig:
    rules: ProjectionRules
    strategy: str = "coap"
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    t_update: int = 200  # T_u (refresh interval; GaLore SVD interval; Flora=1)
    lam: int = 5  # λ: Eqn-7 recalibration every λ·T_u steps
    eqn6_lr: float = 0.1  # paper appendix: SGD lr for Eqn 6, default 0.1
    eqn6_steps: int = 1
    eqn6_normalize: bool = False  # beyond-paper scale-invariant Eqn-6 step
    seed: int = 0
    state_dtype: Any = jnp.float32
    quantize: bool = False  # 8-bit block-wise states
    quant_block: int = kref.QUANT_BLOCK
    update_scale: float = 1.0  # GaLore's α (their repo default 0.25)
    moment_transplant: bool = False  # carry M into the new subspace at refresh
    use_fused_kernel: bool = True  # route through kernels/ops (Pallas on TPU)
    bucket_leaves: bool = True  # batch congruent leaves into stacked launches
    stagger: bool = True  # phase-staggered refresh schedule (module docstring)
    stagger_groups: int = 8  # max phase groups per congruent bucket
    stacked_state: bool = False  # store state pre-stacked (module docstring)
    # Cross-pod int8 collective (distributed/compression.py): all-reduce the
    # int8 codes + per-block scales of G_proj instead of fp32 values, with a
    # per-leaf fp32 error-feedback accumulator (ProjLeaf/ConvLeaf.ef). The
    # knob lives here so init_fn allocates the sidecar and the byte model
    # (plan/bytes.py) predicts it; single-pod updates ignore it.
    sync_codes: bool = False
    # Plan-driven per-bucket knob overrides (quantize / T_u / stagger_groups;
    # repro/plan consumes coap-plan/v1 artifacts into this field).
    overrides: Optional[PlanOverrides] = None

    def __post_init__(self):
        if self.strategy not in STRATEGIES:
            raise ValueError(f"strategy must be one of {STRATEGIES}")
        if self.stacked_state and not self.bucket_leaves:
            raise ValueError(
                "stacked_state=True stores the state along the bucket axis "
                "and requires bucket_leaves=True"
            )

    def any_quantized(self) -> bool:
        """True when ANY leaf stores int8 state (global flag or a per-leaf
        plan override) — the conservative check for consumers that cannot
        handle quantized states (e.g. compressed cross-pod sync)."""
        if self.quantize:
            return True
        return self.overrides is not None and self.overrides.any_quantized()


def _zeros_scales(shape_numel: int, block: int):
    nblocks = -(-shape_numel // block)
    return jnp.zeros((nblocks,), jnp.float32)


def _store(x: jnp.ndarray, cfg: ProjectedAdamConfig):
    """fp32 array -> (stored, scale) under the configured codec."""
    if not cfg.quantize:
        return x.astype(cfg.state_dtype), jnp.zeros((1,), jnp.float32)
    q, s = kops.quantize_blockwise(x, block=cfg.quant_block)
    return q, s


def _load(stored: jnp.ndarray, scale: jnp.ndarray, shape, cfg: ProjectedAdamConfig):
    if not cfg.quantize:
        return stored.astype(jnp.float32)
    return kops.dequantize_blockwise(stored, scale, tuple(shape), block=cfg.quant_block)


def _init_stored(shape, cfg: ProjectedAdamConfig):
    numel = 1
    for s in shape:
        numel *= int(s)
    if not cfg.quantize:
        return jnp.zeros(shape, cfg.state_dtype), jnp.zeros((1,), jnp.float32)
    nblocks = -(-numel // cfg.quant_block)
    return (
        jnp.zeros((nblocks, cfg.quant_block), jnp.int8),
        jnp.zeros((nblocks,), jnp.float32),
    )


def _init_stored_proj(shape, cfg: ProjectedAdamConfig):
    """Projected-moment storage: row-block int8 when quantized, else dense."""
    if not cfg.quantize:
        return jnp.zeros(shape, cfg.state_dtype), jnp.zeros((1,), jnp.float32)
    nblk = kref.rowblock_nblocks(int(shape[-1]), cfg.quant_block)
    return (
        jnp.zeros(shape, jnp.int8),
        jnp.zeros(tuple(shape[:-1]) + (nblk,), jnp.float32),
    )


def _leaf_spec(cfg: ProjectedAdamConfig, path: str, shape) -> ProjSpec:
    return cfg.rules.spec_for(path, shape)


def _apply_overrides(
    cfg: ProjectedAdamConfig, ov: Optional[LeafOverrides]
) -> ProjectedAdamConfig:
    if ov is None:
        return cfg
    kw = {}
    if ov.quantize is not None and ov.quantize != cfg.quantize:
        kw["quantize"] = ov.quantize
    if ov.t_update is not None and ov.t_update != cfg.t_update:
        kw["t_update"] = ov.t_update
    if ov.stagger_groups is not None and ov.stagger_groups != cfg.stagger_groups:
        kw["stagger_groups"] = ov.stagger_groups
    return dataclasses.replace(cfg, **kw) if kw else cfg


def _leaf_cfg(cfg: ProjectedAdamConfig, path: str) -> ProjectedAdamConfig:
    """The effective config for one leaf: plan overrides layered over the
    global knobs. With no overrides this is ``cfg`` itself."""
    if cfg.overrides is None:
        return cfg
    return _apply_overrides(cfg, cfg.overrides.for_path(path))


def _bucket_cfg(cfg: ProjectedAdamConfig, info) -> ProjectedAdamConfig:
    """The effective config for a congruence bucket. Storage codec and
    refresh cadence are bucket-level properties, so every member path must
    resolve to the same EFFECTIVE knobs. Overrides are normalized against
    the global config before comparing: an entry that merely restates the
    global value (or a reordered ``entries`` container) is not a conflict —
    only a genuinely different effective (quantize, T_u, stagger_groups)
    triple raises, and the error names a path from each side."""
    if cfg.overrides is None:
        return cfg

    def norm(ov: Optional[LeafOverrides]):
        if ov is None:
            return (cfg.quantize, cfg.t_update, cfg.stagger_groups)
        return (
            cfg.quantize if ov.quantize is None else ov.quantize,
            cfg.t_update if ov.t_update is None else ov.t_update,
            cfg.stagger_groups
            if ov.stagger_groups is None
            else ov.stagger_groups,
        )

    groups: dict = {}
    for p in info.paths:
        groups.setdefault(norm(cfg.overrides.for_path(p)), []).append(p)
    if len(groups) > 1:
        (ka, pa), (kb, pb) = list(groups.items())[:2]
        raise ValueError(
            f"plan overrides disagree within bucket {info.shape}/{info.dtype}:"
            f" {pa[0]!r} resolves to (quantize, t_update, stagger_groups)="
            f"{ka} but {pb[0]!r} to {kb} — a bucket's knobs must be uniform;"
            " assign overrides per bucket, not per leaf"
        )
    # All members normalize identically; any representative override yields
    # the same effective config (``_apply_overrides`` only replaces knobs
    # that actually differ from the global value).
    return _apply_overrides(cfg, cfg.overrides.for_path(info.paths[0]))


def _layout_of(cfg: ProjectedAdamConfig, flat) -> stacked_state.StackedLayout:
    """THE bucket assignment for this transform: projected, conv (Tucker-2)
    and dense leaves each bucket by congruence signature (the default
    ``classify_default`` — the stacked-bucket/v2 layout). Shared with the
    stacked-state codec so checkpoint / accounting / compression consumers
    see the identical grouping."""
    return stacked_state.layout_for_flat(cfg.rules.spec_for, flat)


def stagger_phases(
    bucket_sizes, t_update: int, stagger_groups: int
) -> list:
    """Deterministic per-leaf refresh phases for the staggered schedule.

    ``bucket_sizes`` lists the projected buckets' leaf counts in tree
    (insertion) order. Each bucket is split into at most ``stagger_groups``
    contiguous near-equal groups (``stagger_groups`` may be a sequence of
    per-bucket caps — how plan overrides stagger a bucket differently);
    the resulting units are spread uniformly
    over ``[0, t_update)`` so the worst refresh step carries ~1/U of the
    synchronized cost. Pure function of the tree structure — phases are
    identical across restarts and between bucketed and per-leaf execution.
    Returns one tuple of per-leaf-position phases per bucket.
    """
    t_u = max(1, int(t_update))
    if isinstance(stagger_groups, (list, tuple)):
        caps = [int(s) for s in stagger_groups]
    else:
        caps = [int(stagger_groups)] * len(bucket_sizes)
    n_groups = [
        max(1, min(int(b), cap, t_u)) for b, cap in zip(bucket_sizes, caps)
    ]
    total = sum(n_groups) or 1
    out = []
    u = 0
    for b, ng in zip(bucket_sizes, n_groups):
        unit_phases = [((u + j) * t_u) // total for j in range(ng)]
        out.append(tuple(unit_phases[(pos * ng) // b] for pos in range(b)))
        u += ng
    return out


def bucket_phases(
    cfg: ProjectedAdamConfig, layout: stacked_state.StackedLayout
) -> dict:
    """THE staggered phase allocation, bucket-indexed: maps every
    staggerable bucket (projected then conv, in layout order) to its
    per-slot refresh phases.

    A pure function of ``(layout, cfg)`` — no step, no RNG, no state — so
    phases re-derive identically across restarts, resumes and replans that
    preserve the layout; ``update_fn`` calls this every trace and the
    elastic supervisor (``train/elastic.py``) calls it to pin down the
    schedule a resumed run will follow. Buckets sharing an effective T_u
    are allocated jointly (phases spread uniformly over [0, T_u) across
    all of them); buckets a plan pins to a different T_u get their own
    allocation over their own interval. With no overrides this is exactly
    the single joint allocation of the global schedule.
    """
    bucket_cfgs = [_bucket_cfg(cfg, info) for info in layout.buckets]
    stag_bis = [
        bi for bi, info in enumerate(layout.buckets)
        if info.kind in (
            stacked_state.BUCKET_PROJECT, stacked_state.BUCKET_CONV
        )
    ]
    by_tu = {}
    for bi in stag_bis:
        by_tu.setdefault(bucket_cfgs[bi].t_update, []).append(bi)
    phase_by_bucket = {}
    for t_u, bis in by_tu.items():
        sizes = [len(layout.buckets[bi].indices) for bi in bis]
        if cfg.stagger and t_u > 1:
            pls = stagger_phases(
                sizes, t_u, [bucket_cfgs[bi].stagger_groups for bi in bis]
            )
        else:
            pls = [(0,) * sz for sz in sizes]
        for bi, pl in zip(bis, pls):
            phase_by_bucket[bi] = pl
    return phase_by_bucket


def _phase_groups(phases) -> list:
    """Maximal runs of equal phase -> [(start, size, phase)]. Phases are
    non-decreasing within a bucket (``stagger_phases`` allocates monotone
    units), so equal phases are always adjacent and groups carry distinct
    phases in [0, T_u) — at most one group matches any given step."""
    groups = []
    start = 0
    for i in range(1, len(phases) + 1):
        if i == len(phases) or phases[i] != phases[start]:
            groups.append((start, i - start, phases[start]))
            start = i
    return groups


def _expand_mask(mask: jnp.ndarray, ndim: int) -> jnp.ndarray:
    """(B,) bool -> (B, 1, ..., 1) broadcastable against a stacked leaf."""
    return mask.reshape(mask.shape + (1,) * (ndim - 1))


def _sched_preds(count, ph: int, t_u: int, lam: int):
    """THE staggered-schedule predicates, defined once: refresh when
    ``(count + phase) % T_u == 0``, recalibrate when ``(count + phase) %
    (λ·T_u) == 0`` — plus the mandatory Eqn-7 initialization for everyone at
    count == 0. ``_refresh_mask`` is the vectorized refresh predicate."""
    do_ref = ((count + ph) % t_u == 0) | (count == 0)
    do_recal = ((count + ph) % (lam * t_u) == 0) | (count == 0)
    return do_ref, do_recal


def _refresh_mask(count, phases, t_u: int) -> jnp.ndarray:
    phase_arr = jnp.asarray(phases, jnp.int32)
    return ((count + phase_arr) % t_u == 0) | (count == 0)


def _stagger_select(groups, count, t_u: int) -> jnp.ndarray:
    """Branch index for a staggered lax.switch: 0 = no-op, 1..G = the (at
    most one — groups carry distinct phases mod T_u) matching phase group,
    G+1 = whole-bucket t=0 initialization."""
    sel = jnp.zeros((), jnp.int32)
    for j, (_, _, ph) in enumerate(groups):
        sel = jnp.where((count + ph) % t_u == 0, j + 1, sel)
    return jnp.where(count == 0, len(groups) + 1, sel)


def _stagger_dispatch(groups, count, t_u: int, noop, group_fn, full_fn):
    """THE staggered group dispatch, shared by the refresh and both
    transplant paths: lax.switch over [no-op] + one branch per phase group +
    [whole-bucket t=0 init]. ``group_fn(s0, sz, ph)`` produces the branch
    result for that group's static slice."""
    branches = (
        [noop]
        + [
            (lambda s0=s0, sz=sz, ph=ph: group_fn(s0, sz, ph))
            for s0, sz, ph in groups
        ]
        + [full_fn]
    )
    return lax.switch(_stagger_select(groups, count, t_u), branches)


def _array_loader(x):
    """A ``_refresh_p`` loader over an array that is already canonical."""
    return lambda sl=slice(None): x[sl]


def _refresh_p(
    cfg: ProjectedAdamConfig,
    spec: ProjSpec,
    p: jnp.ndarray,
    g_loader,
    m_loader,
    count: jnp.ndarray,
    idx_arr: jnp.ndarray,
    phases: Optional[Tuple[int, ...]] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Strategy-specific P refresh on a stacked leaf bucket.

    ``p`` carries a leading (B,) bucket axis; ``idx_arr`` (B,) holds the
    ORIGINAL flat leaf indices (flora folds them into its per-leaf RNG keys,
    so bucketing never changes the random stream). ``g_loader`` and
    ``m_loader`` return the canonical gradient and the first moment of a
    bucket-axis ``slice`` (all of it without one). Both are invoked lazily
    inside the refresh branch — G is only canonicalized and quantized M only
    dequantized on the (rare) refresh steps, never in the per-step hot loop,
    and staggered group branches load only the refreshing slice. The
    gradient may be bf16 (every refresh primitive upcasts internally).

    ``phases`` (len B, non-decreasing) staggers the schedule: leaf b
    refreshes when ``(count + phases[b]) % T_u == 0`` — plus the mandatory
    Eqn-7 initialization for everyone at count==0. With a single phase group
    (the default / ``stagger=False``) this is exactly the synchronized
    Algorithm-1 schedule; with several, a ``lax.switch`` refreshes only the
    matching group's static slice.

    Returns (new_p, refreshed) where ``refreshed`` is a (B,) bool mask.
    """
    b = p.shape[0]
    if phases is None:
        phases = (0,) * b
    groups = _phase_groups(phases)
    t_u = cfg.t_update
    mask = _refresh_mask(count, phases, t_u)

    def eqn6(p_g, gc_g, m_g):
        return correlation.sgd_update(
            p_g, gc_g, m_g, lr=cfg.eqn6_lr, steps=cfg.eqn6_steps,
            normalize=cfg.eqn6_normalize, use_fused=cfg.use_fused_kernel,
        )

    def _staggered(refresh_slice, full_init):
        return _stagger_dispatch(
            groups, count, t_u,
            noop=lambda: p,
            group_fn=lambda s0, sz, ph: p.at[s0:s0 + sz].set(
                refresh_slice(s0, sz, ph)
            ),
            full_fn=full_init,
        )

    if cfg.strategy == "coap":
        if len(groups) == 1:
            do_ref, do_recal = _sched_preds(count, groups[0][2], t_u, cfg.lam)

            def refreshed():
                gc = g_loader()
                return lax.cond(
                    do_recal,
                    lambda: recalibrate.lowcost_svd(gc, p),
                    lambda: eqn6(p, gc, m_loader()),
                )

            return lax.cond(do_ref, refreshed, lambda: p), mask

        def refresh_slice(s0, sz, ph):
            p_g = p[s0:s0 + sz]
            gc_g = g_loader(slice(s0, s0 + sz))
            _, do_recal = _sched_preds(count, ph, t_u, cfg.lam)
            return lax.cond(
                do_recal,
                lambda: recalibrate.lowcost_svd(gc_g, p_g),
                lambda: eqn6(p_g, gc_g, m_loader(slice(s0, s0 + sz))),
            )

        new_p = _staggered(
            refresh_slice, lambda: recalibrate.lowcost_svd(g_loader(), p)
        )
        return new_p, mask

    if cfg.strategy == "galore":
        if len(groups) == 1:
            do_ref, _ = _sched_preds(count, groups[0][2], t_u, cfg.lam)
            new_p = lax.cond(
                do_ref,
                lambda: recalibrate.galore_svd(
                    g_loader(), spec.rank).astype(p.dtype),
                lambda: p,
            )
            return new_p, mask

        def refresh_slice(s0, sz, ph):
            return recalibrate.galore_svd(
                g_loader(slice(s0, s0 + sz)), spec.rank
            ).astype(p.dtype)

        new_p = _staggered(
            refresh_slice,
            lambda: recalibrate.galore_svd(
                g_loader(), spec.rank).astype(p.dtype),
        )
        return new_p, mask

    # flora
    elem_shape = jax.eval_shape(g_loader).shape[1:]

    def resample_idx(idx_slice):
        def one(i):
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.key(cfg.seed), i), count
            )
            return recalibrate.random_projection(
                key, elem_shape, spec.rank, p.dtype
            )

        return jax.vmap(one)(idx_slice)

    if len(groups) == 1:
        do_ref, _ = _sched_preds(count, groups[0][2], t_u, cfg.lam)
        new_p = lax.cond(do_ref, lambda: resample_idx(idx_arr), lambda: p)
        return new_p, mask

    new_p = _staggered(
        lambda s0, sz, ph: resample_idx(idx_arr[s0:s0 + sz]),
        lambda: resample_idx(idx_arr),
    )
    return new_p, mask


def _wants_transplant(cfg: ProjectedAdamConfig) -> bool:
    """Flora always transplants; COAP/GaLore only when opted in."""
    return cfg.strategy == "flora" or cfg.moment_transplant


def _maybe_transplant(
    cfg: ProjectedAdamConfig, m: jnp.ndarray, p_old, p_new, refreshed,
    phases=None, count=None,
) -> jnp.ndarray:
    """M_new = (M P_oldᵀ) P_new — keeps momentum direction across subspace
    switches. Flora's mechanism; optional (off = Algorithm 1 verbatim) for
    COAP/GaLore.

    ``refreshed`` is either a scalar bool (per-leaf callers, e.g. the
    adafactor variant) or a (B,) mask over a stacked bucket: under the
    staggered schedule only the refreshed slice may transplant — P is
    non-orthonormal, so project∘backproject is NOT the identity and must not
    touch leaves whose P did not change. When the caller supplies ``phases``
    and ``count``, the transplant follows the same group structure as
    ``_refresh_p``: only the refreshing slice's (B_g, m, n, r) work runs,
    not the whole bucket's."""
    if not _wants_transplant(cfg):
        return m
    if getattr(refreshed, "ndim", 0) == 0:
        def do():
            restored = projector.backproject(m, p_old)
            return projector.project(restored, p_new)

        return lax.cond(refreshed, do, lambda: m)

    def carry(sl):
        restored = projector.backproject(m[sl], p_old[sl])
        return projector.project(restored, p_new[sl])

    groups = _phase_groups(phases) if phases is not None else []
    if len(groups) <= 1:
        def do_masked():
            return jnp.where(
                _expand_mask(refreshed, m.ndim), carry(slice(None)), m
            )

        return lax.cond(jnp.any(refreshed), do_masked, lambda: m)

    return _stagger_dispatch(
        groups, count, cfg.t_update,
        noop=lambda: m,
        group_fn=lambda s0, sz, ph: m.at[s0:s0 + sz].set(
            carry(slice(s0, s0 + sz))
        ),
        full_fn=lambda: carry(slice(None)),  # t=0: everyone refreshed
    )


def scale_by_projected_adam(cfg: ProjectedAdamConfig) -> GradientTransformation:
    """The regularizer ρ_t of paper Eqn 5 as a GradientTransformation.

    Produces *positive* update directions (caller chains lr sign-flip).
    """

    def init_fn(params):
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        if cfg.overrides is not None:
            # Fail at init, not first update: a mixed-quantize bucket would
            # otherwise stack int8 codes with fp32 moments silently.
            for info in _layout_of(cfg, flat).buckets:
                _bucket_cfg(cfg, info)
        key = jax.random.key(cfg.seed)
        leaves = []
        for idx, (kp, leaf) in enumerate(flat):
            path = path_str(kp)
            spec = _leaf_spec(cfg, path, leaf.shape)
            lcfg = _leaf_cfg(cfg, path)  # plan overrides (storage codec)
            if spec.kind == KIND_PROJECT:
                p0 = projector.init_p(
                    jax.random.fold_in(key, idx), leaf.shape, spec,
                    cfg.state_dtype,
                )
                msh = projector.moment_shape(leaf.shape, spec)
                m0, ms0 = _init_stored_proj(msh, lcfg)
                v0, vs0 = _init_stored_proj(msh, lcfg)
                ef0 = jnp.zeros(msh, jnp.float32) if cfg.sync_codes else None
                leaves.append(
                    ProjLeaf(p=p0, m=m0, v=v0, m_scale=ms0, v_scale=vs0, ef=ef0)
                )
            elif spec.kind == KIND_CONV:
                po, pi = conv_mod.init_factors(
                    jax.random.fold_in(key, idx), leaf.shape, spec
                )
                msh = conv_mod.core_shape(leaf.shape, spec)
                m0, ms0 = _init_stored(msh, lcfg)
                v0, vs0 = _init_stored(msh, lcfg)
                ef0 = jnp.zeros(msh, jnp.float32) if cfg.sync_codes else None
                leaves.append(
                    ConvLeaf(p_o=po, p_i=pi, m=m0, v=v0, m_scale=ms0,
                             v_scale=vs0, ef=ef0)
                )
            else:
                m0, ms0 = _init_stored(leaf.shape, lcfg)
                v0, vs0 = _init_stored(leaf.shape, lcfg)
                leaves.append(DenseLeaf(mu=m0, nu=v0, mu_scale=ms0, nu_scale=vs0))
        if cfg.stacked_state:
            # Same per-leaf states (identical RNG keys per flat index),
            # stored pre-stacked: encode is a bit-exact stack per field.
            return ProjectedAdamState(
                count=jnp.zeros([], jnp.int32),
                leaves=stacked_state.encode(_layout_of(cfg, flat), leaves),
            )
        return ProjectedAdamState(
            count=jnp.zeros([], jnp.int32),
            leaves=jax.tree_util.tree_unflatten(treedef, leaves),
        )

    def _update_proj_bucket(cfg, leaf: ProjLeaf, g, spec: ProjSpec, count, t,
                            idx_arr, phases=None):
        """One step for a stacked bucket of congruent projected leaves (all
        arrays carry a leading (B,) axis; B == 1 for singleton buckets).
        ``cfg`` is the BUCKET-effective config (plan overrides applied —
        shadows the transform's global config on purpose).
        The fused kernels read ``g`` as stored, in its dtype — bf16
        gradients stream in as bf16 (upcast per-tile in VMEM, halving
        per-step G traffic) — and write ΔW as stored: no copy of G or ΔW
        is made on a step that refreshes nothing. Only the refresh and the
        unfused jnp fallbacks see the canonical (m >= n) gradient, through
        ``g_loader``; the fallbacks materialize fp32."""
        p_old = leaf.p

        # Loaders take an optional bucket-axis slice so staggered group
        # refreshes only canonicalize/dequantize/upcast the slice they
        # actually update.
        def g_loader(sl=slice(None)):
            return projector.to_canonical(g[sl], spec)

        if cfg.quantize:
            def m_loader(sl=slice(None)):
                return kops.dequantize_rowblock(
                    leaf.m[sl], leaf.m_scale[sl], block=cfg.quant_block
                )
        else:
            def m_loader(sl=slice(None)):
                return leaf.m[sl].astype(jnp.float32)

        # Eqn 6 / Eqn 7 and any moment transplant run under the bucket's
        # "refresh" scope, the per-step kernel under "update" (op_name).
        with jax.named_scope("refresh"):
            new_p, refreshed = _refresh_p(
                cfg, spec, p_old, g_loader, m_loader, count, idx_arr, phases
            )

            # Projection-health emit (obs/health): refresh-boundary metrics,
            # loading G under a lax.cond on the refresh mask — non-refresh
            # steps execute nothing, so the hot path keeps zero extra G
            # round-trips. Trace-time no-op (identical compiled program)
            # when no monitor is configured.
            health.emit_refresh_matrix(
                health.bucket_label("project", g.shape[1:], g.dtype),
                g_loader, p_old, new_p, refreshed, count,
            )

        if cfg.quantize:
            m_q, m_s = leaf.m, leaf.m_scale
            if _wants_transplant(cfg):
                # On refresh steps the transplanted M takes one extra int8
                # requant->dequant round-trip (requantized here, dequantized
                # again inside the fused kernel) vs a hypothetical
                # dequant->transplant->EMA->requant schedule: one added
                # block-absmax rounding per refresh, accepted so the hot
                # per-step path stays a single kernel with int8-only state.
                # Under stagger only the refreshing group's slice is
                # dequantized/transplanted/requantized (same group structure
                # as _refresh_p — the codec is row-wise, so slice-local
                # requant emits the identical codes).
                def carry_q(sl):
                    carried = projector.project(
                        projector.backproject(m_loader(sl), p_old[sl]),
                        new_p[sl],
                    )
                    return kops.quantize_rowblock(
                        carried, block=cfg.quant_block
                    )

                def q_group(s0, sz, _ph):
                    cq, cs = carry_q(slice(s0, s0 + sz))
                    return (
                        m_q.at[s0:s0 + sz].set(cq),
                        m_s.at[s0:s0 + sz].set(cs),
                    )

                tgroups = _phase_groups(phases) if phases else []
                with jax.named_scope("refresh"):
                    if len(tgroups) <= 1:
                        def transplanted():
                            cq, cs = carry_q(slice(None))
                            return (
                                jnp.where(
                                    _expand_mask(refreshed, cq.ndim), cq, m_q
                                ),
                                jnp.where(
                                    _expand_mask(refreshed, cs.ndim), cs, m_s
                                ),
                            )

                        m_q, m_s = lax.cond(
                            jnp.any(refreshed), transplanted, lambda: (m_q, m_s)
                        )
                    else:
                        m_q, m_s = _stagger_dispatch(
                            tgroups, count, cfg.t_update,
                            noop=lambda: (m_q, m_s),
                            group_fn=q_group,
                            full_fn=lambda: carry_q(slice(None)),  # t=0 init
                        )
            with jax.named_scope("update"):
                if cfg.use_fused_kernel:
                    # Single-pass fused int8 step: no fp32 M/V, no Δ_proj in HBM.
                    nmq, nms, nvq, nvs, update = kops.coap_fused_update_q8(
                        g, new_p, m_q, m_s, leaf.v, leaf.v_scale, t,
                        b1=cfg.b1, b2=cfg.b2, eps=cfg.eps, block=cfg.quant_block,
                        transposed=spec.transpose,
                    )
                else:
                    # Unfused 8-bit schedule — every intermediate round-trips
                    # HBM; kept as the benchmark baseline (benchmarks/overhead).
                    # The oracle IS that schedule expressed as jnp ops.
                    nmq, nms, nvq, nvs, update_c = kref.coap_fused_update_q8(
                        g_loader(), new_p, m_q, m_s, leaf.v, leaf.v_scale, t,
                        b1=cfg.b1, b2=cfg.b2, eps=cfg.eps, block=cfg.quant_block,
                    )
                    update = projector.from_canonical(update_c, spec)
                new_leaf = ProjLeaf(p=new_p, m=nmq, v=nvq, m_scale=nms,
                                    v_scale=nvs, ef=leaf.ef)
        else:
            m = m_loader()
            v = leaf.v.astype(jnp.float32)
            with jax.named_scope("refresh"):
                m = _maybe_transplant(
                    cfg, m, p_old, new_p, refreshed, phases, count
                )
            with jax.named_scope("update"):
                if cfg.use_fused_kernel:
                    new_m, new_v, update = kops.coap_fused_update_bp(
                        g, new_p, m, v, t, b1=cfg.b1, b2=cfg.b2, eps=cfg.eps,
                        transposed=spec.transpose,
                    )
                else:
                    g_proj = projector.project(
                        g_loader().astype(jnp.float32), new_p)
                    new_m = cfg.b1 * m + (1.0 - cfg.b1) * g_proj
                    new_v = cfg.b2 * v + (1.0 - cfg.b2) * jnp.square(g_proj)
                    tf = t.astype(jnp.float32)
                    delta_proj = (new_m / (1.0 - cfg.b1**tf)) / (
                        jnp.sqrt(new_v / (1.0 - cfg.b2**tf)) + cfg.eps
                    )
                    update = projector.from_canonical(
                        projector.backproject(delta_proj, new_p), spec)
                new_leaf = ProjLeaf(
                    p=new_p,
                    m=new_m.astype(cfg.state_dtype),
                    v=new_v.astype(cfg.state_dtype),
                    m_scale=leaf.m_scale,  # fp32 placeholders pass through
                    v_scale=leaf.v_scale,
                    ef=leaf.ef,
                )
        with jax.named_scope("update"):
            update = update * cfg.update_scale
        return update.astype(g.dtype), new_leaf

    def _update_dense_leaf(cfg, leaf: DenseLeaf, g, count, t):
        g32 = g.astype(jnp.float32)
        if cfg.quantize and cfg.use_fused_kernel:
            # 8-bit dense Adam as ONE fused dispatch (dequant -> EMA ->
            # bias-corrected Δ + underflow clip -> requant); same math as the
            # unfused schedule below, but mu/nu never round-trip HBM as
            # fp32 between separate jnp passes.
            nmq, nms, nvq, nvs, upd = kops.quantized_adam_update(
                g32, leaf.mu, leaf.mu_scale, leaf.nu, leaf.nu_scale, t,
                b1=cfg.b1, b2=cfg.b2, eps=cfg.eps, block=cfg.quant_block,
            )
            return upd.astype(g.dtype), DenseLeaf(
                mu=nmq, nu=nvq, mu_scale=nms, nu_scale=nvs
            )
        mu = _load(leaf.mu, leaf.mu_scale, g.shape, cfg)
        nu = _load(leaf.nu, leaf.nu_scale, g.shape, cfg)
        new_mu = cfg.b1 * mu + (1.0 - cfg.b1) * g32
        new_nu = cfg.b2 * nu + (1.0 - cfg.b2) * jnp.square(g32)
        tf = t.astype(jnp.float32)
        upd = (new_mu / (1.0 - cfg.b1**tf)) / (
            jnp.sqrt(new_nu / (1.0 - cfg.b2**tf)) + cfg.eps
        )
        if cfg.quantize:  # int8-v underflow guard (see kernels/ref.py)
            upd = jnp.clip(upd, -kref.QUANT_DELTA_CLIP, kref.QUANT_DELTA_CLIP)
        smu, smus = _store(new_mu, cfg)
        snu, snus = _store(new_nu, cfg)
        return upd.astype(g.dtype), DenseLeaf(
            mu=smu, nu=snu, mu_scale=smus, nu_scale=snus
        )

    def update_fn(updates, state, params=None):
        del params
        count = state.count  # 0-based: first call refreshes/initializes P
        t = count + 1  # 1-based for bias correction (Algorithm 1)
        flat_u, treedef = jax.tree_util.tree_flatten_with_path(updates)
        n_leaves = len(flat_u)
        new_updates = [None] * n_leaves

        # Bucket congruent leaves: one (vmapped) kernel launch per
        # (shape, spec, dtype) group instead of one per leaf — conv
        # (Tucker-2) leaves included since stacked-bucket/v2 (Algorithm 3
        # batched over the bucket axis; conv_mod.update_conv_bucket). The
        # layout is THE bucket assignment shared with the stacked-state
        # codec (checkpoint/accounting/compression).
        layout = _layout_of(cfg, flat_u)

        if cfg.stacked_state:
            prev = state.leaves
            if (
                not isinstance(prev, stacked_state.StackedLeaves)
                or prev.layout.signature() != layout.signature()
            ):
                raise ValueError(
                    "stacked optimizer state does not match the gradient "
                    "tree (optimizer rules / model structure changed since "
                    "init, or a per-leaf state was passed with "
                    "stacked_state=True)"
                )
            flat_s = None
        else:
            prev = None
            flat_s = treedef.flatten_up_to(state.leaves)

        # Bucket-effective configs (plan overrides: quantize / T_u /
        # stagger_groups per bucket; identity when no overrides are set).
        bucket_cfgs = [_bucket_cfg(cfg, info) for info in layout.buckets]

        # Per-leaf refresh phases (staggered schedule): THE allocation,
        # shared with the elastic supervisor (``bucket_phases`` — a pure
        # function of (layout, cfg), so phases re-derive identically on
        # every restart/resume).
        phase_by_bucket = bucket_phases(cfg, layout)

        new_buckets = [None] * len(layout.buckets)
        new_tail = [None] * len(layout.tail)
        new_flat = [None] * n_leaves  # per-leaf mode only

        # Residual tail (empty under the default v2 classification; a
        # custom classify may still route conv leaves here — they keep the
        # synchronized per-leaf Algorithm-3 path).
        for j, tinfo in enumerate(layout.tail):
            leaf = prev.tail[j] if cfg.stacked_state else flat_s[tinfo.index]
            u, nl = conv_mod.update_conv_leaf(
                _leaf_cfg(cfg, tinfo.path), leaf, flat_u[tinfo.index][1],
                tinfo.spec, count, t, tinfo.index,
            )
            new_updates[tinfo.index] = u
            new_tail[j] = nl
            new_flat[tinfo.index] = nl

        for bi, info in enumerate(layout.buckets):
            is_proj = info.kind == stacked_state.BUCKET_PROJECT
            is_conv = info.kind == stacked_state.BUCKET_CONV
            bcfg = bucket_cfgs[bi]
            phases = phase_by_bucket[bi] if (is_proj or is_conv) else None
            if cfg.bucket_leaves:
                slot_groups = [tuple(range(len(info.indices)))]
            else:  # per-leaf A/B mode (stacked_state forbids this)
                slot_groups = [(k,) for k in range(len(info.indices))]
            # One scope per bucket in the step's op_names, health's label
            # for it ("dense/" before a dense Adam bucket's), and inside it
            # gather / refresh / update / scatter.
            label = health.bucket_label(info.kind, info.shape, info.dtype)
            if not (is_proj or is_conv):
                label = "dense/" + label
            with jax.named_scope(label):
                for slots in slot_groups:
                    idxs = [info.indices[k] for k in slots]
                    with jax.named_scope("gather"):
                        g_stack = jnp.stack([flat_u[i][1] for i in idxs])
                        if cfg.stacked_state:
                            # The hot-path win: the bucket state is ALREADY
                            # stacked — no stack copy in, no scatter copy out.
                            leaf_stack = prev.buckets[bi]
                        else:
                            leaf_stack = jax.tree_util.tree_map(
                                lambda *xs: jnp.stack(xs),
                                *[flat_s[i] for i in idxs],
                            )
                    if is_proj:
                        u_stack, nl_stack = _update_proj_bucket(
                            bcfg, leaf_stack, g_stack, info.spec, count, t,
                            jnp.asarray(idxs, jnp.int32),
                            tuple(phases[k] for k in slots),
                        )
                    elif is_conv:
                        u_stack, nl_stack = conv_mod.update_conv_bucket(
                            bcfg, leaf_stack, g_stack, info.spec, count, t,
                            jnp.asarray(idxs, jnp.int32),
                            tuple(phases[k] for k in slots),
                        )
                    else:
                        with jax.named_scope("update"):
                            u_stack, nl_stack = jax.vmap(
                                lambda lf, gg: _update_dense_leaf(
                                    bcfg, lf, gg, count, t)
                            )(leaf_stack, g_stack)
                    with jax.named_scope("scatter"):
                        for b, i in enumerate(idxs):
                            new_updates[i] = u_stack[b]
                            if not cfg.stacked_state:
                                new_flat[i] = jax.tree_util.tree_map(
                                    lambda x: x[b], nl_stack
                                )
                    if cfg.stacked_state:
                        new_buckets[bi] = nl_stack

        if cfg.stacked_state:
            new_leaves = stacked_state.StackedLeaves(
                new_buckets, new_tail, prev.layout
            )
        else:
            new_leaves = jax.tree_util.tree_unflatten(treedef, new_flat)
        return (
            jax.tree_util.tree_unflatten(treedef, new_updates),
            ProjectedAdamState(count=count + 1, leaves=new_leaves),
        )

    return GradientTransformation(init_fn, update_fn)


# ---------------------------------------------------------------------------
# User-facing optimizers
# ---------------------------------------------------------------------------
def _projected_adamw(
    strategy: str,
    learning_rate,
    rules: ProjectionRules,
    *,
    b1=0.9,
    b2=0.999,
    eps=1e-8,
    weight_decay=0.0,
    t_update=200,
    lam=5,
    eqn6_lr=0.1,
    eqn6_steps=1,
    seed=0,
    quantize=False,
    state_dtype=jnp.float32,
    update_scale=1.0,
    moment_transplant=False,
    stagger=True,
    stagger_groups=8,
    stacked_state=False,
    overrides=None,
    quant_block=kref.QUANT_BLOCK,
    mask=None,
) -> GradientTransformation:
    cfg = ProjectedAdamConfig(
        rules=rules,
        strategy=strategy,
        b1=b1,
        b2=b2,
        eps=eps,
        t_update=t_update,
        lam=lam,
        eqn6_lr=eqn6_lr,
        eqn6_steps=eqn6_steps,
        seed=seed,
        quantize=quantize,
        state_dtype=state_dtype,
        update_scale=update_scale,
        moment_transplant=moment_transplant,
        stagger=stagger,
        stagger_groups=stagger_groups,
        stacked_state=stacked_state,
        overrides=overrides,
        quant_block=quant_block,
    )
    return projected_adamw_from_config(
        cfg, learning_rate, weight_decay=weight_decay, mask=mask
    )


def projected_adamw_from_config(
    cfg: ProjectedAdamConfig, learning_rate, *, weight_decay=0.0, mask=None
) -> GradientTransformation:
    """AdamW chain around an explicit :class:`ProjectedAdamConfig` — the
    entry plan consumers use so the config object driving the optimizer is
    the SAME one schedule consumers (``bucket_phases``) introspect."""
    txs = [scale_by_projected_adam(cfg)]
    if weight_decay:
        txs.append(add_decayed_weights(weight_decay, mask=mask))
    txs.append(scale_by_learning_rate(learning_rate))
    return chain(*txs)


def coap_adamw(learning_rate, rules: ProjectionRules, **kw) -> GradientTransformation:
    """AdamW + COAP (paper Algorithm 1 + decoupled weight decay)."""
    return _projected_adamw("coap", learning_rate, rules, **kw)


def galore_adamw(learning_rate, rules: ProjectionRules, **kw) -> GradientTransformation:
    """GaLore baseline. Note their repo's update scale α defaults to 0.25."""
    kw.setdefault("update_scale", 0.25)
    return _projected_adamw("galore", learning_rate, rules, **kw)


def flora_adamw(learning_rate, rules: ProjectionRules, **kw) -> GradientTransformation:
    """Flora baseline: fresh random projections (+ moment transplant)."""
    kw.setdefault("t_update", 1)
    return _projected_adamw("flora", learning_rate, rules, **kw)

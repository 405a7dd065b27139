"""Cross-pod projected-gradient compression (beyond-paper; DESIGN.md §5).

The pod axis is pure data parallelism over the slowest links. The baseline
step all-reduces the full gradient G (m·n per matrix) across pods. But COAP
consumes G only two ways:

  1. every step:   G_proj = G P        (m·r — the moment/update input)
  2. every T_u:    the full G          (Eqn-6/Eqn-7 refresh input)

Projection is linear, so  mean_pods(G)·P == mean_pods(G·P)  exactly. We
therefore all-reduce the r-rank projection each step and the full gradient
only on refresh steps:

    cross-pod bytes/step = m·r + m·n/T_u      vs      m·n

Conv (Tucker-2) leaves compress the same way: the n-mode products are
linear, so the r_O·r_I·K1·K2 projected core is all-reduced each step and
the full O·I·K1·K2 gradient only on factor-refresh steps.

PARITY WITH THE CORE TRANSFORM. ``compressed_update`` supports every
configuration ``scale_by_projected_adam`` supports and runs the same
schedule machinery, so a pod-parallel run obeys the same plan as the
identical single-pod run:

  * **strategies** — coap / galore / flora refresh through the shared
    ``_refresh_p`` (matrix) and the conv strategy dispatch, including
    flora's per-leaf RNG keyed by the ORIGINAL flat leaf index;
  * **staggered refresh** — per-leaf phases come from the shared
    ``bucket_phases`` allocation (the same pure function of (layout, cfg)
    the core transform and the elastic supervisor use), so refresh cadence
    is identical to the single-pod staggered schedule;
  * **per-bucket overrides** — a plan's per-bucket quantize / T_u /
    stagger_groups ride through ``_bucket_cfg`` exactly as in the core
    transform (mixed-override buckets raise the same ValueError, naming
    the offending paths);
  * **quantized states** (``quantize=True``) — the dequant→reduce→requant
    schedule: int8 moment codes are dequantized in-pod, the r-rank
    projected gradient is reduced in fp32, the moment EMA runs in fp32 and
    the results are requantized through the SAME row-block (projected) /
    flat (conv, dense) codecs the single-pod path uses. The op sequence
    per leaf mirrors the unfused oracle (``kernels/ref``'s
    ``coap_fused_update_q8`` / ``quantized_adam_update``) exactly, so
    where the pod-mean is the identity (identical per-pod gradients) the
    emitted int8 codes are BIT-EXACT against the single-pod quantized step
    (``use_fused_kernel=False``); otherwise the only drift is the fp32
    pmean itself — no extra codec rounding, the moments pay exactly the
    same one requantization per step the single-pod schedule pays.

INT8 COLLECTIVE (``sync_codes=True``). The fp32 r-rank reduction is
replaced by an all-reduce of int8 CODES: each pod adds its error-feedback
accumulator to its local G_proj, the per-block absmax is agreed via a
(scales-only) ``pmax``, every pod emits codes under that shared scale, and
the codes are summed (a psum of int8 payloads — the wire carries ~1 byte
per element plus one fp32 scale per ``quant_block`` elements, vs 4 bytes
per element for fp32 sync). The mean is reconstructed as
``scale·Σq/npods``, paying exactly ONE extra blockwise rounding per step —
the same single-rounding rule ``stacked_state.migrate`` documents for
quantize flips. The rounding residue goes into a per-leaf fp32
error-feedback accumulator (``ProjLeaf.ef`` / ``ConvLeaf.ef``, allocated
by ``init_fn`` when ``cfg.sync_codes``; accounted as 'ef_sidecar' and
predicted by ``plan/bytes.py``), so the applied reductions telescope:
``Σ_t applied_t = Σ_t mean_t + ef_0 − ef_T`` — quantization error does not
accumulate in the moments. SIMULATION NOTE: real hardware keeps each pod's
own residual ``y_k − s·q_k`` locally (no extra traffic); to keep the
optimizer state replicated under this pure-DP shard_map (``out_specs
P()``) we store the pod-mean residual instead — the telescoping guarantee
is identical, and the residual mean is NOT part of the modeled wire format
(``benchmarks/overhead.run_sync`` counts codes + scales). The full-G
refresh-step all-reduce stays fp32 (rare; amortized by T_u). Dense leaves
(small) always sync fp32.

Implementation: ``shard_map`` manual over the 'pod' axis only (data/model
stay auto inside), computing per-pod gradients, reducing the compressed
tensors, and running the same leaf update the core transform uses.

Stacked-state aware: when the optimizer state is stored pre-stacked
(``stacked_state=True``; core/stacked_state.py), per-leaf moments are
addressed as bucket slices through the codec's ``leaf_view`` — inside jit
those slices fuse into their consumers, so the reduction schedule (r-rank
every step, full G on refresh steps) is unchanged — and the new leaf states
are re-encoded into the same stacked layout on the way out. The per-leaf
branch validates each state leaf against its path's spec (kind + stored
shapes), so a congruent-but-mismatched state tree raises instead of
silently pairing moments with the wrong leaves.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import conv as conv_mod
from repro.core import projector, recalibrate
from repro.core import stacked_state
from repro.core.coap_adam import (
    ConvLeaf,
    DenseLeaf,
    ProjLeaf,
    ProjectedAdamConfig,
    ProjectedAdamState,
    _array_loader,
    _bucket_cfg,
    _leaf_cfg,
    _load,
    _maybe_transplant,
    _refresh_p,
    _sched_preds,
    _store,
    _wants_transplant,
    bucket_phases,
)
from repro.core.projector import KIND_CONV, KIND_DENSE, KIND_PROJECT, path_str
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.optim import apply_updates
from repro.train.train_state import TrainState


def _allreduce_codes(x, ef, axis_name: str, block: int):
    """Int8-code all-reduce with error feedback (the sync_codes wire path).

    ``x`` is this pod's fp32 contribution, ``ef`` the replicated fp32
    error-feedback accumulator. Wire payload per step: ``numel(x)`` int8
    codes + ``ceil(numel/block)`` fp32 scales (the pmax of block absmaxes).
    Returns ``(reduced_mean, new_ef)`` where the mean carries exactly one
    blockwise rounding and ``new_ef`` is the pod-mean rounding residual
    (see the module docstring's simulation note).

    Telemetry contract (``obs/health.observe_state``): the EF sidecar this
    returns is stored on ``ProjLeaf.ef`` / ``ConvLeaf.ef`` and sampled
    HOST-SIDE at the health cadence as ``ef_rms`` — no in-collective
    instrumentation, no per-device callbacks under shard_map. A healthy
    loop keeps ``ef_rms`` bounded (the applied error telescopes, shrinking
    ~1/T over a window); a monotonically growing trajectory means the
    compensation is not being applied and fires ``EF_NOT_DRAINING``.
    """
    y = x + ef  # compensated contribution: EF applies once, in the mean
    flat = y.reshape(-1)
    n = flat.shape[0]
    nblocks = -(-n // block)
    pad = nblocks * block - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    b = flat.reshape(nblocks, block)
    # Shared per-block scale: agree on the global absmax first (a
    # scales-only exchange), so every pod's codes are commensurable and
    # the sum of codes dequantizes to the sum of quantized values exactly.
    absmax = lax.pmax(jnp.max(jnp.abs(b), axis=-1), axis_name)
    scale = absmax / 127.0
    inv = jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    q = jnp.clip(jnp.round(b * inv[:, None]), -127.0, 127.0)
    # The modeled wire: int8 codes. (Simulated as an f32 psum — integer
    # code sums are exact in f32 far beyond any real pod count.)
    qsum = lax.psum(q, axis_name)
    npods = lax.psum(jnp.ones((), jnp.float32), axis_name)

    def unpack(blocks):
        return blocks.reshape(-1)[:n].reshape(x.shape)

    red = unpack(scale[:, None] * (qsum / npods))
    deq_local = unpack(scale[:, None] * q)
    new_ef = lax.pmean(y - deq_local, axis_name)
    return red, new_ef


def _check_leaf_state(path: str, spec, leaf, lcfg: ProjectedAdamConfig, g):
    """Per-leaf structural validation (the per-leaf-branch counterpart of
    the stacked layout signature check): the state leaf's KIND and stored
    shapes must match what this path's spec implies, or moments would be
    silently paired with the wrong leaves (congruent-but-reordered state
    trees). Raises a loud ValueError naming the path."""
    want = {KIND_PROJECT: ProjLeaf, KIND_CONV: ConvLeaf}.get(
        spec.kind, DenseLeaf
    )
    if not isinstance(leaf, want):
        raise ValueError(
            f"compressed_update: state leaf at {path!r} is "
            f"{type(leaf).__name__}, expected {want.__name__} for spec kind "
            f"{spec.kind!r} — the state tree does not match the gradient "
            "tree (rules / model structure changed since init, or a "
            "reordered congruent tree was passed)"
        )

    def flat_codec_shape(numel: int):
        nblocks = -(-numel // lcfg.quant_block)
        return (nblocks, lcfg.quant_block)

    if spec.kind == KIND_PROJECT:
        # The row-block codec is shape-preserving: quantized or not, the
        # stored moment has the canonical moment shape.
        ok = tuple(leaf.m.shape) == tuple(
            projector.moment_shape(g.shape, spec)
        )
    elif spec.kind == KIND_CONV:
        csh = conv_mod.core_shape(g.shape, spec)
        core = 1
        for s in csh:
            core *= int(s)
        o, i = int(g.shape[0]), int(g.shape[1])
        want_m = flat_codec_shape(core) if lcfg.quantize else tuple(csh)
        ok = (
            tuple(leaf.p_o.shape) == (o, int(spec.rank_o))
            and tuple(leaf.p_i.shape) == (i, int(spec.rank_i))
            and tuple(leaf.m.shape) == want_m
        )
    else:
        nel = 1
        for s in g.shape:
            nel *= int(s)
        want_mu = flat_codec_shape(nel) if lcfg.quantize else tuple(g.shape)
        ok = tuple(leaf.mu.shape) == want_mu
    if not ok:
        raise ValueError(
            f"compressed_update: state leaf at {path!r} has stored shapes "
            "inconsistent with this leaf's spec — the state tree does not "
            "match the gradient tree (reordered congruent tree, or a "
            "quantize flip without stacked_state.migrate?)"
        )


def _check_ef(path: str, leaf) -> None:
    if leaf.ef is None:
        raise ValueError(
            f"compressed_update: sync_codes=True but the state leaf at "
            f"{path!r} has no error-feedback sidecar — the state was "
            "initialized by a config without sync_codes; re-initialize "
            "(or migrate) before enabling the int8 collective"
        )


def _update_proj_compressed(lcfg, leaf: ProjLeaf, g, spec, count, t, idx,
                            ph: int, axis_name: str):
    """One compressed step for one projected leaf: the single-pod unfused
    op sequence (``kref.coap_fused_update_q8`` when quantized) with the
    r-rank reduction spliced in between projection and the moment EMA."""
    gc_local = projector.to_canonical(g, spec).astype(jnp.float32)
    do_ref, _ = _sched_preds(count, ph, lcfg.t_update, lcfg.lam)
    p_old = leaf.p

    if lcfg.quantize:
        def m_loader():
            return kops.dequantize_rowblock(
                leaf.m[None], leaf.m_scale[None], block=lcfg.quant_block
            )
    else:
        def m_loader():
            return leaf.m[None].astype(jnp.float32)

    # Refresh needs the full averaged gradient (rare — every T_u steps for
    # this leaf's phase). Off refresh steps the branch is untaken and the
    # full-G all-reduce does not happen; the local value only feeds
    # _refresh_p's untaken branches.
    gc_full = lax.cond(
        do_ref, lambda: lax.pmean(gc_local, axis_name), lambda: gc_local
    )
    # B=1 lift onto the SHARED strategy/stagger refresh machinery (the
    # original flat idx keeps flora's per-leaf RNG stream unchanged; the
    # single phase (ph,) reproduces this leaf's staggered cadence).
    new_p, refreshed = _refresh_p(
        lcfg, spec, p_old[None], _array_loader(gc_full[None]), m_loader, count,
        jnp.asarray([idx], jnp.int32), (ph,),
    )
    new_p = new_p[0]
    refreshed0 = refreshed[0]

    if lcfg.quantize:
        m_q, m_s = leaf.m, leaf.m_scale
        if _wants_transplant(lcfg):
            # Match the core quantized transplant bit-for-bit: the carried
            # M pays one int8 requant→dequant round-trip on refresh steps
            # (_update_proj_bucket.carry_q — "one added block-absmax
            # rounding per refresh").
            def transplanted():
                carried = projector.project(
                    projector.backproject(m_loader()[0], p_old), new_p
                )
                return kops.quantize_rowblock(
                    carried, block=lcfg.quant_block
                )

            m_q, m_s = lax.cond(
                refreshed0, transplanted, lambda: (m_q, m_s)
            )
        # The unfused oracle schedule, inlined so the reduction replaces
        # its local projection (kref is what use_fused_kernel=False runs).
        m32 = kref.dequantize_rowblock(m_q, m_s, lcfg.quant_block)
        v32 = kref.dequantize_rowblock(leaf.v, leaf.v_scale, lcfg.quant_block)
    else:
        m32 = leaf.m.astype(jnp.float32)
        v32 = leaf.v.astype(jnp.float32)
        m32 = _maybe_transplant(lcfg, m32, p_old, new_p, refreshed0)

    # Every-step path: reduce only the r-rank projection (linearity:
    # project(pmean(G)) == pmean(project(G)) — P is replicated).
    g_proj_local = projector.project(gc_local, new_p)
    if lcfg.sync_codes:
        g_proj, new_ef = _allreduce_codes(
            g_proj_local, leaf.ef, axis_name, lcfg.quant_block
        )
    else:
        g_proj = lax.pmean(g_proj_local, axis_name)
        new_ef = leaf.ef

    new_m = lcfg.b1 * m32 + (1.0 - lcfg.b1) * g_proj
    new_v = lcfg.b2 * v32 + (1.0 - lcfg.b2) * jnp.square(g_proj)
    tf = t.astype(jnp.float32)
    delta = (new_m / (1.0 - lcfg.b1**tf)) / (
        jnp.sqrt(new_v / (1.0 - lcfg.b2**tf)) + lcfg.eps
    )
    if lcfg.quantize:  # int8-v underflow guard (see kernels/ref.py)
        delta = jnp.clip(delta, -kref.QUANT_DELTA_CLIP, kref.QUANT_DELTA_CLIP)
    update_c = projector.backproject(delta, new_p)
    update = projector.from_canonical(update_c, spec) * lcfg.update_scale

    if lcfg.quantize:
        nm, nms = kref.quantize_rowblock(new_m, lcfg.quant_block)
        nv, nvs = kref.quantize_rowblock(new_v, lcfg.quant_block)
    else:
        nm = new_m.astype(lcfg.state_dtype)
        nv = new_v.astype(lcfg.state_dtype)
        nms, nvs = leaf.m_scale, leaf.v_scale  # fp32 placeholders
    return update.astype(g.dtype), ProjLeaf(
        p=new_p, m=nm, v=nv, m_scale=nms, v_scale=nvs, ef=new_ef
    )


def _conv_refresh(lcfg, leaf: ConvLeaf, g_full32, m32, spec, count, ph, idx):
    """Strategy-aware Tucker-2 factor refresh for ONE leaf, mirroring
    ``conv.update_conv_bucket.refresh_slice`` (B=1): coap goes through the
    shared ``refresh_factors``, galore re-SVDs the canonical unfoldings,
    flora resamples with the same ``7919·idx + mode`` key folding."""
    g1 = conv_mod.mode1_canonical(g_full32)
    g2 = conv_mod.mode2_canonical(g_full32)
    if lcfg.strategy == "coap":
        _, do_recal = _sched_preds(count, ph, lcfg.t_update, lcfg.lam)
        return conv_mod.refresh_factors(
            lcfg, leaf.p_o, leaf.p_i, g1, g2, m32, do_recal
        )
    if lcfg.strategy == "galore":
        return (
            recalibrate.galore_svd(g1, spec.rank_o).astype(leaf.p_o.dtype),
            recalibrate.galore_svd(g2, spec.rank_i).astype(leaf.p_i.dtype),
        )

    # flora
    def resample(mode, canon_shape, rank, dtype):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(lcfg.seed), 7919 * idx + mode),
            count,
        )
        return recalibrate.random_projection(key, canon_shape, rank, dtype)

    return (
        resample(1, g1.shape, spec.rank_o, leaf.p_o.dtype),
        resample(2, g2.shape, spec.rank_i, leaf.p_i.dtype),
    )


def _update_conv_compressed(lcfg, leaf: ConvLeaf, g, spec, count, t, idx,
                            ph: int, axis_name: str):
    """Tucker-2 leaves: only the r_O·r_I·K1·K2 core is all-reduced each
    step; the full gradient crosses pods on factor-refresh steps only."""
    g32_local = g.astype(jnp.float32)
    do_ref, _ = _sched_preds(count, ph, lcfg.t_update, lcfg.lam)
    csh = conv_mod.core_shape(g.shape, spec)
    m32 = _load(leaf.m, leaf.m_scale, tuple(csh), lcfg)
    v32 = _load(leaf.v, leaf.v_scale, tuple(csh), lcfg)

    def conv_refreshed():
        g_full = lax.pmean(g32_local, axis_name)
        return _conv_refresh(lcfg, leaf, g_full, m32, spec, count, ph, idx)

    p_o, p_i = lax.cond(
        do_ref, conv_refreshed, lambda: (leaf.p_o, leaf.p_i)
    )
    core_local = conv_mod.project_core(g32_local, p_o, p_i)
    if lcfg.sync_codes:
        g_core, new_ef = _allreduce_codes(
            core_local, leaf.ef, axis_name, lcfg.quant_block
        )
    else:
        g_core = lax.pmean(core_local, axis_name)
        new_ef = leaf.ef
    new_m = lcfg.b1 * m32 + (1.0 - lcfg.b1) * g_core
    new_v = lcfg.b2 * v32 + (1.0 - lcfg.b2) * jnp.square(g_core)
    tf = t.astype(jnp.float32)
    delta_core = (new_m / (1.0 - lcfg.b1**tf)) / (
        jnp.sqrt(new_v / (1.0 - lcfg.b2**tf)) + lcfg.eps
    )
    if lcfg.quantize:  # int8-v underflow guard (see kernels/ref.py)
        delta_core = jnp.clip(
            delta_core, -kref.QUANT_DELTA_CLIP, kref.QUANT_DELTA_CLIP
        )
    update = conv_mod.restore_core(delta_core, p_o, p_i) * lcfg.update_scale
    sm, sms = _store(new_m, lcfg)
    sv, svs = _store(new_v, lcfg)
    return update.astype(g.dtype), ConvLeaf(
        p_o=p_o, p_i=p_i, m=sm, v=sv, m_scale=sms, v_scale=svs, ef=new_ef
    )


def _update_dense_compressed(lcfg, leaf: DenseLeaf, g, t, axis_name: str):
    """Dense leaves: classic full all-reduce + Adam (small tensors; always
    fp32 on the wire). Quantized states follow the dequant→reduce→requant
    schedule of ``kref.quantized_adam_update``."""
    g32 = lax.pmean(g.astype(jnp.float32), axis_name)
    mu = _load(leaf.mu, leaf.mu_scale, tuple(g.shape), lcfg)
    nu = _load(leaf.nu, leaf.nu_scale, tuple(g.shape), lcfg)
    new_mu = lcfg.b1 * mu + (1.0 - lcfg.b1) * g32
    new_nu = lcfg.b2 * nu + (1.0 - lcfg.b2) * jnp.square(g32)
    tf = t.astype(jnp.float32)
    upd = (new_mu / (1.0 - lcfg.b1**tf)) / (
        jnp.sqrt(new_nu / (1.0 - lcfg.b2**tf)) + lcfg.eps
    )
    if lcfg.quantize:  # int8-v underflow guard (see kernels/ref.py)
        upd = jnp.clip(upd, -kref.QUANT_DELTA_CLIP, kref.QUANT_DELTA_CLIP)
    smu, smus = _store(new_mu, lcfg)
    snu, snus = _store(new_nu, lcfg)
    return upd.astype(g.dtype), DenseLeaf(
        mu=smu, nu=snu, mu_scale=smus, nu_scale=snus
    )


def compressed_update(cfg: ProjectedAdamConfig, grads, state: ProjectedAdamState,
                      axis_name: str = "pod"):
    """Per-pod grads -> (updates, new_state) with compressed cross-pod
    reduction. Must run inside shard_map manual over ``axis_name``.

    Semantics == all-reduce(grads) then core update (linearity; the full-G
    all-reduce still happens on refresh steps, under the same lax.cond).
    Supports the full core-transform configuration space — strategies,
    stagger, per-bucket plan overrides, quantized states and the
    ``sync_codes`` int8 collective (module docstring). Any structural
    mismatch between config, state and gradient tree raises a loud
    ValueError instead of silently drifting.
    """
    count = state.count
    t = count + 1
    flat_u, treedef = jax.tree_util.tree_flatten_with_path(grads)
    # THE bucket assignment (shared with the core transform, the
    # stacked-state codec and the elastic supervisor) — drives the bucket-
    # effective configs and the staggered phase allocation even in per-leaf
    # storage mode, so refresh cadence matches the single-pod run exactly.
    layout = stacked_state.layout_for_flat(cfg.rules.spec_for, flat_u)
    # Raises on mixed-override buckets, naming the offending paths. A
    # plan's per-bucket t_update / quantize / stagger_groups become the
    # bucket-effective config here — including overrides that differ from
    # the global knobs (the schedule below is per-leaf, not global).
    bucket_cfgs = [_bucket_cfg(cfg, info) for info in layout.buckets]
    phase_by_bucket = bucket_phases(cfg, layout)

    # Per-flat-index schedule/config tables.
    lcfg_by_idx = {}
    ph_by_idx = {}
    spec_by_idx = {}
    for bi, info in enumerate(layout.buckets):
        staggerable = info.kind in (
            stacked_state.BUCKET_PROJECT, stacked_state.BUCKET_CONV
        )
        for slot, i in enumerate(info.indices):
            lcfg_by_idx[i] = bucket_cfgs[bi]
            spec_by_idx[i] = info.spec
            ph_by_idx[i] = phase_by_bucket[bi][slot] if staggerable else 0
    for tinfo in layout.tail:
        # Residual tail (custom classify only): synchronized per-leaf
        # schedule, like the core transform's tail path.
        lcfg_by_idx[tinfo.index] = _leaf_cfg(cfg, tinfo.path)
        spec_by_idx[tinfo.index] = tinfo.spec
        ph_by_idx[tinfo.index] = 0

    stacked = isinstance(state.leaves, stacked_state.StackedLeaves)
    if stacked:
        # Same structural check the core transform does: a congruent-but-
        # reordered tree must raise, never silently pair moments with the
        # wrong leaves (layout paths/indices are part of the signature).
        if state.leaves.layout.signature() != layout.signature():
            raise ValueError(
                "stacked optimizer state does not match the gradient tree "
                "(optimizer rules / model structure changed since init?)"
            )
        flat_s = [
            stacked_state.leaf_view(state.leaves, i)
            for i in range(len(flat_u))
        ]
    else:
        flat_s = treedef.flatten_up_to(state.leaves)
        for idx, ((kp, g), leaf) in enumerate(zip(flat_u, flat_s)):
            _check_leaf_state(
                path_str(kp), spec_by_idx[idx], leaf, lcfg_by_idx[idx], g
            )
    if cfg.sync_codes:
        for idx, ((kp, _), leaf) in enumerate(zip(flat_u, flat_s)):
            if spec_by_idx[idx].kind in (KIND_PROJECT, KIND_CONV):
                _check_ef(path_str(kp), leaf)

    new_updates, new_leaves = [], []
    for idx, ((kp, g), leaf) in enumerate(zip(flat_u, flat_s)):
        spec = spec_by_idx[idx]
        lcfg = lcfg_by_idx[idx]
        ph = ph_by_idx[idx]
        if spec.kind == KIND_PROJECT:
            u, nl = _update_proj_compressed(
                lcfg, leaf, g, spec, count, t, idx, ph, axis_name
            )
        elif spec.kind == KIND_CONV:
            u, nl = _update_conv_compressed(
                lcfg, leaf, g, spec, count, t, idx, ph, axis_name
            )
        elif spec.kind == KIND_DENSE:
            u, nl = _update_dense_compressed(lcfg, leaf, g, t, axis_name)
        else:
            # Future-proofing: any new projection kind must get an explicit
            # compressed schedule — loud failure, never silent fp32 drift.
            raise ValueError(
                f"compressed_update: unsupported projection kind "
                f"{spec.kind!r} at {path_str(kp)!r} — add a compressed "
                "schedule for it in distributed/compression.py"
            )
        new_updates.append(u)
        new_leaves.append(nl)
    if stacked:
        leaves_out = stacked_state.encode(state.leaves.layout, new_leaves)
    else:
        leaves_out = jax.tree_util.tree_unflatten(treedef, new_leaves)
    return (
        jax.tree_util.tree_unflatten(treedef, new_updates),
        ProjectedAdamState(count=count + 1, leaves=leaves_out),
    )


def make_compressed_train_step(model, cfg: ProjectedAdamConfig, mesh,
                               learning_rate: float):
    """COAP train step with compressed cross-pod gradient sync.

    shard_map is manual over 'pod' only; 'data'/'model' remain auto so the
    in-pod FSDP/TP sharding is still XLA-partitioned. The optimizer states
    and params are replicated across pods (pure DP) — specs P() over pod.
    """
    axis = "pod"

    def per_pod(params, opt_state, step, batch):
        def loss_fn(p):
            loss, _ = model.loss(p, batch)
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # NOTE: no pmean(grads) here — compressed_update reduces instead.
        inner = opt_state  # ProjectedAdamState
        updates, new_inner = compressed_update(cfg, grads, inner, axis)
        updates = jax.tree_util.tree_map(lambda u: -learning_rate * u, updates)
        params = apply_updates(params, updates)
        loss = lax.pmean(loss, axis)
        return params, new_inner, loss

    pspec = P()  # replicated over pod (manual axis)
    in_specs = (pspec, pspec, pspec, P(axis))
    out_specs = (pspec, pspec, pspec)
    mapped = jax.shard_map(
        per_pod, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False, axis_names={axis},
    )

    def step_fn(state: TrainState, batch):
        params, inner, loss = mapped(state.params, state.opt_state, state.step,
                                     batch)
        return (
            TrainState(step=state.step + 1, params=params, opt_state=inner),
            {"loss": loss},
        )

    return step_fn

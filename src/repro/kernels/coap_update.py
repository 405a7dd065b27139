"""Fused COAP-Adam update kernel (the paper's per-step hot loop, TPU-native).

Computes, in ONE pass over HBM:

    G_proj = G @ P            (MXU matmul, fp32 accumulation in VMEM scratch)
    M'     = β₁M + (1−β₁)G_proj
    V'     = β₂V + (1−β₂)G_proj²          (VPU epilogue on the resident tile)
    ΔW_p   = (M'/c₁) / (sqrt(V'/c₂) + ε)

Why fuse: the unfused schedule writes G_proj (m·r) to HBM, then re-reads
G_proj+M+V and writes M'+V'+ΔW — ≈ mn + 7mr words of traffic. The fused
kernel reads G once, streams P per n-block, and touches M/V exactly once:
≈ mn + (m/bm)·nr + 5mr. For LLaMA-1B shapes (m=5461, n=2048, r=512,
bm=512) that is a ~1.9× HBM-traffic reduction on the optimizer step
(measured against cost_analysis in EXPERIMENTS.md §Perf).

Tiling: grid (m/bm, n/bn), n innermost ('arbitrary') for the reduction;
blocks start at bm=512, bn=512, with all MXU dims 128-aligned. For the
back-projection kernel ``plan_two_phase_tiles`` shrinks them until the
estimated scoped VMEM (``bp_vmem_bytes``) fits the 16 MiB limit: at r=512
with fp32 G that is (512, 128). The back-projection kernel reads G and
writes ΔW as the parameter stores them (``transposed`` for m < n) and at
their true row count (a partial last row block), so no copy of either is
made around it; only the contracted axis n is padded, where it is not a
multiple of bn. The wrappers vmap over leading (layer/expert) stack axes.

bf16 gradient streaming: G blocks are DMA'd in the caller's dtype and
upcast to fp32 in VMEM (the ``astype`` inside the body), so bf16 training
halves the kernel's dominant HBM read (the m·n gradient) with fp32 MXU
accumulation — the optimizer never materializes an fp32 copy of G
(``coap_adam._update_proj_bucket`` passes the gradient through as
stored and uncast; only the unfused jnp fallbacks cast eagerly).

``coap_fused_update_bp_pallas`` additionally fuses the back-projection
``ΔW = Δ_proj Pᵀ`` as a second MXU stage in the SAME kernel: the inner grid
dimension runs 2·(n/bn) steps — phase 1 (k < kn) accumulates G@P exactly as
above; the epilogue at k = kn−1 computes Δ_proj into the accumulator
scratch; phase 2 (k ≥ kn) re-streams P per n-block and writes the (bm, bn)
tiles of Δ_proj·Pᵀ. Δ_proj never exists in HBM, and the index maps pin G to
its last block through phase 2 so G is fetched exactly once. Extra traffic
vs the non-BP kernel is one more P sweep per m-row plus the mn output —
strictly less than the unfused schedule's write+read of Δ_proj (2mr) plus
its separate backproject pass (mn + (m/bm)·nr + mn).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 512
DEFAULT_BN = 512
# fp32 products on the MXU. At its default, Mosaic multiplies fp32
# operands in one bf16 pass: on a v5e that moved ΔW at (2048, 2048, r=512)
# by up to 9% of its largest value against the fp32 oracle.
MXU_PRECISION = jax.lax.Precision.HIGHEST


def _kernel(corr_ref, g_ref, p_ref, m_ref, v_ref,
            new_m_ref, new_v_ref, delta_ref, acc_ref,
            *, b1: float, b2: float, eps: float, n_steps: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MXU: accumulate this n-block's contribution to G @ P.
    acc_ref[...] += jnp.dot(
        g_ref[...].astype(jnp.float32),
        p_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=MXU_PRECISION,
    )

    @pl.when(k == n_steps - 1)
    def _epilogue():
        g_proj = acc_ref[...]
        m = m_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        new_m = b1 * m + (1.0 - b1) * g_proj
        new_v = b2 * v + (1.0 - b2) * g_proj * g_proj
        c1 = corr_ref[0]
        c2 = corr_ref[1]
        delta = (new_m / c1) / (jnp.sqrt(new_v / c2) + eps)
        new_m_ref[...] = new_m
        new_v_ref[...] = new_v
        delta_ref[...] = delta


def _kernel_bp(corr_ref, g_ref, p_ref, m_ref, v_ref,
               new_m_ref, new_v_ref, dw_ref, acc_ref,
               *, b1: float, b2: float, eps: float, kn: int,
               transposed: bool):
    """Two-phase body: phase 1 accumulates G@P; the k==kn-1 epilogue runs the
    Adam update and parks Δ_proj in the accumulator scratch; phase 2 emits
    the back-projected tiles of ΔW = Δ_proj Pᵀ, in G's stored orientation
    (``backproject_tile``)."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k < kn)
    def _accumulate():
        acc_ref[...] += project_tile(g_ref[...], p_ref[...], transposed)

    @pl.when(k == kn - 1)
    def _epilogue():
        g_proj = acc_ref[...]
        m = m_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        new_m = b1 * m + (1.0 - b1) * g_proj
        new_v = b2 * v + (1.0 - b2) * g_proj * g_proj
        c1 = corr_ref[0]
        c2 = corr_ref[1]
        delta = (new_m / c1) / (jnp.sqrt(new_v / c2) + eps)
        new_m_ref[...] = new_m
        new_v_ref[...] = new_v
        acc_ref[...] = delta  # scratch reuse: phase 2 consumes Δ_proj

    @pl.when(k >= kn)
    def _backproject():
        dw_ref[...] = backproject_tile(acc_ref[...], p_ref[...], transposed)


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# Shared two-phase grid pieces (also used by quant8's fused int8 kernel so
# the two fused variants stay in lockstep). Both kernels read G, and write
# ΔW, in the orientation the parameter is stored in: canonical (m, n) with
# m >= n, or ``transposed`` (n, m), tiled (bn, bm). The row axis m may end
# in a partial block: Pallas reads its out-of-range rows as garbage and
# masks their writes, and every row's moments and ΔW depend on that row
# alone. The contracted axis n is padded to whole blocks, since garbage
# there would enter every row's sum.
def fused_dims(shape, transposed):
    """(m, n) of a gradient of ``shape`` stored canonical or ``transposed``
    in its last two axes."""
    rows, cols = shape[-2:]
    return (cols, rows) if transposed else (rows, cols)


def g_block(bm, bn, transposed):
    return (bn, bm) if transposed else (bm, bn)


def pin_g_index(kn, transposed=False):
    """G streams through phase 1, then stays pinned on its last block
    (index unchanged -> no phase-2 refetch)."""
    def index(i, k):
        kk = jnp.where(k < kn, k, kn - 1)
        return (kk, i) if transposed else (i, kk)
    return index


def park_out_index(kn, transposed=False):
    """ΔW tiles park on block 0 through phase 1 (no copy-out until the
    index advances), then advance one tile per phase-2 step."""
    def index(i, k):
        kk = jnp.maximum(k - kn, 0)
        return (kk, i) if transposed else (i, kk)
    return index


def project_tile(g, p, transposed):
    """Phase 1: a G tile times its (bn, r) P tile -> (bm, r), contracting
    n, which is the G tile's first axis when ``transposed``."""
    contract = (0,) if transposed else (1,)
    return jax.lax.dot_general(
        g.astype(jnp.float32), p.astype(jnp.float32),
        dimension_numbers=((contract, (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=MXU_PRECISION,
    )


def backproject_tile(delta, p, transposed):
    """Phase 2: the (bm, bn) tile Δ Pᵀ, or the (bn, bm) tile P Δᵀ when
    ``transposed``; both contract r on the MXU."""
    p = p.astype(jnp.float32)
    lhs, rhs = (p, delta) if transposed else (delta, p)
    return jax.lax.dot_general(
        lhs, rhs,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=MXU_PRECISION,
    )


def pad_contracted(g, p, bn, transposed):
    """G and P padded along n to whole ``bn`` blocks (zeros add nothing)."""
    return _pad_to(g, bn, 0 if transposed else 1), _pad_to(p, bn, 0)


def trim_contracted(dw, n, transposed):
    """ΔW without the padded n of ``pad_contracted``."""
    if (dw.shape[0] if transposed else dw.shape[1]) == n:
        return dw
    return dw[:n] if transposed else dw[:, :n]


# Mosaic's default scoped-VMEM limit for one kernel on TPU v5e, less 2 MiB
# for what the footprint estimates below do not see.
SCOPED_VMEM_BYTES = 16 * 1024 * 1024
TILE_BUDGET_BYTES = SCOPED_VMEM_BYTES - 2 * 1024 * 1024
_MIN_BN = 128
_MIN_BM = 32


def plan_two_phase_tiles(m, n, bm, bn, footprint, budget=TILE_BUDGET_BYTES):
    """Row/column tiles for a two-phase kernel, shrunk until they fit VMEM.

    ``bm``/``bn`` are first clamped to the problem. While
    ``footprint(bm, bn)`` (bytes) exceeds ``budget``, ``bn`` halves down
    to 128, then ``bm`` halves down to 32. Shrinking ``bn`` first keeps the
    HBM traffic unchanged: P is re-streamed once per row block, whatever
    the column block. Returns the last candidate even if it still does not
    fit; the compiler then reports the excess."""
    bm_eff = min(bm, max(8, m))
    bn_eff = min(bn, max(128, n))
    while footprint(bm_eff, bn_eff) > budget:
        if bn_eff > _MIN_BN:
            bn_eff //= 2
        elif bm_eff > _MIN_BM:
            bm_eff //= 2
        else:
            break
    return bm_eff, bn_eff


def bp_vmem_bytes(bm, bn, r, g_itemsize):
    """Scoped VMEM of the fp32 back-projection kernel: double-buffered
    G/P/M/V input and M'/V'/ΔW output tiles, the (bm, r) accumulator, and
    the temporaries of the fp32 (``MXU_PRECISION``) products: two (bm, bn)
    and two (bn, r) tiles and one (bm, r). Fitted to what the v5e compiler
    asks for: at r=512 with fp32 G, 19.2 MiB at (bm, bn) = (512, 512),
    16.3 at (512, 256), 12.5 at (512, 128); this gives 20, 15, 12.5."""
    tile = bm * r * 4
    inputs = bm * bn * g_itemsize + bn * r * 4 + 2 * tile
    outputs = 2 * tile + bm * bn * 4
    temps = 2 * bm * bn * 4 + 2 * bn * r * 4 + tile
    return 2 * (inputs + outputs) + tile + temps


def two_phase_compiler_params():
    """dimension_semantics for (parallel rows, arbitrary two-phase inner
    dim)."""
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


@functools.partial(
    jax.jit, static_argnames=("b1", "b2", "eps", "interpret", "bm", "bn")
)
def coap_fused_update_pallas(
    g, p, m, v, count, b1=0.9, b2=0.999, eps=1e-8,
    interpret: bool = False, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
):
    """Public entry. g (...,m,n), p (...,n,r), m/v (...,m,r) -> (m', v', Δ)."""
    if g.ndim > 2:  # stacked weights: vmap over the leading axes
        fn = functools.partial(
            coap_fused_update_pallas, b1=b1, b2=b2, eps=eps,
            interpret=interpret, bm=bm, bn=bn,
        )
        for _ in range(g.ndim - 2):
            fn = jax.vmap(fn, in_axes=(0, 0, 0, 0, None))
        return fn(g, p, m, v, count)

    m_dim, n_dim = g.shape
    r = p.shape[-1]
    t = count.astype(jnp.float32)
    corr = jnp.stack([1.0 - b1**t, 1.0 - b2**t])

    bm_eff = min(bm, max(8, m_dim))
    bn_eff = min(bn, max(128, n_dim))
    g_p = _pad_to(_pad_to(g, bm_eff, 0), bn_eff, 1)
    p_p = _pad_to(p, bn_eff, 0)
    m_p = _pad_to(m.astype(jnp.float32), bm_eff, 0)
    v_p = _pad_to(v.astype(jnp.float32), bm_eff, 0)
    mp, np_ = g_p.shape
    grid = (mp // bm_eff, np_ // bn_eff)

    kernel = functools.partial(
        _kernel, b1=b1, b2=b2, eps=eps, n_steps=grid[1]
    )
    out_shape = [
        jax.ShapeDtypeStruct((mp, r), jnp.float32),
        jax.ShapeDtypeStruct((mp, r), jnp.float32),
        jax.ShapeDtypeStruct((mp, r), jnp.float32),
    ]
    in_specs = [
        pl.BlockSpec((2,), lambda i, k: (0,)),  # corr coefficients
        pl.BlockSpec((bm_eff, bn_eff), lambda i, k: (i, k)),  # G
        pl.BlockSpec((bn_eff, r), lambda i, k: (k, 0)),  # P
        pl.BlockSpec((bm_eff, r), lambda i, k: (i, 0)),  # M
        pl.BlockSpec((bm_eff, r), lambda i, k: (i, 0)),  # V
    ]
    out_specs = [
        pl.BlockSpec((bm_eff, r), lambda i, k: (i, 0)),
        pl.BlockSpec((bm_eff, r), lambda i, k: (i, 0)),
        pl.BlockSpec((bm_eff, r), lambda i, k: (i, 0)),
    ]
    kwargs = dict(
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )
    kwargs["scratch_shapes"] = [pltpu.VMEM((bm_eff, r), jnp.float32)]
    if not interpret:
        kwargs["compiler_params"] = two_phase_compiler_params()

    new_m, new_v, delta = pl.pallas_call(
        kernel, name="coap_fused_update_pallas", **kwargs)(
        corr, g_p, p_p, m_p, v_p
    )
    return new_m[:m_dim], new_v[:m_dim], delta[:m_dim]


@functools.partial(
    jax.jit,
    static_argnames=("b1", "b2", "eps", "interpret", "bm", "bn", "transposed"),
)
def coap_fused_update_bp_pallas(
    g, p, m, v, count, b1=0.9, b2=0.999, eps=1e-8,
    interpret: bool = False, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
    transposed: bool = False,
):
    """Back-projection-fused variant: g (...,m,n), p (...,n,r), m/v (...,m,r)
    -> (m', v', ΔW (...,m,n)). Δ_proj stays in VMEM scratch. With
    ``transposed`` G is stored (...,n,m) and ΔW comes out (...,n,m)."""
    if g.ndim > 2:  # stacked weights: vmap over the leading axes
        fn = functools.partial(
            coap_fused_update_bp_pallas, b1=b1, b2=b2, eps=eps,
            interpret=interpret, bm=bm, bn=bn, transposed=transposed,
        )
        for _ in range(g.ndim - 2):
            fn = jax.vmap(fn, in_axes=(0, 0, 0, 0, None))
        return fn(g, p, m, v, count)

    m_dim, n_dim = fused_dims(g.shape, transposed)
    r = p.shape[-1]
    t = count.astype(jnp.float32)
    corr = jnp.stack([1.0 - b1**t, 1.0 - b2**t])

    gi = jnp.dtype(g.dtype).itemsize
    bm_eff, bn_eff = plan_two_phase_tiles(
        m_dim, n_dim, bm, bn, lambda a, b: bp_vmem_bytes(a, b, r, gi)
    )
    g_p, p_p = pad_contracted(g, p, bn_eff, transposed)
    kn = p_p.shape[0] // bn_eff
    grid = (pl.cdiv(m_dim, bm_eff), 2 * kn)

    kernel = functools.partial(_kernel_bp, b1=b1, b2=b2, eps=eps, kn=kn,
                               transposed=transposed)
    out_shape = [
        jax.ShapeDtypeStruct((m_dim, r), jnp.float32),
        jax.ShapeDtypeStruct((m_dim, r), jnp.float32),
        jax.ShapeDtypeStruct(g_p.shape, jnp.float32),
    ]
    blk = g_block(bm_eff, bn_eff, transposed)
    in_specs = [
        pl.BlockSpec((2,), lambda i, k: (0,)),  # corr coefficients
        pl.BlockSpec(blk, pin_g_index(kn, transposed)),  # G
        pl.BlockSpec((bn_eff, r), lambda i, k: (k % kn, 0)),  # P (both phases)
        pl.BlockSpec((bm_eff, r), lambda i, k: (i, 0)),  # M
        pl.BlockSpec((bm_eff, r), lambda i, k: (i, 0)),  # V
    ]
    out_specs = [
        pl.BlockSpec((bm_eff, r), lambda i, k: (i, 0)),
        pl.BlockSpec((bm_eff, r), lambda i, k: (i, 0)),
        pl.BlockSpec(blk, park_out_index(kn, transposed)),  # ΔW
    ]
    kwargs = dict(
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )
    kwargs["scratch_shapes"] = [pltpu.VMEM((bm_eff, r), jnp.float32)]
    if not interpret:
        kwargs["compiler_params"] = two_phase_compiler_params()

    new_m, new_v, dw = pl.pallas_call(
        kernel, name="coap_fused_update_bp_pallas", **kwargs)(
        corr, g_p, p_p, m.astype(jnp.float32), v.astype(jnp.float32)
    )
    return new_m, new_v, trim_contracted(dw, n_dim, transposed)

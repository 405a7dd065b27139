"""Block-wise absmax int8 quantization kernels (8-bit COAP states).

Two codecs, two families of kernels:

FLAT codec (dense Adam states): tensors are viewed as (nblocks, 256) after
ravel — 256 = 2×VPU lane width — with one fp32 scale per block:

  * quantize:   x -> (q, scale)         scale = absmax/127, q = round(x/scale)
  * dequantize: (q, scale) -> x
  * fused 8-bit Adam step: dequant M,V -> moment EMA + ΔW -> requant, one
    VMEM round trip.

ROW-BLOCK codec (projected COAP states, see kernels/ref.py): an (..., m, r)
moment keeps its shape in int8 with ceil(r/256) scales per row, so a
row-tile (bm, r) dequantizes in VMEM from its own scales alone. On top of it
``coap_fused_update_q8_pallas`` runs the ENTIRE 8-bit COAP step as one
kernel — a single HBM pass per tensor:

    phase 1 (k < kn):    acc += G(i,k) @ P(k)          (MXU)
    epilogue (k = kn-1): dequant int8 M/V tiles in VMEM; moment EMA;
                         bias-corrected Δ with the QUANT_DELTA_CLIP
                         underflow guard; requant M'/V' -> int8 outputs;
                         park Δ in the accumulator scratch          (VPU)
    phase 2 (k >= kn):   ΔW(i,k-kn) = Δ @ P(k-kn)ᵀ                 (MXU)

Neither fp32 M/V nor Δ_proj ever exist in HBM — the memory AND traffic wins
of the paper's 8-bit path hold at peak, instead of only for the at-rest
state. The unfused schedule (dequant + project + Adam + requant +
backproject as separate dispatches) reads/writes every intermediate through
HBM and is kept only as the benchmark baseline (benchmarks/overhead.py).

Like the fp32 fused kernels, ``coap_fused_update_q8_pallas`` accepts bf16 G
and upcasts per-tile in VMEM — with int8 states AND a bf16 gradient stream
the whole 8-bit step moves ~mn·2 + 2mr·1 bytes of tensor traffic.

Hardware adaptation note (DESIGN.md §3): Dettmers' dynamic-tree codebook is
a CUDA-LUT trick; linear absmax maps onto the TPU VPU (mul + round + clip)
with no gather. Same state size, slightly coarser tails. TPU tiling note:
int8 tiles are (32, 128); the fused kernel's row tiles (bm, r) satisfy this
for bm ≥ 32 and r a lane multiple, and ragged r is exercised under
interpret mode (tests) where tiling is unconstrained. Like the fp32 fused
kernel it reads G and writes ΔW as stored, transposed or not, and ends in a
partial row block where m is not a multiple of bm: rows are independent,
so the out-of-range rows Pallas reads there reach no output it writes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref
from repro.kernels.ref import QUANT_BLOCK, QUANT_DELTA_CLIP, rowblock_nblocks

ROWS_PER_PROGRAM = 64  # (64, 256) int8 tiles: fits the int8 (32,128) layout
DEFAULT_BM = 512  # fused-q8 row tile: fewer P sweeps (2·ceil(m/bm)·nr words
# of internal re-stream); ``plan_two_phase_tiles`` shrinks the tiles to fit
# VMEM (``q8_vmem_bytes``).
DEFAULT_BN = 512  # fused-q8 G column block


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = absmax / 127.0
    inv = jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    q = jnp.clip(jnp.round(x * inv), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def _fused8_kernel(corr_ref, g_ref, mq_ref, ms_ref, vq_ref, vs_ref,
                   nmq_ref, nms_ref, nvq_ref, nvs_ref, delta_ref,
                   *, b1, b2, eps):
    g = g_ref[...].astype(jnp.float32)
    m = mq_ref[...].astype(jnp.float32) * ms_ref[...]
    v = vq_ref[...].astype(jnp.float32) * vs_ref[...]
    new_m = b1 * m + (1.0 - b1) * g
    new_v = b2 * v + (1.0 - b2) * g * g
    delta = (new_m / corr_ref[0]) / (jnp.sqrt(new_v / corr_ref[1]) + eps)
    delta_ref[...] = jnp.clip(delta, -QUANT_DELTA_CLIP, QUANT_DELTA_CLIP)

    def requant(x, q_out, s_out):
        absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        scale = absmax / 127.0
        inv = jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
        q_out[...] = jnp.clip(jnp.round(x * inv), -127, 127).astype(jnp.int8)
        s_out[...] = scale

    requant(new_m, nmq_ref, nms_ref)
    requant(new_v, nvq_ref, nvs_ref)


def _to_blocks(x, block):
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % block
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(-1, block)


def _row_pad(x, rows):
    pad = (-x.shape[0]) % rows
    if pad:
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    return x


# shared two-phase grid pieces (same tiling semantics as the fp32 fused
# kernel — see coap_update.py)
from repro.kernels.coap_update import (  # noqa: E402
    backproject_tile,
    fused_dims,
    g_block,
    pad_contracted,
    park_out_index,
    pin_g_index,
    plan_two_phase_tiles,
    project_tile,
    trim_contracted,
    two_phase_compiler_params,
)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def quantize_blockwise_pallas(x, block=QUANT_BLOCK, interpret=False):
    blocks = _to_blocks(x.astype(jnp.float32), block)
    nblocks = blocks.shape[0]
    rows = min(ROWS_PER_PROGRAM, nblocks)
    bp = _row_pad(blocks, rows)
    grid = (bp.shape[0] // rows,)
    q, s = pl.pallas_call(
        _quant_kernel,
        name="quantize_blockwise_pallas",
        grid=grid,
        in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rows, block), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(bp.shape, jnp.int8),
            jax.ShapeDtypeStruct((bp.shape[0], 1), jnp.float32),
        ],
        interpret=interpret,
    )(bp)
    return q[:nblocks], s[:nblocks, 0]


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "block", "interpret"))
def dequantize_blockwise_pallas(q, scale, shape, dtype=jnp.float32,
                                block=QUANT_BLOCK, interpret=False):
    nblocks = q.shape[0]
    rows = min(ROWS_PER_PROGRAM, nblocks)
    qp = _row_pad(q, rows)
    sp = _row_pad(scale[:, None], rows)
    grid = (qp.shape[0] // rows,)
    x = pl.pallas_call(
        _dequant_kernel,
        name="dequantize_blockwise_pallas",
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, block), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, jnp.float32),
        interpret=interpret,
    )(qp, sp)
    size = 1
    for s_ in shape:
        size *= s_
    return x.reshape(-1)[:size].reshape(shape).astype(dtype)


@functools.partial(
    jax.jit, static_argnames=("b1", "b2", "eps", "block", "interpret")
)
def quantized_adam_update_pallas(
    g_proj, m_q, m_scale, v_q, v_scale, count,
    b1=0.9, b2=0.999, eps=1e-8, block=QUANT_BLOCK, interpret=False,
):
    shape = g_proj.shape
    gb = _to_blocks(g_proj.astype(jnp.float32), block)
    nblocks = gb.shape[0]
    assert m_q.shape[0] == nblocks, (m_q.shape, nblocks)
    rows = min(ROWS_PER_PROGRAM, nblocks)
    gp = _row_pad(gb, rows)
    mqp, vqp = _row_pad(m_q, rows), _row_pad(v_q, rows)
    msp, vsp = _row_pad(m_scale[:, None], rows), _row_pad(v_scale[:, None], rows)
    grid = (gp.shape[0] // rows,)
    t = count.astype(jnp.float32)
    corr = jnp.stack([1.0 - b1**t, 1.0 - b2**t])

    row_spec = pl.BlockSpec((rows, block), lambda i: (i, 0))
    s_spec = pl.BlockSpec((rows, 1), lambda i: (i, 0))
    npad = gp.shape[0]
    nmq, nms, nvq, nvs, delta = pl.pallas_call(
        functools.partial(_fused8_kernel, b1=b1, b2=b2, eps=eps),
        name="quantized_adam_update_pallas",
        grid=grid,
        in_specs=[pl.BlockSpec((2,), lambda i: (0,)), row_spec, row_spec,
                  s_spec, row_spec, s_spec],
        out_specs=[row_spec, s_spec, row_spec, s_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((npad, block), jnp.int8),
            jax.ShapeDtypeStruct((npad, 1), jnp.float32),
            jax.ShapeDtypeStruct((npad, block), jnp.int8),
            jax.ShapeDtypeStruct((npad, 1), jnp.float32),
            jax.ShapeDtypeStruct((npad, block), jnp.float32),
        ],
        interpret=interpret,
    )(corr, gp, mqp, msp, vqp, vsp)
    size = 1
    for s_ in shape:
        size *= s_
    delta_full = delta.reshape(-1)[:size].reshape(shape)
    return nmq[:nblocks], nms[:nblocks, 0], nvq[:nblocks], nvs[:nblocks, 0], delta_full


# ---------------------------------------------------------------------------
# Single-pass fused 8-bit COAP step (row-block codec; see module docstring)
# ---------------------------------------------------------------------------
def _dequant_rowblock_tile(q, s, block):
    """(bm, r) int8 tile + (bm, nblk) scales -> fp32, in VMEM. The codec is
    defined ONCE in kernels/ref.py — this just traces those jnp ops inside
    the kernel body (with a cheap broadcast shortcut for the 1-block case).
    """
    if s.shape[-1] == 1:
        return q.astype(jnp.float32) * s
    return _ref.dequantize_rowblock(q, s, block)


def _requant_rowblock_tile(x, q_ref, s_ref, block):
    """fp32 (bm, r) tile -> int8 codes + per-row-block scales, in VMEM.
    Bit-for-bit the ref codec, by construction: it IS ref.quantize_rowblock
    traced into the kernel."""
    q, s = _ref.quantize_rowblock(x, block)
    q_ref[...] = q
    s_ref[...] = s


def q8_vmem_bytes(bm, bn, r, nblk, g_itemsize):
    """Scoped VMEM of the fused int8 kernel: double-buffered G/P and int8
    M/V (+ lane-padded scale) tiles in and out, the (bm, r) accumulator,
    and six (bm, r) fp32 temporaries of the dequant/Adam/requant epilogue.
    At r=512 with fp32 G the v5e compiler asks 16.1 MiB at (bm, bn) =
    (512, 512), 13.1 at (512, 256), 11.7 at (512, 128); this gives 17,
    14, 12.5."""
    tile = bm * r * 4
    state = bm * r + bm * (-(-nblk // 128) * 128) * 4  # int8 codes + scales
    inputs = bm * bn * g_itemsize + bn * r * 4 + 2 * state
    outputs = 2 * state + bm * bn * 4
    return 2 * (inputs + outputs) + tile + 6 * tile


def q8_tiles(m, n, r, nblk, g_itemsize, bm=DEFAULT_BM, bn=DEFAULT_BN):
    """(bm, bn) of the fused int8 kernel for canonical (m, n) at rank r,
    shrunk until ``q8_vmem_bytes`` fits."""
    return plan_two_phase_tiles(
        m, n, bm, bn, lambda a, b: q8_vmem_bytes(a, b, r, nblk, g_itemsize)
    )


def _fused8_proj_kernel(corr_ref, g_ref, p_ref, mq_ref, ms_ref, vq_ref, vs_ref,
                        nmq_ref, nms_ref, nvq_ref, nvs_ref, dw_ref, acc_ref,
                        *, b1, b2, eps, kn, block, transposed):
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
        m = _dequant_rowblock_tile(mq_ref[...], ms_ref[...], block)
        v = _dequant_rowblock_tile(vq_ref[...], vs_ref[...], block)
        new_m = b1 * m + (1.0 - b1) * g_proj
        new_v = b2 * v + (1.0 - b2) * jnp.square(g_proj)
        delta = (new_m / corr_ref[0]) / (jnp.sqrt(new_v / corr_ref[1]) + eps)
        delta = jnp.clip(delta, -QUANT_DELTA_CLIP, QUANT_DELTA_CLIP)
        _requant_rowblock_tile(new_m, nmq_ref, nms_ref, block)
        _requant_rowblock_tile(new_v, nvq_ref, nvs_ref, block)
        acc_ref[...] = delta  # scratch reuse: phase 2 consumes Δ_proj

    @pl.when(k >= kn)
    def _backproject():
        dw_ref[...] = backproject_tile(acc_ref[...], p_ref[...], transposed)


@functools.partial(
    jax.jit,
    static_argnames=("b1", "b2", "eps", "block", "interpret", "bm", "bn",
                     "transposed"),
)
def coap_fused_update_q8_pallas(
    g, p, m_q, m_scale, v_q, v_scale, count,
    b1=0.9, b2=0.999, eps=1e-8, block=QUANT_BLOCK,
    interpret: bool = False, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
    transposed: bool = False,
):
    """One-kernel 8-bit COAP step. g (...,m,n), p (...,n,r), int8 moments
    (...,m,r) with (...,m,nblk) scales -> (m_q', m_s', v_q', v_s', ΔW).
    With ``transposed`` G is stored (...,n,m) and ΔW comes out (...,n,m):
    the kernel tiles the stored array and no copy of G or ΔW is made.
    Broadcasts over leading (layer/expert) stack axes via vmap."""
    if g.ndim > 2:
        fn = functools.partial(
            coap_fused_update_q8_pallas, b1=b1, b2=b2, eps=eps, block=block,
            interpret=interpret, bm=bm, bn=bn, transposed=transposed,
        )
        for _ in range(g.ndim - 2):
            fn = jax.vmap(fn, in_axes=(0, 0, 0, 0, 0, 0, None))
        return fn(g, p, m_q, m_scale, v_q, v_scale, count)

    m_dim, n_dim = fused_dims(g.shape, transposed)
    r = p.shape[-1]
    nblk = rowblock_nblocks(r, block)
    assert m_scale.shape[-1] == nblk, (m_scale.shape, nblk)
    t = count.astype(jnp.float32)
    corr = jnp.stack([1.0 - b1**t, 1.0 - b2**t])

    bm_eff, bn_eff = q8_tiles(m_dim, n_dim, r, nblk,
                              jnp.dtype(g.dtype).itemsize, bm, bn)
    g_p, p_p = pad_contracted(g, p, bn_eff, transposed)
    kn = p_p.shape[0] // bn_eff
    grid = (pl.cdiv(m_dim, bm_eff), 2 * kn)

    kernel = functools.partial(
        _fused8_proj_kernel, b1=b1, b2=b2, eps=eps, kn=kn, block=block,
        transposed=transposed,
    )
    row_q = pl.BlockSpec((bm_eff, r), lambda i, k: (i, 0))
    row_s = pl.BlockSpec((bm_eff, nblk), lambda i, k: (i, 0))
    blk = g_block(bm_eff, bn_eff, transposed)
    in_specs = [
        pl.BlockSpec((2,), lambda i, k: (0,)),  # corr coefficients
        pl.BlockSpec(blk, pin_g_index(kn, transposed)),  # G
        pl.BlockSpec((bn_eff, r), lambda i, k: (k % kn, 0)),  # P (both phases)
        row_q, row_s, row_q, row_s,  # int8 M/V + scales
    ]
    out_specs = [
        row_q, row_s, row_q, row_s,
        pl.BlockSpec(blk, park_out_index(kn, transposed)),  # ΔW (phase 2)
    ]
    kwargs = dict(
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((m_dim, r), jnp.int8),
            jax.ShapeDtypeStruct((m_dim, nblk), jnp.float32),
            jax.ShapeDtypeStruct((m_dim, r), jnp.int8),
            jax.ShapeDtypeStruct((m_dim, nblk), jnp.float32),
            jax.ShapeDtypeStruct(g_p.shape, jnp.float32),
        ],
        interpret=interpret,
    )
    kwargs["scratch_shapes"] = [pltpu.VMEM((bm_eff, r), jnp.float32)]
    if not interpret:
        kwargs["compiler_params"] = two_phase_compiler_params()

    nmq, nms, nvq, nvs, dw = pl.pallas_call(
        kernel, name="coap_fused_update_q8_pallas", **kwargs)(
        corr, g_p, p_p, m_q, m_scale, v_q, v_scale
    )
    return nmq, nms, nvq, nvs, trim_contracted(dw, n_dim, transposed)

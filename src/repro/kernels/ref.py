"""Pure-jnp oracles for every Pallas kernel in this package.

These are the *canonical semantics*: kernels must match them bit-for-bit in
fp32 (tests sweep shapes/dtypes with ``interpret=True``). They are also the
CPU execution path — ``ops.py`` dispatches to these off-TPU, so the whole
framework runs (slowly but exactly) in this container.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

QUANT_BLOCK = 256  # VPU lane width (128) x 2; absmax granularity for int8 states.
# Linear absmax int8 can quantize tiny second-moment entries to 0 while the
# first moment stays nonzero -> m/(sqrt(0)+eps) explodes (observed divergence
# in examples/finetune_compare.py). Dynamic-tree codebooks avoid this by
# construction; our TPU-friendly linear codec instead clips the bias-corrected
# update elementwise (normal Adam updates are |d| <~ 3, so 5 is inert).
QUANT_DELTA_CLIP = 5.0
# The optimizer's projections are fp32 products, as in the fused kernels
# (``coap_update.MXU_PRECISION``); XLA's default on TPU is one bf16 pass.
HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Fused COAP-Adam update (kernel: coap_update.py)
# ---------------------------------------------------------------------------
def coap_fused_update(
    g: jnp.ndarray,  # (m, n) canonical gradient tile
    p: jnp.ndarray,  # (n, r) projection
    m: jnp.ndarray,  # (m, r) first moment (fp32)
    v: jnp.ndarray,  # (m, r) second moment (fp32)
    count: jnp.ndarray,  # scalar int32, 1-based step for bias correction
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One projected-Adam step: G@P on the MXU + moment EMA + bias-corrected
    ΔW_proj epilogue. Returns (new_m, new_v, delta_w_proj) — all (m, r) fp32.
    Broadcasts over leading (layer/expert) stack axes.
    """
    g_proj = jnp.einsum(
        "...mn,...nr->...mr", g.astype(jnp.float32), p.astype(jnp.float32),
        precision=HIGHEST,
    )
    new_m = b1 * m + (1.0 - b1) * g_proj
    new_v = b2 * v + (1.0 - b2) * jnp.square(g_proj)
    t = count.astype(jnp.float32)
    c1 = 1.0 - b1**t
    c2 = 1.0 - b2**t
    delta = (new_m / c1) / (jnp.sqrt(new_v / c2) + eps)
    return new_m, new_v, delta


def coap_fused_update_bp(
    g: jnp.ndarray,
    p: jnp.ndarray,
    m: jnp.ndarray,
    v: jnp.ndarray,
    count: jnp.ndarray,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``coap_fused_update`` with the back-projection fused in: returns
    (new_m, new_v, ΔW) where ``ΔW = Δ_proj Pᵀ`` is the full (m, n) canonical
    update — Δ_proj is never a caller-visible (HBM) tensor.
    """
    new_m, new_v, delta = coap_fused_update(g, p, m, v, count, b1, b2, eps)
    dw = jnp.einsum("...mr,...nr->...mn", delta, p.astype(jnp.float32),
                    precision=HIGHEST)
    return new_m, new_v, dw


# ---------------------------------------------------------------------------
# Block-wise absmax int8 quantization (kernel: quant8.py)
# ---------------------------------------------------------------------------
def _flat_padded(x: jnp.ndarray, block: int) -> Tuple[jnp.ndarray, int]:
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % block
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat, pad


def quantize_blockwise(
    x: jnp.ndarray, block: int = QUANT_BLOCK
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (any shape) -> (q int8 [nblocks, block], scale f32 [nblocks])."""
    flat, _ = _flat_padded(x.astype(jnp.float32), block)
    blocks = flat.reshape(-1, block)
    absmax = jnp.max(jnp.abs(blocks), axis=-1)
    scale = absmax / 127.0
    inv = jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    q = jnp.clip(jnp.round(blocks * inv[:, None]), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_blockwise(
    q: jnp.ndarray, scale: jnp.ndarray, shape: Tuple[int, ...], dtype=jnp.float32
) -> jnp.ndarray:
    """(q [nblocks, block], scale [nblocks]) -> original-shape array."""
    flat = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)
    size = 1
    for s in shape:
        size *= s
    return flat[:size].reshape(shape).astype(dtype)


def quantized_adam_update(
    g_proj: jnp.ndarray,  # (m, r) fresh projected gradient
    m_q: jnp.ndarray,
    m_scale: jnp.ndarray,
    v_q: jnp.ndarray,
    v_scale: jnp.ndarray,
    count: jnp.ndarray,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    block: int = QUANT_BLOCK,
):
    """Fused dequant -> Adam moment update -> requant (8-bit COAP step).

    Returns (new_m_q, new_m_scale, new_v_q, new_v_scale, delta_w_proj).
    """
    shape = g_proj.shape
    m = dequantize_blockwise(m_q, m_scale, shape)
    v = dequantize_blockwise(v_q, v_scale, shape)
    g32 = g_proj.astype(jnp.float32)
    new_m = b1 * m + (1.0 - b1) * g32
    new_v = b2 * v + (1.0 - b2) * jnp.square(g32)
    t = count.astype(jnp.float32)
    delta = (new_m / (1.0 - b1**t)) / (jnp.sqrt(new_v / (1.0 - b2**t)) + eps)
    delta = jnp.clip(delta, -QUANT_DELTA_CLIP, QUANT_DELTA_CLIP)
    nmq, nms = quantize_blockwise(new_m, block)
    nvq, nvs = quantize_blockwise(new_v, block)
    return nmq, nms, nvq, nvs, delta


# ---------------------------------------------------------------------------
# Row-block int8 codec + single-pass fused 8-bit COAP step (kernel: quant8.py)
# ---------------------------------------------------------------------------
# The flat codec above views a tensor as (nblocks, 256) after ravel — fine
# for dense Adam states, but its blocks straddle row boundaries of an
# (..., m, r) moment, so a kernel tiled over rows cannot dequantize a tile
# without neighbouring rows' scales. The ROW-BLOCK codec quantizes along the
# LAST axis only: each row carries ceil(r/block) scales for its own
# ``block``-wide segments (ragged tail allowed). Row tiles are then
# self-contained: (bm, r) int8 + (bm, nblk) scales dequantize in VMEM with
# no cross-tile traffic, which is what lets the 8-bit optimizer step run as
# ONE kernel. For r a multiple of ``block`` the codes are identical to the
# flat codec's; only the scale layout differs.


def rowblock_nblocks(r: int, block: int = QUANT_BLOCK) -> int:
    return -(-int(r) // int(block))


def quantize_rowblock(
    x: jnp.ndarray, block: int = QUANT_BLOCK
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (..., r) -> (q int8 (..., r), scale f32 (..., nblk))."""
    r = x.shape[-1]
    nblk = rowblock_nblocks(r, block)
    pad = nblk * block - r
    x32 = x.astype(jnp.float32)
    if pad:
        x32 = jnp.pad(x32, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    b = x32.reshape(x.shape[:-1] + (nblk, block))
    absmax = jnp.max(jnp.abs(b), axis=-1)
    scale = absmax / 127.0
    inv = jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    q = jnp.clip(jnp.round(b * inv[..., None]), -127, 127)
    q = q.reshape(x.shape[:-1] + (nblk * block,))[..., :r]
    return q.astype(jnp.int8), scale


def dequantize_rowblock(
    q: jnp.ndarray,
    scale: jnp.ndarray,
    block: int = QUANT_BLOCK,
    dtype=jnp.float32,
) -> jnp.ndarray:
    """(q (..., r), scale (..., nblk)) -> fp tensor of q's shape."""
    r = q.shape[-1]
    nblk = scale.shape[-1]
    pad = nblk * block - r
    q32 = q.astype(jnp.float32)
    if pad:
        q32 = jnp.pad(q32, [(0, 0)] * (q.ndim - 1) + [(0, pad)])
    b = q32.reshape(q.shape[:-1] + (nblk, block)) * scale[..., None]
    return b.reshape(q.shape[:-1] + (nblk * block,))[..., :r].astype(dtype)


def rowblock_code_stats(
    q: jnp.ndarray, scale: jnp.ndarray, block: int = QUANT_BLOCK
) -> dict:
    """Codec-health stats of a row-block-coded tensor (``obs/health``).

    Absmax scaling never clips by construction (the block max maps onto
    ±127 exactly), so "saturation" here is the EXCESS rail fraction: the
    share of codes at |q| == 127 beyond the one absmax element each
    nonzero block is guaranteed to park there. That baseline-corrects the
    metric against block geometry (a rank-4 moment row has 1/4 of its
    codes at the rail when healthy) — ~0 for a well-spread block, rising
    when a block's mass collapses onto its absmax — complemented by the
    non-finite-scale fraction (an inf/nan input poisons its block's
    absmax, the loud overflow signal the int8-v underflow/overflow guards
    key on). ``err_rel`` is the uniform quant-noise model:
    rms(step)/sqrt(12) over rms(value), with step == scale
    (scale = absmax/127 IS the quantization step).
    Returns jnp scalars (caller does one device_get)."""
    absq = jnp.abs(q.astype(jnp.int32))
    n_codes = jnp.asarray(absq.size, jnp.float32)
    n_rail = jnp.sum((absq == 127).astype(jnp.float32))
    # One guaranteed rail element per block that has any nonzero code.
    finite0 = jnp.isfinite(scale)
    n_live = jnp.sum(
        ((scale > 0) | ~finite0).astype(jnp.float32)
    )
    sat_rate = jnp.maximum(n_rail - n_live, 0.0) / jnp.maximum(n_codes, 1.0)
    finite = finite0
    nonfinite = 1.0 - jnp.mean(finite.astype(jnp.float32))
    safe_scale = jnp.where(finite, scale, 0.0)
    n_finite = jnp.maximum(jnp.sum(finite.astype(jnp.float32)), 1.0)
    step_ms = jnp.sum(jnp.square(safe_scale)) / n_finite
    err_rms = jnp.sqrt(step_ms / 12.0)
    deq = dequantize_rowblock(q, safe_scale, block)
    val_rms = jnp.sqrt(jnp.mean(jnp.square(deq)))
    return {
        "sat_rate": sat_rate,
        "scale_nonfinite": nonfinite,
        "err_rel": err_rms / jnp.maximum(val_rms, 1e-30),
    }


def coap_fused_update_q8(
    g: jnp.ndarray,  # (..., m, n) canonical gradient
    p: jnp.ndarray,  # (..., n, r) projection
    m_q: jnp.ndarray,  # (..., m, r) int8 first moment (row-block codec)
    m_scale: jnp.ndarray,  # (..., m, nblk) f32
    v_q: jnp.ndarray,
    v_scale: jnp.ndarray,
    count: jnp.ndarray,  # scalar int32, 1-based step
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    block: int = QUANT_BLOCK,
):
    """One 8-bit COAP step in a single logical pass (the paper's quantized
    hot loop): project ``G P``, dequantize the int8 moments, moment EMA +
    bias-corrected Δ with the underflow clip, requantize M'/V', and
    back-project ``Δ Pᵀ``. Neither fp32 moments nor Δ_proj are caller-visible
    tensors. Returns (new_m_q, new_m_scale, new_v_q, new_v_scale, ΔW).
    """
    m = dequantize_rowblock(m_q, m_scale, block)
    v = dequantize_rowblock(v_q, v_scale, block)
    g_proj = jnp.einsum(
        "...mn,...nr->...mr", g.astype(jnp.float32), p.astype(jnp.float32),
        precision=HIGHEST,
    )
    new_m = b1 * m + (1.0 - b1) * g_proj
    new_v = b2 * v + (1.0 - b2) * jnp.square(g_proj)
    t = count.astype(jnp.float32)
    delta = (new_m / (1.0 - b1**t)) / (jnp.sqrt(new_v / (1.0 - b2**t)) + eps)
    delta = jnp.clip(delta, -QUANT_DELTA_CLIP, QUANT_DELTA_CLIP)
    dw = jnp.einsum("...mr,...nr->...mn", delta, p.astype(jnp.float32),
                    precision=HIGHEST)
    nmq, nms = quantize_rowblock(new_m, block)
    nvq, nvs = quantize_rowblock(new_v, block)
    return nmq, nms, nvq, nvs, dw


# ---------------------------------------------------------------------------
# Fused Eqn-6 refresh (kernel: eqn6.py)
# ---------------------------------------------------------------------------
def eqn6_sgd_update(
    p: jnp.ndarray,  # (..., n, r) projection
    g: jnp.ndarray,  # (..., m, n) canonical gradient (fp32 or bf16)
    m_proj: jnp.ndarray,  # (..., m, r) projected first moment
    lr: float = 0.1,
    steps: int = 1,
    normalize: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Oracle for the fused Eqn-6 kernel: ``steps`` SGD iterations on the
    paper's Eqn-6 objective. The closed-form math lives in
    ``core/correlation.py`` (single source of truth — lazily imported here
    because core sits above the kernels layer); this wrapper only re-exposes
    it in the kernel's signature: returns ``(new_p, last_val, last_grad)``
    where val/grad belong to the last iteration's pre-update P.
    ``normalize=True`` pre-scales G and M_proj by 1/rms(G) exactly as
    ``correlation.sgd_update(normalize=True)`` does (the kernel's first
    grid phase computes the same factor).
    """
    from repro.core import correlation  # lazy: avoids core<->kernels cycle

    p32 = p.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    mp32 = m_proj.astype(jnp.float32)
    if normalize:
        rms = jnp.sqrt(
            jnp.mean(jnp.square(g32), axis=(-1, -2), keepdims=True)
        ) + correlation._EPS
        g32 = g32 / rms
        mp32 = mp32 / rms

    def body(_, carry):
        p_cur, _, _ = carry
        val, grad = correlation.loss_and_grad(p_cur, g32, mp32)
        return (p_cur - lr * grad, val, grad)

    init = (p32, jnp.zeros(g.shape[:-2], jnp.float32), jnp.zeros_like(p32))
    new_p, val, grad = jax.lax.fori_loop(0, steps, body, init)
    return new_p.astype(p.dtype), val, grad


# ---------------------------------------------------------------------------
# RMSNorm (kernel: rmsnorm.py) — model-side hot spot for long-context decode
# ---------------------------------------------------------------------------
def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)

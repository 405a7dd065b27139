"""Flash attention as a Pallas TPU kernel (fwd + custom-VJP bwd).

THE model-side hot-spot kernel: the dry-run showed every train/prefill cell
HBM-bound on attention-score traffic (naive: ~8 full (T,S)-sized tensor
passes per layer; pure-JAX chunking does NOT help training because scan
autodiff stores every tile as a residual — measured in EXPERIMENTS.md
§Perf). The kernel keeps the running-softmax state in VMEM, so per layer
the only HBM traffic is q, k, v, o (+ the (T,) lse statistics): the classic
FlashAttention schedule adapted to the MXU/VMEM hierarchy.

Layout: q (BH, T, hd), k/v (BKH, S, hd) — batch×heads flattened into the
leading grid axis; GQA maps q-head → kv-head in the BlockSpec index map.
Grid (bh, nq, nk), kv innermost ('arbitrary') with VMEM scratch
accumulators; the epilogue at the last kv block writes o and lse.

Backward: two Pallas kernels sharing the recompute-from-(q,k,v,lse) trick —
  * dkv pass: grid (bkh, nk, nq): accumulates dk, dv over query blocks.
  * dq  pass: grid (bh,  nq, nk): accumulates dq over kv blocks.
``delta = rowsum(do ⊙ o)`` is precomputed (cheap elementwise jnp).

Supports causal masking, sliding windows and logit softcap (grok).
Validated against the naive jnp oracle in tests/test_flash_attention.py
(interpret mode, values + grads).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_QB = 512
DEFAULT_KB = 512


def _mask(q0, k0, qb, kb, s_real, causal, window):
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
    keep = k_pos < s_real
    if causal:
        keep &= k_pos <= q_pos
    if window is not None:
        keep &= k_pos > q_pos - window
    return keep


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, window, softcap, nk, kb, s_real):
    qi, ki = pl.program_id(1), pl.program_id(2)
    qb = q_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)  # (qb, hd)
    k = k_ref[0].astype(jnp.float32)  # (kb, hd)
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (qb, kb)
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    keep = _mask(qi * qb, ki * kb, qb, kb, s_real, causal, window)
    logits = jnp.where(keep, logits, NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _epilogue():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = (m_scr[...] + jnp.log(l_safe))[:, 0]


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, causal, window, softcap, nq, qb, s_real, group):
    ki, qi = pl.program_id(1), pl.program_id(2)
    kb = k_ref.shape[1]

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0].astype(jnp.float32)  # (qb, hd)
    k = k_ref[0].astype(jnp.float32)  # (kb, hd)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)  # (qb, hd)
    lse = lse_ref[0]  # (qb,)
    delta = delta_ref[0]  # (qb,)

    raw = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (qb, kb)
    if softcap is not None:
        capped = softcap * jnp.tanh(raw / softcap)
        dcap = 1.0 - (capped / softcap) ** 2  # d capped / d raw
    else:
        capped, dcap = raw, None
    keep = _mask(qi * qb, ki * kb, qb, kb, s_real, causal, window)
    logits = jnp.where(keep, capped, NEG_INF)
    p = jnp.exp(logits - lse[:, None])  # (qb, kb) softmax probs
    dv_scr[...] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (qb, kb)
    ds = p * (dp - delta[:, None])
    if dcap is not None:
        ds = ds * dcap
    ds = jnp.where(keep, ds, 0.0) * scale
    dk_scr[...] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(qi == nq - 1)
    def _epilogue():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale, causal, window, softcap, nk, kb, s_real):
    qi, ki = pl.program_id(1), pl.program_id(2)
    qb = q_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]
    delta = delta_ref[0]

    raw = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if softcap is not None:
        capped = softcap * jnp.tanh(raw / softcap)
        dcap = 1.0 - (capped / softcap) ** 2
    else:
        capped, dcap = raw, None
    keep = _mask(qi * qb, ki * kb, qb, kb, s_real, causal, window)
    logits = jnp.where(keep, capped, NEG_INF)
    p = jnp.exp(logits - lse[:, None])
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta[:, None])
    if dcap is not None:
        ds = ds * dcap
    ds = jnp.where(keep, ds, 0.0) * scale
    dq_scr[...] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ki == nk - 1)
    def _epilogue():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _pad_seq(x, blk):
    pad = (-x.shape[1]) % blk
    if pad:
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def _pallas_kwargs(interpret, semantics):
    kw = dict(interpret=interpret)
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=semantics)
    return kw


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9)
)
def flash_attention(q, k, v, scale, causal=True, window=None, softcap=None,
                    qb=DEFAULT_QB, kb=DEFAULT_KB, interpret=False):
    """q (BH, T, hd); k/v (BKH, S, hd) with BH = BKH*group. Returns o."""
    o, _ = _fwd(q, k, v, scale, causal, window, softcap, qb, kb, interpret)
    return o


def _fwd(q, k, v, scale, causal, window, softcap, qb, kb, interpret):
    bh, t, hd = q.shape
    bkh, s, _ = k.shape
    group = bh // bkh
    qb_e, kb_e = min(qb, t), min(kb, s)
    qp, kp, vp = _pad_seq(q, qb_e), _pad_seq(k, kb_e), _pad_seq(v, kb_e)
    tp, sp = qp.shape[1], kp.shape[1]
    nq, nk = tp // qb_e, sp // kb_e
    grid = (bh, nq, nk)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, nk=nk, kb=kb_e, s_real=s,
    )
    kwargs = dict(
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, qb_e, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kb_e, hd), lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, kb_e, hd), lambda b, i, j, g=group: (b // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, qb_e, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, qb_e), lambda b, i, j: (b, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tp, hd), q.dtype),
            jax.ShapeDtypeStruct((bh, tp), jnp.float32),
        ],
        **_pallas_kwargs(interpret, ("parallel", "parallel", "arbitrary")),
    )
    kwargs["scratch_shapes"] = [
        pltpu.VMEM((qb_e, 1), jnp.float32),
        pltpu.VMEM((qb_e, 1), jnp.float32),
        pltpu.VMEM((qb_e, hd), jnp.float32),
    ]
    o, lse = pl.pallas_call(kernel, name="flash_attention_fwd", **kwargs)(
        qp, kp, vp)
    return o[:, :t], (q, k, v, o[:, :t], lse[:, :t])


def _fwd_rule(q, k, v, scale, causal, window, softcap, qb, kb, interpret):
    o, res = _fwd(q, k, v, scale, causal, window, softcap, qb, kb, interpret)
    return o, res


def _bwd_rule(scale, causal, window, softcap, qb, kb, interpret, res, do):
    q, k, v, o, lse = res
    bh, t, hd = q.shape
    bkh, s, _ = k.shape
    group = bh // bkh
    qb_e, kb_e = min(qb, t), min(kb, s)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    qp, dop = _pad_seq(q, qb_e), _pad_seq(do, qb_e)
    kp, vp = _pad_seq(k, kb_e), _pad_seq(v, kb_e)
    pad_t = qp.shape[1] - t
    lse_p = jnp.pad(lse, ((0, 0), (0, pad_t)), constant_values=0.0)
    delta_p = jnp.pad(delta, ((0, 0), (0, pad_t)))
    tp, sp = qp.shape[1], kp.shape[1]
    nq, nk = tp // qb_e, sp // kb_e

    # --- dk / dv: grid over kv blocks, accumulate over q blocks ---
    dkv_kernel = functools.partial(
        _dkv_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, nq=nq, qb=qb_e, s_real=s, group=group,
    )
    # grid (bh, nk, nq): one (kv-head-replicated) pass per q-head; dk/dv
    # outputs are per q-head and summed over the group afterwards.
    kwargs = dict(
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, qb_e, hd), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, kb_e, hd), lambda b, j, i, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, kb_e, hd), lambda b, j, i, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, qb_e, hd), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, qb_e), lambda b, j, i: (b, i)),
            pl.BlockSpec((1, qb_e), lambda b, j, i: (b, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, kb_e, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, kb_e, hd), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sp, hd), jnp.float32),
            jax.ShapeDtypeStruct((bh, sp, hd), jnp.float32),
        ],
        **_pallas_kwargs(interpret, ("parallel", "parallel", "arbitrary")),
    )
    kwargs["scratch_shapes"] = [
        pltpu.VMEM((kb_e, hd), jnp.float32),
        pltpu.VMEM((kb_e, hd), jnp.float32),
    ]
    dk_per_qh, dv_per_qh = pl.pallas_call(
        dkv_kernel, name="flash_attention_dkv", **kwargs)(
        qp, kp, vp, dop, lse_p, delta_p
    )
    dk = dk_per_qh.reshape(bkh, group, sp, hd).sum(axis=1)[:, :s]
    dv = dv_per_qh.reshape(bkh, group, sp, hd).sum(axis=1)[:, :s]

    # --- dq: grid over q blocks, accumulate over kv blocks ---
    dq_kernel = functools.partial(
        _dq_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, nk=nk, kb=kb_e, s_real=s,
    )
    kwargs = dict(
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, qb_e, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kb_e, hd), lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, kb_e, hd), lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, qb_e, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, qb_e), lambda b, i, j: (b, i)),
            pl.BlockSpec((1, qb_e), lambda b, i, j: (b, i)),
        ],
        out_specs=pl.BlockSpec((1, qb_e, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tp, hd), q.dtype),
        **_pallas_kwargs(interpret, ("parallel", "parallel", "arbitrary")),
    )
    kwargs["scratch_shapes"] = [pltpu.VMEM((qb_e, hd), jnp.float32)]
    dq = pl.pallas_call(dq_kernel, name="flash_attention_dq", **kwargs)(
        qp, kp, vp, dop, lse_p, delta_p
    )[:, :t]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_fwd_rule, _bwd_rule)


def attend_flash(q, k, v, *, scale, causal=True, window=None, softcap=None,
                 interpret=False, qb=DEFAULT_QB, kb=DEFAULT_KB):
    """Model-layout adapter: q (B,T,H,hd), k/v (B,S,K,hd) -> (B,T,H,hd)."""
    b, t, h, hd = q.shape
    s, kh = k.shape[1], k.shape[2]
    group = h // kh
    # (B,T,H,hd) -> (B*H, T, hd) with q-heads of one kv-head adjacent
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, t, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kh, s, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kh, s, hd)
    o = flash_attention(qf, kf, vf, scale, causal, window, softcap, qb, kb,
                        interpret)
    return o.reshape(b, h, t, hd).transpose(0, 2, 1, 3)

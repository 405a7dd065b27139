"""Single-pass fused Eqn-6 refresh kernel (loss+grad+SGD step over one G sweep).

The unfused refresh (``core/correlation.loss_and_grad`` as separate einsum
dispatches) streams the full m×n gradient from HBM ~6 times per SGD step:
``GP``, ``GᵀGP``, ``Gᵀ(GP·PᵀP)``, the MSE value (via Ĝ), the row-cosine
D-term, and ``DᵀM_proj`` each re-read G or an m×n intermediate. This kernel
computes the exact same closed-form value+gradient in ONE tiled sweep over
G's row-blocks, because every Eqn-6 term reduces to accumulators that are
local to a (bm, n) row tile:

    A  = (GP)ᵀ(GP)              (r, r)   MXU, per-tile gpᵀgp
    C  = Gᵀ(GP)                 (n, r)   MXU, per-tile Gᵀgp
    E  = Σᵢ αᵢ Gᵢᵀ M_projᵢ      (n, r)   αᵢ from row norms (VPU, local)
    F  = Σᵢ βᵢ M_projᵢᵀM_projᵢ  (r, r)
    ‖G‖²_F, Σᵢ cosᵢ             scalars (SMEM)

with the non-local pieces recovered at sweep end WITHOUT re-reading G:

    t3      = Gᵀ(GP·PᵀP) = C·PᵀP          (PᵀP from resident P)
    ‖Ĝ‖²_F  = ⟨A, PᵀP⟩,  ⟨Ĝ, G⟩ = tr(A)   (so MSE needs no Ĝ materialized)
    ‖M̂ᵢ‖²  = rowᵢ(M_proj·PᵀP)·M_projᵢ     (so M̂ is never formed)
    ∂Cos    = DᵀM_proj = E − P·F           (D is never formed)

The epilogue combines the product rule (see core/correlation.py for the
paper-typo note) and applies ``P ← P − lr·∇`` to the VMEM-resident P, so a
refresh streams G exactly ``steps`` times (grid = (steps, m/bm)) and writes
only (n, r)-sized outputs — no m×n intermediate ever exists in HBM.

bf16 gradient streaming: G (and M_proj) tiles are upcast to fp32 in VMEM
after the DMA, so bf16 training halves refresh G traffic with fp32 math.

``normalize=True`` (the beyond-paper scale-invariant variant) IS fused: the
required ‖G‖ pre-pass runs as a FIRST GRID PHASE — grid becomes
(1 + steps, m/bm), phase s=0 only accumulates Σ‖G‖²_F into SMEM and derives
``1/rms`` at its last row-block; every update sweep then scales the G and
M_proj tiles by that factor in VMEM, exactly matching the jnp oracle
(``correlation.sgd_update(normalize=True)``: G/rms and M_proj/rms with
rms = √mean(G²) + 1e-12). One extra G stream per refresh, still zero m×n
HBM intermediates.

VMEM GUARD. Six (n, r) fp32 buffers stay resident — the P input block, the
new-P and grad output blocks, and the P/C/E scratch — plus A/F/PᵀP (3·r²)
and one (bm, n) G + (bm, r) M tile. At LLaMA-1B attention shapes (n=2048,
r=512) the (n, r) buffers alone are ~25 MB, over the 16 MB/core budget.
:func:`plan_bm` estimates the footprint at trace time (``eqn6_vmem_bytes``)
and auto-shrinks ``bm`` (halving, floor 8) until the tile traffic fits; if
the bm-independent resident buffers already exceed the budget it returns
``None`` and :func:`eqn6_sgd_update_pallas` raises :class:`Eqn6VmemError`,
which ``kernels/ops.eqn6_sgd_update`` catches to fall back to the unfused
jnp path (identical numerics by construction). Budget: the
``vmem_budget`` argument, else ``REPRO_EQN6_VMEM_BUDGET`` (bytes), else
16 MiB. A true n-split kernel variant remains a ROADMAP item; the guard
makes wide layers *correct* (never a kernel that cannot fit), not fast.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.coap_update import _pad_to as _pad_to_axis

DEFAULT_BM = 256
_EPS = 1e-12  # must match core/correlation._EPS exactly (oracle parity)
_MIN_BM = 8
_DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024  # bytes/core, TPU VMEM
_VMEM_ENV = "REPRO_EQN6_VMEM_BUDGET"


# The kernel's products run at the default MXU precision (one bf16 pass
# for fp32 operands on TPU), pinned so that an ambient
# ``jax.default_matmul_precision`` cannot change them: ``eqn6_vmem_bytes``
# plans for these, and under HIGHEST the compiler wants more scoped VMEM
# than the plan gives (ROADMAP A2).
MXU_PRECISION = jax.lax.Precision.DEFAULT
_dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                         precision=MXU_PRECISION)
_dot_general = functools.partial(
    jax.lax.dot_general, preferred_element_type=jnp.float32,
    precision=MXU_PRECISION)


class Eqn6VmemError(RuntimeError):
    """The fused Eqn-6 kernel cannot fit VMEM at any row-tile size."""


def _round_up(x: int, mult: int) -> int:
    return -(-int(x) // mult) * mult


def _vmem_budget(budget=None) -> int:
    if budget is not None:
        return int(budget)
    return int(os.environ.get(_VMEM_ENV, _DEFAULT_VMEM_BUDGET))


def eqn6_vmem_bytes(bm: int, n: int, r: int, g_itemsize: int = 4,
                    mp_itemsize: int = 4) -> int:
    """Trace-time VMEM footprint estimate for one (n, r, bm) tiling.

    Conservative: counts the six resident (n_pad, r_pad) fp32 buffers, the
    three r_pad² accumulators, and the G/M row tiles BOTH as their DMA'd
    dtype and as the in-VMEM fp32 upcast."""
    n_pad = _round_up(n, 128)
    r_pad = _round_up(r, 128)
    fixed = 4 * (6 * n_pad * r_pad + 3 * r_pad * r_pad)
    tiles = bm * n_pad * (g_itemsize + 4) + bm * r_pad * (mp_itemsize + 4)
    return fixed + tiles


def plan_bm(m: int, n: int, r: int, bm: int = DEFAULT_BM,
            g_itemsize: int = 4, mp_itemsize: int = 4, budget=None):
    """Largest feasible row-tile ≤ ``bm`` under the VMEM budget, or None.

    Halves ``bm`` down to 8 while the estimated footprint exceeds the
    budget; returns ``None`` when even bm=8 cannot fit (the resident (n, r)
    buffers are bm-independent — wide layers must fall back to the unfused
    path until the n-split variant lands)."""
    budget = _vmem_budget(budget)
    bm_eff = min(int(bm), max(_MIN_BM, int(m)))
    while True:
        if eqn6_vmem_bytes(bm_eff, n, r, g_itemsize, mp_itemsize) <= budget:
            return bm_eff
        if bm_eff <= _MIN_BM:
            return None
        bm_eff = max(_MIN_BM, bm_eff // 2)


def _sequential_compiler_params():
    """Both grid dims carry state (SGD steps outer, row sweep inner)."""
    return pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"))


def _eqn6_kernel(p_ref, g_ref, mp_ref, p_out_ref, val_ref, grad_ref,
                 p_s, ptp_s, a_s, c_s, e_s, f_s, sc_s,
                 *, lr, nm, m_true, n_true, eps, normalize):
    s = pl.program_id(0)  # SGD step (shifted +1 when normalize: s=0 = ‖G‖)
    k = pl.program_id(1)  # row-block of G

    @pl.when((s == 0) & (k == 0))
    def _load_p():
        p_s[...] = p_ref[...].astype(jnp.float32)
        if normalize:
            sc_s[2] = 0.0
            sc_s[3] = 1.0

    if normalize:
        # ---- first grid phase: ‖G‖ pre-pass (no P math, no outputs) -----
        @pl.when(s == 0)
        def _norm_accum():
            g = g_ref[...].astype(jnp.float32)
            sc_s[2] = sc_s[2] + jnp.sum(g * g)

        @pl.when((s == 0) & (k == nm - 1))
        def _norm_final():
            # Matches the oracle: rms = sqrt(mean(G²)) + _EPS (padded
            # rows/cols are zero, so the tile sum IS the true Σ G²).
            rms = jnp.sqrt(sc_s[2] / (m_true * n_true)) + eps
            sc_s[3] = 1.0 / rms

    def _update_sweep():
        @pl.when(k == 0)
        def _start_sweep():
            # PᵀP from the resident (possibly already-updated) P.
            ptp_s[...] = _dot_general(
                p_s[...], p_s[...],
                dimension_numbers=(((0,), (0,)), ((), ())),
            )
            a_s[...] = jnp.zeros_like(a_s)
            c_s[...] = jnp.zeros_like(c_s)
            e_s[...] = jnp.zeros_like(e_s)
            f_s[...] = jnp.zeros_like(f_s)
            sc_s[0] = 0.0
            sc_s[1] = 0.0

        # ---- per-row-block accumulation (G/M tiles upcast in VMEM) ------
        g = g_ref[...].astype(jnp.float32)  # (bm, n)
        mp = mp_ref[...].astype(jnp.float32)  # (bm, r)
        if normalize:  # scale-invariant variant: tiles scaled by 1/rms
            g = g * sc_s[3]
            mp = mp * sc_s[3]
        gp = _dot(g, p_s[...])  # (bm, r)
        a_s[...] += _dot_general(
            gp, gp, dimension_numbers=(((0,), (0,)), ((), ())),
        )
        c_s[...] += _dot_general(
            g, gp, dimension_numbers=(((0,), (0,)), ((), ())),
        )
        gn2 = jnp.sum(g * g, axis=1, keepdims=True)  # (bm, 1)
        sc_s[0] = sc_s[0] + jnp.sum(gn2)
        # ‖M̂ᵢ‖² and ⟨M̂ᵢ, Gᵢ⟩ via PᵀP / GP — M̂ never formed. Padded rows
        # (zero G and M) contribute exactly 0 everywhere: denom reduces to
        # eps and every numerator is 0.
        w = _dot(mp, ptp_s[...])
        mh2 = jnp.sum(w * mp, axis=1, keepdims=True)
        inner = jnp.sum(mp * gp, axis=1, keepdims=True)
        mh = jnp.sqrt(mh2)
        gn = jnp.sqrt(gn2)
        denom = mh * gn + eps
        sc_s[1] = sc_s[1] + jnp.sum(inner / denom)
        alpha = 1.0 / (m_true * denom)
        beta = inner / (m_true * (mh * mh2 * gn + eps))  # mh³ = mh·mh²
        e_s[...] += _dot_general(
            g, alpha * mp, dimension_numbers=(((0,), (0,)), ((), ())),
        )
        f_s[...] += _dot_general(
            beta * mp, mp, dimension_numbers=(((0,), (0,)), ((), ())),
        )

        @pl.when(k == nm - 1)
        def _finalize():
            a = a_s[...]
            ptp = ptp_s[...]
            c = c_s[...]
            p_cur = p_s[...]
            r = a.shape[0]
            row = jax.lax.broadcasted_iota(jnp.int32, (r, r), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (r, r), 1)
            tr_a = jnp.sum(jnp.where(row == col, a, 0.0))  # ⟨Ĝ, G⟩
            mn = m_true * n_true
            v_mse = (jnp.sum(a * ptp) - 2.0 * tr_a + sc_s[0]) / mn
            g_mse = (2.0 / mn) * (
                _dot(p_cur, a)
                - 2.0 * c
                + _dot(c, ptp)
            )
            v_cos = sc_s[1] / m_true
            g_cos = e_s[...] - _dot(p_cur, f_s[...])
            grad = g_mse * (1.0 - v_cos) - g_cos * v_mse
            val_ref[0, 0] = v_mse * (1.0 - v_cos)
            grad_ref[...] = grad
            new_p = p_cur - lr * grad
            p_s[...] = new_p  # next SGD step (outer grid dim) sees the update
            p_out_ref[...] = new_p

    if normalize:
        pl.when(s >= 1)(_update_sweep)
    else:
        _update_sweep()


@functools.partial(
    jax.jit,
    static_argnames=("lr", "steps", "eps", "interpret", "bm", "normalize",
                     "vmem_budget"),
)
def eqn6_sgd_update_pallas(
    p, g, m_proj, lr=0.1, steps=1, eps=_EPS,
    interpret: bool = False, bm: int = DEFAULT_BM,
    normalize: bool = False, vmem_budget=None,
):
    """Fused Eqn-6 refresh. p (...,n,r), g (...,m,n), m_proj (...,m,r) ->
    (new_p, last_val, last_grad); grad/val are those of the LAST SGD step
    (computed at the pre-update P, like the oracle). Broadcasts over leading
    (layer/expert) stack axes via vmap; g/m_proj may be bf16 (upcast
    per-tile in VMEM). ``normalize=True`` runs the ‖G‖ pre-pass as a first
    grid phase (module docstring). Raises :class:`Eqn6VmemError` when the
    estimated VMEM footprint cannot fit at any row-tile size."""
    if g.ndim > 2:
        fn = functools.partial(
            eqn6_sgd_update_pallas, lr=lr, steps=steps, eps=eps,
            interpret=interpret, bm=bm, normalize=normalize,
            vmem_budget=vmem_budget,
        )
        for _ in range(g.ndim - 2):
            fn = jax.vmap(fn, in_axes=(0, 0, 0))
        return fn(p, g, m_proj)

    m_dim, n_dim = g.shape
    r = p.shape[-1]
    bm_eff = plan_bm(
        m_dim, n_dim, r, bm=bm,
        g_itemsize=jnp.dtype(g.dtype).itemsize,
        mp_itemsize=jnp.dtype(m_proj.dtype).itemsize,
        budget=vmem_budget,
    )
    if bm_eff is None:
        raise Eqn6VmemError(
            f"fused Eqn-6 at (m={m_dim}, n={n_dim}, r={r}) needs "
            f"{eqn6_vmem_bytes(_MIN_BM, n_dim, r)} bytes of VMEM at the "
            f"smallest tile — over the {_vmem_budget(vmem_budget)}-byte "
            "budget; falling back to the unfused path (ROADMAP: n-split "
            "variant)"
        )
    # Zero padding is exact: padded G rows/cols and M rows/cols contribute 0
    # to every accumulator, and padded P rows/cols stay 0 through the update
    # (their gradient is identically 0) — sliced off on exit.
    g_p = _pad_to_axis(_pad_to_axis(g, bm_eff, 0), 128, 1)
    mp_p = _pad_to_axis(_pad_to_axis(m_proj, bm_eff, 0), 128, 1)
    p_p = _pad_to_axis(_pad_to_axis(p, 128, 0), 128, 1)
    mp_pad, np_pad = g_p.shape
    r_pad = p_p.shape[1]
    nm = mp_pad // bm_eff
    grid = (steps + (1 if normalize else 0), nm)

    kernel = functools.partial(
        _eqn6_kernel, lr=lr, nm=nm,
        m_true=float(m_dim), n_true=float(n_dim), eps=eps,
        normalize=normalize,
    )
    out_shape = [
        jax.ShapeDtypeStruct((np_pad, r_pad), jnp.float32),  # new P
        jax.ShapeDtypeStruct((1, 1), jnp.float32),  # last objective value
        jax.ShapeDtypeStruct((np_pad, r_pad), jnp.float32),  # last grad
    ]
    in_specs = [
        pl.BlockSpec((np_pad, r_pad), lambda s, k: (0, 0)),  # P (resident)
        pl.BlockSpec((bm_eff, np_pad), lambda s, k: (k, 0)),  # G row-block
        pl.BlockSpec((bm_eff, r_pad), lambda s, k: (k, 0)),  # M_proj rows
    ]
    out_specs = [
        pl.BlockSpec((np_pad, r_pad), lambda s, k: (0, 0)),
        # Scalar objective in a (1, 1) SMEM block: Mosaic stores no scalar
        # into VMEM, and two dims keep the block legal under vmap.
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((np_pad, r_pad), lambda s, k: (0, 0)),
    ]
    kwargs = dict(
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )
    kwargs["scratch_shapes"] = [
        pltpu.VMEM((np_pad, r_pad), jnp.float32),  # resident P
        pltpu.VMEM((r_pad, r_pad), jnp.float32),  # PᵀP
        pltpu.VMEM((r_pad, r_pad), jnp.float32),  # A
        pltpu.VMEM((np_pad, r_pad), jnp.float32),  # C
        pltpu.VMEM((np_pad, r_pad), jnp.float32),  # E
        pltpu.VMEM((r_pad, r_pad), jnp.float32),  # F
        pltpu.SMEM((4,), jnp.float32),  # ‖G‖², Σ row-cos, ΣG²_raw, 1/rms
    ]
    if not interpret:
        kwargs["compiler_params"] = _sequential_compiler_params()

    p_new, val, grad = pl.pallas_call(
        kernel, name="eqn6_sgd_update_pallas", **kwargs)(p_p, g_p, mp_p)
    return (
        p_new[:n_dim, :r].astype(p.dtype),
        val[0, 0],
        grad[:n_dim, :r],
    )

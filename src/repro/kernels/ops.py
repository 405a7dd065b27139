"""Jit'd public wrappers for the Pallas kernels with backend dispatch.

On TPU the Pallas implementations run natively, except in a program sharded
over an ambient mesh (see ``_mode``); elsewhere (CPU) we execute the
``ref.py`` oracle, or the Pallas body under ``interpret=True`` when
``REPRO_PALLAS=interpret`` is set (used by the kernel test suite). The
numerics are identical by construction (tests enforce it).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.kernels import ref

_MODE_ENV = "REPRO_PALLAS"


def _mode(kernel: str = "") -> str:
    """'pallas' | 'interpret' | 'ref' for a trace of ``kernel``."""
    forced = os.environ.get(_MODE_ENV, "")
    if forced:
        return forced
    if jax.default_backend() != "tpu":
        return "ref"
    # XLA cannot partition a Mosaic kernel. In a program sharded over the
    # ambient mesh (``jax.set_mesh``) the jnp path runs, which XLA does
    # partition; inside a shard_map manual over every sharded axis the body
    # is per device and the kernels run. jit keys its cache on the ambient
    # mesh, so the two never share a trace. Each trace that takes the jnp
    # path this way counts under ``kernels/on_mesh/<kernel>``.
    mesh = jax.sharding.get_abstract_mesh()
    if any(size > 1 and kind != AxisType.Manual
           for size, kind in zip(mesh.axis_sizes, mesh.axis_types)):
        if kernel:
            from repro.obs.registry import get_registry

            get_registry().inc(f"kernels/on_mesh/{kernel}")
        return "ref"
    return "pallas"


def _interpret_flag():
    return _mode() == "interpret"


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps"))
def coap_fused_update(g, p, m, v, count, b1=0.9, b2=0.999, eps=1e-8):
    """Fused G@P + Adam moment EMA + bias-corrected ΔW_proj. See kernel
    ``coap_update.py`` for the TPU implementation and tiling rationale."""
    if _mode("coap_fused_update") == "ref":
        return ref.coap_fused_update(g, p, m, v, count, b1=b1, b2=b2, eps=eps)
    from repro.kernels import coap_update

    return coap_update.coap_fused_update_pallas(
        g, p, m, v, count, b1=b1, b2=b2, eps=eps, interpret=_interpret_flag()
    )


def _swap(x):
    return jnp.swapaxes(x, -1, -2)


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "transposed"))
def coap_fused_update_bp(g, p, m, v, count, b1=0.9, b2=0.999, eps=1e-8,
                         transposed=False):
    """Back-projection-fused step: returns (m', v', ΔW) with ΔW = Δ_proj Pᵀ
    produced as a second MXU stage of the same kernel — Δ_proj never hits
    HBM. With ``transposed``, g is stored (..., n, m) and ΔW is returned
    that way. See ``coap_update.coap_fused_update_bp_pallas``."""
    if _mode("coap_fused_update_bp") == "ref":
        if not transposed:
            return ref.coap_fused_update_bp(g, p, m, v, count, b1=b1, b2=b2,
                                            eps=eps)
        new_m, new_v, dw = ref.coap_fused_update_bp(
            _swap(g), p, m, v, count, b1=b1, b2=b2, eps=eps)
        return new_m, new_v, _swap(dw)
    from repro.kernels import coap_update

    return coap_update.coap_fused_update_bp_pallas(
        g, p, m, v, count, b1=b1, b2=b2, eps=eps, interpret=_interpret_flag(),
        transposed=transposed,
    )


def _count_q8_layout(g, p, block, transposed):
    """One count per trace of the fused int8 kernel under
    ``kernels/q8_layout/stored_orientation`` when it reads G transposed as
    stored, and under ``kernels/q8_layout/ragged_rows`` when its last row
    block is partial."""
    from repro.kernels import quant8
    from repro.kernels.coap_update import fused_dims
    from repro.obs.registry import get_registry

    m_dim, n_dim = fused_dims(g.shape, transposed)
    r = p.shape[-1]
    bm, _ = quant8.q8_tiles(m_dim, n_dim, r, ref.rowblock_nblocks(r, block),
                            jnp.dtype(g.dtype).itemsize)
    if transposed:
        get_registry().inc("kernels/q8_layout/stored_orientation")
    if m_dim % bm:
        get_registry().inc("kernels/q8_layout/ragged_rows")


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "block",
                                             "transposed"))
def coap_fused_update_q8(
    g, p, m_q, m_scale, v_q, v_scale, count,
    b1=0.9, b2=0.999, eps=1e-8, block=ref.QUANT_BLOCK, transposed=False,
):
    """Single-pass 8-bit COAP step (project + dequant + Adam + requant +
    back-project in one kernel; row-block codec). With ``transposed``, g is
    stored (..., n, m) and ΔW is returned that way. See ``quant8``."""
    if _mode("coap_fused_update_q8") == "ref":
        out = ref.coap_fused_update_q8(
            _swap(g) if transposed else g, p, m_q, m_scale, v_q, v_scale,
            count, b1=b1, b2=b2, eps=eps, block=block,
        )
        return out[:4] + (_swap(out[4]) if transposed else out[4],)
    from repro.kernels import quant8

    _count_q8_layout(g, p, block, transposed)
    return quant8.coap_fused_update_q8_pallas(
        g, p, m_q, m_scale, v_q, v_scale, count,
        b1=b1, b2=b2, eps=eps, block=block, interpret=_interpret_flag(),
        transposed=transposed,
    )


@functools.partial(jax.jit, static_argnames=("block",))
def quantize_rowblock(x, block=ref.QUANT_BLOCK):
    """Row-block int8 codec (projected-state layout). jnp-implemented in all
    modes: it runs only at init / refresh-transplant time, never in the
    per-step hot loop (the fused q8 kernel requantizes in-VMEM)."""
    return ref.quantize_rowblock(x, block)


@functools.partial(jax.jit, static_argnames=("block", "dtype"))
def dequantize_rowblock(q, scale, block=ref.QUANT_BLOCK, dtype=jnp.float32):
    """Inverse of :func:`quantize_rowblock` (refresh-path only; see above)."""
    return ref.dequantize_rowblock(q, scale, block, dtype)


@functools.partial(jax.jit, static_argnames=("block",))
def rowblock_code_stats(q, scale, block=ref.QUANT_BLOCK):
    """Codec-health stats (sat/rail rate, non-finite scales, relative
    quant error) of a row-block-coded state tensor — the sampled
    ``obs/health.observe_state`` surface. jnp in all modes: it reads only
    resident int8 state at the health cadence, never the hot loop."""
    return ref.rowblock_code_stats(q, scale, block)


@functools.partial(jax.jit, static_argnames=("block",))
def quantize_blockwise(x, block=ref.QUANT_BLOCK):
    if _mode("quantize_blockwise") == "ref":
        return ref.quantize_blockwise(x, block)
    from repro.kernels import quant8

    return quant8.quantize_blockwise_pallas(x, block, interpret=_interpret_flag())


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "block"))
def dequantize_blockwise(q, scale, shape, dtype=jnp.float32, block=ref.QUANT_BLOCK):
    if _mode("dequantize_blockwise") == "ref":
        return ref.dequantize_blockwise(q, scale, shape, dtype)
    from repro.kernels import quant8

    return quant8.dequantize_blockwise_pallas(
        q, scale, shape, dtype, block, interpret=_interpret_flag()
    )


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "block"))
def quantized_adam_update(
    g_proj, m_q, m_scale, v_q, v_scale, count, b1=0.9, b2=0.999, eps=1e-8,
    block=ref.QUANT_BLOCK,
):
    if _mode("quantized_adam_update") == "ref":
        return ref.quantized_adam_update(
            g_proj, m_q, m_scale, v_q, v_scale, count, b1, b2, eps, block
        )
    from repro.kernels import quant8

    return quant8.quantized_adam_update_pallas(
        g_proj, m_q, m_scale, v_q, v_scale, count, b1, b2, eps, block,
        interpret=_interpret_flag(),
    )


@functools.partial(jax.jit, static_argnames=("lr", "steps", "normalize"))
def _eqn6_ref(p, g, m_proj, lr, steps, normalize):
    return ref.eqn6_sgd_update(
        p, g, m_proj, lr=lr, steps=steps, normalize=normalize
    )[0]


# Fused-Eqn-6 fallback telemetry: plans that land a bucket on the slow
# unfused path must be VISIBLE (launch/dryrun and launch/plan surface
# these counts), not buried in one warning per trace. Counters key on the
# 2-D dispatch shape (m, n, r) and increment once per TRACE that fell
# back; the RuntimeWarning is deduplicated per unique (n, r, budget) —
# the footprint that decides the fallback is bm-independent in (n, r), so
# repeated traces of the same layer shape add no information.
_EQN6_FALLBACK_COUNTS = {}
_EQN6_WARNED = set()


def eqn6_fallback_counts() -> dict:
    """{(m, n, r): traces-that-fell-back} since the last reset."""
    return dict(_EQN6_FALLBACK_COUNTS)


def reset_eqn6_fallbacks() -> None:
    """Clear fallback counters AND the warning dedup set (test isolation /
    per-dryrun-cell accounting)."""
    _EQN6_FALLBACK_COUNTS.clear()
    _EQN6_WARNED.clear()


def _record_eqn6_fallback(g, p, budget: int, err) -> None:
    import warnings

    from repro.obs.registry import get_registry

    m_dim, n_dim = int(g.shape[-2]), int(g.shape[-1])
    r = int(p.shape[-1])
    key = (m_dim, n_dim, r)
    _EQN6_FALLBACK_COUNTS[key] = _EQN6_FALLBACK_COUNTS.get(key, 0) + 1
    # Mirror into the process-wide registry so fallbacks ride heartbeats
    # and dryrun artifacts; reset_eqn6_fallbacks deliberately does NOT
    # clear it — the registry is lifetime-of-process telemetry.
    get_registry().inc(f"eqn6/fallback/{m_dim}x{n_dim}x{r}")
    warn_key = (n_dim, r, int(budget))
    if warn_key not in _EQN6_WARNED:
        _EQN6_WARNED.add(warn_key)
        warnings.warn(f"{err}", RuntimeWarning)


def eqn6_sgd_update(p, g, m_proj, lr=0.1, steps=1, normalize=False):
    """Fused Eqn-6 projection refresh: ``steps`` SGD iterations on the
    paper's Eqn-6 objective with loss+grad computed in ONE tiled sweep over
    G per step (see ``eqn6.py``). Accepts bf16 ``g``/``m_proj`` (upcast
    per-tile in VMEM). ``normalize=True`` fuses the scale-invariant
    variant's ‖G‖ pre-pass as a first grid phase. Returns the new P only
    (in ``p``'s dtype).

    VMEM guard: when the kernel's trace-time footprint estimate cannot fit
    at any row-tile size (wide layers; ``eqn6.plan_bm``), the dispatch
    falls back to the unfused jnp oracle — identical numerics, no
    uncompilable kernel."""
    if _mode("eqn6_sgd_update") == "ref":
        return _eqn6_ref(p, g, m_proj, lr, steps, normalize)
    from repro.kernels import eqn6

    budget = eqn6._vmem_budget()
    try:
        # Resolve the env budget HERE, outside the jit cache: the budget is
        # a static argument of the kernel wrapper, so passing it concretely
        # makes a changed REPRO_EQN6_VMEM_BUDGET a cache miss instead of a
        # silently-ignored env read inside an already-cached trace.
        return eqn6.eqn6_sgd_update_pallas(
            p, g, m_proj, lr=lr, steps=steps, normalize=normalize,
            interpret=_interpret_flag(), vmem_budget=budget,
        )[0]
    except eqn6.Eqn6VmemError as e:
        _record_eqn6_fallback(g, p, budget, e)
        return _eqn6_ref(p, g, m_proj, lr, steps, normalize)


def rmsnorm(x, scale, eps=1e-6):
    if _mode("rmsnorm") == "ref":
        return ref.rmsnorm(x, scale, eps)
    from repro.kernels import rmsnorm as _rk

    return _rk.rmsnorm_pallas(x, scale, eps, interpret=_interpret_flag())

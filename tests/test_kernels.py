"""Per-kernel validation: Pallas (interpret mode) vs the ref.py jnp oracles.

Sweeps shapes/dtypes with hypothesis per the assignment; every kernel must
match its oracle to fp32 tolerance, including ragged (non-multiple) shapes
and stacked leading axes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.core import correlation
from repro.kernels import ref
from repro.kernels.coap_update import (
    coap_fused_update_bp_pallas,
    coap_fused_update_pallas,
)
from repro.kernels.eqn6 import eqn6_sgd_update_pallas
from repro.kernels.quant8 import (
    coap_fused_update_q8_pallas,
    dequantize_blockwise_pallas,
    quantize_blockwise_pallas,
    quantized_adam_update_pallas,
)
from repro.kernels.rmsnorm import rmsnorm_pallas


def _rand(shape, seed, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(seed), shape).astype(dtype)


# ---------------------------------------------------------------------------
# coap_update kernel
# ---------------------------------------------------------------------------
@settings(max_examples=6, deadline=None)
@given(
    m=st.integers(16, 520),
    n=st.integers(128, 700),
    r=st.sampled_from([16, 64, 128]),
    count=st.integers(1, 1000),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_coap_fused_update_matches_ref(m, n, r, count, dtype):
    g = _rand((m, n), 0, dtype)
    p = _rand((n, r), 1) / np.sqrt(r)
    mm = 0.1 * _rand((m, r), 2)
    vv = jnp.abs(0.01 * _rand((m, r), 3))
    cnt = jnp.asarray(count, jnp.int32)
    got = coap_fused_update_pallas(g, p, mm, vv, cnt, interpret=True, bm=128, bn=256)
    want = ref.coap_fused_update(g, p, mm, vv, cnt)
    for a, b, name in zip(got, want, ["m", "v", "delta"]):
        np.testing.assert_allclose(a, b, rtol=3e-5, atol=3e-5, err_msg=name)


def test_coap_fused_update_stacked_axes():
    g = _rand((2, 3, 130, 260), 0)
    p = _rand((2, 3, 260, 32), 1) / np.sqrt(32)
    mm = jnp.zeros((2, 3, 130, 32))
    vv = jnp.zeros((2, 3, 130, 32))
    cnt = jnp.asarray(7, jnp.int32)
    got = coap_fused_update_pallas(g, p, mm, vv, cnt, interpret=True, bm=64, bn=128)
    want = ref.coap_fused_update(g, p, mm, vv, cnt)
    np.testing.assert_allclose(got[2], want[2], rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# coap_update back-projection-fused kernel
# ---------------------------------------------------------------------------
@settings(max_examples=6, deadline=None)
@given(
    m=st.integers(16, 520),
    n=st.integers(128, 700),
    r=st.sampled_from([16, 64, 128]),
    count=st.integers(1, 1000),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_coap_fused_update_bp_matches_ref(m, n, r, count, dtype):
    g = _rand((m, n), 0, dtype)
    p = _rand((n, r), 1) / np.sqrt(r)
    mm = 0.1 * _rand((m, r), 2)
    vv = jnp.abs(0.01 * _rand((m, r), 3))
    cnt = jnp.asarray(count, jnp.int32)
    got = coap_fused_update_bp_pallas(
        g, p, mm, vv, cnt, interpret=True, bm=128, bn=256
    )
    want = ref.coap_fused_update_bp(g, p, mm, vv, cnt)
    for a, b, name in zip(got, want, ["m", "v", "dw"]):
        np.testing.assert_allclose(a, b, rtol=3e-5, atol=3e-5, err_msg=name)


def test_coap_fused_update_bp_stacked_axes():
    g = _rand((2, 3, 130, 260), 0)
    p = _rand((2, 3, 260, 32), 1) / np.sqrt(32)
    mm = jnp.zeros((2, 3, 130, 32))
    vv = jnp.zeros((2, 3, 130, 32))
    cnt = jnp.asarray(7, jnp.int32)
    got = coap_fused_update_bp_pallas(g, p, mm, vv, cnt, interpret=True,
                                      bm=64, bn=128)
    want = ref.coap_fused_update_bp(g, p, mm, vv, cnt)
    np.testing.assert_allclose(got[2], want[2], rtol=3e-5, atol=3e-5)


def test_coap_fused_update_bp_consistent_with_nonbp():
    """ΔW from the fused kernel == Δ_proj Pᵀ of the non-BP kernel."""
    m, n, r = 300, 520, 48
    g = _rand((m, n), 0)
    p = _rand((n, r), 1) / np.sqrt(r)
    mm = 0.1 * _rand((m, r), 2)
    vv = jnp.abs(0.01 * _rand((m, r), 3))
    cnt = jnp.asarray(5, jnp.int32)
    nm1, nv1, delta = coap_fused_update_pallas(
        g, p, mm, vv, cnt, interpret=True, bm=128, bn=256
    )
    nm2, nv2, dw = coap_fused_update_bp_pallas(
        g, p, mm, vv, cnt, interpret=True, bm=128, bn=256
    )
    np.testing.assert_array_equal(np.asarray(nm1), np.asarray(nm2))
    np.testing.assert_array_equal(np.asarray(nv1), np.asarray(nv2))
    np.testing.assert_allclose(dw, delta @ p.T, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# quant8 kernels
# ---------------------------------------------------------------------------
@settings(max_examples=8, deadline=None)
@given(
    numel=st.integers(1, 5000),
    scale_pow=st.integers(-6, 3),
    seed=st.integers(0, 100),
)
def test_quantize_roundtrip_matches_ref(numel, scale_pow, seed):
    x = (10.0**scale_pow) * _rand((numel,), seed)
    q_k, s_k = quantize_blockwise_pallas(x, interpret=True)
    q_r, s_r = ref.quantize_blockwise(x)
    np.testing.assert_array_equal(q_k, q_r)
    np.testing.assert_allclose(s_k, s_r, rtol=1e-6)
    x_k = dequantize_blockwise_pallas(q_k, s_k, (numel,), interpret=True)
    x_r = ref.dequantize_blockwise(q_r, s_r, (numel,))
    np.testing.assert_allclose(x_k, x_r, rtol=1e-6)
    # quantization error bound: |x - dq| <= scale/2 per block element
    err = np.abs(np.asarray(x) - np.asarray(x_k))
    per_block_bound = np.repeat(np.asarray(s_r), ref.QUANT_BLOCK)[:numel] * 0.5 + 1e-12
    assert (err <= per_block_bound + 1e-9).all()


def test_quantize_zero_block_safe():
    x = jnp.zeros((512,))
    q, s = quantize_blockwise_pallas(x, interpret=True)
    assert bool(jnp.all(q == 0)) and bool(jnp.all(s == 0))
    back = dequantize_blockwise_pallas(q, s, (512,), interpret=True)
    assert bool(jnp.all(back == 0))


@settings(max_examples=5, deadline=None)
@given(m=st.integers(8, 200), r=st.sampled_from([16, 64]), seed=st.integers(0, 50))
def test_quantized_adam_update_matches_ref(m, r, seed):
    g = 0.1 * _rand((m, r), seed)
    m0 = 0.05 * _rand((m, r), seed + 1)
    v0 = jnp.abs(0.01 * _rand((m, r), seed + 2))
    mq, ms = ref.quantize_blockwise(m0)
    vq, vs = ref.quantize_blockwise(v0)
    cnt = jnp.asarray(3, jnp.int32)
    got = quantized_adam_update_pallas(g, mq, ms, vq, vs, cnt, interpret=True)
    want = ref.quantized_adam_update(g, mq, ms, vq, vs, cnt)
    for a, b, name in zip(got, want, ["mq", "ms", "vq", "vs", "delta"]):
        if a.dtype == jnp.int8:
            # rounding at the exact .5 boundary may differ by 1 code
            assert int(jnp.max(jnp.abs(a.astype(jnp.int32) - b.astype(jnp.int32)))) <= 1
        else:
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6, err_msg=name)


# ---------------------------------------------------------------------------
# row-block codec + single-pass fused 8-bit COAP kernel
# ---------------------------------------------------------------------------
@settings(max_examples=6, deadline=None)
@given(
    m=st.integers(1, 130),
    r=st.sampled_from([8, 100, 256, 300, 512]),
    scale_pow=st.integers(-6, 3),
    seed=st.integers(0, 100),
)
@example(m=9, r=256, scale_pow=3, seed=24)
def test_rowblock_roundtrip(m, r, scale_pow, seed):
    """Codec invariants incl. ragged r (tail block shorter than 256)."""
    x = (10.0**scale_pow) * _rand((m, r), seed)
    q, s = ref.quantize_rowblock(x)
    assert q.shape == (m, r) and q.dtype == jnp.int8
    assert s.shape == (m, ref.rowblock_nblocks(r))
    back = ref.dequantize_rowblock(q, s)
    # absmax codec: error <= scale/2 per element, scales per row-block,
    # plus the fp32 rounding of the decoded value q·s (one ulp of x).
    x_np = np.asarray(x)
    err = np.abs(x_np - np.asarray(back))
    bound = np.repeat(np.asarray(s), ref.QUANT_BLOCK, axis=-1)[:, :r]
    assert (err <= 0.5 * bound + np.spacing(np.abs(x_np))).all()


def test_rowblock_matches_flat_codec_when_aligned():
    """For r a multiple of 256 the two codecs must emit identical codes."""
    x = _rand((64, 512), 0)
    q_row, s_row = ref.quantize_rowblock(x)
    q_flat, s_flat = ref.quantize_blockwise(x)
    np.testing.assert_array_equal(
        np.asarray(q_row).reshape(-1, ref.QUANT_BLOCK), np.asarray(q_flat)
    )
    np.testing.assert_array_equal(np.asarray(s_row).reshape(-1),
                                  np.asarray(s_flat))


@settings(max_examples=6, deadline=None)
@given(
    m=st.integers(16, 300),
    n=st.sampled_from([128, 256, 520]),
    r=st.sampled_from([32, 48, 300]),
    count=st.integers(1, 500),
)
@example(m=168, n=520, r=300, count=1)
def test_coap_fused_update_q8_exact_codes(m, n, r, count):
    """With a single n-block the kernel's G@P is the oracle's dot — the
    requantized int8 states must be BIT-EXACT, scales/ΔW to fp32 ulp.

    The oracle runs jitted, as ``kernels/ops`` runs it: XLA fuses its
    elementwise epilogue the way it fuses the kernel body, while an eager
    oracle rounds after every op and can move a code by one."""
    g = 0.1 * _rand((m, n), 0)
    p = _rand((n, r), 1) / np.sqrt(r)
    m0 = 0.05 * _rand((m, r), 2)
    v0 = jnp.abs(0.01 * _rand((m, r), 3))
    mq, ms = ref.quantize_rowblock(m0)
    vq, vs = ref.quantize_rowblock(v0)
    cnt = jnp.asarray(count, jnp.int32)
    got = coap_fused_update_q8_pallas(
        g, p, mq, ms, vq, vs, cnt, interpret=True, bm=64, bn=1024
    )
    want = jax.jit(ref.coap_fused_update_q8)(g, p, mq, ms, vq, vs, cnt)
    for a, b, name in zip(got[:4:2], want[:4:2], ["mq", "vq"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    for i, name in [(1, "ms"), (3, "vs"), (4, "dw")]:
        np.testing.assert_allclose(got[i], want[i], rtol=1e-5, atol=1e-5,
                                   err_msg=name)


def test_coap_fused_update_q8_ragged_multiblock():
    """Ragged m/n with n split across blocks: accumulation order differs
    from the oracle, so codes may differ by the .5-rounding code at most."""
    m, n, r = 300, 700, 48
    g = 0.1 * _rand((m, n), 0)
    p = _rand((n, r), 1) / np.sqrt(r)
    m0 = 0.05 * _rand((m, r), 2)
    v0 = jnp.abs(0.01 * _rand((m, r), 3))
    mq, ms = ref.quantize_rowblock(m0)
    vq, vs = ref.quantize_rowblock(v0)
    cnt = jnp.asarray(9, jnp.int32)
    got = coap_fused_update_q8_pallas(
        g, p, mq, ms, vq, vs, cnt, interpret=True, bm=128, bn=256
    )
    want = ref.coap_fused_update_q8(g, p, mq, ms, vq, vs, cnt)
    for a, b, name in zip(got, want, ["mq", "ms", "vq", "vs", "dw"]):
        if a.dtype == jnp.int8:
            diff = np.abs(np.asarray(a, np.int32) - np.asarray(b, np.int32))
            assert diff.max() <= 1, name
        else:
            np.testing.assert_allclose(a, b, rtol=3e-5, atol=3e-5,
                                       err_msg=name)


def test_coap_fused_update_q8_stacked_leaves():
    """Stacked (L, m, n) leaves — the shape the bucketed optimizer emits."""
    g = 0.1 * _rand((4, 130, 260), 0)
    p = _rand((4, 260, 32), 1) / np.sqrt(32)
    m0 = 0.05 * _rand((4, 130, 32), 2)
    v0 = jnp.abs(0.01 * _rand((4, 130, 32), 3))
    mq, ms = ref.quantize_rowblock(m0)
    vq, vs = ref.quantize_rowblock(v0)
    cnt = jnp.asarray(7, jnp.int32)
    got = coap_fused_update_q8_pallas(
        g, p, mq, ms, vq, vs, cnt, interpret=True, bm=64, bn=512
    )
    want = ref.coap_fused_update_q8(g, p, mq, ms, vq, vs, cnt)
    for a, b, name in zip(got, want, ["mq", "ms", "vq", "vs", "dw"]):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=3e-5, atol=3e-5, err_msg=name,
        )


def _fused_args(kernel, lead, m_dim, n_dim, r=64):
    """P and the moments of a fused call (int8 codes and scales for q8),
    from fixed seeds."""
    p = _rand(lead + (n_dim, r), 1) / np.sqrt(r)
    m0 = 0.05 * _rand(lead + (m_dim, r), 2)
    v0 = jnp.abs(0.01 * _rand(lead + (m_dim, r), 3))
    if kernel == "q8":
        return p, ref.quantize_rowblock(m0) + ref.quantize_rowblock(v0)
    return p, (m0, v0)


def _fused(kernel, g, p, states, transposed=False):
    fn = {"q8": coap_fused_update_q8_pallas,
          "bp": coap_fused_update_bp_pallas}[kernel]
    return fn(g, p, *states, jnp.asarray(7, jnp.int32), interpret=True,
              bm=128, bn=128, transposed=transposed)


def _pad_rows(x, rows):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 2)
                   + [(0, rows - x.shape[-2]), (0, 0)])


@pytest.mark.parametrize("lead", [(), (3,)], ids=["2d", "vmap"])
@pytest.mark.parametrize("kernel", ["q8", "bp"])
@pytest.mark.parametrize("layout", ["stored_orientation", "ragged_rows"])
def test_fused_layout_bit_exact(layout, kernel, lead):
    """The fused kernels read G as stored and at its true row count.

    ``stored_orientation``: on a transposed (n, m) gradient the kernel
    gives the bits of the canonical call on its swapaxes — int8 codes,
    scales, moments, and ΔW once transposed back — with m = 300 ending in a
    partial row block of 128 and n = 320 padded to whole column blocks.
    ``ragged_rows``: the call with a partial last row block gives the bits
    of the call on G and moments zero-padded to whole row blocks.

    At rank 64 the CPU backend sums each dot in the same order for either
    operand layout; below it, the stored and canonical products can differ
    in their last bits on the CPU."""
    m_dim, n_dim = 300, 320
    g = 0.1 * _rand(lead + (m_dim, n_dim), 0)
    p, states = _fused_args(kernel, lead, m_dim, n_dim)
    want = _fused(kernel, g, p, states)
    if layout == "stored_orientation":
        got = _fused(kernel, jnp.swapaxes(g, -1, -2), p, states,
                     transposed=True)
        got = got[:-1] + (jnp.swapaxes(got[-1], -1, -2),)
    else:
        got = _fused(kernel, _pad_rows(g, 384), p,
                     tuple(_pad_rows(x, 384) for x in states))
        got = tuple(x[..., :m_dim, :] for x in got)
    assert len(got) == len(want)
    for i, (a, b) in enumerate(zip(got, want)):
        assert a.shape == b.shape, (i, a.shape, b.shape)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"output {i}")


def test_coap_fused_update_q8_underflow_clip_guard():
    """The int8-v underflow guard: when V quantizes to all-zero codes while
    M does not, the raw bias-corrected Δ is ~1/eps; the kernel must emit the
    clipped value (and match the oracle bit-for-bit on codes)."""
    m, n, r = 32, 128, 16
    g = jnp.zeros((m, n))  # no gradient: moments keep their stored values
    p = _rand((n, r), 1) / np.sqrt(r)
    m0 = 1e-3 * jnp.ones((m, r))
    mq, ms = ref.quantize_rowblock(m0)
    vq = jnp.zeros((m, r), jnp.int8)  # V underflowed to zero codes
    vs = jnp.zeros((m, ref.rowblock_nblocks(r)))
    cnt = jnp.asarray(100, jnp.int32)
    got = coap_fused_update_q8_pallas(
        g, p, mq, ms, vq, vs, cnt, interpret=True, bm=32, bn=256
    )
    want = ref.coap_fused_update_q8(g, p, mq, ms, vq, vs, cnt)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_allclose(got[4], want[4], rtol=1e-5, atol=1e-6)
    # the guard really engaged: unclipped Δ would be ~m/eps >> clip
    raw = float(
        (0.9 * 1e-3 / (1 - 0.9**100)) / (0.0 + 1e-8)
    )
    assert raw > ref.QUANT_DELTA_CLIP * 100
    # and ΔW stays bounded by clip * ||P||_1 per row
    assert np.isfinite(np.asarray(got[4])).all()


# ---------------------------------------------------------------------------
# eqn6 fused refresh kernel
# ---------------------------------------------------------------------------
@settings(max_examples=6, deadline=None)
@given(
    m=st.integers(16, 520),
    n=st.integers(24, 700),
    r=st.sampled_from([8, 32, 100]),
    seed=st.integers(0, 100),
)
def test_eqn6_kernel_matches_loss_and_grad_oracle(m, n, r, seed):
    """steps=1: the kernel's val/grad must pin against the closed-form
    ``correlation.loss_and_grad`` oracle and its P update against
    ``correlation.sgd_update`` (ragged shapes included)."""
    r = min(r, n)
    g = _rand((m, n), seed)
    p = _rand((n, r), seed + 1) / np.sqrt(r)
    mp = 0.1 * _rand((m, r), seed + 2)
    p_new, val, grad = eqn6_sgd_update_pallas(
        g=g, p=p, m_proj=mp, lr=0.1, steps=1, interpret=True, bm=64
    )
    want_val, want_grad = correlation.loss_and_grad(p, g, mp)
    np.testing.assert_allclose(val, want_val, rtol=1e-4)
    np.testing.assert_allclose(grad, want_grad, rtol=1e-3, atol=1e-6)
    want_p = correlation.sgd_update(p, g, mp, lr=0.1, steps=1)
    np.testing.assert_allclose(p_new, want_p, rtol=1e-4, atol=1e-6)


def test_eqn6_kernel_multistep_matches_sgd_update():
    """Multi-step SGD loops the grid: G is re-streamed per step against the
    in-VMEM-updated P; must track the oracle's fori_loop."""
    m, n, r = 300, 260, 32
    g = _rand((m, n), 0)
    p = _rand((n, r), 1) / np.sqrt(r)
    mp = 0.1 * _rand((m, r), 2)
    for steps in (2, 5):
        got, _, _ = eqn6_sgd_update_pallas(
            p, g, mp, lr=0.05, steps=steps, interpret=True, bm=128
        )
        want = correlation.sgd_update(p, g, mp, lr=0.05, steps=steps)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_eqn6_kernel_bf16_gradient():
    """bf16 G/M stream straight into the kernel (per-tile VMEM upcast); the
    result must match the oracle fed the same bf16 inputs (upcasting is
    value-exact, so tolerance stays fp32-tight)."""
    m, n, r = 130, 260, 32
    g = _rand((m, n), 0, jnp.bfloat16)
    p = _rand((n, r), 1) / np.sqrt(r)
    mp = (0.1 * _rand((m, r), 2)).astype(jnp.bfloat16)
    p_new, val, grad = eqn6_sgd_update_pallas(
        p, g, mp, lr=0.1, steps=1, interpret=True, bm=64
    )
    want_val, want_grad = correlation.loss_and_grad(
        p, g.astype(jnp.float32), mp.astype(jnp.float32)
    )
    np.testing.assert_allclose(val, want_val, rtol=1e-4)
    np.testing.assert_allclose(grad, want_grad, rtol=1e-3, atol=1e-6)
    want_p = correlation.sgd_update(p, g, mp, lr=0.1, steps=1)
    np.testing.assert_allclose(p_new, want_p, rtol=1e-4, atol=1e-6)


def test_eqn6_kernel_stacked_axes():
    """Stacked (L, ...) leaves — the shape the bucketed refresh emits."""
    g = _rand((2, 3, 130, 260), 0)
    p = _rand((2, 3, 260, 32), 1) / np.sqrt(32)
    mp = 0.1 * _rand((2, 3, 130, 32), 2)
    p_new, val, grad = eqn6_sgd_update_pallas(
        p, g, mp, lr=0.1, steps=1, interpret=True, bm=64
    )
    want_val, want_grad = correlation.loss_and_grad(p, g, mp)
    assert val.shape == (2, 3)
    np.testing.assert_allclose(val, want_val, rtol=1e-4)
    np.testing.assert_allclose(grad, want_grad, rtol=1e-3, atol=1e-6)


def test_eqn6_ref_oracle_is_sgd_update():
    """ref.eqn6_sgd_update must be bit-identical to correlation.sgd_update
    (it IS the same fori_loop, re-exposed in the kernel signature) — for
    the plain AND the normalize variant."""
    g = _rand((64, 48), 7)
    p = _rand((48, 8), 8) / np.sqrt(8)
    mp = 0.1 * _rand((64, 8), 9)
    for normalize in (False, True):
        got, _val, _grad = ref.eqn6_sgd_update(
            p, g, mp, lr=0.1, steps=3, normalize=normalize
        )
        want = correlation.sgd_update(
            p, g, mp, lr=0.1, steps=3, normalize=normalize
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=6, deadline=None)
@given(
    m=st.integers(16, 400),
    n=st.integers(24, 500),
    r=st.sampled_from([8, 32, 100]),
    steps=st.sampled_from([1, 3]),
    seed=st.integers(0, 100),
)
def test_eqn6_kernel_normalize_matches_oracle(m, n, r, steps, seed):
    """normalize=True is fused via a first-grid-phase ‖G‖ pre-pass; the
    result must track the jnp oracle's pre-scaled SGD (including tiny
    gradients, where normalization is the whole point)."""
    r = min(r, n)
    g = 1e-3 * _rand((m, n), seed)  # small G: inert without normalization
    p = _rand((n, r), seed + 1) / np.sqrt(r)
    mp = 1e-4 * _rand((m, r), seed + 2)
    p_new, _val, _grad = eqn6_sgd_update_pallas(
        p, g, mp, lr=0.1, steps=steps, interpret=True, bm=64, normalize=True
    )
    want = correlation.sgd_update(p, g, mp, lr=0.1, steps=steps,
                                  normalize=True)
    np.testing.assert_allclose(p_new, want, rtol=1e-4, atol=1e-6)
    # normalization engaged: the un-normalized refresh would barely move P
    frozen = correlation.sgd_update(p, g, mp, lr=0.1, steps=steps)
    assert float(jnp.max(jnp.abs(p_new - p))) > 10 * float(
        jnp.max(jnp.abs(frozen - p))
    )


def test_eqn6_kernel_normalize_bf16_and_stacked():
    g = _rand((2, 130, 260), 0, jnp.bfloat16)
    p = _rand((2, 260, 32), 1) / np.sqrt(32)
    mp = (0.1 * _rand((2, 130, 32), 2)).astype(jnp.bfloat16)
    p_new, _v, _g = eqn6_sgd_update_pallas(
        p, g, mp, lr=0.1, steps=2, interpret=True, bm=64, normalize=True
    )
    want = correlation.sgd_update(p, g, mp, lr=0.1, steps=2, normalize=True)
    np.testing.assert_allclose(p_new, want, rtol=1e-4, atol=1e-6)


def test_sgd_update_normalize_routes_fused(monkeypatch):
    """use_fused + normalize must dispatch the fused kernel — the unfused
    fallback for normalize is gone (ROADMAP item closed)."""
    from repro.kernels import ops as kops

    calls = []
    orig = kops.eqn6_sgd_update

    def counting(*a, **k):
        calls.append(k.get("normalize"))
        return orig(*a, **k)

    monkeypatch.setattr(kops, "eqn6_sgd_update", counting)
    g = _rand((64, 48), 0)
    p = _rand((48, 8), 1) / np.sqrt(8)
    mp = 0.1 * _rand((64, 8), 2)
    correlation.sgd_update(p, g, mp, use_fused=True, normalize=True)
    assert calls == [True]


# ---------------------------------------------------------------------------
# eqn6 VMEM guard
# ---------------------------------------------------------------------------
def test_eqn6_plan_bm_shrinks_and_falls_back():
    from repro.kernels.eqn6 import Eqn6VmemError, eqn6_vmem_bytes, plan_bm

    # comfortable shapes keep the requested tile
    assert plan_bm(4096, 256, 64) == 256
    # tight budget: bm halves until the tile traffic fits
    assert plan_bm(4096, 512, 128, bm=256, budget=2_500_000) == 128
    # the resident (n, r) buffers are bm-independent: when they alone bust
    # the budget no bm helps -> None (LLaMA-1B wide case at 16MB/core)
    assert plan_bm(4096, 2048, 512, budget=16 * 1024 * 1024) is None
    # estimate is monotone in bm and accounts bf16 tiles as smaller
    assert eqn6_vmem_bytes(64, 512, 128) < eqn6_vmem_bytes(256, 512, 128)
    assert eqn6_vmem_bytes(
        64, 512, 128, g_itemsize=2, mp_itemsize=2
    ) < eqn6_vmem_bytes(64, 512, 128)
    # the kernel wrapper raises the typed error instead of compiling an
    # unfittable kernel
    g = _rand((64, 256), 0)
    p = _rand((256, 64), 1) / 8.0
    mp = 0.1 * _rand((64, 64), 2)
    with pytest.raises(Eqn6VmemError):
        eqn6_sgd_update_pallas(p, g, mp, interpret=True, vmem_budget=1024)


def test_eqn6_ops_falls_back_unfused_on_vmem(monkeypatch):
    """kernels/ops dispatch catches the VMEM error and falls back to the
    jnp oracle (identical numerics) with a warning, instead of dying."""
    import warnings

    from repro.kernels import eqn6 as eqn6_mod
    from repro.kernels import ops as kops

    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    monkeypatch.setenv(eqn6_mod._VMEM_ENV, "1024")  # nothing fits
    kops.reset_eqn6_fallbacks()  # the warning dedupes per (n, r, budget)
    g = _rand((64, 48), 3)
    p = _rand((48, 8), 4) / np.sqrt(8)
    mp = 0.1 * _rand((64, 8), 5)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        got = kops.eqn6_sgd_update(p, g, mp, lr=0.1, steps=2)
    assert any("VMEM" in str(w.message) or "Eqn-6" in str(w.message)
               for w in caught)
    # ...and the fallback is COUNTED (plan/dryrun telemetry satellite)
    assert kops.eqn6_fallback_counts()[(64, 48, 8)] == 1
    want = correlation.sgd_update(p, g, mp, lr=0.1, steps=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# rmsnorm kernel
# ---------------------------------------------------------------------------
@settings(max_examples=6, deadline=None)
@given(
    rows=st.integers(1, 300),
    d=st.sampled_from([128, 256, 1024]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
    seed=st.integers(0, 20),
)
def test_rmsnorm_matches_ref(rows, d, dtype, seed):
    x = _rand((rows, d), seed, dtype)
    scale = 1.0 + 0.1 * _rand((d,), seed + 1)
    got = rmsnorm_pallas(x, scale, interpret=True, bm=64)
    want = ref.rmsnorm(x, scale)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), rtol=2e-2, atol=2e-2
    )


def test_rmsnorm_3d_shape():
    x = _rand((4, 7, 256), 0)
    scale = jnp.ones((256,))
    got = rmsnorm_pallas(x, scale, interpret=True, bm=8)
    want = ref.rmsnorm(x, scale)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

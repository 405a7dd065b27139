"""Compile-only tests: the main-path Pallas kernels at LLaMA-1B widths,
compiled for a described (not attached) TPU v5e. Nothing runs; the TPU
compiler checks tiling, VMEM and lowering, which interpret mode cannot.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import coap_update, eqn6, quant8, ref


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip can be written to the persistent
    cache but not read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _tpu_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32, I8, I32 = jnp.float32, jnp.int8, jnp.int32
# A weight's stored (rows, cols) and the rank. The fused kernels read it
# as stored: transposed where rows < cols, with a partial last row block
# where the larger side is not a multiple of the row tile. LLaMA-1B's
# projected matrices at the paper's rank 512; its up/gate as stored
# (2048, 5461), transposed and ragged; GLM-4-9B's up/gate as stored
# (4096, 13696), transposed and ragged, and its down (13696, 4096), ragged.
FUSED = [(5461, 2048, 512), (2048, 2048, 512)]
FUSED_BP = FUSED + [(2048, 5461, 512)]
FUSED_Q8 = FUSED + [(4096, 13696, 512), (13696, 4096, 512)]


def _fused_dims(rows, cols):
    """(m, n, transposed) of a weight stored (rows, cols)."""
    return max(rows, cols), min(rows, cols), rows < cols


@pytest.mark.parametrize("rows,cols,r", FUSED_BP)
def test_fused_bp_compiles(rows, cols, r, one_chip, no_persistent_cache):
    m, n, transposed = _fused_dims(rows, cols)
    text = _tpu_text(
        lambda g, p, mm, v, c: coap_update.coap_fused_update_bp_pallas(
            g, p, mm, v, c, transposed=transposed),
        one_chip, ((rows, cols), F32), ((n, r), F32), ((m, r), F32),
        ((m, r), F32), ((), I32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows,cols,r", FUSED_Q8)
def test_fused_q8_compiles(rows, cols, r, one_chip, no_persistent_cache):
    m, n, transposed = _fused_dims(rows, cols)
    nb = ref.rowblock_nblocks(r)
    text = _tpu_text(
        lambda g, p, mq, ms, vq, vs, c: quant8.coap_fused_update_q8_pallas(
            g, p, mq, ms, vq, vs, c, transposed=transposed),
        one_chip, ((rows, cols), F32), ((n, r), F32), ((m, r), I8),
        ((m, nb), F32), ((m, r), I8), ((m, nb), F32), ((), I32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("lead,m,precision", [
    ((), 2048, None),
    ((4,), 2048, None),
    ((), 32000, "highest"),
])
def test_eqn6_compiles(lead, m, precision, one_chip, no_persistent_cache):
    """Shapes whose VMEM plan builds the kernel, alone and stacked the
    way a layer-stacked bucket calls it (vmap). The kernel pins its
    products' precision, so an ambient ``highest`` (as in fp32 parity
    runs) keeps the VMEM its plan counts: without the pin the embedding's
    shape at rank 128 needs 19.54 MiB."""
    n, r = 2048, 128
    assert eqn6.plan_bm(m, n, r) is not None
    with jax.default_matmul_precision(precision):
        text = _tpu_text(
            lambda p, g, mp: eqn6.eqn6_sgd_update_pallas(p, g, mp)[0],
            one_chip, (lead + (n, r), F32), (lead + (m, n), F32),
            (lead + (m, r), F32),
        )
    assert "tpu_custom_call" in text


def test_quantize_blockwise_compiles(one_chip, no_persistent_cache):
    text = _tpu_text(lambda x: quant8.quantize_blockwise_pallas(x),
                     one_chip, ((32000, 2048), F32))
    assert "tpu_custom_call" in text

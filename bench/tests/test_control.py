"""The control and the faults, put in the program's place at the tiny
cell's size, come out as not correct against its limits."""
import pytest

from bench import control, harness
from bench.tests import tiny


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    from bench import train_cell

    root = tiny.make_root(tmp_path_factory.mktemp("tiny"))
    cell = harness.load_cell(tiny.CELL, root)
    runner = train_cell.TrainCell(cell, harness.arch_fields(cell.config), 3,
                                  log=lambda m: None)
    runner.setup(warmup=False)
    return control.readings(runner), cell.limits


def test_program_passes(readings):
    got, limits = readings
    assert all(got["program"][k] <= limits[k]["limit"] for k in limits), got


@pytest.mark.parametrize("variant", sorted(control.VARIANTS))
def test_fails_a_limit(readings, variant):
    got, limits = readings
    assert any(got[variant][k] > limits[k]["limit"] for k in limits), got[variant]


def test_fp8_rounds_both_ways():
    import jax
    import jax.numpy as jnp

    x = jnp.linspace(-3.0, 3.0, 97)
    y = control.fp8(x)
    assert 0 < float(jnp.max(jnp.abs(y - x))) <= 3.0 / 16  # e4m3: 3 mantissa bits
    assert jnp.array_equal(y, control.fp8(y))  # idempotent
    g = jax.grad(lambda v: jnp.sum(control.fp8(v) * 0.1 * x))(x)
    want = control._round(0.1 * x, jnp.float8_e5m2, control.E5M2_MAX)
    assert jnp.array_equal(g, want) and not jnp.allclose(g, 0.1 * x, rtol=1e-3)

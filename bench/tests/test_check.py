"""The check splits a leaf into matrices as the reference module's
``matrix_axes`` says: an expert stack (layers, experts, m, n) is layers x
experts matrices, and the worst one is named by its row-major index."""
import jax.numpy as jnp
import numpy as np

from bench import check

L, E, M, N = 2, 4, 3, 5
EXPERTS = "stack/moe/w"


def expert_axes(path, shape):
    if path == EXPERTS:
        return 2
    return 1 if path.startswith("stack/") else 0


def test_expert_stack_reads_one_norm_per_expert():
    x = np.random.default_rng(1).normal(size=(L, E, M, N)).astype(np.float32)
    got = np.asarray(check._norms(EXPERTS, jnp.asarray(x), expert_axes))
    want = [np.linalg.norm(x[layer, e]) for layer in range(L) for e in range(E)]
    assert got.shape == (L * E,)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def _readings(leaves):
    norms = {p: [float(v) for v in np.asarray(check._norms(p, jnp.asarray(x),
                                                            expert_axes))]
             for p, x in leaves.items()}
    return {"losses": [2.0], "grad_norms": norms, "first_grad": norms,
            "first_dense": leaves, "change": norms}


def test_gaps_name_the_worst_expert():
    rng = np.random.default_rng(2)
    ref = {EXPERTS: rng.normal(size=(L, E, M, N)).astype(np.float32),
           "stack/ln": rng.normal(size=(L, N)).astype(np.float32),
           "final_norm": rng.normal(size=(N,)).astype(np.float32)}
    prog = {p: x.copy() for p, x in ref.items()}
    layer, expert = 1, 2
    prog[EXPERTS][layer, expert] *= 1.5  # one expert's matrix of eight goes wrong
    worst = {}
    got = check.gaps(_readings(prog), _readings(ref), expert_axes, worst)
    at = (EXPERTS, layer * E + expert)
    assert worst == {"grad": at, "update": at, "grad_diff": at}
    median = float(np.median(_readings(ref)["grad_norms"][EXPERTS]
                             + _readings(ref)["grad_norms"]["stack/ln"]
                             + _readings(ref)["grad_norms"]["final_norm"]))
    norm = float(np.linalg.norm(ref[EXPERTS][layer, expert]))
    np.testing.assert_allclose(got["grad"], 0.5 * norm / max(norm, median), rtol=1e-5)
    assert got["loss"] == 0.0

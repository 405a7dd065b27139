"""The plain references agree with the program at SMOKE size, in float32."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.references import coap_adamw, dense_gqa


def _smoke(arch_name):
    from repro.configs import get_smoke

    cfg = dataclasses.replace(get_smoke(arch_name), dtype=jnp.float32)
    if arch_name == "glm4-9b":
        cfg = dataclasses.replace(cfg, qkv_bias=True, norm_eps=1.5625e-07)
    fields = {k: getattr(cfg, k) for k in (
        "n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff", "vocab_size",
        "rope_theta", "norm_eps", "qkv_bias")}
    fields["head_dim"] = cfg.resolved_head_dim
    return cfg, fields


def _batch(vocab, seed=0, b=2, t=16):
    toks = jax.random.randint(jax.random.key(seed), (b, t + 1), 0, vocab, jnp.int32)
    return toks[:, :-1], toks[:, 1:]


@pytest.mark.parametrize("arch_name", ["internlm2-1.8b", "glm4-9b"])
def test_model_loss_and_grads_agree(arch_name):
    from repro.models.model import build_model

    cfg, arch = _smoke(arch_name)
    model = build_model(cfg)
    params = weights.maker(dense_gqa.layout(arch))(2 ** 33 + 5)
    if arch["qkv_bias"]:  # zero biases would hide a missing bias
        params["stack"]["attn"] = {
            k: (v + 0.1 if k.endswith("bias") else v)
            for k, v in params["stack"]["attn"].items()}
    tokens, labels = _batch(arch["vocab_size"])
    with jax.default_matmul_precision("highest"):
        want, g_want = jax.value_and_grad(
            lambda p: model.loss(p, {"tokens": tokens, "labels": labels})[0])(params)
        got, g_got = jax.value_and_grad(dense_gqa.loss)(params, tokens, labels, arch)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g_got)[0],
                            jax.tree_util.tree_leaves(g_want)):
        err = np.max(np.abs(np.asarray(a) - np.asarray(b))) / np.max(np.abs(b))
        assert err < 1e-4, (path, err)


@pytest.mark.parametrize("name", ["coap-adamw", "8bit-coap-adamw"])
def test_optimizer_steps_agree(name):
    from repro.core.api import OptimizerConfig, make_optimizer
    from repro.optim import apply_updates

    _, arch = _smoke("internlm2-1.8b")
    from bench.tests import tiny

    # T_u 4: Eqn-6 refreshes after the first step. No Eqn 7 after it: the
    # signs of singular vectors are free, and the moments, kept through a
    # new SVD, take the new signs from neither side's P
    opt = dict(name=name, rank=16, min_dim=32, t_update=4, lam=10, lr=1e-3,
               b1=0.9, b2=0.999, eps=1e-8, grad_clip=1.0, opt_seed=0,
               eqn6_lr=0.1, eqn6_steps=1, stagger_groups=8,
               quantize=name.startswith("8bit-"), quant_block=256, delta_clip=5.0)
    tx = make_optimizer(OptimizerConfig(
        name=name, learning_rate=opt["lr"], rank=opt["rank"], min_dim=opt["min_dim"],
        t_update=opt["t_update"], lam=opt["lam"], seed=opt["opt_seed"]))
    layout = dense_gqa.layout(arch)
    opt["phases"] = tiny.program_phases(layout, opt)
    params = weights.maker(layout)(3)
    kinds = [k for _, _, (k, _, _) in coap_adamw.leaf_kinds(params, opt)]
    assert kinds.count("project") >= 5, kinds
    steps = 5
    done = {how for k in range(steps) for _, how in coap_adamw.refreshes(k, opt)
            if k > 0}
    assert done == {"eqn6"}, done
    prog, ref = params, params
    pstate, rstate = tx.init(params), coap_adamw.init_state(params, opt)
    with jax.default_matmul_precision("highest"):
        for k in range(steps):
            grads = jax.tree_util.tree_map(
                lambda x: jax.random.normal(jax.random.key(k), x.shape) * 1e-2, params)
            updates, pstate = tx.update(grads, pstate, prog)
            prog = apply_updates(prog, updates)
            ref, rstate = coap_adamw.step(ref, grads, rstate, jnp.float32(k + 1),
                                          coap_adamw.refreshes(k, opt), opt)
    # int8 states: a code one step apart moves an element by a scale step
    tol = 2e-3 if opt["quantize"] else 1e-5
    for (path, a), b, w in zip(jax.tree_util.tree_flatten_with_path(ref)[0],
                               jax.tree_util.tree_leaves(prog),
                               jax.tree_util.tree_leaves(params)):
        da, db = np.asarray(a - w), np.asarray(b - w)
        err = np.max(np.abs(da - db)) / np.max(np.abs(db))
        assert err < tol, (path, err)


def test_eqn6_step_agrees():
    """One SGD step on Eqn 6, at a scale where it moves P, against the
    program's closed-form gradient (``repro.core.correlation``)."""
    from repro.core import correlation

    k = jax.random.split(jax.random.key(7), 3)
    g = jax.random.normal(k[0], (2, 48, 32))
    p = jax.random.normal(k[1], (2, 32, 8)) / jnp.sqrt(8.0)
    m = jax.random.normal(k[2], (2, 48, 8))
    with jax.default_matmul_precision("highest"):
        want = correlation.sgd_update(p, g, m, lr=0.1, steps=2)
        got = coap_adamw.eqn6_sgd(g, p, m, 0.1, 2)
    moved = float(jnp.linalg.norm(want - p) / jnp.linalg.norm(p))
    assert moved > 1e-2, moved
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(p))))

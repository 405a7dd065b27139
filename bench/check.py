"""Whether a training run is correct: the program against the reference.

The run's own set-up drives the program's compiled step through its first
``K`` steps (the traffic's ``compared_steps``) on the run's own feed; the
plain reference (``bench/references``) follows the same ``K`` steps from
the same weights and feed once the window has closed, with the first
step's Eqn-7 initialisation and the Eqn-6 refreshes that the optimizer's
schedule puts in them. Four numbers are compared, each against a limit of
its own (``bench/limits/<cell>.json``):

* ``loss``: the largest relative gap between the two losses, over the K
  steps;
* ``grad``: the first gradient as the optimizer received it, worked out
  from the optimizer state after one step (the first moment over
  ``1 - b1``; for a projected matrix that is ``G P``, decoded where the
  state is int8): by the worst matrix, the gap between the program's norm
  and the reference's, over the larger of the reference's norm of that
  matrix and the median matrix's;
* ``update``: the norm of each matrix's change after the K steps, by the
  worst matrix, measured in the same way; matrices whose reference
  gradient is under a thousandth of the median matrix's (a key bias, under
  softmax) move by round-off alone and are left out;
* ``grad_diff``: for the matrices on dense Adam (the embedding, norms,
  biases, and matrices whose rank clips), whose first moment holds the
  gradient itself, the norm of the difference between the program's first
  gradient and the reference's, over the larger of the reference's norm
  and the median matrix's ``grad`` norm, by the worst matrix, with the
  same matrices left out. The norms above average rounding away; this
  one sees it element by element.

The model is the reference module the configuration names (its
``loss``), and so is what a matrix is: ``matrix_axes`` gives how many
leading axes of a leaf index separate matrices (a stack's layers, an
expert stack's layers and experts), and a matrix's index counts them in
row-major order (layer x experts + expert).
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.references import coap_adamw

EXCLUDE_BELOW = 1e-3  # of the median matrix's reference gradient norm


def _norms(path: str, x, matrix_axes) -> jnp.ndarray:
    """Per-matrix Frobenius norms of one leaf, one for each index of the
    leading axes that ``matrix_axes`` counts, flattened in row-major order."""
    x = x.astype(jnp.float32)
    k = matrix_axes(path, x.shape)
    if k:
        return jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(k, x.ndim)))).reshape(-1)
    return jnp.sqrt(jnp.sum(jnp.square(x)))[None]


def _flat(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {coap_adamw.path_of(kp): x for kp, x in flat}


# ------------------------------------------------------------ program side
def _decode_rows(q, s, block):
    r = q.shape[-1]
    nblk = s.shape[-1]
    x = jnp.pad(q.astype(jnp.float32), [(0, 0)] * (q.ndim - 1) + [(0, nblk * block - r)])
    x = x.reshape(q.shape[:-1] + (nblk, block)) * s[..., None]
    return x.reshape(q.shape[:-1] + (nblk * block,))[..., :r]


def _decode_flat(q, s, shape):
    size = int(np.prod(shape))
    return (q.astype(jnp.float32) * s[:, None]).reshape(-1)[:size].reshape(shape)


def _moment_state(opt_state):
    """The optimizer's per-leaf state: the member, at any depth of the
    chain's tuples, that has ``leaves`` and ``count``."""
    if hasattr(opt_state, "leaves") and hasattr(opt_state, "count"):
        return opt_state
    if isinstance(opt_state, tuple):
        for member in opt_state:
            found = _moment_state(member)
            if found is not None:
                return found
    return None


def first_moments(params, opt_state, block: int) -> dict:
    """{path: first moment as stored, decoded to float32}."""
    flat, tdef = jax.tree_util.tree_flatten_with_path(params)
    state = _moment_state(opt_state)
    if state is None:
        raise ValueError("no projected-Adam state in the optimizer state")
    leaves = tdef.flatten_up_to(state.leaves)
    out = {}
    for (kp, w), leaf in zip(flat, leaves):
        if hasattr(leaf, "p"):  # projected: (..., m, r)
            m, s = leaf.m, leaf.m_scale
            out[coap_adamw.path_of(kp)] = (
                _decode_rows(m, s, block) if m.dtype == jnp.int8 else m)
        else:
            m, s = leaf.mu, leaf.mu_scale
            out[coap_adamw.path_of(kp)] = (
                _decode_flat(m, s, w.shape) if m.dtype == jnp.int8 else m)
    return out


@functools.partial(jax.jit, static_argnames=("b1", "block", "matrix_axes"))
def program_first_grads(params, opt_state, b1: float, block: int, matrix_axes):
    """(per-matrix norms of every first gradient, the dense ones whole)."""
    ms = first_moments(params, opt_state, block)
    kinds = _dense_paths(params, opt_state)
    return ({p: _norms(p, m, matrix_axes) / (1.0 - b1) for p, m in ms.items()},
            {p: m / (1.0 - b1) for p, m in ms.items() if p in kinds})


def _dense_paths(params, opt_state) -> set:
    flat, tdef = jax.tree_util.tree_flatten_with_path(params)
    leaves = tdef.flatten_up_to(_moment_state(opt_state).leaves)
    return {coap_adamw.path_of(kp) for (kp, _), leaf in zip(flat, leaves)
            if not hasattr(leaf, "p")}


def change_norms(params, initial, matrix_axes) -> dict:
    """{path: per-matrix norms of params - initial} (call under jit)."""
    a, b = _flat(params), _flat(initial)
    return {p: _norms(p, a[p] - b[p], matrix_axes) for p in a}


# ---------------------------------------------------------- reference side
def _ref_grads(params, tokens, labels, *, loss, arch, rnd, half_batch, rows):
    """Loss and gradient of the mean over every token, taken ``rows`` rows
    at a time so that a large batch fits."""
    if half_batch:
        tokens, labels = tokens[: tokens.shape[0] // 2], labels[: labels.shape[0] // 2]
    n = tokens.shape[0] // rows if tokens.shape[0] % rows == 0 else 1
    grad = jax.value_and_grad(loss)
    if n == 1:
        return grad(params, tokens, labels, arch, rnd)

    def block(acc, xs):
        loss, g = grad(params, xs[0], xs[1], arch, rnd)
        return jax.tree_util.tree_map(jnp.add, acc, (loss, g)), None

    zero = (jnp.zeros([], jnp.float32), jax.tree_util.tree_map(jnp.zeros_like, params))
    (loss, g), _ = jax.lax.scan(block, zero, (tokens.reshape(n, rows, -1),
                                              labels.reshape(n, rows, -1)))
    return loss / n, jax.tree_util.tree_map(lambda x: x / n, g)


def _ref_update(params, grads, state, t, *, refresh, opt, double):
    """One reference step; also returns, for each matrix that Eqn 6
    refreshes, the norm of P's change over the norm of P."""
    old_p = {path: st["p"] for (path, _, _), st in
             zip(coap_adamw.leaf_kinds(params, opt), state)}
    new_params, new_state = coap_adamw.step(params, grads, state, t, refresh, opt)
    moved = {path: jnp.linalg.norm(st["p"] - old_p[path]) / jnp.linalg.norm(old_p[path])
             for (path, _, _), st in zip(coap_adamw.leaf_kinds(params, opt), new_state)
             if dict(refresh).get(path) == "eqn6"}
    if double:
        flat, tdef = jax.tree_util.tree_flatten_with_path(new_params)
        old = _flat(params)
        new_params = jax.tree_util.tree_unflatten(tdef, [
            x + (x - old[coap_adamw.path_of(kp)]) if coap_adamw.path_of(kp) == double else x
            for kp, x in flat])
    return new_params, new_state, moved


def _grad_norms(grads, matrix_axes):
    return {p: _norms(p, g, matrix_axes) for p, g in _flat(grads).items()}


def _first_grad(params, state, *, opt, matrix_axes):
    kinds = coap_adamw.leaf_kinds(params, opt)
    norms = {path: _norms(path, st["m"], matrix_axes) / (1.0 - opt["b1"])
             for (path, _, _), st in zip(kinds, state)}
    dense = {path: st["m"] / (1.0 - opt["b1"])
             for (path, _, (kind, _, _)), st in zip(kinds, state) if kind == "dense"}
    return norms, dense


def reference_readings(make_weights, seed: int, batches, arch: dict, opt: dict,
                       rows: int, model_ref, rnd=None, half_batch: bool = False,
                       double: bool = False, log=None):
    """Follow ``len(batches)`` steps; returns the readings the gaps compare.

    ``batches`` is a list of (tokens, labels) host arrays, one per step;
    the gradient of ``model_ref.loss`` (the configuration's reference
    module) is taken ``rows`` rows at a time.
    ``rnd``, ``half_batch`` and ``double`` (``model_ref.DOUBLED`` moves twice)
    put the control or a fault in the reference's place
    (``bench/control.py``)."""
    rnd = rnd or (lambda x: x)
    p = functools.partial
    axes = model_ref.matrix_axes
    double = model_ref.DOUBLED if double else ""
    with jax.default_matmul_precision("highest"):
        grad_fn = jax.jit(p(_ref_grads, loss=model_ref.loss, arch=arch, rnd=rnd,
                            half_batch=half_batch, rows=rows))
        update = {}  # one compiled step for each set of refreshes
        times = [("start", time.perf_counter())]
        params = make_weights(seed)
        state = jax.block_until_ready(coap_adamw.init_state(params, opt))
        times.append(("init", time.perf_counter()))
        losses, extra = [], {}
        for k, (tokens, labels) in enumerate(batches):
            loss, grads = grad_fn(params, jnp.asarray(tokens), jnp.asarray(labels))
            losses.append(jax.block_until_ready(loss))
            times.append((f"grad{k + 1}", time.perf_counter()))
            if k == 0:
                extra["grad_norms"] = jax.jit(p(_grad_norms, matrix_axes=axes))(grads)
            refresh = coap_adamw.refreshes(k, opt)
            if refresh not in update:
                update[refresh] = jax.jit(p(_ref_update, refresh=refresh, opt=opt,
                                            double=double), donate_argnums=(0, 2))
            params, state, moved = update[refresh](params, grads, state,
                                                   jnp.asarray(k + 1, jnp.float32))
            for path, x in jax.device_get(moved).items():
                extra.setdefault("eqn6_moved", {})[f"{path}@{k + 1}"] = float(x)
            if k == 0:
                extra["first_grad"], dense = jax.jit(
                    p(_first_grad, opt=opt, matrix_axes=axes))(params, state)
                extra["first_dense"] = jax.device_get(dense)
            del grads
            jax.block_until_ready(params)
            times.append((f"update{k + 1}", time.perf_counter()))
        del state
        change = jax.jit(p(change_norms, matrix_axes=axes))(params, make_weights(seed))
        out = jax.device_get({"losses": losses, "change": change,
                              **{k: v for k, v in extra.items() if k != "eqn6_moved"}})
        times.append(("norms", time.perf_counter()))
    if log:
        log("[check] reference seconds: " + ", ".join(
            f"{name} {t - t0:.3f}" for (_, t0), (name, t) in zip(times, times[1:])))
    return {"losses": [float(x) for x in out["losses"]],
            "eqn6_moved": extra.get("eqn6_moved", {}),
            "grad_norms": _to_lists(out["grad_norms"]),
            "first_grad": _to_lists(out["first_grad"]),
            "first_dense": extra["first_dense"],
            "change": _to_lists(out["change"])}


def _to_lists(d: dict) -> dict:
    return {k: [float(x) for x in np.asarray(v).reshape(-1)] for k, v in d.items()}


# ------------------------------------------------------------------ the gaps
def _gaps_by_matrix(prog: dict, ref: dict, keep=None) -> dict:
    """{(path, matrix index): gap} over the kept matrices."""
    ref_all = [x for p in ref for i, x in enumerate(ref[p]) if keep is None or keep[p][i]]
    median = float(np.median(ref_all))
    return {(p, i): abs(a - b) / max(b, median)
            for p in ref for i, (a, b) in enumerate(zip(prog[p], ref[p]))
            if keep is None or keep[p][i]}


def gaps(program: dict, reference: dict, matrix_axes, worst: dict = None) -> dict:
    """{"loss", "grad", "update", "grad_diff"}: the numbers compared, with
    matrices split by ``matrix_axes``. ``worst``, where given, receives the
    (path, matrix index) that sets each of the last three."""
    lp, lr = program["losses"], reference["losses"]
    loss = max(abs(a - b) / abs(b) for a, b in zip(lp, lr))
    gnorm = reference["grad_norms"]
    median = float(np.median([x for v in gnorm.values() for x in v]))
    keep = {p: [x >= EXCLUDE_BELOW * median for x in v] for p, v in gnorm.items()}
    out = {"loss": loss}
    for name, key, kp in (("grad", "first_grad", None), ("update", "change", keep)):
        by = _gaps_by_matrix(program[key], reference[key], kp)
        at = max(by, key=by.get)
        out[name] = by[at]
        if worst is not None:
            worst[name] = at
    fmed = float(np.median([x for v in reference["first_grad"].values() for x in v]))
    by = {}
    for p, b in reference["first_dense"].items():
        a = np.asarray(program["first_dense"][p], np.float64)
        b = np.asarray(b, np.float64)
        k = matrix_axes(p, b.shape)
        a, b = a.reshape((-1,) + a.shape[k:]), b.reshape((-1,) + b.shape[k:])
        for i in range(b.shape[0]):
            if keep[p][i]:
                by[(p, i)] = float(np.linalg.norm(a[i] - b[i])
                                   / max(np.linalg.norm(b[i]), fmed))
    at = max(by, key=by.get)
    out["grad_diff"] = by[at]
    if worst is not None:
        worst["grad_diff"] = at
    return out


def excluded(reference: dict) -> list:
    """Matrices left out of ``update``: [(path, matrix index)]."""
    gnorm = reference["grad_norms"]
    median = float(np.median([x for v in gnorm.values() for x in v]))
    return [(p, i) for p, v in gnorm.items() for i, x in enumerate(v)
            if x < EXCLUDE_BELOW * median]

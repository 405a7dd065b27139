"""Conv/Tucker-2 bucketing + staggered refresh: differential parity suite.

The stacked-bucket/v2 contracts this module pins before the bucketed fast
path may replace the per-leaf Algorithm-3 loop:

  * bucketed vs per-leaf A/B at the established standard — quantized runs
    and int8 codes bit-exact, fp32 to XLA-fusion ulp, flora's per-leaf RNG
    stream identical (``bucket_leaves=False`` is the A/B lever);
  * differential oracle — with the synchronized schedule the bucketed
    update must reproduce the ORIGINAL per-leaf ``conv.update_conv_leaf``
    loop (the Algorithm-3 reference the fast path replaced), bit-exact on
    int8 states;
  * stagger cadence — conv factors refresh exactly at ``(count + phase) %
    T_u == 0`` and recalibrate at ``λ·T_u``, phases from the shipped
    ``stagger_phases`` allocator over proj+conv buckets; ``stagger=False``
    restores the synchronized schedule;
  * Eqn-7 t=0 initialization runs for every conv leaf regardless of phase
    group (both factors come out of the low-cost SVD orthonormal);
  * stacked-state storage parity and accounting byte-neutrality for conv
    buckets;
  * the adafactor layout is UNAFFECTED by the v2 bump (conv stays dense
    there — regression for the ``coap_adafactor`` conv note);
  * benchmark gate — ``benchmarks/overhead.conv_refresh_report`` must show
    a >=2x worst-step refresh-bytes cut and fewer launches for the
    bucketed+staggered conv path (the ``BENCH_conv.json`` methodology).

Runs under ``REPRO_PALLAS=interpret`` in the CI smoke (scripts/ci.sh) so
the quantized paths execute the actual Pallas codec bodies.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import conv as conv_mod
from repro.core import stacked_state as ss
from repro.core.accounting import optimizer_state_bytes
from repro.core.coap_adam import (
    ConvLeaf,
    ProjectedAdamConfig,
    scale_by_projected_adam,
    stagger_phases,
)
from repro.core.coap_adafactor import (
    DenseFactorLeaf,
    ProjectedAdafactorConfig,
    _af_layout,
    scale_by_projected_adafactor,
)
from repro.core.projector import ProjectionRules
from repro.launch.mesh import make_test_mesh
from test_stacked_state import assert_equal_to_fp32_rounding

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _cfg(**kw):
    kw.setdefault("rules", ProjectionRules(rank=8, min_dim=8))
    return ProjectedAdamConfig(**kw)


def _conv_params():
    """Two congruent conv buckets (4x + 2x) + projected + dense leaves."""
    p = {f"conv_a{i}": 0.01 * jnp.ones((32, 16, 3, 3)) for i in range(4)}
    p.update({f"conv_b{i}": 0.01 * jnp.ones((24, 24, 3, 3)) for i in range(2)})
    p["w"] = jnp.zeros((96, 64))
    p["bias"] = jnp.zeros((7,))
    return p


def _grads(params, seed=0):
    key = jax.random.key(seed)
    flat, treedef = jax.tree_util.tree_flatten(params)
    return jax.tree_util.tree_unflatten(
        treedef,
        [
            0.1 * jax.random.normal(jax.random.fold_in(key, i), p.shape)
            for i, p in enumerate(flat)
        ],
    )


def _run(cfg, params, g, steps=4):
    tx = scale_by_projected_adam(cfg)
    state = tx.init(params)
    step = jax.jit(lambda gg, s: tx.update(gg, s, None))
    for _ in range(steps):
        upd, state = step(g, state)
    return tx, upd, state


def _as_perleaf(state_leaves, treedef):
    if isinstance(state_leaves, ss.StackedLeaves):
        return jax.tree_util.tree_unflatten(treedef, ss.decode(state_leaves))
    return state_leaves


def _conv_factor_trajectories(tx, params, n_steps, seed=1):
    """Per conv leaf: the set of counts at which (p_o, p_i) changed."""
    state = tx.init(params)
    step = jax.jit(lambda g, s: tx.update(g, s, None))

    def factors(st):
        return [
            (x.p_o, x.p_i)
            for x in jax.tree_util.tree_leaves(
                st.leaves, is_leaf=lambda x: isinstance(x, ConvLeaf)
            )
            if isinstance(x, ConvLeaf)
        ]

    prev = factors(state)
    changed = [set() for _ in prev]
    for count in range(n_steps):
        _, state = step(_grads(params, seed=seed + count), state)
        now = factors(state)
        for i, ((ao, ai), (bo, bi)) in enumerate(zip(prev, now)):
            delta = max(
                float(jnp.max(jnp.abs(ao - bo))),
                float(jnp.max(jnp.abs(ai - bi))),
            )
            if delta > 1e-7:
                changed[i].add(count)
        prev = now
    return changed


# ---------------------------------------------------------------------------
# A/B parity: bucketed vs per-leaf execution (the established standard)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("quantize", [False, True])
@pytest.mark.parametrize("strategy", ["coap", "galore", "flora"])
def test_conv_bucketed_matches_per_leaf(quantize, strategy):
    """One launch per conv bucket must equal the per-leaf slot loop:
    quantized runs and int8 codes bit-exact, fp32 to XLA-fusion ulp,
    flora's per-leaf RNG keys (7919*idx+mode fold) identical — under the
    staggered schedule."""
    params = _conv_params()
    g = _grads(params, seed=3)
    outs = {}
    for bucketed in (True, False):
        _, upd, state = _run(
            _cfg(strategy=strategy, quantize=quantize, t_update=3, lam=2,
                 stagger=True, bucket_leaves=bucketed),
            params, g,
        )
        outs[bucketed] = (upd, state.leaves)
    for a, b in zip(jax.tree_util.tree_leaves(outs[True]),
                    jax.tree_util.tree_leaves(outs[False])):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.int8 or quantize:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-6)


@pytest.mark.parametrize("quantize", [False, True])
@pytest.mark.parametrize("strategy", ["coap", "flora"])
def test_conv_bucket_matches_per_leaf_oracle(quantize, strategy):
    """Differential oracle: with the synchronized schedule the bucketed
    fast path must reproduce the ORIGINAL per-leaf Algorithm-3 loop
    (``conv.update_conv_leaf``) — int8 codes bit-exact, fp32 to ulp,
    flora RNG identical (the oracle folds 7919*flat_idx+mode)."""
    params = {f"c{i}": 0.01 * jnp.ones((32, 16, 3, 3)) for i in range(4)}
    g = _grads(params, seed=5)
    cfg = _cfg(strategy=strategy, quantize=quantize, t_update=2, lam=2,
               stagger=False)
    tx, _, state = _run(cfg, params, g, steps=3)

    # Oracle: the per-leaf Python loop the bucketed path replaced.
    tx2 = scale_by_projected_adam(cfg)
    ostate = tx2.init(params)
    treedef = jax.tree_util.tree_structure(params)
    oleaves = treedef.flatten_up_to(ostate.leaves)
    flat_g = jax.tree_util.tree_leaves(g)
    count = jnp.zeros([], jnp.int32)
    for _ in range(3):
        new = []
        for i, (lf, gg) in enumerate(zip(oleaves, flat_g)):
            spec = cfg.rules.spec_for(f"c{i}", gg.shape)
            _, nl = jax.jit(
                lambda lf, gg, c, spec=spec, i=i: conv_mod.update_conv_leaf(
                    cfg, lf, gg, spec, c, c + 1, i
                )
            )(lf, gg, count)
            new.append(nl)
        oleaves = new
        count = count + 1
    got = treedef.flatten_up_to(state.leaves)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(oleaves)):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.int8 or quantize:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# stagger cadence on the conv schedule
# ---------------------------------------------------------------------------
def test_conv_staggered_cadence_period_t_u():
    """Every conv leaf refreshes at count 0 (Eqn-7 init) and then exactly
    when (count + phase) % T_u == 0; phases come from the shipped allocator
    over proj+conv buckets, so bucketed and per-leaf agree."""
    t_u = 4
    params = _conv_params()
    tx = scale_by_projected_adam(_cfg(t_update=t_u, lam=2, stagger=True))
    n = 2 * 2 * t_u + 1
    changed = _conv_factor_trajectories(tx, params, n)
    # staggerable sizes: proj buckets [1 x (96,64)] then conv [4, 2]
    phase_lists = stagger_phases([1, 4, 2], t_u, 8)
    conv_phases = [ph for phases in phase_lists[1:] for ph in phases]
    assert len(changed) == len(conv_phases)
    for leaf_changed, ph in zip(changed, conv_phases):
        want = {c for c in range(n) if c == 0 or (c + ph) % t_u == 0}
        assert leaf_changed == want, (ph, leaf_changed, want)
    # staggering engaged across the 4-leaf conv bucket
    assert len({frozenset(c) for c in changed}) > 1


def test_conv_staggered_recalibration_cadence():
    """With eqn6_lr=0 the Eqn-6 factor refresh is a no-op, so conv factors
    change ONLY at Eqn-7 recalibration steps: count 0 and
    (count + phase) % (λ·T_u) == 0."""
    t_u, lam = 3, 2
    params = {f"c{i}": 0.01 * jnp.ones((32, 16, 3, 3)) for i in range(4)}
    tx = scale_by_projected_adam(
        _cfg(t_update=t_u, lam=lam, stagger=True, eqn6_lr=0.0)
    )
    n = 2 * lam * t_u + 1
    changed = _conv_factor_trajectories(tx, params, n)
    phase_lists = stagger_phases([4], t_u, 8)
    for leaf_changed, ph in zip(changed, phase_lists[0]):
        want = {
            c for c in range(n) if c == 0 or (c + ph) % (lam * t_u) == 0
        }
        assert leaf_changed == want, (ph, leaf_changed, want)


def test_conv_stagger_false_is_synchronized():
    t_u = 3
    params = _conv_params()
    tx = scale_by_projected_adam(_cfg(t_update=t_u, lam=2, stagger=False))
    n = 2 * t_u + 1
    changed = _conv_factor_trajectories(tx, params, n)
    want = {c for c in range(n) if c % t_u == 0}
    for leaf_changed in changed:
        assert leaf_changed == want, (leaf_changed, want)


def test_conv_eqn7_init_at_t0_all_phase_groups():
    """At count 0 every conv leaf's BOTH Tucker factors must come out of
    the Eqn-7 low-cost SVD with orthonormal columns — nonzero-phase groups
    included (the whole-bucket init branch of the lax.switch)."""
    params = _conv_params()
    tx = scale_by_projected_adam(_cfg(t_update=4, lam=2, stagger=True))
    state = tx.init(params)
    _, state = jax.jit(lambda g, s: tx.update(g, s, None))(
        _grads(params), state
    )
    convs = [
        x
        for x in jax.tree_util.tree_leaves(
            state.leaves, is_leaf=lambda x: isinstance(x, ConvLeaf)
        )
        if isinstance(x, ConvLeaf)
    ]
    assert convs
    for leaf in convs:
        for p in (leaf.p_o, leaf.p_i):
            ptp = np.asarray(jnp.einsum("nr,nk->rk", p, p))
            np.testing.assert_allclose(ptp, np.eye(p.shape[-1]), atol=1e-4)


# ---------------------------------------------------------------------------
# stacked storage + accounting with conv buckets
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("quantize", [False, True])
def test_conv_stacked_state_matches_per_leaf(quantize):
    """Conv moments stored PRE-STACKED (v2 layout) must produce the same
    run as per-leaf storage — quantized runs bit-exact, fp32 to ulp."""
    params = _conv_params()
    g = _grads(params, seed=7)
    treedef = jax.tree_util.tree_structure(params)
    outs = {}
    for stacked in (True, False):
        _, upd, state = _run(
            _cfg(quantize=quantize, t_update=2, lam=2, stagger=True,
                 stacked_state=stacked),
            params, g,
        )
        outs[stacked] = (upd, _as_perleaf(state.leaves, treedef))
    for a, b in zip(jax.tree_util.tree_leaves(outs[True]),
                    jax.tree_util.tree_leaves(outs[False])):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.int8 or quantize:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-6)


def test_conv_bucket_in_stacked_layout_no_tail():
    """The adam layout buckets conv leaves (stacked-bucket/v2): stacked
    storage holds a ConvLeaf bucket with a (B,) leading axis and no
    residual tail; leaf_view slices recover per-leaf states."""
    params = _conv_params()
    tx = scale_by_projected_adam(_cfg(stacked_state=True))
    state = tx.init(params)
    leaves = state.leaves
    assert isinstance(leaves, ss.StackedLeaves)
    assert leaves.tail == ()
    conv_buckets = [
        (info, bucket)
        for info, bucket in zip(leaves.layout.buckets, leaves.buckets)
        if info.kind == ss.BUCKET_CONV
    ]
    assert [len(i.indices) for i, _ in conv_buckets] == [4, 2]
    for info, bucket in conv_buckets:
        assert isinstance(bucket, ConvLeaf)
        assert bucket.p_o.shape[0] == len(info.indices)
        for slot, idx in enumerate(info.indices):
            view = ss.leaf_view(leaves, idx)
            assert isinstance(view, ConvLeaf)
            np.testing.assert_array_equal(
                np.asarray(view.p_o), np.asarray(bucket.p_o[slot])
            )


@pytest.mark.parametrize("quantize", [False, True])
def test_conv_accounting_byte_neutral_across_layouts(quantize):
    """Byte tables identical for stacked (conv-bucketed) vs per-leaf
    storage — stacking B equal-shape ConvLeaf states is byte-neutral."""
    params = _conv_params()
    reports = {}
    for stacked in (True, False):
        tx = scale_by_projected_adam(
            _cfg(quantize=quantize, stacked_state=stacked)
        )
        reports[stacked] = optimizer_state_bytes(tx.init(params))
    assert reports[True].total_bytes == reports[False].total_bytes
    assert reports[True].by_category == reports[False].by_category
    assert "projection" in reports[True].by_category


def test_compressed_update_conv_stacked_matches_per_leaf():
    """Cross-pod compression on a conv tree: the Tucker-2 core reduction
    addressed through leaf_view (stacked mode) must match per-leaf state
    compression (floats to XLA-fusion ulp)."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.compression import compressed_update

    params = {f"c{i}": 0.01 * jnp.ones((32, 16, 3, 3)) for i in range(2)}
    params["w"] = jnp.zeros((96, 64))
    params["bias"] = jnp.zeros((16,))
    g = _grads(params, seed=2)
    treedef = jax.tree_util.tree_structure(params)
    mesh = make_test_mesh((1,), ("pod",))
    outs = {}
    for stacked in (True, False):
        cfg = _cfg(t_update=2, lam=2, use_fused_kernel=False,
                   stacked_state=stacked)
        tx = scale_by_projected_adam(cfg)
        state = tx.init(params)

        def per_pod(gg, st):
            return compressed_update(cfg, gg, st, "pod")

        mapped = jax.shard_map(
            per_pod, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False, axis_names={"pod"},
        )
        for _ in range(3):
            upd, state = jax.jit(mapped)(g, state)
        outs[stacked] = (upd, _as_perleaf(state.leaves, treedef))
    for a, b in zip(jax.tree_util.tree_leaves(outs[True]),
                    jax.tree_util.tree_leaves(outs[False])):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=2e-6
        )


# ---------------------------------------------------------------------------
# adafactor regression: layout unaffected by the v2 bump
# ---------------------------------------------------------------------------
def test_adafactor_layout_unaffected_by_v2():
    """Algorithm 2 has no Tucker-2 path: conv leaves stay on the dense
    Adafactor path and its layout must contain NO conv buckets and no tail
    — the v1→v2 codec bump changed only the DEFAULT classification, not
    ``_af_classify``."""
    params = _conv_params()
    cfg = ProjectedAdafactorConfig(
        rules=ProjectionRules(rank=8, min_dim=8), t_update=2, lam=2,
    )
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    layout = _af_layout(cfg, flat)
    assert layout.tail == ()
    assert not [b for b in layout.buckets if b.kind == ss.BUCKET_CONV]
    assert layout.version == ss.STACKED_STATE_VERSION  # rides the codec

    # and the transform still runs conv leaves as dense factored states,
    # identically across storage modes up to fp32 rounding
    g = _grads(params, seed=9)
    treedef = jax.tree_util.tree_structure(params)
    outs = {}
    for stacked in (True, False):
        tx = scale_by_projected_adafactor(
            ProjectedAdafactorConfig(
                rules=ProjectionRules(rank=8, min_dim=8), t_update=2,
                lam=2, stacked_state=stacked,
            )
        )
        state = tx.init(params)
        step = jax.jit(lambda gg, s: tx.update(gg, s, None))
        for _ in range(3):
            upd, state = step(g, state)
        outs[stacked] = (upd, _as_perleaf(state.leaves, treedef))
    flat_states = treedef.flatten_up_to(outs[True][1])
    assert isinstance(flat_states[0], DenseFactorLeaf)  # conv_a0 is dense
    for a, b in zip(jax.tree_util.tree_leaves(outs[True]),
                    jax.tree_util.tree_leaves(outs[False])):
        assert_equal_to_fp32_rounding(a, b)


# ---------------------------------------------------------------------------
# benchmark gate (acceptance criteria)
# ---------------------------------------------------------------------------
def test_conv_refresh_gate():
    """Bucketed+staggered conv refresh must cut the worst-step refresh
    bytes >=2x vs the synchronized per-leaf schedule on the conv-heavy
    reference tree, with strictly fewer per-step launches — the
    BENCH_conv.json methodology, gated here."""
    from benchmarks.overhead import conv_refresh_report

    rep = conv_refresh_report(measure=False)
    assert rep["worst_step_bytes_ratio"] >= 2.0, rep["worst_step_bytes_ratio"]
    assert (
        rep["launches_per_step_bucketed"] < rep["launches_per_step_per_leaf"]
    )
    # staggering redistributes, never adds, refresh work
    assert (
        rep["synchronized_per_leaf"]["total_bytes_per_period"]
        == rep["staggered_bucketed"]["total_bytes_per_period"]
    )

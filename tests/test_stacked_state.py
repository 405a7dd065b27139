"""Stacked optimizer-state subsystem: codec, A/B parity, consumer contracts.

Covers the tentpole guarantees:
  * codec round-trip: ``decode(encode(x)) == x`` bit-for-bit (int8 codes
    included) and ``leaf_view`` matches ``decode``;
  * stacked vs per-leaf execution parity for every strategy, quantized and
    fp32, bf16 gradient streaming and flora RNG — the same standard as the
    existing ``bucket_leaves`` A/B guarantee (int8 states bit-exact —
    quantized runs are bit-exact throughout — floats to XLA-fusion ulp);
  * checkpoint cross-mode restore: a checkpoint saved in stacked mode
    restores into a per-leaf template and vice versa, exactly;
  * accounting: identical byte tables for both layouts;
  * cross-pod compression addresses stacked state through ``leaf_view``
    and matches per-leaf state compression bitwise;
  * benchmark gate: the per-step stack/scatter state traffic removed on
    the LLaMA-1B bucket structure is >=2x (BENCH_state methodology).
"""
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import stacked_state as ss
from repro.core.accounting import optimizer_state_bytes
from repro.core.coap_adam import (
    ProjectedAdamConfig,
    ProjLeaf,
    scale_by_projected_adam,
)
from repro.core.coap_adafactor import (
    ProjectedAdafactorConfig,
    scale_by_projected_adafactor,
)
from repro.core.projector import ProjectionRules
from repro.train import checkpoint as ckpt
from repro.launch.mesh import make_test_mesh

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _cfg(**kw):
    kw.setdefault("rules", ProjectionRules(rank=16, min_dim=8))
    return ProjectedAdamConfig(**kw)


def assert_equal_to_fp32_rounding(a, b):
    """Equal up to fp32 rounding, within two ulps of the leaf's largest
    magnitude. The two storage layouts compile to different programs, and
    XLA may fuse a reduction differently in each, so one sum can round
    differently; the math is the same."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    tol = 2 * np.finfo(np.float32).eps * max(float(np.max(np.abs(b), initial=0.0)), 1e-30)
    np.testing.assert_allclose(a, b, rtol=0, atol=tol)


def _params():
    """Two projected buckets + odd projected + conv bucket + dense leaves."""
    p = {f"a{i}": {"w": jnp.zeros((96, 64))} for i in range(4)}
    p.update({f"b{i}": {"w": jnp.zeros((128, 48))} for i in range(2)})
    p["c0"] = {"w": jnp.zeros((80, 72))}
    p["conv_k"] = 0.01 * jnp.ones((128, 128, 3, 3))
    p["bias"] = jnp.zeros((7,))
    p["tiny"] = jnp.zeros((4, 4))
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


def _run(cfg, params, g, steps=3):
    tx = scale_by_projected_adam(cfg)
    state = tx.init(params)
    step = jax.jit(lambda gg, s: tx.update(gg, s, None))
    for _ in range(steps):
        upd, state = step(g, state)
    return tx, upd, state


def _as_perleaf_tree(state_leaves, treedef):
    if isinstance(state_leaves, ss.StackedLeaves):
        return jax.tree_util.tree_unflatten(treedef, ss.decode(state_leaves))
    return state_leaves


# ---------------------------------------------------------------------------
# codec unit behaviour
# ---------------------------------------------------------------------------
def test_encode_decode_roundtrip_bitexact():
    params = _params()
    cfg = _cfg(quantize=True, stacked_state=False)
    tx = scale_by_projected_adam(cfg)
    state = tx.init(params)
    _, state = jax.jit(lambda gg, s: tx.update(gg, s, None))(
        _grads(params), state
    )
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    layout = ss.build_layout(
        cfg.rules.spec_for,
        [ss.path_str(kp) for kp, _ in flat],
        [leaf.shape for _, leaf in flat],
        [jnp.dtype(leaf.dtype).name for _, leaf in flat],
    )
    flat_states = treedef.flatten_up_to(state.leaves)
    stacked = ss.encode(layout, flat_states)
    decoded = ss.decode(stacked)
    assert len(decoded) == len(flat_states)
    for a, b in zip(
        jax.tree_util.tree_leaves(flat_states),
        jax.tree_util.tree_leaves(decoded),
    ):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # leaf_view agrees with decode at every position
    for i in range(layout.n_leaves):
        for a, b in zip(
            jax.tree_util.tree_leaves(ss.leaf_view(stacked, i)),
            jax.tree_util.tree_leaves(decoded[i]),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_layout_deterministic_and_conv_buckets():
    params = _params()
    cfg = _cfg()
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    mk = lambda: ss.build_layout(
        cfg.rules.spec_for,
        [ss.path_str(kp) for kp, _ in flat],
        [leaf.shape for _, leaf in flat],
        [jnp.dtype(leaf.dtype).name for _, leaf in flat],
    )
    la, lb = mk(), mk()
    assert la == lb  # pure function of the tree
    assert la.signature() == lb.signature()
    # stacked-bucket/v2: the conv leaf BUCKETS (no residual tail) and
    # joins the staggerable buckets after the projected ones
    assert la.tail == ()
    conv = [b for b in la.buckets if b.kind == ss.BUCKET_CONV]
    assert [b.paths for b in conv] == [("conv_k",)]
    assert la.staggerable_bucket_sizes() == la.proj_bucket_sizes() + [1]
    # projected buckets come first, with the multi-leaf buckets intact
    proj = [b for b in la.buckets if b.kind == ss.BUCKET_PROJECT]
    assert [len(b.indices) for b in proj] == [4, 2, 1]
    assert [b.kind for b in la.buckets].index(ss.BUCKET_CONV) == len(proj)
    # the legacy classification still reproduces the v1 conv-in-tail layout
    lv1 = ss.build_layout(
        cfg.rules.spec_for,
        [ss.path_str(kp) for kp, _ in flat],
        [leaf.shape for _, leaf in flat],
        [jnp.dtype(leaf.dtype).name for _, leaf in flat],
        classify=ss.classify_v1,
    )
    assert [t.path for t in lv1.tail] == ["conv_k"]
    assert not [b for b in lv1.buckets if b.kind == ss.BUCKET_CONV]
    # every index appears exactly once across buckets + tail
    seen = sorted(
        i for b in la.buckets for i in b.indices
    ) + sorted(t.index for t in la.tail)
    assert sorted(seen) == list(range(la.n_leaves))


def test_stacked_requires_bucketing():
    with pytest.raises(ValueError, match="bucket_leaves"):
        _cfg(stacked_state=True, bucket_leaves=False)


def test_stacked_state_rejects_mismatched_tree():
    params = _params()
    tx = scale_by_projected_adam(_cfg(stacked_state=True))
    state = tx.init(params)
    other = {"x": jnp.zeros((96, 64)), "y": jnp.zeros((96, 64))}
    with pytest.raises(ValueError, match="stacked optimizer state"):
        tx.update(_grads(other), state, None)


# ---------------------------------------------------------------------------
# execution parity: stacked vs per-leaf storage
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("quantize", [False, True])
@pytest.mark.parametrize("strategy", ["coap", "galore", "flora"])
def test_stacked_matches_per_leaf(quantize, strategy):
    """Same updates and states from both storage modes: int8 (and entire
    quantized runs) bit-exact, floats to XLA-fusion ulp — the established
    bucket_leaves A/B standard, now extended to the state layout."""
    params = _params()
    g = _grads(params, seed=3)
    treedef = jax.tree_util.tree_structure(params)
    outs = {}
    for stacked in (True, False):
        _, upd, state = _run(
            _cfg(strategy=strategy, quantize=quantize, t_update=2, lam=2,
                 stacked_state=stacked),
            params, g,
        )
        outs[stacked] = (upd, _as_perleaf_tree(state.leaves, treedef))
    for a, b in zip(jax.tree_util.tree_leaves(outs[True]),
                    jax.tree_util.tree_leaves(outs[False])):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.int8 or quantize:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-6)


def test_stacked_bf16_gradient_streaming_parity():
    """bf16 grads through stacked storage: state bits match the fp32-fed
    stacked run (upcasting bf16 is exact), as in the per-leaf guarantee."""
    params = _params()
    g16 = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), _grads(params, seed=5)
    )
    g32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), g16)
    treedef = jax.tree_util.tree_structure(params)
    out = {}
    for name, g in [("fp32", g32), ("bf16", g16)]:
        _, upd, state = _run(
            _cfg(t_update=2, lam=2, quantize=True, stacked_state=True),
            params, g,
        )
        out[name] = (upd, _as_perleaf_tree(state.leaves, treedef))
    for a, b in zip(jax.tree_util.tree_leaves(out["fp32"][1]),
                    jax.tree_util.tree_leaves(out["bf16"][1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stacked_adafactor_matches_per_leaf_bitwise():
    """The adafactor variant computes per-leaf through leaf_view slices, so
    stacked and per-leaf modes run the same math, equal to fp32 rounding
    (XLA may order a reduction differently in the two programs)."""
    params = _params()
    g = _grads(params, seed=7)
    treedef = jax.tree_util.tree_structure(params)
    outs = {}
    for stacked in (True, False):
        cfg = ProjectedAdafactorConfig(
            rules=ProjectionRules(rank=16, min_dim=8), t_update=2, lam=2,
            stacked_state=stacked,
        )
        tx = scale_by_projected_adafactor(cfg)
        state = tx.init(params)
        step = jax.jit(lambda gg, s: tx.update(gg, s, None))
        for _ in range(3):
            upd, state = step(g, state)
        outs[stacked] = (upd, _as_perleaf_tree(state.leaves, treedef))
    for a, b in zip(jax.tree_util.tree_leaves(outs[True]),
                    jax.tree_util.tree_leaves(outs[False])):
        assert_equal_to_fp32_rounding(a, b)


# ---------------------------------------------------------------------------
# consumer: accounting
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("quantize", [False, True])
def test_accounting_byte_tables_match_across_layouts(quantize):
    params = _params()
    reports = {}
    for stacked in (True, False):
        tx = scale_by_projected_adam(
            _cfg(quantize=quantize, stacked_state=stacked)
        )
        reports[stacked] = optimizer_state_bytes(tx.init(params))
    assert reports[True].total_bytes == reports[False].total_bytes
    assert reports[True].by_category == reports[False].by_category
    assert "projection" in reports[True].by_category


# ---------------------------------------------------------------------------
# consumer: checkpointing (cross-mode restore)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "quantize,state_dtype",
    [(True, jnp.float32), (False, jnp.float32), (False, jnp.bfloat16)],
)
def test_checkpoint_cross_mode_restore(quantize, state_dtype, tmp_path):
    """A checkpoint written in either storage mode restores exactly into a
    template of either mode: the restored arrays equal the source state
    re-expressed in the target layout (pure codec transform)."""
    params = _params()
    g = _grads(params, seed=1)
    treedef = jax.tree_util.tree_structure(params)
    txs, states = {}, {}
    for stacked in (True, False):
        txs[stacked], _, states[stacked] = _run(
            _cfg(quantize=quantize, state_dtype=state_dtype, t_update=2,
                 lam=2, stacked_state=stacked),
            params, g,
        )
    for src in (True, False):
        for dst in (True, False):
            d = str(tmp_path / f"{src}_{dst}")
            ckpt.save(d, 3, states[src])
            template = jax.eval_shape(lambda: txs[dst].init(params))
            restored = ckpt.restore(d, template)
            # expected: the SOURCE state, re-laid-out into dst's structure
            want = states[src].leaves
            want = _as_perleaf_tree(want, treedef)
            got = _as_perleaf_tree(restored.leaves, treedef)
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
                assert a.dtype == b.dtype
                np.testing.assert_array_equal(
                    np.asarray(a.astype(jnp.float32)),
                    np.asarray(b.astype(jnp.float32)),
                )
            np.testing.assert_array_equal(
                np.asarray(restored.count), np.asarray(states[src].count)
            )


def test_stacked_manifest_declares_codec(tmp_path):
    import json

    params = _params()
    tx, _, state = _run(_cfg(stacked_state=True), params, _grads(params))
    d = str(tmp_path)
    ckpt.save(d, 1, state)
    with open(os.path.join(d, "ckpt_00000001", "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["version"] == 2
    assert manifest["stacked"], "stacked state must emit stacked entries"
    for se in manifest["stacked"]:
        assert se["codec"] == ss.STACKED_CODEC
        assert se["axis"] == 0
        assert len(se["slots"]) >= 1
    # unknown codec versions must fail loudly, not mis-slice
    se = manifest["stacked"][0]
    se["codec"] = "stacked-bucket/v999"
    with open(os.path.join(d, "ckpt_00000001", "manifest.json"), "w") as f:
        json.dump(manifest, f)
    template = jax.eval_shape(lambda: tx.init(params))
    with pytest.raises(ValueError, match="codec"):
        ckpt.restore(d, template)


# ---------------------------------------------------------------------------
# consumer: cross-pod compression via leaf_view
# ---------------------------------------------------------------------------
def test_compressed_update_stacked_matches_per_leaf():
    """compressed_update on stacked state (leaf_view addressing) must match
    the per-leaf state path — same jnp reduction schedule, state layout
    only differs (floats to XLA-fusion ulp, the A/B standard)."""
    from repro.distributed.compression import compressed_update

    params = {f"a{i}": {"w": jnp.zeros((96, 64))} for i in range(3)}
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

        from jax.sharding import PartitionSpec as P

        mapped = jax.shard_map(
            per_pod, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False, axis_names={"pod"},
        )
        for _ in range(3):
            upd, state = jax.jit(mapped)(g, state)
        outs[stacked] = (upd, _as_perleaf_tree(state.leaves, treedef))
    for a, b in zip(jax.tree_util.tree_leaves(outs[True]),
                    jax.tree_util.tree_leaves(outs[False])):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.int8:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-6)


def test_compressed_update_stacked_rejects_reordered_tree():
    """A congruent-but-reordered gradient tree (same leaf count and
    shapes, different paths) must raise, never silently pair bucket slices
    with the wrong leaves."""
    from repro.distributed.compression import compressed_update

    params = {"a": {"w": jnp.zeros((96, 64))}, "z": {"w": jnp.zeros((96, 64))}}
    cfg = _cfg(use_fused_kernel=False, stacked_state=True)
    tx = scale_by_projected_adam(cfg)
    state = tx.init(params)
    reordered = {"b": {"w": jnp.zeros((96, 64))},
                 "c": {"w": jnp.zeros((96, 64))}}
    with pytest.raises(ValueError, match="stacked optimizer state"):
        compressed_update(cfg, _grads(reordered), state, "pod")


def test_abstract_accounting_parity_eval_shape():
    """abstract_state_bytes (jax.eval_shape over init — the no-alloc path
    the 314B benchmarks use) must report identical tables for both
    layouts: encode is byte-neutral even on abstract arrays."""
    from repro.core.accounting import abstract_state_bytes

    params = _params()
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params
    )
    reps = {}
    for stacked in (True, False):
        tx = scale_by_projected_adam(
            _cfg(quantize=True, stacked_state=stacked)
        )
        reps[stacked] = abstract_state_bytes(tx, shapes)
    assert reps[True].total_bytes == reps[False].total_bytes
    assert reps[True].by_category == reps[False].by_category


# ---------------------------------------------------------------------------
# benchmark gate (acceptance criteria)
# ---------------------------------------------------------------------------
def test_state_traffic_gate_llama1b():
    """Pre-stacked storage must remove >=2x of the per-step state bytes
    moved on the LLaMA-1B bucket structure (both int8 and fp32 states), and
    stacking must never *add* traffic."""
    from benchmarks.overhead import state_traffic_report

    for quantize in (True, False):
        rep = state_traffic_report(quantize=quantize)
        assert rep["ratio"] >= 2.0, (quantize, rep["ratio"])
        assert rep["copy_bytes_removed_per_step"] > 0
        for row in rep["buckets"].values():
            assert (
                row["per_step_bytes_stacked_mode"]
                <= row["per_step_bytes_per_leaf_mode"]
            )


def test_state_traffic_gate_measured(monkeypatch):
    """The analytic table above is a model; this gates what the COMPILED
    step actually does: XLA cost_analysis of one whole jitted int8 update
    must access measurably fewer bytes in stacked mode (a regression that
    reintroduces the stack/scatter copies on the hot path drives the
    measured ratio back to ~1.0 and fails here). Pinned to the ref/compiled
    dispatch: interpret-mode Pallas emulation restructures the whole-step
    HLO and is not the shipped program this gate is about."""
    from benchmarks.overhead import measured_state_step_bytes

    monkeypatch.delenv("REPRO_PALLAS", raising=False)
    meas = measured_state_step_bytes(quantize=True)
    assert meas["per_leaf"] > meas["stacked"], meas
    assert meas["ratio"] >= 1.05, meas
    assert meas["bytes_removed_per_step"] > 0

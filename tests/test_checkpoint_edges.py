"""Checkpoint edge paths: async save ordering, keep= pruning, bf16 round
trip — under both per-leaf and stacked-state manifests — plus the
cross-VERSION stacked-codec contract: a ``stacked-bucket/v1`` checkpoint
(conv states in the per-leaf TAIL) restores under v2 code and a v2
checkpoint (conv bucketed) restores into a v1-layout template, elastic
reshard included; unknown future codec versions still fail loudly.

The atomicity contract: a ``ckpt_<step>`` directory becomes visible ONLY
via the final ``os.rename`` of a fully-flushed ``.tmp`` directory, so no
reader (poller, restarted trainer, ``latest_step``) can ever observe a torn
checkpoint — asynchronous saves included.
"""
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import stacked_state as ss
from repro.core.coap_adam import (
    ProjectedAdamConfig,
    ProjectedAdamState,
    scale_by_projected_adam,
)
from repro.core.projector import ProjectionRules
from repro.train import checkpoint as ckpt


def _params():
    p = {f"a{i}": {"w": jnp.zeros((64, 32))} for i in range(3)}
    p["bias"] = jnp.zeros((5,))
    return p


def _state(stacked: bool, state_dtype=jnp.float32, seed=0):
    params = _params()
    tx = scale_by_projected_adam(
        ProjectedAdamConfig(
            rules=ProjectionRules(rank=8, min_dim=8), t_update=2, lam=2,
            stacked_state=stacked, state_dtype=state_dtype,
        )
    )
    state = tx.init(params)
    key = jax.random.key(seed)
    flat, treedef = jax.tree_util.tree_flatten(params)
    g = jax.tree_util.tree_unflatten(
        treedef,
        [
            0.1 * jax.random.normal(jax.random.fold_in(key, i), p.shape)
            for i, p in enumerate(flat)
        ],
    )
    _, state = jax.jit(lambda gg, s: tx.update(gg, s, None))(g, state)
    return tx, params, state


def _complete_dirs(d):
    out = []
    for name in sorted(os.listdir(d)):
        if not name.startswith("ckpt_") or name.endswith(".tmp"):
            continue
        cdir = os.path.join(d, name)
        mpath = os.path.join(cdir, "manifest.json")
        assert os.path.exists(mpath), f"torn checkpoint visible: {name}"
        with open(mpath) as f:
            manifest = json.load(f)
        for entry in manifest["leaves"] + manifest.get("stacked", []):
            assert os.path.exists(os.path.join(cdir, entry["file"])), (
                f"manifest references missing file in {name}"
            )
        out.append(name)
    return out


@pytest.mark.parametrize("stacked", [False, True])
def test_async_save_never_exposes_torn_checkpoint(tmp_path, stacked,
                                                  monkeypatch):
    """The rename that publishes ckpt_<step> must happen only after the
    manifest and every referenced array file exist in the tmp dir; while
    the async writer runs, any visible checkpoint must be complete."""
    _, _, state = _state(stacked)
    d = str(tmp_path)
    real_rename = os.rename
    renamed = []

    def checked_rename(src, dst, *a, **k):
        if str(dst).split(os.sep)[-1].startswith("ckpt_") and str(
            src
        ).endswith(".tmp"):
            mpath = os.path.join(src, "manifest.json")
            assert os.path.exists(mpath), "rename before manifest write"
            with open(mpath) as f:
                manifest = json.load(f)
            entries = manifest["leaves"] + manifest.get("stacked", [])
            assert entries
            for entry in entries:
                assert os.path.exists(os.path.join(src, entry["file"]))
            renamed.append(dst)
        return real_rename(src, dst, *a, **k)

    monkeypatch.setattr(os, "rename", checked_rename)
    try:
        path = ckpt.save(d, 1, state, async_=True)
        assert path.endswith("ckpt_00000001")
        # While the writer runs, pollers may only ever see complete ckpts.
        for _ in range(50):
            _complete_dirs(d)
    finally:
        ckpt.wait_pending()
    assert renamed, "atomic publish rename never happened"
    assert _complete_dirs(d) == ["ckpt_00000001"]
    assert ckpt.latest_step(d) == 1


@pytest.mark.parametrize("stacked", [False, True])
def test_async_save_ordering_and_wait(tmp_path, stacked):
    tx, params, state = _state(stacked)
    d = str(tmp_path)
    for step in (1, 2, 3):
        ckpt.save(d, step, state, keep=10, async_=True)
    ckpt.wait_pending()
    assert ckpt.latest_step(d) == 3
    assert _complete_dirs(d) == [
        "ckpt_00000001", "ckpt_00000002", "ckpt_00000003"
    ]
    template = jax.eval_shape(lambda: tx.init(params))
    restored = ckpt.restore(d, template)  # newest, readable
    for a, b in zip(jax.tree_util.tree_leaves(restored),
                    jax.tree_util.tree_leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("stacked", [False, True])
def test_keep_pruning(tmp_path, stacked):
    """keep= retains only the newest N complete checkpoints; pruning never
    touches the newest one and restore still works after GC."""
    tx, params, state = _state(stacked)
    d = str(tmp_path)
    for step in range(1, 6):
        ckpt.save(d, step, state, keep=2)
    kept = _complete_dirs(d)
    assert kept == ["ckpt_00000004", "ckpt_00000005"]
    assert ckpt.latest_step(d) == 5
    template = jax.eval_shape(lambda: tx.init(params))
    restored = ckpt.restore(d, template, step=4)
    np.testing.assert_array_equal(
        np.asarray(restored.count), np.asarray(state.count)
    )


@pytest.mark.parametrize("stacked", [False, True])
def test_bf16_as_uint16_roundtrip(tmp_path, stacked):
    """bf16 arrays are stored as uint16 views with the logical dtype in the
    manifest, for per-leaf AND stacked entries; restore recovers the exact
    bf16 bits."""
    tx, params, state = _state(stacked, state_dtype=jnp.bfloat16)
    d = str(tmp_path)
    ckpt.save(d, 1, state)
    # the manifest records bfloat16 logical dtypes somewhere
    with open(os.path.join(d, "ckpt_00000001", "manifest.json")) as f:
        manifest = json.load(f)
    entries = manifest["leaves"] + manifest.get("stacked", [])
    assert any(e["dtype"] == "bfloat16" for e in entries)
    if stacked:
        assert any(
            e["dtype"] == "bfloat16" for e in manifest["stacked"]
        ), "stacked bf16 arrays must go through the uint16 view too"
    # and the files on disk are uint16 (numpy has no bf16)
    bf16_entry = next(e for e in entries if e["dtype"] == "bfloat16")
    raw = np.load(os.path.join(d, "ckpt_00000001", bf16_entry["file"]))
    assert raw.dtype == np.uint16
    template = jax.eval_shape(lambda: tx.init(params))
    restored = ckpt.restore(d, template)
    for a, b in zip(jax.tree_util.tree_leaves(restored),
                    jax.tree_util.tree_leaves(state)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(b.astype(jnp.float32)),
        )


# ---------------------------------------------------------------------------
# cross-version stacked codec (stacked-bucket/v1 <-> v2, conv leaves)
# ---------------------------------------------------------------------------
_RULES = ProjectionRules(rank=8, min_dim=8)


def _conv_state(stacked: bool, quantize: bool = False):
    """A mixed tree with a conv bucket (v2) and one jitted step of state."""
    params = {f"c{i}": 0.01 * jnp.ones((16, 12, 3, 3)) for i in range(3)}
    params["w"] = jnp.zeros((64, 32))
    params["bias"] = jnp.zeros((5,))
    tx = scale_by_projected_adam(
        ProjectedAdamConfig(rules=_RULES, t_update=2, lam=2,
                            quantize=quantize, stacked_state=stacked)
    )
    state = tx.init(params)
    key = jax.random.key(0)
    flat, treedef = jax.tree_util.tree_flatten(params)
    g = jax.tree_util.tree_unflatten(
        treedef,
        [
            0.1 * jax.random.normal(jax.random.fold_in(key, i), p.shape)
            for i, p in enumerate(flat)
        ],
    )
    _, state = jax.jit(lambda gg, s: tx.update(gg, s, None))(g, state)
    return tx, params, state


def _encode_v1(params, per_leaf_state):
    """Re-express a per-leaf state in the LEGACY v1 stacked layout (conv in
    the per-leaf tail) — what a v1 writer would have produced."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    layout_v1 = ss.layout_for_flat(
        _RULES.spec_for, flat, classify=ss.classify_v1
    )
    assert layout_v1.tail, "v1 layout must keep conv per-leaf"
    flat_states = jax.tree_util.tree_structure(params).flatten_up_to(
        per_leaf_state.leaves
    )
    return ProjectedAdamState(
        count=per_leaf_state.count,
        leaves=ss.encode(layout_v1, flat_states),
    )


def _rewrite_stacked_codecs(cdir: str, codec: str):
    mpath = os.path.join(cdir, "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    assert manifest["stacked"]
    for se in manifest["stacked"]:
        se["codec"] = codec
    with open(mpath, "w") as f:
        json.dump(manifest, f)


def _leaves_equal(got, want, treedef):
    if isinstance(got, ss.StackedLeaves):
        got = jax.tree_util.tree_unflatten(treedef, ss.decode(got))
    if isinstance(want, ss.StackedLeaves):
        want = jax.tree_util.tree_unflatten(treedef, ss.decode(want))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(b.astype(jnp.float32)),
        )


@pytest.mark.parametrize("quantize", [False, True])
def test_v1_checkpoint_restores_under_v2(tmp_path, quantize):
    """A faithful stacked-bucket/v1 checkpoint — conv states as plain
    per-leaf entries, matrix buckets tagged with the v1 codec — restores
    under v2 code into BOTH a v2 stacked template (conv buckets assemble
    slot-by-slot via the logical-path namespace) and a per-leaf template."""
    tx_p, params, state_p = _conv_state(stacked=False, quantize=quantize)
    tx_s, _, _ = _conv_state(stacked=True, quantize=quantize)
    treedef = jax.tree_util.tree_structure(params)
    v1_state = _encode_v1(params, state_p)

    d = str(tmp_path)
    ckpt.save(d, 1, v1_state)
    cdir = os.path.join(d, "ckpt_00000001")
    _rewrite_stacked_codecs(cdir, ss.STACKED_CODEC_V1)
    with open(os.path.join(cdir, "manifest.json")) as f:
        manifest = json.load(f)
    # faithful v1 file: conv arrays are per-leaf 'leaves' entries
    assert any("/p_o" in e["path"] for e in manifest["leaves"])
    assert all(
        se["codec"] == ss.STACKED_CODEC_V1 for se in manifest["stacked"]
    )

    for tx_dst in (tx_s, tx_p):
        template = jax.eval_shape(lambda tx=tx_dst: tx.init(params))
        restored = ckpt.restore(d, template)
        _leaves_equal(restored.leaves, state_p.leaves, treedef)
        np.testing.assert_array_equal(
            np.asarray(restored.count), np.asarray(state_p.count)
        )


@pytest.mark.parametrize("quantize", [False, True])
def test_v2_checkpoint_restores_into_v1_layout_template(tmp_path, quantize):
    """The reverse direction: a v2 checkpoint (conv bucketed) restores into
    a LEGACY v1-layout template (conv in the tail) — conv leaves load as
    slices of their bucket files."""
    tx_s, params, state_s = _conv_state(stacked=True, quantize=quantize)
    _, _, state_p = _conv_state(stacked=False, quantize=quantize)
    treedef = jax.tree_util.tree_structure(params)
    d = str(tmp_path)
    ckpt.save(d, 2, state_s)
    with open(
        os.path.join(d, "ckpt_00000002", "manifest.json")
    ) as f:
        manifest = json.load(f)
    assert all(se["codec"] == ss.STACKED_CODEC for se in manifest["stacked"])
    # v2 file: conv states live inside stacked bucket entries
    assert any(
        any("/p_o" in sp for sp in se["slots"]) for se in manifest["stacked"]
    )

    template = jax.eval_shape(lambda: _encode_v1(params, state_p))
    restored = ckpt.restore(d, template)
    assert isinstance(restored.leaves, ss.StackedLeaves)
    assert restored.leaves.layout.tail, "template layout keeps conv per-leaf"
    _leaves_equal(restored.leaves, state_s.leaves, treedef)


def test_unknown_future_codec_fails_loudly(tmp_path):
    """A stacked-bucket/v3 entry must raise, never mis-slice."""
    tx_s, params, state_s = _conv_state(stacked=True)
    d = str(tmp_path)
    ckpt.save(d, 1, state_s)
    _rewrite_stacked_codecs(
        os.path.join(d, "ckpt_00000001"), "stacked-bucket/v3"
    )
    template = jax.eval_shape(lambda: tx_s.init(params))
    with pytest.raises(ValueError, match="codec"):
        ckpt.restore(d, template)


def test_elastic_reshard_v1_checkpoint_to_v2_template():
    """A v1-layout checkpoint saved on a 4-device mesh restores onto an
    8-device mesh into a v2 stacked template — cross-version logical paths
    plus elastic device_put in one motion."""
    import test_distributed

    test_distributed.run_sub("""
        import json, os, tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_test_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import stacked_state as ss
        from repro.core.coap_adam import (
            ProjectedAdamConfig, ProjectedAdamState, scale_by_projected_adam)
        from repro.core.projector import ProjectionRules
        from repro.train import checkpoint as ckpt

        rules = ProjectionRules(rank=8, min_dim=8)
        params = {f"c{i}": 0.01 * jnp.ones((16, 12, 3, 3)) for i in range(3)}
        params["w"] = jnp.zeros((64, 32))
        flat, treedef = jax.tree_util.tree_flatten(params)
        key = jax.random.key(0)
        g = jax.tree_util.tree_unflatten(treedef, [
            0.1 * jax.random.normal(jax.random.fold_in(key, i), p.shape)
            for i, p in enumerate(flat)])

        def build(stacked):
            tx = scale_by_projected_adam(ProjectedAdamConfig(
                rules=rules, t_update=2, lam=2, stacked_state=stacked))
            st = tx.init(params)
            _, st = jax.jit(lambda gg, s: tx.update(gg, s, None))(g, st)
            return tx, st

        tx_p, st_p = build(False)
        tx_s, st_s = build(True)

        # legacy v1 layout: conv in the per-leaf tail
        fp, _ = jax.tree_util.tree_flatten_with_path(params)
        layout_v1 = ss.layout_for_flat(rules.spec_for, fp,
                                       classify=ss.classify_v1)
        st_v1 = ProjectedAdamState(
            count=st_p.count,
            leaves=ss.encode(
                layout_v1, treedef.flatten_up_to(st_p.leaves)),
        )
        mesh4 = make_test_mesh((4,), ("data",))
        st_sharded = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, NamedSharding(mesh4, P())), st_v1)
        tmp = tempfile.mkdtemp()
        ckpt.save(tmp, 1, st_sharded)
        cdir = os.path.join(tmp, "ckpt_00000001")
        with open(os.path.join(cdir, "manifest.json")) as f:
            manifest = json.load(f)
        for se in manifest["stacked"]:
            se["codec"] = ss.STACKED_CODEC_V1
        with open(os.path.join(cdir, "manifest.json"), "w") as f:
            json.dump(manifest, f)

        mesh8 = make_test_mesh((8,), ("data",))
        template = jax.eval_shape(lambda: tx_s.init(params))
        specs = jax.tree_util.tree_map(
            lambda _: P(), template, is_leaf=lambda x: hasattr(x, "shape"))
        restored = ckpt.restore(tmp, template, mesh=mesh8, spec_tree=specs)
        got = ss.decode(restored.leaves)
        want = ss.decode(st_s.leaves)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert len(restored.leaves.layout.conv_bucket_sizes()) == 1
        print("elastic v1->v2 reshard ok")
    """)


def test_v1_manifest_still_restores(tmp_path):
    """Version-1 manifests (pre-codec: no version/stacked keys) keep
    restoring — forward compatibility for old checkpoints."""
    d = str(tmp_path)
    state = {"w": jnp.arange(8, dtype=jnp.bfloat16), "c": jnp.asarray(3)}
    ckpt.save(d, 1, state)
    cdir = os.path.join(d, "ckpt_00000001")
    with open(os.path.join(cdir, "manifest.json")) as f:
        manifest = json.load(f)
    del manifest["version"]
    del manifest["stacked"]
    with open(os.path.join(cdir, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    template = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state
    )
    restored = ckpt.restore(d, template)
    assert restored["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(restored["w"].astype(jnp.float32)),
        np.asarray(state["w"].astype(jnp.float32)),
    )


# ---------------------------------------------------------------------------
# Torn-checkpoint detection (crc32 integrity, manifest v2 optional field)
# ---------------------------------------------------------------------------
def _template_like(state):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)


@pytest.mark.parametrize("stacked", [False, True])
def test_crc32_recorded_and_clean_restore(tmp_path, stacked):
    """Every array row carries a crc32; an untouched checkpoint restores."""
    _, _, state = _state(stacked)
    d = str(tmp_path)
    ckpt.save(d, 3, state)
    with open(os.path.join(d, "ckpt_00000003", "manifest.json")) as f:
        manifest = json.load(f)
    rows = manifest["leaves"] + manifest.get("stacked", [])
    assert rows and all("crc32" in r for r in rows)
    restored = ckpt.restore(d, _template_like(state))
    for a, b in zip(jax.tree_util.tree_leaves(restored),
                    jax.tree_util.tree_leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("stacked", [False, True])
def test_torn_write_fails_loudly_naming_file(tmp_path, stacked):
    """Garbling one array file after the atomic rename (fault-injected
    partial copy) raises TornCheckpointError naming the offending file."""
    from repro.train.faults import FaultInjector, FaultSchedule

    _, _, state = _state(stacked)
    d = str(tmp_path)
    ckpt.save(d, 2, state)
    inj = FaultInjector(FaultSchedule(torn_write_at=(2,)), seed=1)
    inj.after_save(d, 2)
    assert inj.torn == 1
    with pytest.raises(ckpt.TornCheckpointError) as ei:
        ckpt.restore(d, _template_like(state))
    assert "ckpt_00000002" in str(ei.value)
    assert ".npy" in str(ei.value)


def test_manifest_without_crc32_still_restores(tmp_path):
    """crc32 is an OPTIONAL manifest field: stripping it (older v2
    writers) must not break restore — backward compatibility."""
    state = {"w": jnp.arange(12.0).reshape(3, 4), "c": jnp.asarray(7)}
    d = str(tmp_path)
    ckpt.save(d, 1, state)
    mpath = os.path.join(d, "ckpt_00000001", "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    for row in manifest["leaves"] + manifest.get("stacked", []):
        row.pop("crc32", None)
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    restored = ckpt.restore(d, _template_like(state))
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(state["w"]))


def test_meta_roundtrips_and_steps_listing(tmp_path):
    """save(meta=...) rides the manifest atomically; read_meta / steps
    expose it (the elastic supervisor stores the plan artifact here)."""
    state = {"w": jnp.ones((4,))}
    d = str(tmp_path)
    ckpt.save(d, 2, state, meta={"plan": {"answer": 42}})
    ckpt.save(d, 5, state)
    assert ckpt.steps(d) == [2, 5]
    assert ckpt.read_meta(d, 2) == {"plan": {"answer": 42}}
    assert ckpt.read_meta(d, 5) is None
    assert ckpt.read_meta(d) is None  # latest (5) has no meta

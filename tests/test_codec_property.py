"""Property-based stacked-state codec tests (stacked-bucket/v2).

Randomized pytrees mixing dense, projected and conv (Tucker-2) leaves —
drawn through ``hypothesis`` (derandomized by ``tests/conftest.py``) —
must satisfy, for every draw:

  * ``decode(encode(x)) == x`` bit-for-bit, int8 codes and scales
    included, with ``leaf_view`` agreeing at every flat index;
  * the layout is a partition: every flat leaf index appears exactly once
    across buckets + tail, projected buckets first, conv before dense;
  * every ``manifest_entries`` logical path resolves back to its leaf:
    stacked entries' axis-0 slices equal the per-leaf arrays their
    ``slots`` name, and the stacked and per-leaf walks of the same state
    expose the identical logical-path namespace;
  * the codec tag is ``stacked-bucket/v2`` with v1 still decodable.
"""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import stacked_state as ss
from repro.core.coap_adam import ProjectedAdamConfig, scale_by_projected_adam
from repro.core.projector import ProjectionRules

# Congruence pools: several leaves may share a signature (multi-leaf
# buckets) or not (singletons) depending on the draw.
_PROJ_SHAPES = [(48, 32), (64, 24), (32, 48)]
_CONV_SHAPES = [(16, 12, 3, 3), (16, 16, 3, 3), (12, 16, 2, 2)]
_DENSE_SHAPES = [(7,), (4, 4), (9,)]


def _build_params(n_proj, n_conv, n_dense, seed):
    """Deterministic mixed tree from the draw; >=1 leaf guaranteed."""
    rng = np.random.RandomState(seed)
    p = {}
    for j in range(n_proj):
        shape = _PROJ_SHAPES[rng.randint(len(_PROJ_SHAPES))]
        p[f"proj{j}"] = {"w": jnp.zeros(shape)}
    for j in range(n_conv):
        shape = _CONV_SHAPES[rng.randint(len(_CONV_SHAPES))]
        p[f"conv{j}_kernel"] = 0.01 * jnp.ones(shape)
    for j in range(n_dense + 1):  # always at least one leaf in the tree
        shape = _DENSE_SHAPES[rng.randint(len(_DENSE_SHAPES))]
        p[f"bias{j}"] = jnp.zeros(shape)
    return p


def _stepped_state(params, quantize, seed):
    """An optimizer state with non-trivial contents (one jitted step)."""
    cfg = ProjectedAdamConfig(
        rules=ProjectionRules(rank=8, min_dim=8), t_update=2, lam=2,
        quantize=quantize,
    )
    tx = scale_by_projected_adam(cfg)
    state = tx.init(params)
    key = jax.random.key(seed)
    flat, treedef = jax.tree_util.tree_flatten(params)
    g = jax.tree_util.tree_unflatten(
        treedef,
        [
            0.1 * jax.random.normal(jax.random.fold_in(key, i), x.shape)
            for i, x in enumerate(flat)
        ],
    )
    _, state = jax.jit(lambda gg, s: tx.update(gg, s, None))(g, state)
    return cfg, state


def _layout_for(cfg, params):
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return ss.layout_for_flat(cfg.rules.spec_for, flat)


@settings(max_examples=6, deadline=None)
@given(
    n_proj=st.integers(min_value=0, max_value=4),
    n_conv=st.integers(min_value=0, max_value=4),
    n_dense=st.integers(min_value=0, max_value=2),
    quantize=st.booleans(),
    seed=st.integers(min_value=0, max_value=10_000),
)
def test_roundtrip_bitexact_random_trees(n_proj, n_conv, n_dense, quantize,
                                         seed):
    """decode(encode(x)) == x bit-for-bit and leaf_view == decode at every
    index, for randomized mixed trees under stacked-bucket/v2."""
    params = _build_params(n_proj, n_conv, n_dense, seed)
    cfg, state = _stepped_state(params, quantize, seed)
    layout = _layout_for(cfg, params)
    treedef = jax.tree_util.tree_structure(params)
    flat_states = treedef.flatten_up_to(state.leaves)

    stacked = ss.encode(layout, flat_states)
    decoded = ss.decode(stacked)
    assert len(decoded) == len(flat_states) == layout.n_leaves
    for a, b in zip(
        jax.tree_util.tree_leaves(flat_states),
        jax.tree_util.tree_leaves(decoded),
    ):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for i in range(layout.n_leaves):
        for a, b in zip(
            jax.tree_util.tree_leaves(ss.leaf_view(stacked, i)),
            jax.tree_util.tree_leaves(decoded[i]),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=6, deadline=None)
@given(
    n_proj=st.integers(min_value=0, max_value=4),
    n_conv=st.integers(min_value=0, max_value=4),
    n_dense=st.integers(min_value=0, max_value=2),
    seed=st.integers(min_value=0, max_value=10_000),
)
def test_layout_partitions_every_leaf(n_proj, n_conv, n_dense, seed):
    """The layout is a partition of the flat indices with the v2 bucket
    order (project, conv, dense) and an empty tail under the default
    classification; bucket members share their congruence signature."""
    params = _build_params(n_proj, n_conv, n_dense, seed)
    cfg = ProjectedAdamConfig(rules=ProjectionRules(rank=8, min_dim=8))
    layout = _layout_for(cfg, params)
    assert layout.version == ss.STACKED_STATE_VERSION == 2
    assert layout.tail == ()
    seen = sorted(i for b in layout.buckets for i in b.indices)
    assert seen == list(range(layout.n_leaves))
    order = [b.kind for b in layout.buckets]
    rank = {ss.BUCKET_PROJECT: 0, ss.BUCKET_CONV: 1, ss.BUCKET_DENSE: 2}
    assert order == sorted(order, key=rank.__getitem__)
    for b in layout.buckets:
        assert len(b.indices) == len(b.paths) >= 1
        assert len(b.indices) == len(set(b.indices))
    assert layout.staggerable_bucket_sizes() == (
        layout.proj_bucket_sizes() + layout.conv_bucket_sizes()
    )


@settings(max_examples=6, deadline=None)
@given(
    n_proj=st.integers(min_value=0, max_value=3),
    n_conv=st.integers(min_value=1, max_value=4),
    n_dense=st.integers(min_value=0, max_value=2),
    quantize=st.booleans(),
    seed=st.integers(min_value=0, max_value=10_000),
)
def test_manifest_logical_paths_resolve(n_proj, n_conv, n_dense, quantize,
                                        seed):
    """Every stacked manifest entry's slot path resolves back to its leaf:
    slice j of the bucket array equals the per-leaf array the logical path
    names, and both storage modes expose one logical-path namespace."""
    params = _build_params(n_proj, n_conv, n_dense, seed)
    cfg, state = _stepped_state(params, quantize, seed)
    layout = _layout_for(cfg, params)
    treedef = jax.tree_util.tree_structure(params)
    flat_states = treedef.flatten_up_to(state.leaves)
    stacked = ss.encode(layout, flat_states)
    per_leaf_tree = jax.tree_util.tree_unflatten(treedef, flat_states)

    stacked_entries = ss.manifest_entries({"opt": stacked})
    leaf_entries = ss.manifest_entries({"opt": per_leaf_tree})
    by_path = {e.path: e.value for e in leaf_entries}
    assert all(e.kind == "leaf" for e in leaf_entries)

    logical = set()
    for e in stacked_entries:
        if e.kind == "stacked":
            assert e.slots is not None and len(e.slots) == e.value.shape[0]
            for j, sp in enumerate(e.slots):
                assert sp in by_path, sp
                np.testing.assert_array_equal(
                    np.asarray(e.value[j]), np.asarray(by_path[sp])
                )
                logical.add(sp)
        else:
            assert e.path in by_path
            np.testing.assert_array_equal(
                np.asarray(e.value), np.asarray(by_path[e.path])
            )
            logical.add(e.path)
    # one shared namespace: the stacked walk covers exactly the per-leaf one
    assert logical == set(by_path)


def test_codec_tag_is_v2_and_v1_decodable():
    assert ss.STACKED_CODEC == "stacked-bucket/v2"
    assert ss.STACKED_CODEC_V1 == "stacked-bucket/v1"
    assert ss.DECODABLE_CODECS == {ss.STACKED_CODEC_V1, ss.STACKED_CODEC}
    assert ss.STACKED_STATE_VERSION == 2

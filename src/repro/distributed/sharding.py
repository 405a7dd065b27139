"""Logical-axis sharding: one rule table lays out every architecture.

Mesh axes: ``pod`` (pure DP across pods — slow inter-pod links; params
replicated, gradients all-reduced, optionally COAP-compressed, see
``distributed/compression.py``), ``data`` (FSDP: params/grads/optimizer
states sharded, all-gather on use), ``model`` (tensor parallel: heads / ffn
/ vocab).

Every ParamDef carries logical axis names; ``spec_for_axes`` maps them to
mesh axes, dropping any axis that does not divide evenly (safe fallback to
replication — e.g. the 8-expert dim on a 16-way axis stays local, DESIGN.md
§4). Activation/cache constraints are applied only when an ambient mesh
is set (``jax.set_mesh``), so the same model code runs unsharded on
CPU tests.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.models.layers import ParamDef, is_param_def

# Logical axis -> preferred mesh axis (in priority order; first that fits).
PARAM_RULES: Dict[str, Tuple[str, ...]] = {
    "vocab": ("model",),
    "embed": ("data",),  # FSDP dim
    "ffn": ("model",),
    "heads": ("model",),
    "lora": ("model",),  # MLA latents: small; sharded if divisible
    "experts": (),  # 8 experts never divide the 16-way axes: keep local
    "moe_embed": (),  # expert d_model: replicated (see models/moe.py note)
    "layers": (),  # scan dim
}

# A second table used by the perf hillclimb (EXPERIMENTS.md §Perf) — fully
# model-parallel layout for tiny models where FSDP all-gathers dominate.
PARAM_RULES_TP_ONLY: Dict[str, Tuple[str, ...]] = {
    **PARAM_RULES,
    "embed": (),
}

# Decode-time layout: expert weights ARE the traffic at 1-token steps, so
# shard their d_model over 'data' (train replicates it to kill per-layer
# activation all-reduces — see models/moe.py; EXPERIMENTS.md §Perf). The
# serve engine loads checkpoints with this table; elastic restore reshards.
PARAM_RULES_SERVE: Dict[str, Tuple[str, ...]] = {
    **PARAM_RULES,
    "moe_embed": ("data",),
}


def mesh_axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 0


def current_mesh():
    """The ambient mesh entered with ``jax.set_mesh`` (None when unsharded).

    Inside a ``jax.shard_map`` body this is the abstract mesh, with the
    body's manual axes typed ``Manual``. Meshes with ``Explicit`` axes are
    rejected here, before a spec deep in the model fails on them: the
    model's constraints are written for ``Auto`` axes
    (``launch/mesh.py``)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return None
    explicit = [a for a, t in zip(mesh.axis_names, mesh.axis_types)
                if t == AxisType.Explicit]
    if explicit:
        raise ValueError(
            f"mesh axes {explicit} are Explicit; this program shards with "
            "Auto axes — build the mesh with repro.launch.mesh (axis_types="
            "AxisType.Auto)"
        )
    return mesh


def spec_for_axes(axes: Sequence[Optional[str]], shape: Sequence[int], mesh,
                  rules: Dict[str, Tuple[str, ...]] = PARAM_RULES) -> P:
    """Logical axes tuple -> PartitionSpec, dropping non-dividing axes."""
    used = set()
    out = []
    for dim, ax in zip(shape, axes):
        chosen = None
        if ax is not None:
            for cand in rules.get(ax, ()):
                size = mesh_axis_size(mesh, cand)
                if size and dim % size == 0 and cand not in used:
                    chosen = cand
                    used.add(cand)
                    break
        out.append(chosen)
    return P(*out)


def param_specs(defs, mesh, rules=PARAM_RULES):
    """Def-tree -> PartitionSpec tree."""
    return jax.tree_util.tree_map(
        lambda d: spec_for_axes(d.axes, d.shape, mesh, rules),
        defs,
        is_leaf=is_param_def,
    )


def named(mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def tree_named(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def replicated_specs(template) -> Any:
    """``P()`` for every array leaf of ``template`` — the elastic
    supervisor's default placement when restoring a checkpoint onto a
    freshly-built (possibly resized) data mesh: land replicated first,
    then let pjit reshard into the step function's layout. The template
    may be abstract (ShapeDtypeStructs from ``jax.eval_shape``)."""
    return jax.tree_util.tree_map(lambda _: P(), template)


# ---------------------------------------------------------------------------
# Activation / batch constraints
# ---------------------------------------------------------------------------
def batch_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes the global batch shards over (pod+data when present)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _nonmanual_axes(mesh) -> set:
    """Axes usable in sharding constraints (drops shard_map-manual axes)."""
    return {a for a, t in zip(mesh.axis_names, mesh.axis_types)
            if t != AxisType.Manual}


def constrain(x, logical: Sequence[Optional[str]]):
    """with_sharding_constraint via logical names; no-op without a mesh.

    logical entries: 'batch' | 'seq_data' | 'model' | 'data' | None.
    Axes currently Manual (inside shard_map) are dropped from constraints.
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    allowed = _nonmanual_axes(mesh)
    used = set()
    axes = []
    for dim, ax in zip(x.shape, logical):
        if ax == "batch":
            cand = tuple(a for a in batch_axes(mesh) if a in allowed)
            total = 1
            for c in cand:
                total *= mesh.shape[c]
            if cand and dim % total == 0 and not (set(cand) & used):
                axes.append(cand if len(cand) > 1 else cand[0])
                used.update(cand)
            else:
                axes.append(None)
        elif ax in ("seq_data", "data", "model"):
            name = "data" if ax == "seq_data" else ax
            size = mesh_axis_size(mesh, name)
            if size and dim % size == 0 and name not in used and name in allowed:
                axes.append(name)
                used.add(name)
            else:
                axes.append(None)
        else:
            axes.append(None)
    return jax.lax.with_sharding_constraint(x, P(*axes))


def batch_specs(batch_tree, mesh, seq_shard: bool = False):
    """Shardings for the input batch dict: batch dim over (pod, data) —
    or, when the batch doesn't divide (long_500k B=1), the sequence dim
    over 'data' (sequence parallelism)."""
    baxes = batch_axes(mesh)
    total = 1
    for a in baxes:
        total *= mesh.shape[a]

    def one(x):
        shape = x.shape
        if len(shape) == 0:
            return P()
        spec: list = [None] * len(shape)
        # positions for mrope have a leading (3,...) axis; batch is axis 1
        b_axis = 1 if (len(shape) >= 2 and shape[0] == 3) else 0
        if shape[b_axis] % total == 0 and total > 1 and not seq_shard:
            spec[b_axis] = baxes if len(baxes) > 1 else baxes[0]
        elif len(shape) > b_axis + 1 and "data" in mesh.axis_names:
            # sequence parallelism fallback
            s_axis = b_axis + 1
            if shape[s_axis] % mesh.shape["data"] == 0:
                spec[s_axis] = "data"
        return P(*spec)

    return jax.tree_util.tree_map(one, batch_tree)

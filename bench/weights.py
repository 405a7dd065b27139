"""Weights of a cell, made on the device from the seed in one jitted call.

The layout is the parameter tree the program trains (paths and shapes),
as ``layout(arch)`` of the reference module the configuration names
states it; the harness refuses to run where the program's tree differs.
Initialisation, by path:

* ``embed/embedding``: normal, standard deviation 0.02;
* norms (``ln1``, ``ln2``, ``final_norm``): ones; biases: zeros;
* every other matrix: normal with standard deviation ``1/sqrt(fan_in)``,
  ``fan_in`` being the size of its second-to-last axis.

Each leaf draws from a key made from the seed's low and high 32 bits (a
seed may be larger than 32 bits hold), folded with the leaf's index in
the sorted list of paths, so the reference remakes the same weights.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _init_leaf(key, path: str, shape):
    name = path.rsplit("/", 1)[-1]
    if name.endswith("bias"):
        return jnp.zeros(shape, jnp.float32)
    if "norm" in name or name.startswith("ln"):
        return jnp.ones(shape, jnp.float32)
    std = 0.02 if path == "embed/embedding" else 1.0 / math.sqrt(shape[-2])
    return std * jax.random.normal(key, shape, jnp.float32)


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, x in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return out


def _make(layout_items, seed_words):
    key = jax.random.fold_in(jax.random.key(seed_words[0]), seed_words[1])
    flat = {path: _init_leaf(jax.random.fold_in(key, i), path, shape)
            for i, (path, shape) in enumerate(layout_items)}
    return nest(flat)


def maker(layout: dict):
    """A jitted ``seed -> params`` for this layout (one compiled program)."""
    items = tuple(sorted((p, tuple(s)) for p, s in layout.items()))
    fn = jax.jit(functools.partial(_make, items))

    def make(seed: int):
        words = jnp.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                            jnp.uint32)
        return fn(words)

    return make

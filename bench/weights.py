"""Weights of a cell, made on the device from the seed in one jitted call.

The layout is the benchmark's own statement of the parameter tree the
program trains (paths and shapes); the harness refuses to run where the
program's tree differs. Initialisation, by path:

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


def dense_gqa_layout(arch: dict) -> dict:
    """{path: shape} of a dense GQA decoder with an untied head."""
    d, f, v, n = arch["d_model"], arch["d_ff"], arch["vocab_size"], arch["n_layers"]
    q, kv = arch["n_heads"] * arch["head_dim"], arch["n_kv_heads"] * arch["head_dim"]
    shapes = {
        "embed/embedding": (v, d),
        "final_norm": (d,),
        "lm_head/w": (d, v),
        "stack/attn/wq": (n, d, q),
        "stack/attn/wk": (n, d, kv),
        "stack/attn/wv": (n, d, kv),
        "stack/attn/wo": (n, q, d),
        "stack/ln1": (n, d),
        "stack/ln2": (n, d),
        "stack/mlp/gate": (n, d, f),
        "stack/mlp/up": (n, d, f),
        "stack/mlp/down": (n, f, d),
    }
    if arch.get("qkv_bias"):
        shapes.update({"stack/attn/wq_bias": (n, q), "stack/attn/wk_bias": (n, kv),
                       "stack/attn/wv_bias": (n, kv)})
    return shapes


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

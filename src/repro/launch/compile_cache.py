"""JAX's persistent compilation cache, kept at a fixed path.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads the variable itself
and this module sets no other directory. Otherwise the cache lives in
``<checkout>/.jax_cache`` (git-ignored). The path is fixed, with no
temporary name, process id or time in it, so a later run of the same
program in the same checkout finds what an earlier run compiled.

The cache key hashes the program, and a Pallas kernel carries the source
file paths of its body in its serialized Mosaic module. Source paths are
therefore made relative to the checkout, so that two checkouts of the same
commit share their entries.

Setting the options starts no JAX backend, so a supervisor parent that
must stay off the accelerator may call this too.
"""
from __future__ import annotations

import os
import pathlib
import re

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(f"{CHECKOUT}{os.sep}"))
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


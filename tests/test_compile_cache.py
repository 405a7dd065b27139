"""The persistent compilation cache lives where JAX_COMPILATION_CACHE_DIR
says, and otherwise in ``<checkout>/.jax_cache``."""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_DIR = os.path.join(REPO, ".jax_cache")

# Compiles one small function the cache has never seen: the constant is
# the child's pid, so the entry is new in a shared directory too.
BODY = textwrap.dedent("""
    import os
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    print("DIR", enable_compile_cache())
    print("CFG", jax.config.jax_compilation_cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    k = float(os.getpid())
    jax.jit(lambda v: jnp.sin(v) * k)(jnp.arange(7.0)).block_until_ready()
""")


def _run(env_dir):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run([sys.executable, "-c", BODY], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    lines = dict(line.split(" ", 1) for line in proc.stdout.splitlines())
    return lines["DIR"], lines["CFG"]


def _entries(path):
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def test_cache_goes_where_the_environment_says(tmp_path):
    target = str(tmp_path / "cache")
    used, cfg = _run(target)
    assert used == cfg == target
    assert any(name.startswith("jit__lambda") for name in _entries(target))


def test_cache_defaults_to_the_checkout():
    before = _entries(DEFAULT_DIR)
    used, cfg = _run(None)
    assert used == cfg == DEFAULT_DIR
    new = _entries(DEFAULT_DIR) - before
    assert any(name.startswith("jit__lambda") for name in new)


def test_source_paths_are_relative_to_the_checkout():
    """A Pallas kernel carries the source paths of its body into the cache
    key, so they are made relative: two checkouts share their entries."""
    body = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import CHECKOUT, enable_compile_cache
        from repro.kernels import ref
        enable_compile_cache()
        text = jax.jit(ref.rmsnorm).lower(
            jnp.ones((4, 8)), jnp.ones(8)).as_text(debug_info=True)
        assert str(CHECKOUT) not in text
        assert 'loc("src/repro/kernels/ref.py"' in text
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", body], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr


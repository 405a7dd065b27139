"""GPipe pipeline stage == sequential execution (8 host devices)."""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body, devices=8, timeout=600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                          env=env, capture_output=True, text=True,
                          timeout=timeout)
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"
    return proc.stdout


def test_pipeline_matches_sequential():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_test_mesh
        from repro.distributed.pipeline import pipeline_apply, split_stage_params

        L, D = 8, 32
        key = jax.random.key(0)
        params = {"w": 0.3 * jax.random.normal(key, (L, D, D)),
                  "b": 0.01 * jax.random.normal(jax.random.fold_in(key, 1),
                                                 (L, D))}
        def layer(p, h):
            return jnp.tanh(h @ p["w"] + p["b"])

        def stage_fn(stage_params, h):
            def body(hh, p):
                return layer(p, hh), None
            h, _ = jax.lax.scan(body, h, stage_params)
            return h

        x = jax.random.normal(jax.random.fold_in(key, 2), (6, 4, D))  # 6 micro

        # sequential reference
        ref = jax.vmap(lambda mb: stage_fn(params, mb))(x)

        for n_stages in (2, 4):
            mesh = make_test_mesh((n_stages, 8 // n_stages), ("pod", "data"))
            sp = split_stage_params(params, n_stages)
            out = pipeline_apply(stage_fn, sp, x, mesh=mesh, axis="pod")
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-5, atol=2e-6)
            print(f"pipeline {n_stages} stages ok")
    """)

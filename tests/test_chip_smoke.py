"""CPU rehearsal of ``chip_smoke.py``: its phases at a smoke size, the
Pallas bodies in interpret mode, and the cross-chip phase on 4 virtual
devices. The script itself refuses to run without a TPU."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from repro.configs import get_smoke  # noqa: E402


def _smoke_cfg():
    return dataclasses.replace(get_smoke(chip_smoke.ARCH), remat=True)


def test_kernel_parity_phase_interpret(monkeypatch):
    """The parity phase at shapes no other test traces: ``kernels/ops``
    caches its traces without keying on REPRO_PALLAS."""
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    rows = chip_smoke.kernel_parity(
        fused_shapes=((136, 384, 128), (264, 256, 128)),
        eqn6_shape=(200, 384, 128),
        q8_stored_shapes=((256, 392, 128),),
    )
    assert [r["ok"] for r in rows] == [True] * 6
    # interpret mode lowers the bodies to plain XLA: no TPU kernel
    assert not any(r["custom_call"] for r in rows)


@pytest.mark.parametrize("optimizer", chip_smoke.OPTIMIZERS)
def test_train_phase_interpret(optimizer, monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    out = chip_smoke.train(
        _smoke_cfg(), optimizer, str(tmp_path / optimizer),
        save=True, rank=16, min_dim=16, batch=8, seq=64, lr=3e-3,
    )
    assert out["steps"] == chip_smoke.STEPS
    assert out["checkpoint_step"] == chip_smoke.STEPS
    assert out["last_loss"] < out["first_loss"]
    assert out["eqn6_steps"] >= 1 and out["recal_steps"] >= 1


def test_cross_chip_phase_on_virtual_devices():
    """``--chips 4``'s two comparisons on 4 host devices."""
    env = dict(os.environ)
    env.update(
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        JAX_PLATFORMS="cpu",
        REPRO_PALLAS="interpret",
    )
    code = textwrap.dedent(f"""
        import dataclasses, sys
        sys.path.insert(0, {REPO!r})
        import chip_smoke
        from repro.configs import get_smoke
        cfg = get_smoke(chip_smoke.ARCH)
        a = chip_smoke.sharded_step_parity(cfg, rank=16, batch=8, seq=32)
        assert a["param_devices"] == 4, a
        b = chip_smoke.compressed_step_parity(cfg, rank=16, batch=8, seq=32)
        assert sum(b["collectives"].values()) > 0, b
        print("cross-chip ok")
    """)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "cross-chip ok" in proc.stdout


def test_script_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_kernel_parity_without_a_tpu_kernel_fails(monkeypatch, capsys):
    """Parity against the oracle proves nothing if a kernel silently ran
    as the oracle: the script fails unless each program holds a
    ``tpu_custom_call``."""
    from repro.launch import compile_cache

    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "-")
    monkeypatch.setattr(chip_smoke.CacheLog, "install", lambda self: self)
    monkeypatch.setattr(chip_smoke, "check_device", lambda n: {})
    monkeypatch.setattr(chip_smoke, "kernel_parity", lambda: [
        {"name": "fused_bp", "ok": True, "custom_call": True},
        {"name": "eqn6", "ok": True, "custom_call": False}])
    with pytest.raises(SystemExit, match="tpu_custom_call.*eqn6"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_cache_log_counts_writes_then_hits(tmp_path):
    """The ``[cache]`` lines: a compile is written on the first run and
    read back on the second."""
    body = textwrap.dedent(f"""
        import json
        import jax, jax.numpy as jnp
        import sys
        sys.path.insert(0, {REPO!r})
        from chip_smoke import CacheLog
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        log = CacheLog().install()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda v: jnp.cos(v) * 3.0)(jnp.arange(5.0))
        print(json.dumps(log.take()))
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    runs = []
    for _ in range(2):
        proc = subprocess.run([sys.executable, "-c", body], env=env,
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr
        runs.append(json.loads(proc.stdout.splitlines()[-1]))
    assert "jit__lambda" in runs[0]["written"] and not runs[0]["hits"]
    assert "jit__lambda" in runs[1]["hits"] and not runs[1]["written"]
    assert runs[1]["misses"] == 0

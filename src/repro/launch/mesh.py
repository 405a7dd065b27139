"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets --xla_force_host_platform_device_count=512 before
any jax import; smoke tests see 1 device and never call these).

  single-pod: (16, 16)    = 256 chips,  axes (data, model)
  multi-pod:  (P, 16, 16) = P·256 chips, axes (pod, data, model); the pod
              axis is pure data parallelism over the slowest links — the
              compressed-sync wire model (``distributed/compression.py``,
              ``benchmarks/overhead.run_sync``) prices exactly this axis.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False, pods: int = 2):
    """The (data, model) production mesh; ``multi_pod=True`` prepends a pod
    axis of size ``pods`` (cross-pod topology sweeps vary this)."""
    if pods < 1:
        raise ValueError(f"pods must be >= 1, got {pods}")
    shape = (pods, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have {len(devices)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} (launch/dryrun.py sets this automatically)"
        )
    return jax.make_mesh(
        shape, axes, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def make_test_mesh(shape=(2, 2, 2), axes=("pod", "data", "model")):
    """Small Auto-axis mesh over the first ``prod(shape)`` devices, for the
    multi-device tests and the chip smoke run."""
    n = 1
    for s in shape:
        n *= s
    return jax.make_mesh(
        shape, axes, devices=jax.devices()[:n],
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def pod_mesh(pods: int = 2):
    """A pod-only mesh (pure cross-pod DP) for compressed-sync tests and
    benchmarks: ``(pods,)`` over axis 'pod'."""
    return jax.make_mesh(
        (pods,), ("pod",),
        axis_types=(jax.sharding.AxisType.Auto,),
    )

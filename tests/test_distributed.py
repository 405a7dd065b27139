"""Multi-device distribution tests (8 host CPU devices via subprocess, so the
main test process keeps its single-device jax). Covers: sharded train step,
cross-pod compressed gradients == uncompressed baseline, elastic checkpoint
reshard 4→8 devices, sharding-rule unit behaviour."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, devices: int = 8, timeout=600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    code = textwrap.dedent(body)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


def test_sharding_rules_unit():
    """Pure-python rule behaviour (no mesh devices needed beyond 8)."""
    run_sub("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_test_mesh
        from jax.sharding import PartitionSpec as P
        from repro.distributed import sharding as shd
        mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
        # standard 2D weight: embed->data, ffn->model
        s = shd.spec_for_axes(("embed", "ffn"), (128, 256), mesh)
        assert s == P("data", "model"), s
        # non-dividing dim falls back to replication
        s = shd.spec_for_axes(("embed", "ffn"), (127, 256), mesh)
        assert s == P(None, "model"), s
        # experts stay local; stacked layers unsharded
        s = shd.spec_for_axes(("layers", "experts", "embed", "ffn"),
                              (4, 8, 128, 256), mesh)
        assert s == P(None, None, "data", "model"), s
        print("rules ok")
    """)


def test_explicit_mesh_is_rejected_with_a_clear_error():
    run_sub("""
        import jax
        from jax.sharding import AxisType
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_test_mesh
        assert shd.current_mesh() is None
        auto = make_test_mesh((2, 2), ("data", "model"))
        with jax.set_mesh(auto):
            assert shd.current_mesh().axis_names == ("data", "model")
        explicit = jax.make_mesh((2, 2), ("data", "model"),
                                 axis_types=(AxisType.Explicit,) * 2)
        with jax.set_mesh(explicit):
            try:
                shd.current_mesh()
            except ValueError as e:
                assert "Explicit" in str(e), e
            else:
                raise AssertionError("explicit mesh accepted")
        print("ok")
    """, devices=4)


def test_kernel_dispatch_follows_the_ambient_mesh():
    """On TPU the kernels run unless the program is sharded over a
    non-manual mesh axis (XLA cannot partition a Pallas kernel)."""
    run_sub("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.kernels import ops
        from repro.launch.mesh import make_test_mesh
        from repro.obs.registry import get_registry
        jax.default_backend = lambda: "tpu"  # dispatch as on a chip
        assert ops._mode() == "pallas"
        with jax.set_mesh(make_test_mesh((2, 2), ("data", "model"))):
            assert ops._mode() == "ref"
            # a kernel's trace that a mesh sends to the jnp path is counted
            assert ops._mode("rmsnorm") == "ref"
        assert get_registry().snapshot()["counters"] == {
            "kernels/on_mesh/rmsnorm": 1}
        with jax.set_mesh(make_test_mesh((1,), ("pod",))):
            assert ops._mode() == "pallas"
        modes = []

        def body(x):
            modes.append(ops._mode())
            return x

        pod = make_test_mesh((2,), ("pod",))
        with jax.set_mesh(pod):
            jax.shard_map(body, mesh=pod, in_specs=P("pod"),
                          out_specs=P("pod"))(jnp.ones(4))
        pod_data = make_test_mesh((2, 2), ("pod", "data"))
        with jax.set_mesh(pod_data):
            jax.shard_map(body, mesh=pod_data, in_specs=P("pod"),
                          out_specs=P("pod"), axis_names={"pod"})(
                              jnp.ones(4))
        assert modes == ["pallas", "ref"], modes
        assert get_registry().get("kernels/on_mesh/rmsnorm") == 1
        print("ok")
    """, devices=4)


def test_sharded_train_step_runs_and_matches_single_device():
    """A COAP train step under pjit on a (2,2,2) mesh must equal the
    unsharded step (same params/batch)."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_test_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke
        from repro.models.model import build_model
        from repro.core.api import OptimizerConfig, make_optimizer
        from repro.train.step import make_train_step
        from repro.train.train_state import TrainState
        from repro.distributed import sharding as shd

        cfg = get_smoke("tinyllama-1.1b")
        model = build_model(cfg)
        tx = make_optimizer(OptimizerConfig(name="coap-adamw", learning_rate=1e-3,
                                            rank=8, t_update=2, lam=2, min_dim=16))
        params = model.init(jax.random.key(0))
        state = TrainState.create(params, tx)
        key = jax.random.key(1)
        batch = {"tokens": jax.random.randint(key, (8, 16), 0, cfg.vocab_size),
                 "labels": jax.random.randint(key, (8, 16), 0, cfg.vocab_size)}
        step = make_train_step(model, tx)

        # single-device reference
        ref_state, ref_metrics = jax.jit(step)(state, batch)

        mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
        pspecs = model.param_specs(mesh)
        with jax.set_mesh(mesh):
            bspec = shd.batch_specs(batch, mesh)
            bshard = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), bspec)
            sharded_batch = jax.device_put(batch, bshard)
            sharded_step = jax.jit(step)
            out_state, out_metrics = sharded_step(state, sharded_batch)
        np.testing.assert_allclose(float(ref_metrics["loss"]),
                                   float(out_metrics["loss"]), rtol=2e-4)
        a = jax.tree_util.tree_leaves(ref_state.params)[3]
        b = jax.tree_util.tree_leaves(out_state.params)[3]
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=3e-3)
        print("sharded step ok, loss", float(out_metrics["loss"]))
    """)


def test_crosspod_compression_matches_uncompressed():
    """The beyond-paper compressed cross-pod sync must be numerically
    equivalent to all-reducing full gradients (linearity of projection)."""
    run_sub("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_test_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke
        from repro.models.model import build_model
        from repro.core.coap_adam import ProjectedAdamConfig, scale_by_projected_adam
        from repro.core.projector import ProjectionRules
        from repro.distributed.compression import make_compressed_train_step
        from repro.optim import apply_updates
        from repro.train.train_state import TrainState

        # fp32 so the only difference between paths is the collective
        # schedule, not bf16 reduction order.
        cfg = dataclasses.replace(get_smoke("tinyllama-1.1b"),
                                  dtype=jnp.float32)
        model = build_model(cfg)
        pcfg = ProjectedAdamConfig(
            rules=ProjectionRules(rank=8, min_dim=16),
            strategy="coap", t_update=2, lam=2, use_fused_kernel=False)
        tx = scale_by_projected_adam(pcfg)
        params = model.init(jax.random.key(0))
        opt_state = tx.init(params)
        key = jax.random.key(1)
        batch = {"tokens": jax.random.randint(key, (8, 16), 0, cfg.vocab_size),
                 "labels": jax.random.randint(key, (8, 16), 0, cfg.vocab_size)}
        lr = 1e-3

        # Reference: global-batch gradient, plain update.
        def loss_fn(p):
            return model.loss(p, batch)[0]
        grads = jax.grad(loss_fn)(params)
        upd, _ = tx.update(grads, opt_state, params)
        ref_params = apply_updates(
            params, jax.tree_util.tree_map(lambda u: -lr * u, upd))

        # Compressed: 2 pods, per-pod half batches, r-rank cross-pod sync.
        mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
        state = TrainState(step=jnp.zeros([], jnp.int32), params=params,
                           opt_state=opt_state)
        step_fn = make_compressed_train_step(model, pcfg, mesh, lr)
        with jax.set_mesh(mesh):
            bshard = jax.tree_util.tree_map(
                lambda x: jax.device_put(
                    x, NamedSharding(mesh, P("pod"))), batch)
            new_state, metrics = jax.jit(step_fn)(state, bshard)
        for a, b in zip(jax.tree_util.tree_leaves(ref_params),
                        jax.tree_util.tree_leaves(new_state.params)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=2e-3, atol=2e-5)
        print("compression equivalence ok")

        # Stacked state storage: same compressed schedule, moments
        # addressed as bucket slices via the codec's leaf_view — must
        # match the plain-update reference identically.
        scfg = dataclasses.replace(pcfg, stacked_state=True)
        stx = scale_by_projected_adam(scfg)
        sstate = TrainState(step=jnp.zeros([], jnp.int32), params=params,
                            opt_state=stx.init(params))
        sstep_fn = make_compressed_train_step(model, scfg, mesh, lr)
        with jax.set_mesh(mesh):
            snew_state, _ = jax.jit(sstep_fn)(sstate, bshard)
        for a, b in zip(jax.tree_util.tree_leaves(ref_params),
                        jax.tree_util.tree_leaves(snew_state.params)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=2e-3, atol=2e-5)
        print("stacked compression equivalence ok")
    """)


def test_crosspod_conv_compression_matches_uncompressed():
    """Tucker-2 cross-pod compression on a REAL 2-pod mesh: all-reducing
    only the r_O x r_I x K1 x K2 core each step (full G on refresh steps)
    must equal the core transform on the globally averaged gradient — the
    linearity claim a 1-pod mesh (pmean == identity) cannot exercise.
    Multi-step, so eqn6 refresh AND recal steps both cross pods."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_test_mesh
        from jax.sharding import PartitionSpec as P
        from repro.core.coap_adam import (
            ProjectedAdamConfig, scale_by_projected_adam)
        from repro.core.projector import ProjectionRules
        from repro.distributed.compression import compressed_update

        params = {f"c{i}": 0.01 * jnp.ones((16, 12, 3, 3)) for i in range(2)}
        params["w"] = jnp.zeros((64, 32))
        params["bias"] = jnp.zeros((5,))
        # stagger=False: compression uses the synchronized schedule, so the
        # single-host reference must too (matters beyond step 0).
        cfg = ProjectedAdamConfig(
            rules=ProjectionRules(rank=8, min_dim=8), t_update=2, lam=2,
            use_fused_kernel=False, stagger=False)
        tx = scale_by_projected_adam(cfg)

        flat, treedef = jax.tree_util.tree_flatten(params)
        def gtree(seed):
            key = jax.random.key(seed)
            return jax.tree_util.tree_unflatten(treedef, [
                0.1 * jax.random.normal(jax.random.fold_in(key, i), x.shape)
                for i, x in enumerate(flat)])
        g0, g1 = gtree(1), gtree(2)
        g_mean = jax.tree_util.tree_map(lambda a, b: 0.5 * (a + b), g0, g1)

        # Reference: the core transform fed the globally averaged gradient.
        ref_state = tx.init(params)
        step = jax.jit(lambda gg, s: tx.update(gg, s, None))
        for _ in range(4):  # crosses refresh (t=2) and recal (t=4) steps
            ref_upd, ref_state = step(g_mean, ref_state)

        # Compressed: per-pod gradients, core-only reduction each step.
        mesh = make_test_mesh((2,), ("pod",))
        gstack = jax.tree_util.tree_map(
            lambda a, b: jnp.stack([a, b]), g0, g1)
        state = tx.init(params)

        def per_pod(gg, st):
            mine = jax.tree_util.tree_map(lambda x: x[0], gg)
            return compressed_update(cfg, mine, st, "pod")

        mapped = jax.shard_map(
            per_pod, mesh=mesh, in_specs=(P("pod"), P()),
            out_specs=(P(), P()), check_vma=False, axis_names={"pod"})
        for _ in range(4):
            upd, state = jax.jit(mapped)(gstack, state)

        # States integrate the schedule and must agree tightly; raw update
        # directions pass through the Adam normalizer m/(sqrt(v)+eps),
        # which amplifies ulp-level state noise wherever v ~ 0 early in
        # training, so they get the looser (lr-pre-scaling) tolerance the
        # matrix equivalence test applies after lr scaling.
        for a, b in zip(jax.tree_util.tree_leaves(ref_state.leaves),
                        jax.tree_util.tree_leaves(state.leaves)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)
        for a, b in zip(jax.tree_util.tree_leaves(ref_upd),
                        jax.tree_util.tree_leaves(upd)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=5e-4)
        print("conv cross-pod compression equivalence ok")
    """)


def test_elastic_checkpoint_reshard():
    """Save on a 4-device mesh, restore onto an 8-device mesh."""
    run_sub("""
        import os, tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_test_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.train import checkpoint as ckpt

        tmp = tempfile.mkdtemp()
        mesh4 = make_test_mesh((2, 2), ("data", "model"))
        w = jnp.arange(64 * 32, dtype=jnp.float32).reshape(64, 32)
        sharded = jax.device_put(w, NamedSharding(mesh4, P("data", "model")))
        state = {"w": sharded, "step": jnp.asarray(7)}
        ckpt.save(tmp, 7, state)

        mesh8 = make_test_mesh((4, 2), ("data", "model"))
        template = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        specs = {"w": P("data", "model"), "step": P()}
        restored = ckpt.restore(tmp, template, mesh=mesh8, spec_tree=specs)
        np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(w))
        assert restored["w"].sharding.mesh.shape["data"] == 4
        print("elastic reshard ok")
    """)


def test_elastic_checkpoint_reshard_stacked_cross_mode():
    """Save a STACKED optimizer state sharded on a 4-device mesh, restore
    onto an 8-device mesh into BOTH a per-leaf template and a stacked
    template — the codec's logical-path namespace plus elastic device_put."""
    run_sub("""
        import tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_test_mesh
        from repro.core import stacked_state as ss
        from repro.core.coap_adam import (
            ProjectedAdamConfig, scale_by_projected_adam)
        from repro.core.projector import ProjectionRules
        from repro.train import checkpoint as ckpt

        params = {f"l{i}": {"w": jnp.zeros((64, 32))} for i in range(4)}
        params["bias"] = jnp.zeros((8,))
        flat, treedef = jax.tree_util.tree_flatten(params)
        key = jax.random.key(0)
        g = jax.tree_util.tree_unflatten(treedef, [
            0.1 * jax.random.normal(jax.random.fold_in(key, i), p.shape)
            for i, p in enumerate(flat)])

        def build(stacked):
            tx = scale_by_projected_adam(ProjectedAdamConfig(
                rules=ProjectionRules(rank=8, min_dim=8), t_update=2,
                lam=2, stacked_state=stacked))
            st = tx.init(params)
            _, st = jax.jit(lambda gg, s: tx.update(gg, s, None))(g, st)
            return tx, st

        tx_s, st_s = build(True)
        tx_p, st_p = build(False)

        mesh4 = make_test_mesh((4,), ("data",))
        from jax.sharding import NamedSharding, PartitionSpec as P
        st_sharded = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, NamedSharding(mesh4, P())), st_s)
        tmp = tempfile.mkdtemp()
        ckpt.save(tmp, 1, st_sharded)

        mesh8 = make_test_mesh((8,), ("data",))
        for tx_dst, want_state, label in [
                (tx_p, st_p, "per-leaf"), (tx_s, st_s, "stacked")]:
            template = jax.eval_shape(lambda: tx_dst.init(params))
            specs = jax.tree_util.tree_map(
                lambda _: P(), template,
                is_leaf=lambda x: hasattr(x, "shape"))
            restored = ckpt.restore(tmp, template, mesh=mesh8,
                                    spec_tree=specs)
            got = restored.leaves
            want = want_state.leaves
            if isinstance(got, ss.StackedLeaves):
                got = ss.decode(got)
                want = ss.decode(want)
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
                np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    rtol=1e-5, atol=2e-6)
            print("reshard restore", label, "ok")
    """)


def test_crosspod_quantized_matches_single_pod():
    """Quantized (int8-state) compressed sync on a REAL 2-pod mesh — the
    dequant->reduce->requant schedule. Where the pod-mean is the identity
    (identical per-pod gradients) the emitted int8 codes must be BIT-EXACT
    against the single-pod quantized step (use_fused_kernel=False oracle
    ops), per-leaf AND stacked layouts. With genuinely different per-pod
    gradients the only drift is the fp32 pmean ordering, bounded by a few
    code steps after requantization (the documented single-rounding rule)."""
    run_sub("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_test_mesh
        from jax.sharding import PartitionSpec as P
        from repro.core import stacked_state as ss
        from repro.core.coap_adam import (
            ProjectedAdamConfig, scale_by_projected_adam)
        from repro.core.projector import ProjectionRules
        from repro.distributed.compression import compressed_update

        params = {"a": 0.01 * jnp.ones((64, 48)),
                  "b": 0.01 * jnp.ones((40, 24)),
                  "c": 0.01 * jnp.ones((16, 12, 3, 3)),
                  "bias": jnp.zeros((5,))}
        cfg = ProjectedAdamConfig(
            rules=ProjectionRules(rank=8, min_dim=8), t_update=2, lam=2,
            quantize=True, use_fused_kernel=False, moment_transplant=True)
        tx = scale_by_projected_adam(cfg)
        flat, treedef = jax.tree_util.tree_flatten(params)
        def gtree(seed):
            key = jax.random.key(seed)
            return jax.tree_util.tree_unflatten(treedef, [
                0.1 * jax.random.normal(jax.random.fold_in(key, 31 * seed + i),
                                        x.shape)
                for i, x in enumerate(flat)])

        mesh = make_test_mesh((2,), ("pod",))
        def run_compressed(ccfg, gstack_of, steps=4):
            state = scale_by_projected_adam(ccfg).init(params)
            def per_pod(gg, st):
                mine = jax.tree_util.tree_map(lambda x: x[0], gg)
                return compressed_update(ccfg, mine, st, "pod")
            mapped = jax.shard_map(
                per_pod, mesh=mesh, in_specs=(P("pod"), P()),
                out_specs=(P(), P()), check_vma=False, axis_names={"pod"})
            upd = None
            for s in range(steps):
                upd, state = jax.jit(mapped)(gstack_of(s), state)
            return upd, state

        # Single-pod reference (the core transform, unfused oracle ops).
        ref_state = tx.init(params)
        step = jax.jit(lambda gg, s: tx.update(gg, s, None))
        for s in range(4):
            ref_upd, ref_state = step(gtree(s), ref_state)

        # --- pmean == identity: BIT-EXACT codes, per-leaf layout.
        same = lambda s: jax.tree_util.tree_map(
            lambda x: jnp.stack([x, x]), gtree(s))
        upd, state = run_compressed(cfg, same)
        def assert_exact(leaves_a, leaves_b, label):
            fa = jax.tree_util.tree_leaves_with_path(leaves_a)
            fb = jax.tree_util.tree_leaves_with_path(leaves_b)
            assert len(fa) == len(fb)
            for (pa, a), (pb, b) in zip(fa, fb):
                a, b = np.asarray(a), np.asarray(b)
                if a.dtype == np.int8:
                    np.testing.assert_array_equal(a, b,
                        err_msg=f"{label}:{jax.tree_util.keystr(pa)}")
                else:
                    np.testing.assert_allclose(
                        a.astype(np.float32), b.astype(np.float32),
                        rtol=1e-6, atol=1e-7,
                        err_msg=f"{label}:{jax.tree_util.keystr(pa)}")
        assert_exact(ref_state.leaves, state.leaves, "per-leaf")
        for a, b in zip(jax.tree_util.tree_leaves(ref_upd),
                        jax.tree_util.tree_leaves(upd)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)
        print("quantized bit-exact per-leaf ok")

        # --- stacked layout: same schedule addressed as bucket slices.
        scfg = dataclasses.replace(cfg, stacked_state=True)
        supd, sstate = run_compressed(scfg, same)
        assert isinstance(sstate.leaves, ss.StackedLeaves)
        assert_exact(ref_state.leaves, ss.decode(sstate.leaves), "stacked")
        print("quantized bit-exact stacked ok")

        # --- different per-pod gradients: project(pmean(G)) vs
        # pmean(project(G)) differ only in fp32 summation order, so after
        # requantization the codes sit within a few code steps (one
        # rounding per step, geometrically damped by b1 across steps).
        def gpair(s):
            g0, g1 = gtree(10 + s), gtree(20 + s)
            return jax.tree_util.tree_map(
                lambda a, b: jnp.stack([a, b]), g0, g1)
        ref2 = tx.init(params)
        for s in range(4):
            g0, g1 = gtree(10 + s), gtree(20 + s)
            gm = jax.tree_util.tree_map(lambda a, b: 0.5 * (a + b), g0, g1)
            ref2_upd, ref2 = step(gm, ref2)
        dupd, dstate = run_compressed(cfg, gpair)
        fa = jax.tree_util.tree_leaves_with_path(ref2.leaves)
        fb = jax.tree_util.tree_leaves_with_path(dstate.leaves)
        for (pa, a), (pb, b) in zip(fa, fb):
            a, b = np.asarray(a), np.asarray(b)
            if a.dtype == np.int8:
                diff = np.abs(a.astype(np.int32) - b.astype(np.int32))
                assert diff.max() <= 3, (jax.tree_util.keystr(pa), diff.max())
            else:
                np.testing.assert_allclose(
                    a.astype(np.float32), b.astype(np.float32),
                    rtol=5e-3, atol=5e-4,
                    err_msg=jax.tree_util.keystr(pa))
        for a, b in zip(jax.tree_util.tree_leaves(ref2_upd),
                        jax.tree_util.tree_leaves(dupd)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-2, atol=5e-3)
        print("quantized drift bound ok")
    """)


def test_crosspod_sync_codes_int8_collective():
    """The sync_codes wire path on a REAL 2-pod mesh. (1) Telescoping
    invariant of the raw collective: with constant per-pod inputs,
    sum_t(applied_t) == T*mean + ef_0 - ef_T to fp32 rounding — the int8
    rounding residue never accumulates. (2) The EF accumulator stays bounded by one
    code step forever, so the error in the applied time-average drains to
    zero as 1/T on constant gradients. (3) End-to-end compressed training with
    sync_codes=True tracks the fp32-sync run, with a live EF sidecar."""
    run_sub("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_test_mesh
        from jax.sharding import PartitionSpec as P
        from repro.core.coap_adam import (
            ProjectedAdamConfig, scale_by_projected_adam)
        from repro.core.projector import ProjectionRules
        from repro.distributed.compression import (
            _allreduce_codes, compressed_update)
        from repro.optim import apply_updates

        mesh = make_test_mesh((2,), ("pod",))
        T, BLOCK = 12, 32
        xs = jax.random.normal(jax.random.key(0), (2, 4, 96))

        def collective(xstack):
            x = xstack[0]
            ef = jnp.zeros_like(x)
            acc = jnp.zeros_like(x)
            efs = []
            for _ in range(T):
                red, ef = _allreduce_codes(x, ef, "pod", BLOCK)
                acc = acc + red
                efs.append(ef)
            return acc, efs[-2], efs[-1], red

        mapped = jax.shard_map(
            collective, mesh=mesh, in_specs=(P("pod"),),
            out_specs=(P(), P(), P(), P()), check_vma=False,
            axis_names={"pod"})
        acc, ef_prev, ef_last, red_last = jax.jit(mapped)(xs)
        mean = np.asarray(jnp.mean(xs, 0))
        # telescoping: rounding residue ends in ef, never in the sum
        np.testing.assert_allclose(
            np.asarray(acc) + np.asarray(ef_last), T * mean,
            rtol=1e-5, atol=1e-5)
        # The accumulator never grows: |ef| stays bounded by ONE code
        # step (the shared block scale) for all time — rounding error
        # drains into a bounded residual instead of accumulating. (It
        # orbits inside that bound rather than hitting a pointwise fixed
        # point: the shared-scale rounding is a small cycle, not a
        # contraction.)
        bound = (np.abs(np.asarray(xs)).max()
                 + np.abs(np.asarray(ef_last)).max()) / 127.0
        for e in (ef_prev, ef_last):
            assert np.abs(np.asarray(e)).max() <= bound * 1.01
        # ... so the error in the APPLIED time-average drains to zero as
        # 1/T on constant gradients (the telescoping sum, per element):
        assert np.abs(np.asarray(acc) / T - mean).max() <= (
            2.0 * bound / T) * 1.01
        # single-rounding per-step bound: |applied - mean| <= block scale
        assert np.abs(np.asarray(red_last) - mean).max() <= bound * 1.01
        print("collective telescoping ok")

        # --- end-to-end: sync_codes tracks the fp32 sync run.
        params = {"a": 0.01 * jnp.ones((64, 48)),
                  "c": 0.01 * jnp.ones((16, 12, 3, 3)),
                  "bias": jnp.zeros((5,))}
        base = ProjectedAdamConfig(
            rules=ProjectionRules(rank=8, min_dim=8), t_update=100, lam=2,
            use_fused_kernel=False)
        flat, treedef = jax.tree_util.tree_flatten(params)
        key = jax.random.key(3)
        gstack = jax.tree_util.tree_map(
            lambda x: jnp.stack([x, 1.5 * x]),
            jax.tree_util.tree_unflatten(treedef, [
                0.1 * jax.random.normal(jax.random.fold_in(key, i), x.shape)
                for i, x in enumerate(flat)]))

        def train(ccfg, steps=6, lr=0.01):
            state = scale_by_projected_adam(ccfg).init(params)
            p = params
            def per_pod(gg, st):
                mine = jax.tree_util.tree_map(lambda x: x[0], gg)
                return compressed_update(ccfg, mine, st, "pod")
            mapped = jax.shard_map(
                per_pod, mesh=mesh, in_specs=(P("pod"), P()),
                out_specs=(P(), P()), check_vma=False, axis_names={"pod"})
            for _ in range(steps):
                upd, state = jax.jit(mapped)(gstack, state)
                p = apply_updates(p, jax.tree_util.tree_map(
                    lambda u: -lr * u, upd))
            return p, state

        p_ref, st_ref = train(base)
        p_q, st_q = train(dataclasses.replace(base, sync_codes=True))
        assert st_ref.leaves["a"].ef is None
        ef = st_q.leaves["a"].ef
        assert ef is not None and bool(jnp.all(jnp.isfinite(ef)))
        # constant gradients + frozen P (T_u=100): EF stabilizes
        assert st_q.leaves["c"].ef is not None
        # Training-trajectory tolerance, not parity: the EF collective
        # corrects the TIME-AVERAGE of g_proj, but Adam's m/(sqrt(v)+eps)
        # normalizer is nonlinear in the moments, so per-element drift can
        # reach a few lr-steps where v ~ 0 early in training. Bound the
        # drift at a few lr-steps per element, and require the overall
        # trajectories to agree to ~10% in norm (measured ~7.7% here).
        for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                        jax.tree_util.tree_leaves(p_q)):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            np.testing.assert_allclose(a, b, rtol=0, atol=3e-2)
            assert np.linalg.norm(a - b) <= 0.12 * max(
                np.linalg.norm(a), 1e-3)
        print("sync_codes end-to-end ok")
    """)


# ---------------------------------------------------------------------------
# Schedule-parity and validation tests: pmean over a 1-pod mesh is the
# identity, so these run in the main (single-device) process and pin the
# SCHEDULE, not the collective.
# ---------------------------------------------------------------------------
def _compressed_runner(cfg, params):
    """compressed_update wrapped in a 1-pod shard_map (pmean == identity)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.distributed.compression import compressed_update
    from repro.launch.mesh import make_test_mesh

    mesh = make_test_mesh((1,), ("pod",))
    return jax.shard_map(
        lambda gg, st: compressed_update(cfg, gg, st, "pod"),
        mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False, axis_names={"pod"},
    )


def _stagger_tree():
    import jax.numpy as jnp

    params = {f"l{i}": {"w": 0.01 * jnp.ones((32, 24))} for i in range(4)}
    params["solo"] = jnp.zeros((40, 16))
    params["bias"] = jnp.zeros((5,))
    return params


def _gtree(params, seed):
    import jax

    flat, treedef = jax.tree_util.tree_flatten(params)
    key = jax.random.key(seed)
    return jax.tree_util.tree_unflatten(treedef, [
        0.1 * jax.random.normal(jax.random.fold_in(key, 31 * seed + i),
                                x.shape)
        for i, x in enumerate(flat)])


def test_compressed_stagger_cadence_matches_core():
    """Regression for the silent-desync bug: with stagger on, the
    compressed path must refresh each leaf on EXACTLY the steps the core
    transform does (shared bucket_phases allocation), and the phase groups
    must actually differ — not collapse back to the synchronized
    schedule."""
    import jax
    import numpy as np

    from repro.core.coap_adam import (
        ProjectedAdamConfig,
        scale_by_projected_adam,
    )
    from repro.core.projector import ProjectionRules

    params = _stagger_tree()
    # T_u=4 with 3 stagger units (2 for the l-bucket + 1 for solo) spreads
    # phases 0/1/2 — the l-bucket genuinely splits across two phases.
    cfg = ProjectedAdamConfig(
        rules=ProjectionRules(rank=8, min_dim=8), t_update=4, lam=2,
        stagger=True, stagger_groups=2, use_fused_kernel=False)
    tx = scale_by_projected_adam(cfg)
    ref_state = tx.init(params)
    state = tx.init(params)
    step_ref = jax.jit(lambda gg, s: tx.update(gg, s, None))
    step_cmp = jax.jit(_compressed_runner(cfg, params))

    names = [f"l{i}" for i in range(4)] + ["solo"]

    def p_of(s, name):
        leaf = s.leaves[name]["w"] if name.startswith("l") else s.leaves[name]
        return np.asarray(leaf.p)

    prev_ref = {n: p_of(ref_state, n) for n in names}
    prev_cmp = {n: p_of(state, n) for n in names}
    changed_ref = {n: [] for n in names}
    changed_cmp = {n: [] for n in names}
    for s in range(9):
        g = _gtree(params, s)
        ru, ref_state = step_ref(g, ref_state)
        cu, state = step_cmp(g, state)
        for a, b in zip(jax.tree_util.tree_leaves(ru),
                        jax.tree_util.tree_leaves(cu)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-6)
        for n in names:
            now_r, now_c = p_of(ref_state, n), p_of(state, n)
            changed_ref[n].append(not np.array_equal(prev_ref[n], now_r))
            changed_cmp[n].append(not np.array_equal(prev_cmp[n], now_c))
            prev_ref[n], prev_cmp[n] = now_r, now_c
    # cadence parity, leaf by leaf
    for n in names:
        assert changed_cmp[n] == changed_ref[n], (
            n, changed_cmp[n], changed_ref[n])
    # stagger is ACTIVE: the congruent bucket spans >1 refresh pattern
    patterns = {tuple(changed_cmp[f"l{i}"]) for i in range(4)}
    assert len(patterns) > 1, patterns


def test_compressed_per_bucket_t_update_override_matches_core():
    """Per-bucket T_u overrides run natively through the compressed
    schedule (no rejection), at the overridden cadence, matching the core
    transform — including a reordered entries container that restates the
    global value for another leaf."""
    import dataclasses

    import jax
    import numpy as np

    from repro.core.coap_adam import (
        LeafOverrides,
        PlanOverrides,
        ProjectedAdamConfig,
        scale_by_projected_adam,
    )
    from repro.core.projector import ProjectionRules

    params = _stagger_tree()
    base = ProjectedAdamConfig(
        rules=ProjectionRules(rank=8, min_dim=8), t_update=2, lam=2,
        stagger=True, stagger_groups=2, use_fused_kernel=False)
    # the l-bucket pinned to T_u=4; solo restates the global T_u=2;
    # entries deliberately out of tree order.
    cfg = dataclasses.replace(base, overrides=PlanOverrides(entries=(
        ("l2/w", LeafOverrides(t_update=4)),
        ("solo", LeafOverrides(t_update=2)),
        ("l0/w", LeafOverrides(t_update=4)),
        ("l3/w", LeafOverrides(t_update=4)),
        ("l1/w", LeafOverrides(t_update=4)),
    )))
    tx = scale_by_projected_adam(cfg)
    ref_state = tx.init(params)
    state = tx.init(params)
    step_ref = jax.jit(lambda gg, s: tx.update(gg, s, None))
    step_cmp = jax.jit(_compressed_runner(cfg, params))
    changed = {n: [] for n in ["l0", "solo"]}
    prev = {"l0": np.asarray(state.leaves["l0"]["w"].p),
            "solo": np.asarray(state.leaves["solo"].p)}
    for s in range(8):
        g = _gtree(params, s)
        ru, ref_state = step_ref(g, ref_state)
        cu, state = step_cmp(g, state)
        for a, b in zip(jax.tree_util.tree_leaves(ru),
                        jax.tree_util.tree_leaves(cu)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-6)
        now = {"l0": np.asarray(state.leaves["l0"]["w"].p),
               "solo": np.asarray(state.leaves["solo"].p)}
        for n in changed:
            changed[n].append(not np.array_equal(prev[n], now[n]))
            prev[n] = now[n]
    # overridden bucket refreshes every 4 steps, solo every 2 — distinct
    # cadences from ONE config (the old code rejected this outright).
    assert sum(changed["l0"]) < sum(changed["solo"]), changed
    assert sum(changed["l0"]) >= 2, changed  # it does refresh


def test_compressed_perleaf_reordered_state_raises():
    """Regression (per-leaf branch of the signature check): a congruent-
    but-reordered state tree must raise, never silently pair moments with
    the wrong leaves."""
    import jax.numpy as jnp
    import pytest as _pytest

    from repro.core.coap_adam import (
        ProjectedAdamConfig,
        scale_by_projected_adam,
    )
    from repro.core.projector import ProjectionRules

    params = {"a": jnp.zeros((64, 32)), "b": jnp.zeros((48, 16))}
    cfg = ProjectedAdamConfig(
        rules=ProjectionRules(rank=8, min_dim=8), t_update=2, lam=2,
        use_fused_kernel=False)
    tx = scale_by_projected_adam(cfg)
    state = tx.init(params)
    swapped = state._replace(
        leaves={"a": state.leaves["b"], "b": state.leaves["a"]})
    g = _gtree(params, 0)
    runner = _compressed_runner(cfg, params)
    with _pytest.raises(ValueError, match="does not match the gradient"):
        runner(g, swapped)


def test_compressed_sync_codes_requires_ef_sidecar():
    """sync_codes=True against a state initialized without the EF sidecar
    must fail loudly (re-init/migrate, don't silently skip compensation)."""
    import dataclasses

    import jax.numpy as jnp
    import pytest as _pytest

    from repro.core.coap_adam import (
        ProjectedAdamConfig,
        scale_by_projected_adam,
    )
    from repro.core.projector import ProjectionRules

    params = {"a": jnp.zeros((64, 32))}
    cfg = ProjectedAdamConfig(
        rules=ProjectionRules(rank=8, min_dim=8), t_update=2, lam=2,
        use_fused_kernel=False)
    state = scale_by_projected_adam(cfg).init(params)
    assert state.leaves["a"].ef is None
    ecfg = dataclasses.replace(cfg, sync_codes=True)
    runner = _compressed_runner(ecfg, params)
    with _pytest.raises(ValueError, match="error-feedback"):
        runner(_gtree(params, 0), state)

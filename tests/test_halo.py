"""Halo-exchange sequence parallelism (parallel/halo.py) vs the pure-JAX
stream-partition oracle (core/stream_partition.py) — 8 fake CPU devices in a
subprocess (device count locks at first jax init, so tests that need >1
device must run isolated)."""
import pytest

from conftest import run_subprocess_devices


@pytest.mark.slow
def test_halo_apply_equals_reference(repo_src):
    out = run_subprocess_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import equalizer as eq
        from repro.core import stream_partition as sp
        from repro.parallel import halo

        cfg = eq.CNNEqConfig()
        key = jax.random.PRNGKey(0)
        params = eq.init(key, cfg)
        folded = eq.fold_bn(params, eq.init_bn_state(cfg), cfg)
        apply_fn = lambda chunks: eq.apply_folded(folded, chunks, cfg)

        n_inst = 8
        mesh = jax.make_mesh((n_inst,), ("data",))
        n_syms = 256 * n_inst
        x = jax.random.normal(key, (n_syms * cfg.n_os,))

        y_ref = sp.partitioned_apply(apply_fn, x, n_inst, cfg)
        y_halo = halo.halo_apply(apply_fn, x, cfg, mesh, axis="data")
        assert y_halo.shape == y_ref.shape
        np.testing.assert_allclose(np.asarray(y_halo), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)

        # and the batched variant
        xb = jax.random.normal(key, (3, n_syms * cfg.n_os))
        yb = halo.halo_apply_batched(apply_fn, xb, cfg, mesh, axis="data")
        yr = jnp.stack([sp.partitioned_apply(apply_fn, xb[i], n_inst, cfg)
                        for i in range(3)])
        np.testing.assert_allclose(np.asarray(yb), np.asarray(yr),
                                   rtol=1e-4, atol=1e-4)
        print("HALO-OK")
    """, n_devices=8, repo_src=repo_src)
    assert "HALO-OK" in out


@pytest.mark.slow
def test_halo_apply_with_engine(repo_src):
    """The production path: fused-kernel EqualizerEngine per mesh device."""
    out = run_subprocess_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import equalizer as eq
        from repro.core import stream_partition as sp
        from repro.core.engine import EqualizerEngine
        from repro.parallel import halo

        cfg = eq.CNNEqConfig()
        key = jax.random.PRNGKey(0)
        params = eq.init(key, cfg)
        engine = EqualizerEngine.from_params(
            params, eq.init_bn_state(cfg), cfg, backend="fused_fp32",
            tile_m=64)

        n_inst = 8
        mesh = jax.make_mesh((n_inst,), ("data",))
        x = jax.random.normal(key, (256 * n_inst * cfg.n_os,))
        y_halo = halo.halo_apply(engine, x, cfg, mesh, axis="data")
        y_ref = sp.partitioned_apply(engine, x, n_inst, cfg)
        np.testing.assert_allclose(np.asarray(y_halo), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5)
        print("ENGINE-HALO-OK")
    """, n_devices=8, repo_src=repo_src)
    assert "ENGINE-HALO-OK" in out


@pytest.mark.slow
def test_halo_apply_int8_engine_exchanges_int8(repo_src):
    """fused_int8 engine → the halo travels as requantized int8 (4× less
    ppermute traffic) and the sharded result is BIT-identical to the
    unsharded engine (requantization to the layer-0 grid is idempotent)."""
    out = run_subprocess_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import equalizer as eq
        from repro.core.engine import EqualizerEngine
        from repro.parallel import halo

        cfg = eq.CNNEqConfig()
        key = jax.random.PRNGKey(0)
        params = eq.init(key, cfg)
        fmt = tuple((2, 5, 3, 4) for _ in range(cfg.layers))
        folded = eq.fold_bn(params, eq.init_bn_state(cfg), cfg)
        engine = EqualizerEngine.from_folded(
            folded, cfg, backend="fused_int8", formats=fmt, tile_m=32)
        assert halo._engine_halo_quant(engine) == (3, 4)

        n_inst = 8
        mesh = jax.make_mesh((n_inst,), ("data",))
        x = jax.random.normal(key, (256 * n_inst * cfg.n_os,))
        y_halo = halo.halo_apply(engine, x, cfg, mesh, axis="data")
        y_whole = engine(x)
        np.testing.assert_array_equal(np.asarray(y_halo),
                                      np.asarray(y_whole))

        # the exchanged payload really is int8: jaxpr has int8 ppermutes
        # and no fp32 ones
        n_inst_sub = 4
        import jax.core
        def body(c):
            return halo.halo_exchange(
                c[None, :], halo.halo_samples(cfg, n_inst_sub), "data",
                quant=(3, 4))
        from jax.sharding import PartitionSpec as P
        mesh4 = jax.make_mesh((8,), ("data",))
        jaxpr = jax.make_jaxpr(jax.shard_map(
            lambda c: body(c)[0], mesh=mesh4, in_specs=P("data"),
            out_specs=P("data"), check_vma=False))(x)
        def eqns(j):                   # every equation, sub-jaxprs too
            for e in j.eqns:
                yield e
                for v in e.params.values():
                    sub = getattr(v, "jaxpr", v)
                    if hasattr(sub, "eqns"):
                        yield from eqns(sub)
        perm_dtypes = {str(e.invars[0].aval.dtype) for e in eqns(jaxpr.jaxpr)
                       if e.primitive.name == "ppermute"}
        assert perm_dtypes == {"int8"}, perm_dtypes
        print("INT8-HALO-OK")
    """, n_devices=8, repo_src=repo_src)
    assert "INT8-HALO-OK" in out


@pytest.mark.slow
def test_halo_exchange_unit(repo_src):
    out = run_subprocess_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.parallel.halo import halo_exchange

        mesh = jax.make_mesh((4,), ("data",))
        x = jnp.arange(32, dtype=jnp.float32)          # 8 per device

        def f(c):
            return halo_exchange(c, 3, "data")

        y = jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data"), check_vma=False)(x)
        y = np.asarray(y).reshape(4, 14)
        # device 1 holds [8..16); halo = [5,6,7] + [16,17,18]
        np.testing.assert_array_equal(y[1][:3], [5, 6, 7])
        np.testing.assert_array_equal(y[1][-3:], [16, 17, 18])
        # stream edges are zero-padded
        np.testing.assert_array_equal(y[0][:3], [0, 0, 0])
        np.testing.assert_array_equal(y[3][-3:], [0, 0, 0])
        print("EXCHANGE-OK")
    """, n_devices=4, repo_src=repo_src)
    assert "EXCHANGE-OK" in out


@pytest.mark.slow
def test_grad_compression_psum(repo_src):
    out = run_subprocess_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim import grad_comp

        mesh = jax.make_mesh((4,), ("pod",))
        g = jax.random.normal(jax.random.PRNGKey(0), (4, 256))

        def f(gi, err):
            mean, new_err = grad_comp.compressed_psum(
                {"w": gi[0]}, {"w": err[0]}, "pod")
            return mean["w"][None], new_err["w"][None]

        err0 = jnp.zeros((4, 256))
        mean, err1 = jax.shard_map(f, mesh=mesh,
                                   in_specs=(P("pod"), P("pod")),
                                   out_specs=(P("pod"), P("pod")))(g, err0)
        want = jnp.mean(g, axis=0)
        got = np.asarray(mean).reshape(4, 256)[0]
        # int8 quantization error is bounded by scale/2 per pod
        scale = float(jnp.max(jnp.abs(g))) / 127
        assert np.max(np.abs(got - np.asarray(want))) < scale
        # error feedback: residuals are nonzero and bounded
        e = np.asarray(err1)
        assert 0 < np.max(np.abs(e)) < scale
        print("COMP-OK")
    """, n_devices=4, repo_src=repo_src)
    assert "COMP-OK" in out


@pytest.mark.slow
def test_elastic_reshard_restore(repo_src, tmp_path):
    out = run_subprocess_devices(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager
        from repro.runtime import best_mesh, ElasticRestore
        from repro.parallel import sharding

        # save on an 8-device (4,2) mesh
        mesh8 = jax.make_mesh((4, 2), ("data", "model"))
        tree = {{"layers": {{"w_gate": jnp.arange(64, dtype=jnp.float32)
                             .reshape(8, 8)}}}}
        specs = sharding.param_specs(tree, mesh8, "train")
        sharded = jax.device_put(
            tree, jax.tree.map(lambda s: NamedSharding(mesh8, s), specs))
        ckpt = CheckpointManager(r"{tmp_path}", keep_k=2)
        ckpt.save(3, sharded)

        # restore onto a DIFFERENT mesh (2 devices) — elastic shrink
        mesh2 = best_mesh(n_devices=2, model_parallel=2,
                          devices=jax.devices()[:2])
        er = ElasticRestore(ckpt)
        restored, step = er.restore(tree, mesh2)
        assert step == 3
        np.testing.assert_array_equal(
            np.asarray(restored["layers"]["w_gate"]),
            np.arange(64, dtype=np.float32).reshape(8, 8))
        shard_shapes = sorted(
            s.data.shape
            for s in restored["layers"]["w_gate"].addressable_shards)
        print("shapes", shard_shapes)
        assert len(shard_shapes) == 2          # resharded onto 2 devices
        print("ELASTIC-OK")
    """, n_devices=8, repo_src=repo_src)
    assert "ELASTIC-OK" in out

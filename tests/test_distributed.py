"""Multi-device tests. Each runs in a subprocess with fake host devices so
the main pytest process keeps its single-device backend."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_compressed_psum_approximates_mean():
    print(run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.distributed.compression import compressed_psum
        from jax import shard_map
        mesh = Mesh(np.array(jax.devices()), ("d",))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 500))
        f = shard_map(lambda xs: compressed_psum(xs[0], "d")[0][None],
                      mesh=mesh, in_specs=(P("d", None),),
                      out_specs=P("d", None), check_vma=False)
        m = jax.jit(f)(x)
        err = float(jnp.abs(m[0] - x.mean(0)).max() / jnp.abs(x.mean(0)).max())
        assert err < 0.05, err
        # every device holds the identical reduced tensor
        assert bool(jnp.allclose(m[0], m[7]))
        print("ok", err)
    """))


def test_error_feedback_removes_bias():
    print(run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.distributed.compression import compressed_psum
        from jax import shard_map
        mesh = Mesh(np.array(jax.devices()), ("d",))
        # same tiny gradient every step: with error feedback the running sum
        # of compressed means must track the true accumulation
        g = jax.random.normal(jax.random.PRNGKey(1), (8, 64)) * 1e-3

        def step(resid, _):
            m, r = compressed_psum(g_local + resid, "d")
            return r, m

        def run(gl):
            global g_local
            g_local = gl[0]
            resid = jnp.zeros((64,), jnp.float32)
            resid, ms = jax.lax.scan(step, resid, None, length=50)
            return ms.sum(0)[None]

        f = shard_map(run, mesh=mesh, in_specs=(P("d", None),),
                      out_specs=P("d", None), check_vma=False)
        total = jax.jit(f)(g)[0]
        true = g.mean(0) * 50
        rel = float(jnp.abs(total - true).max() / jnp.abs(true).max())
        assert rel < 0.05, rel
        print("ok", rel)
    """))


def test_distributed_geek_matches_quality():
    print(run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, collections
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.core.distributed import make_fit_dense
        from repro.core.geek import GeekConfig
        from repro.data.synthetic import sift_like
        mesh = Mesh(np.array(jax.devices()), ("data",))
        data = sift_like(jax.random.PRNGKey(0), n=4096, k=24)
        cfg = GeekConfig(m=40, t=32, silk_l=6, delta=5, k_max=64,
                         pair_cap=8192)
        fit = make_fit_dense(mesh, cfg)
        x = jax.device_put(data.x, NamedSharding(mesh, P("data", None)))
        lab, c, cv, ks, rad, ovf = fit(x, jax.random.PRNGKey(1))
        lab = np.array(lab); true = np.array(data.true_labels)
        pur = sum(collections.Counter(true[lab==cc]).most_common(1)[0][1]
                  for cc in set(lab.tolist()))/len(lab)
        assert pur > 0.95, pur
        assert int(ks) >= 24
        print("ok purity", pur)
    """, timeout=600))


def test_pjit_train_step_runs_on_mesh():
    print(run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_arch
        from repro.launch.mesh import make_test_mesh, shardings_for
        from repro.launch.steps import make_train_step
        from repro.models import init_params, param_specs
        from repro.models.sharding import activation_sharding
        from repro.optim import adamw
        cfg = get_arch("qwen3_0_6b", smoke=True)
        mesh = make_test_mesh((2, 2))
        opt = adamw(1e-3)
        params = init_params(cfg, jax.random.PRNGKey(0))
        psh = shardings_for(param_specs(cfg), mesh)
        params = jax.device_put(params, psh)
        state = jax.device_put(opt.init(params),
                               shardings_for(opt.state_specs(
                                   param_specs(cfg), params), mesh))
        key = jax.random.PRNGKey(1)
        batch = {"inputs": jax.random.randint(key, (4, 32), 0, cfg.vocab_size),
                 "labels": jax.random.randint(key, (4, 32), 0, cfg.vocab_size)}
        batch = jax.device_put(batch, NamedSharding(mesh, P("data")))
        fn = make_train_step(cfg, opt)
        with mesh, activation_sharding(mesh):
            step = jax.jit(fn, donate_argnums=(0, 1))
            losses = []
            for i in range(8):
                params, state, _, metrics = step(params, state,
                                                 jnp.int32(i), batch)
                losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0], losses
        print("ok", losses[0], "->", losses[-1])
    """, timeout=600))


def test_ddp_compress_matches_pjit_direction():
    print(run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.configs import get_arch
        from repro.distributed.compression import compressed_psum_tree
        from repro.models import init_params, train_loss
        from jax import shard_map
        cfg = get_arch("smollm_360m", smoke=True)
        mesh = Mesh(np.array(jax.devices()), ("data",))
        params = init_params(cfg, jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(1)
        batch = {"inputs": jax.random.randint(key, (8, 32), 0, cfg.vocab_size),
                 "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab_size)}

        def ddp(params, batch):
            loss, g = jax.value_and_grad(
                lambda p: train_loss(p, cfg, batch)[0])(params)
            gm, _ = compressed_psum_tree(g, "data")
            return jax.lax.pmean(loss, "data"), gm

        f = shard_map(ddp, mesh=mesh, in_specs=(P(), P("data")),
                      out_specs=(P(), P()), check_vma=False)
        loss, g_comp = jax.jit(f)(params, batch)
        # exact global gradient for comparison
        loss2, g_true = jax.value_and_grad(
            lambda p: train_loss(p, cfg, batch)[0])(params)
        flat_c = jnp.concatenate([x.reshape(-1) for x in jax.tree.leaves(g_comp)])
        flat_t = jnp.concatenate([x.reshape(-1).astype(jnp.float32)
                                  for x in jax.tree.leaves(g_true)])
        cos = jnp.dot(flat_c, flat_t) / (jnp.linalg.norm(flat_c)
                                         * jnp.linalg.norm(flat_t))
        assert float(cos) > 0.99, float(cos)
        print("ok cosine", float(cos))
    """, timeout=600))


def test_sharded_pallas_assign_matches_single_device():
    """The fused Pallas assign (+ per-cluster accumulation) under shard_map
    agrees exactly with the single-device kernel call."""
    print(run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.core.distributed import _assign_l2_accumulate
        from repro.core.geek import GeekConfig
        from repro.kernels import ops as kops
        from jax import shard_map
        mesh = Mesh(np.array(jax.devices()), ("data",))
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (1024, 32))
        c = jax.random.normal(jax.random.fold_in(key, 1), (17, 32))
        valid = jnp.arange(17) % 5 != 2
        cfg = GeekConfig(use_pallas=True)

        def body(xs):
            lab, d2, sums, cnt = _assign_l2_accumulate(xs, c, valid, cfg)
            return lab, jax.lax.psum(sums, "data"), jax.lax.psum(cnt, "data")

        f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("data", None),),
                              out_specs=(P("data"), P(), P()),
                              check_vma=False))
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
        lab_s, sums_s, cnt_s = f(xs)
        lab1, d21, sums1, cnt1 = kops.distance_argmin_l2(x, c, valid,
                                                         accumulate=True)
        assert (np.array(lab_s) == np.array(lab1)).all()
        np.testing.assert_allclose(np.array(sums_s), np.array(sums1),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.array(cnt_s), np.array(cnt1))
        print("ok fused sharded == single device")
    """, timeout=600))


def test_distributed_geek_pallas_refinement():
    """use_pallas=True + refine_sweeps reaches the fused kernel inside
    shard_map and preserves clustering quality."""
    print(run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, collections
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.core.distributed import make_fit_dense
        from repro.core.geek import GeekConfig
        from repro.data.synthetic import sift_like
        mesh = Mesh(np.array(jax.devices()), ("data",))
        data = sift_like(jax.random.PRNGKey(0), n=4096, k=24)
        cfg = GeekConfig(m=40, t=32, silk_l=6, delta=5, k_max=64,
                         pair_cap=8192, use_pallas=True, refine_sweeps=1)
        fit = make_fit_dense(mesh, cfg)
        x = jax.device_put(data.x, NamedSharding(mesh, P("data", None)))
        lab, c, cv, ks, rad, ovf = fit(x, jax.random.PRNGKey(1))
        lab = np.array(lab); true = np.array(data.true_labels)
        pur = sum(collections.Counter(true[lab==cc]).most_common(1)[0][1]
                  for cc in set(lab.tolist()))/len(lab)
        assert pur > 0.95, pur
        assert int(ks) >= 24
        print("ok purity", pur)
    """, timeout=600))


# ---------------------------------------------------------------------------
# Unified sharded path (GEEK.fit(mesh=) / make_predict_sharded,
# DESIGN.md §10): bit-identity with the in-core fits on 1/2/4-device
# CPU meshes, checkpoint round-trip, sharded streaming, and the
# permutation/mesh-size property test.
# ---------------------------------------------------------------------------

def test_fit_sharded_matches_incore_all_types():
    """Sharded fit (seed_cap=None) returns a GeekModel whose labels and
    centers are bit-identical to the in-core fit for every data type,
    on 1-, 2-, and 4-device meshes built from 4 forced CPU devices."""
    print(run_with_devices("""
        import jax, numpy as np
        from repro.core.api import GEEK, DenseData, HeteroData, SparseData
        from repro.core.geek import GeekConfig
        from repro.data.synthetic import sift_like, geonames_like, url_like
        from repro.utils.compat import make_mesh

        def fit(dataset, key, cfg, **kw):
            est = GEEK(cfg)
            model = est.fit(dataset, key, **kw)
            return est.result_, model

        cfg = GeekConfig(m=16, t=32, silk_l=4, delta=5, k_max=64,
                         pair_cap=8192)
        key = jax.random.PRNGKey(1)
        dkey = jax.random.PRNGKey(0)
        d0 = sift_like(dkey, n=2048, k=16)
        h0 = geonames_like(dkey, n=2048, k=16)
        s0 = url_like(dkey, n=2048, k=16)
        cases = {
            "dense": DenseData(d0.x),
            "hetero": HeteroData(h0.x_num, h0.x_cat),
            "sparse": SparseData(s0.sets, s0.mask),
        }
        for kind, dataset in cases.items():
            res0, m0 = fit(dataset, key, cfg)
            for g in (1, 2, 4):
                mesh = make_mesh(devices=jax.devices()[:g])
                res1, m1 = fit(dataset, key, cfg, mesh=mesh)
                assert (np.asarray(res0.labels)
                        == np.asarray(res1.labels)).all(), (kind, g)
                assert (np.asarray(m0.centers)
                        == np.asarray(m1.centers)).all(), (kind, g)
                assert (np.asarray(m0.radius)
                        == np.asarray(m1.radius)).all(), (kind, g)
                assert int(res0.k_star) == int(res1.k_star), (kind, g)
            print("ok", kind)
    """, n=4, timeout=600))


def test_fit_sharded_ragged_rows_match_incore():
    """n not divisible by the mesh size: cyclic padding keeps labels,
    centers, and radii bit-identical to the in-core fit."""
    print(run_with_devices("""
        import jax, numpy as np
        from repro.core.api import GEEK, DenseData
        from repro.core.geek import GeekConfig
        from repro.data.synthetic import sift_like
        from repro.utils.compat import make_mesh

        def fit(dataset, key, cfg, **kw):
            est = GEEK(cfg)
            model = est.fit(dataset, key, **kw)
            return est.result_, model

        cfg = GeekConfig(m=16, t=32, silk_l=4, delta=5, k_max=64,
                         pair_cap=8192)
        data = sift_like(jax.random.PRNGKey(0), n=1537, k=12)  # 1537 % 4 != 0
        key = jax.random.PRNGKey(1)
        res0, m0 = fit(DenseData(data.x), key, cfg)
        res1, m1 = fit(DenseData(data.x), key, cfg, mesh=make_mesh())
        assert res1.labels.shape == (1537,)
        assert (np.asarray(res0.labels) == np.asarray(res1.labels)).all()
        assert (np.asarray(m0.radius) == np.asarray(m1.radius)).all()
        # seed ids must stay inside the real dataset even with seed_cap
        res2, _ = fit(DenseData(data.x), key, cfg, mesh=make_mesh(),
                      seed_cap=500)
        ids = np.asarray(res2.seeds.id)[np.asarray(res2.seeds.valid)]
        assert ids.min() >= 0 and ids.max() < 1537, (ids.min(), ids.max())
        print("ok ragged + seed_cap")
    """, n=4, timeout=600))


def test_sharded_model_checkpoint_roundtrip_serves():
    """Sharded fit -> save_model -> restore_model(mesh=...) ->
    make_predict_sharded reproduces the fit labels bit-identically
    (and matches single-device predict on the restored model)."""
    print(run_with_devices("""
        import jax, numpy as np, tempfile
        from repro.checkpoint.manager import restore_model, save_model
        from repro.core.api import GEEK, HeteroData
        from repro.core.distributed import make_predict_sharded
        from repro.core.geek import GeekConfig
        from repro.core.model import predict
        from repro.data.synthetic import geonames_like
        from repro.utils.compat import make_mesh

        mesh = make_mesh()
        cfg = GeekConfig(m=16, t=32, silk_l=4, delta=5, k_max=64,
                         pair_cap=8192)
        data = geonames_like(jax.random.PRNGKey(0), n=2048, k=16)
        est = GEEK(cfg)
        model = est.fit(HeteroData(data.x_num, data.x_cat),
                        jax.random.PRNGKey(1), mesh=mesh)
        res = est.result_
        with tempfile.TemporaryDirectory() as ckpt:
            save_model(ckpt, model)
            restored = restore_model(ckpt, mesh=mesh)
        lab_s, dst_s = make_predict_sharded(mesh)(restored, data.x_num,
                                                  data.x_cat)
        assert (np.asarray(lab_s) == np.asarray(res.labels)).all()
        lab_1, dst_1 = predict(restored,
                               restored.encode(data.x_num, data.x_cat))
        assert (np.asarray(lab_s) == np.asarray(lab_1)).all()
        assert (np.asarray(dst_s) == np.asarray(dst_1)).all()
        print("ok sharded serve == fit == single-device")
    """, n=4, timeout=600))


def test_sharded_streaming_matches_incore():
    """GEEK.fit(chunk=, mesh=) — the sharded chunked assignment pass
    (donated per-device buffers, sentinel-padded ragged tail) stays
    bit-identical to the in-core fit."""
    print(run_with_devices("""
        import jax, numpy as np
        from repro.core.api import GEEK, DenseData, SparseData
        from repro.core.geek import GeekConfig
        from repro.data.synthetic import sift_like, url_like
        from repro.utils.compat import make_mesh

        def fit(dataset, key, cfg, **kw):
            est = GEEK(cfg)
            est.fit(dataset, key, **kw)
            return est.result_

        mesh = make_mesh()
        cfg = GeekConfig(m=16, t=32, silk_l=4, delta=5, k_max=64,
                         pair_cap=8192)
        key = jax.random.PRNGKey(1)
        d = sift_like(jax.random.PRNGKey(0), n=1900, k=12)  # ragged tail
        res0 = fit(DenseData(d.x), key, cfg)
        res1 = fit(DenseData(np.asarray(d.x)), key, cfg,
                   chunk=512, mesh=mesh)
        assert (np.asarray(res0.labels) == res1.labels).all()
        s = url_like(jax.random.PRNGKey(0), n=1900, k=12)
        res2 = fit(SparseData(s.sets, s.mask), key, cfg)
        res3 = fit(SparseData(np.asarray(s.sets), np.asarray(s.mask)),
                   key, cfg, chunk=512, mesh=mesh)
        assert (np.asarray(res2.labels) == res3.labels).all()
        try:
            fit(DenseData(np.asarray(d.x)), key, cfg, chunk=511,
                mesh=mesh)
            raise AssertionError("chunk % g validation missing")
        except ValueError:
            pass
        print("ok sharded streaming")
    """, n=4, timeout=600))


def test_distributed_geek_compressed_refinement():
    """GeekConfig.compress_collectives routes the refine-sweep partial
    sums through the int8 quantized all-reduce and preserves quality."""
    print(run_with_devices("""
        import jax, numpy as np, collections
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.core.distributed import make_fit_dense
        from repro.core.geek import GeekConfig
        from repro.data.synthetic import sift_like
        mesh = Mesh(np.array(jax.devices()), ("data",))
        data = sift_like(jax.random.PRNGKey(0), n=4096, k=24)
        cfg = GeekConfig(m=40, t=32, silk_l=6, delta=5, k_max=64,
                         pair_cap=8192, refine_sweeps=2,
                         compress_collectives=True)
        fit = make_fit_dense(mesh, cfg)
        x = jax.device_put(data.x, NamedSharding(mesh, P("data", None)))
        lab, c, cv, ks, rad, ovf = fit(x, jax.random.PRNGKey(1))
        lab = np.array(lab); true = np.array(data.true_labels)
        pur = sum(collections.Counter(true[lab==cc]).most_common(1)[0][1]
                  for cc in set(lab.tolist()))/len(lab)
        assert pur > 0.95, pur
        print("ok compressed-refine purity", pur)
    """, timeout=600))


def test_sharded_discovery_compressed_wire_bit_identical():
    """compress_collectives=True narrows the bucket-map all_to_all to
    uint8/uint16 on the wire — losslessly, so the distributed-discovery
    fit stays bit-identical to the in-core fit."""
    print(run_with_devices("""
        import jax, numpy as np
        from repro.core.api import GEEK, DenseData, SparseData
        from repro.core.geek import GeekConfig
        from repro.data.synthetic import sift_like, url_like
        from repro.utils.compat import make_mesh

        def fit(dataset, key, cfg, **kw):
            est = GEEK(cfg)
            model = est.fit(dataset, key, **kw)
            return est.result_, model

        mesh = make_mesh()
        cfg = GeekConfig(m=16, t=32, silk_l=4, delta=5, k_max=64,
                         pair_cap=8192, compress_collectives=True)
        key = jax.random.PRNGKey(1)
        d = sift_like(jax.random.PRNGKey(0), n=2048, k=16)
        res0, m0 = fit(DenseData(d.x), key, cfg)
        res1, m1 = fit(DenseData(d.x), key, cfg, mesh=mesh)
        assert (np.asarray(res0.labels) == np.asarray(res1.labels)).all()
        assert (np.asarray(m0.centers) == np.asarray(m1.centers)).all()
        s = url_like(jax.random.PRNGKey(0), n=1100, k=8)  # cap_t = n > 2^8
        res2, m2 = fit(SparseData(s.sets, s.mask), key, cfg)
        res3, m3 = fit(SparseData(s.sets, s.mask), key, cfg, mesh=mesh)
        assert (np.asarray(res2.labels) == np.asarray(res3.labels)).all()
        assert (np.asarray(m2.centers) == np.asarray(m3.centers)).all()
        print("ok compressed wire bit-identical")
    """, n=4, timeout=600))


def test_property_sharded_permutation_and_mesh_invariance():
    """Hypothesis property: for seed_cap=None the sharded fit — now the
    distributed-discovery path by default — is equivariant to
    permutations across shard boundaries (any re-sharding of the rows
    reproduces the in-core fit on those rows bit-for-bit) and invariant
    to the mesh size across g in {1, 2, 4}. Runs hypothesis inside the
    multi-device subprocess; skips when hypothesis or a second device
    is unavailable."""
    out = run_with_devices("""
        import sys
        try:
            from hypothesis import given, settings, strategies as st
        except ImportError:
            print("NO_HYPOTHESIS"); sys.exit(0)
        import jax, numpy as np
        from repro.core.api import GEEK, DenseData
        from repro.core.geek import GeekConfig
        from repro.data.synthetic import sift_like
        from repro.utils.compat import make_mesh

        if len(jax.devices()) < 2:
            print("NO_DEVICES"); sys.exit(0)
        cfg = GeekConfig(m=8, t=16, silk_l=3, delta=4, k_max=32,
                         pair_cap=4096)
        key = jax.random.PRNGKey(1)

        def fit(dataset, **kw):
            est = GEEK(cfg)
            model = est.fit(dataset, key, **kw)
            return est.result_, model

        # two fixed row counts so jit/compile caches amortize across
        # examples; the drawn seed varies the permutation
        data = {n: np.asarray(sift_like(jax.random.PRNGKey(0), n=n,
                                        k=8).x) for n in (96, 130)}
        meshes = {g: make_mesh(devices=jax.devices()[:g])
                  for g in (1, 2, 4)}

        @settings(max_examples=8, deadline=None, derandomize=True)
        @given(st.integers(0, 2**31 - 1), st.sampled_from([96, 130]))
        def prop(seed, n):
            rng = np.random.default_rng(seed)
            xp = data[n][rng.permutation(n)]   # re-shard rows arbitrarily
            res0, m0 = fit(DenseData(jax.numpy.asarray(xp)))
            prev = (np.asarray(res0.labels), np.asarray(m0.centers))
            for g in (1, 2, 4):
                res_g, m_g = fit(DenseData(xp), mesh=meshes[g])
                assert (prev[0] == np.asarray(res_g.labels)).all(), g
                assert (prev[1] == np.asarray(m_g.centers)).all(), g
                prev = (np.asarray(res_g.labels), np.asarray(m_g.centers))

        prop()
        print("ok property held")
    """, n=4, timeout=600)
    if "NO_HYPOTHESIS" in out:
        pytest.skip("hypothesis not installed "
                    "(pip install -r requirements-dev.txt)")
    if "NO_DEVICES" in out:
        pytest.skip("needs >= 2 devices")
    print(out)

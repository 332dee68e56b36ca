"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, pack, ref


@pytest.mark.parametrize("n,k,d", [(64, 8, 16), (130, 33, 70), (257, 128, 128),
                                   (100, 5, 960)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_distance_argmin_l2_sweep(n, k, d, dtype):
    key = jax.random.PRNGKey(n + k + d)
    x = jax.random.normal(key, (n, d), dtype)
    c = jax.random.normal(jax.random.fold_in(key, 1), (k, d), dtype)
    valid = jnp.arange(k) % 7 != 3
    lk, dk = ops.distance_argmin_l2(x, c, valid, bn=64, bk=32)
    lr, dr = ref.distance_argmin_l2_ref(x, c, valid)
    # ties under low precision can flip the argmin; compare distances instead
    np.testing.assert_allclose(np.array(dk), np.array(dr),
                               rtol=2e-2, atol=2e-2)
    agree = float((lk == lr).mean())
    assert agree > 0.99


@pytest.mark.parametrize("n,k,d,card", [(50, 4, 9, 5), (129, 17, 45, 20),
                                        (64, 8, 400, 1 << 15)])
def test_distance_argmin_hamming_sweep(n, k, d, card):
    key = jax.random.PRNGKey(n * k)
    codes = jax.random.randint(key, (n, d), 0, card)
    c = jax.random.randint(jax.random.fold_in(key, 1), (k, d), 0, card)
    valid = jnp.ones((k,), bool)
    lk, dk = ops.distance_argmin_hamming(codes, c, valid, bn=32, bk=8)
    lr, dr = ref.distance_argmin_hamming_ref(codes, c, valid)
    np.testing.assert_array_equal(np.array(dk), np.array(dr))
    np.testing.assert_array_equal(np.array(lk), np.array(lr))


@pytest.mark.parametrize("n,k,d,bits", [(50, 4, 9, 4), (129, 17, 45, 8),
                                        (64, 8, 400, 16), (33, 70, 7, 2)])
def test_distance_argmin_hamming_packed_sweep(n, k, d, bits):
    """Packed kernel vs the *unpacked* equality oracle: labels and counts
    bit-identical. Shapes include k < bk and ragged d."""
    rng = np.random.default_rng(n * k + bits)
    card = 1 << bits
    codes = jnp.asarray(rng.integers(0, card, (n, d)), jnp.int32)
    c = jnp.asarray(rng.integers(0, card, (k, d)), jnp.int32)
    valid = jnp.arange(k) % 7 != 3
    xp = pack.pack_codes(codes, bits)
    cp = pack.pack_codes(c, bits)
    lk, dk = ops.distance_argmin_hamming_packed(xp, cp, valid, bits=bits,
                                                bn=32, bk=128)
    lr, dr = ref.distance_argmin_hamming_ref(codes, c, valid)
    np.testing.assert_array_equal(np.array(dk), np.array(dr))
    np.testing.assert_array_equal(np.array(lk), np.array(lr))
    # packed-domain oracle agrees too
    lp, dp = ref.distance_argmin_hamming_packed_ref(xp, cp, valid, bits=bits)
    np.testing.assert_array_equal(np.array(dp), np.array(dr))


@pytest.mark.parametrize("kernel", ["l2", "hamming", "packed"])
def test_distance_argmin_autotuned_tiles(kernel):
    """No explicit bn/bk: the shape-keyed autotuner picks the tiles
    and the kernels still match the oracles on ragged shapes."""
    key = jax.random.PRNGKey(11)
    for n, k, d in [(37, 3, 5), (300, 65, 129), (128, 260, 48)]:
        valid = jnp.arange(k) % 9 != 4
        if kernel == "l2":
            x = jax.random.normal(key, (n, d))
            c = jax.random.normal(jax.random.fold_in(key, 1), (k, d))
            lk, dk = ops.distance_argmin_l2(x, c, valid)
            lr, dr = ref.distance_argmin_l2_ref(x, c, valid)
            np.testing.assert_allclose(np.array(dk), np.array(dr),
                                       rtol=1e-4, atol=1e-4)
        else:
            rng = np.random.default_rng(n)
            codes = jnp.asarray(rng.integers(0, 16, (n, d)), jnp.int32)
            c = jnp.asarray(rng.integers(0, 16, (k, d)), jnp.int32)
            lr, dr = ref.distance_argmin_hamming_ref(codes, c, valid)
            if kernel == "hamming":
                lk, dk = ops.distance_argmin_hamming(codes, c, valid)
            else:
                lk, dk = ops.distance_argmin_hamming_packed(
                    pack.pack_codes(codes, 4), pack.pack_codes(c, 4),
                    valid, bits=4)
            np.testing.assert_array_equal(np.array(dk), np.array(dr))
            np.testing.assert_array_equal(np.array(lk), np.array(lr))


@pytest.mark.parametrize("n,k,d", [(64, 8, 16), (130, 33, 70), (257, 40, 128)])
def test_distance_argmin_l2_accumulate(n, k, d):
    """Fused per-cluster partial sums/counts match a segment_sum second pass."""
    key = jax.random.PRNGKey(n + k)
    x = jax.random.normal(key, (n, d))
    c = jax.random.normal(jax.random.fold_in(key, 1), (k, d))
    valid = jnp.arange(k) % 7 != 3
    lab, d2, sums, cnt = ops.distance_argmin_l2(x, c, valid, accumulate=True)
    lab0, d20 = ops.distance_argmin_l2(x, c, valid)
    np.testing.assert_array_equal(np.array(lab), np.array(lab0))
    np.testing.assert_allclose(np.array(d2), np.array(d20), rtol=1e-6)
    seg_s = jax.ops.segment_sum(x.astype(jnp.float32), lab, num_segments=k)
    seg_c = jax.ops.segment_sum(jnp.ones((n,), jnp.float32), lab,
                                num_segments=k)
    np.testing.assert_allclose(np.array(sums), np.array(seg_s),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.array(cnt), np.array(seg_c))


@pytest.mark.parametrize("B,Hq,Hkv,S,dh", [(1, 4, 4, 128, 32),
                                           (2, 8, 2, 100, 64),
                                           (1, 6, 1, 65, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, Hq, Hkv, S, dh, causal, rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (B, Hq, S, dh), jnp.float32)
    k = jax.random.normal(k2, (B, Hkv, S, dh), jnp.float32)
    v = jax.random.normal(k3, (B, Hkv, S, dh), jnp.float32)
    o1 = ops.flash_attention(q, k, v, causal=causal, bq=32, bk=32)
    o2 = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.array(o1), np.array(o2),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("B,Hq,Hkv,S,K,dh", [(1, 4, 4, 1, 48, 32),
                                             (2, 4, 2, 3, 100, 64),
                                             (1, 3, 1, 40, 33, 16)])
def test_flash_centroid_attention_sweep(B, Hq, Hkv, S, K, dh, rng):
    """Augmented-dimension centroid attention vs the jnp oracle,
    including GQA, ragged q/K lengths and dead (-1e30 log-mass) rows."""
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    q = jax.random.normal(k1, (B, Hq, S, dh), jnp.float32)
    c = jax.random.normal(k2, (B, Hkv, K, dh), jnp.float32)
    vc = jax.random.normal(k3, (B, Hkv, K, dh), jnp.float32)
    lm = jnp.log(1.0 + 8.0 * jax.random.uniform(k4, (B, Hkv, K)))
    lm = jnp.where(jnp.arange(K) < K - 5, lm, -1e30)   # 5 dead rows
    o1 = ops.flash_centroid_attention(q, c, vc, lm, bq=32, bk=32)
    o2 = ref.centroid_attention_ref(q, c, vc, lm)
    np.testing.assert_allclose(np.array(o1), np.array(o2),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16(rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (1, 2, 64, 32), jnp.bfloat16)
    k = jax.random.normal(k2, (1, 2, 64, 32), jnp.bfloat16)
    v = jax.random.normal(k3, (1, 2, 64, 32), jnp.bfloat16)
    o1 = ops.flash_attention(q, k, v, bq=32, bk=32)
    o2 = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.array(o1, np.float32),
                               np.array(o2, np.float32), rtol=5e-2, atol=5e-2)


def test_geek_code_bits_rounding_and_sparse_width(rng):
    """code_bits=5 rounds up to a packable width instead of crashing, and
    the sparse fit ignores a too-narrow code_bits (DOPH codes are 16-bit)."""
    import dataclasses
    from repro.core.api import GEEK, HeteroData, SparseData
    from repro.core.geek import GeekConfig
    key = jax.random.PRNGKey(7)
    templates = jax.random.randint(key, (4, 20), 0, 3000)
    pick = jax.random.randint(jax.random.fold_in(key, 1), (128,), 0, 4)
    sets = templates[pick]
    mask = jnp.ones_like(sets, bool)
    base = GeekConfig(silk_l=3, delta=3, k_max=16, pair_cap=2048)
    est16 = GEEK(base)
    est16.fit(SparseData(sets, mask), jax.random.PRNGKey(1))
    r16 = est16.result_
    # a narrow hetero code_bits must not truncate 16-bit DOPH codes
    est4 = GEEK(dataclasses.replace(base, code_bits=4))
    est4.fit(SparseData(sets, mask), jax.random.PRNGKey(1))
    r4 = est4.result_
    np.testing.assert_array_equal(np.array(r16.labels), np.array(r4.labels))
    # unsupported width on the packed path rounds up (5 -> 8), no crash
    xn = jax.random.normal(key, (96, 8))
    GEEK(dataclasses.replace(base, hamming_impl="packed", code_bits=5)).fit(
        HeteroData(xn, None), jax.random.PRNGKey(2))


def test_geek_pipeline_with_pallas_assignment(rng):
    """use_pallas=True path produces the same clusters as the jnp path."""
    from repro.core.api import GEEK, DenseData
    from repro.core.geek import GeekConfig
    from repro.data.synthetic import dense_blobs
    import dataclasses
    data = dense_blobs(rng, n=512, d=24, k=8)
    base = GeekConfig(m=8, t=16, silk_l=3, delta=3, k_max=32, pair_cap=2048)
    est1 = GEEK(base)
    est1.fit(DenseData(data.x), jax.random.PRNGKey(1))
    r1 = est1.result_
    est2 = GEEK(dataclasses.replace(base, use_pallas=True))
    est2.fit(DenseData(data.x), jax.random.PRNGKey(1))
    r2 = est2.result_
    assert int(r1.k_star) == int(r2.k_star)
    assert float((r1.labels == r2.labels).mean()) > 0.999

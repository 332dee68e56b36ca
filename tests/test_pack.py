"""Bit-packed code layout + tile autotuner (DESIGN.md §6-§7)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import assign as A
from repro.kernels import autotune, pack

ALL_BITS = (1, 2, 4, 8, 16, 32)


def _random_codes(seed, n, d, bits):
    rng = np.random.default_rng(seed)
    hi = min(1 << bits, 1 << 31)
    return jnp.asarray(rng.integers(0, hi, size=(n, d)), jnp.int32)


# ---------------------------------------------------------------------------
# pack / unpack
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", ALL_BITS)
@pytest.mark.parametrize("n,d", [(1, 1), (7, 3), (11, 37), (4, 64), (3, 33)])
def test_pack_roundtrip(bits, n, d):
    codes = _random_codes(bits * 101 + n + d, n, d, bits)
    packed = pack.pack_codes(codes, bits)
    assert packed.dtype == jnp.uint32
    assert packed.shape == (n, pack.packed_width(d, bits))
    np.testing.assert_array_equal(np.array(pack.unpack_codes(packed, bits, d)),
                                  np.array(codes))


@given(st.integers(1, 40), st.integers(1, 50), st.sampled_from(ALL_BITS))
@settings(max_examples=25, deadline=None)
def test_pack_roundtrip_property(n, d, bits):
    codes = _random_codes(n * 1000 + d * 7 + bits, n, d, bits)
    packed = pack.pack_codes(codes, bits)
    np.testing.assert_array_equal(np.array(pack.unpack_codes(packed, bits, d)),
                                  np.array(codes))


def test_pack_masks_oversized_codes():
    codes = jnp.asarray([[17]], jnp.int32)          # 17 = 0b10001, bits=4
    packed = pack.pack_codes(codes, 4)
    assert int(pack.unpack_codes(packed, 4, 1)[0, 0]) == 1


def test_bits_for_cardinality():
    assert pack.bits_for_cardinality(2) == 1
    assert pack.bits_for_cardinality(3) == 2
    assert pack.bits_for_cardinality(16) == 4
    assert pack.bits_for_cardinality(17) == 8
    assert pack.bits_for_cardinality(1 << 16) == 16
    assert pack.bits_for_cardinality((1 << 16) + 1) == 32
    with pytest.raises(ValueError):
        pack.bits_for_cardinality(0)


def test_popcount32_matches_lax():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 1 << 32, size=(2048,), dtype=np.uint32))
    np.testing.assert_array_equal(
        np.array(pack.popcount32(x)),
        np.array(jax.lax.population_count(x).astype(jnp.int32)))


# ---------------------------------------------------------------------------
# packed Hamming == unpacked Hamming (counts and labels bit-identical)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", ALL_BITS)
@pytest.mark.parametrize("n,k,d", [(33, 5, 7), (64, 8, 64), (10, 3, 130)])
def test_packed_hamming_equals_unpacked(bits, n, k, d):
    codes = _random_codes(bits + n, n, d, bits)
    cents = _random_codes(bits + n + 1, k, d, bits)
    ref = (codes[:, None, :] != cents[None, :, :]).sum(-1)
    got = pack.packed_hamming(pack.pack_codes(codes, bits),
                              pack.pack_codes(cents, bits), bits)
    np.testing.assert_array_equal(np.array(got), np.array(ref))


@pytest.mark.parametrize("bits,card", [(4, 16), (8, 256), (16, 60000)])
def test_assign_hamming_packed_labels_bit_identical(bits, card):
    rng = np.random.default_rng(bits)
    codes = jnp.asarray(rng.integers(0, card, (257, 23)), jnp.int32)
    cents = jnp.asarray(rng.integers(0, card, (19, 23)), jnp.int32)
    valid = jnp.arange(19) % 4 != 1
    lab_u, dist_u = A.assign_hamming(codes, cents, valid, block=64)
    lab_p, dist_p = A.assign_hamming_packed(
        pack.pack_codes(codes, bits), pack.pack_codes(cents, bits),
        valid, bits=bits, d=23, block=64)
    np.testing.assert_array_equal(np.array(lab_u), np.array(lab_p))
    np.testing.assert_array_equal(np.array(dist_u), np.array(dist_p))


@pytest.mark.parametrize("card", [2, 5, 16])
def test_assign_hamming_onehot_labels_bit_identical(card):
    rng = np.random.default_rng(card)
    codes = jnp.asarray(rng.integers(0, card, (130, 18)), jnp.int32)
    cents = jnp.asarray(rng.integers(0, card, (9, 18)), jnp.int32)
    valid = jnp.arange(9) % 3 != 1
    lab_u, dist_u = A.assign_hamming(codes, cents, valid, block=64)
    lab_o, dist_o = A.assign_hamming_onehot(codes, cents, valid, card=card,
                                            block=64)
    np.testing.assert_array_equal(np.array(lab_u), np.array(lab_o))
    np.testing.assert_array_equal(np.array(dist_u), np.array(dist_o))


# ---------------------------------------------------------------------------
# autotuner policy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["l2", "hamming", "hamming_packed"])
@pytest.mark.parametrize("n,k,d", [(8, 8, 8), (100, 5, 960), (65536, 1024, 64),
                                   (1 << 20, 4096, 128), (129, 17, 3)])
def test_select_tiles_fits_budget(kind, n, k, d):
    tc = autotune.select_tiles(kind, n, k, d)
    assert tc.bn >= 8 and tc.bk >= 8
    # bk is a lane axis on the chip: 128-aligned or one tile over all of k
    assert tc.bk % 128 == 0 or tc.bk >= k
    if kind != "l2":
        assert tc.bn * tc.bk <= autotune.VPU_TILE_ELEMS
    used = autotune._vmem_bytes(kind, tc.bn, tc.bk, d, 4)
    assert used <= autotune.DEFAULT_BUDGET


def test_select_tiles_deterministic_and_cached():
    a = autotune.select_tiles("l2", 4096, 256, 64)
    b = autotune.select_tiles("l2", 4096, 256, 64)
    assert a is b  # lru_cache hit
    assert a == autotune.TileConfig(a.bn, a.bk)


def test_select_tiles_huge_d_still_resolves():
    tc = autotune.select_tiles("hamming", 8, 8, 100000)
    assert tc.bn == 8 and tc.bk == 8


def test_cost_estimates_positive():
    for ce in (autotune.cost_l2(64, 8, 16), autotune.cost_hamming(64, 8, 16),
               autotune.cost_hamming_packed(64, 8, 4)):
        assert ce.flops > 0 and ce.bytes_accessed > 0

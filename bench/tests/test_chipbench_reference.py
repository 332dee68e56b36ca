"""The plain references that decide ``correct``: sound answers pass,
planted wrong answers and the lower-precision controls fail."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import data  # noqa: E402
from bench.reference import centers as ref_centers  # noqa: E402
from bench.reference import hamming, l2  # noqa: E402


@pytest.fixture(scope="module")
def blobs():
    x, _, c = data.dense_blobs(data.seed_key(2**31 + 11), n=4096, d=128,
                               k=64, spread=0.08)
    k_max = 80
    cp = jnp.zeros((k_max, 128)).at[:64].set(c)
    return x, cp, jnp.arange(k_max) < 64


def test_round_significand_matches_bfloat16():
    v = jax.random.normal(jax.random.PRNGKey(1), (10_000,)) * 37.0
    want = v.astype(jnp.bfloat16).astype(jnp.float32)
    assert np.array_equal(np.asarray(l2.round_significand(v, 7)),
                          np.asarray(want))


def test_sound_answers_read_under_one(blobs):
    x, c, v = blobs
    for bits in (22, 7):        # float32-exact and bfloat16 operands
        lab, dst = l2.control_answers(x, c, v, bits=bits, block=1000)
        r = l2.readings(x, lab, dst, c, v, block=1000)
        assert r["label_ratio"] <= 1.0 and r["dist_ratio"] <= 1.0


def test_control_reads_over_one(blobs):
    x, c, v = blobs
    lab, dst = l2.control_answers(x, c, v, bits=3)
    r = l2.readings(x, lab, dst, c, v)
    assert r["dist_ratio"] > 1.0


def test_planted_wrong_label_beyond_the_near_tie_bound_fails(blobs):
    x, c, v = blobs
    lab, dst = l2.control_answers(x, c, v, bits=22)
    far = np.asarray(lab).copy()
    far[123] = (far[123] + 7) % 64       # another, distant, valid center
    assert l2.readings(x, far, dst, c, v)["label_ratio"] > 1.0
    far[123] = 70                        # an invalid slot
    assert l2.readings(x, far, dst, c, v)["label_ratio"] == np.inf


def test_planted_wrong_distance_fails(blobs):
    x, c, v = blobs
    lab, dst = l2.control_answers(x, c, v, bits=22)
    bad = np.asarray(dst).copy()
    bad[5] *= 1.5
    assert l2.readings(x, lab, bad, c, v)["dist_ratio"] > 1.0


@pytest.fixture(scope="module")
def sets():
    s, m, _ = data.url_like(data.seed_key(5), n=3000, k=16, nnz=116,
                            universe=3_231_961, shared_frac=0.75)
    return s, m.at[:7, 90:].set(False)


def test_doph_matches_the_program_in_blocks(sets):
    from repro.core.transform import SparseTransform
    key = jax.random.PRNGKey(9)
    want = SparseTransform(key, 64)(*sets)
    got = hamming.doph_codes(*sets, key, m=64, block=1024)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_hamming_readings(sets):
    codes = hamming.doph_codes(*sets, jax.random.PRNGKey(9), m=64)
    centers = codes[::150][:16]
    valid = jnp.ones(16, bool)
    lab, dst = hamming.control_answers(codes, centers, valid, keep_bits=16)
    assert hamming.readings(codes, lab, dst, centers, valid) == \
        {"label_gap": 0, "dist_err": 0.0}
    bad = np.asarray(lab).copy()
    row = int(np.argmin(np.asarray(dst)))      # a row with a clear winner
    bad[row] = (bad[row] + 1) % 16
    assert hamming.readings(codes, bad, dst, centers, valid)[
        "label_gap"] > 0
    lab8, dst8 = hamming.control_answers(codes, centers, valid, keep_bits=8)
    r = hamming.readings(codes, lab8, dst8, centers, valid)
    assert r["label_gap"] > 0 or r["dist_err"] > 0


def test_modes_match_the_program(sets):
    from repro.core import assign, silk
    codes = hamming.doph_codes(*sets, jax.random.PRNGKey(3), m=64)
    ids = (jnp.arange(700) * 7) % 3000
    grp = jnp.arange(700) % 37
    seeds = silk.Seeds(grp.astype(jnp.int32), ids.astype(jnp.int32),
                       jnp.ones(700, bool), jnp.int32(37), 64)
    want, wvalid = assign.mode_centers(codes, seeds)
    got, gvalid = hamming.mode_centers(np.asarray(codes)[np.asarray(ids)],
                                       np.asarray(grp), 64)
    assert np.array_equal(np.asarray(wvalid), gvalid)
    assert np.array_equal(np.asarray(want)[gvalid], got[gvalid])


def test_centroids_match_the_program_and_bf16_does_not(blobs):
    from repro.core import assign, silk
    x = blobs[0]
    ids = (jnp.arange(2000) * 3) % 4096
    grp = jnp.arange(2000) % 23
    seeds = silk.Seeds(grp.astype(jnp.int32), ids.astype(jnp.int32),
                       jnp.ones(2000, bool), jnp.int32(23), 32)
    want, _ = assign.centroid_centers(x, seeds)
    rows = np.asarray(x)[np.asarray(ids)]
    ref, valid = ref_centers.centroids(rows, np.asarray(grp), 32)
    assert ref_centers.centroid_err(np.asarray(want), ref, valid,
                                    rows) < 1e-5
    ctl = ref_centers.centroids_bf16(rows, np.asarray(grp), 32)
    assert ref_centers.centroid_err(ctl, ref, valid, rows) > 1e-3


def test_seed_reference_reads_groups_against_generated_clusters():
    from bench.reference import seeds
    truth = np.array([0, 0, 0, 1, 1, 1, 2, 2])
    r = seeds.readings(truth, [0, 0, 0, 1, 1, -1], [0, 1, 3, 4, 5, 6],
                       [True, True, True, True, True, False])
    assert r["seed_impurity"] == pytest.approx(0.2)   # row 3 is astray
    assert r["rows_per_true_seed"] == pytest.approx(2.0)   # rows 0 1 4 5


def test_seed_reference_reads_no_seed_as_inf():
    from bench.reference import seeds
    r = seeds.readings(np.zeros(4, np.int64), [0], [0], [False])
    assert r["rows_per_true_seed"] == float("inf")

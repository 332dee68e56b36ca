"""Facade + protocol tests (DESIGN.md §11).

Covers:
- the facade's execution-mode bit-identity matrix (in-core ≡ streamed
  ≡ sharded ≡ predict-on-fit-data)
- KMeansPPSeeder parity with baselines.seed_then_assign on a fixed key
- checkpoint round-trip of the bucketer/seeder manifest fields
- a non-SILK Seeder end-to-end: fit -> checkpoint -> sharded predict
- the discovery= knob: explicit "sharded" raises with a named reason
  when distributed discovery can't run; the default (None) silently
  falls back to "gathered" (PR 7 satellite)

The legacy fit_*/fit_*_streaming/make_fit_sharded shims (and their
identity tests) were removed in PR 7 per the DESIGN.md §11 clock.

Multi-device sharding is covered by tests/test_distributed.py; here
sharded paths run on a 1-device mesh, which exercises the same
shard_map code.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro import (GEEK, DenseData, GeekConfig, HeteroData, KMeansPPSeeder,
                   ScalableKMeansPPSeeder, SparseData, restore_model,
                   save_model)
from repro.core import baselines
from repro.data import synthetic
from repro.utils.compat import make_mesh

CFG = GeekConfig(m=8, t=16, bucket_k=2, bucket_l=8, silk_l=3, delta=4,
                 k_max=64, pair_cap=4096)
KEY = jax.random.PRNGKey(0)
FIT_KEY = jax.random.PRNGKey(1)


def _dense(n=1500):
    return synthetic.sift_like(KEY, n=n, k=12)


def _datasets():
    d = _dense()
    h = synthetic.geonames_like(KEY, n=1200, k=8)
    s = synthetic.url_like(KEY, n=800, k=8)
    return {
        "dense": (DenseData(d.x), (d.x,)),
        "hetero": (HeteroData(h.x_num, h.x_cat), (h.x_num, h.x_cat)),
        "sparse": (SparseData(s.sets, s.mask), (s.sets, s.mask)),
    }


# ---------------------------------------------------------------------------
# Execution-mode bit-identity matrix through the facade alone
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["dense", "hetero", "sparse"])
def test_facade_mode_matrix_bit_identity(kind):
    spec, parts = _datasets()[kind]
    base = GEEK(CFG)
    base.fit(spec, FIT_KEY)
    ref = np.asarray(base.result_.labels)

    # streaming (ragged tail), host-numpy input
    np_parts = tuple(np.asarray(p) for p in parts)
    np_spec = {"dense": DenseData(np_parts[0]),
               "hetero": HeteroData(*np_parts),
               "sparse": SparseData(*np_parts)}[kind]
    st = GEEK(CFG)
    st.fit(np_spec, FIT_KEY, chunk=333)
    np.testing.assert_array_equal(np.asarray(st.result_.labels), ref)

    # sharded (1-device mesh exercises the shard_map path)
    sh = GEEK(CFG)
    sh.fit(spec, FIT_KEY, mesh=make_mesh())
    np.testing.assert_array_equal(np.asarray(sh.result_.labels), ref)

    # predict on the fit data ≡ fit labels
    lab, _ = sh.predict(spec)
    np.testing.assert_array_equal(np.asarray(lab), ref)


def test_seed_cap_requires_bounded_mode():
    d = _dense(500)
    with pytest.raises(ValueError, match="seed_cap"):
        GEEK(CFG).fit(DenseData(d.x), FIT_KEY, seed_cap=100)


def test_bare_array_means_dense_and_tuples_rejected():
    d = _dense(500)
    est = GEEK(CFG)
    est.fit(d.x, FIT_KEY)                       # bare (n, d) array OK
    assert est.model_.metric == "l2"
    with pytest.raises(TypeError, match="ambiguous"):
        GEEK(CFG).fit((d.x, d.x), FIT_KEY)


# ---------------------------------------------------------------------------
# Pluggable seeders
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k_max", [16, 64])
def test_kmeanspp_seeder_matches_seed_then_assign(k_max):
    """GEEK(cfg, seeder=KMeansPPSeeder(k)) ≡ baselines.seed_then_assign
    on the same fixed key — the facade hands non-bucket seeders the
    whole fit key, so the D^2 draws are identical.

    With k_max = k both sides run the same (n, k) distance program, and
    dists are bit-identical. A wider center table (invalid rows masked)
    is another dot program, whose x·c may sum in another order: labels
    and centers stay exact, dists agree to f32 rounding."""
    d = _dense()
    k = 16
    key = jax.random.PRNGKey(7)
    est = GEEK(dataclasses.replace(CFG, k_max=k_max),
               seeder=KMeansPPSeeder(k))
    model = est.fit(DenseData(d.x), key)
    base = baselines.seed_then_assign(d.x, k, key)
    np.testing.assert_array_equal(np.asarray(model.centers)[:k],
                                  np.asarray(base.centers))
    assert not np.asarray(model.center_valid)[k:].any()
    np.testing.assert_array_equal(np.asarray(est.result_.labels),
                                  np.asarray(base.labels))
    dists, base_dists = np.asarray(est.result_.dists), np.asarray(base.dists)
    if k_max == k:
        np.testing.assert_array_equal(dists, base_dists)
    else:
        # each x·c is within γ_d·|x||c| of exact (γ_d = d·u / (1 - d·u),
        # u = 2^-24) and the squared distance carries -2·x·c, so two
        # summation orders differ by at most 4·γ_d·|x||c|; rtol covers
        # the sqrt and the squaring back
        u = np.finfo(np.float32).eps / 2
        dim = d.x.shape[1]
        gamma = dim * u / (1 - dim * u)
        bound = (4 * gamma * np.linalg.norm(np.asarray(d.x), axis=1).max()
                 * np.linalg.norm(np.asarray(base.centers), axis=1).max())
        np.testing.assert_allclose(np.square(dists), np.square(base_dists),
                                   rtol=4 * u, atol=bound)
    assert int(est.result_.k_star) == k
    assert model.seeder_id == "kmeans++"


def test_scalable_kmeanspp_seeder_end_to_end():
    d = _dense()
    k = 16
    est = GEEK(CFG, seeder=ScalableKMeansPPSeeder(k, rounds=3))
    model = est.fit(DenseData(d.x), jax.random.PRNGKey(3))
    assert int(est.result_.k_star) == k
    assert model.seeder_id == "scalable-kmeans++"
    # seeds are real data rows (singleton groups -> centers are rows)
    x = np.asarray(d.x)
    centers = np.asarray(model.centers)[np.asarray(model.center_valid)]
    ids = np.asarray(est.result_.seeds.id)
    assert np.array_equal(centers, x[ids[: len(centers)]])


def test_kmeanspp_rejected_for_code_spaces():
    h = synthetic.geonames_like(KEY, n=600, k=8)
    with pytest.raises(ValueError, match="metrics"):
        GEEK(CFG, seeder=KMeansPPSeeder(8)).fit(
            HeteroData(h.x_num, h.x_cat), FIT_KEY)


def test_seeder_k_must_fit_budget():
    d = _dense(500)
    with pytest.raises(ValueError, match="k_max"):
        GEEK(CFG, seeder=KMeansPPSeeder(CFG.k_max + 1)).fit(
            DenseData(d.x), FIT_KEY)


def test_kmeanspp_seeder_streaming_and_sharded_match_incore():
    """The bit-identity matrix holds for a non-SILK seeder too."""
    d = _dense()
    key = jax.random.PRNGKey(5)
    ref = GEEK(CFG, seeder=KMeansPPSeeder(12))
    ref.fit(DenseData(d.x), key)
    st = GEEK(CFG, seeder=KMeansPPSeeder(12))
    st.fit(DenseData(np.asarray(d.x)), key, chunk=400)
    np.testing.assert_array_equal(np.asarray(st.result_.labels),
                                  np.asarray(ref.result_.labels))
    sh = GEEK(CFG, seeder=KMeansPPSeeder(12))
    sh.fit(DenseData(d.x), key, mesh=make_mesh())
    np.testing.assert_array_equal(np.asarray(sh.result_.labels),
                                  np.asarray(ref.result_.labels))


# ---------------------------------------------------------------------------
# Checkpoint round-trip of pipeline identity + non-SILK serving
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrips_pipeline_identity(tmp_path):
    d = _dense()
    est = GEEK(CFG)
    model = est.fit(DenseData(d.x), FIT_KEY)
    save_model(str(tmp_path), model)
    restored = restore_model(str(tmp_path))
    assert restored.bucketer_id == "lsh"
    assert restored.seeder_id == "silk"
    assert restored.static_meta() == model.static_meta()


def test_non_silk_fit_checkpoint_sharded_predict(tmp_path):
    """Acceptance: a non-SILK Seeder runs end-to-end through fit ->
    checkpoint -> sharded predict."""
    d = _dense()
    est = GEEK(CFG, seeder=KMeansPPSeeder(16))
    model = est.fit(DenseData(d.x), jax.random.PRNGKey(9))
    save_model(str(tmp_path), model)
    mesh = make_mesh()
    restored = restore_model(str(tmp_path), mesh=mesh)
    assert restored.seeder_id == "kmeans++"
    lab, _ = GEEK(CFG).predict(DenseData(d.x), model=restored, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(lab),
                                  np.asarray(est.result_.labels))


def test_predict_partial_batches_match_full(tmp_path):
    h = synthetic.geonames_like(KEY, n=1000, k=8)
    est = GEEK(CFG)
    est.fit(HeteroData(h.x_num, h.x_cat), FIT_KEY)
    full, _ = est.predict(HeteroData(h.x_num, h.x_cat))
    part, _ = est.predict(HeteroData(np.asarray(h.x_num),
                                     np.asarray(h.x_cat)), batch=300)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(part))


# ---------------------------------------------------------------------------
# discovery= knob (PR 6): routing, validation, and the gather-size guard
# ---------------------------------------------------------------------------

def test_discovery_knob_validation_and_modes_agree():
    """Unknown discovery values fail fast; the two valid modes are
    bit-identical on a 1-device mesh at full coverage."""
    d = _dense()
    mesh = make_mesh()
    with pytest.raises(ValueError, match="discovery"):
        GEEK(CFG).fit(DenseData(d.x), FIT_KEY, mesh=mesh, discovery="bogus")
    sh = GEEK(CFG)
    sh.fit(DenseData(d.x), FIT_KEY, mesh=mesh, discovery="sharded")
    ga = GEEK(CFG)
    ga.fit(DenseData(d.x), FIT_KEY, mesh=mesh, discovery="gathered")
    np.testing.assert_array_equal(np.asarray(sh.result_.labels),
                                  np.asarray(ga.result_.labels))
    ic = GEEK(CFG)
    ic.fit(DenseData(d.x), FIT_KEY)
    np.testing.assert_array_equal(np.asarray(sh.result_.labels),
                                  np.asarray(ic.result_.labels))


def test_discovery_resolution_default_falls_back_with_warning():
    """The default (discovery=None) routes the stock full-coverage
    pipeline to 'sharded' and falls back to 'gathered' when a reservoir
    subsamples or a non-bucket seeder is plugged in — announcing the
    plan change with a UserWarning instead of silently replicating the
    reservoir on every device."""
    import warnings as warnings_mod
    from repro.core.api import _resolve_discovery
    from repro import LSHBucketer, SILKSeeder
    b, s = LSHBucketer(), SILKSeeder()
    with warnings_mod.catch_warnings():
        warnings_mod.simplefilter("error")   # sharded paths never warn
        assert _resolve_discovery(None, None, 1000, b, s) == "sharded"
        assert _resolve_discovery(None, 1000, 1000, b, s) == "sharded"
    with pytest.warns(UserWarning, match="fell back to gathered"):
        assert _resolve_discovery(None, 500, 1000, b, s) == "gathered"
    with pytest.warns(UserWarning, match="fell back to gathered"):
        assert _resolve_discovery(None, None, 1000, b,
                                  KMeansPPSeeder(8)) == "gathered"
    # explicit "gathered" acknowledges the plan: no warning
    with warnings_mod.catch_warnings():
        warnings_mod.simplefilter("error")
        assert _resolve_discovery("gathered", None, 1000, b,
                                  s) == "gathered"
        assert _resolve_discovery("gathered", 500, 1000, b,
                                  s) == "gathered"


def test_discovery_fallback_warning_names_every_reason():
    """The warning text is part of the contract: it names each blocking
    reason and the acknowledge-to-silence knob."""
    from repro.core.api import _resolve_discovery
    from repro import LSHBucketer
    with pytest.warns(UserWarning) as rec:
        _resolve_discovery(None, 500, 1000, LSHBucketer(),
                           KMeansPPSeeder(8))
    msg = str(rec[0].message)
    assert "seed_cap=500" in msg and "n=1000" in msg
    assert "seeder" in msg
    assert "discovery='gathered'" in msg


def test_discovery_explicit_sharded_raises_with_named_reason():
    """An explicit discovery="sharded" that cannot be honored is a
    ValueError naming every blocking reason — never a silent fallback
    (PR 7 bugfix; the pre-fix behavior replicated the reservoir on
    every device while claiming to shard)."""
    from repro.core.api import _resolve_discovery
    from repro import LSHBucketer, SILKSeeder
    b, s = LSHBucketer(), SILKSeeder()
    assert _resolve_discovery("sharded", None, 1000, b, s) == "sharded"
    assert _resolve_discovery("sharded", 1000, 1000, b, s) == "sharded"
    with pytest.raises(ValueError, match="seed_cap=500"):
        _resolve_discovery("sharded", 500, 1000, b, s)
    with pytest.raises(ValueError, match="seeder"):
        _resolve_discovery("sharded", None, 1000, b, KMeansPPSeeder(8))
    # both reasons at once -> both named
    with pytest.raises(ValueError, match="seed_cap") as ei:
        _resolve_discovery("sharded", 500, 1000, b, KMeansPPSeeder(8))
    assert "seeder" in str(ei.value)
    # and the end-to-end path: an explicit sharded fit with seed_cap
    # raises instead of silently gathering
    d = _dense(500)
    with pytest.raises(ValueError, match="sharded"):
        GEEK(CFG).fit(DenseData(d.x), FIT_KEY, mesh=make_mesh(),
                      seed_cap=100, discovery="sharded")


def test_gathered_reservoir_cap_raises_clear_error():
    """An over-cap gathered fit raises a sized ValueError instead of an
    opaque OOM — and the default sharded mode is unaffected by the cap."""
    import dataclasses
    d = _dense()
    mesh = make_mesh()
    tiny = dataclasses.replace(CFG, gather_cap_bytes=1024)
    with pytest.raises(ValueError, match="gather_cap_bytes"):
        GEEK(tiny).fit(DenseData(d.x), FIT_KEY, mesh=mesh,
                       discovery="gathered")
    est = GEEK(tiny)   # sharded discovery never gathers the reservoir
    est.fit(DenseData(d.x), FIT_KEY, mesh=mesh, discovery="sharded")
    ic = GEEK(CFG)
    ic.fit(DenseData(d.x), FIT_KEY)
    np.testing.assert_array_equal(np.asarray(est.result_.labels),
                                  np.asarray(ic.result_.labels))
    # a seed_cap subsample also stays under the cap (strided reservoir)
    GEEK(tiny).fit(DenseData(d.x), FIT_KEY, mesh=mesh, seed_cap=4)

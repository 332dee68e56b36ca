"""Whole runs of the fit cells on the CPU: a sound run is correct, and
a run whose answers are altered where they are produced is not."""
import json

import jax
import jax.numpy as jnp
import pytest

from bench import control
from bench.tests._cells import run_small, small_cell


@pytest.mark.parametrize("cell", ["sift1m.fit", "url.fit"])
def test_sound_fit_run_is_correct_and_its_control_is_not(cell):
    seen = {}
    fields, checks, line = run_small(cell, keep=seen)
    assert fields["correct"], checks
    ctl = control.fit_control(small_cell(cell).config, seen["parts"],
                              seen["answers"][0])
    assert any(ctl[k] > checks[k].limit for k in ctl), ctl
    out = json.loads(line)
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"setup_s", "fit_points_per_s",
                                   "fit_peak_hbm_gb"}
    assert out["attempted"] >= 1 and out["failed"] == 0


def test_traced_fit_run_reports_per_layer_metrics():
    fields, _, line = run_small("sift1m.fit", trace=True)
    out = json.loads(line)
    assert fields["correct"]
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert "breakdown" in out
    # the CPU has no TPU plane: nothing ran "on the device", and the
    # sort share is left out rather than read as 0
    assert "fit.sort_share" not in out["metrics"]


def _alter_first_label(fn):
    def altered(*a, **kw):
        lab, dst = fn(*a, **kw)
        return lab.at[0].set(lab[0] + 1), dst
    return altered


def test_dense_fit_with_an_altered_label_is_not_correct(monkeypatch):
    from repro.core import assign
    jax.clear_caches()
    monkeypatch.setattr(assign, "assign_l2",
                        _alter_first_label(assign.assign_l2))
    fields, checks, _ = run_small("sift1m.fit")
    assert not fields["correct"]
    assert not checks["label_ratio"].ok


def test_sparse_fit_with_an_altered_label_is_not_correct(monkeypatch):
    from repro.kernels import ops
    jax.clear_caches()
    monkeypatch.setattr(ops, "distance_argmin_hamming_packed",
                        _alter_first_label(
                            ops.distance_argmin_hamming_packed))
    fields, checks, _ = run_small("url.fit")
    assert not fields["correct"]
    assert not checks["label_gap"].ok


def test_dense_fit_with_a_moved_center_is_not_correct(monkeypatch):
    from repro.core import assign
    jax.clear_caches()
    real = assign.centroid_centers

    def moved(x, seeds):
        c, v = real(x, seeds)
        return c.at[jnp.argmax(v), 0].add(1e-2), v
    monkeypatch.setattr(assign, "centroid_centers", moved)
    fields, checks, _ = run_small("sift1m.fit")
    assert not fields["correct"]
    assert not checks["center_err"].ok


def _shuffle_seed_rows(real):
    """``select_top_groups`` whose published pairs keep their groups but
    take the rows of other pairs, drawn at random: wrong groups."""
    def shuffled(pairs, group_cap, k_max):
        s = real(pairs, group_cap, k_max)
        perm = jax.random.permutation(jax.random.PRNGKey(0), s.id.shape[0])
        return s._replace(id=s.id[perm])
    return shuffled


@pytest.mark.parametrize("cell", ["url.fit"])
def test_fit_publishing_wrong_seed_groups_is_not_correct(monkeypatch, cell):
    from repro.core import silk
    jax.clear_caches()
    monkeypatch.setattr(silk, "select_top_groups",
                        _shuffle_seed_rows(silk.select_top_groups))
    fields, checks, _ = run_small(cell)
    assert not fields["correct"]
    assert not checks["seed_impurity"].ok


@pytest.mark.parametrize("cell", ["url.fit"])
def test_fit_with_fewer_silk_rounds_seeds_fewer_rows(cell):
    from bench import seed_control
    jax.clear_caches()
    config = small_cell(cell).config
    seed = 2**31 + 101
    read = {}
    for rounds in (config["fit"]["silk_l"], 1):
        fields = dict(config["fit"], silk_l=rounds)
        parts, truth, a, _ = seed_control.fit_once(config, fields, seed)
        read[rounds] = seed_control.seeds.readings(
            truth, a.seed_group, a.seed_id, a.seed_valid)
    sound, fault = read[config["fit"]["silk_l"]], read[1]
    assert sound["rows_per_true_seed"] <= config["limits"]["fit"][
        "rows_per_true_seed"]
    assert fault["rows_per_true_seed"] >= 2.0 * sound["rows_per_true_seed"]

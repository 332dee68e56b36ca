"""The benchmark's arithmetic: arrivals, the query order, percentiles,
the fit window, the roofline count and its bound, and seeds."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, roofline  # noqa: E402

PEAKS = harness.peaks_for("TPU v5 lite")


def test_exponential_gaps_fill_the_window():
    g = harness.exponential_gaps(5000, 40.0)
    assert g.sum() == pytest.approx(40.0)
    assert g.mean() == pytest.approx(40.0 / 5000)
    assert np.median(g) == pytest.approx(np.log(2) * g.mean(), rel=0.02)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**33 + 1])
def test_open_schedule_is_one_multiset_per_rate(seed):
    from bench import serving
    traffic = {"rate_rps": 300.0, "rows_per_request": 32}
    a = serving.open_schedule(traffic, 10.0, 65536, 1)
    b = serving.open_schedule(traffic, 10.0, 65536, seed)
    assert np.array_equal(np.sort(a["size"]), np.sort(b["size"]))
    assert a["due"].size == b["due"].size == 3000
    assert (np.diff(b["due"]) > 0).all()
    assert b["due"][0] == 0 and b["due"][-1] < 10.0
    assert (b["start"] + b["size"] <= 65536).all()


def test_open_schedule_of_single_queries_draws_every_pool_row():
    from bench import serving
    j = serving.open_schedule({"rate_rps": 2000.0, "rows_per_request": 1},
                              40.0, 10_000, 2**31 + 9)
    assert (j["size"] == 1).all() and j["due"].size == 80_000
    assert set(j["start"].tolist()) == set(range(10_000))


@pytest.mark.parametrize("q,want", [(50, 2.5), (95, 3.85), (0, 1.0),
                                    (100, 4.0)])
def test_percentile_is_linear_between_order_statistics(q, want):
    assert harness.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)


def test_percentile_of_nothing_is_nan():
    assert np.isnan(harness.percentile([], 95))


@pytest.mark.parametrize("elapsed,last,seconds,more", [
    (30.0, 30.0, 40.0, False), (10.0, 10.0, 40.0, True),
    (20.0, 20.0, 40.0, True), (20.1, 20.0, 40.0, False)])
def test_fit_window(elapsed, last, seconds, more):
    assert harness.fit_may_start(elapsed, last, seconds) is more


def test_assign_work_counts_the_algorithm():
    flops, nbytes = roofline.assign_work(1000, 2, 1000, 128)
    assert flops == 2 * 1000 * 1000 * 128
    assert nbytes == 1000 * (4 * 128 + 8) + 2 * 4 * 1000 * 128


@pytest.mark.parametrize("rows,batches,which", [
    (4_000_000, 1_000, "compute"),     # big batches: the MXU bounds
    (100_000, 10_000, "memory")])      # tiny batches: center reads bound
def test_roofline_bound_choice(rows, batches, which):
    flops, nbytes = roofline.assign_work(rows, batches, 1000, 128)
    least, bound = roofline.bound(flops, nbytes, PEAKS)
    assert bound == which
    assert least == pytest.approx(max(flops / 197e12, nbytes / 819e9))


def test_roofline_share_never_reads_zero_for_no_work():
    assert roofline.share(1e-3, 0.0) is None
    assert roofline.share(1e-3, 4e-3) == pytest.approx(0.25)


@pytest.mark.parametrize("seed", [0, 2**31 - 1, 2**31 + 17, 2**40 + 3])
def test_large_seeds_make_distinct_keys(seed):
    import jax
    from bench import data
    k = data.seed_key(seed)
    assert k.shape == (2,)
    assert not np.array_equal(np.asarray(k),
                              np.asarray(data.seed_key(seed + 1)))
    assert np.array_equal(np.asarray(k), np.asarray(data.seed_key(seed)))
    del jax


def test_result_line_keeps_checks_last_and_writes_inf_as_a_name():
    import json
    line = harness.result_line(
        correct=False, attempted=1, failed=0, metrics={},
        device={"platform": "tpu"}, checks=[
            harness.Check("label_ratio", float("inf"), 1.0),
            harness.Check("dist_ratio", 0.5, 1.0)])
    out = json.loads(line)
    assert list(out)[-1] == "checks"
    assert out["checks"]["label_ratio"] == {"value": "inf", "limit": 1.0}
    assert not harness.Check("x", float("nan"), 1.0).ok

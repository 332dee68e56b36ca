"""Driver ``fit``: back-to-back in-core fits of the configuration's data.

Set-up makes the data on the device from the seed and runs one whole
warm-up fit, which loads the fit program from the compile cache (the
in-core fit is one jitted program; it cannot be warmed without a run).
The window then runs ``GEEK.fit`` with a new fit key each time, waiting
for the labels. The first fit starts at the window's start; no fit
starts once the elapsed time plus the last fit's duration would pass
``--seconds``; at least one runs.

End-to-end: ``fit_points_per_s`` (rows of all fits over the time from
the first fit's start to the last fit's end) and ``fit_peak_hbm_gb``
(the device's ``peak_bytes_in_use`` after the window, 10^9 B).

``correct``: every fit of the window is compared with the plain
references: its seeding (the seed groups it published, against the
clusters the benchmark generated), its one-pass assignment (labels and
distances over its own published centers) and its centers (centroids,
or per-attribute modes, of the seed groups it published).
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import data, harness
from bench.reference import centers as ref_centers
from bench.reference import hamming, l2, seeds


@dataclasses.dataclass
class FitAnswer:
    """What one fit published, on the host or device."""

    labels: object
    dists: object
    centers: object
    center_valid: object
    seed_group: object
    seed_id: object
    seed_valid: object
    doph_key: object = None


def geek_config(fields: dict):
    """``GeekConfig`` from the configuration's spelled-out fields; a
    field the file does not name is reported, an unknown one refused."""
    from repro import GeekConfig
    known = {f.name for f in dataclasses.fields(GeekConfig)}
    unknown = set(fields) - known
    if unknown:
        raise harness.SpecError(f"unknown GeekConfig fields {sorted(unknown)}")
    missing = known - set(fields)
    if missing:
        harness.log(f"fit: GeekConfig fields at their defaults: "
                    f"{sorted(missing)}")
    return GeekConfig(**fields)


def answer(est, model) -> FitAnswer:
    r = est.result_
    t = model.transform
    return FitAnswer(r.labels, r.dists, r.centers, r.center_valid,
                     r.seeds.group, r.seeds.id, r.seeds.valid,
                     getattr(t, "doph_key", None))


def readings(config: dict, parts: tuple, truth, a: FitAnswer) -> dict:
    """The compared numbers of one fit against the plain references;
    ``truth`` is each row's generated cluster."""
    kind = config["data"]["kind"]
    sv = np.asarray(a.seed_valid)
    member = np.asarray(a.seed_id)[sv]
    group = np.asarray(a.seed_group)[sv]
    k_max = int(np.asarray(a.center_valid).shape[0])
    cvalid = np.asarray(a.center_valid)
    if kind == "dense":
        x = parts[0]
        out = l2.readings(x, a.labels, a.dists, a.centers, a.center_valid)
        rows = np.asarray(x[np.asarray(member)])
        ref, ref_valid = ref_centers.centroids(rows, group, k_max)
        out["center_err"] = ref_centers.centroid_err(
            np.asarray(a.centers), ref, ref_valid, rows)
    else:
        m = int(config["fit"]["doph_m"])
        codes = hamming.doph_codes(*parts, a.doph_key, m=m)
        out = hamming.readings(codes, a.labels, a.dists, a.centers,
                               a.center_valid)
        ref, ref_valid = hamming.mode_centers(
            np.asarray(codes[np.asarray(member)]), group, k_max)
        cm = np.asarray(a.centers)
        out["center_cells_wrong"] = int((cm[ref_valid]
                                         != ref[ref_valid]).sum())
    out["center_valid_wrong"] = int((cvalid != ref_valid).sum())
    out.update(seeds.readings(truth, a.seed_group, a.seed_id, a.seed_valid))
    return out


def checks_of(config: dict, worst: dict) -> list:
    """The readings the configuration gives a limit; the others (a
    seeding number that no control separates there) are logged."""
    limits = config["limits"]["fit"]
    rest = {k: v for k, v in worst.items() if k not in limits}
    if rest:
        harness.log(f"fit: readings not compared: {rest}")
    return [harness.Check(k, float(worst[k]), float(limits[k]))
            for k in limits]


def worst_of(per_fit: list) -> dict:
    return {k: max(r[k] for r in per_fit) for k in per_fit[0]}


def run(ctx):
    import jax

    from repro import GEEK, DenseData, SparseData
    config = ctx.cell.config
    key = data.seed_key(ctx.seed)
    made = data.make(config, jax.random.fold_in(key, 0))
    parts = jax.block_until_ready(made["parts"])
    # the generated clusters live on the host: the fit's peak is its own
    truth = np.asarray(made.pop("truth"))
    wrap = DenseData if config["data"]["kind"] == "dense" else SparseData
    est = GEEK(geek_config(config["fit"]))
    n = int(parts[0].shape[0])

    def fit(i: int):
        with jax.profiler.TraceAnnotation("bench.fit"):
            model = est.fit(wrap(*parts), jax.random.fold_in(key, 1 + i))
            jax.block_until_ready(est.result_.labels)
        return answer(est, model)

    fit(0)                                     # warm-up, set-up
    compiles0 = ctx.clock.events
    answers, spans = [], []
    t_w0 = time.perf_counter()
    setup_s = t_w0 - ctx.t_start
    with ctx.window():
        while True:
            ts = time.perf_counter()
            answers.append(fit(len(answers) + 1))
            te = time.perf_counter()
            spans.append(te - ts)
            if not harness.fit_may_start(te - t_w0, spans[-1],
                                         ctx.seconds):
                break
    t_w1 = te
    peak = harness.device_bytes("peak_bytes_in_use") or 0  # 0: no stats
    del est
    compiles = ctx.clock.events - compiles0
    harness.log(f"fit: {len(answers)} fit(s) of {n} rows in "
                f"{t_w1 - t_w0:.6f} s ({', '.join(f'{s:.6f}' for s in spans)}); "
                f"set-up {setup_s:.6f} s; compiles in window {compiles}")
    if ctx.keep is not None:
        ctx.keep.update(parts=parts, truth=truth, answers=answers)
    per_fit = [readings(config, parts, truth, a) for a in answers]
    worst = worst_of(per_fit)
    return ctx.outcome(
        attempted=len(answers), failed=0,
        end_to_end={"setup_s": setup_s,
                    "fit_points_per_s": n * len(answers) / (t_w1 - t_w0),
                    "fit_peak_hbm_gb": peak / 1e9},
        checks=checks_of(config, worst), memory_peak_bytes=peak,
        measured={"fits": len(answers), "rows": n},
        notes={"fits": len(answers), "fit_s": spans,
               "compiles_in_window": compiles})

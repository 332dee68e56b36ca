"""The readings that a fit cell's limits are set from: the program's
sound fits on many seeds, and its seeding faults, at the cell's size.

    python3 bench/seed_control.py --workload sift1m.fit \\
        --seeds a,b,...  --fault-seeds a,b,c \\
        --faults silk_l=1,pair_cap=16384 [--out file.jsonl]

One process on the chip. For each seed it makes the cell's data, runs
the cell's fit as the window's first fit runs it (same configuration,
same fit key) and prints every compared number (``drivers/fit.py``),
with the fit's time, k* and seed counts. On the fault seeds it also
reads the seeding numbers of:

- ``shuffled``: the sound fit's seed groups with their rows permuted,
  as a fit that publishes wrong groups would;
- each ``--faults`` entry: the program run with that one ``GeekConfig``
  field changed, as a fit with fewer SILK rounds (``silk_l``) or with
  its seed pairs truncated (``pair_cap``) would.

The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

_ROOT = Path(__file__).resolve().parent.parent
sys.path[0:1] = [str(_ROOT), str(_ROOT / "src")]

from bench import data, harness, run  # noqa: E402
from bench.drivers import fit as fit_driver  # noqa: E402
from bench.reference import seeds  # noqa: E402


def shuffled(a: fit_driver.FitAnswer, rng) -> np.ndarray:
    """The seed rows permuted among the valid pairs: the same group
    sizes, wrong members."""
    sv = np.asarray(a.seed_valid)
    ids = np.asarray(a.seed_id).copy()
    ids[sv] = rng.permutation(ids[sv])
    return ids


def fit_once(config: dict, fields: dict, seed: int):
    """One fit of the cell's data for ``seed``, keyed as the window's
    first fit: (parts, truth, answer, timing and seed counts)."""
    import jax

    from repro import GEEK, DenseData, SparseData
    key = data.seed_key(seed)
    made = data.make(config, jax.random.fold_in(key, 0))
    parts = jax.block_until_ready(made["parts"])
    wrap = DenseData if config["data"]["kind"] == "dense" else SparseData
    est = GEEK(fit_driver.geek_config(fields))
    t0 = time.perf_counter()
    model = est.fit(wrap(*parts), jax.random.fold_in(key, 2))
    jax.block_until_ready(est.result_.labels)
    dt = time.perf_counter() - t0
    a = fit_driver.answer(est, model)
    info = {"fit_s": dt, "k_star": int(est.result_.k_star),
            "overflow": int(est.result_.overflow),
            "seed_pairs": int(np.asarray(a.seed_valid).sum())}
    return parts, made["truth"], a, info


def _parse_faults(text: str) -> list:
    out = []
    for item in filter(None, text.split(",")):
        k, v = item.split("=")
        out.append((k, int(v)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = harness.find_cell(harness.load_spec(), args.workload)
    if cell.traffic["driver"] != "fit":
        raise SystemExit("seed_control reads fit cells only")
    run._configure_jax()
    harness.device_check(cell.chips)
    config = cell.config
    fault_seeds = [int(s) for s in filter(None, args.fault_seeds.split(","))]
    with (open(args.out, "a") if args.out
          else contextlib.nullcontext()) as sink:

        def emit(rec: dict) -> None:
            line = json.dumps(dict(rec, workload=cell.name))
            print(line, flush=True)
            if sink:
                sink.write(line + "\n")
                sink.flush()

        for seed in (int(s) for s in args.seeds.split(",")):
            parts, truth, a, info = fit_once(config, config["fit"], seed)
            t0 = time.perf_counter()
            r = fit_driver.readings(config, parts, truth, a)
            emit({"kind": "program", "seed": seed, **info,
                  "check_s": time.perf_counter() - t0, "readings": r})
            if seed in fault_seeds:
                ids = shuffled(a, np.random.default_rng(seed))
                emit({"kind": "shuffled", "seed": seed,
                      "readings": seeds.readings(truth, a.seed_group, ids,
                                                 a.seed_valid)})
            del parts, truth, a
        for field, value in _parse_faults(args.faults):
            for seed in fault_seeds:
                fields = dict(config["fit"], **{field: value})
                parts, truth, a, info = fit_once(config, fields, seed)
                emit({"kind": f"{field}={value}", "seed": seed, **info,
                      "readings": seeds.readings(truth, a.seed_group,
                                                 a.seed_id, a.seed_valid)})
                del parts, truth, a
    return 0

if __name__ == "__main__":
    sys.exit(main())

"""The control of a cell's ``correct``: the plain reference put in the
program's place at the next lower precision, which must come out as not
correct.

    python3 bench/control.py --workload <cell> --seeds a,b,c [--seconds s]

One process on the chip. For each seed it runs the cell once through
its own driver with a short window (so the program's readings are those
of the timed path at the cell's size), then computes the control's
answers for the same inputs and reads them with the same comparison:

- dense L2: the reference's dot operands rounded to float8 e4m3's 3
  significand bits (the configuration states bfloat16 operands), and
  centroids summed in bfloat16 (it states float32);
- sparse Hamming: the reference's argmin over codes narrowed to their
  top 8 of 16 bits (it states exact 16-bit codes).

It prints one JSON line per seed with both sets of readings and the
limits. The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

_ROOT = Path(__file__).resolve().parent.parent
sys.path[0:1] = [str(_ROOT), str(_ROOT / "src")]

from bench import client, harness, run  # noqa: E402
from bench.reference import centers as ref_centers  # noqa: E402
from bench.reference import hamming, l2  # noqa: E402


def fit_control(config: dict, parts: tuple, a) -> dict:
    """The control's readings for one fit's inputs."""
    sv = np.asarray(a.seed_valid)
    member = np.asarray(a.seed_id)[sv]
    group = np.asarray(a.seed_group)[sv]
    k_max = int(np.asarray(a.center_valid).shape[0])
    if config["data"]["kind"] == "dense":
        x = parts[0]
        lab, dst = l2.control_answers(x, a.centers, a.center_valid)
        out = l2.readings(x, lab, dst, a.centers, a.center_valid)
        rows = np.asarray(x[member])
        ref, ref_valid = ref_centers.centroids(rows, group, k_max)
        out["center_err"] = ref_centers.centroid_err(
            ref_centers.centroids_bf16(rows, group, k_max), ref, ref_valid,
            rows)
        return out
    codes = hamming.doph_codes(*parts, a.doph_key,
                               m=int(config["fit"]["doph_m"]))
    lab, dst = hamming.control_answers(codes, a.centers, a.center_valid)
    return hamming.readings(codes, lab, dst, a.centers, a.center_valid)


def serve_control(cell, seed: int, merged: dict) -> dict:
    """The control's readings over the served (row, label) pairs' rows."""
    import jax

    from bench import data, serving
    key = data.seed_key(seed)
    made = data.make(cell.config, jax.random.fold_in(key, 0),
                     n=int(cell.config["query_rows"]))
    pool = np.asarray(made["parts"][0], np.float32)
    _, centers, valid = serving.served_model(cell.config, made["centers"])
    rows = np.unique(merged["pair_key"] // client.LABELS)
    lab, dst = l2.control_answers(pool[rows], centers, valid)
    r = l2.readings(pool[rows], lab, dst, centers, valid)
    return {"answer_ratio": max(r.values()), **r}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    cell = harness.find_cell(harness.load_spec(), args.workload)
    run._configure_jax()
    device = harness.device_check(cell.chips)
    for seed in (int(s) for s in args.seeds.split(",")):
        seen: dict = {}
        fields, checks = run.execute(cell, seed, args.seconds, False, device,
                                     keep=seen)
        if "answers" in seen:
            ctl = [fit_control(cell.config, seen["parts"], a)
                   for a in seen["answers"]]
            control = {k: max(c[k] for c in ctl) for k in ctl[0]}
        else:
            control = serve_control(cell, seed, seen["merged"])
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "program_correct": fields["correct"],
            "program": {c.name: c.value for c in checks},
            "control": control,
            "limits": {c.name: c.limit for c in checks},
            "control_fails": any(control.get(c.name, 0) > c.limit
                                 for c in checks)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

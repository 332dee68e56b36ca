"""Find the knee of an open-loop serving cell: the highest offered rate
the served path sustains with no growing backlog.

    python3 bench/sweep.py --workload sift1m.serve --rates 1200,1400,1600 \\
        --seed <n> --seconds 15

One process on the chip; each rate is one run of the cell's own driver
with its traffic's ``rate_rps`` replaced. For each rate it prints the
latency percentiles, how late the generator ran, and the median latency
of the window's last quarter over its first: a ratio well above 1 means
the queue grows through the run. The knee is found once, by hand, and
written into the traffic file as a number; no run searches for it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
sys.path[0:1] = [str(_ROOT), str(_ROOT / "src")]

from bench import harness, run  # noqa: E402


def backlog_ratio(due, done) -> float:
    """Median latency of the last quarter of requests (by due time)
    over that of the first quarter."""
    import numpy as np
    order = np.argsort(due)
    lat = (np.asarray(done) - np.asarray(due))[order]
    q = max(1, lat.size // 4)
    return float(np.nanmedian(lat[-q:]) / np.nanmedian(lat[:q]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    cell = harness.find_cell(harness.load_spec(), args.workload)
    if cell.traffic["driver"] != "serve_open":
        raise SystemExit("the sweep drives open-loop cells only")
    run._configure_jax()
    device = harness.device_check(cell.chips)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell.traffic["rate_rps"] = rate
        seen = {}
        fields, checks = run.execute(
            cell, args.seed + i, args.seconds, False, device,
            keep=seen)
        m = seen["merged"]
        ok = m["status"] == 200
        lat = (m["done"] - m["due"])[ok] * 1e3
        late = (m["sent"] - m["due"])[ok] * 1e3
        print(json.dumps({
            "rate_rps": rate, "correct": fields["correct"],
            "attempted": fields["attempted"], "failed": fields["failed"],
            "p50_ms": harness.percentile(lat, 50),
            "p95_ms": harness.percentile(lat, 95),
            "p99_ms": harness.percentile(lat, 99),
            "late_p99_ms": harness.percentile(late, 99),
            "backlog_ratio": backlog_ratio(m["due"][ok], m["done"][ok]),
            "rows_per_s": float(m["size"][ok].sum()) / args.seconds}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

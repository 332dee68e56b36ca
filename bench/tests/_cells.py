"""Whole runs of the benchmark's cells on the CPU at a test size: the
harness's look for a chip is skipped, the rest of a run is driven."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, run  # noqa: E402

#: test sizes; widths (d, items per row, DOPH codes) stay as configured
SMALL = {
    "sift1m.fit": ({"n": 12288, "data.clusters": 24}, {}),
    "url.fit": ({"n": 8192, "data.clusters": 16}, {}),
    "sift1m.serve": ({"query_rows": 4096},
                     {"rate_rps": 60.0, "processes": 2, "threads": 4,
                      "trace_offset_s": 0.3, "trace_s": 0.8}),
}
#: stands for the peaks table, which has no CPU
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def small_cell(name: str) -> harness.Cell:
    cell = harness.find_cell(harness.load_spec(), name)
    cfg, traffic = SMALL[name]
    for k, v in cfg.items():
        d = cell.config
        *path, last = k.split(".")
        for p in path:
            d = d[p]
        d[last] = v
    cell.traffic.update(traffic)
    return cell


def run_small(name: str, *, seed: int = 2**31 + 101, seconds: float = 1.5,
              trace: bool = False, keep: dict | None = None):
    fields, checks = run.execute(small_cell(name), seed, seconds, trace,
                                 harness.device_info(), peaks=PEAKS,
                                 keep=keep)
    line = harness.result_line(checks=checks, **fields)
    return fields, {c.name: c for c in checks}, line

"""Run one cell of GEEK's chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It refuses to run (non-zero exit, no result line) unless
JAX's devices are TPU chips, as many as the cell asks for. It makes the
cell's inputs from ``--seed``, warms up every shape the window uses
(set-up), measures for ``--seconds``, checks what the timed path
produced against the plain reference, and prints as its last line one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, and with ``--trace 1`` ``breakdown``; the compared numbers
come last, under ``checks``, and again as the last lines on standard
error. ``--trace 0`` reports the cell's end-to-end metrics, ``--trace
1`` its per-layer metrics from a traced window.

JAX's persistent compilation cache is ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()
T_START_MONO = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parent.parent
# the checkout and the program under test, in place of this directory
sys.path[0:1] = [str(_ROOT), str(_ROOT / "src")]

from bench import harness, tracing  # noqa: E402


@dataclasses.dataclass
class Outcome:
    """What a driver hands back."""

    attempted: int
    failed: int
    end_to_end: dict
    checks: list
    memory_peak_bytes: int | None
    measured: dict
    notes: dict


class Context:
    """One run of one cell, as a driver sees it."""

    def __init__(self, cell: harness.Cell, seed: int, seconds: float,
                 trace: bool, *, t_start: float = T_START,
                 t_start_mono: float = T_START_MONO,
                 keep: dict | None = None):
        self.cell, self.seed, self.seconds, self.trace = (cell, seed,
                                                          seconds, trace)
        self.t_start, self.t_start_mono = t_start, t_start_mono
        self.clock = harness.CompileClock()
        self.trace_dir = None
        self._traced = False
        #: where a driver leaves its raw records for a caller (the sweep)
        self.keep = keep

    @contextlib.contextmanager
    def window(self):
        """Record the profiler trace of the block when the run is
        traced (once per run)."""
        if not self.trace:
            yield
            return
        if self._traced:
            raise RuntimeError("a run traces one window")
        self._traced = True
        with tracing.recording(self.trace_dir):
            yield

    def outcome(self, **kw) -> Outcome:
        return Outcome(**kw)


def _configure_jax() -> None:
    """The compile cache at its fixed path; every program cached and
    none evicted. Eviction, which a size limit in the environment turns
    on, reads an access-time file for every entry and fails every write
    when one lacks it (a cache directory restored without them), so a
    run would compile all its programs again."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.cache_dir()
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    import jax
    jax.config.update("jax_compilation_cache_dir", harness.cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def execute(cell: harness.Cell, seed: int, seconds: float, trace: bool,
            device: dict, peaks: dict | None = None,
            **ctx_kw) -> tuple[dict, list]:
    """Run the cell; returns (result fields, checks). ``peaks`` stands
    in for the table's entry of the device (CPU tests only)."""
    driver = harness.load_driver(cell.traffic["driver"])
    readers = {m["name"]: harness.load_reader(m["name"])
               for m in cell.per_layer} if trace else {}
    ctx = Context(cell, seed, seconds, trace, **ctx_kw)
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        ctx.trace_dir = tdir
        out = driver.run(ctx)
        summary = None
        if trace:
            summary = tracing.summarize(tracing.load_events(
                tracing.find_trace(tdir)))
    device = dict(device, memory_peak_bytes=out.memory_peak_bytes)
    metrics = {}
    breakdown = None
    if trace:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = tracing.breakdown(summary)
        measured = types.SimpleNamespace(
            **out.measured, trace=summary, config=cell.config,
            traffic=cell.traffic,
            peaks=peaks or harness.peaks_for(device["kind"]))
        for m in cell.per_layer:
            v = readers[m["name"]].read(measured)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            if m["name"] not in out.end_to_end:
                raise harness.SpecError(f"driver gave no {m['name']!r}")
            metrics[m["name"]] = {"value": float(out.end_to_end[m["name"]]),
                                  "unit": m["unit"]}
    correct = (all(c.ok for c in out.checks) and out.failed == 0
               and out.attempted > 0)
    fields = {"correct": correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": metrics, "device": device,
              "breakdown": breakdown, "notes": out.notes}
    return fields, out.checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = harness.find_cell(harness.load_spec(), args.workload)
        _configure_jax()
        device = harness.device_check(cell.chips)
        fields, checks = execute(cell, args.seed, args.seconds,
                                 bool(args.trace), device)
    except harness.SpecError as e:
        harness.log(f"error: {e}")
        return 2
    except harness.NoChipError as e:
        harness.log(f"error: {e}")
        return 3
    harness.print_checks(checks)
    print(harness.result_line(checks=checks, **fields), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

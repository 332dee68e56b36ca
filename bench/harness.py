"""What every cell shares: the spec, discovery by name, the device, the
result line.

Nothing here imports the program (``repro``) or JAX at module level, so
the client processes and the CPU tests can import it freely.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
import threading
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

#: JAX's backend-compile event (a persistent-cache hit records none)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SpecError(ValueError):
    """A cell, configuration, traffic mix, driver or reader is missing
    or malformed."""


class NoChipError(RuntimeError):
    """JAX found no TPU, or not the number of chips the cell asks for."""


class UnknownDeviceError(KeyError):
    """``peaks.json`` has no entry for this ``device_kind``."""


@dataclasses.dataclass
class Cell:
    """One ``workloads`` entry of ``BENCHMARK.json`` with its files."""

    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def load_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def load_spec(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"BENCHMARK.json has no {what} named {name!r}")


def cell_metrics(spec: dict, cell: str) -> tuple[list, list]:
    """The end-to-end and per-layer metric entries this cell reports.

    An end-to-end metric with a ``workloads`` key belongs to the cells
    it lists, one without to every cell; a per-layer metric lists its
    cells under ``workloads`` always.
    """
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    for m in spec["per_layer"]:
        if "workloads" not in m:
            raise SpecError(f"per-layer metric {m['name']!r} lists no "
                            "workloads")
    per_layer = [m for m in spec["per_layer"] if cell in m["workloads"]]
    return e2e, per_layer


def find_cell(spec: dict, name: str, bench_dir: Path = BENCH_DIR) -> Cell:
    """Resolve a cell name to its entry, configuration and traffic mix."""
    w = _by_name(spec["workloads"], name, "workload")
    _by_name(spec["configs"], w["config"], "config")
    config = load_json(bench_dir / "configs" / f"{w['config']}.json")
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    if "driver" not in traffic:
        raise SpecError(f"traffic {w['traffic']!r} names no driver")
    e2e, per_layer = cell_metrics(spec, name)
    return Cell(name, w["config"], w["traffic"], int(w["chips"]), config,
                traffic, e2e, per_layer)


def _load_module(path: Path, what: str):
    if not path.is_file():
        raise SpecError(f"no {what} at {path}")
    mod_name = "bench_" + what + "_" + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_driver(name: str, bench_dir: Path = BENCH_DIR):
    """``drivers/<name>.py``: one general driver per kind of traffic."""
    return _load_module(bench_dir / "drivers" / f"{name}.py", "driver")


def load_reader(metric: str, bench_dir: Path = BENCH_DIR):
    """``metrics/<metric>.py``: the reader of one per-layer metric."""
    mod = _load_module(bench_dir / "metrics" / f"{metric}.py", "reader")
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"reader {metric!r} has no read(measured)")
    return mod


def peaks_for(kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    """The published peaks of one chip of this ``device_kind``."""
    table = load_json(bench_dir / "peaks.json")
    if kind not in table:
        raise UnknownDeviceError(
            f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# -- the device (copied from chip_smoke.py: device_check, CompileClock,
# -- device_bytes) -----------------------------------------------------------

def device_info() -> dict:
    """The device JAX reports."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_check(chips: int) -> dict:
    """The device JAX reports; raises NoChipError unless it is ``chips``
    TPU chips."""
    dev = device_info()
    if dev["platform"] != "tpu":
        raise NoChipError(f"no TPU: JAX's first device is "
                          f"{dev['platform']!r}; the benchmark runs on "
                          "the chip only")
    if dev["count"] != chips:
        raise NoChipError(f"the cell needs {chips} TPU chip(s), JAX sees "
                          f"{dev['count']}")
    return dev


class CompileClock:
    """Backend compilations, counted from JAX's own monitoring events
    over all threads (a persistent-cache hit is not one)."""

    def __init__(self):
        import jax
        self.events = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.events += 1


def device_bytes(stat: str):
    """Largest ``memory_stats()[stat]`` over the local devices (None
    where the backend keeps no memory stats)."""
    import jax
    vals = [(d.memory_stats() or {}).get(stat) for d in jax.local_devices()]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


# -- arithmetic --------------------------------------------------------------

def percentile(values, q: float) -> float:
    """The q-th percentile, linear between order statistics (numpy's
    default); NaN for no values."""
    import numpy as np
    v = np.asarray(values, np.float64)
    return float(np.percentile(v, q)) if v.size else math.nan


def exponential_gaps(count: int, total_s: float):
    """``count`` Poisson inter-arrival gaps as exponential quantiles,
    scaled to sum to ``total_s``. The same multiset for every seed."""
    import numpy as np
    u = (np.arange(count) + 0.5) / count
    g = -np.log1p(-u)
    return g * (total_s / g.sum())


def fit_may_start(elapsed_s: float, last_s: float, seconds: float) -> bool:
    """Whether another back-to-back fit starts: not once the elapsed
    time plus the last fit's duration would pass the window."""
    return elapsed_s + last_s <= seconds


# -- checks and the result line ----------------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit (the
    number passes at or below the limit)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit  # NaN fails


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: list,
                breakdown: dict | None = None,
                notes: dict | None = None) -> str:
    """The JSON result line; the compared numbers come last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if notes:
        out["notes"] = notes
    out["checks"] = {c.name: {"value": _json_number(c.value),
                              "limit": c.limit} for c in checks}
    return json.dumps(out, allow_nan=False)


def _json_number(v: float):
    """A compared number as JSON holds it: a non-finite reading (a label
    on no center) as its name, "inf" or "nan"."""
    return v if math.isfinite(v) else str(v)


def print_checks(checks: list) -> None:
    """The compared numbers as the last lines on standard error."""
    for c in checks:
        print(f"check {c.name} = {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def cache_dir() -> str:
    """JAX's persistent compilation cache: a fixed path in the checkout."""
    return os.path.join(ROOT, ".jax_cache")

"""Whole runs of the serving cell on the CPU: client processes, the
HTTP front end, the engine proxy, a sound run is correct and a run
whose answers are altered where they are produced is not; and the
command refuses to run without a chip."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import control
from bench.tests._cells import ROOT, run_small, small_cell


@pytest.mark.parametrize("cell,metric", [("sift1m.serve", "serve_p95_ms")])
def test_sound_serve_run_is_correct_and_its_control_is_not(cell, metric):
    seen = {}
    fields, checks, line = run_small(cell, keep=seen)
    assert fields["correct"], checks
    ctl = control.serve_control(small_cell(cell), 2**31 + 101,
                                seen["merged"])
    assert any(ctl[k] > checks[k].limit for k in ctl), ctl
    out = json.loads(line)
    assert set(out["metrics"]) == {"setup_s", metric}
    assert out["metrics"][metric]["value"] > 0
    assert out["attempted"] > 10 and out["failed"] == 0


@pytest.mark.parametrize("cell,names", [
    ("sift1m.serve", {"engine.wait_p95_ms", "frontend.overhead_p50_ms",
                      "engine.rows_per_batch.open"})])
def test_traced_serve_run_reads_the_host_layers(cell, names):
    fields, _, line = run_small(cell, trace=True)
    out = json.loads(line)
    assert fields["correct"]
    assert names <= set(out["metrics"])
    assert out["metrics"].get("frontend.overhead_p50_ms", {}).get(
        "value", 0) >= 0


def test_serve_run_with_an_altered_answer_is_not_correct(monkeypatch):
    from repro.serve import engine
    jax.clear_caches()
    real = engine._exact_step

    def altered_step(n_parts, donate):
        step = real(n_parts, donate)

        def run(model, *parts):
            lab, dst = step(model, *parts)
            return lab.at[0].set((lab[0] + 1) % 1000), dst
        return run
    monkeypatch.setattr(engine, "_exact_step", altered_step)
    fields, checks, _ = run_small("sift1m.serve")
    assert not fields["correct"]
    assert not checks["answer_ratio"].ok


def test_frontend_overhead_matches_requests_to_engine_records():
    from bench import serving
    pool = np.arange(40, dtype=np.float32).reshape(10, 4)
    engine = {"t_submit": np.array([1.0, 2.0]),
              "t_done": np.array([1.5, 2.1]), "rows": np.array([2, 1]),
              "head": pool[[3, 5], :4]}
    merged = {"start": np.array([3, 5, 3]), "size": np.array([2, 1, 2]),
              "sent": np.array([0.9, 1.95, 5.0]),
              "done": np.array([1.7, 2.2, 5.5]),
              "status": np.array([200, 200, 200])}
    got = serving.frontend_overhead_ms(pool, engine, merged)
    assert got == pytest.approx([300.0, 150.0])


@pytest.mark.parametrize("cell", ["sift1m.fit", "sift1m.serve"])
def test_command_refuses_without_a_chip(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", cell,
                        "--seed", "3000000000", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr

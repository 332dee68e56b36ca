"""The program's own spans, read by six per-layer metrics of the serving
cell: a traced run at a test size reports them all, an untraced run
records no span, and each reader gives ``None`` on an empty record."""
import json
import types

import pytest

from bench import harness
from bench.tests._cells import run_small
from repro.utils import spans

METRICS = ("engine.queue_p95_ms", "engine.flush_p95_ms",
           "engine.held_p95_ms", "engine.retire_p95_ms",
           "engine.loop_lag_max_ms", "frontend.self_p50_ms")
SPANS = ("geek.frontend.request", "geek.frontend.wait", "geek.engine.queue",
         "geek.engine.flush", "geek.engine.held", "geek.engine.retire",
         "geek.engine.lag")


@pytest.fixture(autouse=True)
def _empty_record():
    spans.clear()
    yield
    spans.clear()


def test_traced_serve_run_reports_the_program_spans():
    fields, _, line = run_small("sift1m.serve", trace=True)
    out = json.loads(line)["metrics"]
    assert fields["correct"]
    assert set(METRICS) <= set(out), sorted(out)
    assert all(out[m]["value"] >= 0 and out[m]["unit"] == "ms"
               for m in METRICS)
    assert out["engine.queue_p95_ms"]["value"] \
        <= out["engine.wait_p95_ms"]["value"]


def test_untraced_serve_run_records_no_span():
    fields, _, _ = run_small("sift1m.serve")
    assert fields["correct"]
    assert all(spans.records(s)["start"].size == 0 for s in SPANS)


@pytest.mark.parametrize("metric", METRICS)
def test_reader_gives_none_on_an_empty_record(metric):
    m = types.SimpleNamespace(trace=None, counters=None,
                              trace_counters=None)
    assert harness.load_reader(metric).read(m) is None

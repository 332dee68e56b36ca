"""The span recorder (``repro.utils.spans``) and the spans of the served
path (DESIGN.md §13, §15).

The contracts under test:

- **Switch.** Spans are recorded exactly while a profiler session is
  live; with none, a span site records nothing and the served path reads
  no ``time.time_ns`` clock at all.
- **Ids and ring.** Spans carry their request and micro-batch ids; a
  nested span inherits its request; the ring drops its oldest spans past
  capacity and counts them.
- **Clock.** A span's start is the profiler's host event of the same
  name, at ``profile_start_time`` plus its offset.
- **Served path.** Through the HTTP front end, every request has one
  front-end span and one queue span, every micro-batch one flush, held
  and retire span, joined by ids and in causal order.
- **Names in the programs.** The fit's stage scopes and the serve
  step's name reach the HLO.
"""
import glob
import re
import sys
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest
from jax._src.lib import _profiler

from repro.core import api
from repro.core.api import GEEK, DenseData
from repro.core.geek import GeekConfig
from repro.serve import ClusterFrontend, ClusterServer
from repro.serve import engine as engine_mod
from repro.utils import spans

CFG = GeekConfig(m=8, t=16, silk_l=3, delta=3, k_max=32, pair_cap=4096)


@pytest.fixture(autouse=True)
def _empty_record():
    spans.clear()
    yield
    spans.clear()


@pytest.fixture(scope="module")
def fitted():
    from repro.data import synthetic
    d = synthetic.dense_blobs(jax.random.PRNGKey(0), n=600, d=16, k=8)
    model = GEEK(CFG).fit(DenseData(d.x), jax.random.PRNGKey(1))
    return jax.block_until_ready(model), np.asarray(d.x)


def _count(name):
    return spans.records(name)["start"].size


def _wait_for(pred, timeout=10.0):
    """Spans close just after the response that they time: wait for
    the last ones."""
    end = time.monotonic() + timeout
    while not pred() and time.monotonic() < end:
        time.sleep(0.005)
    assert pred()


def _post(url, rows):
    req = urllib.request.Request(
        url + "/v1/assign", data=np.ascontiguousarray(rows, "<f4").tobytes(),
        headers={"Content-Type": "application/octet-stream"})
    with urllib.request.urlopen(req, timeout=60) as r:
        assert r.status == 200
        r.read()


def _post_all(url, x, n, threads=4):
    """n single-row requests from ``threads`` concurrent callers."""
    def worker(i):
        for j in range(i, n, threads):
            _post(url, x[j:j + 1])
    ts = [threading.Thread(target=worker, args=(i,)) for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in ts)


# -- the recorder -------------------------------------------------------------

def test_switch_is_the_profiler_session(tmp_path):
    assert spans.enabled is _profiler.TraceMe.is_enabled
    assert not spans.enabled()
    with jax.profiler.trace(str(tmp_path)):
        assert spans.enabled()
    assert not spans.enabled()


def test_no_session_records_nothing():
    with spans.span("geek.test.off", req=spans.NEW, batch=3) as s:
        assert spans.current_req() == -1
    assert s is spans._OFF
    assert _count("geek.test.off") == 0
    assert spans.dropped() == 0


def test_session_records_spans_with_their_ids(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with spans.span("geek.test.outer", req=spans.NEW) as outer:
            assert spans.current_req() == outer.req >= 0
            with spans.span("geek.test.inner", batch=7):
                pass
        assert spans.current_req() == -1
        with spans.span("geek.test.outer", req=41):
            pass
    with spans.span("geek.test.outer", req=42):
        pass   # after the session: not recorded
    out = spans.records("geek.test.outer")
    inner = spans.records("geek.test.inner")
    assert out["req"].tolist() == [outer.req, 41]
    assert out["batch"].tolist() == [-1, -1]
    assert inner["req"].tolist() == [outer.req]
    assert inner["batch"].tolist() == [7]
    assert (out["end"] >= out["start"]).all()
    assert out["start"][0] <= inner["start"][0] <= inner["end"][0] \
        <= out["end"][0]
    assert spans.records("geek.test.none")["start"].size == 0


def test_ring_drops_the_oldest_spans_and_counts_them():
    spans.clear(capacity=4)
    for i in range(6):
        spans.interval("geek.test.a" if i % 2 else "geek.test.b",
                       100 * i, 100 * i + 10, req=i, batch=-1)
    assert spans.dropped() == 2
    assert spans.records("geek.test.a")["req"].tolist() == [3, 5]
    assert spans.records("geek.test.b")["req"].tolist() == [2, 4]
    assert spans.records("geek.test.b")["start"].tolist() == [200, 400]
    spans.clear()
    assert spans.dropped() == 0 and _count("geek.test.a") == 0


def test_spans_are_on_the_profilers_clock(tmp_path):
    """Each span's start is the xplane host event of the same name at
    ``profile_start_time + start_ns``, within 0.5 ms."""
    with jax.profiler.trace(str(tmp_path)):
        for i in range(5):
            with spans.span("geek.test.clock", req=i):
                time.sleep(0.002)
            time.sleep(0.001)
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path[0])
    t0 = None
    host = []
    for plane in pd.planes:
        if plane.name == "Task Environment":
            t0 = dict(plane.stats)["profile_start_time"]
        elif plane.name == "/host:CPU":
            host.extend(t for line in plane.lines for e in line.events
                        if e.name == "geek.test.clock"
                        for t in [e.start_ns])
    got = spans.records("geek.test.clock")["start"]
    assert t0 is not None and len(host) == got.size == 5
    assert np.abs(np.sort(t0 + np.asarray(host)) - np.sort(got)).max() \
        < 0.5e6


# -- the served path ----------------------------------------------------------

def test_served_path_spans_join_requests_to_batches(fitted, tmp_path):
    model, x = fitted
    n = 40
    with ClusterServer(model, max_batch=64, deadline_ms=2.0,
                       min_bucket=16) as server, \
            ClusterFrontend(server) as fe:
        server.warmup(x[:4])
        _post(fe.url, x[:1])
        assert _count("geek.frontend.request") == 0   # no session yet
        with jax.profiler.trace(str(tmp_path)):
            s0 = server.stats()
            _post_all(fe.url, x, n)
            _wait_for(lambda: server.stats()["completed"]
                      - s0["completed"] == n)
            batches = server.stats()["batches"] - s0["batches"]
            _wait_for(lambda: _count("geek.engine.retire") == batches
                      and _count("geek.frontend.request") == n)
    req = spans.records("geek.frontend.request")
    wait = spans.records("geek.frontend.wait")
    queue = spans.records("geek.engine.queue")
    flush = spans.records("geek.engine.flush")
    held = spans.records("geek.engine.held")
    retire = spans.records("geek.engine.retire")
    assert req["start"].size == wait["start"].size == n
    assert queue["start"].size == n
    assert flush["start"].size == held["start"].size == batches
    assert retire["start"].size == batches
    # one id a request, carried from the front end into the engine
    assert len(set(req["req"].tolist())) == n
    assert set(wait["req"].tolist()) == set(req["req"].tolist())
    assert set(queue["req"].tolist()) == set(req["req"].tolist())
    # every queue span names a flushed micro-batch; each batch's spans
    # share its id
    ids = flush["batch"].tolist()
    assert len(set(ids)) == batches and set(queue["batch"].tolist()) <= \
        set(ids)
    assert sorted(held["batch"].tolist()) == sorted(ids) \
        == sorted(retire["batch"].tolist())
    # each request's spans in causal order: submit inside the front-end
    # span, then the queue, its batch's flush, held and retire, the
    # future resolved inside the retire, then the response
    at = lambda r, key: {k: (int(s), int(e)) for k, s, e in
                         zip(r[key], r["start"], r["end"])}
    f_of, h_of, r_of = (at(flush, "batch"), at(held, "batch"),
                        at(retire, "batch"))
    req_of, w_of = at(req, "req"), at(wait, "req")
    for r, b, qs, qe in zip(queue["req"], queue["batch"], queue["start"],
                            queue["end"]):
        chain = [req_of[r][0], qs, qe, *f_of[b], *h_of[b], r_of[b][0],
                 w_of[r][1], req_of[r][1]]
        assert chain == sorted(chain), chain
    for r in (req, wait, queue, flush, held, retire):
        assert (r["end"] >= r["start"]).all()


def test_timed_waits_record_the_worker_lag(fitted, tmp_path):
    """Each lone request waits out the deadline: a timed wait each."""
    model, x = fitted
    with ClusterServer(model, max_batch=64, deadline_ms=20.0,
                       min_bucket=16) as server:
        server.warmup(x[:4])
        with jax.profiler.trace(str(tmp_path)):
            for i in range(3):
                server.submit(x[i:i + 1]).result(timeout=60)
    lag = spans.records("geek.engine.lag")
    assert lag["start"].size >= 3
    assert (lag["end"] >= lag["start"]).all()
    assert (lag["req"] == -1).all() and (lag["batch"] == -1).all()
    # direct callers get an id from submit
    q = spans.records("geek.engine.queue")
    assert q["start"].size == 3 and (q["req"] >= 0).all()


def test_served_path_reads_no_clock_without_a_session(fitted, monkeypatch):
    """No profiler session: nothing recorded, and no ``time.time_ns``
    read by the program's code, on any thread."""
    model, x = fitted
    calls = []
    real = time.time_ns

    def counting():
        f = sys._getframe(1)
        if "repro" in f.f_code.co_filename:
            calls.append(f.f_code.co_name)
        return real()
    with ClusterServer(model, max_batch=64, deadline_ms=2.0,
                       min_bucket=16) as server, \
            ClusterFrontend(server) as fe:
        server.warmup(x[:4])
        monkeypatch.setattr(time, "time_ns", counting)
        _post_all(fe.url, x, 12)
        server.submit(x[:3]).result(timeout=60)
        monkeypatch.undo()
    assert calls == []
    for name in ("geek.frontend.request", "geek.frontend.wait",
                 "geek.engine.queue", "geek.engine.flush",
                 "geek.engine.held", "geek.engine.retire",
                 "geek.engine.lag"):
        assert _count(name) == 0, name


# -- names in the programs ----------------------------------------------------

def test_fit_program_carries_the_stage_scopes():
    from repro.data import synthetic
    s = synthetic.url_like(jax.random.PRNGKey(0), n=200, k=4)
    low = api._fit_incore.lower(
        (s.sets, s.mask), jax.random.PRNGKey(1), cfg=CFG, kind="sparse",
        none_pattern=(False, False), bucketer=api.LSHBucketer(),
        seeder=api.SILKSeeder(), assigner=api.KernelAssigner())
    hlo = low.as_text(dialect="hlo", debug_info=True)
    scopes = set(re.findall(r'op_name="[^"]*?(geek\.\w+)', hlo))
    assert scopes == {"geek.transform", "geek.bucket", "geek.silk",
                      "geek.centers", "geek.assign"}


@pytest.mark.parametrize("probed", [False, True])
def test_serve_steps_have_stable_names_and_scope(fitted, probed):
    model, x = fitted
    step = (engine_mod._probed_step(1, 2) if probed
            else engine_mod._exact_step(1, False))
    if probed:
        from repro.core.model import build_model
        model = build_model(model.centers, model.center_valid, model.k_star,
                            model.radius, metric=model.metric,
                            index_tables=4, index_bucket=8)
    hlo = step.lower(model, x[:16]).as_text(dialect="hlo", debug_info=True)
    name = "jit_serve_probed" if probed else "jit_serve_exact"
    assert hlo.startswith(f"HloModule {name},")
    assert "geek.serve/" in hlo

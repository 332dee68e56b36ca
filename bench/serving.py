"""The serving driver's parts: the served model, the server behind its
HTTP front end, the engine proxy, and the client processes.

The served path is ``ClusterFrontend`` -> ``EngineProxy`` ->
``ClusterServer``: the proxy forwards every call to the server and
times each ``submit`` until its future resolves (the engine's time for
that request), inside ``bench.submit`` and ``bench.engine_wait`` host
spans that a traced run records.
"""
from __future__ import annotations

import functools
import json
import subprocess
import sys
import threading
import time

import numpy as np

from bench import client, data, harness
from bench.reference import l2


class _WaitTraced:
    """The engine's future, whose ``result`` wait is a host span."""

    def __init__(self, fut):
        self._fut = fut

    def result(self, timeout=None):
        import jax
        with jax.profiler.TraceAnnotation("bench.engine_wait"):
            return self._fut.result(timeout=timeout)


class EngineProxy:
    """Stands for the ``ClusterServer`` behind the front end; records,
    per request, the submit time, the time its future resolved, its
    rows and its first row's first four values (to match it with the
    client's record). The records live in numpy arrays, so that they
    add no objects for the server process's garbage collector to walk.
    """

    def __init__(self, server, capacity: int = 1 << 18):
        self._server = server
        self._lock = threading.Lock()
        self._capacity = capacity
        self.reset()

    def __getattr__(self, name):
        return getattr(self._server, name)

    def reset(self) -> None:
        """Forget what was recorded (the warm-up's requests)."""
        with self._lock:
            self.count = 0
            self.t_submit = np.zeros(self._capacity)
            self.t_done = np.full(self._capacity, np.nan)
            self.rows = np.zeros(self._capacity, np.int64)
            self.head = np.zeros((self._capacity, 4), np.float32)

    def _slot(self) -> int:
        """The next record's index; -1 once the arrays are full (later
        requests then go unrecorded)."""
        with self._lock:
            if self.count == self._capacity:
                return -1
            self.count += 1
            return self.count - 1

    def _done(self, i: int, _fut) -> None:
        self.t_done[i] = time.monotonic()

    def submit(self, parts):
        import jax
        rows = parts[0] if isinstance(parts, (tuple, list)) else parts
        i = self._slot()
        if i >= 0:
            self.t_submit[i] = time.monotonic()
            self.rows[i] = rows.shape[0]
            self.head[i] = rows[0, :4]
        with jax.profiler.TraceAnnotation("bench.submit"):
            fut = self._server.submit(parts)
        if i >= 0:
            fut.add_done_callback(functools.partial(self._done, i))
        return _WaitTraced(fut)

    def records(self) -> dict:
        n = self.count
        return {"t_submit": self.t_submit[:n], "t_done": self.t_done[:n],
                "rows": self.rows[:n], "head": self.head[:n]}


def served_model(config: dict, centers):
    """The model a server that restored a fitted model holds, built with
    ``build_model`` from the generated centers in ``k_max`` slots."""
    import jax.numpy as jnp

    from repro.core.model import build_model
    s = config["serve_model"]
    k, d = centers.shape
    k_max = int(s["k_max"])
    padded = jnp.zeros((k_max, d), jnp.float32).at[:k].set(centers)
    valid = jnp.arange(k_max) < k
    model = build_model(padded, valid, jnp.int32(k),
                        jnp.zeros((k_max,), jnp.float32), metric=s["metric"],
                        assign_block=int(s["assign_block"]),
                        use_pallas=bool(s["use_pallas"]),
                        index_tables=int(s["index_tables"]),
                        index_bucket=int(s["index_bucket"]))
    return model, padded, valid


def open_schedule(traffic: dict, seconds: float, pool_rows: int, seed: int):
    """Open-loop jobs: Poisson arrivals at ``rate_rps``, each request
    ``rows_per_request`` rows. The set of gaps and sizes is fixed by the
    rate and the window; the seed orders the gaps, and orders the query
    pool, whose rows the requests start at in turn, as a query set is
    sent through once before any query comes again."""
    rng = np.random.default_rng(seed)
    count = max(1, int(round(traffic["rate_rps"] * seconds)))
    gaps = rng.permutation(harness.exponential_gaps(count, seconds))
    due = np.cumsum(gaps) - gaps[0]
    size = np.full(count, int(traffic["rows_per_request"]))
    start = np.minimum(np.resize(rng.permutation(pool_rows), count),
                       pool_rows - size)
    return {"due": due, "size": size, "start": start}


class Clients:
    """The client processes; started, released at ``t0``, collected."""

    def __init__(self, url: tuple, pool: np.ndarray, jobs_per_proc: list,
                 threads: int, timeout_s: float):
        self.procs = []
        host, port = url
        try:
            # all start (and import) at once; then each is fed in turn
            for _ in jobs_per_proc:
                self.procs.append(subprocess.Popen(
                    [sys.executable, str(harness.BENCH_DIR / "client.py")],
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                    cwd=str(harness.ROOT)))
            hdr = (json.dumps({"host": host, "port": port,
                               "threads": threads, "d": int(pool.shape[1]),
                               "pool_rows": int(pool.shape[0]),
                               "timeout_s": timeout_s}) + "\n").encode()
            raw = np.ascontiguousarray(pool, "<f4").tobytes()
            for p, jobs in zip(self.procs, jobs_per_proc):
                p.stdin.write(hdr)
                p.stdin.write(raw)
                client.write_blob(p.stdin, jobs)
            for p in self.procs:
                if p.stdout.readline().strip() != b"ready":
                    raise RuntimeError("a client process failed to start")
        except BaseException:
            self.stop()
            raise

    def go(self, t0: float) -> None:
        for p in self.procs:
            p.stdin.write(f"go {t0!r}\n".encode())
            p.stdin.flush()

    def collect(self, timeout_s: float) -> list:
        out = []
        deadline = time.monotonic() + timeout_s
        try:
            for p in self.procs:
                timer = threading.Timer(max(1.0, deadline - time.monotonic()),
                                        p.kill)
                timer.start()
                try:
                    out.append(client.read_blob(p.stdout))
                finally:
                    timer.cancel()
                p.wait(timeout=max(1.0, deadline - time.monotonic()))
        finally:
            self.stop()
        return out

    def stop(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()
            p.wait()
            for f in (p.stdin, p.stdout):
                if f is not None:
                    f.close()


def merge(results: list) -> dict:
    """The clients' records, concatenated; answer pairs merged."""
    keys = ("due", "sent", "done", "size", "start", "status")
    out = {k: np.concatenate([r[k] for r in results]) for k in keys}
    key = np.concatenate([r["pair_key"] for r in results])
    dmin = np.concatenate([r["pair_dmin"] for r in results])
    dmax = np.concatenate([r["pair_dmax"] for r in results])
    order = np.argsort(key, kind="stable")
    key, dmin, dmax = key[order], dmin[order], dmax[order]
    first = np.ones(key.size, bool)
    first[1:] = key[1:] != key[:-1]
    idx = np.flatnonzero(first)
    if idx.size:
        out.update(pair_key=key[idx],
                   pair_dmin=np.minimum.reduceat(dmin, idx),
                   pair_dmax=np.maximum.reduceat(dmax, idx))
    else:
        out.update(pair_key=key, pair_dmin=dmin, pair_dmax=dmax)
    return out


def answer_readings(pool, centers, valid, merged: dict) -> dict:
    """Every answer of the window against the reference, as one number:
    the worst of the label and distance readings (``reference/l2.py``)
    over the distinct (row, label) pairs, for the least and the largest
    distance answered. One number, because the served rows lie far from
    any cluster boundary: a lower precision moves distances, not labels,
    so only the pair of readings together separates the program from
    its control while still catching a wrong label."""
    if merged["pair_key"].size == 0:
        return {"answer_ratio": float("inf")}
    rows = merged["pair_key"] // client.LABELS
    labels = (merged["pair_key"] % client.LABELS).astype(np.int32)
    x = pool[rows]
    worst = 0.0
    for d in (merged["pair_dmin"], merged["pair_dmax"]):
        r = l2.readings(x, labels, d, centers, valid)
        worst = max(worst, r["label_ratio"], r["dist_ratio"])
    return {"answer_ratio": worst}


def frontend_overhead_ms(pool, engine: dict, merged: dict) -> np.ndarray:
    """Per request matched to its engine record (same first row and
    rows, submitted while the client waited): the client's send-to-
    receive time less the engine's submit-to-resolve time, in ms."""
    first = {pool[i, :4].tobytes(): i for i in range(pool.shape[0])}
    groups: dict = {}
    for ta, tb, n, head in zip(engine["t_submit"], engine["t_done"],
                               engine["rows"], engine["head"]):
        groups.setdefault((first.get(head.tobytes(), -1), int(n)),
                          []).append((ta, tb))
    out = []
    for s, n, ts, td, st in zip(merged["start"], merged["size"],
                                merged["sent"], merged["done"],
                                merged["status"]):
        if st != 200:
            continue
        for ta, tb in groups.get((int(s), int(n)), ()):
            if ts <= ta <= td and tb == tb:
                out.append(((td - ts) - (tb - ta)) * 1e3)
                break
    return np.asarray(out)


def counter_delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in ("batches", "rows_served", "padded_rows",
                                     "submitted", "completed", "failed")}


def serve(ctx):
    """Run one open-loop serving window; returns the driver's outcome."""
    import jax

    from repro.serve import ClusterFrontend, ClusterServer
    config, traffic = ctx.cell.config, ctx.cell.traffic
    engine_cfg = config["serve_model"]
    t_enter = time.monotonic()
    key = data.seed_key(ctx.seed)
    pool_rows = int(config["query_rows"])
    made = data.make(config, jax.random.fold_in(key, 0), n=pool_rows)
    pool = np.asarray(made["parts"][0], np.float32)
    model, centers, valid = served_model(config, made["centers"])
    server = ClusterServer(model, max_batch=int(engine_cfg["max_batch"]),
                           deadline_ms=float(engine_cfg["deadline_ms"]),
                           probes=None)
    proxy = EngineProxy(server)
    fe = ClusterFrontend(proxy, default_deadline_s=float(
        engine_cfg["request_timeout_s"])).start()
    clients = None
    try:
        t_model = time.monotonic()
        server.warmup(pool[:64])
        t_ladder = time.monotonic()
        warm = client.Conn(*fe.address, pool, 60.0)
        for n in sorted({1, int(traffic["rows_per_request"])}):
            if warm.assign(0, n)[0] != 200:
                raise RuntimeError("warm-up request failed")
        warm.conn.close()
        procs, threads = int(traffic["processes"]), int(traffic["threads"])
        jobs = open_schedule(traffic, ctx.seconds, pool_rows, ctx.seed)
        per = [{k: v[i::procs] for k, v in jobs.items()}
               for i in range(procs)]
        t_http = time.monotonic()
        clients = Clients(fe.address, pool, per, threads,
                          float(engine_cfg["request_timeout_s"]))
        proxy.reset()
        compiles0 = ctx.clock.events
        t0 = time.monotonic() + 0.1
        clients.go(t0)
        _sleep_until(t0)
        setup_s = t0 - ctx.t_start_mono
        harness.log(
            f"serve set-up: {setup_s:.6f} s: start to driver "
            f"{t_enter - ctx.t_start_mono:.6f}, data and model "
            f"{t_model - t_enter:.6f}, ladder warm-up "
            f"{t_ladder - t_model:.6f}, HTTP warm-up "
            f"{t_http - t_ladder:.6f}, clients {t0 - t_http:.6f}")
        s0 = server.stats()
        t_end = t0 + ctx.seconds
        trace_counts = None
        if ctx.trace:
            lo = t0 + float(traffic["trace_offset_s"])
            _sleep_until(lo)
            with ctx.window():
                a = server.stats()
                _sleep_until(min(t_end, lo + float(traffic["trace_s"])))
                b = server.stats()
            trace_counts = counter_delta(a, b)
        _sleep_until(t_end)
        s1 = server.stats()
        results = clients.collect(ctx.seconds + 90.0)
        peak = harness.device_bytes("peak_bytes_in_use") or 0
        compiles = ctx.clock.events - compiles0
    finally:
        fe.close()
        server.close()
        if clients is not None:
            clients.stop()
    merged = merge(results)
    if ctx.keep is not None:
        ctx.keep["merged"] = merged
    engine = proxy.records()
    ok = merged["status"] == 200
    attempted = int(jobs["due"].size)
    failed = attempted - int(ok.sum())
    window = counter_delta(s0, s1)
    lat_ms = (merged["done"][ok] - merged["due"][ok]) * 1e3
    late = (merged["sent"] - merged["due"]) * 1e3
    e2e = {"setup_s": setup_s,
           "serve_p95_ms": harness.percentile(lat_ms, 95)}
    harness.log(
        f"serve open: {attempted} requests, {int(ok.sum())} answered; "
        f"latency p50 {harness.percentile(lat_ms, 50)!r} ms, "
        f"p95 {e2e['serve_p95_ms']!r} ms, p99 "
        f"{harness.percentile(lat_ms, 99)!r} ms; generator late p50 "
        f"{harness.percentile(late[ok], 50)!r} ms, p99 "
        f"{harness.percentile(late[ok], 99)!r} ms, max "
        f"{float(np.nanmax(late)) if late.size else 0.0!r} ms")
    harness.log(f"serve: engine counters over the window {window}; "
                f"compiles in window {compiles}")
    worst = answer_readings(pool, centers, valid, merged)
    limits = config["limits"]["serve"]
    checks = [harness.Check(k, float(worst[k]), float(limits[k]))
              for k in limits]
    done = ~np.isnan(engine["t_done"])
    wait_ms = (engine["t_done"][done] - engine["t_submit"][done]) * 1e3
    measured = {"counters": window, "trace_counters": trace_counts,
                "engine_wait_ms": wait_ms,
                "frontend_overhead_ms": (frontend_overhead_ms(
                    pool, engine, merged) if ctx.trace else np.zeros(0)),
                "k": int(np.asarray(valid).sum()), "d": int(pool.shape[1])}
    return ctx.outcome(attempted=attempted, failed=failed,
                       end_to_end=e2e, checks=checks,
                       memory_peak_bytes=peak, measured=measured,
                       notes={"compiles_in_window": compiles,
                              "answered": int(ok.sum())})


def _sleep_until(t: float) -> None:
    wait = t - time.monotonic()
    if wait > 0:
        time.sleep(wait)

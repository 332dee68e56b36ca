#!/usr/bin/env python3
"""Chip smoke check: GEEK's fit-and-serve path on TPU, through the entry
points a user calls.

    python chip_smoke.py             # one chip: device, dense, sparse phases
    python chip_smoke.py --chips 4   # four chips: the sharded path only

One process, no children. Each step prints one line (wall and compile
seconds, k*, agreement, device bytes in use after the step and the
running peak since start); any failure exits non-zero
with no result line. The last line on success is exactly
``{"ok": true, "device": {...}}``.

Phases (one chip):

- **device**: ``jax.devices()[0].platform`` must be ``"tpu"``; there is
  no CPU path (``NoTPUError``).
- **dense**: SIFT1M's shape (TEXMEX ANN_SIFT1M, the paper's Sift set):
  n=1,000,000 f32 rows of d=128 from ``data.synthetic.sift_like`` with
  1,000 true clusters. ``GEEK(GeekConfig()).fit``, then 8 requests of
  4,096 rows through ``repro.serve.ClusterServer`` (the last one as a
  ``POST /v1/assign`` through ``ClusterFrontend`` on loopback), checked
  against a blocked exact f32 argmin at ``precision=HIGHEST``
  (``l2_near_tie_check``). The same rows are then predicted with
  ``use_pallas=True`` (the compiled ``distance_argmin_l2``) and held to
  the same check.
- **sparse**: the paper's URL corpus shape: a 3.2M-item universe,
  32 items per row, n=1,048,576 rows from ``url_like`` with 1,000 true
  clusters. Fit and serve as above with ``hamming_impl="packed"`` and
  ``use_pallas=True``; served labels must equal the equality path
  (``assign_hamming``) exactly.

``--chips 4``: the sharded fit ``GEEK.fit(..., mesh=make_mesh())`` at
the dense shape against the single-device fit in the same process
(labels, k* and centers bit-identical), ``make_predict_sharded``
against ``predict``, and ``WorkerPool(workers=4)`` against ``predict``
(labels identical).

JAX's persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``<checkout>/.jax_cache`` (``utils.platform``).
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.utils.platform import enable_compile_cache  # noqa: E402

# Full sizes. A cut forced by compile or run time is printed as its own
# line and listed in CHANGES.md; widths (d, universe, items per row) are
# never cut.
N_DENSE = 1_000_000
N_SPARSE = 1 << 20
TRUE_K = 1000
REQUESTS, REQUEST_ROWS = 8, 4096
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoTPUError(RuntimeError):
    """JAX found no TPU (or not the requested number of chips)."""


class CheckFailed(RuntimeError):
    """A phase's output broke its correctness contract."""


class CompileClock:
    """Seconds the XLA backend spends compiling, from JAX's own
    monitoring events (a persistent-cache hit records no compile),
    summed over threads: serving workers compile concurrently, so a
    step's compile seconds can exceed its wall seconds. Tracing and
    lowering are not counted: their events nest."""

    def __init__(self):
        import jax
        self.total = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.total += secs


def device_bytes(stat: str):
    """Largest ``memory_stats()[stat]`` over the local devices (None
    where the backend keeps no memory stats)."""
    import jax
    vals = [(d.memory_stats() or {}).get(stat) for d in jax.local_devices()]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


@contextlib.contextmanager
def step(name: str, clock: CompileClock):
    """Time one step; print its line (plus the fields the body filled)."""
    info: dict = {}
    c0, t0 = clock.total, time.perf_counter()
    try:
        yield info
    except Exception as e:
        print(f"[{name}] FAILED {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        raise
    fields = {"wall_s": round(time.perf_counter() - t0, 3),
              "compile_s": round(clock.total - c0, 3),
              **info, "bytes_in_use": device_bytes("bytes_in_use"),
              # the device's high-water mark since the process started,
              # not this step's own peak
              "running_peak_bytes": device_bytes("peak_bytes_in_use")}
    print(f"[{name}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def serve_requests(model, parts: tuple) -> tuple:
    """Serve ``REQUESTS`` requests of ``REQUEST_ROWS`` rows of ``parts``:
    all but the last via ``ClusterServer.submit``, the last as a JSON
    ``POST /v1/assign`` through ``ClusterFrontend`` on loopback. Every
    future is resolved; returns (labels, dists) in row order."""
    import numpy as np

    from repro.serve import ClusterFrontend, ClusterServer
    r = REQUEST_ROWS
    chunks = [tuple(p[i * r:(i + 1) * r] for p in parts)
              for i in range(REQUESTS)]
    with ClusterServer(model, max_batch=r) as server, \
            ClusterFrontend(server, default_deadline_s=900.0) as fe:
        futs = [server.submit(c) for c in chunks[:-1]]
        body = json.dumps({"parts": [p.tolist() for p in chunks[-1]]})
        req = urllib.request.Request(
            fe.url + "/v1/assign", data=body.encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=900) as resp:
            http = json.loads(resp.read())
        got = [f.result(timeout=900) for f in futs]
    labels = np.concatenate([g.labels for g in got]
                            + [np.asarray(http["labels"], np.int32)])
    dists = np.concatenate([g.dists for g in got]
                           + [np.asarray(http["dists"], np.float32)])
    return labels, dists


def l2_near_tie_check(x, labels, centers, center_valid, *, block=4096):
    """Hold served L2 labels to a blocked exact f32 argmin.

    The served path computes d2 = |x|^2 - 2 x.c + |c|^2 with x.c at the
    TPU's default matmul precision, which rounds both operands to
    bfloat16 (8-bit significand, unit roundoff 2^-9). Each product x_i c_i
    is then off by at most 2^-8 of |x_i c_i|, so the served d2 is off by
    at most B(x, c) = 2^-7 sum_i |x_i||c_i| + 2^-20 (|x|^2 + |c|^2); the
    second term covers f32 accumulation and the three-term sum (a few
    f32 ulps of those magnitudes, taken 16x over) in both the served
    path and this reference. A served label s can differ from the exact
    label e only when the exact d2(s) - d2(e) <= B(x, c_s) + B(x, c_e):
    a near tie. Any other difference is a wrong answer.

    Returns (agreement, near_ties, violations) over the rows.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    c = jnp.asarray(centers, jnp.float32)
    valid = jnp.asarray(center_valid)
    csq = jnp.sum(c * c, axis=-1)
    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def one(xb, lb):
        d2 = (jnp.sum(xb * xb, -1, keepdims=True)
              - 2.0 * jnp.dot(xb, c.T, precision=hi) + csq[None, :])
        d2 = jnp.where(valid[None, :], d2, jnp.inf)
        best = jnp.argmin(d2, axis=-1)
        gap = (jnp.take_along_axis(d2, lb[:, None], 1)[:, 0]
               - jnp.min(d2, axis=-1))
        ax, xsq = jnp.abs(xb), jnp.sum(xb * xb, -1)

        def bound(j):
            return (2.0 ** -7 * jnp.sum(ax * jnp.abs(c[j]), -1)
                    + 2.0 ** -20 * (xsq + csq[j]))
        return best, gap, bound(lb) + bound(best)

    x = np.asarray(x, np.float32)
    labels = np.asarray(labels, np.int32)
    same = ties = bad = 0
    for off in range(0, x.shape[0], block):
        best, gap, tol = (np.asarray(a) for a in
                          one(x[off:off + block], labels[off:off + block]))
        lb = labels[off:off + block]
        diff = lb != best
        same += int((~diff).sum())
        ties += int((diff & (gap <= tol)).sum())
        bad += int((diff & ~(gap <= tol)).sum())
    return same / x.shape[0], ties, bad


def _require_l2(name: str, agreement: float, ties: int, bad: int) -> None:
    if bad:
        raise CheckFailed(f"{name}: {bad} rows differ from the exact f32 "
                          f"argmin by more than the near-tie bound "
                          f"(agreement {agreement:.6f}, near ties {ties})")


def dense_phase(n: int, seed: int, clock: CompileClock) -> None:
    """Fit at the SIFT1M shape, serve, and check XLA and Pallas labels."""
    import jax
    import numpy as np

    from repro import GEEK, DenseData, GeekConfig
    from repro.data.synthetic import sift_like

    key = jax.random.PRNGKey(seed)
    with step("dense fit", clock) as info:
        x = jax.block_until_ready(
            sift_like(jax.random.fold_in(key, 0), n=n, k=TRUE_K).x)
        est = GEEK(GeekConfig())
        model = est.fit(DenseData(x), jax.random.fold_in(key, 1))
        jax.block_until_ready(est.result_.labels)
        info.update(n=n, d=x.shape[1], k_star=int(model.k_star),
                    overflow=int(est.result_.overflow))
    rows = np.asarray(x[:REQUESTS * REQUEST_ROWS])
    with step("dense serve", clock) as info:
        labels, _ = serve_requests(model, (rows,))
        agreement, ties, bad = l2_near_tie_check(
            rows, labels, model.centers, model.center_valid)
        info.update(rows=rows.shape[0], agreement=agreement, near_ties=ties)
        _require_l2("dense serve", agreement, ties, bad)
    with step("dense pallas", clock) as info:
        pallas = dataclasses.replace(model, use_pallas=True)
        plabels, _ = est.predict(DenseData(rows), model=pallas)
        agreement, ties, bad = l2_near_tie_check(
            rows, plabels, model.centers, model.center_valid)
        info.update(rows=rows.shape[0], agreement=agreement, near_ties=ties,
                    same_as_served=float((np.asarray(plabels)
                                          == labels).mean()))
        _require_l2("dense pallas", agreement, ties, bad)


def sparse_phase(n: int, seed: int, clock: CompileClock) -> None:
    """Fit and serve the URL shape on the packed Pallas kernel; labels
    must equal the equality path exactly."""
    import jax
    import numpy as np

    from repro import GEEK, GeekConfig, SparseData
    from repro.core.assign import assign_hamming
    from repro.data.synthetic import url_like

    key = jax.random.PRNGKey(seed)
    with step("sparse fit", clock) as info:
        data = url_like(jax.random.fold_in(key, 2), n=n, k=TRUE_K)
        sets, mask = jax.block_until_ready((data.sets, data.mask))
        est = GEEK(GeekConfig(hamming_impl="packed", use_pallas=True))
        model = est.fit(SparseData(sets, mask), jax.random.fold_in(key, 3))
        jax.block_until_ready(est.result_.labels)
        info.update(n=n, items_per_row=sets.shape[1],
                    impl=model.impl, code_bits=model.code_bits,
                    k_star=int(model.k_star),
                    overflow=int(est.result_.overflow))
    m = REQUESTS * REQUEST_ROWS
    parts = (np.asarray(sets[:m]), np.asarray(mask[:m]))
    with step("sparse serve", clock) as info:
        labels, dists = serve_requests(model, parts)
        codes = jax.jit(model.encode)(*parts)
        want, wdist = jax.jit(assign_hamming)(codes, model.centers,
                                              model.center_valid)
        want = np.asarray(want)
        wdist = np.asarray(wdist) / model.d
        agreement = float((labels == want).mean())
        info.update(rows=m, agreement=agreement)
        if agreement != 1.0 or not np.array_equal(dists, wdist):
            raise CheckFailed(
                f"sparse serve: packed Pallas labels differ from the "
                f"equality path on {int((labels != want).sum())} rows, "
                f"dists on {int((dists != wdist).sum())}")


def sharded_phase(n: int, seed: int, clock: CompileClock) -> None:
    """Sharded fit, sharded predict and a 4-worker pool against the
    single-device paths in the same process."""
    import jax
    import numpy as np

    from repro import GEEK, DenseData, GeekConfig
    from repro.core.distributed import make_predict_sharded
    from repro.core.model import predict
    from repro.data.synthetic import sift_like
    from repro.serve import WorkerPool
    from repro.utils.compat import make_mesh

    key = jax.random.PRNGKey(seed)
    x = jax.block_until_ready(
        sift_like(jax.random.fold_in(key, 0), n=n, k=TRUE_K).x)
    fit_key = jax.random.fold_in(key, 1)
    with step("sharded fit/single", clock) as info:
        one = GEEK(GeekConfig())
        m1 = one.fit(DenseData(x), fit_key)
        jax.block_until_ready(one.result_.labels)
        info.update(n=n, devices=1, k_star=int(m1.k_star))
    mesh = make_mesh()
    with step("sharded fit/mesh", clock) as info:
        sh = GEEK(GeekConfig())
        mg = sh.fit(DenseData(x), fit_key, mesh=mesh)
        jax.block_until_ready(sh.result_.labels)
        same = {
            "labels": np.array_equal(np.asarray(one.result_.labels),
                                     np.asarray(sh.result_.labels)),
            "k_star": int(m1.k_star) == int(mg.k_star),
            "centers": np.array_equal(np.asarray(m1.centers),
                                      np.asarray(mg.centers)),
            "center_valid": np.array_equal(np.asarray(m1.center_valid),
                                           np.asarray(mg.center_valid)),
        }
        info.update(n=n, devices=mesh.size, k_star=int(mg.k_star),
                    agreement=float((np.asarray(one.result_.labels)
                                     == np.asarray(sh.result_.labels))
                                    .mean()))
        if not all(same.values()):
            raise CheckFailed("sharded fit is not bit-identical to the "
                              f"single-device fit: {same}")
    rows = np.asarray(x[:REQUESTS * REQUEST_ROWS])
    want, _ = predict(m1, jax.numpy.asarray(rows))
    want = np.asarray(want)
    with step("sharded predict", clock) as info:
        got, _ = make_predict_sharded(mesh)(m1, rows)
        agreement = float((np.asarray(got) == want).mean())
        info.update(rows=rows.shape[0], devices=mesh.size,
                    agreement=agreement)
        if agreement != 1.0:
            raise CheckFailed("make_predict_sharded labels differ from "
                              f"predict (agreement {agreement})")
    with step("sharded pool", clock) as info:
        r = REQUEST_ROWS
        with WorkerPool(m1, workers=mesh.size, max_batch=r) as pool:
            futs = [pool.submit(rows[i * r:(i + 1) * r])
                    for i in range(REQUESTS)]
            got = np.concatenate([f.result(timeout=900).labels
                                  for f in futs])
        agreement = float((got == want).mean())
        info.update(rows=rows.shape[0], workers=mesh.size,
                    agreement=agreement)
        if agreement != 1.0:
            raise CheckFailed("WorkerPool labels differ from predict "
                              f"(agreement {agreement})")


def device_check(chips: int) -> dict:
    """The device JAX reports; raises NoTPUError unless it is ``chips``
    TPU chips."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise NoTPUError(f"no TPU: JAX's first device is {dev['platform']!r}"
                         " — this check runs on the chip only")
    if dev["count"] != chips:
        raise NoTPUError(f"need {chips} TPU chip(s), JAX sees "
                         f"{dev['count']}")
    return dev


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: device, dense and sparse phases; 4: the "
                         "sharded path and what it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="data and fit seed")
    args = ap.parse_args()

    enable_compile_cache()
    clock = CompileClock()
    with step("device", clock) as info:
        dev = device_check(args.chips)
        info.update(**dev)
    if args.chips == 4:
        sharded_phase(N_DENSE, args.seed, clock)
    else:
        dense_phase(N_DENSE, args.seed, clock)
        sparse_phase(N_SPARSE, args.seed, clock)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

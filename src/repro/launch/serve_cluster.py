"""Cluster-assignment serving CLI — a thin wrapper over ``repro.serve``.

The ROADMAP's "heavy traffic" scenario: SILK discovery runs once
(offline), the fitted GeekModel is checkpointed, and a serving process
restores it and answers assignment traffic with the one-pass kernels
only. Since DESIGN.md §13 the actual server is
:class:`repro.serve.ClusterServer` — an async micro-batching engine
with a pad ladder, double-buffered dispatch, and hot-swap — and this
driver only fits-or-restores a model, stands the server up, and pushes
synthetic raw traffic through ``submit()``, reporting sustained
points/sec plus per-request p50/p99 latency.

  PYTHONPATH=src python -m repro.launch.serve_cluster --data dense \
      --n-fit 16384 --batch 4096 --steps 20
  PYTHONPATH=src python -m repro.launch.serve_cluster --data hetero \
      --ckpt /tmp/geek_model --save   # second run restores, skips the fit
  PYTHONPATH=src python -m repro.launch.serve_cluster --data sparse \
      --mesh --host-devices 4
      # --mesh: restore replicated onto a 1-axis mesh over all local
      # devices and serve each micro-batch row-sharded (bit-identical);
      # --host-devices replaces hand-set XLA_FLAGS (utils/platform.py)
  PYTHONPATH=src python -m repro.launch.serve_cluster --http :8080 \
      --workers 2 --host-devices 2 --refit-every 30
      # DESIGN.md §15: serve over HTTP from a 2-worker pool (one
      # engine per forced host device) and let the autopilot refit
      # from served traffic every 30s; traffic drives through the
      # socket, so the numbers include the wire
"""
from __future__ import annotations

import argparse
import time

#: expected transform kind per data type — a restored checkpoint fitted on
#: a different type must be refused, not served garbage
_KIND = {"dense": "identity", "hetero": "hetero", "sparse": "sparse"}


def _fit(args, cfg):
    import jax

    from repro.core.api import GEEK, DenseData, HeteroData, SparseData
    from repro.data import synthetic
    key = jax.random.PRNGKey(args.seed)
    if args.data == "dense":
        d = synthetic.sift_like(key, n=args.n_fit, k=args.k)
        dataset = DenseData(d.x)
    elif args.data == "hetero":
        h = synthetic.geonames_like(key, n=args.n_fit, k=args.k)
        dataset = HeteroData(h.x_num, h.x_cat)
    else:
        s = synthetic.url_like(key, n=args.n_fit, k=args.k)
        dataset = SparseData(s.sets, s.mask)
    # one facade call for every data kind — the dataset spec dispatches
    model = GEEK(cfg).fit(dataset, jax.random.PRNGKey(1))
    return jax.block_until_ready(model)


def _traffic(args, step: int) -> tuple:
    """A fresh batch of RAW query parts (new synthetic draws each step) —
    the model's transform does the coding, exactly as at fit time."""
    import jax

    from repro.data import synthetic
    key = jax.random.PRNGKey(1000 + step)
    if args.data == "dense":
        return (synthetic.sift_like(key, n=args.batch, k=args.k).x,)
    if args.data == "hetero":
        h = synthetic.geonames_like(key, n=args.batch, k=args.k)
        return (h.x_num, h.x_cat)
    s = synthetic.url_like(key, n=args.batch, k=args.k)
    return (s.sets, s.mask)


def _drive_http(args, url: str, req_rows: int, occupancy):
    """Run the traffic loop through the socket; returns loop stats.

    A small closed-loop client pool (8 in-flight requests) keeps the
    engine's micro-batches fed — sequential requests would serialize on
    the wire and measure the client, not the server.
    """
    import json
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    def post(parts):
        body = json.dumps(
            {"parts": [None if p is None else np.asarray(p).tolist()
                       for p in parts]}).encode()
        req = urllib.request.Request(
            url + "/v1/assign", data=body,
            headers={"Content-Type": "application/json"})
        t0 = time.time()
        with urllib.request.urlopen(req, timeout=120) as r:
            out = json.loads(r.read())
        return time.time() - t0, np.asarray(out["labels"], np.int64)

    total, latencies = 0, []
    with ThreadPoolExecutor(max_workers=8) as pool:
        for step in range(args.steps):
            batch = _traffic(args, step)
            n = next(p.shape[0] for p in batch if p is not None)
            chunks = [tuple(None if p is None else p[off:off + req_rows]
                            for p in batch)
                      for off in range(0, n, req_rows)]
            for dt, labels in pool.map(post, chunks):
                latencies.append(dt)
                total += labels.shape[0]
                occupancy += np.bincount(labels,
                                         minlength=occupancy.shape[0])
    return total, latencies, occupancy


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", default=None,
                    choices=["dense", "hetero", "sparse"])
    ap.add_argument("--metric", default=None, choices=["l2", "hamming"],
                    help="deprecated alias: l2 -> dense, hamming -> hetero")
    ap.add_argument("--n-fit", type=int, default=16384)
    ap.add_argument("--k", type=int, default=64, help="true #clusters")
    ap.add_argument("--k-max", type=int, default=256)
    ap.add_argument("--batch", type=int, default=4096,
                    help="rows of fresh traffic per step (also the "
                         "server's max_batch)")
    ap.add_argument("--request-rows", type=int, default=None,
                    help="rows per submitted request (default: --batch, "
                         "i.e. one request per step; smaller values "
                         "exercise micro-batching)")
    ap.add_argument("--deadline-ms", type=float, default=5.0,
                    help="micro-batch flush deadline")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None,
                    help="model checkpoint dir (restore if it has one)")
    ap.add_argument("--save", action="store_true",
                    help="save the fitted model to --ckpt")
    ap.add_argument("--mesh", action="store_true",
                    help="serve row-sharded over all local devices "
                         "(model replicated; labels bit-identical)")
    ap.add_argument("--probes", type=int, default=None,
                    help="probe the model's center index with this "
                         "multi-probe radius (sub-linear in k; empty "
                         "probes fall back to the exact scan). Default: "
                         "exact full scan. Composes with --mesh (the "
                         "sharded probed step)")
    ap.add_argument("--http", default=None, metavar="[HOST]:PORT",
                    help="serve over HTTP (repro.serve.ClusterFrontend) "
                         "and drive the traffic loop through the socket; "
                         "':8080' binds loopback:8080, ':0' picks a port")
    ap.add_argument("--workers", type=int, default=None,
                    help="serve from a WorkerPool of this many per-device "
                         "engines (needs that many local devices — see "
                         "--host-devices). Default: one ClusterServer")
    ap.add_argument("--refit-every", type=float, default=None,
                    metavar="SECONDS",
                    help="run a RefitAutopilot: reservoir served traffic "
                         "and refit-validate-publish on this period")
    ap.add_argument("--smoke", action="store_true")
    from repro.utils.platform import (add_platform_args, apply_platform_args,
                                      enable_compile_cache)
    add_platform_args(ap)
    args = ap.parse_args()
    apply_platform_args(args)          # before the first JAX computation
    enable_compile_cache()

    import jax
    import numpy as np

    from repro.checkpoint.manager import restore_model, save_model
    from repro.core.geek import GeekConfig
    from repro.serve import ClusterServer
    from repro.utils.compat import make_mesh

    if args.metric is not None:
        if args.data is not None:
            raise SystemExit("[serve] pass --data OR the deprecated "
                             "--metric alias, not both")
        args.data = "dense" if args.metric == "l2" else "hetero"
    elif args.data is None:
        args.data = "dense"
    if args.smoke:
        args.n_fit, args.batch, args.steps = 2048, 512, 5

    cfg = GeekConfig(m=16, t=32, silk_l=4, delta=5, k_max=args.k_max,
                     pair_cap=1 << 15)
    mesh = make_mesh() if args.mesh else None

    model = None
    if args.ckpt:
        try:
            model = restore_model(args.ckpt, mesh=mesh)
            kind = getattr(model.transform, "kind", None)
            if kind != _KIND[args.data]:
                raise SystemExit(
                    f"[serve] checkpoint at {args.ckpt} holds a "
                    f"{kind or 'pre-transform'} model, but --data is "
                    f"{args.data!r} — refusing to serve mismatched traffic")
            print(f"[serve] restored model from {args.ckpt} "
                  f"(k*={int(model.k_star)}, metric={model.metric}, "
                  f"transform={kind})")
        except (FileNotFoundError, ValueError) as e:
            print(f"[serve] no usable model at {args.ckpt} ({e}); fitting")
    if model is None:
        t0 = time.time()
        model = _fit(args, cfg)
        print(f"[serve] fitted: k*={int(model.k_star)} metric={model.metric} "
              f"impl={model.impl or '-'} time={time.time() - t0:.1f}s")
        if args.ckpt and args.save:
            save_model(args.ckpt, model)
            print(f"[serve] saved model to {args.ckpt}")

    # -- serving loop ------------------------------------------------------
    # the engine owns batching/padding/dispatch; this loop only submits
    # raw request parts and collects futures (or HTTP responses)
    req_rows = args.request_rows or args.batch
    if args.workers is not None:
        if mesh is not None:
            raise SystemExit("[serve] --workers (per-device pool) and "
                             "--mesh (row-sharded single engine) are "
                             "different scale-out stories — pick one")
        from repro.serve import WorkerPool
        server = WorkerPool(model, workers=args.workers,
                            probes=args.probes, max_batch=args.batch,
                            deadline_ms=args.deadline_ms)
    else:
        server = ClusterServer(model, probes=args.probes, mesh=mesh,
                               max_batch=args.batch,
                               deadline_ms=args.deadline_ms)
    warm = _traffic(args, -1)
    server.warmup(tuple(None if p is None else p[:req_rows] for p in warm))

    autopilot = None
    if args.refit_every is not None:
        from repro.serve import RefitAutopilot
        autopilot = RefitAutopilot(server, cfg, reservoir=4 * args.batch,
                                   min_rows=min(args.n_fit, 2 * args.batch),
                                   refit_every_s=args.refit_every,
                                   seed=args.seed).start()
        print(f"[serve] autopilot refitting every {args.refit_every}s "
              f"(reservoir={4 * args.batch} rows)")

    frontend = None
    if args.http is not None:
        from repro.serve import ClusterFrontend
        host, _, port = args.http.rpartition(":")
        frontend = ClusterFrontend(
            server, host=host or "127.0.0.1", port=int(port or 0),
            observer=autopilot.observe if autopilot else None).start()
        print(f"[serve] http on {frontend.url} "
              "(POST /v1/assign, GET /v1/stats)")

    total, latencies = 0, []
    occupancy = np.zeros((model.k_max,), np.int64)
    t_wall = time.time()
    if frontend is not None:
        total, latencies, occupancy = _drive_http(
            args, frontend.url, req_rows, occupancy)
    else:
        for step in range(args.steps):
            batch = tuple(None if p is None else np.asarray(p)
                          for p in _traffic(args, step))
            if autopilot is not None:
                autopilot.observe(batch)   # no socket, no observer hook
            n = next(p.shape[0] for p in batch if p is not None)
            futs = []
            for off in range(0, n, req_rows):
                parts = tuple(None if p is None else p[off:off + req_rows]
                              for p in batch)
                t0 = time.time()
                futs.append((t0, server.submit(parts)))
            for t0, fut in futs:
                res = fut.result()
                latencies.append(time.time() - t0)
                total += res.labels.shape[0]
                occupancy += np.bincount(res.labels,
                                         minlength=model.k_max)
    t_wall = time.time() - t_wall
    if autopilot is not None:
        autopilot.close()
        ast = autopilot.stats()
        print(f"[serve] autopilot: {ast['refits']} refits, "
              f"{ast['published']} published, {ast['rollbacks']} "
              f"rollbacks (serving v{server.version})")
    if frontend is not None:
        frontend.close()
    server.close()

    pps = total / max(t_wall, 1e-9)
    p50, p99 = np.percentile(np.asarray(latencies) * 1e3, [50, 99])
    hot = int(occupancy.argmax())
    tag = f" x{len(jax.devices())} devices" if mesh is not None else ""
    if args.workers is not None:
        tag += f" pool={args.workers}"
    if args.http is not None:
        tag += " http"
    if args.probes is not None:
        tag += f" probes={args.probes}"
    st = server.stats()
    if "flushes" not in st:      # WorkerPool: sum the per-worker tallies
        st["flushes"] = {
            k: sum(w["flushes"][k] for w in st["workers"])
            for k in st["workers"][0]["flushes"]}
    print(f"[serve{tag}] {args.steps} steps x {args.batch} rows "
          f"({req_rows}/request): {pps:,.0f} points/s sustained, "
          f"p50={p50:.1f}ms p99={p99:.1f}ms, "
          f"{st['batches']} micro-batches "
          f"(flushes: {st['flushes']}), "
          f"hottest cluster {hot} got {int(occupancy[hot])} points")


if __name__ == "__main__":
    main()

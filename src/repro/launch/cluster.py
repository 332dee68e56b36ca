"""GEEK clustering driver — the paper's end-to-end system.

Runs the full transformation -> seeding -> one-pass-assignment pipeline
on synthetic analogues of the paper's datasets through the ONE facade
(`repro.core.api.GEEK`): the dataset picks the kind, `--streaming` /
`--mesh` pick the execution mode, and `--seeder` swaps the seeding
strategy (SILK default; the paper's §4.1 comparison seeders plug into
the same pipeline). `--distributed` keeps the paper-§3.4 table-sync
dense variant; `--compare` adds the iteration baselines.

  PYTHONPATH=src python -m repro.launch.cluster --dataset sift --n 20000 \
      --k 64 --compare
  PYTHONPATH=src python -m repro.launch.cluster --dataset url --n 100000 \
      --streaming --chunk 8192 --seed-cap 20000   # out-of-core, any type
  PYTHONPATH=src python -m repro.launch.cluster --dataset sift \
      --seeder kmeanspp                           # swapped seeding stage
  PYTHONPATH=src python -m repro.launch.cluster --dataset geonames \
      --mesh --host-devices 4
      # --host-devices replaces hand-set XLA_FLAGS (utils/platform.py)
"""
from __future__ import annotations

import argparse
import time


def mean_radius(radius, valid):
    import jax.numpy as jnp
    r = jnp.where(valid, radius, 0.0)
    return float(r.sum() / jnp.maximum(valid.sum(), 1))


def make_dataset(args, key):
    """One synthetic dataset as a facade Dataset spec (+ raw handle)."""
    from repro.core.api import DenseData, HeteroData, SparseData
    from repro.data import synthetic
    if args.dataset in ("sift", "gist"):
        gen = (synthetic.sift_like if args.dataset == "sift"
               else synthetic.gist_like)
        data = gen(key, n=args.n, k=args.k)
        return DenseData(data.x), data, "geek"
    if args.dataset == "geonames":
        data = synthetic.geonames_like(key, n=args.n, k=args.k)
        return HeteroData(data.x_num, data.x_cat), data, "geek/hetero"
    data = synthetic.url_like(key, n=args.n, k=args.k)
    return SparseData(data.sets, data.mask), data, "geek/sparse"


def make_seeder(name: str, k: int):
    """--seeder flag -> Seeder protocol object (None = SILK default)."""
    from repro.core.api import KMeansPPSeeder, ScalableKMeansPPSeeder
    if name == "silk":
        return None
    if name == "kmeanspp":
        return KMeansPPSeeder(k)
    return ScalableKMeansPPSeeder(k)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="sift",
                    choices=["sift", "gist", "geonames", "url"])
    ap.add_argument("--n", type=int, default=20000)
    ap.add_argument("--k", type=int, default=64, help="true #clusters")
    ap.add_argument("--k-max", type=int, default=256)
    ap.add_argument("--m", type=int, default=40)
    ap.add_argument("--t", type=int, default=64)
    ap.add_argument("--silk-l", type=int, default=6)
    ap.add_argument("--delta", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seeder", default="silk",
                    choices=["silk", "kmeanspp", "scalable-kmeanspp"],
                    help="seeding stage: SILK (k* discovered) or a "
                         "k-means++ family seeder (k = --k, dense only)")
    ap.add_argument("--distributed", action="store_true",
                    help="paper-§3.4 table-sync dense fit over all local "
                         "devices (approximate sharded discovery)")
    ap.add_argument("--mesh", action="store_true",
                    help="unified sharded fit over all local devices "
                         "(any data type, exact, GeekModel out)")
    ap.add_argument("--streaming", action="store_true",
                    help="out-of-core fit: device memory bounded by --chunk")
    ap.add_argument("--chunk", type=int, default=8192,
                    help="rows on device per streamed assignment step")
    ap.add_argument("--seed-cap", type=int, default=None,
                    help="max reservoir rows for streamed/sharded discovery "
                         "(default: all rows -> bit-identical to in-core)")
    ap.add_argument("--compare", action="store_true")
    from repro.utils.platform import (add_platform_args, apply_platform_args,
                                      enable_compile_cache)
    add_platform_args(ap)
    args = ap.parse_args()
    apply_platform_args(args)          # before the first JAX computation
    enable_compile_cache()
    if args.streaming and args.distributed:
        raise SystemExit("--streaming and --distributed are exclusive")
    if args.mesh and args.distributed:
        raise SystemExit("--mesh and --distributed are exclusive "
                         "(--mesh is the unified sharded path)")

    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import baselines
    from repro.core.api import GEEK
    from repro.core.distributed import make_fit_dense
    from repro.core.geek import GeekConfig, hetero_codes
    from repro.utils.compat import make_mesh

    key = jax.random.PRNGKey(args.seed)
    cfg = GeekConfig(m=args.m, t=args.t, silk_l=args.silk_l, delta=args.delta,
                     k_max=args.k_max, pair_cap=1 << 16)
    dataset, data, tag = make_dataset(args, key)

    if args.distributed:
        if dataset.kind != "dense":
            raise SystemExit("--distributed (table-sync §3.4) is dense-only")
        mesh = Mesh(np.array(jax.devices()), ("data",))
        fit = make_fit_dense(mesh, cfg)
        x = jax.device_put(data.x, NamedSharding(mesh, P("data", None)))
        t0 = time.time()
        labels, centers, cvalid, k_star, radius, ovf = fit(
            x, jax.random.PRNGKey(1))
        jax.block_until_ready(labels)
        dt = time.time() - t0
        print(f"[geek/dist x{len(jax.devices())}] n={args.n} "
              f"k*={int(k_star)} mean_radius={mean_radius(radius, cvalid):.4f} "
              f"time={dt:.2f}s overflow={int(ovf)}")
        return

    mesh = make_mesh() if args.mesh else None
    est = GEEK(cfg, seeder=make_seeder(args.seeder, args.k))
    t0 = time.time()
    # seed_cap passes through unconditionally: the facade itself rejects
    # it without a bounded-memory mode, so a forgotten --streaming/--mesh
    # errors instead of silently running an unbounded in-core fit
    est.fit(dataset, jax.random.PRNGKey(1), mesh=mesh,
            chunk=args.chunk if args.streaming else None,
            seed_cap=args.seed_cap)
    res = est.result_
    jax.block_until_ready(res.labels)   # no-op for host-numpy results
    dt = time.time() - t0

    if args.seeder != "silk":
        tag += f"/{args.seeder}"
    if args.streaming:
        tag += "/stream"
    if mesh is not None:
        tag += f"/sharded x{len(jax.devices())}"
    print(f"[{tag}] n={args.n} k*={int(res.k_star)} "
          f"mean_radius={mean_radius(res.radius, res.center_valid):.4f} "
          f"time={dt:.2f}s")

    if not args.compare:
        return
    k = int(res.k_star)
    if dataset.kind == "dense":
        for name, fn in [
            ("lloyd", lambda: baselines.lloyd(data.x, k,
                                              jax.random.PRNGKey(2), iters=10)),
            ("kmeans++1p", lambda: baselines.seed_then_assign(
                data.x, k, jax.random.PRNGKey(3))),
            ("random1p", lambda: baselines.seed_then_assign(
                data.x, k, jax.random.PRNGKey(4), method="random")),
            ("sampled", lambda: baselines.sampled_kmeans(
                data.x, k, jax.random.PRNGKey(5), iters=10)),
        ]:
            t0 = time.time()
            r = fn()
            jax.block_until_ready(r.labels)
            print(f"[{name:10s}] k={k} "
                  f"mean_radius={mean_radius(r.radius, r.center_valid):.4f} "
                  f"time={time.time()-t0:.2f}s")
    elif dataset.kind == "hetero":
        codes = hetero_codes(data.x_num, data.x_cat, cfg.t_cat)
        t0 = time.time()
        r = baselines.kmodes(codes, k, jax.random.PRNGKey(2))
        jax.block_until_ready(r.labels)
        print(f"[kmodes    ] mean_radius="
              f"{mean_radius(r.radius, r.center_valid):.4f} "
              f"time={time.time()-t0:.2f}s")


if __name__ == "__main__":
    main()

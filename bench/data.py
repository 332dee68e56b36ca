"""Data made from the seed, on the device.

The generators are copied from ``src/repro/data/synthetic.py``
(``dense_blobs``, ``url_like``), so that a change to the program cannot
change the data a cell runs on. ``dense_blobs`` here also returns the
generated centers, which the served model of a serving cell is built
from; both return each row's generated cluster, the ground truth that
the fit's seeding is held to (``reference/seeds.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("n", "d", "k", "spread"))
def dense_blobs(key, *, n: int, d: int, k: int, spread: float):
    """Gaussian mixture rows: (x (n, d), labels (n,), centers (k, d))."""
    kc, kl, kn = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (k, d), jnp.float32)
    labels = jax.random.randint(kl, (n,), 0, k)
    noise = jax.random.normal(kn, (n, d), jnp.float32) * spread
    return centers[labels] + noise, labels.astype(jnp.int32), centers


@functools.partial(jax.jit, static_argnames=("n", "k", "nnz", "universe",
                                             "shared_frac"))
def url_like(key, *, n: int, k: int, nnz: int, universe: int,
             shared_frac: float):
    """Sparse sets: each cluster shares a core item set; members keep
    about ``shared_frac`` of it and draw the rest uniformly.
    Returns (sets (n, nnz) int32, mask (n, nnz) bool, labels (n,))."""
    kc, kl, kk, kr = jax.random.split(key, 4)
    labels = jax.random.randint(kl, (n,), 0, k)
    core = jax.random.randint(kc, (k, nnz), 0, universe)
    keep = jax.random.uniform(kk, (n, nnz)) < shared_frac
    rand = jax.random.randint(kr, (n, nnz), 0, universe)
    sets = jnp.where(keep, core[labels], rand)
    return (sets.astype(jnp.int32), jnp.ones((n, nnz), bool),
            labels.astype(jnp.int32))


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**64 (seeds may pass 32
    bits)."""
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def make(config: dict, key, *, n: int | None = None) -> dict:
    """The configuration's data set, on the device: ``{"parts": (...),
    "truth": (n,) generated cluster of each row, "centers": (k, d) or
    None}``. ``n`` overrides the row count (a serving cell's query
    pool)."""
    g = config["data"]
    rows = int(config["n"] if n is None else n)
    if g["generator"] == "dense_blobs":
        x, truth, centers = dense_blobs(key, n=rows, d=int(config["d"]),
                                    k=int(g["clusters"]),
                                    spread=float(g["spread"]))
        return {"parts": (x,), "truth": truth, "centers": centers}
    if g["generator"] == "url_like":
        sets, mask, truth = url_like(key, n=rows, k=int(g["clusters"]),
                                 nnz=int(config["items_per_row"]),
                                 universe=int(config["universe"]),
                                 shared_frac=float(g["shared_frac"]))
        return {"parts": (sets, mask), "truth": truth, "centers": None}
    raise ValueError(f"unknown generator {g['generator']!r}")

"""Plain centroid reference: the mean of each seed group's member rows,
in float64 on the host."""
from __future__ import annotations

import numpy as np


def centroids(rows, group, k_max: int, dtype=np.float64):
    """(k_max, d) means of ``rows`` grouped by ``group`` (in [0,
    k_max)), summed in ``dtype``, and which groups have members."""
    rows = np.asarray(rows).astype(dtype)
    group = np.asarray(group, np.int64)
    sums = np.zeros((k_max, rows.shape[1]), dtype)
    np.add.at(sums, group, rows)
    cnt = np.bincount(group, minlength=k_max)[:k_max]
    valid = cnt > 0
    out = np.zeros_like(sums)
    out[valid] = sums[valid] / cnt[valid, None].astype(dtype)
    return out, valid


def centroid_err(centers, ref, ref_valid, rows) -> float:
    """The largest element gap between published and reference
    centroids, over valid groups, in units of the member rows' root
    mean square."""
    if not ref_valid.any():
        return 0.0
    scale = float(np.sqrt(np.mean(np.asarray(rows, np.float64) ** 2)))
    gap = np.abs(np.asarray(centers, np.float64)[ref_valid]
                 - ref[ref_valid]).max()
    return float(gap / max(scale, 1e-30))


def centroids_bf16(rows, group, k_max: int):
    """The control's centroids: the same means with every input, sum and
    quotient rounded to bfloat16, one step below the float32 the
    configuration states."""
    import jax.numpy as jnp
    bf = jnp.bfloat16
    r = jnp.asarray(rows, bf)
    g = jnp.asarray(group, jnp.int32)
    sums = jnp.zeros((k_max, r.shape[1]), bf).at[g].add(r)
    cnt = jnp.zeros((k_max,), bf).at[g].add(jnp.ones_like(g, bf))
    return np.asarray((sums / jnp.maximum(cnt, 1)[:, None]).astype(
        jnp.float32))

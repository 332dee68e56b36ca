"""Central vectors + one-pass data assignment (paper §3.3).

Central vectors:
- homogeneous dense  -> centroid (segment-mean over seed-group members)
- hetero / sparse    -> per-attribute mode over the unified categorical codes
  (sort-based segment mode: no (k, d, cardinality) one-hot blow-up)

Assignment: a single nearest-central-vector pass. The hot loop is the
O(n·d·k) fused distance+argmin — Pallas kernel on TPU
(`repro.kernels.distance_argmin`), pure-jnp here as oracle/CPU path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.silk import Seeds
from repro.kernels.pack import field_mismatch_count, onehot_codes
from repro.utils.hashing import run_starts


# ---------------------------------------------------------------------------
# Central vectors
# ---------------------------------------------------------------------------

def centroid_centers(x: jax.Array, seeds: Seeds) -> tuple[jax.Array, jax.Array]:
    """(k_max, d) centroids + (k_max,) validity from seed-group members."""
    k_max = seeds.k_max
    g = jnp.where(seeds.valid, seeds.group, k_max)
    w = seeds.valid.astype(x.dtype)
    sums = jax.ops.segment_sum(x[seeds.id] * w[:, None], g, num_segments=k_max + 1)[:k_max]
    cnt = jax.ops.segment_sum(w, g, num_segments=k_max + 1)[:k_max]
    centers = sums / jnp.maximum(cnt, 1.0)[:, None]
    return centers, cnt > 0


def mode_centers(codes: jax.Array, seeds: Seeds, *, attr_chunk: int = 64
                 ) -> tuple[jax.Array, jax.Array]:
    """(k_max, d) per-attribute modes + validity, via sort-based counting.

    For each (group, attribute) cell: mode = value with the largest member
    count (ties -> smallest value, deterministic). Works for arbitrary
    32-bit code cardinality (DOPH codes included).
    """
    k_max = seeds.k_max
    c = seeds.id.shape[0]
    d = codes.shape[1]
    g = jnp.where(seeds.valid, seeds.group, k_max)
    member_codes = codes[seeds.id].astype(jnp.int32)      # (C, d)
    cnt = jax.ops.segment_sum(seeds.valid.astype(jnp.int32), g,
                              num_segments=k_max + 1)[:k_max]

    out = []
    for a0 in range(0, d, attr_chunk):
        a1 = min(a0 + attr_chunk, d)
        w = a1 - a0
        vals = member_codes[:, a0:a1].T.reshape(-1)       # (w*C,)
        cell = (jnp.broadcast_to(jnp.arange(w, dtype=jnp.int32)[:, None] * (k_max + 1), (w, c))
                + g[None, :]).reshape(-1)                 # (w*C,) cell = attr*(k+1)+grp
        valid = jnp.broadcast_to(seeds.valid, (w, c)).reshape(-1)
        order = jnp.lexsort((vals, cell, ~valid))
        cell_s, val_s, v_s = cell[order], vals[order], valid[order]
        starts = run_starts(cell_s, val_s, valid=v_s)
        run_id = jnp.cumsum(starts.astype(jnp.int32)) - 1
        counts = jax.ops.segment_sum(v_s.astype(jnp.int32), run_id,
                                     num_segments=w * c)
        ncells = w * (k_max + 1)
        run_cnt = jnp.where(starts, counts[run_id], 0)
        best_cnt = jax.ops.segment_max(run_cnt, cell_s, num_segments=ncells)
        is_best = starts & (run_cnt == best_cnt[cell_s]) & (run_cnt > 0)
        big = jnp.int32(jnp.iinfo(jnp.int32).max)
        mode = jax.ops.segment_min(jnp.where(is_best, val_s, big), cell_s,
                                   num_segments=ncells)
        out.append(mode.reshape(w, k_max + 1)[:, :k_max].T)  # (k_max, w)
    centers = jnp.concatenate(out, axis=1)
    centers = jnp.where((cnt > 0)[:, None], centers, 0)
    return centers, cnt > 0


# ---------------------------------------------------------------------------
# One-pass assignment (jnp path; Pallas kernel in repro.kernels)
# ---------------------------------------------------------------------------

_MIN_DOT_ROWS = 8


def assign_l2(x: jax.Array, centers: jax.Array, center_valid: jax.Array,
              *, block: int = 4096) -> tuple[jax.Array, jax.Array]:
    """Nearest centroid under Euclidean distance. Returns (labels, sq_dists).

    A row's result does not depend on the batch it arrives in: a one-row
    ``x @ centers.T`` lowers to a matrix-vector product, which sums in
    another order than the matrix-matrix product of larger batches, so
    batches under ``_MIN_DOT_ROWS`` rows are zero-padded up to it.
    """
    n = x.shape[0]
    if n < _MIN_DOT_ROWS:
        xp = jnp.pad(x, ((0, _MIN_DOT_ROWS - n), (0, 0)))
        labels, d2 = assign_l2(xp, centers, center_valid, block=block)
        return labels[:n], d2[:n]
    csq = jnp.sum(centers * centers, axis=-1)
    inf = jnp.array(jnp.finfo(x.dtype).max, x.dtype)

    def chunk(xb):
        xsq = jnp.sum(xb * xb, axis=-1, keepdims=True)
        d2 = xsq - 2.0 * (xb @ centers.T) + csq[None, :]
        d2 = jnp.where(center_valid[None, :], d2, inf)
        lab = jnp.argmin(d2, axis=-1)
        return lab.astype(jnp.int32), jnp.maximum(jnp.min(d2, axis=-1), 0.0)

    return _blocked(chunk, x, block)


def assign_l2_with_partials(x: jax.Array, centers: jax.Array,
                            center_valid: jax.Array, *, block: int = 4096):
    """assign_l2 plus per-cluster partial sums/counts — the jnp
    (second-pass) counterpart of the fused ``accumulate=True`` kernel."""
    lab, d2 = assign_l2(x, centers, center_valid, block=block)
    k = centers.shape[0]
    sums = jax.ops.segment_sum(x.astype(jnp.float32), lab, num_segments=k)
    cnt = jax.ops.segment_sum(jnp.ones_like(lab, jnp.float32), lab,
                              num_segments=k)
    return lab, d2, sums, cnt


def assign_hamming(codes: jax.Array, centers: jax.Array, center_valid: jax.Array,
                   *, block: int = 4096) -> tuple[jax.Array, jax.Array]:
    """Nearest center under attribute-mismatch count (≈ 1-Jaccard on
    minwise codes: P[code match] = J). Returns (labels, mismatch counts)."""
    d = codes.shape[1]
    big = jnp.int32(d + 1)

    def chunk(xb):
        eq = (xb[:, None, :] == centers[None, :, :]).sum(axis=-1)
        dist = d - eq
        dist = jnp.where(center_valid[None, :], dist, big)
        lab = jnp.argmin(dist, axis=-1)
        return lab.astype(jnp.int32), jnp.min(dist, axis=-1).astype(jnp.float32)

    return _blocked(chunk, codes, block)


def assign_hamming_packed(packed: jax.Array, packed_centers: jax.Array,
                          center_valid: jax.Array, *, bits: int,
                          d: int | None = None,
                          block: int = 4096) -> tuple[jax.Array, jax.Array]:
    """assign_hamming on bit-packed codes (see `repro.kernels.pack`).

    XOR + field-collapse + popcount over d·bits/32 uint32 words — no
    (block, k, d) equality broadcast, 32/bits× less memory traffic.
    Mismatch counts (and therefore labels) are bit-identical to the
    unpacked path: a b-bit field differs iff the original codes differ.
    Pass the unpacked width ``d`` to reproduce assign_hamming's ``d + 1``
    invalid-center sentinel exactly (otherwise int32 max is used).
    """
    kpc = packed_centers
    big = jnp.int32(jnp.iinfo(jnp.int32).max if d is None else d + 1)

    def chunk(xb):
        z = xb[:, None, :] ^ kpc[None, :, :]
        dist = jnp.sum(field_mismatch_count(z, bits), axis=-1)
        dist = jnp.where(center_valid[None, :], dist, big)
        lab = jnp.argmin(dist, axis=-1)
        return lab.astype(jnp.int32), jnp.min(dist, axis=-1).astype(jnp.float32)

    return _blocked(chunk, packed, block)


def assign_hamming_onehot(codes: jax.Array, centers: jax.Array,
                          center_valid: jax.Array, *, card: int,
                          block: int = 4096,
                          centers_onehot: jax.Array | None = None
                          ) -> tuple[jax.Array, jax.Array]:
    """assign_hamming for low-cardinality codes via one-hot bf16 matmul.

    matches = x1h @ c1h.T rides the MXU exactly like the L2 path (f32
    accumulation keeps integer counts exact for d < 2**24, so labels stay
    bit-identical to the equality path). One-hot width is d·card — only
    worthwhile for small card (t_cat discretization bins).

    ``centers_onehot`` lets a serving path (GeekModel) pass centers that
    were one-hot encoded once at model build instead of per call.
    """
    d = codes.shape[1]
    big = jnp.int32(d + 1)
    c1h = (onehot_codes(centers, card) if centers_onehot is None
           else centers_onehot)                              # (k, d*card)

    def chunk(xb):
        x1h = onehot_codes(xb, card)
        matches = jax.lax.dot_general(
            x1h, c1h, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dist = d - matches.astype(jnp.int32)
        dist = jnp.where(center_valid[None, :], dist, big)
        lab = jnp.argmin(dist, axis=-1)
        return lab.astype(jnp.int32), jnp.min(dist, axis=-1).astype(jnp.float32)

    return _blocked(chunk, codes, block)


def _blocked(fn, x, block):
    n = x.shape[0]
    if n <= block:
        return fn(x)
    pad = (-n) % block
    xp = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    labs, dists = jax.lax.map(fn, xp.reshape(-1, block, *x.shape[1:]))
    return labs.reshape(-1)[:n], dists.reshape(-1)[:n]


def cluster_radius(dists: jax.Array, labels: jax.Array, k_max: int) -> jax.Array:
    """Paper's effectiveness metric: per-cluster max point-center distance.
    Clusters that received no points report radius 0."""
    return jnp.maximum(jax.ops.segment_max(dists, labels, num_segments=k_max), 0.0)


def cluster_sizes(labels: jax.Array, k_max: int) -> jax.Array:
    return jax.ops.segment_sum(jnp.ones_like(labels), labels, num_segments=k_max)

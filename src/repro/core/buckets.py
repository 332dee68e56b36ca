"""Unified bucket format (paper §3.1): every data type becomes BucketTables.

A `BucketTables` is T hash tables over the same n objects. In table t,
object `ids[t, p]` lives in bucket `segments[t, p]` (dense per-table index,
ascending along p). Exactly one entry per (table, object): the flattened
view has T·n entries — the quantity N_B·D_B that drives SILK's complexity
(paper §3.5).

Two construction paths:
- `partition_even`        : QALSH rank-partition, homogeneous dense data
                            (Algorithm 1 — sort each table, cut into t buckets)
- `partition_by_signature`: MinHash (K, L) static bucketing, heterogeneous /
                            sparse data (Algorithms 2 & 3)
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.utils.hashing import run_starts


class BucketTables(NamedTuple):
    """T LSH hash tables over the same n objects (see module docstring)."""

    ids: jax.Array          # (T, n) int32 — data ids, sorted by bucket within table
    segments: jax.Array     # (T, n) int32 — dense bucket index within table
    num_buckets: jax.Array  # (T,)  int32 — # non-empty buckets per table
    buckets_per_table: int  # static cap on buckets per table (t or n)
    # static: every bucket is exactly this many consecutive entries of its
    # table (so the flat entries are buckets_per_table·T equal runs), or
    # None when bucket sizes vary or are not known when tracing
    run_len: int | None = None

    @property
    def num_tables(self) -> int:
        """Number of hash tables T."""
        return self.ids.shape[0]

    @property
    def n(self) -> int:
        """Number of objects per table."""
        return self.ids.shape[1]

    @property
    def total_bucket_cap(self) -> int:
        """Static cap on global bucket ids: T · buckets_per_table."""
        return self.num_tables * self.buckets_per_table

    def flatten(self) -> tuple[jax.Array, jax.Array]:
        """(T·n,) ids and *global* segment ids (table-offset applied)."""
        T, n = self.ids.shape
        offs = (jnp.arange(T, dtype=jnp.int32) * self.buckets_per_table)[:, None]
        return self.ids.reshape(-1), (self.segments + offs).reshape(-1)


def partition_even(h: jax.Array, t: int) -> BucketTables:
    """Algorithm 1: sort each hash table, evenly partition into t buckets.

    h: (n, m) QALSH values. Bucket of the rank-r object is floor(r·t/n), so
    bucket sizes differ by at most one — the paper's granularity-control
    replacement for the hard-to-tune bucket width w. When t divides n
    every bucket holds exactly n/t entries, recorded as ``run_len``.
    """
    n, m = h.shape
    order = jnp.argsort(h, axis=0)                      # (n, m) — ids by rank
    ranks = jnp.arange(n, dtype=jnp.int32)
    seg = (ranks * t // n).astype(jnp.int32)            # (n,) even partition
    ids = order.T.astype(jnp.int32)                     # (m, n)
    segments = jnp.broadcast_to(seg, (m, n))
    return BucketTables(ids, segments, jnp.full((m,), t, jnp.int32), t,
                        n // t if n % t == 0 else None)


def partition_by_boundaries(h: jax.Array, boundaries: jax.Array) -> BucketTables:
    """Distributed variant of Algorithm 1: bucket via precomputed quantile
    boundaries (t-1 per table) instead of a global sort. Used by the
    shard_map pipeline — see DESIGN.md §2 (sample-quantile adaptation).
    """
    n, m = h.shape
    t = boundaries.shape[1] + 1
    # bucket id per object = #boundaries below its hash value
    bid = jax.vmap(jnp.searchsorted, in_axes=(1, 1))(boundaries, h)  # (m, n)
    bid = bid.astype(jnp.int32)
    order = jnp.argsort(bid, axis=1)
    ids = jnp.take_along_axis(
        jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (m, n)), order, axis=1)
    segments = jnp.take_along_axis(bid, order, axis=1)
    return BucketTables(ids, segments, jnp.full((m,), t, jnp.int32), t)


def partition_by_signature(sigs: jax.Array) -> BucketTables:
    """Algorithms 2 & 3: group objects whose (K-fold) MinHash signatures
    collide. sigs: (L, n) uint32. Buckets per table ≤ n (cap = n).
    """
    L, n = sigs.shape

    def one_table(sig):
        """Sort one table's signatures into (ids, segments, n_buckets)."""
        order = jnp.argsort(sig)
        ss = sig[order]
        starts = run_starts(ss)
        seg = jnp.cumsum(starts.astype(jnp.int32)) - 1
        return order.astype(jnp.int32), seg, seg[-1] + 1

    ids, segments, nb = jax.vmap(one_table)(sigs)
    return BucketTables(ids, segments, nb.astype(jnp.int32), n)


# ---------------------------------------------------------------------------
# Owned-table slices — the bucket-id-range partition of the sharded fit
# ---------------------------------------------------------------------------
# Global bucket ids are table-major (``flatten``: table·buckets_per_table
# + local), so giving device j a contiguous block of *tables* IS a
# contiguous bucket-id-range partition. These helpers run the exact
# per-table math of ``partition_even`` / ``partition_by_signature`` on an
# owned slice, and additionally return the inverse map ``b_of_id``
# (bucket of each object) that the distributed majority vote exchanges
# back to the id owners (``core.distributed.discover_sharded``).

def rank_partition_slice(h_cols: jax.Array, t: int):
    """Algorithm 1 on an owned column slice of the QALSH hash matrix.

    Per-column math is identical to ``partition_even`` (stable argsort +
    even rank cut), so table τ built here from the full column h[:, τ]
    is bit-identical to table τ of the in-core fit.

    Parameters
    ----------
    h_cols : (n, mt) float array
        The mt owned tables' hash values for ALL n objects.
    t : int
        Buckets per table.

    Returns
    -------
    (ids, segments, b_of_id, sizes)
        ``ids``/``segments`` (mt, n) as in ``BucketTables``; ``b_of_id``
        (mt, n) maps object id -> its bucket in each owned table;
        ``sizes`` (mt, t) per-bucket entry counts.
    """
    n, mt = h_cols.shape
    order = jnp.argsort(h_cols, axis=0)                 # (n, mt) — ids by rank
    ranks = jnp.arange(n, dtype=jnp.int32)
    seg = (ranks * t // n).astype(jnp.int32)            # (n,) even partition
    ids = order.T.astype(jnp.int32)                     # (mt, n)
    segments = jnp.broadcast_to(seg, (mt, n))
    b_of_id = jax.vmap(
        lambda o: jnp.zeros((n,), jnp.int32).at[o].set(seg))(ids)
    sizes = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), seg,
                                num_segments=t)
    return ids, segments, b_of_id, jnp.broadcast_to(sizes, (mt, t))


def signature_partition_slice(sigs: jax.Array):
    """Algorithms 2 & 3 on an owned row slice of the signature matrix.

    Per-table math is identical to ``partition_by_signature`` (stable
    argsort of the full signature row + run numbering), so table τ built
    here is bit-identical to table τ of the in-core fit.

    Parameters
    ----------
    sigs : (mt, n) uint32
        The mt owned tables' MinHash signatures for ALL n objects.

    Returns
    -------
    (ids, segments, b_of_id, sizes)
        As in ``rank_partition_slice``; bucket cap is n per table.
    """
    n = sigs.shape[1]

    def one_table(sig):
        """Per-table signature grouping plus the bucket-of-object map."""
        order = jnp.argsort(sig)
        ss = sig[order]
        starts = run_starts(ss)
        seg = jnp.cumsum(starts.astype(jnp.int32)) - 1
        b_of_id = jnp.zeros((n,), jnp.int32).at[order].set(seg)
        sizes = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), seg,
                                    num_segments=n)
        return order.astype(jnp.int32), seg, b_of_id, sizes

    return jax.vmap(one_table)(sigs)

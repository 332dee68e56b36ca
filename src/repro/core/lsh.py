"""LSH families used by GEEK's data-transformation phase (paper §2.2, §3.1).

- QALSH projections  : h_a(x) = a·x, a ~ N(0, I)            (Euclidean)
- MinHash            : h_pi(A) = min_{a in A} pi(a)          (Jaccard)
- DOPH               : densified one-permutation hashing     (sparse dim-reduction)

All functions are pure, fixed-shape, and jit/vmap/shard_map friendly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.utils.hashing import (UMAX32, combine2_u32, derive_hash_keys,
                                 hash_u32, mix_u32)


# ---------------------------------------------------------------------------
# QALSH (paper Eq. 3)
# ---------------------------------------------------------------------------

def qalsh_projections(key: jax.Array, d: int, m: int, dtype=jnp.float32) -> jax.Array:
    """Draw m i.i.d. QALSH functions: a (d, m) matrix with N(0,1) entries."""
    return jax.random.normal(key, (d, m), dtype=dtype)


def qalsh_hash(x: jax.Array, a: jax.Array) -> jax.Array:
    """h_a(x) = a·x for a batch: (n, d) @ (d, m) -> (n, m)."""
    return x @ a


# ---------------------------------------------------------------------------
# MinHash over padded item sets (paper Eq. 2 + static (K, L) bucketing)
# ---------------------------------------------------------------------------

def minhash_signatures(
    items: jax.Array,            # (n, s) int32/uint32 item ids
    mask: jax.Array,             # (n, s) bool — True for real items
    keys: jax.Array,             # (L, K, 2) uint32 hash keys
) -> jax.Array:
    """Per-object (L,) uint32 signatures: each is K minhashes mixed together.

    Equivalent to G(x) = (h_pi1(x), …, h_piK(x)) hashed to one bucket key.
    """
    L, K, _ = keys.shape

    def one_table(tkeys):
        sig = jnp.zeros((items.shape[0],), jnp.uint32)
        for k in range(K):
            hv = hash_u32(items, tkeys[k, 0], tkeys[k, 1])
            hv = jnp.where(mask, hv, UMAX32)
            sig = mix_u32(sig, jnp.min(hv, axis=-1))
        return sig

    return jax.vmap(one_table)(keys)  # (L, n)


def code_items(codes: jax.Array, key: jax.Array) -> jax.Array:
    """Attribute-value pairs as hashed set items: item_j = H(j, code_j).

    Turns an (n, d) categorical-code matrix into an (n, d) uint32 item-set
    view, so Jaccard over the items approximates normalized Hamming over
    the codes. Shared by the hetero bucketing pipeline and the center
    index (``model.build_center_index``).
    """
    (hk,) = derive_hash_keys(key, (1,))
    dims = jnp.arange(codes.shape[1], dtype=jnp.int32)[None, :]
    return combine2_u32(jnp.broadcast_to(dims, codes.shape), codes,
                        hk[0], hk[1])


def reduce_runs(x: jax.Array, run_len: int, op) -> jax.Array:
    """``op`` over each run of ``run_len`` consecutive entries of (P,) ``x``.

    The segment reduction of a layout whose segments are equal contiguous
    runs (``BucketTables.run_len``), as a dense reduction instead of a
    scatter into the segments.
    """
    with jax.named_scope("geek.silk.runs"):
        return op(x.reshape(-1, run_len), axis=1)


def minhash_over_segments(
    values: jax.Array,           # (P,) int32 member ids (flattened buckets)
    segments: jax.Array,         # (P,) int32 bucket index per member
    num_segments: int,
    keys: jax.Array,             # (K, 2) uint32
    valid: jax.Array | None = None,
    run_len: int | None = None,
) -> jax.Array:
    """(num_segments,) uint32 signature per bucket = K segment-min hashes mixed.

    This is MinHash applied to *buckets as sets of data ids* — the core of
    SILK (paper §3.2). With ``run_len``, segment s must be exactly entries
    [s·run_len, (s+1)·run_len) and P = num_segments·run_len; each min is
    then a reduction over the runs (``reduce_runs``), bit-identical to the
    scatter-min.
    """
    K = keys.shape[0]
    sig = jnp.zeros((num_segments,), jnp.uint32)
    for k in range(K):
        hv = hash_u32(values, keys[k, 0], keys[k, 1])
        if valid is not None:
            hv = jnp.where(valid, hv, UMAX32)
        if run_len is None:
            mins = jax.ops.segment_min(hv, segments,
                                       num_segments=num_segments)
        else:
            mins = reduce_runs(hv, run_len, jnp.min)
        sig = mix_u32(sig, mins)
    return sig


# ---------------------------------------------------------------------------
# DOPH — densified one-permutation hashing (Shrivastava & Li, ICML'14)
# ---------------------------------------------------------------------------

def doph_codes(
    sets: jax.Array,             # (n, s) int32 item ids (padded)
    mask: jax.Array,             # (n, s) bool
    key: jax.Array,
    m: int,                      # output dimensionality (e.g. 400)
) -> jax.Array:
    """(n, m) uint32 minwise codes; Pr[code_i(A) == code_i(B)] ≈ J(A, B).

    One permutation hash splits the hash range into m bins and takes the
    min per bin; empty bins borrow from the next non-empty bin to the
    right (cyclically), offset by the borrow distance ("densification via
    rotation"), which preserves the collision probability.
    """
    (hk,) = derive_hash_keys(key, (1,))
    h = hash_u32(sets, hk[0], hk[1])
    h = jnp.where(mask, h, UMAX32)
    bins = (h % jnp.uint32(m)).astype(jnp.int32)
    bins = jnp.where(mask, bins, m)  # padded items -> overflow bin

    def per_set(hrow, brow):
        vals = jax.ops.segment_min(hrow, brow, num_segments=m + 1)[:m]
        # densify: nearest non-empty bin to the right, cyclic, O(m log m)
        empty = vals == UMAX32
        idx = jnp.arange(2 * m, dtype=jnp.int32)
        nonempty2 = jnp.tile(~empty, 2)
        cand = jnp.where(nonempty2, idx, jnp.int32(2 * m))
        # suffix-min of cand: nearest non-empty index >= i
        suff = jax.lax.associative_scan(jnp.minimum, cand[::-1])[::-1]
        j = suff[:m]
        dist = (j - jnp.arange(m, dtype=jnp.int32)).astype(jnp.uint32)
        borrowed = vals[j % m] + dist * jnp.uint32(0x9E3779B1)
        return jnp.where(empty, borrowed, vals)

    return jax.vmap(per_set)(h, bins)

"""SILK — Seeding based on simILar bucKets (paper §3.2, Algorithm 4).

Pipeline per SILK hash table:
  1. MinHash each *bucket* (a set of data ids) into a K-fold signature.
  2. Buckets with colliding signatures form a *bin*.
  3. Majority voting inside each bin: ids present in more than half of the
     bin's buckets form the shared core C_shared.
  4. Cores with |C_shared| >= delta become candidate seed groups.
Repeating for L tables over-generates near-duplicate cores, so one more
SILK round over the cores themselves (min_bin_size=1, delta=1) performs the
paper's near-duplicate removal.

Everything is expressed as fixed-shape sort + segment ops (TPU-native
equivalent of the paper's GPU hash tables — see DESIGN.md §2). The L SILK
rounds run one after another (``silk_rounds``).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.buckets import BucketTables
from repro.core.lsh import minhash_over_segments, reduce_runs
from repro.utils.hashing import derive_hash_keys, run_starts


class SeedPairs(NamedTuple):
    """Padded (group, id) membership pairs for candidate seed groups."""
    group: jax.Array       # (C,) int32 — dense group index, -1 when invalid
    id: jax.Array          # (C,) int32 — data id
    valid: jax.Array       # (C,) bool
    num_groups: jax.Array  # ()  int32
    overflow: jax.Array    # ()  int32 — pairs dropped by the static cap


class Seeds(NamedTuple):
    """Final seed groups after dedup + top-k_max selection."""
    group: jax.Array       # (C,) int32 in [0, k_max) or -1
    id: jax.Array          # (C,) int32
    valid: jax.Array       # (C,) bool
    k_star: jax.Array      # ()  int32 — discovered number of seeds (paper: k*)
    k_max: int             # static budget


def compact_pairs(group, ids, valid, cap: int):
    """Keep at most ``cap`` pairs, lowest group ids first (deterministic).

    Valid pairs sort ahead of invalid ones by (group, id), so the kept
    prefix is a pure function of the *set* of valid (group, id) pairs —
    which is what makes the hierarchical distributed merge exact: the
    global top-``cap`` is always contained in the union of per-device
    top-``cap`` prefixes (``core.distributed.silk_seeding_sharded``).
    Returns ``(group, ids, valid, overflow)`` with ``overflow`` counting
    valid pairs dropped by the cap.
    """
    invalid = ~valid
    order = jnp.lexsort((ids, group, invalid))
    overflow = jnp.maximum(valid.sum() - cap, 0)
    take = order[:cap]
    return group[take], ids[take], valid[take], overflow


#: deprecated private alias (pre-PR-6 name), kept for external callers
_compact_pairs = compact_pairs


def bins_from_signatures(sig: jax.Array, bucket_valid: jax.Array):
    """Group buckets with colliding signatures into bins (paper §3.2).

    Bins are numbered in ascending-signature order — a pure function of
    the signature *values*, never of bucket layout — so in-core and
    distributed callers that feed the same (sig, valid) vectors get
    bit-identical bin structure. Invalid buckets sort last and never
    start or join a bin.

    Parameters
    ----------
    sig : (nbcap,) uint32
        Per-bucket MinHash signature (``lsh.minhash_over_segments``).
    bucket_valid : (nbcap,) bool
        True for non-empty buckets.

    Returns
    -------
    (bin_of_bucket, bin_nbuckets)
        ``bin_of_bucket`` maps bucket -> dense bin id (garbage for
        invalid buckets — never dereference those); ``bin_nbuckets`` is
        the number of buckets in each bin.
    """
    nbcap = sig.shape[0]
    border = jnp.lexsort((sig, ~bucket_valid))           # valid first, by sig
    sig_s = sig[border]
    bval_s = bucket_valid[border]
    bstarts = run_starts(sig_s, valid=bval_s)
    bin_id_s = jnp.cumsum(bstarts.astype(jnp.int32)) - 1
    bin_of_bucket = jnp.zeros((nbcap,), jnp.int32).at[border].set(bin_id_s)
    bin_nbuckets = jax.ops.segment_sum(bval_s.astype(jnp.int32), bin_id_s,
                                       num_segments=nbcap)
    return bin_of_bucket, bin_nbuckets


def rowwise_majority(bins_rows: jax.Array, bin_nbuckets: jax.Array,
                     min_bin_size: int):
    """Majority voting, re-expressed per object (one row per object).

    ``bins_rows[i, t]`` is the bin that object i's bucket in table t
    landed in (sentinel ``nbcap`` when the slot is padding). Each object
    appears exactly once per table, so the multiset of a row's bin
    values IS the multiset of that object's (bin, id) entries in the
    flattened layout ``silk_round`` votes over — sorting the row and
    counting runs yields the same (count·2 > |Bin|) majority verdicts,
    just partitioned by object instead of globally. This is what lets
    the distributed path vote on id-sharded rows and reduce only the
    small per-bin core sizes (``core.distributed``).

    Returns ``(srt, maj)``: the row-sorted bins and a mask that is True
    at the first entry of each majority run.
    """
    nbcap = bin_nbuckets.shape[0]
    srt = jnp.sort(bins_rows, axis=1)
    left = jax.vmap(lambda r: jnp.searchsorted(r, r, side="left"))(srt)
    right = jax.vmap(lambda r: jnp.searchsorted(r, r, side="right"))(srt)
    cnt = (right - left).astype(jnp.int32)
    first = jnp.concatenate(
        [jnp.ones((srt.shape[0], 1), bool), srt[:, 1:] != srt[:, :-1]],
        axis=1)
    real = srt < nbcap
    nb = bin_nbuckets[jnp.clip(srt, 0, nbcap - 1)]
    maj = first & real & (cnt * 2 > nb) & (nb >= min_bin_size)
    return srt, maj


def silk_round(
    flat_ids: jax.Array,      # (P,) int32 — bucket member ids
    flat_seg: jax.Array,      # (P,) int32 — global bucket index in [0, nbcap)
    entry_valid: jax.Array,   # (P,) bool
    nbcap: int,               # static cap on #buckets
    keys: jax.Array,          # (K, 2) uint32 minhash keys for this table
    delta: int,               # seeding threshold (paper: delta)
    min_bin_size: int,        # 2 for seeding (skip |Bin|<=1), 1 for dedup
    pair_cap: int,
    run_len: int | None = None,  # static; ``BucketTables.run_len``
) -> SeedPairs:
    """One SILK table: bucket-minhash -> bins -> majority vote -> cores.

    With ``run_len`` the per-bucket signatures and sizes are reductions
    over equal runs instead of scatters into ``nbcap`` slots; the result
    is bit-identical.
    """
    P = flat_ids.shape[0]
    ones = entry_valid.astype(jnp.int32)

    # -- bucket signatures + sizes -----------------------------------------
    if run_len is None:
        sizes = jax.ops.segment_sum(ones, flat_seg, num_segments=nbcap)
    else:
        sizes = reduce_runs(ones, run_len, jnp.sum)
    sig = minhash_over_segments(flat_ids, flat_seg, nbcap, keys,
                                valid=entry_valid, run_len=run_len)
    bucket_valid = sizes > 0

    # -- bins: group buckets by signature (shared with the sharded path) ---
    bin_of_bucket, bin_nbuckets = bins_from_signatures(sig, bucket_valid)

    # -- majority voting over (bin, id) pairs -------------------------------
    ebin = bin_of_bucket[flat_seg]
    eorder = jnp.lexsort((flat_ids, ebin, ~entry_valid))
    eb_s = ebin[eorder]
    id_s = flat_ids[eorder]
    ev_s = entry_valid[eorder]
    rstarts = run_starts(eb_s, id_s, valid=ev_s)
    run_id = jnp.cumsum(rstarts.astype(jnp.int32)) - 1
    counts = jax.ops.segment_sum(ev_s.astype(jnp.int32), run_id, num_segments=P)
    cnt_here = counts[run_id]
    nb_here = bin_nbuckets[eb_s]
    maj = rstarts & (cnt_here * 2 > nb_here) & (nb_here >= min_bin_size)

    # -- seed-group selection: |C_shared| >= delta ---------------------------
    core_size = jax.ops.segment_sum(maj.astype(jnp.int32), eb_s, num_segments=nbcap)
    keep_bin = core_size >= delta
    new_group_of_bin = jnp.cumsum(keep_bin.astype(jnp.int32)) - 1
    num_groups = keep_bin.sum().astype(jnp.int32)

    out_valid = maj & keep_bin[eb_s]
    out_group = jnp.where(out_valid, new_group_of_bin[eb_s], -1)
    g, i, v, overflow = compact_pairs(out_group, id_s, out_valid, pair_cap)
    return SeedPairs(g, i, v, num_groups, overflow)


def select_top_groups(pairs: SeedPairs, group_cap: int, k_max: int) -> Seeds:
    """Keep the k_max largest groups (static budget; paper §3.3 generates
    'more seeds than needed' — the budget is how we bound shapes)."""
    sizes = jax.ops.segment_sum(pairs.valid.astype(jnp.int32),
                                jnp.where(pairs.valid, pairs.group, group_cap),
                                num_segments=group_cap + 1)[:group_cap]
    top_sizes, top_idx = jax.lax.top_k(sizes, k_max)
    remap = jnp.full((group_cap + 1,), -1, jnp.int32)
    remap = remap.at[top_idx].set(
        jnp.where(top_sizes > 0, jnp.arange(k_max, dtype=jnp.int32), -1))
    new_group = remap[jnp.where(pairs.valid, pairs.group, group_cap)]
    valid = pairs.valid & (new_group >= 0)
    k_star = (top_sizes > 0).sum().astype(jnp.int32)
    return Seeds(jnp.where(valid, new_group, -1), pairs.id, valid, k_star, k_max)


def silk_rounds(flat_ids, flat_seg, entry_valid, nbcap: int,
                table_keys: jax.Array, delta: int, pair_cap: int,
                run_len: int | None = None):
    """The L seeding rounds of Algorithm 4, one per row of ``table_keys``.

    The rounds run one after another (``lax.map``), not vmapped: each
    sorts all m·n bucket entries, and the L rounds' sort temporaries
    held at once do not fit one chip's HBM at n = 10^6. Every fit path
    seeds through here, so they all share that bound.

    ``run_len`` is ``silk_round``'s: set it only when bucket b is
    exactly entries [b·run_len, (b+1)·run_len).

    Returns ``(group, id, valid, overflow)``: the rounds' pairs
    concatenated, round r's group ids offset by ``r * pair_cap`` so they
    stay distinct, and the rounds' total overflow.
    """
    rounds = jax.lax.map(
        lambda tk: silk_round(flat_ids, flat_seg, entry_valid, nbcap, tk,
                              delta, 2, pair_cap, run_len),
        table_keys)
    offs = (jnp.arange(table_keys.shape[0], dtype=jnp.int32)
            * pair_cap)[:, None]
    group = jnp.where(rounds.valid, rounds.group + offs, -1).reshape(-1)
    return (group, rounds.id.reshape(-1), rounds.valid.reshape(-1),
            rounds.overflow.sum())


def silk_seeding(
    buckets: BucketTables,
    key: jax.Array,
    *,
    silk_k: int,
    silk_l: int,
    delta: int,
    pair_cap: int,
    k_max: int,
) -> tuple[Seeds, jax.Array]:
    """Full SILK (Algorithm 4): L seeding rounds + one dedup round.

    Returns (seeds, total_overflow). Overflow > 0 means the static pair
    budget truncated candidate cores (increase ``pair_cap``).
    """
    flat_ids, flat_seg = buckets.flatten()
    entry_valid = jnp.ones_like(flat_ids, dtype=bool)
    nbcap = buckets.total_bucket_cap

    table_keys = derive_hash_keys(key, (silk_l + 1, silk_k))
    cat_group, cat_ids, cat_valid, round_overflow = silk_rounds(
        flat_ids, flat_seg, entry_valid, nbcap, table_keys[:silk_l], delta,
        pair_cap, buckets.run_len)
    group_cap = silk_l * pair_cap

    # dedup round: cores are buckets now; singleton bins are kept (a unique
    # core bins alone and majority-votes into itself unchanged)
    seg = jnp.where(cat_valid, cat_group, group_cap - 1)
    dedup = silk_round(cat_ids, seg, cat_valid, group_cap,
                       table_keys[silk_l], 1, 1, pair_cap)

    seeds = select_top_groups(dedup, pair_cap, k_max)
    overflow = round_overflow + dedup.overflow
    return seeds, overflow

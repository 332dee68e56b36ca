"""Bucket construction + SILK invariants (paper §3.1-3.2)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.buckets import partition_by_signature, partition_even
from repro.core.lsh import minhash_over_segments, reduce_runs
from repro.core.silk import (select_top_groups, silk_round, silk_rounds,
                             silk_seeding)
from repro.utils.hashing import derive_hash_keys


# -- Algorithm 1: even partition ---------------------------------------------

@given(st.integers(2, 6), st.integers(10, 120))
@settings(max_examples=30, deadline=None)
def test_partition_even_sizes(t, n):
    h = jnp.linspace(0, 1, n)[:, None] * jnp.ones((1, 3))
    b = partition_even(h, t)
    for row in np.array(b.segments):
        sizes = np.bincount(row, minlength=t)
        assert sizes.max() - sizes.min() <= 1      # even granularity
    # ids are a permutation per table
    for row in np.array(b.ids):
        assert sorted(row.tolist()) == list(range(n))


def test_partition_even_keeps_proximity(rng):
    """Bucket index is monotone in hash rank (proximity preserved)."""
    h = jax.random.normal(rng, (64,))[:, None]
    b = partition_even(h, 4)
    seg = np.array(b.segments[0])
    ids = np.array(b.ids[0])
    assert (np.diff(seg) >= 0).all()               # segments ascend
    # the sorted-by-hash order of ids matches ascending hash values
    hv = np.array(h[:, 0])[ids]
    assert (np.diff(hv) >= 0).all()


def test_partition_by_signature_groups_equal_sigs():
    sigs = jnp.asarray([[3, 1, 3, 2, 1, 3]], dtype=jnp.uint32)
    b = partition_by_signature(sigs)
    ids = np.array(b.ids[0])
    seg = np.array(b.segments[0])
    assert int(b.num_buckets[0]) == 3
    groups = {}
    for i, s in zip(ids, seg):
        groups.setdefault(int(s), set()).add(int(i))
    assert set(map(frozenset, groups.values())) == {
        frozenset({1, 4}), frozenset({3}), frozenset({0, 2, 5})}


# -- SILK ---------------------------------------------------------------------

def _flat_buckets(buckets: list[list[int]]):
    ids = jnp.asarray([i for b in buckets for i in b], dtype=jnp.int32)
    seg = jnp.asarray([j for j, b in enumerate(buckets) for _ in b],
                      dtype=jnp.int32)
    return ids, seg


def test_silk_majority_voting_paper_example(rng):
    """Figure 1 / Example 2 structure: four near-identical buckets with a
    shared core {1,2,4} + noise must majority-vote to exactly the core."""
    buckets = [[1, 2, 4, 7], [1, 2, 4, 8], [1, 2, 4], [1, 2, 4, 9]]
    ids, seg = _flat_buckets(buckets)
    keys = derive_hash_keys(rng, (1,))  # K=1: all buckets share min id 1
    pairs = silk_round(ids, seg, jnp.ones_like(ids, bool), 4, keys,
                       delta=1, min_bin_size=2, pair_cap=64)
    got = {int(i) for i, v in zip(pairs.id, pairs.valid) if v}
    assert got == {1, 2, 4}            # 7, 8, 9 appear once -> filtered
    assert int(pairs.num_groups) == 1


def test_silk_delta_filters_small_cores(rng):
    buckets = [[1, 2], [1, 2], [5, 6, 7, 8, 9], [5, 6, 7, 8, 9]]
    ids, seg = _flat_buckets(buckets)
    keys = derive_hash_keys(rng, (2,))
    pairs = silk_round(ids, seg, jnp.ones_like(ids, bool), 4, keys,
                       delta=3, min_bin_size=2, pair_cap=64)
    got = {int(i) for i, v in zip(pairs.id, pairs.valid) if v}
    assert got <= {5, 6, 7, 8, 9}      # the size-2 core fails delta=3


def test_silk_singleton_bins_ignored(rng):
    """|Bin| <= 1 is skipped in seeding mode (paper Algorithm 4 line 9)."""
    buckets = [[1, 2, 3], [7, 8, 9]]   # disjoint -> different signatures
    ids, seg = _flat_buckets(buckets)
    keys = derive_hash_keys(rng, (3,))
    pairs = silk_round(ids, seg, jnp.ones_like(ids, bool), 2, keys,
                       delta=1, min_bin_size=2, pair_cap=64)
    assert int(pairs.valid.sum()) == 0


def test_silk_dedup_keeps_singletons_and_merges_dups(rng):
    """Dedup mode (min_bin_size=1): unique cores survive; identical cores
    merge (paper: 'remove the near duplications of C')."""
    cores = [[1, 2, 4], [1, 2, 4], [6]]
    ids, seg = _flat_buckets(cores)
    keys = derive_hash_keys(rng, (3,))
    pairs = silk_round(ids, seg, jnp.ones_like(ids, bool), 3, keys,
                       delta=1, min_bin_size=1, pair_cap=64)
    groups = {}
    for gr, i, v in zip(pairs.group, pairs.id, pairs.valid):
        if v:
            groups.setdefault(int(gr), set()).add(int(i))
    assert sorted(map(frozenset, groups.values()), key=len) == [
        frozenset({6}), frozenset({1, 2, 4})]


def test_silk_dedup_idempotent(rng):
    """Running dedup twice changes nothing (fixed point)."""
    cores = [[1, 2, 3], [9, 10, 11], [20]]
    ids, seg = _flat_buckets(cores)
    keys = derive_hash_keys(rng, (3,))
    p1 = silk_round(ids, seg, jnp.ones_like(ids, bool), 3, keys,
                    delta=1, min_bin_size=1, pair_cap=64)
    seg2 = jnp.where(p1.valid, p1.group, 63)
    p2 = silk_round(p1.id, seg2, p1.valid, 64, keys,
                    delta=1, min_bin_size=1, pair_cap=64)
    as_sets = lambda p: sorted(
        ({int(i) for g2, i, v in zip(p.group, p.id, p.valid)
          if v and int(g2) == int(g)} for g in set(
              int(x) for x, v in zip(p.group, p.valid) if v)), key=sorted)
    assert as_sets(p1) == as_sets(p2)


def test_select_top_groups_budget(rng):
    from repro.core.silk import SeedPairs
    group = jnp.asarray([0, 0, 0, 1, 1, 2], jnp.int32)
    ids = jnp.arange(6, dtype=jnp.int32)
    valid = jnp.ones(6, bool)
    pairs = SeedPairs(group, ids, valid, jnp.int32(3), jnp.int32(0))
    seeds = select_top_groups(pairs, 8, k_max=2)
    assert int(seeds.k_star) == 2
    kept = {int(g) for g, v in zip(seeds.group, seeds.valid) if v}
    assert kept == {0, 1}              # two largest groups kept


def test_silk_seeding_end_to_end_discovers_clusters(rng):
    """Full SILK over QALSH buckets of separable blobs: k* >= true k and
    every discovered core is label-pure."""
    from repro.core import lsh
    from repro.data.synthetic import dense_blobs
    data = dense_blobs(rng, n=512, d=16, k=8, spread=0.02)
    a = lsh.qalsh_projections(jax.random.PRNGKey(7), 16, 12)
    buckets = partition_even(lsh.qalsh_hash(data.x, a), 8)
    seeds, overflow = silk_seeding(buckets, jax.random.PRNGKey(8),
                                   silk_k=2, silk_l=4, delta=4,
                                   pair_cap=4096, k_max=64)
    assert int(seeds.k_star) >= 8
    true = np.array(data.true_labels)
    dominance = []
    for g in range(int(seeds.k_star)):
        members = np.array(seeds.id)[(np.array(seeds.group) == g)
                                     & np.array(seeds.valid)]
        if len(members):
            counts = np.bincount(true[members])
            dominance.append(counts.max() / len(members))
    # cores are dominated by one true cluster each; occasional bridge cores
    # are expected — the one-pass assignment corrects them (paper §3.3)
    dominance = np.array(dominance)
    assert (dominance > 0.9).mean() > 0.75, dominance


# -- hierarchical distributed merge (core.distributed counterpart) -----------

def _hand_tables(tables: list[list[list[int]]], cap_t: int):
    """Flatten hand-built per-table bucket partitions into silk_round's
    global layout plus the per-object bucket map the sharded path votes
    over. Every object must appear in exactly one bucket per table."""
    flat_ids, flat_seg = [], []
    n = 1 + max(i for t in tables for b in t for i in b)
    b_of_id = np.zeros((len(tables), n), np.int32)
    for t, bks in enumerate(tables):
        for b, members in enumerate(bks):
            for i in members:
                flat_ids.append(i)
                flat_seg.append(t * cap_t + b)   # global, table-major
                b_of_id[t, i] = b
    return (jnp.asarray(flat_ids, jnp.int32),
            jnp.asarray(flat_seg, jnp.int32), b_of_id, n)


def _merge_two_halves(tables, cap_t, keys, delta, pair_cap):
    """Simulate the sharded path's per-round merge with pure functions:
    two 'devices' each vote on their own half of the rows, core sizes
    are summed (the psum), each half compacts its top-pair_cap pairs,
    and one more compact_pairs merges them (the all_gather + merge)."""
    from repro.core.lsh import minhash_over_segments
    from repro.core.silk import (bins_from_signatures, compact_pairs,
                                 rowwise_majority)
    flat_ids, flat_seg, b_of_id, n = _hand_tables(tables, cap_t)
    nbcap = len(tables) * cap_t
    # replicated stage: signatures + bins (identical on every device)
    sizes = jax.ops.segment_sum(jnp.ones_like(flat_ids), flat_seg,
                                num_segments=nbcap)
    sig = minhash_over_segments(flat_ids, flat_seg, nbcap, keys)
    bin_of_bucket, bin_nbuckets = bins_from_signatures(sig, sizes > 0)
    # device-local stage: majority vote on each half's rows
    goff = np.arange(len(tables), dtype=np.int32)[:, None] * cap_t
    ebin_all = np.array(bin_of_bucket)[b_of_id + goff].T      # (n, T)
    halves = [np.arange(0, n // 2), np.arange(n // 2, n)]
    cores, locals_ = [], []
    for rows in halves:
        srt, maj = rowwise_majority(jnp.asarray(ebin_all[rows]),
                                    bin_nbuckets, 2)
        cores.append(jax.ops.segment_sum(
            maj.astype(jnp.int32).reshape(-1),
            jnp.where(maj, srt, nbcap).reshape(-1),
            num_segments=nbcap + 1)[:nbcap])
        locals_.append((rows, srt, maj))
    core_size = cores[0] + cores[1]                           # the psum
    keep_bin = core_size >= delta
    new_group_of_bin = jnp.cumsum(keep_bin.astype(jnp.int32)) - 1
    # per-device compaction, then the exact global merge
    parts = []
    total = 0
    for rows, srt, maj in locals_:
        out_valid = maj & keep_bin[jnp.clip(srt, 0, nbcap - 1)]
        out_group = jnp.where(out_valid,
                              new_group_of_bin[jnp.clip(srt, 0, nbcap - 1)],
                              -1)
        out_ids = jnp.broadcast_to(
            jnp.asarray(rows, jnp.int32)[:, None], srt.shape)
        total += int(out_valid.sum())
        parts.append(compact_pairs(out_group.reshape(-1),
                                   out_ids.reshape(-1),
                                   out_valid.reshape(-1), pair_cap))
    mg = jnp.concatenate([p[0] for p in parts])
    mi = jnp.concatenate([p[1] for p in parts])
    mv = jnp.concatenate([p[2] for p in parts])
    g, i, v, _ = compact_pairs(mg, mi, mv, pair_cap)
    overflow = max(total - pair_cap, 0)
    return (g, i, v, overflow,
            int(keep_bin.sum()), flat_ids, flat_seg, nbcap)


def test_hierarchical_merge_matches_silk_round():
    """The sharded path's hierarchical merge (per-half rowwise majority,
    summed core sizes, per-half top-pair_cap compaction, one more
    compact_pairs) is bit-identical to the in-core silk_round on
    hand-built bucket tables whose seed groups span both halves."""
    # identical member sets collide under bucket MinHash -> bins:
    # {0,1,6,7} (t0,t1) and {2,3,4,5} (t0,t2) and {10,11} (t0,t1,t2)
    # become cores; {0,1,6,7} spans the device boundary at n/2 = 6.
    tables = [
        [[0, 1, 6, 7], [2, 3, 4, 5], [8, 9], [10, 11]],
        [[0, 1, 6, 7], [2, 3, 4], [5, 8, 9], [10, 11]],
        [[0, 1, 6], [2, 3, 4, 5], [7, 8, 9], [10, 11]],
    ]
    cap_t, delta = 4, 2
    keys = derive_hash_keys(jax.random.PRNGKey(3), (1, 4))[0]
    for pair_cap in (64, 5):   # uncapped, and capped below the 10 true pairs
        g, i, v, ovf, ngroups, flat_ids, flat_seg, nbcap = _merge_two_halves(
            tables, cap_t, keys, delta, pair_cap)
        ref = silk_round(flat_ids, flat_seg,
                         jnp.ones_like(flat_ids, bool), nbcap, keys,
                         delta, 2, pair_cap)
        assert ngroups == int(ref.num_groups) == 3
        np.testing.assert_array_equal(np.array(v), np.array(ref.valid))
        np.testing.assert_array_equal(np.array(g)[np.array(v)],
                                      np.array(ref.group)[np.array(ref.valid)])
        np.testing.assert_array_equal(np.array(i)[np.array(v)],
                                      np.array(ref.id)[np.array(ref.valid)])
        assert ovf == int(ref.overflow)
    # sanity: the expected cores really are the three constructed ones
    g, i, v, _, _, _, _, _ = _merge_two_halves(tables, cap_t, keys, delta, 64)
    members = {}
    for gg, ii, vv in zip(np.array(g), np.array(i), np.array(v)):
        if vv:
            members.setdefault(int(gg), set()).add(int(ii))
    assert set(map(frozenset, members.values())) == {
        frozenset({0, 1, 6, 7}), frozenset({2, 3, 4, 5}),
        frozenset({10, 11})}


# -- equal-run buckets: dense run reductions in place of scatters ------------

def _even_entries(key, T, t, run):
    """Flat entries of T tables of t buckets of ``run`` ids each, laid
    out as ``BucketTables.flatten`` lays out ``partition_even``."""
    n = t * run
    ids = jax.vmap(lambda k: jax.random.permutation(k, n))(
        jax.random.split(key, T)).astype(jnp.int32)
    seg = (jnp.arange(T, dtype=jnp.int32)[:, None] * t
           + jnp.arange(n, dtype=jnp.int32)[None, :] // run)
    return ids.reshape(-1), seg.reshape(-1)


@pytest.mark.parametrize("T,t,run,K,masked", [
    (1, 4, 5, 1, False),       # one table
    (1, 3, 7, 3, True),        # one table, entries off
    (3, 8, 7, 3, False),
    (4, 16, 1, 2, True),       # runs of one entry
    (5, 6, 32, 3, True),
])
def test_run_reduction_matches_scatter(T, t, run, K, masked):
    """On equal contiguous runs the run path is bit-identical to the
    scatter path: bucket signatures, bucket sizes, and whole SILK seeds
    over a ``partition_even`` table set."""
    key = jax.random.PRNGKey(T * 1000 + t * 10 + run)
    k_ids, k_valid, k_hash, k_silk = jax.random.split(key, 4)
    flat_ids, flat_seg = _even_entries(k_ids, T, t, run)
    nbcap = T * t
    valid = (jax.random.bernoulli(k_valid, 0.7, flat_ids.shape) if masked
             else jnp.ones(flat_ids.shape, bool))
    keys = derive_hash_keys(k_hash, (K,))
    np.testing.assert_array_equal(
        np.array(minhash_over_segments(flat_ids, flat_seg, nbcap, keys,
                                       valid=valid, run_len=run)),
        np.array(minhash_over_segments(flat_ids, flat_seg, nbcap, keys,
                                       valid=valid)))
    ones = valid.astype(jnp.int32)
    np.testing.assert_array_equal(
        np.array(reduce_runs(ones, run, jnp.sum)),
        np.array(jax.ops.segment_sum(ones, flat_seg, num_segments=nbcap)))

    h = jax.random.normal(k_ids, (t * run, T))
    buckets = partition_even(h, t)
    assert buckets.run_len == run
    kw = dict(silk_k=K, silk_l=3, delta=2, pair_cap=T * t * run, k_max=16)
    seeds, ovf = silk_seeding(buckets, k_silk, **kw)
    ref, ref_ovf = silk_seeding(buckets._replace(run_len=None), k_silk, **kw)
    for got, want in zip(seeds[:4], ref[:4]):
        np.testing.assert_array_equal(np.array(got), np.array(want))
    assert int(seeds.k_star) == int(ref.k_star)
    assert int(ovf) == int(ref_ovf)


def _nbcap_scatters(buckets, K):
    """(u32, s32, scoped) of the lowered ``silk_rounds`` over ``buckets``:
    the u32 and s32 scatters into the ``nbcap`` bucket slots, and whether
    the ``geek.silk.runs`` scope is there; with the layout's run length,
    then with none."""
    flat_ids, flat_seg = buckets.flatten()
    nbcap = buckets.total_bucket_cap
    keys = derive_hash_keys(jax.random.PRNGKey(1), (2, K))

    def counts(run_len):
        fn = jax.jit(lambda i, s, v, k: silk_rounds(
            i, s, v, nbcap, k, 2, 64, run_len))
        text = fn.lower(flat_ids, flat_seg, jnp.ones_like(flat_ids, bool),
                        keys).as_text(debug_info=True)
        out = re.findall(r'"stablehlo\.scatter".*?\}\) : \([^)]*\) -> '
                         r'(tensor<[^>]*>)', text, re.S)
        return (out.count(f"tensor<{nbcap}xui32>"),
                out.count(f"tensor<{nbcap}xi32>"), "geek.silk.runs" in text)
    return counts(buckets.run_len), counts(None)


@pytest.mark.parametrize("layout", ["even", "even_ragged", "signature"])
def test_run_path_replaces_bucket_scatters(layout):
    """The counter of the run path, read from the lowered program: on a
    ``partition_even`` layout with t | n the K signature scatter-mins and
    the sizes scatter into the bucket slots are gone (one scope
    ``geek.silk.runs`` in their place); the layouts whose buckets are not
    equal runs keep every scatter."""
    K, t = 3, 8
    if layout == "signature":
        sigs = jax.random.randint(jax.random.PRNGKey(2), (4, 48), 0, 5
                                  ).astype(jnp.uint32)
        buckets = partition_by_signature(sigs)
    else:
        n = 48 if layout == "even" else 50
        buckets = partition_even(
            jax.random.normal(jax.random.PRNGKey(2), (n, 4)), t)
    (u32, s32, scoped), (u32_ref, s32_ref, scoped_ref) = _nbcap_scatters(
        buckets, K)
    assert (u32_ref, scoped_ref) == (K, False)
    if layout == "even":
        assert buckets.run_len == 6
        assert (u32, s32, scoped) == (0, s32_ref - 1, True)
    else:
        assert buckets.run_len is None
        assert (u32, s32, scoped) == (u32_ref, s32_ref, scoped_ref)

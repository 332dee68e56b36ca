"""Multi-device GEEK — sharded fit, sharded serving, and the paper's
table-sync variant (paper §3.4, DESIGN.md §3/§10).

Two complementary distributed paths live here:

1. **Unified sharded fit** — the peer of the in-core and streaming
   paths, reached through the facade: ``GEEK(cfg).fit(data, key,
   mesh=…)`` (``repro.core.api``, which owns the sharded fit bodies and
   routes them through the same Bucketer/Seeder/Assigner protocols as
   every other mode). All three data types run the same program:
   per-device coding through the persisted ``Transform`` pipeline
   (``model.encode``), discovery, and a local one-pass assignment
   through the shared ``predict_*`` dispatch. Discovery comes in two
   modes behind the ``discovery=`` knob (DESIGN.md §10):

   - ``"sharded"`` (default, ``discover_sharded`` below) — DISTRIBUTED
     SILK discovery: device-local bucket tables after one tiled
     all_to_all of the hash columns, device-local majority voting on
     owned rows, hierarchical group merge. Bit-identical to the in-core
     fit at full coverage, with the heavy per-entry sort work split g
     ways — fit throughput scales with the mesh.
   - ``"gathered"`` (fallback for subsampled reservoirs and custom
     Bucketer/Seeder pipelines) — discovery replicated on an
     all-gathered device-local reservoir (bit-identical when the
     reservoir covers all points — the same contract as
     ``core.streaming``), bounded by ``GeekConfig.gather_cap_bytes``.

   Either way the fit returns a canonical ``GeekModel`` that
   round-trips the checkpoint manager and serves through
   ``make_predict_sharded``. This module keeps the sharding *machinery*
   (``_pad_and_shard``, ``_gather_rows``, the layout exchanges, the
   distributed-discovery stages, ``make_predict_sharded``).

2. **Table-sync dense fit** (``make_fit_dense``) — the paper's MPI
   design mapped onto JAX collectives, stage by stage:

     paper (g GPU processes, MPI)        here (g devices on a "data" mesh axis)
     ----------------------------------  -----------------------------------------
     even data split across processes    x sharded P("data", None)
     GPU QALSH hashing                   local x_l @ A (A replicated via same key)
     global sort + even partition        sample-quantile boundaries from an
                                         all-gathered stride sample (DESIGN.md §2)
     bucket synchronization              one tiled all_to_all: device j receives
     (tables -> processes, balanced)     *whole hash tables* — identical #IDs per
                                         device regardless of bucket skew (§3.4)
     local-bin majority voting           silk_round on local tables only
     C_shared synchronization            all_gather of the (small) seed pairs
     SILK dedup pass                     replicated dedup round on gathered cores
     local centroids + broadcast         psum of local partial sums / counts
     one-pass assignment                 local fused distance+argmin

   The intermediate-data load balance and communication-cost arguments
   of the paper carry over verbatim: every device owns m/g complete
   tables (same N_B·D_B), and only C_shared pairs — not bins — cross
   the wire. Discovery here is sharded but *approximate* (sample
   quantiles, per-device SILK rounds). The unified path's
   ``discovery="sharded"`` mode supersedes it for exact work: it keeps
   the same table-ownership layout but rebuilds each table from exact
   full columns, so it shards discovery WITHOUT giving up bit-identity;
   ``make_fit_dense`` remains as the paper-faithful approximate
   benchmark variant.

Mesh/axis conventions (docs/architecture.md): every entry point takes a
1-axis ``jax.sharding.Mesh`` and the *name* of the data-parallel axis
(default ``"data"``). Data is sharded ``P(axis, None)`` — rows split,
features replicated; models and seeds are replicated ``P()``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import assign as assign_mod
from repro.core import lsh
from repro.core.buckets import BucketTables
from repro.core.geek import GeekConfig, _reinsert_none
from repro.core.model import (GeekModel, patch_probed_fallback, predict,
                              predict_probed)
from repro.core.silk import select_top_groups, silk_round, silk_rounds
from repro.utils.hashing import derive_hash_keys


def _pad_and_shard(present: list, g: int, mesh, axis: str):
    """Validate row agreement, cyclically pad to a mesh multiple, shard.

    Host copies happen only when padding is needed; already-on-device
    parts with mesh-divisible rows go straight through ``device_put``
    (a no-op when the sharding already matches). Returns
    ``(device_parts, n)`` with n the true (pre-padding) row count.
    """
    rows = {int(p.shape[0]) for p in present}
    if len(rows) != 1:
        raise ValueError(f"input parts disagree on rows: {rows}")
    n = rows.pop()
    n_pad = -(-n // g) * g
    if n_pad != n:  # cyclic pad: duplicate rows, never sentinels
        present = [np.resize(np.asarray(p), (n_pad,) + p.shape[1:])
                   for p in present]
    sharding = NamedSharding(mesh, P(axis, None))
    return [jax.device_put(p, sharding) for p in present], n


# ---------------------------------------------------------------------------
# Unified sharded fit — sharding machinery (bodies live in core.api)
# ---------------------------------------------------------------------------

def _gather_rows(a_local: jax.Array, axis: str, keep: int | None) -> jax.Array:
    """All-gather per-device row blocks into one (g*s, d) array.

    Concatenation follows axis-index order, so when every device holds a
    contiguous shard of a row-sharded array the gathered result is the
    original global row order. ``keep`` statically slices off trailing
    padding rows (None keeps everything).
    """
    g = jax.lax.all_gather(a_local, axis)          # (g, s, d)
    out = g.reshape(-1, a_local.shape[1])
    return out if keep is None else out[:keep]


# ---------------------------------------------------------------------------
# Distributed SILK discovery — the default sharded fit (DESIGN.md §10)
# ---------------------------------------------------------------------------
# Device-local bucket tables, one tiled all_to_all per direction, and a
# hierarchical SILK merge — bit-identical to the in-core fit because every
# stage either replays the exact in-core math on exactly the in-core
# inputs (bucket building on full columns, bin formation on the gathered
# signature vector, the replicated dedup round) or re-partitions work
# whose result is order-independent (per-object majority rows, exact
# top-pair_cap merge, integer core-size psum).
#
# Layout conventions (g devices, axis "data", n true rows, nl = n_pad/g):
#   row layout    — (nl, ·) per device, global row id = axis_index·nl + i
#   table layout  — each device owns a contiguous block of hash tables;
#                   global bucket ids are table-major (BucketTables.flatten),
#                   so table ownership IS a bucket-id-range partition
#   wire          — hash values / signatures cross once (row -> table
#                   layout), the inverse bucket map crosses once back
#                   (table -> row layout, narrow ints under
#                   cfg.compress_collectives); per SILK round only
#                   bucket-level vectors and the top-pair_cap candidate
#                   pairs move (all_gather), never per-entry data.


def exchange_columns(x_local: jax.Array, axis: str, n: int) -> jax.Array:
    """Row layout -> column-owner layout: (nl, W) -> (n, W_pad/g).

    Pads the trailing columns to a mesh multiple (owners of pad columns
    see zeros — callers mask pad tables out downstream) and slices the
    gathered rows back to the true n, so each device holds FULL columns
    of its owned slice in global row order.
    """
    g = axis_size(axis)
    w = x_local.shape[1]
    wp = -(-w // g) * g
    if wp != w:
        x_local = jnp.pad(x_local, ((0, 0), (0, wp - w)))
    cols = jax.lax.all_to_all(x_local, axis, split_axis=1, concat_axis=0,
                              tiled=True)               # (n_pad, wp/g)
    return cols[:n]


def exchange_rows(x_local: jax.Array, axis: str, n: int) -> jax.Array:
    """Row layout -> row-owner layout: (R, nl) -> (R_pad/g, n).

    The transpose twin of ``exchange_columns`` for (tables, rows)-shaped
    payloads (MinHash signature matrices).
    """
    g = axis_size(axis)
    r = x_local.shape[0]
    rp = -(-r // g) * g
    if rp != r:
        x_local = jnp.pad(x_local, ((0, rp - r), (0, 0)))
    rows = jax.lax.all_to_all(x_local, axis, split_axis=0, concat_axis=1,
                              tiled=True)               # (rp/g, n_pad)
    return rows[:, :n]


def scatter_table_rows(b_of_id: jax.Array, axis: str, sentinel: int,
                       compress: bool) -> jax.Array:
    """Table layout -> row layout: (mt, n) bucket map -> (T_pad, nl).

    The one bulk exchange per fit that goes *back* from table owners to
    id owners: each device ends up with, for its own rows, the bucket
    those rows landed in under EVERY table. Pad rows get ``sentinel``.
    With ``compress`` the payload ships as the narrowest lossless
    unsigned int (``repro.distributed.compression``) — bucket ids are
    < sentinel, so this is exact.
    """
    g = axis_size(axis)
    n = b_of_id.shape[1]
    n_pad = -(-n // g) * g
    if n_pad != n:
        b_of_id = jnp.pad(b_of_id, ((0, 0), (0, n_pad - n)),
                          constant_values=sentinel)
    if compress:
        from repro.distributed.compression import narrow_int_all_to_all
        return narrow_int_all_to_all(b_of_id, axis, sentinel + 1,
                                     split_axis=1, concat_axis=0)
    return jax.lax.all_to_all(b_of_id, axis, split_axis=1, concat_axis=0,
                              tiled=True)               # (mt*g, nl)


def collect_seed_rows(space_local: jax.Array, ids: jax.Array,
                      valid: jax.Array, axis: str) -> jax.Array:
    """Gather the rows named by global ``ids`` onto every device.

    Each id has exactly one owner (contiguous row ranges partition the
    padded rows), so a masked-gather + psum reconstructs the rows with
    one zero-add per non-owner — exact for int codes and, for floats,
    bitwise except the (-0.0 + 0.0) corner. Invalid lanes come back as
    zero rows, matching the in-core center math where invalid seed
    lanes are weighted to zero anyway.
    """
    nl = space_local.shape[0]
    lo = jax.lax.axis_index(axis) * nl
    own = valid & (ids >= lo) & (ids < lo + nl)
    rel = jnp.clip(ids - lo, 0, nl - 1)
    rows = jnp.where(own[:, None], space_local[rel],
                     jnp.zeros((), space_local.dtype))
    return jax.lax.psum(rows, axis)


def fit_transform_sharded(kind: str, parts: tuple, tkey, cfg: GeekConfig,
                          axis: str, n: int):
    """Fit the persistent ``Transform`` from sharded rows, exactly.

    Dense (identity) and sparse (keyed DOPH) transforms are
    data-independent. The hetero quantile boundaries need global
    per-column sorts: columns are exchanged to owners
    (``exchange_columns``), each owner replays the in-core
    sort + ``quantile_boundaries`` math on its full columns, and the
    small (d_num, t_cat-1) boundary matrix is all-gathered back — the
    same boundaries ``NumericDiscretizer.fit`` computes in-core, bit
    for bit.
    """
    from repro.core.geek import make_hetero_transform, make_sparse_transform
    from repro.core.model import NumericDiscretizer, quantile_boundaries
    from repro.core.transform import HeteroTransform, IdentityTransform
    if kind == "dense":
        return IdentityTransform()
    if kind == "sparse":
        return make_sparse_transform(tkey, cfg)
    x_num = parts[0]
    if x_num is None or x_num.shape[1] == 0:
        return make_hetero_transform(x_num, cfg.t_cat)
    d_num = x_num.shape[1]
    cols = exchange_columns(x_num, axis, n)              # (n, d_num_pad/g)
    b_local = quantile_boundaries(jnp.sort(cols, axis=0), cfg.t_cat)
    b_all = jax.lax.all_gather(b_local, axis)
    boundaries = b_all.reshape(-1, b_local.shape[1])[:d_num]
    return HeteroTransform(NumericDiscretizer(boundaries))


def silk_seeding_sharded(ids_t, seg_t, sizes, bins_rows, skey,
                         cfg: GeekConfig, axis: str, *, n: int,
                         num_tables: int, cap_t: int):
    """Distributed SILK: device-local voting, hierarchical group merge.

    Per round (L rounds + dedup, same keys as ``silk_seeding``):

    1. each table owner MinHashes its owned buckets; the per-bucket
       (sig, size) vectors — bucket-level, not entry-level — are
       all-gathered and sliced to the exact in-core layout;
    2. bin formation runs replicated via the shared
       ``silk.bins_from_signatures`` (identical on every device);
    3. majority voting runs device-locally on each device's own rows
       (``silk.rowwise_majority`` over the exchanged bucket map) — the
       heavy per-entry sort, now 1/g per device; per-bin core sizes are
       an exact integer psum;
    4. each device compacts its top-``pair_cap`` candidate pairs, the
       (g, pair_cap) candidates are all-gathered, and one more
       ``silk.compact_pairs`` yields the exact global top-``pair_cap``
       (the global prefix is contained in the union of local prefixes);
       overflow is computed from the psummed true candidate count.

    The dedup round and top-group selection then run replicated on the
    merged (bounded, n-independent) pairs — literally the in-core
    ``silk_round`` + ``select_top_groups``.

    Parameters
    ----------
    ids_t, seg_t : (mt, n) int32
        Owned-table bucket entries (``rank_partition_slice`` /
        ``signature_partition_slice``).
    sizes : (mt, cap_t) int32
        Owned-table per-bucket sizes.
    bins_rows : (T_pad, nl) int32
        Exchanged bucket map: bucket of each local row under every
        global table (``scatter_table_rows``; pad slots = ``cap_t``).
    skey : PRNG key
        SILK key (replicated).
    n, num_tables, cap_t : int
        True row count, true table count, per-table bucket cap.

    Returns
    -------
    (seeds, overflow)
        The ``Seeds`` contract with GLOBAL dataset row ids, plus the
        total pair-budget overflow — both replicated and bit-identical
        to ``silk_seeding`` on the in-core bucket tables.
    """
    from repro.core.silk import (SeedPairs, bins_from_signatures,
                                 compact_pairs, rowwise_majority)
    idx = jax.lax.axis_index(axis)
    nl = bins_rows.shape[1]
    nbcap = num_tables * cap_t
    table_keys = derive_hash_keys(skey, (cfg.silk_l + 1, cfg.silk_k))

    sizes_all = jax.lax.all_gather(sizes, axis)
    sizes_all = sizes_all.reshape(-1, cap_t)[:num_tables]
    bucket_valid = (sizes_all > 0).reshape(-1)           # (nbcap,) replicated

    gid = idx * nl + jnp.arange(nl, dtype=jnp.int32)     # global row ids
    tb = bins_rows.T                                     # (nl, T_pad)
    goff = (jnp.arange(tb.shape[1], dtype=jnp.int32) * cap_t)[None, :]
    entry_real = (tb < cap_t) & (gid < n)[:, None]
    gbucket = jnp.where(entry_real, tb + goff, nbcap)    # sentinel = nbcap

    rounds = []
    for r in range(cfg.silk_l):
        # 1. bucket-level signatures: local MinHash, small all_gather
        sig_t = jax.vmap(
            lambda i, s: lsh.minhash_over_segments(i, s, cap_t,
                                                   table_keys[r])
        )(ids_t, seg_t)
        sig = jax.lax.all_gather(sig_t, axis)
        sig = sig.reshape(-1, cap_t)[:num_tables].reshape(-1)
        # 2. bins, replicated — the shared in-core helper
        bin_of_bucket, bin_nbuckets = bins_from_signatures(sig, bucket_valid)
        # 3. device-local majority vote on owned rows
        ebin = jnp.where(entry_real,
                         bin_of_bucket[jnp.clip(gbucket, 0, nbcap - 1)],
                         nbcap)
        srt, maj = rowwise_majority(ebin, bin_nbuckets, 2)
        core = jax.ops.segment_sum(
            maj.astype(jnp.int32).reshape(-1),
            jnp.where(maj, srt, nbcap).reshape(-1),
            num_segments=nbcap + 1)[:nbcap]
        core_size = jax.lax.psum(core, axis)
        keep_bin = core_size >= cfg.delta
        new_group_of_bin = jnp.cumsum(keep_bin.astype(jnp.int32)) - 1
        num_groups = keep_bin.sum().astype(jnp.int32)
        # 4. local compaction -> all_gather -> exact global top-pair_cap
        srt_c = jnp.clip(srt, 0, nbcap - 1)
        out_valid = maj & keep_bin[srt_c]
        out_group = jnp.where(out_valid, new_group_of_bin[srt_c], -1)
        out_ids = jnp.broadcast_to(gid[:, None], srt.shape)
        lg, li, lv, _ = compact_pairs(out_group.reshape(-1),
                                      out_ids.reshape(-1),
                                      out_valid.reshape(-1), cfg.pair_cap)
        mg = jax.lax.all_gather(lg, axis).reshape(-1)
        mi = jax.lax.all_gather(li, axis).reshape(-1)
        mv = jax.lax.all_gather(lv, axis).reshape(-1)
        rg, ri, rv, _ = compact_pairs(mg, mi, mv, cfg.pair_cap)
        total = jax.lax.psum(out_valid.sum().astype(jnp.int32), axis)
        overflow_r = jnp.maximum(total - cfg.pair_cap, 0)
        rounds.append(SeedPairs(rg, ri, rv, num_groups, overflow_r))

    # dedup + selection, replicated on the merged pairs — in-core verbatim
    offs = (jnp.arange(cfg.silk_l, dtype=jnp.int32) * cfg.pair_cap)[:, None]
    r_group = jnp.stack([p.group for p in rounds])
    r_ids = jnp.stack([p.id for p in rounds])
    r_valid = jnp.stack([p.valid for p in rounds])
    cat_group = jnp.where(r_valid, r_group + offs, -1).reshape(-1)
    cat_ids = r_ids.reshape(-1)
    cat_valid = r_valid.reshape(-1)
    group_cap = cfg.silk_l * cfg.pair_cap
    seg = jnp.where(cat_valid, cat_group, group_cap - 1)
    dedup = silk_round(cat_ids, seg, cat_valid, group_cap,
                       table_keys[cfg.silk_l], 1, 1, cfg.pair_cap)
    seeds = select_top_groups(dedup, cfg.pair_cap, cfg.k_max)
    overflow = sum(p.overflow for p in rounds) + dedup.overflow
    return seeds, overflow


def discover_sharded(kind: str, parts: tuple, key, cfg: GeekConfig,
                     axis: str, n: int, *, bucketer):
    """Stage 1 + 2 of the sharded fit with DISTRIBUTED discovery.

    The sharded peer of ``api.discover`` for the stock
    LSHBucketer + SILKSeeder pipeline: per-device coding, owned-table
    bucket building after one tiled all_to_all, and hierarchical SILK
    (``silk_seeding_sharded``). Key consumption routes through
    ``bucketer.split_key`` — the same anchor as every other mode — so
    seeds are bit-identical to the in-core fit at full coverage.

    Returns ``(transform, space_local, seeds, overflow)`` with
    ``space_local`` the device's coded row shard and ``seeds`` carrying
    global dataset row ids.
    """
    from repro.core.buckets import (rank_partition_slice,
                                    signature_partition_slice)
    from repro.core.geek import _code_items
    tkey, bkeys, skey = bucketer.split_key(kind, key)
    with jax.named_scope("geek.transform"):
        transform = fit_transform_sharded(kind, parts, tkey, cfg, axis, n)
        space_local = transform(*parts)                  # (nl, d')

    with jax.named_scope("geek.bucket"):
        if kind == "dense":
            (k_proj,) = bkeys
            a = lsh.qalsh_projections(k_proj, space_local.shape[1], cfg.m,
                                      dtype=space_local.dtype)
            h_local = lsh.qalsh_hash(space_local, a)     # (nl, m)
            h_cols = exchange_columns(h_local, axis, n)  # (n, m_pad/g)
            ids_t, seg_t, b_of_id, sizes = rank_partition_slice(h_cols,
                                                                cfg.t)
            num_tables, cap_t = cfg.m, cfg.t
        else:
            k_item, k_sig = bkeys
            items = _code_items(space_local, k_item)
            sig_keys = derive_hash_keys(k_sig, (cfg.bucket_l, cfg.bucket_k))
            sigs = lsh.minhash_signatures(items, jnp.ones_like(items, bool),
                                          sig_keys)      # (L, nl)
            sig_rows = exchange_rows(sigs, axis, n)      # (L_pad/g, n)
            ids_t, seg_t, b_of_id, sizes = signature_partition_slice(
                sig_rows)
            num_tables, cap_t = cfg.bucket_l, n

        # mask pad tables before shipping the bucket map back to id owners
        mt = b_of_id.shape[0]
        gt = jax.lax.axis_index(axis) * mt + jnp.arange(mt, dtype=jnp.int32)
        b_of_id = jnp.where((gt < num_tables)[:, None], b_of_id, cap_t)
        bins_rows = scatter_table_rows(b_of_id, axis, cap_t,
                                       cfg.compress_collectives)  # (T_pad, nl)

    with jax.named_scope("geek.silk"):
        seeds, overflow = silk_seeding_sharded(ids_t, seg_t, sizes,
                                               bins_rows, skey, cfg, axis,
                                               n=n, num_tables=num_tables,
                                               cap_t=cap_t)
    return transform, space_local, seeds, overflow


# ---------------------------------------------------------------------------
# Sharded serving — multi-device predict over a replicated GeekModel
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _build_predict_sharded(mesh, axis: str, none_pattern: tuple[bool, ...],
                           probes: int | None = None):
    """Compile the sharded encode+predict step for one None pattern.

    ``probes=None`` is the exact 2-output body; an int probes the
    model's center index and returns the 3-output (labels, dists,
    empty) triple for the caller's host-side fallback patch.
    """
    if probes is None:
        def body(model, *present):
            """Per-device serving body: encode + predict the row shard."""
            parts = _reinsert_none(present, none_pattern)
            return predict(model, model.encode(*parts))
        n_out = 2
    else:
        def body(model, *present):
            """Per-device probed serving body: encode + index probe."""
            parts = _reinsert_none(present, none_pattern)
            return predict_probed(model, model.encode(*parts), probes)
        n_out = 3

    n_present = sum(1 for absent in none_pattern if not absent)
    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(P(),) + (P(axis, None),) * n_present,
        out_specs=(P(axis),) * n_out,
        check_vma=False)
    return jax.jit(mapped)


def make_predict_sharded(mesh, *, axis: str = "data",
                         probes: int | None = None):
    """Build the multi-device serving counterpart of ``model.predict``.

    Each device codes and assigns its row shard with the model's
    persisted fit-time transform (``model.encode``) + the shared
    one-pass dispatch, so sharded serving is bit-identical to
    single-device ``predict(model, model.encode(*parts))`` — rows are
    independent and the model is replicated.

    Parameters
    ----------
    mesh : jax.sharding.Mesh
        1-axis device mesh.
    axis : str
        Mesh axis name to shard batch rows over.
    probes : int or None
        ``None``: exact scan. ``p >= 0``: each device probes the
        model's center index (sub-linear in k); empty-probe rows are
        then patched on the host through the exact sharded path
        (``model.patch_probed_fallback``), exactly like single-device
        ``predict(model, x, probes=p)``.

    Returns
    -------
    predict_fn : callable
        ``predict_fn(model, *parts) -> (labels, dists)`` taking RAW
        query parts — ``(x,)`` dense, ``(x_num, x_cat)`` hetero,
        ``(sets, mask)`` sparse — as global (n, d_i) arrays. Batches
        whose n is not a multiple of the mesh size are cyclically
        padded and the outputs sliced back to n. ``model`` may live on
        host or any device; it is replicated onto the mesh.
    """
    g = mesh.shape[axis]

    def predict_fn(model: GeekModel, *parts):
        """Pad + shard the batch, run the compiled sharded predict."""
        none_pattern = tuple(p is None for p in parts)
        if all(none_pattern):
            raise ValueError("every query part is None")
        dev, n = _pad_and_shard([p for p in parts if p is not None],
                                g, mesh, axis)
        fn = _build_predict_sharded(mesh, axis, none_pattern, probes)
        if probes is None:
            labels, dists = fn(model, *dev)
            return labels[:n], dists[:n]
        labels, dists, empty = fn(model, *dev)
        exact = make_predict_sharded(mesh, axis=axis)
        return patch_probed_fallback(
            labels[:n], dists[:n], empty[:n],
            lambda idx: exact(model,
                              *(None if p is None else jnp.asarray(p)[idx]
                                for p in parts)))

    return predict_fn


# ---------------------------------------------------------------------------
# Table-sync dense fit — the paper's §3.4 MPI design on collectives
# ---------------------------------------------------------------------------

def _assign_l2(x_local, centers, center_valid, cfg: GeekConfig):
    """Local one-pass assignment: fused Pallas kernel when cfg.use_pallas."""
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        return kops.distance_argmin_l2(x_local, centers, center_valid)
    return assign_mod.assign_l2(x_local, centers, center_valid,
                                block=cfg.assign_block)


def _assign_l2_accumulate(x_local, centers, center_valid, cfg: GeekConfig):
    """Assignment + per-cluster partial sums/counts for one Lloyd sweep.

    On the Pallas path the accumulation is fused into the assignment
    kernel (one-hot(labels)ᵀ @ x while the point tile is still in VMEM) —
    the sweep makes no second pass over the data.
    """
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        return kops.distance_argmin_l2(x_local, centers, center_valid,
                                       accumulate=True)
    return assign_mod.assign_l2_with_partials(x_local, centers, center_valid,
                                              block=cfg.assign_block)


def _refine_all_reduce(psums, pcnt, axis: str, cfg: GeekConfig):
    """All-reduce one Lloyd sweep's (k, d) partial sums + (k,) counts.

    With ``cfg.compress_collectives`` the f32 sums ride the int8
    quantized ring all-reduce from ``repro.distributed.compression``
    (4x fewer wire bytes; the (k,) counts stay an exact psum — they are
    tiny and divide the sums, so quantizing them would amplify error).
    The refinement loop tolerates the quantization exactly the way DDP
    training tolerates compressed gradients: each sweep re-assigns from
    scratch, so the error does not accumulate.
    """
    if cfg.compress_collectives:
        from repro.distributed.compression import compressed_psum
        mean, _ = compressed_psum(psums, axis)        # mean over devices
        rsums = mean * axis_size(axis)                # psum semantics
    else:
        rsums = jax.lax.psum(psums, axis)
    return rsums, jax.lax.psum(pcnt, axis)


def _quantile_boundaries(h_local: jax.Array, t: int, samples: int,
                         axis: str) -> jax.Array:
    """(m, t-1) global bucket boundaries from an all-gathered stride sample."""
    nl, m = h_local.shape
    s = min(samples, nl)
    stride = max(nl // s, 1)
    sample = h_local[::stride][:s]                           # (s, m)
    alls = jax.lax.all_gather(sample, axis).reshape(-1, m)   # (g*s, m)
    srt = jnp.sort(alls, axis=0)
    gs = srt.shape[0]
    q = (jnp.arange(1, t, dtype=jnp.int32) * gs) // t
    return srt[q].T                                          # (m, t-1)


def fit_dense_sharded(x_local: jax.Array, key: jax.Array, cfg: GeekConfig,
                      *, axis: str = "data", samples: int = 1024):
    """Per-device body of the paper-§3.4 table-sync fit.

    Call via shard_map (see ``make_fit_dense``). Discovery itself is
    sharded (per-device SILK on all_to_all-synchronized hash tables),
    which makes it approximate versus the in-core fit — sample-quantile
    bucket boundaries and per-device SILK rounds; the facade's sharded
    fit (``GEEK(cfg).fit(data, key, mesh=…)``) is the exact alternative.

    Parameters
    ----------
    x_local : jax.Array
        This device's (n/g, d) row shard.
    key : jax.Array
        PRNG key, replicated (all devices derive identical projections).
    cfg : GeekConfig
        Static configuration; ``cfg.m`` must divide the mesh size.
    axis : str
        Mesh axis name.
    samples : int
        Per-device rows contributed to the quantile boundary sample.

    Returns
    -------
    tuple
        ``(labels_local, centers, center_valid, k_star, radius,
        overflow)`` — labels sharded (n/g,), everything else replicated.
    """
    g = axis_size(axis)
    idx = jax.lax.axis_index(axis)
    nl, d = x_local.shape
    n = nl * g
    m, t = cfg.m, cfg.t
    assert m % g == 0, "hash tables must divide the data axis (paper §3.4)"
    mt = m // g

    k_proj, k_silk = jax.random.split(key)

    # -- phase 1: transformation (local hash, quantile partition) ----------
    a = lsh.qalsh_projections(k_proj, d, m, dtype=x_local.dtype)
    h = lsh.qalsh_hash(x_local, a)                           # (nl, m)
    bounds = _quantile_boundaries(h, t, samples, axis)       # (m, t-1)
    bid = jax.vmap(jnp.searchsorted, in_axes=(0, 1))(bounds, h)  # (m, nl)
    bid = bid.astype(jnp.int32)

    # -- bucket synchronization: device j <- whole tables [j*mt, (j+1)*mt) --
    bid_all = jax.lax.all_to_all(bid, axis, split_axis=0, concat_axis=1,
                                 tiled=True)                 # (mt, n)
    order = jnp.argsort(bid_all, axis=1)
    ids = order.astype(jnp.int32)                            # global point ids
    segments = jnp.take_along_axis(bid_all, order, axis=1)
    buckets = BucketTables(ids, segments, jnp.full((mt,), t, jnp.int32), t)

    # -- phase 2: SILK on local tables, C_shared all-gather, dedup ----------
    flat_ids, flat_seg = buckets.flatten()
    valid = jnp.ones_like(flat_ids, dtype=bool)
    table_keys = derive_hash_keys(k_silk, (cfg.silk_l + 1, cfg.silk_k))

    lgroup, lids, lvalid, round_overflow = silk_rounds(
        flat_ids, flat_seg, valid, mt * t, table_keys[:cfg.silk_l],
        cfg.delta, cfg.pair_cap)

    # C_shared sync (small!) — the paper's communication-cost trick
    gg = jax.lax.all_gather(lgroup, axis)                    # (g, L*cap)
    gi = jax.lax.all_gather(lids, axis)
    gv = jax.lax.all_gather(lvalid, axis)
    local_span = cfg.silk_l * cfg.pair_cap
    group_global = jnp.where(
        gv, gg + (jnp.arange(g, dtype=jnp.int32) * local_span)[:, None], 0)
    group_cap = g * local_span
    seg = jnp.where(gv.reshape(-1), group_global.reshape(-1), group_cap - 1)
    dedup = silk_round(gi.reshape(-1), seg, gv.reshape(-1), group_cap,
                       table_keys[cfg.silk_l], 1, 1, cfg.pair_cap)
    seeds = select_top_groups(dedup, cfg.pair_cap, cfg.k_max)
    overflow = round_overflow + dedup.overflow

    # -- phase 3: local centroids + psum, one-pass local assignment --------
    lo = idx * nl
    mine = seeds.valid & (seeds.id >= lo) & (seeds.id < lo + nl)
    rel = jnp.clip(seeds.id - lo, 0, nl - 1)
    grp = jnp.where(mine, seeds.group, cfg.k_max)
    w = mine.astype(x_local.dtype)
    sums = jax.ops.segment_sum(x_local[rel] * w[:, None], grp,
                               num_segments=cfg.k_max + 1)[:cfg.k_max]
    cnt = jax.ops.segment_sum(w, grp, num_segments=cfg.k_max + 1)[:cfg.k_max]
    sums = jax.lax.psum(sums, axis)
    cnt = jax.lax.psum(cnt, axis)
    centers = sums / jnp.maximum(cnt, 1.0)[:, None]
    center_valid = cnt > 0

    # optional Lloyd refinement: each sweep is one fused assign+accumulate
    # pass (no second pass over the data) + an all-reduce of the (k, d)
    # partials — int8-compressed when cfg.compress_collectives
    for _ in range(cfg.refine_sweeps):
        _, _, psums, pcnt = _assign_l2_accumulate(x_local, centers,
                                                  center_valid, cfg)
        rsums, rcnt = _refine_all_reduce(psums, pcnt, axis, cfg)
        centers = jnp.where((rcnt > 0)[:, None],
                            rsums / jnp.maximum(rcnt, 1.0)[:, None], centers)
        center_valid = center_valid & (rcnt > 0)

    labels, d2 = _assign_l2(x_local, centers, center_valid, cfg)
    dists = jnp.sqrt(d2)
    radius = jax.lax.pmax(
        assign_mod.cluster_radius(dists, labels, cfg.k_max), axis)
    return labels, centers, center_valid, seeds.k_star, radius, overflow


def make_fit_dense(mesh, cfg: GeekConfig, *, axis: str = "data"):
    """shard_map-wrap the table-sync distributed fit (paper §3.4).

    Parameters
    ----------
    mesh : jax.sharding.Mesh
        1-axis device mesh.
    cfg : GeekConfig
        Static configuration.
    axis : str
        Mesh axis name the input rows are sharded over.

    Returns
    -------
    callable
        Jitted ``fn(x, key)`` with x (n, d) sharded ``P(axis, None)``;
        returns ``(labels, centers, center_valid, k_star, radius,
        overflow)`` — labels sharded, the rest replicated. Raw arrays,
        not a ``GeekModel`` — this is the paper-faithful benchmark
        path; ``GEEK(cfg).fit(data, key, mesh=…)`` is the
        model-producing one.
    """
    fn = functools.partial(fit_dense_sharded, cfg=cfg, axis=axis)

    def body(xl, key):
        """Per-device table-sync fit body (fit_dense_sharded)."""
        lab, c, cv, ks, rad, ovf = fn(xl, key)
        return lab, c, cv, ks, rad, ovf

    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P()),
        out_specs=(P(axis), P(), P(), P(), P(), P()),
        check_vma=False)
    return jax.jit(mapped)

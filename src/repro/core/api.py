"""One estimator facade + pluggable stage protocols (DESIGN.md §11).

The paper's claim is that GEEK is *generic*: any data type becomes
buckets, any seeding method can sit behind the bucket layer, and
assignment is one pass. This module is that claim as an API. Instead of
a kind × mode matrix of entry points (the pre-PR-5 ``fit_dense`` /
``fit_hetero_streaming`` / ``make_fit_sharded``, removed in PR 7),
there is ONE estimator::

    from repro import GEEK, DenseData, GeekConfig

    est = GEEK(GeekConfig(k_max=256))
    model = est.fit(DenseData(x), key)              # in-core
    model = est.fit(DenseData(x), key, chunk=8192)  # out-of-core streaming
    model = est.fit(DenseData(x), key, mesh=mesh)   # sharded over a mesh
    labels, dists = est.predict(DenseData(new_x))   # serving (mesh= too)

Data kind, execution mode, and metric are orthogonal axes: the kind
rides in the ``Dataset`` spec (``DenseData`` / ``HeteroData`` /
``SparseData``), the mode in ``fit`` keywords (``chunk=`` streams,
``mesh=`` shards, both compose), and the metric follows the kind. The
per-run ``GeekResult`` (labels/dists/seeds on the fit data) lands in
``est.result_``; ``fit`` returns the persistent ``GeekModel``.

Underneath, the paper's three stages are pluggable protocols — small
frozen (hence jit-static) strategy objects:

- ``Bucketer`` — raw data → persistent ``Transform`` + code space +
  LSH bucket tables. Default ``LSHBucketer`` (QALSH rank-partition for
  dense, MinHash (K, L) for code spaces, DOPH coding for sparse).
- ``Seeder`` — buckets (or the space itself) → the ``Seeds`` contract
  (``core.silk.Seeds``). Default ``SILKSeeder``; ``KMeansPPSeeder`` and
  ``ScalableKMeansPPSeeder`` adapt the §4.1 baselines to the same
  contract, so they flow through streaming/sharding/checkpoints
  unchanged.
- ``Assigner`` — seeds → central vectors + the one-pass assignment
  (the packed/one-hot/L2 kernel dispatch). Default ``KernelAssigner``.

All execution modes route through the same ``discover`` +
``Assigner`` calls, so the bit-identity matrix (in-core ≡ streaming ≡
sharded at ``seed_cap=None``; fit ≡ predict on the fit data) holds
structurally for ANY protocol combination, not just the defaults.
This facade is the only fit surface — the pre-facade ``fit_*`` shims
finished their deprecation cycle and were removed in PR 7 (DESIGN.md
§11, deprecation policy).
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, ClassVar

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import assign as assign_mod
from repro.core import baselines as baselines_mod
from repro.core import lsh
from repro.core.buckets import (BucketTables, partition_by_signature,
                                partition_even)
from repro.core.geek import (N_PARTS, GeekConfig, GeekResult, _code_items,
                             _reinsert_none, _seed_codes, _seed_dense,
                             hetero_code_bits, make_hetero_transform,
                             make_sparse_transform)
from repro.core.model import (GeekModel, NumericDiscretizer,
                              quantile_boundaries)
from repro.core.silk import Seeds, silk_seeding
from repro.core.transform import HeteroTransform
from repro.utils.hashing import derive_hash_keys


# ---------------------------------------------------------------------------
# Dataset specs — the data-kind axis
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DenseData:
    """Homogeneous dense rows (Euclidean metric, paper Algorithm 1).

    Parameters
    ----------
    x : (n, d) array, optional
        In-core rows (numpy or JAX).
    chunks : iterable of (m_i, d) arrays, optional
        Host-chunk iterator for streaming fits (``fit(..., chunk=…)``);
        mutually exclusive with ``x``.
    """

    x: Any = None
    chunks: Any = None
    kind: ClassVar[str] = "dense"

    @property
    def parts(self) -> tuple:
        """In-core part tuple ``(x,)``; chunk-iterator datasets have none."""
        if self.chunks is not None:
            if self.x is not None:
                raise ValueError("pass exactly one of x / chunks")
            raise ValueError("chunk-iterator dataset has no in-core parts; "
                             "fit it with chunk= (streaming)")
        if self.x is None:
            raise ValueError("dense data needs x")
        return (self.x,)

    def payload(self):
        """The raw fit input (array or chunk iterator) for streaming."""
        if (self.x is None) == (self.chunks is None):
            raise ValueError("pass exactly one of x / chunks")
        return self.x if self.x is not None else self.chunks


@dataclasses.dataclass(frozen=True)
class HeteroData:
    """Heterogeneous rows (1-Jaccard metric, paper Algorithm 2).

    Parameters
    ----------
    x_num : (n, d_num) float array or None
        Numeric columns (quantile-discretized by the fitted transform).
    x_cat : (n, d_cat) int array or None
        Categorical columns. At least one of the two must be present.
    chunks : iterable of (x_num_i, x_cat_i) pairs, optional
        Host-chunk iterator for streaming fits; mutually exclusive with
        the in-core arrays.
    """

    x_num: Any = None
    x_cat: Any = None
    chunks: Any = None
    kind: ClassVar[str] = "hetero"

    @property
    def parts(self) -> tuple:
        """In-core part tuple ``(x_num, x_cat)`` (either may be None)."""
        if self.chunks is not None:
            raise ValueError("chunk-iterator dataset has no in-core parts; "
                             "fit it with chunk= (streaming)")
        if self.x_num is None and self.x_cat is None:
            raise ValueError("hetero data needs x_num and/or x_cat")
        return (self.x_num, self.x_cat)

    def payload(self):
        """The raw fit input (part tuple or chunk iterator) for streaming."""
        if self.chunks is not None:
            if self.x_num is not None or self.x_cat is not None:
                raise ValueError("pass arrays OR chunks, not both")
            return self.chunks
        return self.parts


@dataclasses.dataclass(frozen=True)
class SparseData:
    """Sparse sets (Jaccard metric via DOPH, paper Algorithm 3).

    Parameters
    ----------
    sets : (n, s_max) int array
        Padded set items.
    mask : (n, s_max) bool array
        True for real items, False for padding.
    chunks : iterable of (sets_i, mask_i) pairs, optional
        Host-chunk iterator for streaming fits; mutually exclusive with
        the in-core arrays.
    """

    sets: Any = None
    mask: Any = None
    chunks: Any = None
    kind: ClassVar[str] = "sparse"

    @property
    def parts(self) -> tuple:
        """In-core part tuple ``(sets, mask)``."""
        if self.chunks is not None:
            raise ValueError("chunk-iterator dataset has no in-core parts; "
                             "fit it with chunk= (streaming)")
        if self.sets is None or self.mask is None:
            raise ValueError("sparse data needs both sets and mask")
        return (self.sets, self.mask)

    def payload(self):
        """The raw fit input (part tuple or chunk iterator) for streaming."""
        if self.chunks is not None:
            if self.sets is not None or self.mask is not None:
                raise ValueError("pass arrays OR chunks, not both")
            return self.chunks
        return self.parts


Dataset = DenseData | HeteroData | SparseData


def as_dataset(data) -> Dataset:
    """Coerce fit/predict input to a ``Dataset`` spec.

    A bare (n, d) array means dense; hetero/sparse inputs must be
    explicit (``HeteroData`` / ``SparseData``) — a 2-tuple of arrays is
    ambiguous between them, so it is rejected rather than guessed.
    """
    if isinstance(data, (DenseData, HeteroData, SparseData)):
        return data
    if hasattr(data, "shape") and getattr(data, "ndim", 0) == 2:
        return DenseData(data)
    raise TypeError(
        f"expected DenseData/HeteroData/SparseData or a (n, d) array, got "
        f"{type(data).__name__} — tuples are ambiguous (hetero vs sparse)")


# ---------------------------------------------------------------------------
# Bucketer protocol — stage 1 (paper §3.1): data -> transform + buckets
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LSHBucketer:
    """The paper's LSH bucket layer, one scheme per data kind.

    dense  — QALSH projections, even rank-partition into t buckets/table
    hetero — quantile-discretize ++ categorical, MinHash (K, L) buckets
    sparse — keyed 16-bit DOPH codes, MinHash (K, L) buckets

    Frozen (no arrays), so it is hashable and rides through ``jit`` /
    ``shard_map`` as a static argument. A custom Bucketer implements
    the same five methods (``split_key`` / ``fit_transform`` /
    ``buckets`` / ``metric`` / ``code_bits``).
    """

    name: ClassVar[str] = "lsh"

    def split_key(self, kind: str, key: jax.Array):
        """Split the fit key into (transform, bucket-keys, seeder) parts.

        Consumption per kind matches the pre-facade ``fit_*`` entry
        points exactly — what anchored the facade's bit-identity with
        the (now removed) shims, and keeps old fits reproducible.
        """
        if kind == "dense":
            k_proj, k_silk = jax.random.split(key)
            return None, (k_proj,), k_silk
        if kind == "hetero":
            k_item, k_sig, k_silk = jax.random.split(key, 3)
            return None, (k_item, k_sig), k_silk
        # sparse: the transform derives its DOPH key from the fit key
        # itself (make_sparse_transform), the rest split as before
        _, k_item, k_sig, k_silk = jax.random.split(key, 4)
        return key, (k_item, k_sig), k_silk

    def fit_transform(self, kind: str, parts: tuple, tkey, cfg: GeekConfig,
                      *, boundaries=None):
        """Fit the persistent raw→code-space ``Transform`` for one kind.

        ``boundaries`` overrides the hetero quantile fit (the streaming
        ``boundaries="exact"`` two-pass option).
        """
        if kind == "dense":
            from repro.core.transform import IdentityTransform
            return IdentityTransform()
        if kind == "hetero":
            x_num = parts[0]
            if (boundaries is not None and x_num is not None
                    and x_num.shape[1] > 0):
                return HeteroTransform(
                    NumericDiscretizer(jnp.asarray(boundaries)))
            return make_hetero_transform(x_num, cfg.t_cat)
        return make_sparse_transform(tkey, cfg)

    def buckets(self, kind: str, space: jax.Array, bkeys: tuple,
                cfg: GeekConfig) -> BucketTables:
        """Bucket the transformed space with the kind's LSH family."""
        if kind == "dense":
            (k_proj,) = bkeys
            a = lsh.qalsh_projections(k_proj, space.shape[1], cfg.m,
                                      dtype=space.dtype)
            return partition_even(lsh.qalsh_hash(space, a), cfg.t)
        k_item, k_sig = bkeys
        items = _code_items(space, k_item)
        sig_keys = derive_hash_keys(k_sig, (cfg.bucket_l, cfg.bucket_k))
        sigs = lsh.minhash_signatures(items, jnp.ones_like(items, bool),
                                      sig_keys)
        return partition_by_signature(sigs)

    def metric(self, kind: str) -> str:
        """Assignment metric for one data kind ("l2" or "hamming")."""
        return "l2" if kind == "dense" else "hamming"

    def code_bits(self, kind: str, parts: tuple, cfg: GeekConfig) -> int:
        """Static code-width bound feeding the packed/one-hot dispatch."""
        if kind == "dense":
            return 0
        if kind == "hetero":
            return hetero_code_bits(cfg, parts[1])
        return 16  # DOPH codes are truncated to 16 bits


# ---------------------------------------------------------------------------
# Seeder protocol — stage 2 (paper §3.2): buckets/space -> Seeds
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SILKSeeder:
    """The paper's SILK seeding — k* discovered from similar buckets.

    ``needs_buckets=True``: the facade builds the Bucketer's LSH tables
    and hands them over; the seeder never touches raw data.
    """

    name: ClassVar[str] = "silk"
    needs_buckets: ClassVar[bool] = True

    def seed(self, space: jax.Array, buckets: BucketTables, key: jax.Array,
             cfg: GeekConfig) -> tuple[Seeds, jax.Array]:
        """Run L SILK rounds + dedup over the bucket tables."""
        del space
        return silk_seeding(buckets, key, silk_k=cfg.silk_k,
                            silk_l=cfg.silk_l, delta=cfg.delta,
                            pair_cap=cfg.pair_cap, k_max=cfg.k_max)


def _index_seeds(idx: jax.Array, k: int, k_max: int) -> Seeds:
    """Wrap k seed-point row indices in the ``Seeds`` contract.

    Singleton groups: group j contains exactly data row ``idx[j]``, so
    centroid centers reproduce the seed points bit-for-bit (a one-row
    segment mean is the row itself).
    """
    if k > k_max:
        raise ValueError(f"seeder k={k} exceeds GeekConfig.k_max={k_max}")
    return Seeds(group=jnp.arange(k, dtype=jnp.int32),
                 id=idx.astype(jnp.int32),
                 valid=jnp.ones((k,), bool),
                 k_star=jnp.int32(k), k_max=k_max)


@dataclasses.dataclass(frozen=True)
class KMeansPPSeeder:
    """k-means++ D^2 seeding behind the Seeds contract (k pre-specified).

    ``needs_buckets=False``: the facade skips LSH bucket construction
    and hands the seeder the whole fit key, so
    ``GEEK(cfg, seeder=KMeansPPSeeder(k)).fit(DenseData(x), key)``
    assigns exactly like ``baselines.seed_then_assign(x, k, key)``.
    L2 spaces only — D^2 sampling has no meaning over categorical codes.
    """

    k: int
    name: ClassVar[str] = "kmeans++"
    needs_buckets: ClassVar[bool] = False
    metrics: ClassVar[tuple[str, ...]] = ("l2",)

    def seed(self, space: jax.Array, buckets, key: jax.Array,
             cfg: GeekConfig) -> tuple[Seeds, jax.Array]:
        """Draw k D^2-sampled seed rows as singleton seed groups."""
        del buckets
        idx = baselines_mod.kmeanspp_indices(space, self.k, key)
        return _index_seeds(idx, self.k, cfg.k_max), jnp.int32(0)


@dataclasses.dataclass(frozen=True)
class ScalableKMeansPPSeeder:
    """k-means|| (Bahmani et al. '12) behind the Seeds contract.

    Oversample-then-reduce: ``rounds`` rounds of ``oversample``
    D^2-proportional draws, candidates weighted by attraction, reduced
    to k via weighted k-means++ (``baselines.scalable_kmeanspp_indices``).
    """

    k: int
    rounds: int = 5
    oversample: int | None = None
    name: ClassVar[str] = "scalable-kmeans++"
    needs_buckets: ClassVar[bool] = False
    metrics: ClassVar[tuple[str, ...]] = ("l2",)

    def seed(self, space: jax.Array, buckets, key: jax.Array,
             cfg: GeekConfig) -> tuple[Seeds, jax.Array]:
        """Oversample + reduce to k singleton seed groups."""
        del buckets
        idx = baselines_mod.scalable_kmeanspp_indices(
            space, self.k, key, rounds=self.rounds,
            oversample=self.oversample)
        return _index_seeds(idx, self.k, cfg.k_max), jnp.int32(0)


# ---------------------------------------------------------------------------
# Assigner protocol — stage 3 (paper §3.3): seeds -> centers + one pass
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelAssigner:
    """Central vectors + the shared one-pass kernel dispatch.

    ``build`` derives centers (centroids for l2, per-attribute modes for
    hamming) and packs them once into a ``GeekModel``; ``assign`` is the
    serving dispatch (L2 / equality / packed / one-hot, jnp or Pallas)
    that fit, streaming, sharding, and ``predict`` all share.
    """

    name: ClassVar[str] = "kernel"

    def build(self, space: jax.Array, seeds: Seeds, cfg: GeekConfig, *,
              metric: str, bits: int, transform,
              bucketer_id: str = "", seeder_id: str = "") -> GeekModel:
        """Centers + model for one fit — everything but the n-sized pass."""
        if metric == "l2":
            _, _, model = _seed_dense(space, seeds, cfg, transform=transform,
                                      bucketer_id=bucketer_id,
                                      seeder_id=seeder_id)
            return model
        return _seed_codes(space, seeds, cfg, bits=bits, transform=transform,
                           bucketer_id=bucketer_id, seeder_id=seeder_id)

    def assign(self, model: GeekModel, space: jax.Array):
        """One-pass assignment of coded rows against the model.

        Delegates to ``model.predict``'s dispatch (shape validation +
        int32 cast for code spaces included), so the fused
        fit/streaming/sharded paths and standalone serving stay one
        code path.
        """
        from repro.core.model import predict
        return predict(model, space)


# ---------------------------------------------------------------------------
# Shared discovery — every execution mode funnels through this
# ---------------------------------------------------------------------------

def discover(kind: str, parts: tuple, key: jax.Array, cfg: GeekConfig,
             bucketer, seeder, *, boundaries=None, code=None):
    """Stage 1 + 2: fit the transform, bucket, seed.

    One copy shared by the in-core, streaming-reservoir, and sharded
    fit bodies — the structural anchor of the bit-identity matrix.
    ``code`` optionally replaces the default ``transform(*parts)``
    coding with ``code(transform, parts)`` (the sharded sparse path
    codes each shard locally and gathers the narrow codes instead of
    gathering raw sets). Returns ``(transform, space, seeds,
    overflow)``.
    """
    if getattr(seeder, "needs_buckets", True):
        tkey, bkeys, skey = bucketer.split_key(kind, key)
    else:
        # no LSH keys drawn: the seeder owns the whole fit key, which is
        # what makes KMeansPPSeeder reproduce seed_then_assign(x, k, key)
        tkey, bkeys, skey = key, None, key
    with jax.named_scope("geek.transform"):
        transform = bucketer.fit_transform(kind, parts, tkey, cfg,
                                           boundaries=boundaries)
        space = transform(*parts) if code is None else code(transform, parts)
    with jax.named_scope("geek.bucket"):
        buckets = (bucketer.buckets(kind, space, bkeys, cfg)
                   if bkeys is not None else None)
    with jax.named_scope("geek.silk"):
        seeds, overflow = seeder.seed(space, buckets, skey, cfg)
    return transform, space, seeds, overflow


@functools.partial(jax.jit, static_argnames=("cfg", "kind", "none_pattern",
                                             "bucketer", "seeder",
                                             "assigner"))
def _fit_incore(present: tuple, key: jax.Array, *, cfg: GeekConfig,
                kind: str, none_pattern: tuple[bool, ...], bucketer, seeder,
                assigner) -> tuple[GeekResult, GeekModel]:
    """In-core fit: discover + build + ONE assignment pass, one program."""
    parts = _reinsert_none(present, none_pattern)
    transform, space, seeds, overflow = discover(kind, parts, key, cfg,
                                                 bucketer, seeder)
    with jax.named_scope("geek.centers"):
        model = assigner.build(space, seeds, cfg,
                               metric=bucketer.metric(kind),
                               bits=bucketer.code_bits(kind, parts, cfg),
                               transform=transform,
                               bucketer_id=bucketer.name,
                               seeder_id=seeder.name)
    with jax.named_scope("geek.assign"):
        labels, dists = assigner.assign(model, space)
    radius = assign_mod.cluster_radius(dists, labels, cfg.k_max)
    result = GeekResult(labels, dists, model.centers, model.center_valid,
                        seeds.k_star, radius, seeds, overflow)
    return result, dataclasses.replace(model, radius=radius)


@functools.partial(jax.jit, static_argnames=("cfg", "kind", "none_pattern",
                                             "bucketer", "seeder",
                                             "assigner"))
def _seed_reservoir(present: tuple, boundaries, key: jax.Array, *,
                    cfg: GeekConfig, kind: str,
                    none_pattern: tuple[bool, ...], bucketer, seeder,
                    assigner):
    """Discovery on a streaming reservoir — same pipeline as in-core,
    minus the n-sized assignment pass (``core.streaming`` streams it)."""
    parts = _reinsert_none(present, none_pattern)
    transform, space, seeds, overflow = discover(kind, parts, key, cfg,
                                                 bucketer, seeder,
                                                 boundaries=boundaries)
    with jax.named_scope("geek.centers"):
        model = assigner.build(space, seeds, cfg,
                               metric=bucketer.metric(kind),
                               bits=bucketer.code_bits(kind, parts, cfg),
                               transform=transform,
                               bucketer_id=bucketer.name,
                               seeder_id=seeder.name)
    return model, seeds, overflow


# ---------------------------------------------------------------------------
# Sharded fit — distributed discovery by default, gathered as fallback
# ---------------------------------------------------------------------------

def _resolve_discovery(discovery: str | None, seed_cap, n: int, bucketer,
                       seeder) -> str:
    """Resolve the ``discovery=`` knob to "sharded" or "gathered".

    ``None`` (the default) means *auto*: distributed SILK discovery
    (``core.distributed.discover_sharded``) when the stock
    ``LSHBucketer`` + ``SILKSeeder`` pipeline runs at full coverage,
    falling back to "gathered" — with a ``UserWarning`` naming every
    reason, since the gathered plan replicates the reservoir on every
    device — when a reservoir is requested (``seed_cap`` strictly
    subsamples) or a custom/bucket-free Bucketer/Seeder is plugged in
    (their key/bucket semantics are not distributable generically).
    Passing an explicit ``"gathered"`` acknowledges the plan and
    silences the warning.

    An *explicit* ``"sharded"`` is a promise about execution and memory
    behavior, so the same conditions raise instead of silently handing
    back a plan that replicates the reservoir on every device. Explicit
    ``"gathered"`` always gathers.
    """
    if discovery not in (None, "sharded", "gathered"):
        raise ValueError(f"discovery must be None (auto), 'sharded' or "
                         f"'gathered', got {discovery!r}")
    if discovery == "gathered":
        return "gathered"
    reasons = []
    if seed_cap is not None and seed_cap < n:
        reasons.append(f"seed_cap={seed_cap} subsamples the reservoir "
                       f"(n={n})")
    if type(bucketer) is not LSHBucketer:
        bname = getattr(bucketer, "name", type(bucketer).__name__)
        reasons.append(f"custom bucketer {bname!r} is not distributable")
    if type(seeder) is not SILKSeeder:
        sname = getattr(seeder, "name", type(seeder).__name__)
        reasons.append(f"seeder {sname!r} does not consume distributed "
                       "bucket tables")
    if not reasons:
        return "sharded"
    if discovery == "sharded":
        raise ValueError(
            "discovery='sharded' was requested explicitly but distributed "
            "discovery cannot run: " + "; ".join(reasons) + ". Pass "
            "discovery='gathered' (replicated-reservoir discovery) or "
            "leave discovery=None to let the fit fall back automatically")
    warnings.warn(
        "discovery=None fell back to gathered (replicated-reservoir) "
        "discovery: " + "; ".join(reasons) + ". Pass "
        "discovery='gathered' explicitly to acknowledge the replication "
        "and silence this warning", UserWarning, stacklevel=3)
    return "gathered"


def _check_gather_bytes(kind: str, parts: tuple, n: int,
                        cfg: GeekConfig) -> None:
    """Fail fast when the gathered reservoir would be unreasonably big.

    The gathered-discovery path replicates the full reservoir on every
    device when ``seed_cap=None``; instead of an opaque device OOM this
    raises with the estimated bytes and the ways out. Sparse data
    gathers the (n, doph_m) int32 codes, not the raw sets.
    """
    if kind == "sparse":
        est = n * cfg.doph_m * 4
    else:
        est = sum(n * int(np.prod(p.shape[1:], dtype=np.int64))
                  * p.dtype.itemsize for p in parts if p is not None)
    if est > cfg.gather_cap_bytes:
        raise ValueError(
            f"gathered discovery would replicate a ~{est:,}-byte "
            f"reservoir per device (cap: GeekConfig.gather_cap_bytes="
            f"{cfg.gather_cap_bytes:,}); use discovery='sharded' "
            "(distributed discovery, the default for the stock "
            "pipeline), pass seed_cap= to subsample the reservoir, or "
            "raise gather_cap_bytes")


@functools.lru_cache(maxsize=None)
def _build_fit_sharded(mesh, cfg: GeekConfig, kind: str, axis: str,
                       none_pattern: tuple[bool, ...], n: int, nl: int,
                       stride: int, bucketer, seeder, assigner,
                       discovery: str = "gathered"):
    """Compile the per-(shape, mesh, config, pipeline) sharded fit.

    With ``discovery="sharded"`` the body is distributed SILK discovery
    (``core.distributed.discover_sharded``: owned-table bucket building
    behind one tiled all_to_all each way + hierarchical merge) — seeds,
    labels, centers, radius bit-identical to the in-core fit, with the
    per-entry sorting work split g ways. With ``"gathered"`` the body is
    ``discover`` + ``Assigner`` on an all-gathered device-local
    reservoir (DESIGN.md §10) — ``seed_cap=None`` makes the gathered
    reservoir the dataset in row order, hence bit-identity for any
    pipeline, at replicated-discovery cost.
    """
    from repro.core.distributed import (_gather_rows, collect_seed_rows,
                                        discover_sharded)

    if discovery == "sharded":
        def body(key, *present):
            """Per-device fit body: distributed discovery, local assign."""
            parts = _reinsert_none(present, none_pattern)
            transform, space_local, seeds, overflow = discover_sharded(
                kind, parts, key, cfg, axis, n, bucketer=bucketer)
            # rebuild the seed-member rows on every device (one-owner
            # psum) and replay the in-core center math on them: the
            # segment sums see the same rows in the same order
            space_sel = collect_seed_rows(space_local, seeds.id,
                                          seeds.valid, axis)
            local_seeds = seeds._replace(
                id=jnp.arange(space_sel.shape[0], dtype=jnp.int32))
            with jax.named_scope("geek.centers"):
                model = assigner.build(
                    space_sel, local_seeds, cfg,
                    metric=bucketer.metric(kind),
                    bits=bucketer.code_bits(kind, parts, cfg),
                    transform=transform, bucketer_id=bucketer.name,
                    seeder_id=seeder.name)
            with jax.named_scope("geek.assign"):
                labels, dists = assigner.assign(model, space_local)
            radius = jax.lax.pmax(
                assign_mod.cluster_radius(dists, labels, cfg.k_max), axis)
            model = dataclasses.replace(model, radius=radius)
            return labels, dists, model, seeds, overflow

        n_present = sum(1 for absent in none_pattern if not absent)
        mapped = shard_map(
            body, mesh=mesh,
            in_specs=(P(),) + (P(axis, None),) * n_present,
            out_specs=(P(axis), P(axis), P(), P(), P()),
            check_vma=False)
        return jax.jit(mapped)

    s = -(-nl // stride)                 # per-device reservoir rows
    keep = n if stride == 1 else None    # exact slice only at stride 1

    def _remap_seed_ids(seeds: Seeds) -> Seeds:
        """Map gathered-reservoir row ids back to dataset row ids."""
        if stride == 1:
            return seeds                 # gathered order == dataset order
        gid = ((seeds.id // s) * nl + (seeds.id % s) * stride) % n
        return seeds._replace(id=jnp.where(seeds.valid, gid, seeds.id))

    def body(key, *present):
        """Per-device fit body: gather reservoir, discover, assign shard."""
        parts = _reinsert_none(present, none_pattern)
        local_codes = []   # the sparse hook records the local coding so
                           # the assignment pass reuses it (coded once)
        if kind == "sparse":
            # the sparse transform is data-independent (keyed DOPH):
            # code each shard locally and gather only the narrow codes
            def code(t, p):
                """Code the local shard, gather the strided reservoir."""
                local_codes.append(t(*p))
                return _gather_rows(local_codes[0][::stride], axis, keep)
            disc_parts = parts
        else:
            # dense/hetero gather the raw reservoir itself
            disc_parts, code = tuple(
                None if p is None else _gather_rows(p[::stride], axis, keep)
                for p in parts), None
        # the SAME discover() as the in-core and streaming bodies
        transform, space_res, seeds, overflow = discover(
            kind, disc_parts, key, cfg, bucketer, seeder, code=code)
        with jax.named_scope("geek.transform"):
            space_local = (local_codes[0] if local_codes
                           else transform(*parts))
        with jax.named_scope("geek.centers"):
            model = assigner.build(space_res, seeds, cfg,
                                   metric=bucketer.metric(kind),
                                   bits=bucketer.code_bits(kind, parts, cfg),
                                   transform=transform,
                                   bucketer_id=bucketer.name,
                                   seeder_id=seeder.name)
        with jax.named_scope("geek.assign"):
            labels, dists = assigner.assign(model, space_local)
        radius = jax.lax.pmax(
            assign_mod.cluster_radius(dists, labels, cfg.k_max), axis)
        model = dataclasses.replace(model, radius=radius)
        return labels, dists, model, _remap_seed_ids(seeds), overflow

    n_present = sum(1 for absent in none_pattern if not absent)
    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(P(),) + (P(axis, None),) * n_present,
        out_specs=(P(axis), P(axis), P(), P(), P()),
        check_vma=False)
    return jax.jit(mapped)


# ---------------------------------------------------------------------------
# The facade
# ---------------------------------------------------------------------------

@jax.jit
def _encode_predict(model: GeekModel, *parts):
    """One serving step: fit-time coding + one-pass assignment."""
    from repro.core.model import predict
    return predict(model, model.encode(*parts))


@functools.partial(jax.jit, static_argnames=("probes",))
def _encode_predict_probed(model: GeekModel, *parts, probes: int):
    """One probed serving step: coding + center-index assignment.

    Returns the raw (labels, dists, empty) triple; the caller patches
    empty-probe rows via ``model.patch_probed_fallback`` on the host.
    """
    from repro.core.model import predict_probed
    return predict_probed(model, model.encode(*parts), probes)


class GEEK:
    """The one GEEK estimator: any data kind, any mode, any pipeline.

    Parameters
    ----------
    cfg : GeekConfig
        Static pipeline configuration.
    bucketer : Bucketer
        Stage-1 strategy (default ``LSHBucketer``).
    seeder : Seeder
        Stage-2 strategy (default ``SILKSeeder``; ``KMeansPPSeeder`` /
        ``ScalableKMeansPPSeeder`` for the §4.1 baseline seeders).
    assigner : Assigner
        Stage-3 strategy (default ``KernelAssigner``).

    Attributes
    ----------
    model_ : GeekModel
        The fitted model after ``fit`` (sklearn-style trailing
        underscore).
    result_ : GeekResult
        The per-run result (labels/dists/seeds on the fit data).

    Examples
    --------
    >>> est = GEEK(GeekConfig(k_max=256))
    >>> model = est.fit(HeteroData(x_num, x_cat), key)   # in-core
    >>> labels, dists = est.predict(HeteroData(q_num, q_cat))
    >>> model = est.fit(SparseData(sets, mask), key, chunk=8192,
    ...                 seed_cap=20000)                  # out-of-core
    >>> model = est.fit(DenseData(x), key, mesh=make_mesh())  # sharded
    """

    def __init__(self, cfg: GeekConfig, *, bucketer=None, seeder=None,
                 assigner=None):
        self.cfg = cfg
        self.bucketer = LSHBucketer() if bucketer is None else bucketer
        self.seeder = SILKSeeder() if seeder is None else seeder
        self.assigner = KernelAssigner() if assigner is None else assigner
        self.model_: GeekModel | None = None
        self.result_: GeekResult | None = None

    # -- fit ----------------------------------------------------------------

    def _check_pipeline(self, kind: str) -> None:
        """Reject seeders that cannot run in this kind's metric space."""
        metric = self.bucketer.metric(kind)
        allowed = getattr(self.seeder, "metrics", None)
        if allowed is not None and metric not in allowed:
            raise ValueError(
                f"seeder {self.seeder.name!r} supports metrics {allowed}, "
                f"but {kind!r} data assigns in {metric!r}")

    def fit(self, data, key: jax.Array, *, mesh=None, mesh_axis: str = "data",
            chunk: int | None = None, seed_cap: int | None = None,
            boundaries: str = "reservoir",
            discovery: str | None = None) -> GeekModel:
        """Fit the pipeline on one dataset; the ONE entry point.

        Parameters
        ----------
        data : Dataset or (n, d) array
            ``DenseData`` / ``HeteroData`` / ``SparseData`` (a bare 2-D
            array means dense).
        key : jax.Array
            PRNG key (consumed exactly as the pre-facade ``fit_*`` did).
        mesh : jax.sharding.Mesh or None
            Shard the fit over a 1-axis mesh (``utils.compat.make_mesh``).
            Without ``chunk`` this is the sharded fit (distributed
            discovery by default — see ``discovery``); with ``chunk``
            the streamed assignment pass runs sharded.
        mesh_axis : str
            Mesh axis name rows are sharded over.
        chunk : int or None
            Stream the assignment pass over host chunks of this many
            rows (out-of-core; device memory bounded by ``chunk``).
        seed_cap : int or None
            Max reservoir rows for streamed/sharded discovery. ``None``
            keeps the whole dataset — labels/centers bit-identical to
            the in-core fit. Requires ``chunk=`` or ``mesh=``.
        boundaries : {"reservoir", "exact"}
            Hetero streaming only: where numeric quantile boundaries
            come from (see ``core.streaming``).
        discovery : {None, "sharded", "gathered"}
            Sharded fits only (``mesh=`` without ``chunk=``): ``None``
            (default, auto) distributes SILK discovery itself —
            device-local bucket tables behind a tiled all_to_all
            exchange plus a hierarchical merge, bit-identical to the
            in-core fit and scaling with the mesh — and falls back to
            "gathered" (replicated discovery on the all-gathered
            reservoir) with a ``UserWarning`` naming the reasons when
            ``seed_cap`` subsamples or a custom/bucket-free
            Bucketer/Seeder is plugged in. An
            explicit ``"sharded"`` raises in those cases instead of
            switching execution plans behind your back
            (``_resolve_discovery``); ``"gathered"`` forces the
            reservoir path.

        Returns
        -------
        GeekModel
            The persistent fitted model (also stored as ``model_``; the
            per-run ``GeekResult`` lands in ``result_``).
        """
        data = as_dataset(data)
        self._check_pipeline(data.kind)
        if boundaries not in ("reservoir", "exact"):
            raise ValueError(f"boundaries must be 'reservoir' or 'exact', "
                             f"got {boundaries!r}")
        if boundaries == "exact" and not (chunk is not None
                                          and data.kind == "hetero"):
            # the knob exists to repair a subsampled streaming reservoir's
            # quantiles — anywhere else it would be silently ignored
            raise ValueError(
                "boundaries='exact' only applies to hetero streaming fits "
                "(chunk=...); in-core and sharded fits with seed_cap=None "
                "use exact boundaries already")
        if chunk is not None:
            result, model = self._fit_streaming(data, key, chunk, seed_cap,
                                                boundaries, mesh, mesh_axis)
        elif mesh is not None:
            result, model = self._fit_sharded(data, key, mesh, mesh_axis,
                                              seed_cap, discovery)
        else:
            if seed_cap is not None:
                raise ValueError("seed_cap needs a bounded-memory mode: "
                                 "pass chunk= (streaming) or mesh= (sharded)")
            present = tuple(p for p in data.parts if p is not None)
            none_pattern = tuple(p is None for p in data.parts)
            result, model = _fit_incore(present, key, cfg=self.cfg,
                                        kind=data.kind,
                                        none_pattern=none_pattern,
                                        bucketer=self.bucketer,
                                        seeder=self.seeder,
                                        assigner=self.assigner)
        self.result_, self.model_ = result, model
        return model

    def _fit_streaming(self, data, key, chunk, seed_cap, boundaries, mesh,
                       mesh_axis):
        """Out-of-core fit: reservoir discovery + streamed assignment."""
        from repro.core import streaming as stream_mod
        cfg, kind = self.cfg, data.kind
        stream_mod._check_mesh_chunk(mesh, mesh_axis, chunk)
        chunks, n, whole = stream_mod._collect(data.payload(),
                                               N_PARTS[kind], chunk)
        if kind == "sparse" and (chunks[0][0] is None or chunks[0][1] is None):
            raise ValueError("sparse streaming needs both sets and mask")
        sample, sample_idx = stream_mod._stride_sample(chunks, n, seed_cap,
                                                       whole)
        bounds = None
        if kind == "hetero":
            # boundaries was validated in fit(); "exact" only lands here
            if boundaries == "exact" and chunks[0][0] is not None:
                # second pass over the numeric columns only, on host —
                # mirrors NumericDiscretizer.fit (same sorted values ->
                # same boundaries)
                num = (whole[0] if whole is not None
                       else np.concatenate([c[0] for c in chunks], axis=0))
                bounds = quantile_boundaries(np.sort(num, axis=0), cfg.t_cat)
        present = tuple(jax.device_put(p) for p in sample if p is not None)
        none_pattern = tuple(p is None for p in sample)
        model, seeds, overflow = _seed_reservoir(
            present, bounds, key, cfg=cfg, kind=kind,
            none_pattern=none_pattern, bucketer=self.bucketer,
            seeder=self.seeder, assigner=self.assigner)
        return stream_mod._streamed_fit(chunks, n, cfg, chunk, model, seeds,
                                        overflow, sample_idx, mesh=mesh,
                                        mesh_axis=mesh_axis,
                                        assigner=self.assigner)

    def _fit_sharded(self, data, key, mesh, mesh_axis, seed_cap, discovery):
        """Sharded fit: rows split over the mesh, discovery per knob."""
        from repro.core.distributed import _pad_and_shard
        cfg, kind, parts = self.cfg, data.kind, data.parts
        none_pattern = tuple(p is None for p in parts)
        if kind != "hetero" and any(none_pattern):
            raise ValueError(f"{kind} fit parts must not be None")
        g = mesh.shape[mesh_axis]
        dev, n = _pad_and_shard([p for p in parts if p is not None],
                                g, mesh, mesh_axis)
        mode = _resolve_discovery(discovery, seed_cap, n, self.bucketer,
                                  self.seeder)
        stride = (1 if seed_cap is None or seed_cap >= n
                  else -(-n // seed_cap))
        if mode == "gathered" and stride == 1:
            _check_gather_bytes(kind, parts, n, cfg)
        fn = _build_fit_sharded(mesh, cfg, kind, mesh_axis, none_pattern, n,
                                -(-n // g), stride, self.bucketer,
                                self.seeder, self.assigner, mode)
        labels, dists, model, seeds, overflow = fn(key, *dev)
        result = GeekResult(labels[:n], dists[:n], model.centers,
                            model.center_valid, model.k_star, model.radius,
                            seeds, overflow)
        return result, model

    # -- serving ------------------------------------------------------------

    def predict(self, data, *, model: GeekModel | None = None, mesh=None,
                mesh_axis: str = "data", batch: int | None = None,
                probes: int | None = None):
        """Assign new raw traffic with the fitted (or given) model.

        Parameters
        ----------
        data : Dataset or (n, d) array
            Raw query parts of the model's kind; coded by the persisted
            fit-time transform (``model.encode``).
        model : GeekModel or None
            Defaults to ``model_`` from the last ``fit`` (pass a
            checkpoint-restored model to serve without fitting).
        mesh : jax.sharding.Mesh or None
            Row-shard the batch over a mesh
            (``core.distributed.make_predict_sharded``) — bit-identical
            to single-device serving.
        mesh_axis : str
            Mesh axis name for sharded serving.
        batch : int or None
            Serve in partial batches of this many rows (host-side
            slicing; the ragged tail is sentinel-padded so every step
            reuses one compiled shape). Labels are row-independent, so
            batching never changes them.
        probes : int or None
            ``None`` (default): exact O(k) scan, bit-identical to the
            historical path. ``p >= 0``: probe the model's center index
            (sub-linear in k) with exact-path fallback for empty-probe
            rows — see ``core.model.predict``. Composes with ``batch=``
            and ``mesh=``.

        Returns
        -------
        (labels, dists)
            Same semantics as ``GeekResult`` on the fit data.
        """
        if model is None:
            model = self.model_
        if model is None:
            raise ValueError("not fitted: call fit() first or pass model=")
        parts = as_dataset(data).parts
        if batch is not None:
            return self._predict_batched(model, parts, batch, mesh,
                                         mesh_axis, probes)
        if mesh is not None:
            from repro.core.distributed import make_predict_sharded
            return make_predict_sharded(mesh, axis=mesh_axis,
                                        probes=probes)(model, *parts)
        if probes is None:
            return _encode_predict(model, *parts)
        from repro.core.model import patch_probed_fallback
        labels, dists, empty = _encode_predict_probed(model, *parts,
                                                      probes=int(probes))
        return patch_probed_fallback(
            labels, dists, empty,
            lambda idx: _encode_predict(
                model, *(None if p is None else jnp.asarray(p)[idx]
                         for p in parts)))

    def _predict_batched(self, model, parts, batch, mesh, mesh_axis, probes):
        """Partial-batch serving loop (one compiled shape, padded tail)."""
        from repro.core.streaming import _pad_rows
        n = next(p.shape[0] for p in parts if p is not None)
        host = tuple(None if p is None else np.asarray(p) for p in parts)
        labels = np.empty((n,), np.int32)
        dists = np.empty((n,), np.float32)
        for off in range(0, n, batch):
            m = min(batch, n - off)
            sl = tuple(None if p is None else p[off:off + m] for p in host)
            if m < batch:
                sl = tuple(None if p is None else _pad_rows(p, batch)
                           for p in sl)
            lab, dst = self.predict(self._wrap_parts(model, sl),
                                    model=model, mesh=mesh,
                                    mesh_axis=mesh_axis, probes=probes)
            labels[off:off + m] = np.asarray(lab)[:m]
            dists[off:off + m] = np.asarray(dst)[:m]
        return labels, dists

    @staticmethod
    def _wrap_parts(model, parts: tuple) -> Dataset:
        """Rewrap raw part slices in the model's Dataset kind."""
        kind = getattr(model.transform, "kind", "identity")
        if kind == "hetero":
            return HeteroData(*parts)
        if kind == "sparse":
            return SparseData(*parts)
        return DenseData(*parts)

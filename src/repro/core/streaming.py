"""Out-of-core GEEK for every data type: seed from a reservoir, stream
the transformation + assignment.

The paper's headline cost split (§3.3/§3.5) is an expensive discovery
phase (LSH transformation + SILK) followed by ONE cheap assignment pass.
The in-core ``fit_*`` entry points keep all n points resident on device
for both phases; these drivers bound device memory by the *chunk* size
instead:

  1. A stride-sampled reservoir (every ``ceil(n / seed_cap)``-th row) is
     transformed, bucketed, and SILK-seeded **once** — the only phase
     that needs super-chunk device residency, and it sees at most
     ``seed_cap`` rows. With ``seed_cap=None`` the reservoir is the whole
     dataset (stride 1) and seeds/centers are bit-identical to the
     in-core fit.
  2. The one-pass assignment streams over host-resident chunks. Each
     chunk is device_put, coded by the model's fit-time **transform**
     (identity / quantile-boundary discretization / keyed DOPH — all
     row-independent), assigned with the chunk buffers donated (XLA
     reuses them for outputs — steady-state HBM is one chunk, not n),
     and the labels land back in host numpy. The final ragged chunk is
     padded with masked sentinel rows so every step reuses one compiled
     shape; coding + assignment are independent of batch composition, so
     streamed labels are bit-identical to the in-core path regardless of
     the chunk size.

The one entry point is the facade: ``GEEK(cfg).fit(data, key,
chunk=…)`` (``repro.core.api``, DESIGN.md §11) — the facade runs
discovery on the reservoir through its Bucketer/Seeder protocols and
hands this module the chunked assignment pass (``_streamed_fit``).
This module owns the *execution machinery* only: host-side chunk
normalization, the stride-sampled reservoir, and the donated-buffer
streamed assignment loop. (The legacy ``fit_*_streaming`` shims were
removed in PR 7 per the DESIGN.md §11 deprecation clock.)

``data`` may be arrays (numpy/JAX; chunks are sliced from them) or an
iterator of host chunks (materialized chunk-by-chunk into host RAM — n
is bounded by host memory, never by HBM). Hetero numeric quantile
boundaries are estimated from the reservoir, or from the full data
with ``boundaries="exact"`` (a second host pass over the numeric
columns only).

Every driver also takes ``mesh=`` (docs/architecture.md): with a 1-axis
``jax.sharding.Mesh`` the streamed assignment pass runs **sharded** —
each chunk is split ``P(axis, None)`` across the mesh and assigned by a
``shard_map``-wrapped encode+predict step (per-device donated buffers;
the sentinel-padded ragged tail shards like any other chunk), so
steady-state per-device HBM is ``chunk / g`` rows. Coding and
assignment stay row-independent, so sharded streamed labels remain
bit-identical to the in-core fit.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import assign as assign_mod
from repro.core.geek import GeekConfig, GeekResult
from repro.core.model import GeekModel


# ---------------------------------------------------------------------------
# Host-side chunking over tuples of parallel arrays
# ---------------------------------------------------------------------------
# Every streamed input is normalized to an iterator of *part tuples*:
# (x,) for dense, (x_num, x_cat) for hetero, (sets, mask) for sparse.
# Missing optional parts (e.g. no categorical columns) stay None in every
# tuple. Pieces of unrelated sizes are re-cut AND coalesced to exactly
# ``chunk`` rows, so a reader yielding tiny shards never causes tiny
# padded device steps downstream.

def _as_piece_stream(data, nparts: int):
    """Normalize array / tuple-of-arrays / iterator input to an iterator
    of part tuples of host arrays (None slots preserved)."""
    def to_tuple(piece):
        """Coerce one streamed piece to a host-array part tuple."""
        if nparts == 1 and not isinstance(piece, (tuple, list)):
            piece = (piece,)
        if not isinstance(piece, (tuple, list)) or len(piece) != nparts:
            raise ValueError(f"expected {nparts}-part chunks, got "
                             f"{type(piece).__name__}")
        return tuple(None if p is None else np.asarray(p) for p in piece)

    if nparts == 1 and hasattr(data, "shape") \
            and getattr(data, "ndim", 0) == 2:
        yield to_tuple(data)                      # one whole array
    elif nparts > 1 and isinstance(data, (tuple, list)):
        yield to_tuple(data)                      # whole arrays in one piece
    else:
        for piece in data:
            yield to_tuple(piece)


def _cat_parts(bufs: list[tuple]) -> tuple:
    """Concatenate a list of part tuples row-wise, slot by slot."""
    out = []
    for i in range(len(bufs[0])):
        if bufs[0][i] is None:
            out.append(None)
            continue
        ps = [t[i] for t in bufs]
        out.append(np.concatenate(ps, axis=0) if len(ps) > 1
                   else np.ascontiguousarray(ps[0]))
    return tuple(out)


def _rows(parts: tuple) -> int:
    return next(p.shape[0] for p in parts if p is not None)


def _iter_chunks(pieces, chunk: int):
    """Yield part tuples of exactly ``chunk`` rows (final one ragged)."""
    buf: list[tuple] = []
    have = 0
    first_slots = None
    for parts in pieces:
        slots = tuple(p is not None for p in parts)
        if first_slots is None:
            first_slots = slots
        elif slots != first_slots:
            raise ValueError("inconsistent None parts across chunks")
        sizes = {p.shape[0] for p in parts if p is not None}
        if not sizes:
            raise ValueError("every part of a chunk is None")
        if len(sizes) != 1:
            raise ValueError(f"chunk parts disagree on rows: {sizes}")
        for p in parts:
            if p is not None and p.ndim != 2:
                raise ValueError(f"chunks must be (m, d), got {p.shape}")
        m, start = sizes.pop(), 0
        while start < m:
            take = min(chunk - have, m - start)
            buf.append(tuple(None if p is None else p[start:start + take]
                             for p in parts))
            have += take
            start += take
            if have == chunk:
                yield _cat_parts(buf)
                buf, have = [], 0
    if have:
        yield _cat_parts(buf)


def _stride_sample(chunks: list[tuple], n: int, seed_cap: int | None,
                   whole: tuple | None):
    """Reservoir for the discovery phase: stride-sampled part tuple plus
    the dataset row of each reservoir row (None when 1:1). ``whole`` is
    the original array input, reused at stride 1 to avoid a host copy."""
    stride = 1 if seed_cap is None or seed_cap >= n else -(-n // seed_cap)
    if stride == 1:
        return (whole if whole is not None else _cat_parts(chunks)), None
    bufs, idx_parts, off = [], [], 0
    for parts in chunks:
        m = _rows(parts)
        first = (-off) % stride
        bufs.append(tuple(None if p is None else p[first::stride]
                          for p in parts))
        idx_parts.append(np.arange(off + first, off + m, stride,
                                   dtype=np.int32))
        off += m
    return _cat_parts(bufs), np.concatenate(idx_parts)


# ---------------------------------------------------------------------------
# Streamed one-pass assignment (shared by all three drivers)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _assign_chunk_fn(donate: bool, mesh=None, axis: str = "data",
                     assigner=None):
    """Jitted step with the chunk buffers donated — after the first step
    the transfer reuses the previous chunk's device buffers instead of
    growing HBM. CPU cannot donate (XLA warns and ignores), so donation
    is requested only on accelerator backends.

    ``assigner`` is the facade's (hashable, jit-static) Assigner
    protocol object; ``model.encode`` IS the fit-time coding (identity /
    boundaries / keyed DOPH), so each step is the chunked transformation
    + the shared one-pass dispatch.

    With ``mesh`` the step is shard_map-wrapped: the chunk arrives
    row-sharded ``P(axis, None)``, every device assigns its shard
    through the same encode+assign dispatch, and the partial radius is
    pmax-reduced — per-device buffers are donated just like the
    single-device path.
    """
    def chunk_body(model: GeekModel, parts: tuple, k_max: int):
        """One streamed step: labels/dists for a chunk + partial radius."""
        with jax.named_scope("geek.assign"):
            labels, dists = assigner.assign(model, model.encode(*parts))
        radius = assign_mod.cluster_radius(dists, labels, k_max)
        return labels, dists, radius

    if mesh is None:
        return jax.jit(chunk_body, static_argnames=("k_max",),
                       donate_argnums=(1,) if donate else ())

    def step(model, parts, k_max):
        """Sharded chunk step: shard rows, assign, pmax the radius."""
        def body(model, parts):
            """Per-device encode+assign on this device's row shard."""
            with jax.named_scope("geek.assign"):
                labels, dists = assigner.assign(model, model.encode(*parts))
            radius = jax.lax.pmax(
                assign_mod.cluster_radius(dists, labels, k_max), axis)
            return labels, dists, radius
        return shard_map(body, mesh=mesh,
                         in_specs=(P(), P(axis, None)),
                         out_specs=(P(axis), P(axis), P()),
                         check_vma=False)(model, parts)

    return jax.jit(step, static_argnames=("k_max",),
                   donate_argnums=(1,) if donate else ())


def _pad_rows(p: np.ndarray, to: int) -> np.ndarray:
    """Sentinel rows: zeros (False for bool masks) — assignment of real
    rows is row-independent, padded rows are sliced away on host."""
    pad = np.zeros((to - p.shape[0], p.shape[1]), p.dtype)
    return np.concatenate([p, pad], axis=0)


def _check_mesh_chunk(mesh, mesh_axis: str, chunk: int) -> None:
    """Sharded streaming needs chunk rows to split evenly over the mesh."""
    if mesh is None:
        return
    g = mesh.shape[mesh_axis]
    if chunk % g:
        raise ValueError(f"chunk={chunk} must be a multiple of the mesh "
                         f"size g={g} for sharded streaming")


def _streamed_fit(chunks: list[tuple], n: int, cfg: GeekConfig, chunk: int,
                  seed_model, seeds, overflow, sample_idx, *,
                  mesh=None, mesh_axis: str = "data", assigner=None):
    """Pass 2: stream chunks through transform + assignment, assemble the
    host-numpy GeekResult and the radius-finalized model. ``assigner``
    is the facade's Assigner protocol object. With ``mesh`` each chunk
    is row-sharded over the mesh for the assignment step."""
    if assigner is None:                      # default = the kernel dispatch
        from repro.core.api import KernelAssigner
        assigner = KernelAssigner()
    model = jax.block_until_ready(seed_model)
    if sample_idx is not None:
        # keep the fit_* contract: Seeds.id holds dataset row ids, not
        # positions inside the strided reservoir
        seeds = seeds._replace(id=jnp.asarray(sample_idx)[seeds.id])

    labels = np.empty((n,), np.int32)
    dists = np.empty((n,), np.float32)
    radius = np.zeros((cfg.k_max,), np.float32)
    assign_chunk = _assign_chunk_fn(jax.default_backend() != "cpu",
                                    mesh, mesh_axis, assigner)
    sharding = (NamedSharding(mesh, P(mesh_axis, None))
                if mesh is not None else None)
    off = 0
    for parts in chunks:
        m = _rows(parts)
        if m < chunk:  # ragged tail: pad with masked sentinel rows
            parts = tuple(None if p is None else _pad_rows(p, chunk)
                          for p in parts)
        dev = tuple(None if p is None else jax.device_put(p, sharding)
                    for p in parts)
        lab, dst, rad = assign_chunk(model, dev, cfg.k_max)
        lab, dst = np.asarray(lab)[:m], np.asarray(dst)[:m]
        if m < chunk:
            # recompute on host so sentinel rows contribute no radius
            rad = np.zeros((cfg.k_max,), np.float32)
            np.maximum.at(rad, lab, dst)
        labels[off:off + m] = lab
        dists[off:off + m] = dst
        np.maximum(radius, np.asarray(rad), out=radius)
        off += m

    result = GeekResult(labels, dists, np.asarray(model.centers),
                        np.asarray(model.center_valid),
                        np.asarray(model.k_star), radius, seeds,
                        np.asarray(overflow))
    model = dataclasses.replace(model, radius=jnp.asarray(radius))
    return result, model


def _collect(data, nparts: int, chunk: int):
    """Pass 0 shared prologue: host chunks + row count + the no-copy
    ``whole`` tuple when the input was in-memory arrays."""
    if chunk < 1:
        raise ValueError(f"chunk must be positive, got {chunk}")
    whole = None
    if nparts == 1 and hasattr(data, "shape") \
            and getattr(data, "ndim", 0) == 2:
        whole = (np.asarray(data),)
    elif nparts > 1 and isinstance(data, (tuple, list)):
        whole = tuple(None if p is None else np.asarray(p) for p in data)
    chunks = list(_iter_chunks(_as_piece_stream(data, nparts), chunk))
    if not chunks:
        raise ValueError("streaming fit: empty input")
    return chunks, sum(_rows(c) for c in chunks), whole

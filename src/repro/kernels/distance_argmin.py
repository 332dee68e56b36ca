"""Fused pairwise-distance + argmin Pallas TPU kernel family.

GEEK's one-pass assignment (paper §3.3) is O(n·d·k) — the dominant compute
term (Table 1). The naive XLA path materializes the (n, k) distance matrix
in HBM; these kernels stream (bn, d) point tiles and (bk, d) center tiles
through VMEM and keep only the running (min, argmin) per point — HBM
traffic drops from O(n·k) to O(n·d + k·d + n).

Grid: (n/bn, k/bk), k innermost; scratch (running min/argmin) persists
across the k sweep and is flushed on the last k tile. The n axis is
embarrassingly parallel; the k axis carries the scratch, so the grid is
annotated ``dimension_semantics=("parallel", "arbitrary")``.

Tile sizes default to the shape-keyed autotuner (`repro.kernels.autotune`)
instead of hard-coded blocks; explicit bn/bk overrides remain for
tests and benchmarking.

Three metrics:
  - L2             : ‖x‖² − 2·x·c + ‖c‖²  (MXU matmul). Optionally also
                     accumulates per-cluster partial sums + counts in the
                     same pass (``accumulate=True``) so a Lloyd refinement
                     sweep needs no second pass over the data.
  - Hamming        : #mismatching attributes (VPU equality counts, one
                     attribute per step) ≈ (1 − Jaccard)·d on minwise codes.
  - Hamming packed : same counts on bit-packed uint32 codes — XOR +
                     field-collapse + SWAR popcount over d·b/32 words,
                     32/b× less HBM traffic and no (bn, bk, d) equality
                     broadcast (DESIGN.md §6).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune
from repro.kernels.pack import field_mismatch_count

_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))
# the accumulating variant writes one shared (k, d) output block from every
# n-tile, so neither grid axis is safe to parallelize
_PARAMS_ACC = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary"))


def _resolve_tiles(kind: str, n: int, k: int, d: int, itemsize: int, bn, bk):
    tc = autotune.select_tiles(kind, n, k, d, itemsize)
    return bn or tc.bn, bk or tc.bk


# ---------------------------------------------------------------------------
# L2 kernel
# ---------------------------------------------------------------------------

def _l2_kernel(x_ref, c_ref, csq_ref, valid_ref, lab_ref, dist_ref,
               minv, argv, *, bk: int, nk: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        minv[...] = jnp.full_like(minv, jnp.float32(jnp.finfo(jnp.float32).max))
        argv[...] = jnp.zeros_like(argv)

    x = x_ref[...].astype(jnp.float32)                       # (bn, d)
    c = c_ref[...].astype(jnp.float32)                       # (bk, d)
    dot = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (bn, bk)
    xsq = jnp.sum(x * x, axis=-1, keepdims=True)
    d2 = xsq - 2.0 * dot + csq_ref[...]                      # (bn, bk)
    d2 = jnp.where(valid_ref[...] != 0, d2,
                   jnp.float32(jnp.finfo(jnp.float32).max))

    local_arg = jnp.argmin(d2, axis=-1).astype(jnp.int32)    # (bn,)
    local_min = jnp.min(d2, axis=-1)
    better = local_min[:, None] < minv[...]
    argv[...] = jnp.where(better, local_arg[:, None] + j * bk, argv[...])
    minv[...] = jnp.where(better, local_min[:, None], minv[...])

    @pl.when(j == nk - 1)
    def _flush():
        lab_ref[...] = argv[...]
        dist_ref[...] = jnp.maximum(minv[...], 0.0)


def _l2_acc_kernel(x_ref, c_ref, csq_ref, valid_ref,
                   lab_ref, dist_ref, sum_ref, cnt_ref,
                   minv, argv, *, bk: int, nk: int, bn: int, n: int,
                   kpad: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        minv[...] = jnp.full_like(minv, jnp.float32(jnp.finfo(jnp.float32).max))
        argv[...] = jnp.zeros_like(argv)

    @pl.when((i == 0) & (j == 0))
    def _init_acc():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    x = x_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    dot = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    xsq = jnp.sum(x * x, axis=-1, keepdims=True)
    d2 = xsq - 2.0 * dot + csq_ref[...]
    d2 = jnp.where(valid_ref[...] != 0, d2,
                   jnp.float32(jnp.finfo(jnp.float32).max))

    local_arg = jnp.argmin(d2, axis=-1).astype(jnp.int32)
    local_min = jnp.min(d2, axis=-1)
    better = local_min[:, None] < minv[...]
    argv[...] = jnp.where(better, local_arg[:, None] + j * bk, argv[...])
    minv[...] = jnp.where(better, local_min[:, None], minv[...])

    @pl.when(j == nk - 1)
    def _flush():
        lab_ref[...] = argv[...]
        dist_ref[...] = jnp.maximum(minv[...], 0.0)
        # fused per-cluster accumulation: one-hot(labels)ᵀ @ x on the MXU —
        # the refinement sweep reuses the x tile already resident in VMEM
        row = i * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0)
        onehot = ((argv[...] == jax.lax.broadcasted_iota(
            jnp.int32, (bn, kpad), 1)) & (row < n)).astype(jnp.float32)
        sum_ref[...] += jax.lax.dot_general(
            onehot, x, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (kpad, d)
        cnt_ref[...] += jnp.sum(onehot, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bn", "bk", "accumulate",
                                             "interpret"))
def distance_argmin_l2(x: jax.Array, centers: jax.Array, center_valid: jax.Array,
                       *, bn: int | None = None, bk: int | None = None,
                       accumulate: bool = False, interpret: bool = False):
    """Returns (labels (n,), squared distance (n,)); with ``accumulate=True``
    additionally (per-cluster partial sums (k, d) f32, counts (k,) f32).
    Shapes are padded to tile multiples here; d is zero-padded (zeros do
    not change L2). ``accumulate`` pins the (k_pad, d_pad) accumulator in
    VMEM for the whole grid — it needs k·d ≲ 2M f32 within TPU v5e's 16 MiB
    scoped-VMEM default (``autotune.V5E_SCOPED_VMEM_BYTES``); use
    the jnp second pass (`assign_l2_with_partials`) beyond that."""
    n, d = x.shape
    k = centers.shape[0]
    if accumulate and (bn is None or bk is None):
        # the (k_pad, d_pad) accumulator block stays VMEM-resident for the
        # whole grid — carve it out of the tile budget (k_pad <= pad(k, 1024)
        # since every bk candidate divides 1024)
        acc_bytes = (-(-k // 1024) * 1024) * ((d + (-d) % 128) + 1) * 4
        budget = max(autotune.DEFAULT_BUDGET - acc_bytes,
                     autotune.DEFAULT_BUDGET // 8)
        tc = autotune.select_tiles("l2", n, k, d, 4, budget)
        bn, bk = bn or tc.bn, bk or tc.bk
    else:
        bn, bk = _resolve_tiles("l2", n, k, d, 4, bn, bk)
    npad, kpad = (-n) % bn, (-k) % bk
    dpad = (-d) % 128  # MXU lane alignment
    xp = jnp.pad(x.astype(jnp.float32), ((0, npad), (0, dpad)))
    cp = jnp.pad(centers.astype(jnp.float32), ((0, kpad), (0, dpad)))
    vp = jnp.pad(center_valid.astype(jnp.int32), (0, kpad))
    csq = jnp.sum(cp * cp, axis=-1)[None, :]                 # (1, k+pad)
    np_, kp_, dp_ = n + npad, k + kpad, d + dpad
    nk = kp_ // bk

    in_specs = [
        pl.BlockSpec((bn, dp_), lambda i, j: (i, 0)),
        pl.BlockSpec((bk, dp_), lambda i, j: (j, 0)),
        pl.BlockSpec((1, bk), lambda i, j: (0, j)),
        pl.BlockSpec((1, bk), lambda i, j: (0, j)),
    ]
    out_specs = [
        pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((np_, 1), jnp.int32),
        jax.ShapeDtypeStruct((np_, 1), jnp.float32),
    ]
    scratch = [pltpu.VMEM((bn, 1), jnp.float32),
               pltpu.VMEM((bn, 1), jnp.int32)]

    if not accumulate:
        lab, dist = pl.pallas_call(
            functools.partial(_l2_kernel, bk=bk, nk=nk),
            grid=(np_ // bn, nk),
            in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=scratch,
            compiler_params=_PARAMS,
            cost_estimate=autotune.cost_l2(np_, kp_, dp_),
            interpret=interpret,
        )(xp, cp, csq, vp[None, :])
        return lab[:n, 0], dist[:n, 0]

    out_specs += [
        pl.BlockSpec((kp_, dp_), lambda i, j: (0, 0)),
        pl.BlockSpec((1, kp_), lambda i, j: (0, 0)),
    ]
    out_shape += [
        jax.ShapeDtypeStruct((kp_, dp_), jnp.float32),
        jax.ShapeDtypeStruct((1, kp_), jnp.float32),
    ]
    lab, dist, sums, cnt = pl.pallas_call(
        functools.partial(_l2_acc_kernel, bk=bk, nk=nk, bn=bn, n=n, kpad=kp_),
        grid=(np_ // bn, nk),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_PARAMS_ACC,
        cost_estimate=autotune.cost_l2(np_, 2 * kp_, dp_),
        interpret=interpret,
    )(xp, cp, csq, vp[None, :])
    return lab[:n, 0], dist[:n, 0], sums[:k, :d], cnt[0, :k]


# ---------------------------------------------------------------------------
# Hamming kernels (unpacked codes, and bit-packed codes — DESIGN.md §6)
#
# Both walk the attributes (words) one at a time, statically unrolled: the
# point column x[:, a] (bn, 1) meets the center row cT[a] (1, bk) of the
# transposed center tile, so every step is a lane-dense (bn, bk) VPU op.
# A (bn, bk, chunk) broadcast would put the narrow chunk on the 128-wide
# lane axis, and Mosaic only lowers a lane offset it can prove 128-aligned,
# so a dynamic chunk loop does not compile for the chip.
# ---------------------------------------------------------------------------

def _int_min_argmin(dist):
    """Row min and first index of the min of an int32 (bn, bk) block,
    both (bn, 1). Mosaic lowers argmin for float32 only, so the index is
    a min over the column iota where the row min is hit — the same
    lowest-index tie-break as ``jnp.argmin``."""
    m = jnp.min(dist, axis=-1, keepdims=True)
    col = jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    arg = jnp.min(jnp.where(dist == m, col, jnp.int32(dist.shape[1])),
                  axis=-1, keepdims=True)
    return m, arg


def _ham_kernel(x_ref, ct_ref, valid_ref, lab_ref, dist_ref, minv, argv,
                *, bk: int, nk: int, d: int, packed_bits: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        minv[...] = jnp.full_like(minv, jnp.int32(jnp.iinfo(jnp.int32).max))
        argv[...] = jnp.zeros_like(argv)

    x, ct = x_ref[...], ct_ref[...]                          # (bn, d), (d, bk)
    dist = jnp.zeros((x.shape[0], bk), jnp.int32)
    for a in range(d):
        xa = x[:, a:a + 1]                                   # (bn, 1)
        ca = ct[a:a + 1, :]                                  # (1, bk)
        if packed_bits:
            dist = dist + field_mismatch_count(xa ^ ca, packed_bits)
        else:
            dist = dist + (xa != ca).astype(jnp.int32)
    dist = jnp.where(valid_ref[...] != 0, dist,
                     jnp.int32(jnp.iinfo(jnp.int32).max))

    local_min, local_arg = _int_min_argmin(dist)
    better = local_min < minv[...]
    argv[...] = jnp.where(better, local_arg + j * bk, argv[...])
    minv[...] = jnp.where(better, local_min, minv[...])

    @pl.when(j == nk - 1)
    def _flush():
        lab_ref[...] = argv[...]
        dist_ref[...] = minv[...]


def _hamming_call(x, centers, center_valid, *, kind: str, packed_bits: int,
                  bn, bk, interpret: bool):
    """Pad, transpose the centers and run ``_ham_kernel``. Padded center
    columns are masked invalid; padded point rows are sliced off."""
    n, d = x.shape
    k = centers.shape[0]
    bn, bk = _resolve_tiles(kind, n, k, d, 4, bn, bk)
    npad, kpad = (-n) % bn, (-k) % bk
    xp = jnp.pad(x, ((0, npad), (0, 0)))
    ct = jnp.pad(centers, ((0, kpad), (0, 0))).T             # (d, k+pad)
    vp = jnp.pad(center_valid.astype(jnp.int32), (0, kpad))
    np_, kp_ = n + npad, k + kpad
    nk = kp_ // bk
    cost = (autotune.cost_hamming_packed(np_, kp_, d) if packed_bits
            else autotune.cost_hamming(np_, kp_, d))

    lab, dist = pl.pallas_call(
        functools.partial(_ham_kernel, bk=bk, nk=nk, d=d,
                          packed_bits=packed_bits),
        grid=(np_ // bn, nk),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bk), lambda i, j: (0, j)),
            pl.BlockSpec((1, bk), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.int32),
            jax.ShapeDtypeStruct((np_, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn, 1), jnp.int32),
            pltpu.VMEM((bn, 1), jnp.int32),
        ],
        compiler_params=_PARAMS,
        cost_estimate=cost,
        interpret=interpret,
    )(xp, ct, vp[None, :])
    return lab[:n, 0], dist[:n, 0]


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def distance_argmin_hamming(codes: jax.Array, centers: jax.Array,
                            center_valid: jax.Array, *, bn: int | None = None,
                            bk: int | None = None,
                            interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Returns (labels (n,), mismatch count (n,) int32)."""
    return _hamming_call(codes.astype(jnp.int32), centers.astype(jnp.int32),
                         center_valid, kind="hamming", packed_bits=0,
                         bn=bn, bk=bk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bits", "bn", "bk", "interpret"))
def distance_argmin_hamming_packed(packed: jax.Array, packed_centers: jax.Array,
                                   center_valid: jax.Array, *, bits: int,
                                   bn: int | None = None, bk: int | None = None,
                                   interpret: bool = False
                                   ) -> tuple[jax.Array, jax.Array]:
    """Fused argmin over bit-packed codes (see `repro.kernels.pack`).

    packed: (n, w) uint32, packed_centers: (k, w) uint32, both from
    `pack_codes(..., bits)`. Returns (labels (n,), mismatch count (n,)).
    Unused fields of the last word are zero on both sides, so they never
    mismatch — counts are exact with no sentinel correction.
    """
    return _hamming_call(packed.astype(jnp.uint32),
                         packed_centers.astype(jnp.uint32), center_valid,
                         kind="hamming_packed", packed_bits=bits,
                         bn=bn, bk=bk, interpret=interpret)

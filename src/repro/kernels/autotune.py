"""Shape-keyed tile selection for the assignment kernels (DESIGN.md §7).

Picks (bn, bk) from (kind, n, k, d, itemsize) under an explicit VMEM
budget, favouring the largest tiles that fit (bigger tiles = more
MXU/VPU work per HBM byte). Results are lru_cached per shape key, so
repeated `pallas_call` tracing reuses the decision, and every choice is
deterministic — no on-device timing, which keeps the selector usable at
trace time inside jit.

Budget model: Pallas double-buffers grid inputs, so input tiles count
twice; the (bn, bk) distance temp counts once for L2 and three times
for the Hamming kernels (running count, step value, compare mask), which
also hold one lane-broadcast point column per attribute. The
budget is half of the scoped-VMEM limit the TPU compiler applies to a
kernel by default (16 MiB on TPU v5e), leaving the other half for the
compiler's own scratch.

The Hamming kernels unroll one (bn, bk) VPU step per attribute, so
their tile is also capped at ``VPU_TILE_ELEMS`` elements (64 vregs of
32-bit lanes): a larger tile adds code and spills, not throughput.
"""
from __future__ import annotations

import dataclasses
import functools

from jax.experimental import pallas as pl

#: default scoped-VMEM limit of one Pallas kernel on TPU v5e, as the v5e
#: compiler reports it when a kernel overflows ("limit 16.00M")
V5E_SCOPED_VMEM_BYTES = 16 * 1024 * 1024
DEFAULT_BUDGET = V5E_SCOPED_VMEM_BYTES // 2

#: largest (bn, bk) tile of the per-attribute Hamming kernels
VPU_TILE_ELEMS = 256 * 256

_TILE_CANDIDATES = (1024, 512, 256, 128, 64, 32, 16, 8)


@dataclasses.dataclass(frozen=True)
class TileConfig:
    bn: int      # point-tile rows
    bk: int      # center-tile rows


def _pad_to(x: int, m: int) -> int:
    return x + (-x) % m


def _vmem_bytes(kind: str, bn: int, bk: int, d: int, itemsize: int) -> int:
    if kind == "l2":
        dp = _pad_to(d, 128)
        inputs = (bn * dp + bk * dp) * itemsize + 2 * bk * 4
        temp = bn * bk * 4
    elif kind in ("hamming", "hamming_packed"):
        # d is the attribute count, or the packed word count. Mosaic keeps
        # every lane-broadcast point column x[:, a] live at once, each
        # (bn, 128) lanes wide: bn * d * 512 bytes (measured against the
        # compiler's VMEM stack report)
        inputs = (bn * d + d * bk) * 4 + bk * 4
        temp = 3 * bn * bk * 4 + bn * d * 128 * 4
    else:
        raise ValueError(f"unknown kernel kind {kind!r}")
    scratch = bn * 8 + 2 * bn * 4
    return 2 * inputs + temp + scratch


@functools.lru_cache(maxsize=512)
def select_tiles(kind: str, n: int, k: int, d: int, itemsize: int = 4,
                 budget: int = DEFAULT_BUDGET) -> TileConfig:
    """Largest (bn, bk) fitting the VMEM budget for this shape.

    ``d`` is the attribute count for "l2"/"hamming" and the packed word
    count for "hamming_packed". Ties prefer taller point tiles (bn) —
    the n grid axis is the parallel one.
    """
    cap = max(n, k, 8) * 2
    bns = [t for t in _TILE_CANDIDATES if t <= max(_pad_to(n, 8), 8) * 2 and t <= cap]
    # bk is the lane axis of the (1, bk) center-side blocks: a multiple of
    # 128, or one tile covering all of k (Mosaic's block-shape rule)
    bks = [t for t in _TILE_CANDIDATES
           if t <= max(_pad_to(k, 8), 8) * 2 and t <= cap
           and (t % 128 == 0 or t >= k)]
    elems = VPU_TILE_ELEMS if kind != "l2" else None
    best = None
    best_score = (-1, -1)
    for bn in bns or [8]:
        for bk in bks or [8]:
            if elems is not None and bn * bk > elems:
                continue
            if _vmem_bytes(kind, bn, bk, d, itemsize) > budget:
                continue
            score = (bn * bk, bn)
            if best is None or score > best_score:
                best, best_score = TileConfig(bn, bk), score
    if best is None:  # pathological d: take the smallest tile regardless
        best = TileConfig(8, 8)
    return best


# ---------------------------------------------------------------------------
# Cost estimates — let the XLA scheduler overlap the kernel correctly.
# ---------------------------------------------------------------------------

def cost_l2(n: int, k: int, d: int, itemsize: int = 4) -> pl.CostEstimate:
    return pl.CostEstimate(
        flops=2 * n * k * d + 5 * n * k,
        bytes_accessed=n * d * itemsize + k * d * itemsize + n * 8,
        transcendentals=0,
    )


def cost_hamming(n: int, k: int, d: int) -> pl.CostEstimate:
    return pl.CostEstimate(
        flops=2 * n * k * d,
        bytes_accessed=n * d * 4 + k * d * 4 + n * 8,
        transcendentals=0,
    )


def cost_hamming_packed(n: int, k: int, w: int) -> pl.CostEstimate:
    # ~12 VPU ops per word: xor + log2(b) fold + 5-step SWAR popcount + add
    return pl.CostEstimate(
        flops=12 * n * k * w,
        bytes_accessed=n * w * 4 + k * w * 4 + n * 8,
        transcendentals=0,
    )

"""The least time the chip could take for a piece of work.

The work is counted from the algorithm, not from what an
implementation does: a later PR that changes how the work is done
cannot change what the share is measured against.
"""
from __future__ import annotations


def assign_work(rows: float, batches: float, k: int, d: int) -> tuple:
    """(FLOP, bytes) of assigning ``rows`` float32 rows of width ``d``
    to the nearest of ``k`` centers in ``batches`` batches: one
    multiply-add per row, center and feature; each row read once
    (4 d bytes) and its label and distance written (8 bytes); the
    centers read once per batch (4 k d bytes)."""
    flops = 2.0 * rows * k * d
    nbytes = rows * (4.0 * d + 8.0) + batches * 4.0 * k * d
    return flops, nbytes


def bound(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds, which bound): the larger of compute time at the
    bf16 peak and memory time at the HBM bandwidth."""
    t_compute = flops / float(peaks["bf16_flops_per_s"])
    t_memory = nbytes / float(peaks["hbm_bytes_per_s"])
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"


def share(least_s: float, busy_s: float) -> float | None:
    """The least time over the time the device was busy; None when it
    was not busy."""
    if busy_s <= 0:
        return None
    return least_s / busy_s

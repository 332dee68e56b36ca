"""The program's own spans, for readers of per-layer metrics that the
program records itself (``repro.utils.spans``).

The program records a span only while a profiler session is live, and
a run of a cell opens one session, its traced window: so the record a
reader finds after the window holds the window's spans, and an
untraced run's holds none. A checkout whose program has no span record
gives ``None``, as does a record with no span of the name.
"""
from __future__ import annotations

import numpy as np


def records(name: str) -> dict | None:
    """The spans of ``name``: ``start``, ``end`` (ns), ``req``, ``batch``."""
    try:
        from repro.utils import spans
    except ImportError:
        return None
    r = spans.records(name)
    return r if r["start"].size else None


def durations_ms(name: str) -> np.ndarray | None:
    r = records(name)
    return None if r is None else (r["end"] - r["start"]) / 1e6


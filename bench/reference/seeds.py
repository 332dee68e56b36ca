"""Plain reference for a fit's seeding: the seed groups it published,
held to the clusters the benchmark generated (each row's ground truth
from ``bench/data.py``), on the host in numpy.

Two numbers, lower is better:

- ``seed_impurity``: the share of published (group, row) pairs whose
  row lies outside its group's majority generated cluster. A seed group
  is a SILK core, rows that share buckets across hash tables; on
  separated clusters it lies inside one. Wrong groups read near 1.
- ``rows_per_true_seed``: the data's rows over the distinct rows the
  seeding placed in a group of their own generated cluster: the inverse
  of the seeding's recall. Fewer SILK rounds, or seed pairs truncated,
  leave fewer rows seeded and read higher; no seed at all reads inf.
"""
from __future__ import annotations

import numpy as np


def readings(truth, seed_group, seed_id, seed_valid) -> dict:
    """The seeding's two numbers against the generated clusters."""
    truth = np.asarray(truth, np.int64)
    sv = np.asarray(seed_valid, bool)
    group = np.asarray(seed_group, np.int64)[sv]
    rows = np.asarray(seed_id, np.int64)[sv]
    if rows.size == 0:
        return {"seed_impurity": 0.0, "rows_per_true_seed": float("inf")}
    y = truth[rows]
    width = int(truth.max()) + 1
    # majority generated cluster of each group: the most frequent
    # (group, cluster) pair per group, the lowest cluster on a tie
    pair, count = np.unique(group * width + y, return_counts=True)
    pg, py = pair // width, pair % width
    order = np.lexsort((py, -count, pg))
    first = np.ones(order.size, bool)
    first[1:] = pg[order][1:] != pg[order][:-1]
    maj_group, maj_y = pg[order][first], py[order][first]
    own = y == maj_y[np.searchsorted(maj_group, group)]
    true_rows = np.unique(rows[own]).size
    return {"seed_impurity": float(1.0 - own.sum() / rows.size),
            "rows_per_true_seed": (truth.size / true_rows if true_rows
                                   else float("inf"))}

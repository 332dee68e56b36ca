"""engine.rows_per_batch.open: real rows per micro-batch over the
window, from the deltas of ``ClusterServer.stats()``: ``rows_served``
over ``batches``."""


def read(m):
    c = getattr(m, "counters", None)
    if not c or c["batches"] <= 0:
        return None
    return c["rows_served"] / c["batches"]

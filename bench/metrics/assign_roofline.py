"""assign_roofline: the least time the chip could take to assign the
real rows served in the traced window (``bench/roofline.py``: 2 rows k d
FLOP at the bf16 peak against the rows' and centers' bytes at the HBM
bandwidth, whichever is longer) over the time the device was busy in
that window, in %. Every device op of a serving cell belongs to the
serve step, so no program name is needed. Real rows only: the pad
ladder's padding is not work the rows needed."""
from bench import roofline


def read(m):
    c = getattr(m, "trace_counters", None)
    if m.trace is None or not c or c["rows_served"] <= 0:
        return None
    flops, nbytes = roofline.assign_work(c["rows_served"], c["batches"],
                                         m.k, m.d)
    least, _ = roofline.bound(flops, nbytes, m.peaks)
    s = roofline.share(least, m.trace.busy_s)
    return None if s is None else 100.0 * s

"""The device's idle share of the traced window, in %: one less the
union of the intervals in which an XLA operation ran, over the window's
length (``bench/tracing.py``)."""


def read(m):
    if m.trace is None:
        return None
    return 100.0 * m.trace.idle_share

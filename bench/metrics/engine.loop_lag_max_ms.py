"""engine.loop_lag_max_ms: the longest of the program's span
``geek.engine.lag`` in the traced window: how late the engine's worker
ran again after a timed wait on its queue (the flush deadline) was due.
0 means no wake was late; ``None`` that no timed wake was recorded.
Read from the program's own span record (``bench/program_spans.py``)."""
from bench import program_spans


def read(m):
    d = program_spans.durations_ms("geek.engine.lag")
    return None if d is None else float(d.max())

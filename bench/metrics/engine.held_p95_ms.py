"""engine.held_p95_ms: the 95th percentile, over the traced window's
micro-batches, of the program's span ``geek.engine.held``: how long an
issued micro-batch waits for the worker to start its retire (the retire
comes at the next flush, or when the queue is empty). Read from the
program's own span record (``bench/program_spans.py``)."""
from bench import harness, program_spans


def read(m):
    d = program_spans.durations_ms("geek.engine.held")
    return None if d is None else harness.percentile(d, 95)

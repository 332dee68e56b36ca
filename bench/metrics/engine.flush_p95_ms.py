"""engine.flush_p95_ms: the 95th percentile, over the traced window's
micro-batches, of the program's span ``geek.engine.flush``: a
micro-batch's host work before it runs: concatenating its requests,
padding to the ladder rung, the copy to the device and issuing the
jitted step. Read from the program's own span record
(``bench/program_spans.py``)."""
from bench import harness, program_spans


def read(m):
    d = program_spans.durations_ms("geek.engine.flush")
    return None if d is None else harness.percentile(d, 95)

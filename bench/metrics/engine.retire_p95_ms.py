"""engine.retire_p95_ms: the 95th percentile, over the traced window's
micro-batches, of the program's span ``geek.engine.retire``: a
micro-batch's retire: blocking on its results, the copy to the host,
resolving its requests' futures. Read from the program's own span record
(``bench/program_spans.py``)."""
from bench import harness, program_spans


def read(m):
    d = program_spans.durations_ms("geek.engine.retire")
    return None if d is None else harness.percentile(d, 95)

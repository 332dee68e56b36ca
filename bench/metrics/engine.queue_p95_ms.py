"""engine.queue_p95_ms: the 95th percentile, over the traced window's
requests, of the program's span ``geek.engine.queue``: a request's wait
in the engine's queue, from ``ClusterServer.submit`` to its being taken
into a micro-batch. Read from the program's own span record
(``bench/program_spans.py``)."""
from bench import harness, program_spans


def read(m):
    d = program_spans.durations_ms("geek.engine.queue")
    return None if d is None else harness.percentile(d, 95)

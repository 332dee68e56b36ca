"""Driver ``serve_open``: an open loop through ``POST /v1/assign``.

Poisson arrivals at the traffic's fixed ``rate_rps``, each request
``rows_per_request`` rows of the query pool, sent by client processes
on a schedule regardless of what is outstanding. End-to-end:
``serve_p95_ms``, the 95th percentile of latency over every request of
the window, each timed from when it was due to when its whole response
was received (``bench/serving.py``).
"""
from bench import serving


def run(ctx):
    return serving.serve(ctx)

"""frontend.self_p50_ms: the median, over the traced window's requests,
of the HTTP front end's own time for a request: its span
``geek.frontend.request`` (body read to response written) less its
child ``geek.frontend.wait`` (the wait for the engine), joined by the
request's id. Read from the program's own span record
(``bench/program_spans.py``)."""
import numpy as np

from bench import harness, program_spans


def read(m):
    req = program_spans.records("geek.frontend.request")
    wait = program_spans.records("geek.frontend.wait")
    if req is None or wait is None:
        return None
    order = np.argsort(wait["req"], kind="stable")
    ids = wait["req"][order]
    pos = np.minimum(np.searchsorted(ids, req["req"]), ids.size - 1)
    hit = ids[pos] == req["req"]
    if not hit.any():
        return None
    w = order[pos[hit]]
    own = ((req["end"][hit] - req["start"][hit])
           - (wait["end"][w] - wait["start"][w])) / 1e6
    return harness.percentile(own, 50)

"""frontend.overhead_p50_ms: the median over the window's requests of
the client's send-to-receive time less that request's engine time: the
HTTP round trip, parse and respond."""
from bench import harness


def read(m):
    w = getattr(m, "frontend_overhead_ms", None)
    if w is None or len(w) == 0:
        return None
    return harness.percentile(w, 50)

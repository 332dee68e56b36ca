"""engine.wait_p95_ms: the 95th percentile, over the window's requests,
of the engine's time for a request: from ``ClusterServer.submit`` to its
future resolved, as the proxy behind the front end times it."""
from bench import harness


def read(m):
    w = getattr(m, "engine_wait_ms", None)
    if w is None or len(w) == 0:
        return None
    return harness.percentile(w, 95)

"""fit.sort_share: device self time of XLA ``sort`` instructions over
device busy time in the traced window, in %. Fusions the compiler built
around a sort are not counted: their trace names do not say what they
hold."""
from bench import tracing


def read(m):
    if m.trace is None:
        return None
    s = m.trace.share(lambda name: tracing.opcode(name) == "sort")
    return None if s is None else 100.0 * s

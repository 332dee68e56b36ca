"""The profiler trace: recording a window, and reducing it to numbers.

A traced run records the JAX profiler's trace of its window with the
Python tracer off, and marks the window with a host span
``bench.window`` (``jax.profiler.TraceAnnotation``), so that the
window's bounds and the device's operations share the profiler's
clock. The reduction reads:

- ``busy``: the union of the intervals in which an XLA operation ran on
  each TPU, clipped to the window, averaged over the chips;
- op self time: an operation's duration less that of the operations
  nested in it on the same line (a ``while`` holds its body's ops);
- idle gaps: the intervals of the window in which no operation ran,
  each named by the ``bench.*`` host span that overlaps it most.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Events:
    """Trace events as (name, start_ns, end_ns) on the profiler clock."""

    device_ops: dict        # chip index -> list of (name, start, end)
    host_spans: list        # bench.* spans: (name, start, end)


@dataclasses.dataclass
class Summary:
    """A traced window reduced to what the readers need."""

    window_ns: tuple        # (start, end)
    busy_ns: float          # union of op intervals, mean over chips
    op_self_ns: dict        # op name -> self time, summed over chips
    gaps: list              # (label, seconds), longest first
    chips: int = 1

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / (self.window_ns[1] - self.window_ns[0])

    def share(self, select) -> float | None:
        """Self time of the ops whose name ``select`` accepts, over busy
        time; None when the device was never busy."""
        if self.busy_ns <= 0:
            return None
        t = sum(v for k, v in self.op_self_ns.items() if select(k))
        return t / self.chips / self.busy_ns


@contextlib.contextmanager
def recording(log_dir: str):
    """Record the profiler trace of the block, marked as the window."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


def find_trace(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = f32[4096]{0:T(1024)} fusion(...)`` -> ``fusion.12
    f32[4096]``: the instruction and its result shape."""
    m = re.match(r"%?([\w\-.]+) = (\(?[a-z0-9]+\[[0-9,]*\])?", hlo_text)
    if not m:
        return hlo_text[:80]
    return m.group(1) + (" " + m.group(2).lstrip("(") if m.group(2) else "")


def opcode(name: str) -> str:
    """``sort.3 f32[..]`` -> ``sort``; ``copy-done.1`` -> ``copy-done``."""
    return re.sub(r"\.\d+$", "", name.split(" ")[0])


def load_events(path: str) -> Events:
    """Device op events and bench host spans from an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: dict = {}
    spans = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = ops.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((op_name(e.name), e.start_ns,
                                e.start_ns + e.duration_ns)
                               for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Events(ops, spans)


def union(intervals, lo: float, hi: float) -> list:
    """Merged intervals, clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(ops, lo: float, hi: float) -> dict:
    """Op name -> self time within [lo, hi]: nested ops (same line,
    inside another's interval) are taken off their parent."""
    acc: dict = {}
    stack: list = []   # [name, start, end, child_time]

    def close(frame):
        name, s, e, child = frame
        acc[name] = acc.get(name, 0.0) + max(0.0, (min(e, hi) - max(s, lo))
                                             - child)
        if stack:
            stack[-1][3] += max(0.0, min(e, hi) - max(s, lo))

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return {k: v for k, v in acc.items() if v > 0}


def label_gap(s: float, e: float, spans) -> str:
    """The bench span overlapping [s, e] the most, or ``no_span``."""
    best, best_t = "no_span", 0.0
    per: dict = {}
    for name, a, b in spans:
        if name == WINDOW_SPAN:
            continue
        t = min(b, e) - max(a, s)
        if t > 0:
            per[name] = per.get(name, 0.0) + t
    for name, t in per.items():
        if t > best_t:
            best, best_t = name, t
    return best


def summarize(ev: Events, top_gaps: int = 10) -> Summary:
    """Reduce the events of one traced window."""
    windows = [(s, e) for n, s, e in ev.host_spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    chips = sorted(ev.device_ops) or [0]
    busy, selfs, gaps = [], {}, []
    for c in chips:
        ops = ev.device_ops.get(c, [])
        merged = union(((s, e) for _, s, e in ops), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for k, v in self_times(ops, lo, hi).items():
            selfs[k] = selfs.get(k, 0.0) + v
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(label_gap(s, e, ev.host_spans), (e - s) / 1e9)
             for s, e in gaps[:top_gaps]]
    return Summary((lo, hi), sum(busy) / len(chips), selfs, named,
                   len(chips))


def breakdown(summary: Summary, top: int = 10) -> dict:
    """The ``breakdown`` of the result line: the device ops that took
    most time (self time, seconds, summed over chips) and the longest
    idle gaps by what the host was doing."""
    ops = sorted(summary.op_self_ns.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in summary.gaps[:top]]}

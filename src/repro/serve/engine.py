"""Async micro-batched serving engine (DESIGN.md §13).

``launch/serve_cluster.py`` used to be the whole serving story: one
model, synchronous fixed-size batches, no queuing. This module is the
server it wraps now — a request loop built around three ideas:

1. **Micro-batching.** Callers ``submit()`` single rows or small
   batches; a worker thread accumulates them and flushes a micro-batch
   when ``max_batch`` rows are queued or the OLDEST queued request has
   waited ``deadline_ms``, whichever comes first (max-batch wins when
   both hold). Latency-vs-throughput is exactly this pair of knobs.

2. **A pad ladder.** Every micro-batch is cyclically padded up to a
   small ladder of bucket shapes (powers of two plus 1.5x mid-rungs,
   up to ``max_batch``),
   so the jitted serve step sees a bounded set of static shapes — after
   one warmup pass over the ladder, steady-state serving never
   recompiles, whatever request sizes arrive.

3. **Double-buffered dispatch.** JAX dispatch is asynchronous: the
   engine issues micro-batch N+1 (host→device copy + compute) *before*
   blocking on N's results, so transfer of the next batch overlaps
   compute of the current one. On GPU/TPU backends the batch buffers
   are donated to XLA; requests resolve as futures in submit order.

Hot-swap rides the :class:`~repro.serve.registry.ModelRegistry`: the
worker snapshots the registry's current model exactly once per
micro-batch, so ``swap()`` is atomic between micro-batches — in-flight
requests finish on the model they were batched under, and no
micro-batch ever mixes versions. Exact (``probes=None``), probed
(``probes=p`` — center-index candidates + host-side exact fallback),
and sharded (``mesh=``) serving all ride this one loop; labels are
bit-identical to the direct ``predict`` paths they wrap (distances to
float tolerance only — padding to a ladder rung changes the XLA
program shape, which may reassociate the distance reductions).
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import queue
import threading
import time
from concurrent.futures import Future, InvalidStateError

import jax
import numpy as np

from repro.core.model import (GeekModel, patch_probed_fallback, predict,
                              predict_probed)
from repro.serve.registry import ModelRegistry, _transform_kind
from repro.utils import spans

#: queue sentinel shutting the worker down
_CLOSE = object()


class ServerClosedError(RuntimeError):
    """``submit()`` after ``close()`` — the worker is gone for good.

    Named so callers (and the HTTP front end, which maps it to a 503)
    can distinguish "this server was shut down deliberately" from the
    plain ``RuntimeError`` a died worker raises. Raised immediately at
    submit time: a request must never be enqueued onto a dead worker,
    where its future would hang forever.
    """

#: expected request arity per transform kind — ``(x,)`` dense,
#: ``(x_num, x_cat)`` hetero, ``(sets, mask)`` sparse
_KIND_ARITY = {"identity": 1, "hetero": 2, "sparse": 2}


# ---------------------------------------------------------------------------
# Jitted serve steps (shared with launch/serve_cluster via this module)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _exact_step(n_parts: int, donate: bool):
    """The jitted exact serving step for one request arity.

    One program: fit-time coding (``model.encode``) + one-pass
    assignment, so serving raw traffic is a single XLA launch. With
    ``donate=True`` (GPU/TPU) the batch buffers are donated — XLA
    reuses them for outputs, which is what lets two micro-batches
    alternate in place. CPU ignores donation, so we don't request it
    there (avoids a warning per call).
    """
    def serve_exact(model, *parts):
        """Encode raw parts and assign in one traced program."""
        with jax.named_scope("geek.serve"):
            return predict(model, model.encode(*parts))
    kwargs = {"donate_argnums": tuple(range(1, 1 + n_parts))} if donate \
        else {}
    return jax.jit(serve_exact, **kwargs)


@functools.lru_cache(maxsize=None)
def _probed_step(n_parts: int, probes: int):
    """The jitted probed serving step: coding + center-index assignment.

    Returns the raw ``(labels, dists, empty)`` triple; the engine
    patches empty-probe rows on the host at retire time (the batch
    buffers are never donated here — the patch re-reads them).
    """
    del n_parts  # arity only keys the cache alongside probes

    def serve_probed(model, *parts):
        """Encode raw parts and probe the center index in one program."""
        with jax.named_scope("geek.serve"):
            return predict_probed(model, model.encode(*parts), probes)
    return jax.jit(serve_probed, static_argnames=())


def pad_ladder(max_batch: int, *, min_bucket: int = 64,
               multiple: int = 1) -> tuple[int, ...]:
    """The bucket shapes micro-batches are padded to.

    Powers of two from ``min_bucket`` up to (and always including)
    ``max_batch``, plus the 1.5x midpoint between each pair, all
    rounded up to ``multiple`` (the mesh size for sharded serving, so
    the sharded path never re-pads to a new shape). A short ladder
    bounds jit compiles to ``len(ladder)`` per model static-signature;
    the mid-rungs cap padding waste at 1/3 of a bucket — the engine
    self-clocks near one rung under steady load, and the padding
    fraction there is throughput lost directly.

    Parameters
    ----------
    max_batch : int
        The engine's flush threshold — the top rung.
    min_bucket : int
        Smallest bucket (single-row requests pad to this).
    multiple : int
        Round every rung up to this multiple (>= 1).

    Returns
    -------
    tuple of int
        Strictly increasing bucket sizes; the last is >= ``max_batch``.
    """
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    mult = max(int(multiple), 1)
    up = lambda v: -(-v // mult) * mult
    rungs, b = set(), max(1, min(min_bucket, max_batch))
    while b < max_batch:
        rungs.add(up(b))
        if b + b // 2 < max_batch:
            rungs.add(up(b + b // 2))
        b <<= 1
    rungs.add(up(max_batch))
    return tuple(sorted(rungs))


def bucket_for(n: int, ladder: tuple[int, ...]) -> int:
    """The smallest ladder rung holding ``n`` rows."""
    i = bisect.bisect_left(ladder, n)
    if i == len(ladder):
        raise ValueError(f"batch of {n} rows exceeds the ladder top "
                         f"{ladder[-1]}")
    return ladder[i]


@dataclasses.dataclass(frozen=True)
class Assignment:
    """One resolved request: labels/dists plus serving provenance.

    Attributes
    ----------
    labels : (n,) np.ndarray int32
        Cluster assignments, bit-identical to the direct ``predict``
        path the engine's configuration wraps.
    dists : (n,) np.ndarray float32
        Distances, same semantics as ``GeekResult`` (equal to the
        direct path to float tolerance; ladder padding may reassociate
        the reductions).
    version : int
        Registry version of the model that served this request — every
        row of one request (and in fact one micro-batch) is served by
        exactly this version.
    """

    labels: np.ndarray
    dists: np.ndarray
    version: int


class _Request:
    """A queued submit: host-side parts + the future to resolve.

    ``req`` is the request's span id and ``t_ns`` its submit time on the
    profiler's clock, both stamped only while a profiler session is
    live (else -1 and 0; ``repro.utils.spans``).
    """

    __slots__ = ("parts", "n", "future", "t_submit", "req", "t_ns")

    def __init__(self, parts, n, future, t_submit, req=-1, t_ns=0):
        self.parts = parts
        self.n = n
        self.future = future
        self.t_submit = t_submit
        self.req = req
        self.t_ns = t_ns


class ClusterServer:
    """Micro-batched async assignment server over a fitted GeekModel.

    Parameters
    ----------
    model_or_ckpt : GeekModel or str
        The model to serve, or a checkpoint directory to restore it
        from (``repro.checkpoint.manager.restore_model``).
    probes : int or None
        ``None``: exact serving. ``p >= 0``: probe the model's center
        index (sub-linear in k); empty-probe rows are patched with the
        exact scan at retire time, exactly like ``predict(probes=p)``.
    mesh : jax.sharding.Mesh or None
        Row-shard every micro-batch over this 1-axis mesh
        (``make_predict_sharded`` — composes with ``probes``, which
        then routes through the *sharded* probed step rather than
        silently serving single-device).
    max_batch : int
        Flush threshold: a micro-batch dispatches as soon as this many
        rows are queued.
    deadline_ms : float
        Flush deadline: a micro-batch dispatches once the oldest queued
        request has waited this long, full or not.
    mesh_axis : str
        Mesh axis name for sharded serving.
    min_bucket : int
        Bottom rung of the pad ladder.
    ladder : tuple of int or None
        Explicit pad-ladder override (strictly increasing rungs whose
        top covers ``max_batch``; every rung must be a multiple of the
        mesh size). ``None`` derives the default power-of-two +
        1.5x-mid-rung ladder from ``max_batch``/``min_bucket``. The
        override exists because the best rung set is *per serving
        path*: the probed step's candidate-gather cost grows with the
        rung, so a probed server can run a denser ladder (less padding
        per batch) than the exact path, whose kernels prefer fewer,
        rounder shapes (ROADMAP serving item c; rung sensitivity is
        recorded by ``bench_serving``).
    registry : ModelRegistry or None
        Shared registry for multi-model deployments; by default the
        server owns a private one.
    name : str
        Registry name this server serves (and ``swap`` publishes to).
    device : jax.Device or None
        Pin every micro-batch (and a per-record model copy) to this
        device. This is the multi-worker story: a
        :class:`~repro.serve.dispatch.WorkerPool` runs one server per
        device so independent micro-batches compute in parallel.
        Mutually exclusive with ``mesh`` (sharded serving places its
        own data).

    Notes
    -----
    ``submit(parts)`` returns a ``concurrent.futures.Future`` resolving
    to an :class:`Assignment`. Requests never span micro-batches and a
    micro-batch is served by exactly one model version (the registry
    snapshot taken at flush time), so a ``swap()`` mid-stream is atomic:
    zero dropped requests, zero mixed batches.

    Failure contract: a serve step that raises resolves exactly that
    micro-batch's futures with the exception and the worker keeps
    serving; an error that kills the worker itself resolves EVERY
    outstanding future with it and makes further ``submit`` calls
    raise. Futures always resolve — callers never need timeouts.
    """

    def __init__(self, model_or_ckpt, *, probes: int | None = None,
                 mesh=None, max_batch: int = 4096,
                 deadline_ms: float = 5.0, mesh_axis: str = "data",
                 min_bucket: int = 64,
                 ladder: tuple[int, ...] | None = None,
                 registry: ModelRegistry | None = None,
                 name: str = "default", device=None):
        if isinstance(model_or_ckpt, str):
            from repro.checkpoint.manager import restore_model
            model = restore_model(model_or_ckpt, mesh=mesh)
        elif isinstance(model_or_ckpt, GeekModel):
            model = model_or_ckpt
        else:
            raise TypeError("model_or_ckpt must be a GeekModel or a "
                            f"checkpoint directory, got "
                            f"{type(model_or_ckpt).__name__}")
        if probes is not None:
            probes = int(probes)
            if probes < 0:
                raise ValueError(f"probes must be >= 0, got {probes}")
            if model.index_tables <= 0:
                raise ValueError(
                    "probed serving requested but the model was built "
                    "with index_tables=0 (no center index) — serve with "
                    "probes=None or rebuild the model with an index")
        if deadline_ms <= 0:
            raise ValueError(f"deadline_ms must be > 0, got {deadline_ms}")
        if device is not None and mesh is not None:
            raise ValueError("device= pins single-device serving and "
                             "cannot compose with mesh= (sharded serving "
                             "places its own data)")
        self.probes = probes
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.max_batch = int(max_batch)
        self.deadline = float(deadline_ms) / 1e3
        self.name = name
        self._device = device
        self._dev_model = None    # (ModelRecord, model-on-device) cache
        g = mesh.shape[mesh_axis] if mesh is not None else 1
        if ladder is not None:
            rungs = tuple(int(r) for r in ladder)
            if not rungs or rungs[0] < 1 or \
                    any(b <= a for a, b in zip(rungs, rungs[1:])):
                raise ValueError("ladder must be a non-empty strictly "
                                 f"increasing tuple of positive ints, got "
                                 f"{rungs}")
            if rungs[-1] < self.max_batch:
                raise ValueError(f"ladder top rung {rungs[-1]} does not "
                                 f"cover max_batch={self.max_batch}")
            if any(r % g for r in rungs):
                raise ValueError(f"every ladder rung must be a multiple of "
                                 f"the mesh size {g}, got {rungs}")
            self.ladder = rungs
        else:
            self.ladder = pad_ladder(self.max_batch, min_bucket=min_bucket,
                                     multiple=g)
        self.registry = registry if registry is not None else ModelRegistry()
        if name not in self.registry.names():
            self.registry.publish(name, model)
        self._arity = _KIND_ARITY[_transform_kind(model)]
        self._donate = (jax.default_backend() in ("gpu", "tpu")
                        and probes is None and mesh is None)
        if mesh is not None:
            from repro.core.distributed import make_predict_sharded
            self._sharded_fn = make_predict_sharded(mesh, axis=mesh_axis,
                                                    probes=probes)
        self._queue: queue.Queue = queue.Queue()
        self._inflight = None
        self._pending: list[_Request] = []   # worker-owned accumulation
        self._fatal: BaseException | None = None
        self._closed = False
        self._stats_lock = threading.Lock()
        self._stats = {"submitted": 0, "completed": 0, "failed": 0,
                       "batches": 0, "rows_served": 0, "padded_rows": 0,
                       "flushes": {"max_batch": 0, "deadline": 0,
                                   "close": 0},
                       "swaps": 0}
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name="repro-serve-worker")
        self._worker.start()

    # -- public surface ------------------------------------------------------

    @property
    def model(self) -> GeekModel:
        """The model the NEXT micro-batch will be served by."""
        return self.registry.current(self.name).model

    @property
    def version(self) -> int:
        """Registry version of :attr:`model`."""
        return self.registry.current(self.name).version

    def submit(self, parts) -> Future:
        """Enqueue one request; returns a future of :class:`Assignment`.

        Parameters
        ----------
        parts : array or tuple of arrays
            Raw query parts of the model's kind — ``x`` / ``(x,)``
            dense, ``(x_num, x_cat)`` hetero (either may be None as
            fitted), ``(sets, mask)`` sparse. 1 to ``max_batch`` rows;
            chunk bigger payloads into several submits (the engine
            micro-batches, it does not split).
        """
        if self._closed:
            raise ServerClosedError(
                "server is closed — submit() after close() cannot be "
                "served (stand up a new ClusterServer)")
        if self._fatal is not None:
            raise RuntimeError("serving worker died") from self._fatal
        if not isinstance(parts, (tuple, list)):
            parts = (parts,)
        if len(parts) != self._arity:
            raise ValueError(f"expected {self._arity} query part(s) for "
                             f"this model's kind, got {len(parts)}")
        parts = tuple(None if p is None else np.asarray(p) for p in parts)
        ns = {p.shape[0] for p in parts if p is not None}
        if len(ns) != 1:
            raise ValueError("query parts disagree on row count (or are "
                             "all None)")
        n = ns.pop()
        if not 1 <= n <= self.max_batch:
            raise ValueError(f"request of {n} rows outside [1, "
                             f"{self.max_batch}] — split oversized "
                             "payloads into several submits")
        fut: Future = Future()
        with self._stats_lock:
            self._stats["submitted"] += 1
        req, t_ns = -1, 0
        if spans.enabled():
            # the front end's request id, or a fresh one for a direct caller
            req, t_ns = spans.current_req(), time.time_ns()
            if req < 0:
                req = spans.new_id()
        self._queue.put(_Request(parts, n, fut, time.monotonic(), req, t_ns))
        if self._fatal is not None and not fut.done():
            # lost the race with a concurrent worker death: the drain in
            # _fail may have missed this request, so resolve it here
            # (never hang a future)
            try:
                fut.set_exception(RuntimeError("serving worker died"))
            except InvalidStateError:
                pass  # _fail got it first
        if self._closed and not fut.done():
            # lost the race with a concurrent close(): the pre-check above
            # ran before _closed was set, so this request may have landed
            # BEHIND the close sentinel after the worker's final drain —
            # onto a dead worker, where its future would hang forever.
            # Resolve it here with the same named error the pre-check
            # raises; if the closing worker's drain did pick it up, its
            # set_result simply loses the race (both sides tolerate
            # InvalidStateError).
            try:
                fut.set_exception(ServerClosedError(
                    "server closed while the request was being submitted"))
            except InvalidStateError:
                pass  # the close drain served it first
        return fut

    def swap(self, model_or_ckpt, *, step: int | None = None) -> int:
        """Publish a new model version; returns its version number.

        The swap takes effect atomically at the next micro-batch
        boundary: requests already batched (or in flight) finish on the
        version they were batched under. A model of a different traffic
        kind or feature width is refused (``ModelRegistry.publish``).
        """
        if isinstance(model_or_ckpt, str):
            version = self.registry.load(self.name, model_or_ckpt,
                                         step=step, mesh=self.mesh)
        else:
            version = self.registry.publish(self.name, model_or_ckpt)
        with self._stats_lock:
            self._stats["swaps"] += 1
        return version

    def warmup(self, parts) -> None:
        """Compile every ladder rung with example traffic.

        Pads ``parts`` (same layout as ``submit``) cyclically to each
        bucket shape and runs the serve step, so steady-state serving
        never compiles. Probed serving additionally compiles its exact
        fallback lazily, on the first batch with empty-probe rows (a
        bounded O(log max_batch) family of shapes).
        """
        if not isinstance(parts, (tuple, list)):
            parts = (parts,)
        parts = tuple(None if p is None else np.asarray(p) for p in parts)
        n = next(p.shape[0] for p in parts if p is not None)
        model = self._on_device(self.registry.current(self.name))
        for bucket in self.ladder:
            idx = np.arange(bucket) % n
            padded = tuple(None if p is None else p[idx] for p in parts)
            finish = self._dispatch(model, padded, min(n, bucket))
            finish()

    def stats(self) -> dict:
        """A snapshot of serving counters (copies; safe to mutate)."""
        with self._stats_lock:
            out = dict(self._stats)
            out["flushes"] = dict(self._stats["flushes"])
            return out

    def close(self, timeout: float | None = 30.0) -> None:
        """Flush queued requests, retire in-flight work, stop the worker."""
        if self._closed:
            return
        self._closed = True
        self._queue.put(_CLOSE)
        self._worker.join(timeout=timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- worker loop ---------------------------------------------------------

    def _run(self) -> None:
        """Worker entry: the serve loop behind a fatal-error backstop.

        Per-batch errors (a failing jitted step, a poisoned model) are
        contained by ``_flush``/``_retire`` — the batch's futures get
        the exception, the worker keeps serving. Anything that still
        escapes the loop is a worker-killing bug; ``_fail`` then
        resolves EVERY outstanding future (in flight, pending, queued)
        with the error so no ``submit`` ever hangs, and subsequent
        submits raise instead of queueing into a dead loop.
        """
        try:
            self._serve_loop()
        except BaseException as e:   # noqa: BLE001 — fatal backstop
            self._fail(e)

    def _fail(self, exc: BaseException) -> None:
        """Resolve every outstanding future with ``exc``; poison submit."""
        self._fatal = exc
        doomed = list(self._pending)
        self._pending.clear()
        if self._inflight is not None:
            doomed.extend(self._inflight[0])
            self._inflight = None
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is not _CLOSE:
                doomed.append(item)
        for r in doomed:
            try:
                r.future.set_exception(exc)
            except InvalidStateError:
                pass
        with self._stats_lock:
            self._stats["failed"] += len(doomed)

    def _serve_loop(self) -> None:
        pending = self._pending
        rows = sum(r.n for r in pending)
        closing = False
        while not closing:
            # drain everything already queued before deciding to flush —
            # under backlog the oldest deadline is long expired, and
            # flushing after every single get() would serve one request
            # per micro-batch forever (no coalescing, backlog persists)
            while True:
                try:
                    item = self._queue.get_nowait()
                except queue.Empty:
                    break
                if item is _CLOSE:
                    closing = True
                    break
                pending.append(item)
                rows += item.n
            if pending and rows >= self.max_batch:
                # max-batch flush outranks an expired deadline (and the
                # close sentinel): a full bucket is ready, dispatch it
                # at the top rung
                rows = self._flush(pending, rows, "max_batch")
                continue
            if closing:
                continue
            if pending:
                wait = self.deadline - (time.monotonic()
                                        - pending[0].t_submit)
                if wait <= 0:
                    rows = self._flush(pending, rows, "deadline")
                    continue
            else:
                wait = None
                # idle: don't sit on finished work while blocking open-ended
                self._retire()
            due = (time.time_ns() + int(wait * 1e9)
                   if wait is not None and spans.enabled() else 0)
            try:
                item = self._queue.get(timeout=wait)
            except queue.Empty:
                item = None
            if due:
                # how late the worker ran again after a timed wait (an
                # early wake, by a new request, is a zero-length span)
                now = time.time_ns()
                spans.interval("geek.engine.lag", min(due, now), now)
            if item is None:
                continue
            if item is _CLOSE:
                closing = True
                continue
            pending.append(item)
            rows += item.n
        # drain: anything that raced in behind the close sentinel
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is not _CLOSE:
                pending.append(item)
                rows += item.n
        while pending:
            rows = self._flush(pending, rows, "close")
        self._retire()

    def _flush(self, pending: list[_Request], rows: int,
               reason: str) -> int:
        """Dispatch one micro-batch from the head of ``pending``.

        Takes the longest request prefix fitting ``max_batch`` (requests
        never split), dispatches it against the registry's CURRENT model
        — the hot-swap atomicity point — and only then retires the
        previous in-flight batch, so batch N+1's host→device copy
        overlaps batch N's compute. Returns the rows still pending.
        """
        take, taken = [], 0
        while pending and taken + pending[0].n <= self.max_batch:
            take.append(pending.pop(0))
            taken += take[-1].n
        if not take:  # can't happen while submit() bounds n; be safe
            return rows
        batch = -1
        if spans.enabled():
            batch, t_take = spans.new_id(), time.time_ns()
            for r in take:
                if r.t_ns:
                    spans.interval("geek.engine.queue", r.t_ns, t_take,
                                   req=r.req, batch=batch)
        try:
            with spans.span("geek.engine.flush", batch=batch):
                # registry snapshot INSIDE the per-batch guard: a failing
                # registry (or a poisoned record) fails this batch's
                # futures and the worker keeps serving — it must never
                # kill the loop
                rec = self.registry.current(self.name)
                host = tuple(
                    None if take[0].parts[i] is None else
                    np.concatenate([r.parts[i] for r in take], axis=0)
                    for i in range(self._arity))
                finish = self._dispatch(self._on_device(rec), host, taken)
        except Exception as e:                  # noqa: BLE001 — per-batch
            for r in take:
                r.future.set_exception(e)
            with self._stats_lock:
                self._stats["failed"] += len(take)
            return rows - taken
        t_issued = time.time_ns() if batch >= 0 else 0
        self._retire()
        self._inflight = (take, taken, rec, finish, batch, t_issued)
        with self._stats_lock:
            self._stats["batches"] += 1
            self._stats["flushes"][reason] += 1
            self._stats["padded_rows"] += bucket_for(taken,
                                                     self.ladder) - taken
        return rows - taken

    def _on_device(self, rec) -> GeekModel:
        """The record's model, copied to the pinned device (cached).

        With ``device=None`` this is just ``rec.model``. With a pinned
        device the model pytree is ``device_put`` once per registry
        record (the cache is keyed by record identity, so a hot-swap
        refreshes it exactly once) — computation then follows the
        committed inputs onto that device. Benign race: ``warmup`` and
        the worker may both populate the cache; the worst case is one
        duplicate transfer.
        """
        if self._device is None:
            return rec.model
        cached = self._dev_model
        if cached is None or cached[0] is not rec:
            cached = (rec, jax.device_put(rec.model, self._device))
            self._dev_model = cached
        return cached[1]

    def _dispatch(self, model: GeekModel, host: tuple, n: int):
        """Pad to the ladder, issue the async serve step; returns a
        ``finish() -> (labels, dists)`` callable that blocks."""
        bucket = bucket_for(n, self.ladder)
        if bucket > n:
            # cyclic pad (always real rows) — gather only the tail, the
            # first n rows are the batch itself
            idx = np.arange(bucket - n) % n
            padded = tuple(None if p is None else
                           np.concatenate([p, p[idx]], axis=0)
                           for p in host)
        else:
            padded = host
        # NOTE: real-row slicing happens on the HOST (np.asarray first,
        # [:n] second) — slicing the device array would jit a
        # dynamic_slice per (bucket, n) pair, an unbounded shape family
        # that breaks the zero-steady-state-recompile contract
        if self.mesh is not None:
            # make_predict_sharded handles probed patching internally
            out = self._sharded_fn(model, *padded)
            return lambda: tuple(np.asarray(o)[:n] for o in out)
        dev = tuple(None if p is None else jax.device_put(p, self._device)
                    for p in padded)
        if self.probes is None:
            out = _exact_step(self._arity, self._donate)(model, *dev)
            return lambda: tuple(np.asarray(o)[:n] for o in out)
        lab, dst, emp = _probed_step(self._arity, self.probes)(model, *dev)

        def finish():
            """Probed retire: slice real rows, patch empty probes exact."""
            labels, dists = patch_probed_fallback(
                np.asarray(lab)[:n], np.asarray(dst)[:n],
                np.asarray(emp)[:n],
                lambda ix: _exact_step(self._arity, False)(
                    model, *(None if p is None else
                             jax.device_put(p[np.asarray(ix)],
                                            self._device)
                             for p in host)))
            return np.asarray(labels), np.asarray(dists)

        return finish

    def _retire(self) -> None:
        """Resolve the previous micro-batch's futures (blocks on device)."""
        if self._inflight is None:
            return
        take, taken, rec, finish, batch, t_issued = self._inflight
        self._inflight = None
        if t_issued:
            spans.interval("geek.engine.held", t_issued, time.time_ns(),
                           batch=batch)
        with spans.span("geek.engine.retire", batch=batch):
            try:
                labels, dists = finish()
            except Exception as e:              # noqa: BLE001 — per-batch
                for r in take:
                    r.future.set_exception(e)
                with self._stats_lock:
                    self._stats["failed"] += len(take)
                return
            off = 0
            for r in take:
                try:
                    r.future.set_result(Assignment(labels[off:off + r.n],
                                                   dists[off:off + r.n],
                                                   rec.version))
                except InvalidStateError:
                    pass  # a submit/close race already failed this future
                off += r.n
            with self._stats_lock:
                self._stats["completed"] += len(take)
                self._stats["rows_served"] += taken

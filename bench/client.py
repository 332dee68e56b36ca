"""A load-generating client process: the standard library and numpy only.

It never imports JAX, so it neither holds the chip nor shares the
server's interpreter lock. The parent (``bench/serving.py``) starts it
with ``subprocess`` and speaks to it over its standard streams:

1. stdin: one JSON header line, then the request pool (``pool_rows`` x
   ``d`` float32, row-major), then a length-prefixed ``.npz`` of this
   client's jobs;
2. stdout: ``ready`` once its connections are open;
3. stdin: ``go <t0>``, ``t0`` on the shared monotonic clock;
4. stdout, at the end: a length-prefixed ``.npz`` of what happened.

Every request is ``POST /v1/assign`` with a raw float32 body of pool
rows ``start .. start + n`` and asks for a raw response (int32 labels,
then float32 distances).

Request i is due at ``t0 + due[i]`` (an open loop); a dispatcher hands
it to the first free connection thread. A request is timed from when
it was due, so a stall shows in the requests behind it; ``sent - due``
is how late the generator ran.

The answers are folded per distinct (pool row, label) pair, with the
least and largest distance answered for it, so that the parent can
compare every answer of the window with the reference.
"""
from __future__ import annotations

import gc
import http.client
import io
import json
import queue
import struct
import sys
import threading
import time

import numpy as np

#: label ids are below this; a pair key is row * LABELS + label
LABELS = 1 << 20


def _read_exact(f, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = f.read(n - len(buf))
        if not chunk:
            raise EOFError("parent closed the pipe")
        buf += chunk
    return bytes(buf)


def read_blob(f) -> dict:
    (n,) = struct.unpack("<Q", _read_exact(f, 8))
    with np.load(io.BytesIO(_read_exact(f, n))) as z:
        return {k: z[k] for k in z.files}


def write_blob(f, arrays: dict) -> None:
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    raw = buf.getvalue()
    f.write(struct.pack("<Q", len(raw)))
    f.write(raw)
    f.flush()


class Conn:
    """One keep-alive HTTP connection to the front end."""

    def __init__(self, host: str, port: int, pool: np.ndarray,
                 timeout: float):
        self.addr = (host, port, timeout)
        self.pool = pool
        self.conn = None
        self._open()

    def _open(self):
        host, port, timeout = self.addr
        self.conn = http.client.HTTPConnection(host, port, timeout=timeout)
        self.conn.connect()

    def assign(self, start: int, n: int):
        """(status, labels, dists); status 200 or the HTTP status, -1 on
        a broken connection."""
        body = self.pool[start:start + n].tobytes()
        try:
            self.conn.request("POST", "/v1/assign", body=body, headers={
                "Content-Type": "application/octet-stream",
                "Accept": "application/octet-stream"})
            resp = self.conn.getresponse()
            raw = resp.read()
        except (OSError, http.client.HTTPException):
            self.conn.close()
            self._open()
            return -1, None, None
        if resp.status != 200 or len(raw) != 8 * n:
            return resp.status if resp.status != 200 else -2, None, None
        return (200, np.frombuffer(raw[:4 * n], "<i4"),
                np.frombuffer(raw[4 * n:], "<f4"))


def fold_answers(starts, sizes, labels, dists) -> dict:
    """Distinct (pool row, label) pairs with the least and largest
    distance answered for each."""
    if not labels:
        z = np.zeros(0, np.int64)
        return {"pair_key": z, "pair_dmin": z.astype(np.float32),
                "pair_dmax": z.astype(np.float32)}
    rows = np.concatenate([s + np.arange(n) for s, n in zip(starts, sizes)])
    key = rows.astype(np.int64) * LABELS + np.concatenate(labels)
    d = np.concatenate(dists).astype(np.float32)
    order = np.argsort(key, kind="stable")
    key, d = key[order], d[order]
    first = np.ones(key.size, bool)
    first[1:] = key[1:] != key[:-1]
    idx = np.flatnonzero(first)
    return {"pair_key": key[idx],
            "pair_dmin": np.minimum.reduceat(d, idx),
            "pair_dmax": np.maximum.reduceat(d, idx)}


def run_open(conns, jobs, t0: float):
    due, size, start = jobs["due"], jobs["size"], jobs["start"]
    m = due.size
    sent = np.full(m, np.nan)
    done = np.full(m, np.nan)
    status = np.zeros(m, np.int32)
    answers: dict = {}
    todo: queue.SimpleQueue = queue.SimpleQueue()

    def worker(c: Conn):
        while True:
            i = todo.get()
            if i is None:
                return
            sent[i] = time.monotonic()
            st, lab, dst = c.assign(int(start[i]), int(size[i]))
            done[i] = time.monotonic()
            status[i] = st
            if st == 200:
                answers[i] = (lab, dst)

    threads = [threading.Thread(target=worker, args=(c,), daemon=True)
               for c in conns]
    for t in threads:
        t.start()
    for i in np.argsort(due, kind="stable"):
        wait = t0 + due[i] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        todo.put(int(i))
    for _ in threads:
        todo.put(None)
    for t in threads:
        t.join()
    ok = sorted(answers)
    out = fold_answers(start[ok], size[ok], [answers[i][0] for i in ok],
                       [answers[i][1] for i in ok])
    out.update(due=t0 + due, sent=sent, done=done, size=size, start=start,
               status=status)
    return out


def main() -> int:
    stdin, stdout = sys.stdin.buffer, sys.stdout.buffer
    hdr = json.loads(stdin.readline())
    pool = np.frombuffer(_read_exact(stdin, hdr["pool_rows"] * hdr["d"] * 4),
                         "<f4").reshape(hdr["pool_rows"], hdr["d"])
    jobs = read_blob(stdin)
    # the generator keeps every answer until the end; a collection pause
    # would show as lateness, so none runs
    gc.disable()
    conns = [Conn(hdr["host"], hdr["port"], pool, hdr["timeout_s"])
             for _ in range(hdr["threads"])]
    stdout.write(b"ready\n")
    stdout.flush()
    t0 = float(stdin.readline().split()[1])
    out = run_open(conns, jobs, t0)
    for c in conns:
        c.conn.close()
    write_blob(stdout, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())

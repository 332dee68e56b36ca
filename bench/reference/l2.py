"""Plain L2 reference: a blocked exact float32 argmin at ``HIGHEST``.

Copied in idea and bound from ``chip_smoke.l2_near_tie_check``. The
program computes d2 = |x|^2 - 2 x.c + |c|^2 with x.c at the TPU's
default matmul precision, which rounds both operands to bfloat16 (unit
roundoff 2^-9). Each product x_i c_i is then off by at most 2^-8 of
|x_i c_i|, so the program's d2 to center c is off by at most
B(x, c) = 2^-7 sum_i |x_i||c_i| + 2^-20 (|x|^2 + |c|^2); the second term
covers float32 accumulation and the three-term sum in both the program
and this reference. Two readings follow, both at most 1 for a sound
answer:

- ``label_ratio``: (d2(s) - d2(e)) / (B(x, c_s) + B(x, c_e)), where s is
  the answered label and e the exact one: a label can differ from the
  exact one only at a near tie;
- ``dist_ratio``: |dist^2 - d2(s)| / B(x, c_s) for the answered
  distance.

A label on an invalid or unknown center reads infinity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def round_significand(v, bits: int):
    """Round float32 values to ``bits`` significand bits (nearest, ties
    to even), keeping the float32 exponent: bfloat16 is 7, float8 e4m3
    is 3. The control's lower precision, emulated identically on every
    backend."""
    u = jax.lax.bitcast_convert_type(v.astype(jnp.float32), jnp.uint32)
    drop = 23 - bits
    half = jnp.uint32((1 << (drop - 1)) - 1)
    lsb = (u >> jnp.uint32(drop)) & jnp.uint32(1)
    u = (u + half + lsb) & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _bound(ax, xsq, ac, csq):
    """B(x, c) for rows ``ax`` = |x| against centers ``ac`` = |c|."""
    return (2.0 ** -7 * jnp.dot(ax, ac.T, precision=HI)
            + 2.0 ** -20 * (xsq[:, None] + csq[None, :]))


@functools.partial(jax.jit, static_argnames=("block",))
def _readings_block(x, labels, dists, off, c, valid, block: int):
    """Max (label_ratio, dist_ratio) over rows off..off+block."""
    xb = jax.lax.dynamic_slice_in_dim(x, off, block).astype(jnp.float32)
    lb = jax.lax.dynamic_slice_in_dim(labels, off, block).astype(jnp.int32)
    db = jax.lax.dynamic_slice_in_dim(dists, off, block).astype(jnp.float32)
    k = c.shape[0]
    csq = jnp.sum(c * c, -1)
    xsq = jnp.sum(xb * xb, -1)
    d2 = xsq[:, None] - 2.0 * jnp.dot(xb, c.T, precision=HI) + csq[None, :]
    d2 = jnp.where(valid[None, :], d2, jnp.inf)
    e = jnp.argmin(d2, -1)
    ls = jnp.clip(lb, 0, k - 1)
    ok = (lb >= 0) & (lb < k) & valid[ls]
    bnd = _bound(jnp.abs(xb), xsq, jnp.abs(c), csq)
    take = lambda a, i: jnp.take_along_axis(a, i[:, None], 1)[:, 0]
    b_s, b_e = take(bnd, ls), take(bnd, e)
    d2_s, d2_e = take(d2, ls), take(d2, e)
    label_ratio = jnp.where(ok, (d2_s - d2_e) / (b_s + b_e), jnp.inf)
    dist_ratio = jnp.where(ok, jnp.abs(db * db - d2_s) / b_s, jnp.inf)
    return jnp.max(label_ratio), jnp.max(dist_ratio)


def _offsets(n: int, block: int):
    """Block starts covering n rows with whole blocks (the last one is
    moved back to end at n; its rows are read twice)."""
    return [min(off, n - block) for off in range(0, n, block)]


def readings(x, labels, dists, centers, valid, *, block: int = 8192):
    """Max ``label_ratio`` and ``dist_ratio`` over the rows."""
    c = jnp.asarray(centers, jnp.float32)
    v = jnp.asarray(valid, bool)
    x, labels, dists = (jnp.asarray(a) for a in (x, labels, dists))
    n = int(x.shape[0])
    block = min(block, n)
    out = [_readings_block(x, labels, dists, off, c, v, block)
           for off in _offsets(n, block)]
    lr, dr = np.asarray(jnp.stack([jnp.stack(o) for o in out])).max(0)
    return {"label_ratio": float(lr), "dist_ratio": float(dr)}


@functools.partial(jax.jit, static_argnames=("bits", "block"))
def _control_block(x, off, c, valid, bits: int, block: int):
    """The reference at a lower precision: dot operands rounded to
    ``bits`` significand bits. Returns (labels, dists) like the
    program."""
    xb = jax.lax.dynamic_slice_in_dim(x, off, block).astype(jnp.float32)
    xr, cr = round_significand(xb, bits), round_significand(c, bits)
    d2 = (jnp.sum(xb * xb, -1, keepdims=True)
          - 2.0 * jnp.dot(xr, cr.T, precision=HI)
          + jnp.sum(c * c, -1)[None, :])
    d2 = jnp.where(valid[None, :], d2, jnp.inf)
    return (jnp.argmin(d2, -1).astype(jnp.int32),
            jnp.sqrt(jnp.maximum(jnp.min(d2, -1), 0.0)))


def control_answers(x, centers, valid, *, bits: int = 3,
                    block: int = 8192):
    """Labels and distances of the control: the reference computed with
    its dot operands at float8 e4m3's 3 significand bits, one step
    below the bfloat16 operands the configuration states."""
    c = jnp.asarray(centers, jnp.float32)
    v = jnp.asarray(valid, bool)
    x = jnp.asarray(x)
    n = int(x.shape[0])
    block = min(block, n)
    labels = np.zeros(n, np.int32)
    dists = np.zeros(n, np.float32)
    for off in _offsets(n, block):
        lab, dst = _control_block(x, off, c, v, bits, block)
        labels[off:off + block] = np.asarray(lab)
        dists[off:off + block] = np.asarray(dst)
    return labels, dists

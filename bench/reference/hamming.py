"""Plain Hamming reference for sparse sets: DOPH codes, an exact argmin
of mismatches by equality, and per-attribute modes.

DOPH (densified one-permutation hashing, Shrivastava & Li, ICML 2014),
written from its definition: each item is hashed by a multiply-add and
a murmur3-style finalizer under the fitted model's published key, the
hash range is split into ``m`` bins by ``h mod m``, each bin keeps its
minimum, and an empty bin borrows the minimum of the nearest non-empty
bin to its right (cyclically) plus the borrow distance times the
golden-ratio constant. A code is the top 16 bits of the bin value.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

UMAX = 0xFFFFFFFF
GOLDEN = 0x9E3779B1


def _hash(x, a, b):
    h = x.astype(jnp.uint32) * a + b
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    return h ^ (h >> 16)


@functools.partial(jax.jit, static_argnames=("m", "code_bits", "block"))
def _doph_block(sets, mask, off, key, m: int, code_bits: int, block: int):
    sb = jax.lax.dynamic_slice_in_dim(sets, off, block)
    mb = jax.lax.dynamic_slice_in_dim(mask, off, block)
    ab = jax.random.bits(key, (1, 2), dtype=jnp.uint32)[0]
    a, b = ab[0] | jnp.uint32(1), ab[1]
    h = jnp.where(mb, _hash(sb, a, b), jnp.uint32(UMAX))
    bins = jnp.where(mb, (h % jnp.uint32(m)).astype(jnp.int32), m)
    rows = jnp.broadcast_to(jnp.arange(block)[:, None], bins.shape)
    vals = jnp.full((block, m + 1), UMAX, jnp.uint32).at[rows, bins].min(h)
    vals = vals[:, :m]
    filled = vals != jnp.uint32(UMAX)

    def borrow(dist, carry):
        out, found = carry
        cand = jnp.roll(vals, -dist, axis=1)
        take = ~found & jnp.roll(filled, -dist, axis=1)
        step = (dist.astype(jnp.uint32) * jnp.uint32(GOLDEN))
        return jnp.where(take, cand + step, out), found | take

    out, _ = jax.lax.fori_loop(1, m, borrow, (vals, filled))
    return (out >> jnp.uint32(32 - code_bits)).astype(jnp.int32)


def doph_codes(sets, mask, key, *, m: int, code_bits: int = 16,
               block: int = 65536):
    """(n, m) int32 codes of the top ``code_bits`` bits of each bin,
    computed in blocks of rows."""
    sets, mask = jnp.asarray(sets), jnp.asarray(mask)
    n = int(sets.shape[0])
    block = min(block, n)
    offs = [min(o, n - block) for o in range(0, n, block)]
    out = [_doph_block(sets, mask, o, key, m, code_bits, block)
           for o in offs]
    if len(out) == 1:
        return out[0]
    return jnp.concatenate([jnp.concatenate(out[:-1])[:offs[-1]], out[-1]])


@functools.partial(jax.jit, static_argnames=("block",))
def _gap_block(codes, labels, dists, off, centers, valid, block: int):
    """Per block: max (label's mismatches - min mismatches) and max
    |answered distance * m - label's mismatches|."""
    cb = jax.lax.dynamic_slice_in_dim(codes, off, block)
    lb = jax.lax.dynamic_slice_in_dim(labels, off, block).astype(jnp.int32)
    db = jax.lax.dynamic_slice_in_dim(dists, off, block)
    k, m = centers.shape
    mis = (cb[:, None, :] != centers[None, :, :]).sum(-1)
    mis = jnp.where(valid[None, :], mis, m + 1)
    ls = jnp.clip(lb, 0, k - 1)
    ok = (lb >= 0) & (lb < k) & valid[ls]
    mis_s = jnp.take_along_axis(mis, ls[:, None], 1)[:, 0]
    gap = jnp.where(ok, mis_s - mis.min(-1), m + 1)
    derr = jnp.where(ok, jnp.abs(db * m - mis_s.astype(jnp.float32)),
                     jnp.inf)
    return gap.max(), derr.max()


def readings(codes, labels, dists, centers, valid, *, block: int = 2048):
    """``label_gap``: the largest number of mismatches by which an
    answered label lies above the exact minimum (0 when every label is
    an argmin); ``dist_err``: the largest gap between an answered
    distance times m and the label's mismatch count (0 when exact)."""
    codes, labels, dists = (jnp.asarray(a) for a in (codes, labels, dists))
    c = jnp.asarray(centers, jnp.int32)
    v = jnp.asarray(valid, bool)
    n = int(codes.shape[0])
    block = min(block, n)
    offs = [min(o, n - block) for o in range(0, n, block)]
    out = [_gap_block(codes, labels, dists, o, c, v, block) for o in offs]
    g = max(int(o[0]) for o in out) if out else 0
    e = max(float(o[1]) for o in out) if out else 0.0
    return {"label_gap": g, "dist_err": e}


def mode_centers(member_codes, member_group, k_max: int):
    """Per (group, attribute): the smallest code among those with the
    largest member count; and which groups have members.
    ``member_codes`` (C, m) int, ``member_group`` (C,) int in
    [0, k_max)."""
    member_codes = np.asarray(member_codes, np.int64)
    g = np.asarray(member_group, np.int64)
    c, m = member_codes.shape
    modes = np.zeros((k_max, m), np.int64)
    valid = np.zeros(k_max, bool)
    valid[g] = True
    if c == 0:
        return modes, valid
    attr = np.broadcast_to(np.arange(m), (c, m))
    cell = (attr * k_max + g[:, None]).ravel()
    vals = member_codes.ravel()
    key, counts = np.unique(cell * (1 << 32) + vals, return_counts=True)
    kc, kv = key >> 32, key & 0xFFFFFFFF
    # order: cell ascending, count descending, value ascending
    order = np.lexsort((kv, -counts, kc))
    kc, kv = kc[order], kv[order]
    first = np.ones(kc.size, bool)
    first[1:] = kc[1:] != kc[:-1]
    cells, best = kc[first], kv[first]
    modes[cells % k_max, cells // k_max] = best
    return modes, valid


@functools.partial(jax.jit, static_argnames=("shift", "block"))
def _control_block(codes, off, centers, valid, shift: int, block: int):
    cb = jax.lax.dynamic_slice_in_dim(codes, off, block) >> shift
    m = centers.shape[1]
    mis = (cb[:, None, :] != (centers >> shift)[None, :, :]).sum(-1)
    mis = jnp.where(valid[None, :], mis, m + 1)
    return (jnp.argmin(mis, -1).astype(jnp.int32),
            mis.min(-1).astype(jnp.float32) / m)


def control_answers(codes, centers, valid, *, keep_bits: int = 8,
                    code_bits: int = 16, block: int = 2048):
    """Labels and distances of the control: the reference's exact
    argmin computed on codes narrowed to their top ``keep_bits`` of
    ``code_bits`` bits, one step below the 16-bit codes the
    configuration states."""
    codes = jnp.asarray(codes)
    c = jnp.asarray(centers, jnp.int32)
    v = jnp.asarray(valid, bool)
    n = int(codes.shape[0])
    block = min(block, n)
    labels = np.zeros(n, np.int32)
    dists = np.zeros(n, np.float32)
    for off in (min(o, n - block) for o in range(0, n, block)):
        lab, dst = _control_block(codes, off, c, v, code_bits - keep_bits,
                                  block)
        labels[off:off + block] = np.asarray(lab)
        dists[off:off + block] = np.asarray(dst)
    return labels, dists

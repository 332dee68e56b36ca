"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def distance_argmin_l2_ref(x, centers, center_valid):
    x = x.astype(jnp.float32)
    centers = centers.astype(jnp.float32)
    d2 = (jnp.sum(x * x, -1, keepdims=True) - 2.0 * (x @ centers.T)
          + jnp.sum(centers * centers, -1)[None, :])
    d2 = jnp.where(center_valid[None, :], d2, jnp.finfo(jnp.float32).max)
    return jnp.argmin(d2, -1).astype(jnp.int32), jnp.maximum(jnp.min(d2, -1), 0.0)


def distance_argmin_hamming_ref(codes, centers, center_valid):
    dist = (codes[:, None, :] != centers[None, :, :]).sum(-1).astype(jnp.int32)
    dist = jnp.where(center_valid[None, :], dist, jnp.iinfo(jnp.int32).max)
    return jnp.argmin(dist, -1).astype(jnp.int32), jnp.min(dist, -1)


def distance_argmin_hamming_packed_ref(packed, packed_centers, center_valid,
                                       *, bits):
    """Packed-domain oracle: XOR + per-field collapse + popcount."""
    from repro.kernels.pack import packed_hamming
    dist = packed_hamming(packed, packed_centers, bits)
    dist = jnp.where(center_valid[None, :], dist, jnp.iinfo(jnp.int32).max)
    return jnp.argmin(dist, -1).astype(jnp.int32), jnp.min(dist, -1)


def centroid_attention_ref(q, centers, v_cent, log_mass):
    """q: (B,Hq,S,dh); centers/v_cent: (B,Hkv,K,dh); log_mass: (B,Hkv,K).

    Mass-weighted non-causal softmax over centroids (GQA by repetition);
    ``log_mass = -1e30`` rows are effectively excluded. The oracle for
    ``flash_centroid_attention``.
    """
    B, Hq, S, dh = q.shape
    Hkv = centers.shape[1]
    rep = Hq // Hkv
    c = jnp.repeat(centers, rep, axis=1)
    vc = jnp.repeat(v_cent, rep, axis=1)
    lm = jnp.repeat(log_mass, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   c.astype(jnp.float32)) / (dh ** 0.5)
    s = s + lm[:, :, None, :].astype(jnp.float32)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      vc.astype(jnp.float32)).astype(q.dtype)


def attention_ref(q, k, v, *, causal=True):
    """q: (B,Hq,S,dh); k,v: (B,Hkv,S,dh). GQA by head repetition."""
    B, Hq, S, dh = q.shape
    Hkv = k.shape[1]
    rep = Hq // Hkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (dh ** 0.5)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)

"""Pieces of the plain float32 references, shared by the architecture files.

Each architecture file under ``bench/archs/`` writes its decoder's
reference (``logits_at``) from the published description alone, out of
these pieces: RMSNorm, rotary position embedding (rotate-half form) and
causal grouped-query attention in blocks of query rows.  None of it
imports anything of the program; there is no cache, no kernel and no
batching: one sequence, every position.

``rnd`` rounds both operands of every matrix multiplication.  The identity
gives the reference; :func:`fp8` gives the control, the same arithmetic
with 8-bit floating-point operands, one precision below the bfloat16 the
configurations compute in.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512            # query rows per attention block (bounds memory)


def identity(x):
    return x


def fp8(x):
    """Round to float8 e4m3 with one per-tensor scale (absmax to 448)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = 448.0 / amax
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def f32(tree):
    """Every leaf of ``tree`` in float32: the reference computes in float32
    whatever type the weights are stored in."""
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x: (S, H, hd); rotate-half rotary embedding at positions ``pos``."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attend(q, k, v, rnd=identity):
    """Causal grouped-query attention of one sequence: q ``(S, Hq, hd)``,
    k and v ``(S, Hkv, hd)`` -> ``(S, Hq * hd)``, ``Q_BLOCK`` query rows at
    a time.  ``S`` must be a multiple of ``Q_BLOCK``."""
    S, Hq, hd = q.shape
    Hkv = k.shape[1]
    rep = Hq // Hkv
    if S % Q_BLOCK:
        raise ValueError(f"sequence length {S} is not a multiple of "
                         f"{Q_BLOCK}")
    pos = jnp.arange(S)
    qg = q.reshape(S, Hkv, rep, hd)

    def block(i):
        lo = i * Q_BLOCK
        qb = jax.lax.dynamic_slice_in_dim(qg, lo, Q_BLOCK, 0)
        s = jnp.einsum("qgrh,kgh->grqk", rnd(qb), rnd(k)) / math.sqrt(hd)
        qpos = lo + jnp.arange(Q_BLOCK)
        causal = pos[None, :] <= qpos[:, None]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("grqk,kgh->qgrh", rnd(p), rnd(v))
        return o.reshape(Q_BLOCK, Hq * hd)

    return jax.lax.map(block, jnp.arange(S // Q_BLOCK)).reshape(S, Hq * hd)

"""Pure-jnp oracle for ragged decode attention: dense scores over the whole
cache with a per-slot validity mask.  This is byte-for-byte the math the
serving decode path always used (``layers.decode_attention``), kept here so
the Pallas kernel has exactly one reference to be validated against."""

import math

import jax
import jax.numpy as jnp


def ragged_decode_ref(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                      pos: jax.Array) -> jax.Array:
    """q: (B, Hq, hd); k,v: (B, Hkv, Smax, hd) head-major; pos: (B,) int32
    — the index of each slot's newest token (inclusive).  Returns
    (B, Hq, hd) float32."""
    B, Hq, hd = q.shape
    Hkv, Smax = k_cache.shape[1], k_cache.shape[2]
    rep = Hq // Hkv
    qr = q.reshape(B, Hkv, rep, hd)
    s = jnp.einsum("bgrh,bgsh->bgrs", qr, k_cache,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    valid = jnp.arange(Smax)[None, :] <= pos[:, None]        # (B, Smax)
    s = jnp.where(valid[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrs,bgsh->bgrh", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Hq, hd)

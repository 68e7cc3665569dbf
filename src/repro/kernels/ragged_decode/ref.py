"""Pure-jnp oracle for ragged decode attention: write the token's K/V into
the stacked cache, then dense scores over the layer's whole cache with a
per-slot validity mask.  This is the math the serving decode path runs off
the TPU, kept here so the Pallas kernel has exactly one reference to be
validated against."""

import math

import jax
import jax.numpy as jnp


def decode_attend_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                      pos: jax.Array) -> jax.Array:
    """q: (B, Hq, hd); k,v: one layer's (B, Hkv, hd, S) cache; pos: (B,)
    int32 — rows ``0..pos[b]`` are live.  Returns (B, Hq, hd) float32."""
    B, Hq, hd = q.shape
    Hkv, S = k.shape[1], k.shape[3]
    rep = Hq // Hkv
    qr = q.reshape(B, Hkv, rep, hd)
    s = jnp.einsum("bgrh,bghs->bgrs", qr, k,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    valid = jnp.arange(S)[None, :] <= pos[:, None]           # (B, S)
    s = jnp.where(valid[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrs,bghs->bgrh", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Hq, hd)


def ragged_decode_ref(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                      k_new: jax.Array, v_new: jax.Array, pos: jax.Array,
                      layer):
    """q: (B, Hq, hd); k,v caches: stacked (L, B, Hkv, hd, Smax); k_new,
    v_new: (B, Hkv, hd); pos: (B,) int32 — each slot's newest token, written
    at that position (past the cache: dropped) and attended inclusively;
    layer: int32 scalar.  Returns (out (B, Hq, hd) float32, k_cache,
    v_cache)."""
    b = jnp.arange(q.shape[0])
    k_cache = k_cache.at[layer, b, :, :, pos].set(
        k_new.astype(k_cache.dtype), mode="drop")
    v_cache = v_cache.at[layer, b, :, :, pos].set(
        v_new.astype(v_cache.dtype), mode="drop")
    k = jax.lax.dynamic_index_in_dim(k_cache, layer, 0, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(v_cache, layer, 0, keepdims=False)
    return decode_attend_ref(q, k, v, pos), k_cache, v_cache

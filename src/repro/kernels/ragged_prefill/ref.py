"""Pure-jnp oracle for chunked ragged prefill attention: dense scores over
the whole cache with per-slot causal + chunk-length masks.  Mathematically
this is ``layers.blocked_attention``'s causal semantics restated at a
per-slot query offset (the chunk's queries see every resident cache row up
to their own absolute position), kept dense-and-masked here so the Pallas
kernel has exactly one reference to be validated against — the same split
as ``ragged_decode``."""

import math

import jax
import jax.numpy as jnp


def ragged_prefill_ref(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                       start: jax.Array, qlen: jax.Array) -> jax.Array:
    """q: (B, T, Hq, hd) — chunk token ``i`` of slot ``b`` sits at absolute
    position ``start[b] + i``; k,v: (B, Hkv, Smax, hd) head-major caches
    already holding the chunk's own K/V rows; start, qlen: (B,) int32.
    Returns (B, T, Hq, hd) float32 with rows ``i >= qlen[b]`` zeroed."""
    B, T, Hq, hd = q.shape
    Hkv, Smax = k_cache.shape[1], k_cache.shape[2]
    rep = Hq // Hkv
    qr = q.reshape(B, T, Hkv, rep, hd)
    s = jnp.einsum("btgrh,bgsh->btgrs", qr, k_cache,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    qpos = start[:, None] + jnp.arange(T)[None, :]            # (B, T)
    causal = jnp.arange(Smax)[None, None, :] <= qpos[:, :, None]
    s = jnp.where(causal[:, :, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("btgrs,bgsh->btgrh", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    out = out.reshape(B, T, Hq, hd)
    valid = jnp.arange(T)[None, :] < qlen[:, None]            # (B, T)
    return jnp.where(valid[:, :, None, None], out, 0.0)

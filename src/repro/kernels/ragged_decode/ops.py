"""Public ragged decode-attention op: Pallas on TPU, interpret mode for
validation, jnp oracle fallback elsewhere.

Unlike the other kernel wrappers this one is *not* jitted here — it is
always traced inside a caller's jit (``Model.decode_fused``), and the
backend choice is made at trace time.
:func:`force_pallas` flips the choice for validation; because the decision
is baked in at trace time, build a fresh :class:`~repro.models.Model`
(fresh jit cache) inside the context to exercise the kernel end-to-end.
"""

from __future__ import annotations

import contextlib

import jax

from .kernel import ragged_decode_pallas
from .ref import ragged_decode_ref

_FORCED = False


@contextlib.contextmanager
def force_pallas(enable: bool = True):
    """Route :func:`ragged_decode_attention` through the Pallas kernel
    (interpret mode off-TPU) for traces entered inside this context."""
    global _FORCED
    prev, _FORCED = _FORCED, enable
    try:
        yield
    finally:
        _FORCED = prev


def ragged_decode_attention(q: jax.Array, k_cache: jax.Array,
                            v_cache: jax.Array, k_new: jax.Array,
                            v_new: jax.Array, pos: jax.Array, layer, *,
                            block_k: int | None = None):
    """One-token GQA attention against a ragged batch cache, writing the
    token's K/V first.

    q: (B, Hq, hd); k,v caches: stacked (L, B, Hkv, hd, Smax); k_new, v_new:
    (B, Hkv, hd); pos: (B,) int32 index of each slot's newest token, where
    its K/V is written (a position past the cache writes nothing) and up to
    which it attends; layer: int32 scalar.  ``block_k`` fixes the kernel's
    block (tests); by default it follows the shapes.  Returns (out (B, Hq,
    hd) float32, k_cache, v_cache).
    """
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu or _FORCED:
        B, Hq, hd = q.shape
        Hkv = k_cache.shape[2]
        rep = Hq // Hkv
        out, k_cache, v_cache = ragged_decode_pallas(
            q.reshape(B, Hkv, rep, hd), k_cache, v_cache, k_new, v_new, pos,
            layer, block_k=block_k, interpret=not on_tpu)
        return out.reshape(B, Hq, hd), k_cache, v_cache
    return ragged_decode_ref(q, k_cache, v_cache, k_new, v_new, pos, layer)

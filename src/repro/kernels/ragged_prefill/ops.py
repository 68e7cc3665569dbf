"""Public chunked ragged prefill-attention op: Pallas on TPU, interpret mode
for validation, jnp oracle fallback elsewhere.

Same dispatch contract as :mod:`repro.kernels.ragged_decode`: the op is not
jitted here — it is always traced inside a caller's jit
(``Model.prefill_chunk``), and the backend choice is baked in at trace time.
:func:`force_pallas` flips the choice for validation; build a fresh
:class:`~repro.models.Model` (fresh jit cache) inside the context to
exercise the kernel end-to-end.
"""

from __future__ import annotations

import contextlib

import jax

from .kernel import ragged_prefill_pallas
from .ref import ragged_prefill_ref

_FORCED = False


@contextlib.contextmanager
def force_pallas(enable: bool = True):
    """Route :func:`ragged_prefill_attention` through the Pallas kernel
    (interpret mode off-TPU) for traces entered inside this context."""
    global _FORCED
    prev, _FORCED = _FORCED, enable
    try:
        yield
    finally:
        _FORCED = prev


def ragged_prefill_attention(q: jax.Array, k_cache: jax.Array,
                             v_cache: jax.Array, start: jax.Array,
                             qlen: jax.Array, *,
                             block_k: int = 128) -> jax.Array:
    """Chunked GQA prefill attention against a ragged batch cache.

    q: (B, T, Hq, hd) — chunk token ``i`` of slot ``b`` is at absolute
    position ``start[b] + i``; k,v: (B, Hkv, Smax, hd) head-major caches
    already holding the chunk's K/V rows; start, qlen: (B,) int32 (chunk
    origin and live rows).  Returns (B, T, Hq, hd) float32 with padded rows
    zeroed.
    """
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu or _FORCED:
        B, T, Hq, hd = q.shape
        Hkv = k_cache.shape[1]
        rep = Hq // Hkv
        # fold GQA into the query rows: (B, T, Hkv, rep, hd) ->
        # (B, Hkv, T*rep, hd), row i = chunk token i // rep
        qf = q.reshape(B, T, Hkv, rep, hd).transpose(0, 2, 1, 3, 4)
        qf = qf.reshape(B, Hkv, T * rep, hd)
        out = ragged_prefill_pallas(qf, k_cache, v_cache, start, qlen,
                                    rep=rep, block_k=block_k,
                                    interpret=not on_tpu)
        out = out.reshape(B, Hkv, T, rep, hd).transpose(0, 2, 1, 3, 4)
        return out.reshape(B, T, Hq, hd)
    return ragged_prefill_ref(q, k_cache, v_cache, start, qlen)

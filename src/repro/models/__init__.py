"""Model registry: family -> module with a uniform functional interface.

Every family module provides::

    init(cfg, key)        -> (params, specs)         specs: logical axis names
    forward(cfg, p, batch)-> logits                  (training compute)
    prefill(cfg, p, batch)-> (last logits, cache)
    decode(cfg, p, token, pos, cache) -> (logits, cache)
                                         pos: scalar or per-slot (B,) vector
    cache_spec(cfg, B, S) -> pytree of ShapeDtypeStruct
    cache_logical_axes(cfg) -> matching logical-axis tree
    cache_seq_axes(cfg)   -> axis-index tree: which axis grows with decode
                             position (None = fixed-size state)

On top of those, every :class:`Model` exposes per-slot session helpers
(``extract_session`` / ``insert_session``) that slice one sequence's cache
state out of / into a batch cache — the substrate for ragged continuous
batching and live session migration between serving replicas — and
``decode_fused``, the serving fast path: a donated-cache, on-device-greedy,
k-token ``lax.scan`` over the family's single-step ``decode`` (the family
modules therefore keep ``decode`` position-pure: all cross-step state lives
in the carried cache/pos, never in Python)."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import jamba, mamba2, moe, sessions, transformer, vlm


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable
    forward: Callable
    prefill: Callable
    decode: Callable
    decode_jit: Callable          # jitted decode owned by this Model: every
                                  # engine/replica built over it shares one
                                  # compiled executable, and the executable's
                                  # lifetime is the Model's (no global cache)
    decode_fused: Callable        # (params, token (B,1), pos (B,), cache, k)
                                  # -> (tokens (B,k), next_token, pos, cache)
                                  # greedy fast path: cache DONATED (updated
                                  # in place, the argument buffer is dead
                                  # after the call), argmax on device, k
                                  # decode steps per dispatch (lax.scan) —
                                  # one host sync per k tokens, not one
                                  # logits transfer per token
    cache_spec: Callable
    cache_logical_axes: Callable
    cache_seq_axes: Callable
    extract_session: Callable     # (cache, slot, pos) -> session dict (numpy)
    insert_session: Callable      # (cache, slot, session) -> new cache
    prefill_chunk: Callable | None = None
                                  # (params, tokens (B,T), cache, start (B,),
                                  # qlen (B,)) -> (logits (B,1,V), cache)
                                  # chunked prefill step with the cache
                                  # DONATED (updated in place between
                                  # chunks); None for families whose prefill
                                  # is not chunkable (they prefill a prompt
                                  # as one whole-sequence "chunk")


_FAMILY = {
    "dense": transformer,
    "audio": transformer,
    "moe": moe,
    "ssm": mamba2,
    "hybrid": jamba,
    "vlm": vlm,
}


def _fused_decode(cfg: ModelConfig, mod) -> Callable:
    """Build the donated k-token greedy decode: a ``lax.scan`` over the
    family's single-step ``decode`` with the argmax inside the jit, so
    logits never leave the device and the KV/state cache is updated in
    place (``donate_argnums``) instead of being copied every token.

    ``k`` is static (one executable per chunk size).  The caller must treat
    the cache argument as CONSUMED — pass the returned cache forward and
    never touch the old reference (sessions are safe: they hold host-numpy
    copies, see :mod:`repro.models.sessions`).
    """
    def fused(params, token, pos, cache, k: int):
        def step(carry, _):
            tok, p, c = carry
            logits, c = mod.decode(cfg, params, tok, p, c)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return (nxt[:, None], p + 1, c), nxt
        with jax.named_scope("decode_fused"):
            (token, pos, cache), toks = jax.lax.scan(
                step, (token, pos, cache), None, length=k)
            return jnp.moveaxis(toks, 0, 1), token, pos, cache

    return jax.jit(fused, static_argnums=4, donate_argnums=3)


def _chunked_prefill(cfg: ModelConfig, mod) -> Callable | None:
    """Jitted chunked-prefill step with the growing cache donated between
    chunks, for families whose prefill is expressible as repeated
    fixed-size chunk consumption (attention caches written at per-slot
    offsets).  The audio family shares the transformer module but prefills
    from frames, not token ids, so it keeps the whole-sequence path."""
    if not hasattr(mod, "prefill_chunk") or cfg.family == "audio":
        return None

    def chunk(params, tokens, cache, start, qlen):
        with jax.named_scope("prefill_chunk"):
            return mod.prefill_chunk(cfg, params, tokens, cache, start, qlen)

    return jax.jit(chunk, donate_argnums=2)


def get_model(cfg: ModelConfig) -> Model:
    mod = _FAMILY[cfg.family]
    bind = lambda f: (lambda *a, **kw: f(cfg, *a, **kw))

    def extract_session(cache, slot: int, pos: int):
        return sessions.extract_session(cache, slot, pos,
                                        mod.cache_logical_axes(cfg),
                                        mod.cache_seq_axes(cfg))

    def insert_session(cache, slot: int, session):
        with jax.named_scope("insert_session"):
            return sessions.insert_session(cache, slot, session,
                                           mod.cache_logical_axes(cfg))

    return Model(cfg=cfg, init=bind(mod.init), forward=bind(mod.forward),
                 prefill=bind(mod.prefill), decode=bind(mod.decode),
                 decode_jit=jax.jit(bind(mod.decode)),
                 decode_fused=_fused_decode(cfg, mod),
                 cache_spec=bind(mod.cache_spec),
                 cache_logical_axes=bind(mod.cache_logical_axes),
                 cache_seq_axes=bind(mod.cache_seq_axes),
                 extract_session=extract_session,
                 insert_session=insert_session,
                 prefill_chunk=_chunked_prefill(cfg, mod))

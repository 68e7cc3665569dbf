"""Dense decoder-only transformer (qwen2 / qwen2.5 / starcoder2 / smollm) and
encoder-only audio backbone (hubert) — scan-over-layers with block remat.

Layer stacking: per-layer params are stacked along a leading L axis and the
block is a single rematerialized function scanned over layers — keeps the HLO
compact at 24-100 layers and bounds saved activations to one (B,S,D) residual
per layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..distributed.sharding import constrain
from . import layers as L


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _layer_init(cfg: ModelConfig, key):
    k1, k2 = jax.random.split(key)
    p, s = {}, {}
    p["ln1"], s["ln1"] = L.norm_init(cfg.d_model, cfg.norm, jnp.dtype(cfg.param_dtype))
    p["attn"], s["attn"] = L.attention_init(cfg, k1)
    p["ln2"], s["ln2"] = L.norm_init(cfg.d_model, cfg.norm, jnp.dtype(cfg.param_dtype))
    p["mlp"], s["mlp"] = L.mlp_init(cfg, k2)
    return p, s


def init(cfg: ModelConfig, key) -> tuple[dict, dict]:
    kemb, klay = jax.random.split(key)
    p, s = {}, {}
    p["tok"], s["tok"] = L.embedding_init(cfg, kemb)
    lkeys = jax.random.split(klay, cfg.n_layers)
    p["layers"] = jax.vmap(lambda k: _layer_init(cfg, k)[0])(lkeys)
    _, spec1 = _layer_init(cfg, jax.random.PRNGKey(0))
    s["layers"] = jax.tree.map(lambda t: (None, *t), spec1,
                               is_leaf=lambda t: isinstance(t, tuple))
    p["ln_f"], s["ln_f"] = L.norm_init(cfg.d_model, cfg.norm,
                                       jnp.dtype(cfg.param_dtype))
    if cfg.family == "audio":      # classification head over frame vocab
        p["head"], s["head"] = L.dense_init(
            jax.random.fold_in(key, 7), cfg.d_model, cfg.vocab,
            "fsdp", "vocab", jnp.dtype(cfg.param_dtype))
    return p, s


# ---------------------------------------------------------------------------
# block
# ---------------------------------------------------------------------------

def _block(cfg: ModelConfig, lp, x, positions):
    h = L.apply_norm(lp["ln1"], x, cfg.norm)
    a = L.attention_apply(cfg, lp["attn"], h, positions=positions)
    x = x + a.x
    h = L.apply_norm(lp["ln2"], x, cfg.norm)
    x = x + L.mlp_apply(cfg, lp["mlp"], h)
    return constrain(x, "batch", "seq_sp", None)


def _block_prefill(cfg: ModelConfig, lp, x, positions):
    h = L.apply_norm(lp["ln1"], x, cfg.norm)
    a = L.attention_apply(cfg, lp["attn"], h, positions=positions)
    x = x + a.x
    h = L.apply_norm(lp["ln2"], x, cfg.norm)
    x = x + L.mlp_apply(cfg, lp["mlp"], h)
    return constrain(x, "batch", "seq_sp", None), (a.k, a.v)


def _block_prefill_chunk(cfg: ModelConfig, lp, x, kfull, vfull, layer_idx,
                         start, qlen, positions):
    h = L.apply_norm(lp["ln1"], x, cfg.norm)
    out, kfull, vfull = L.attention_prefill_chunk_inplace(
        cfg, lp["attn"], h, kfull, vfull, layer_idx, start, qlen, positions)
    x = x + out
    h = L.apply_norm(lp["ln2"], x, cfg.norm)
    x = x + L.mlp_apply(cfg, lp["mlp"], h)
    return x, kfull, vfull


def _block_decode(cfg: ModelConfig, lp, x, kfull, vfull, layer_idx, pos):
    h = L.apply_norm(lp["ln1"], x, cfg.norm)
    out, kfull, vfull = L.attention_decode_inplace(
        cfg, lp["attn"], h, kfull, vfull, layer_idx, pos)
    x = x + out
    h = L.apply_norm(lp["ln2"], x, cfg.norm)
    x = x + L.mlp_apply(cfg, lp["mlp"], h)
    return x, kfull, vfull


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def _inputs_to_x(cfg: ModelConfig, p, batch):
    if cfg.family == "audio":
        x = batch["frames"].astype(jnp.dtype(cfg.compute_dtype))
        return constrain(x, "batch", "seq_sp", None)
    return L.embed_tokens(cfg, p["tok"], batch["tokens"])


def forward(cfg: ModelConfig, p, batch) -> jax.Array:
    """Full-sequence forward -> logits (training/prefill compute)."""
    x = _inputs_to_x(cfg, p, batch)
    positions = jnp.arange(x.shape[1])
    blk = jax.checkpoint(lambda x, lp: _block(cfg, lp, x, positions))

    def body(x, lp):
        return blk(x, lp), None

    x, _ = jax.lax.scan(body, x, p["layers"])
    x = L.apply_norm(p["ln_f"], x, cfg.norm)
    if cfg.family == "audio":
        cdt = jnp.dtype(cfg.compute_dtype)
        return constrain(x @ p["head"].astype(cdt), "batch", "seq_sp", "vocab")
    return L.lm_head(cfg, p["tok"], x)


def prefill(cfg: ModelConfig, p, batch):
    """Forward + KV caches; returns (last-token logits, cache)."""
    x = _inputs_to_x(cfg, p, batch)
    positions = jnp.arange(x.shape[1])
    blk = jax.checkpoint(lambda x, lp: _block_prefill(cfg, lp, x, positions))

    def body(x, lp):
        x, kv = blk(x, lp)
        return x, kv

    x, (ks, vs) = jax.lax.scan(body, x, p["layers"])
    x = L.apply_norm(p["ln_f"], x, cfg.norm)
    logits = L.lm_head(cfg, p["tok"], x[:, -1:])
    return logits, {"k": ks, "v": vs}        # (L, B, Hkv, hd, S)


def prefill_chunk(cfg: ModelConfig, p, tokens, cache, start, qlen):
    """Consume one fixed-size prompt chunk against growing
    (L, B, Hkv, hd, Smax) caches — the chunked-prefill admission path.  ``tokens``:
    (B, T) chunk ids (rows past ``qlen[b]`` are padding); ``start``: (B,)
    absolute position of each slot's first chunk token; ``qlen``: (B,) live
    tokens.  The stacked caches ride the scan carry and take a T-row
    dynamic scatter per layer, so the jit can donate them between chunks
    (``Model.prefill_chunk``).  Returns (logits at each slot's last live
    token (B, 1, V), cache) — the logits are only meaningful once the
    chunk covering the prompt's final token has been consumed."""
    x = L.embed_tokens(cfg, p["tok"], tokens)
    B, T = tokens.shape
    start = jnp.asarray(start, jnp.int32).reshape(-1)
    qlen = jnp.asarray(qlen, jnp.int32).reshape(-1)
    positions = start[:, None] + jnp.arange(T)[None, :]

    def body(carry, xs):
        x, kfull, vfull = carry
        lp, i = xs
        x, kfull, vfull = _block_prefill_chunk(cfg, lp, x, kfull, vfull, i,
                                               start, qlen, positions)
        return (x, kfull, vfull), None

    (x, ks, vs), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (p["layers"], jnp.arange(cfg.n_layers)))
    x = L.apply_norm(p["ln_f"], x, cfg.norm)
    last = jnp.maximum(qlen - 1, 0)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)
    return L.lm_head(cfg, p["tok"], x_last), {"k": ks, "v": vs}


def decode(cfg: ModelConfig, p, token, pos, cache):
    """One decode step against (L, B, Hkv, hd, Smax) caches.  The stacked
    caches ride the scan carry and the decode kernel updates them in place
    (one block per slot), so per-layer traffic is the attention
    read + a 1-token write.  ``pos``
    is a scalar or a per-slot (B,) vector — ragged batches decode each slot
    at its own position.  This is also the single-step body
    ``Model.decode_fused`` scans k times with the cache donated: all
    cross-step state must stay in (pos, cache) so the scan carry is the
    whole contract."""
    x = L.embed_tokens(cfg, p["tok"], token)
    pos = L.position_vector(pos, x.shape[0])

    def body(carry, xs):
        x, kfull, vfull = carry
        lp, i = xs
        x, kfull, vfull = _block_decode(cfg, lp, x, kfull, vfull, i, pos)
        return (x, kfull, vfull), None

    (x, ks, vs), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (p["layers"], jnp.arange(cfg.n_layers)))
    x = L.apply_norm(p["ln_f"], x, cfg.norm)
    return L.lm_head(cfg, p["tok"], x), {"k": ks, "v": vs}


def cache_spec(cfg: ModelConfig, batch: int, max_seq: int):
    kv = L.kv_spec(cfg, (cfg.n_layers,), batch, max_seq)
    return {"k": kv, "v": kv}


def cache_logical_axes(cfg: ModelConfig):
    return {"k": L.kv_logical_axes(1), "v": L.kv_logical_axes(1)}


def cache_seq_axes(cfg: ModelConfig):
    """Axis index (in the full cache leaf) that grows with decode position;
    None = fixed-size state.  Used by session extract/insert."""
    return {"k": L.kv_seq_axis(1), "v": L.kv_seq_axis(1)}

"""Plain reference of the served decoder, in float32 at ``highest`` precision.

A Llama/Qwen2-style decoder written from the published description alone:
token embedding, per layer RMSNorm -> grouped-query attention with rotary
position embedding (rotate-half form, ``rope_theta``) and optional QKV bias
-> residual -> RMSNorm -> SiLU-gated MLP -> residual, a final RMSNorm and
the LM head tied to the embedding.  It imports nothing of the program and
has no cache, no kernels and no batching: one sequence, every position.

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


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x: (S, H, hd); rotate-half rotary embedding at positions ``pos``."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits_at(published: dict, arch: dict, w: dict, tokens, rows,
              rnd=identity):
    """Logits ``(R, V)`` at positions ``rows`` of the sequence ``tokens``
    ``(S,)``.  Causal, so padding after the last row changes nothing."""
    D = published["hidden_size"]
    Hq = published["num_attention_heads"]
    Hkv = published["num_key_value_heads"]
    hd = published.get("head_dim", D // Hq)
    eps = published["rms_norm_eps"]
    theta = published["rope_theta"]
    rep = Hq // Hkv
    S = tokens.shape[0]
    nq = S // Q_BLOCK if S % Q_BLOCK == 0 else None
    pos = jnp.arange(S)

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b))

    def attend(q, k, v):
        """q: (S, Hq, hd), k/v: (S, Hkv, hd) -> (S, Hq*hd)."""
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

        if nq is None:
            raise ValueError(f"sequence length {S} is not a multiple of "
                             f"{Q_BLOCK}")
        return jax.lax.map(block, jnp.arange(nq)).reshape(S, Hq * hd)

    def layer(x, lw):
        a = lw["attn"]
        h = _rms(x, lw["ln1"]["scale"], eps)
        q, k, v = mm(h, a["wq"]), mm(h, a["wk"]), mm(h, a["wv"])
        if arch["qkv_bias"]:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = _rope(q.reshape(S, Hq, hd), pos, theta)
        k = _rope(k.reshape(S, Hkv, hd), pos, theta)
        x = x + mm(attend(q, k, v.reshape(S, Hkv, hd)), a["wo"])
        m = lw["mlp"]
        h = _rms(x, lw["ln2"]["scale"], eps)
        g = mm(h, m["w_gate"])
        x = x + mm(jax.nn.silu(g) * mm(h, m["w_up"]), m["w_down"])
        return x, None

    embed = w["tok"]["embed"]
    x = embed[tokens]
    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = _rms(x[rows], w["ln_f"]["scale"], eps)
    return mm(x, embed.T)


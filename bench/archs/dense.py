"""Dense decoder, Llama/Qwen2 style, with the LM head tied to the embedding.

A configuration names this file with ``"arch": "dense"``.  It reads the
published keys ``hidden_size``, ``intermediate_size``,
``num_attention_heads``, ``num_key_value_heads``, ``num_hidden_layers``,
``vocab_size``, ``head_dim`` (``hidden_size / num_attention_heads`` where
absent), ``rms_norm_eps``, ``rope_theta`` and ``tie_word_embeddings``, and
the architecture's ``qkv_bias`` and ``compute_dtype``.

Per layer: RMSNorm -> grouped-query attention with rotary position
embedding and optional QKV bias -> residual -> RMSNorm -> SiLU-gated MLP
-> residual; then a final RMSNorm and the tied head.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import counting, reference, weights


def _head_dim(hf: dict) -> int:
    return hf.get("head_dim", hf["hidden_size"] // hf["num_attention_heads"])


def program_want(published: dict, arch: dict) -> dict:
    """The program ``ModelConfig`` attributes the file fixes."""
    hf = published
    return {"d_model": hf["hidden_size"], "n_layers": hf["num_hidden_layers"],
            "n_heads": hf["num_attention_heads"],
            "n_kv_heads": hf["num_key_value_heads"],
            "d_ff": hf["intermediate_size"], "vocab": hf["vocab_size"],
            "rope_theta": hf["rope_theta"],
            "tie_embeddings": hf["tie_word_embeddings"],
            "qkv_bias": arch["qkv_bias"], "hd": _head_dim(hf),
            "compute_dtype": arch["compute_dtype"]}


def make(published: dict, arch: dict, key) -> dict:
    """The weight tree for ``published`` sizes, in float32; call under
    ``jax.jit``."""
    D = published["hidden_size"]
    F = published["intermediate_size"]
    V = published["vocab_size"]
    L = published["num_hidden_layers"]
    Hq = published["num_attention_heads"]
    Hkv = published["num_key_value_heads"]
    hd = _head_dim(published)
    normal = weights.normals(key)

    attn = {"wq": normal((L, D, Hq * hd), 1 / math.sqrt(D)),
            "wk": normal((L, D, Hkv * hd), 1 / math.sqrt(D)),
            "wv": normal((L, D, Hkv * hd), 1 / math.sqrt(D)),
            "wo": normal((L, Hq * hd, D), 1 / math.sqrt(Hq * hd))}
    if arch["qkv_bias"]:
        attn.update(bq=normal((L, Hq * hd), weights.BIAS_STD),
                    bk=normal((L, Hkv * hd), weights.BIAS_STD),
                    bv=normal((L, Hkv * hd), weights.BIAS_STD))
    mlp = {"w_gate": normal((L, D, F), 1 / math.sqrt(D)),
           "w_up": normal((L, D, F), 1 / math.sqrt(D)),
           "w_down": normal((L, F, D), 1 / math.sqrt(F))}
    ones = jnp.ones((L, D), jnp.float32)
    # final norm scale chosen so that the logits have std ``LOGIT_STD``
    final = weights.LOGIT_STD / (weights.EMBED_STD * math.sqrt(D))
    return {"tok": {"embed": normal((V, D), weights.EMBED_STD)},
            "layers": {"ln1": {"scale": ones}, "attn": attn,
                       "ln2": {"scale": ones}, "mlp": mlp},
            "ln_f": {"scale": jnp.full((D,), final, jnp.float32)}}


def logits_at(published: dict, arch: dict, w: dict, tokens, rows,
              rnd=reference.identity):
    """Float32 logits ``(R, V)`` at positions ``rows`` of the sequence
    ``tokens`` ``(S,)``.  Causal, so padding after the last row changes
    nothing."""
    Hq = published["num_attention_heads"]
    Hkv = published["num_key_value_heads"]
    hd = _head_dim(published)
    eps = published["rms_norm_eps"]
    theta = published["rope_theta"]
    S = tokens.shape[0]
    pos = jnp.arange(S)

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b))

    def layer(x, lw):
        lw = reference.f32(lw)
        a = lw["attn"]
        h = reference.rms(x, lw["ln1"]["scale"], eps)
        q, k, v = mm(h, a["wq"]), mm(h, a["wk"]), mm(h, a["wv"])
        if arch["qkv_bias"]:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = reference.rope(q.reshape(S, Hq, hd), pos, theta)
        k = reference.rope(k.reshape(S, Hkv, hd), pos, theta)
        o = reference.attend(q, k, v.reshape(S, Hkv, hd), rnd)
        x = x + mm(o, a["wo"])
        m = lw["mlp"]
        h = reference.rms(x, lw["ln2"]["scale"], eps)
        g = mm(h, m["w_gate"])
        x = x + mm(jax.nn.silu(g) * mm(h, m["w_up"]), m["w_down"])
        return x, None

    embed = reference.f32(w["tok"]["embed"])
    x, _ = jax.lax.scan(layer, embed[tokens], w["layers"])
    x = reference.rms(x[rows], reference.f32(w["ln_f"]["scale"]), eps)
    return mm(x, embed.T)


def dims(published: dict) -> counting.Dims:
    """Attention sizes and the weight-matmul FLOPs of one token through
    every layer: QKV, output projection and the gated MLP."""
    D = published["hidden_size"]
    Hq = published["num_attention_heads"]
    Hkv = published["num_key_value_heads"]
    hd = _head_dim(published)
    L = published["num_hidden_layers"]
    qkv = D * (Hq + 2 * Hkv) * hd
    out = Hq * hd * D
    mlp = 3 * D * published["intermediate_size"]
    return counting.Dims(layers=L, d_model=D, heads=Hq, kv_heads=Hkv,
                         head_dim=hd, vocab=published["vocab_size"],
                         matmul_flops_per_token=2.0 * L * (qkv + out + mlp))

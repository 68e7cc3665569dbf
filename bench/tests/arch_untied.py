"""A test-only architecture: a dense decoder without QKV bias whose LM head
is a matrix of its own, not the embedding.  The tests drop it into a
throwaway benchmark root as ``bench/archs/untied.py`` to show that a new
architecture is one file there and a configuration that names it."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import counting, reference, weights


def _sizes(hf: dict):
    return (hf["hidden_size"], hf["intermediate_size"], hf["vocab_size"],
            hf["num_hidden_layers"], hf["num_attention_heads"],
            hf["num_key_value_heads"],
            hf["hidden_size"] // hf["num_attention_heads"])


def program_want(published: dict, arch: dict) -> dict:
    D, F, V, L, Hq, Hkv, hd = _sizes(published)
    return {"d_model": D, "d_ff": F, "vocab": V, "n_layers": L,
            "n_heads": Hq, "n_kv_heads": Hkv, "hd": hd,
            "rope_theta": published["rope_theta"], "tie_embeddings": False,
            "qkv_bias": False, "compute_dtype": arch["compute_dtype"]}


def make(published: dict, arch: dict, key) -> dict:
    D, F, V, L, Hq, Hkv, hd = _sizes(published)
    normal = weights.normals(key)
    attn = {"wq": normal((L, D, Hq * hd), 1 / math.sqrt(D)),
            "wk": normal((L, D, Hkv * hd), 1 / math.sqrt(D)),
            "wv": normal((L, D, Hkv * hd), 1 / math.sqrt(D)),
            "wo": normal((L, Hq * hd, D), 1 / math.sqrt(Hq * hd))}
    mlp = {"w_gate": normal((L, D, F), 1 / math.sqrt(D)),
           "w_up": normal((L, D, F), 1 / math.sqrt(D)),
           "w_down": normal((L, F, D), 1 / math.sqrt(F))}
    ones = jnp.ones((L, D), jnp.float32)
    # with a head of std 1/sqrt(D), this final norm scale gives the logits
    # a std of LOGIT_STD
    return {"tok": {"embed": normal((V, D), weights.EMBED_STD),
                    "lm_head": normal((D, V), 1 / math.sqrt(D))},
            "layers": {"ln1": {"scale": ones}, "attn": attn,
                       "ln2": {"scale": ones}, "mlp": mlp},
            "ln_f": {"scale": jnp.full((D,), weights.LOGIT_STD, jnp.float32)}}


def logits_at(published: dict, arch: dict, w: dict, tokens, rows,
              rnd=reference.identity):
    D, F, V, L, Hq, Hkv, hd = _sizes(published)
    eps, theta = published["rms_norm_eps"], published["rope_theta"]
    S = tokens.shape[0]
    pos = jnp.arange(S)

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b))

    def layer(x, lw):
        lw = reference.f32(lw)
        a, m = lw["attn"], lw["mlp"]
        h = reference.rms(x, lw["ln1"]["scale"], eps)
        q = reference.rope(mm(h, a["wq"]).reshape(S, Hq, hd), pos, theta)
        k = reference.rope(mm(h, a["wk"]).reshape(S, Hkv, hd), pos, theta)
        v = mm(h, a["wv"]).reshape(S, Hkv, hd)
        x = x + mm(reference.attend(q, k, v, rnd), a["wo"])
        h = reference.rms(x, lw["ln2"]["scale"], eps)
        up = jax.nn.silu(mm(h, m["w_gate"])) * mm(h, m["w_up"])
        return x + mm(up, m["w_down"]), None

    tok = reference.f32(w["tok"])
    x, _ = jax.lax.scan(layer, tok["embed"][tokens], w["layers"])
    x = reference.rms(x[rows], reference.f32(w["ln_f"]["scale"]), eps)
    return mm(x, tok["lm_head"])


def dims(published: dict) -> counting.Dims:
    D, F, V, L, Hq, Hkv, hd = _sizes(published)
    per_layer = D * (Hq + 2 * Hkv) * hd + Hq * hd * D + 3 * D * F
    return counting.Dims(layers=L, d_model=D, heads=Hq, kv_heads=Hkv,
                         head_dim=hd, vocab=V,
                         matmul_flops_per_token=2.0 * L * per_layer)

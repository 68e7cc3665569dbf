"""Random weights from the seed, made by the benchmark on the device.

The benchmark, not the program, makes the weights: the reference then
takes nothing the program has made.  The tree has the program's layout
(layers stacked along a leading axis) and the type the program serves
them in (float32, cast to the compute type on use).

Every configuration gets the same scales.  Projections are normal with
the 1/sqrt(fan-in) scale; QKV biases, where the architecture has them,
have std ``BIAS_STD``.  The embedding is small (``EMBED_STD``) and the
final norm's scale sets the logits' std to ``LOGIT_STD``, so the layers,
not the current token's tied embedding, decide the next token, and the
reference's top logits stand apart by more than bfloat16 rounding moves
them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


EMBED_STD = 0.02
BIAS_STD = 0.02
LOGIT_STD = 2.0


def make(published: dict, arch: dict, key) -> dict:
    """The weight tree for ``published`` sizes; call under ``jax.jit``."""
    D = published["hidden_size"]
    F = published["intermediate_size"]
    V = published["vocab_size"]
    L = published["num_hidden_layers"]
    Hq = published["num_attention_heads"]
    Hkv = published["num_key_value_heads"]
    hd = published.get("head_dim", D // Hq)
    f32 = jnp.float32
    keys = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return jax.random.normal(next(keys), shape, f32) * std

    attn = {"wq": normal((L, D, Hq * hd), 1 / math.sqrt(D)),
            "wk": normal((L, D, Hkv * hd), 1 / math.sqrt(D)),
            "wv": normal((L, D, Hkv * hd), 1 / math.sqrt(D)),
            "wo": normal((L, Hq * hd, D), 1 / math.sqrt(Hq * hd))}
    if arch["qkv_bias"]:
        attn.update(bq=normal((L, Hq * hd), BIAS_STD),
                    bk=normal((L, Hkv * hd), BIAS_STD),
                    bv=normal((L, Hkv * hd), BIAS_STD))
    mlp = {"w_gate": normal((L, D, F), 1 / math.sqrt(D)),
           "w_up": normal((L, D, F), 1 / math.sqrt(D)),
           "w_down": normal((L, F, D), 1 / math.sqrt(F))}
    ones = jnp.ones((L, D), f32)
    # final norm scale chosen so that the logits have std ``LOGIT_STD``
    final = LOGIT_STD / (EMBED_STD * math.sqrt(D))
    return {"tok": {"embed": normal((V, D), EMBED_STD)},
            "layers": {"ln1": {"scale": ones}, "attn": attn,
                       "ln2": {"scale": ones}, "mlp": mlp},
            "ln_f": {"scale": jnp.full((D,), final, f32)}}


def make_on_device(published: dict, arch: dict, seed: int,
                   device=None) -> dict:
    """One jitted call, on ``device``, from a 31-bit ``seed``."""
    out = (None if device is None
           else jax.sharding.SingleDeviceSharding(device))
    fn = jax.jit(lambda k: make(published, arch, k),
                 out_shardings=out)
    return fn(jax.random.PRNGKey(seed))

"""The reference follows the published decoder: at toy widths and float32
it gives the program's own logits, with and without QKV bias, so the
comparison that decides ``correct`` measures the engine and not a
mismatch of definitions."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, weights
from repro.configs import get_config
from repro.models import get_model


@pytest.mark.parametrize("arch,theta,bias", [("smollm-135m", 1e4, False),
                                             ("qwen2-0.5b", 1e6, True)])
def test_reference_matches_program_forward_in_float32(arch, theta, bias):
    hf = {"hidden_size": 64, "intermediate_size": 96,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "num_hidden_layers": 2, "vocab_size": 300,
          # the program's RMSNorm epsilon, so the two agree to rounding
          "rms_norm_eps": 1e-6, "rope_theta": theta,
          "tie_word_embeddings": True}
    cfg = dataclasses.replace(get_config(arch), n_layers=2, d_model=64,
                              n_heads=4, n_kv_heads=2, d_ff=96, vocab=300,
                              compute_dtype="float32")
    w = weights.make_on_device(hf, {"qkv_bias": bias}, 3)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 300, 512),
                         jnp.int32)
    rows = jnp.asarray([0, 1, 100, 511], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = get_model(cfg).forward(w, {"tokens": tokens[None]})[0, rows]
        want = reference.logits_at(hf, {"qkv_bias": bias}, w, tokens, rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_fp8_rounds_to_eight_bit_floats():
    x = jnp.linspace(-3.0, 3.0, 1001)
    y = reference.fp8(x)
    assert float(jnp.max(jnp.abs(y - x))) <= 3.0 * 2 ** -4
    assert len(np.unique(np.asarray(y))) < 256

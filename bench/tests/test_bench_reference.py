"""The dense architecture's reference follows the published decoder: at toy
widths and float32 it gives the program's own logits, with and without
QKV bias, so the comparison that decides ``correct`` measures the engine
and not a mismatch of definitions.  Its weights and logits are pinned to
values recorded before they moved into ``bench/archs/dense.py``."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import loader, reference, weights
from bench.tests import tiny
from repro.configs import get_config
from repro.models import get_model

DENSE = loader.load_arch("dense")


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
    config = {"published": hf, "architecture": {"qkv_bias": bias}}
    w = weights.make_on_device(DENSE, config, 3)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 300, 512),
                         jnp.int32)
    rows = jnp.asarray([0, 1, 100, 511], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = get_model(cfg).forward(w, {"tokens": tokens[None]})[0, rows]
        want = DENSE.logits_at(hf, {"qkv_bias": bias}, w, tokens, rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


# The tiny configuration's weights (seed 11) and reference logits, taken on
# the CPU from ``bench/weights.py`` ``make`` and ``bench/reference.py``
# ``logits_at`` as they stood before the dense decoder moved to its own
# architecture file: a SHA-256 over every leaf's path, dtype, shape and
# bytes, and the logits of 512 seeded tokens at rows 0, 1, 100 and 511,
# columns 0, 7, 100 and 511.
PINNED = {
    False: ("1d53965b2bdded5d5d9b1519c7f0a76cb9053d997900030d7a74f01121e9ef7d",
            [[-0.5037120580673218, 2.9397568702697754, 2.4015562534332275,
              -0.5345338582992554],
             [-1.9498817920684814, 1.7600065469741821, 1.8602755069732666,
              0.31298208236694336],
             [-0.7332121133804321, 2.5524017810821533, 1.9302246570587158,
              0.6358108520507812],
             [3.1907966136932373, 1.6455453634262085, -0.0054216329008340836,
              0.586090087890625]],
            [278, 131, 173, 223]),
    True: ("522275a11aab9c86cdc449eecb0cdf795fb1734e667d5b88b7194dcc1e086b42",
           [[0.9828774333000183, 1.3526568412780762, -1.212401270866394,
             1.2221916913986206],
            [2.156315803527832, 0.9038596749305725, -1.9426251649856567,
             1.2308306694030762],
            [1.1295422315597534, 0.32872411608695984, 1.0073153972625732,
             3.2836852073669434],
            [1.130713701248169, -1.0316020250320435, 2.8726699352264404,
             1.8755064010620117]],
           [459, 162, 439, 216]),
}


@pytest.mark.parametrize("bias", [False, True])
def test_dense_weights_and_logits_are_pinned(bias):
    settings = dict(tiny.CONFIG["architecture"], qkv_bias=bias)
    w = weights.make_on_device(DENSE, dict(tiny.CONFIG,
                                           architecture=settings), 11)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(a.tobytes())
    digest, logits, argmax = PINNED[bias]
    assert h.hexdigest() == digest
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 512, 512),
                         jnp.int32)
    rows = jnp.asarray([0, 1, 100, 511], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(DENSE.logits_at(tiny.PUBLISHED, settings, w,
                                         tokens, rows))
    np.testing.assert_allclose(got[:, [0, 7, 100, 511]], logits,
                               rtol=1e-6, atol=1e-6)
    assert list(np.argmax(got, -1)) == argmax


def test_fp8_rounds_to_eight_bit_floats():
    x = jnp.linspace(-3.0, 3.0, 1001)
    y = reference.fp8(x)
    assert float(jnp.max(jnp.abs(y - x))) <= 3.0 * 2 ** -4
    assert len(np.unique(np.asarray(y))) < 256

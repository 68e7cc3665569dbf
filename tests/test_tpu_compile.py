"""Compile the serving path for a TPU v5e that is described, not attached.

Interpret mode never checks Mosaic's tiling rules, so these tests hand the
TPU compiler the two Pallas kernels, and one whole ``decode_fused`` and
``prefill_chunk`` step, at smollm-135m's published widths (bf16, B=8,
Smax=2048, Hkv=3, rep=3, hd=64) and assert the kernel survives as a
``tpu_custom_call``.  Nothing runs; a pass says the chip's compiler accepts
the program, not that it is correct or fast.

The topology is described inside a fixture only: the TPU library may be
loaded by one process at a time, and describing it at import would make
every test worker race for it.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.ragged_decode.kernel import ragged_decode_pallas
from repro.kernels.ragged_prefill.kernel import ragged_prefill_pallas
from repro.models import get_model

B, SMAX, HKV, REP, HD, T = 8, 2048, 3, 3, 64, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(chip, shp, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shp, dtype, sharding=chip)


def _tree_on(chip, tree):
    return jax.tree.map(lambda s: _on(chip, s.shape, s.dtype), tree)


def test_ragged_decode_kernel_compiles_for_v5e(one_chip):
    kv = _on(one_chip, (B, HKV, SMAX, HD))
    compiled = jax.jit(ragged_decode_pallas).lower(
        _on(one_chip, (B, HKV, REP, HD)), kv, kv,
        _on(one_chip, (B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ragged_prefill_kernel_compiles_for_v5e(one_chip):
    kv = _on(one_chip, (1, HKV, SMAX, HD))
    fn = lambda q, k, v, s, n: ragged_prefill_pallas(q, k, v, s, n, rep=REP)
    compiled = jax.jit(fn).lower(
        _on(one_chip, (1, HKV, T * REP, HD)), kv, kv,
        _on(one_chip, (1,), jnp.int32), _on(one_chip, (1,), jnp.int32)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture
def smollm(one_chip, monkeypatch):
    """smollm-135m at published widths as shapes on the described chip.
    ``ops.py`` picks the kernel by ``jax.default_backend()``, which sees
    the CPU here: steer it onto the TPU branch for this test only."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = get_model(get_config("smollm-135m"))
    params = _tree_on(one_chip, jax.eval_shape(
        lambda k: model.init(k)[0], jax.random.PRNGKey(0)))
    return model, params


def test_smollm_decode_fused_step_compiles_for_v5e(one_chip, smollm):
    model, params = smollm
    compiled = model.decode_fused.lower(
        params, _on(one_chip, (B, 1), jnp.int32),
        _on(one_chip, (B,), jnp.int32),
        _tree_on(one_chip, model.cache_spec(B, SMAX)), 4).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_smollm_prefill_chunk_step_compiles_for_v5e(one_chip, smollm):
    model, params = smollm
    i32 = lambda *shp: _on(one_chip, shp, jnp.int32)
    compiled = model.prefill_chunk.lower(
        params, i32(1, T), _tree_on(one_chip, model.cache_spec(1, SMAX)),
        i32(1), i32(1)).compile()
    assert "tpu_custom_call" in compiled.as_text()

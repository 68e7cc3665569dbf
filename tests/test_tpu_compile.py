"""Compile the serving path for a TPU v5e that is described, not attached.

Interpret mode never checks Mosaic's tiling rules, so these tests hand the
TPU compiler the two Pallas kernels, and one whole ``decode_fused`` and
``prefill_chunk`` step, at smollm-135m's published widths (bf16, B=8,
Smax=2048, Hkv=3, rep=3, hd=64) and assert the kernel survives as a
``tpu_custom_call``.  The compiled ``decode_fused`` is also held to moving
no KV cache outside the kernel: no top-level instruction but a view, the
loop and the kernel itself may produce a layer's K shape (a layer slice,
a relayout, a scatter or a whole-cache copy would).  Nothing runs; a pass
says the chip's compiler accepts the program, not that it is correct or
fast.

The topology is described inside a fixture only: the TPU library may be
loaded by one process at a time, and describing it at import would make
every test worker race for it.
"""

import collections
import dataclasses
import os
import re

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


_HEAD = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s([\w\-]+)\(")
_ARRAY = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
_VIEWS = {"parameter", "get-tuple-element", "bitcast", "tuple", "while"}


def cache_shaped_ops(hlo: str, dims, layers: int) -> list[str]:
    """Top-level instructions of a compiled module (fusion bodies skipped)
    whose result holds an array with a layer's K dims ``dims`` in any
    order, bare or with a leading ``layers`` or 1 — other than views, the
    loop and the Pallas kernel's custom call.  Matched by shape, not by
    size, so the weights' dtype converts do not count."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        if cur is None:
            m = _HEAD.match(line)
            if m:
                cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        else:
            cur.append(line)
    fused = {c for lines in comps.values() for ln in lines if " fusion(" in ln
             for c in re.findall(r"calls=%?([\w.\-]+)", ln)}
    wants = [collections.Counter(dims) + collections.Counter(lead)
             for lead in ((), (layers,), (1,))]
    found = []
    for name, lines in comps.items():
        if name in fused:
            continue
        for ln in lines:
            m = _INSTR.match(ln)
            if not m or m.group(3) in _VIEWS or "tpu_custom_call" in ln:
                continue
            shapes = [collections.Counter(int(d) for d in a.split(",") if d)
                      for a in _ARRAY.findall(m.group(2))]
            if any(s == w for s in shapes for w in wants):
                found.append(f"{name}: {m.group(3)} {m.group(1)}")
    return found


def test_ragged_decode_kernel_compiles_for_v5e(one_chip):
    kv = _on(one_chip, (2, B, HKV, HD, SMAX))
    new = _on(one_chip, (B, HKV, HD))
    compiled = jax.jit(ragged_decode_pallas, donate_argnums=(1, 2)).lower(
        _on(one_chip, (B, HKV, REP, HD)), kv, kv, new, new,
        _on(one_chip, (B,), jnp.int32), _on(one_chip, (), jnp.int32)
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert cache_shaped_ops(text, (B, HKV, SMAX, HD), 2) == []


def test_ragged_prefill_kernel_compiles_for_v5e(one_chip):
    kv = _on(one_chip, (1, HKV, SMAX, HD))
    fn = lambda q, k, v, s, n: ragged_prefill_pallas(q, k, v, s, n, rep=REP)
    compiled = jax.jit(fn).lower(
        _on(one_chip, (1, HKV, T * REP, HD)), kv, kv,
        _on(one_chip, (1,), jnp.int32), _on(one_chip, (1,), jnp.int32)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _model_on(chip, cfg):
    """A model's params as shapes on the described chip."""
    model = get_model(cfg)
    return model, _tree_on(chip, jax.eval_shape(
        lambda k: model.init(k)[0], jax.random.PRNGKey(0)))


def _decode_fused_hlo(chip, model, params, batch, smax) -> str:
    return model.decode_fused.lower(
        params, _on(chip, (batch, 1), jnp.int32),
        _on(chip, (batch,), jnp.int32),
        _tree_on(chip, model.cache_spec(batch, smax)), 4).compile().as_text()


@pytest.fixture
def on_tpu(monkeypatch):
    """``ops.py`` picks the kernel by ``jax.default_backend()``, which sees
    the CPU here: steer it onto the TPU branch for this test only."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture
def smollm(one_chip, on_tpu):
    """smollm-135m at published widths as shapes on the described chip."""
    return _model_on(one_chip, get_config("smollm-135m"))


def test_smollm_decode_fused_step_compiles_for_v5e(one_chip, smollm):
    model, params = smollm
    text = _decode_fused_hlo(one_chip, model, params, B, SMAX)
    assert "tpu_custom_call" in text
    cfg = model.cfg
    assert cache_shaped_ops(text, (B, cfg.n_kv_heads, SMAX, cfg.hd),
                            cfg.n_layers) == []


def test_qwen2_decode_fused_moves_no_cache_for_v5e(one_chip, on_tpu):
    """qwen2-0.5b's per-layer widths (Hkv=2, rep=7, hd=64) with 3 layers,
    B=16, Smax=2048: the decode loop neither slices, relayouts, scatters
    into nor copies the stacked cache."""
    cfg = dataclasses.replace(get_config("qwen2-0.5b"), n_layers=3)
    model, params = _model_on(one_chip, cfg)
    text = _decode_fused_hlo(one_chip, model, params, 16, SMAX)
    assert "tpu_custom_call" in text
    assert cache_shaped_ops(text, (16, cfg.n_kv_heads, SMAX, cfg.hd),
                            cfg.n_layers) == []


def test_smollm_prefill_chunk_step_compiles_for_v5e(one_chip, smollm):
    model, params = smollm
    i32 = lambda *shp: _on(one_chip, shp, jnp.int32)
    compiled = model.prefill_chunk.lower(
        params, i32(1, T), _tree_on(one_chip, model.cache_spec(1, SMAX)),
        i32(1), i32(1)).compile()
    assert "tpu_custom_call" in compiled.as_text()

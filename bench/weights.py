"""Random weights from the seed, made by the benchmark on the device.

The benchmark, not the program, makes the weights: the reference then
takes nothing the program has made.  The configuration's architecture file
(``bench/archs/<arch>.py``) builds the tree in the program's layout
(layers stacked along a leading axis), in float32; :func:`make_on_device`
stores it in the configuration's ``architecture.param_dtype`` (float32
where it names none), the type the program is set to hold them in.

Every architecture draws with the same scales.  Projections are normal
with the 1/sqrt(fan-in) scale; biases, where the architecture has them,
have std ``BIAS_STD``.  The embedding is small (``EMBED_STD``) and the
final norm's scale sets the logits' std to ``LOGIT_STD``, so the layers,
not the current token's tied embedding, decide the next token, and the
reference's top logits stand apart by more than bfloat16 rounding moves
them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


EMBED_STD = 0.02
BIAS_STD = 0.02
LOGIT_STD = 2.0


def normals(key, n: int = 16):
    """``normal(shape, std)``: float32 normal draws with std ``std``, the
    i-th call from the i-th of ``n`` splits of ``key``."""
    keys = iter(jax.random.split(key, n))

    def normal(shape, std):
        return jax.random.normal(next(keys), shape, jnp.float32) * std
    return normal


def param_dtype(config: dict) -> str:
    """The type the configuration stores its weights in."""
    return config["architecture"].get("param_dtype", "float32")


def make_on_device(arch, config: dict, seed: int, device=None) -> dict:
    """``arch.make``'s tree for ``config``, in its ``param_dtype``: one
    jitted call, on ``device``, from a 31-bit ``seed``."""
    dt = jnp.dtype(param_dtype(config))

    def build(key):
        tree = arch.make(config["published"], config["architecture"], key)
        return jax.tree.map(lambda a: a.astype(dt), tree)

    out = (None if device is None
           else jax.sharding.SingleDeviceSharding(device))
    return jax.jit(build, out_shardings=out)(jax.random.PRNGKey(seed))

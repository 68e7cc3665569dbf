"""Error-feedback int8 gradient compression for the cross-pod (DCN) hop.

Two pieces:

* :func:`ef_compress_grads` — the numerical transform used inside
  ``train_step`` when ``compress_dcn`` is on: per-leaf symmetric int8
  quantization with an error-feedback residual carried in optimizer state
  (Seide et al.-style 1-bit-SGD generalized to 8 bits).  On real multi-pod
  hardware the reduce order is: reduce-scatter intra-pod (ICI, fp32) ->
  all-reduce of the *compressed* payload cross-pod (DCN) -> all-gather
  intra-pod.  This function reproduces the numerics of that pipeline; the
  collective itself is exercised by the demo below and in the dry-run.

* :func:`compressed_allreduce_demo` — a shard_map collective that actually
  performs the hierarchical compressed all-reduce over a ('pod','data') mesh
  for a flat buffer, so the pattern (int8 payload over the pod axis) is
  compiled and visible in HLO.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def ef_init(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def _quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def ef_compress_grads(grads, residual):
    """Apply int8 quantization with error feedback.  Returns
    (compressed-then-decompressed grads, new residual)."""
    def one(g, r):
        x = g.astype(jnp.float32) + r
        q, scale = _quantize(x)
        deq = _dequantize(q, scale)
        return deq.astype(g.dtype), x - deq

    out = jax.tree.map(one, grads, residual)
    new_g = jax.tree.map(lambda t: t[0], out,
                         is_leaf=lambda t: isinstance(t, tuple))
    new_r = jax.tree.map(lambda t: t[1], out,
                         is_leaf=lambda t: isinstance(t, tuple))
    return new_g, new_r


def compressed_allreduce_demo(x: jax.Array, mesh) -> jax.Array:
    """Hierarchical compressed mean over a ('pod','data') mesh.

    Every device holds a distinct full gradient (here synthesized as
    ``x * (1 + 0.01*device_rank)`` so the expected mean is analytic);
    the reduction is: fp32 psum intra-pod (ICI) -> int8 all-gather across
    pods (DCN payload) -> dequantize + average."""

    def body(xs):
        pod = jax.lax.axis_index("pod")
        data = jax.lax.axis_index("data")
        ndata = jax.lax.psum(1, "data")
        rank = pod * ndata + data
        g = xs * (1.0 + 0.01 * rank.astype(jnp.float32))
        s = jax.lax.psum(g, "data")                  # fp32 intra-pod (ICI)
        q, scale = _quantize(s)
        qs = jax.lax.all_gather(q, "pod")            # int8 cross-pod (DCN)
        scales = jax.lax.all_gather(scale, "pod")
        deq = jnp.sum(qs.astype(jnp.float32) * scales[:, None], axis=0)
        npod = qs.shape[0]
        return deq / (npod * ndata)

    fn = jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
    return fn(x)

"""Layer 2 — lowered-artifact audit: assert on the jaxpr/StableHLO the
serving fast paths actually compile to, not on the Python that produced it.

For every model family this lowers ``Model.decode_fused`` and (where the
family has one) ``Model.prefill_chunk`` with tiny shapes and checks:

* **dropped-donation** — the donated KV/state cache must *actually* alias
  input to output: every cache leaf's argument in the lowered ``@main``
  carries a ``tf.aliasing_output`` attribute.  XLA silently drops
  donations it cannot honor (a dtype change, a layout mismatch, a stray
  copy in the model) and the only symptom is a per-token full-cache copy —
  the exact regression that would erase PR 4's 4.25x.  A missing alias is
  a hard error.
* **host-callback** — no callback primitive (``pure_callback``,
  ``io_callback``, ``debug_callback``, ...) may appear anywhere in the
  jaxpr: a host callback inside the decode scan serializes every chunk on
  the host.
* **f64-promotion** — no float64 value anywhere in the jaxpr: an
  accidental weak-type promotion doubles cache bandwidth and silently
  halves the roofline.
* **retrace-budget** — calling the fused decode across the supported
  chunk sizes and batch shapes must compile exactly one executable per
  (chunk, batch) cell.  A cache-miss count above that budget means
  something non-hashable/unstable leaks into the trace (a new executable
  per *call* is a serving stall every time it happens).

The checks run on ``reduced=True`` configs — donation, callback, dtype,
and retrace behaviour are structural properties of the program, identical
at reduced and production scale.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp

from .findings import SEVERITY_ERROR, Finding

#: The five serving families (one arch per family, reduced configs) the
#: audit lowers — the same set the token-identity golden tests pin.
FAMILY_ARCHS = ("qwen2-0.5b", "granite-moe-1b-a400m", "mamba2-130m",
                "jamba-v0.1-52b", "llama-3.2-vision-90b")

#: Supported decode chunk sizes / batch shapes the retrace audit sweeps.
DECODE_CHUNKS = (1, 4)
BATCH_SHAPES = (2, 3)
AUDIT_SEQ = 16
PREFILL_CHUNK_T = 4

#: Jaxpr primitives that round-trip through the host.
_CALLBACK_PRIMS = ("callback", "outside_call", "host_callback",
                   "debug_print")

# findings anchor on the module that builds the jitted fast paths
_MODELS_PATH = "src/repro/models/__init__.py"


# -- StableHLO argument parsing ---------------------------------------------

def main_arg_segments(stablehlo_text: str) -> list:
    """Split the lowered module's ``@main`` signature into one text
    segment per argument (``%arg0: tensor<...> {attrs}``), in argument
    order.  Donation shows up here as a ``tf.aliasing_output`` attribute
    on the donated argument."""
    start = stablehlo_text.index("@main(") + len("@main(")
    depth = 1
    i = start
    while depth:
        c = stablehlo_text[i]
        depth += (c == "(") - (c == ")")
        i += 1
    sig = stablehlo_text[start:i - 1]
    marks = [(int(m.group(1)), m.start())
             for m in re.finditer(r"%arg(\d+):", sig)]
    segs = [""] * len(marks)
    for (argno, pos), nxt in zip(marks, [m[1] for m in marks[1:]]
                                 + [len(sig)]):
        segs[argno] = sig[pos:nxt]
    return segs


_MLIR_DTYPES = {"float32": "f32", "float64": "f64", "float16": "f16",
                "bfloat16": "bf16", "int64": "i64", "int32": "i32",
                "int16": "i16", "int8": "i8", "uint32": "ui32",
                "uint8": "ui8", "bool": "i1"}


def mlir_tensor_type(aval) -> str:
    """The MLIR tensor type a shape/dtype lowers to (``tensor<2x4xf32>``)."""
    el = _MLIR_DTYPES[str(jnp.dtype(aval.dtype))]
    dims = "x".join(str(d) for d in aval.shape)
    return f"tensor<{dims}x{el}>" if dims else f"tensor<{el}>"


def donation_findings(stablehlo_text: str, cache_leaves,
                      label: str, path: str = _MODELS_PATH) -> list:
    """``dropped-donation`` findings for ``cache_leaves`` (a list of
    ``(leaf_name, aval)`` pairs, the flattened donated cache argument).

    Donation that survives lowering shows up as a ``tf.aliasing_output``
    attribute on the argument in ``@main``.  Only the cache is donated, so
    the multiset of aliased argument *types* must cover the multiset of
    cache-leaf types — matching by type rather than by argument index
    keeps the audit correct when jit prunes unused arguments from the
    lowering (which shifts every index after the pruned one)."""
    aliased = []
    for seg in main_arg_segments(stablehlo_text):
        if "tf.aliasing_output" in seg:
            m = re.search(r"tensor<[^>]*>", seg)
            if m:
                aliased.append(m.group(0))
    findings = []
    for name, aval in cache_leaves:
        ty = mlir_tensor_type(aval)
        if ty in aliased:
            aliased.remove(ty)
        else:
            findings.append(Finding(
                "dropped-donation", SEVERITY_ERROR, path, 0,
                f"{label}: cache leaf {name} ({ty}) is donated but no "
                f"argument of its type aliases an output in the lowered "
                f"executable — XLA dropped the donation, so every "
                f"dispatch copies the full cache"))
    return findings


def cache_leaf_names(cache_spec) -> list:
    """Flatten a cache pytree into ``(dotted_name, aval)`` pairs in leaf
    order, for :func:`donation_findings`."""
    flat, _ = jax.tree_util.tree_flatten_with_path(cache_spec)
    out = []
    for keypath, leaf in flat:
        name = "".join(str(k) for k in keypath) or "<root>"
        out.append((name, leaf))
    return out


# -- jaxpr walking -----------------------------------------------------------

def _iter_jaxprs(jaxpr):
    """Yield a jaxpr and every sub-jaxpr nested in its eqn params."""
    import jax.extend.core as jc
    stack = [jaxpr]
    while stack:
        jx = stack.pop()
        yield jx
        for eqn in jx.eqns:
            for v in eqn.params.values():
                vals = v if isinstance(v, (list, tuple)) else (v,)
                for x in vals:
                    if isinstance(x, jc.ClosedJaxpr):
                        stack.append(x.jaxpr)
                    elif isinstance(x, jc.Jaxpr):
                        stack.append(x)


def jaxpr_findings(jaxpr, label: str, path: str = _MODELS_PATH) -> list:
    """``host-callback`` + ``f64-promotion`` findings over a (recursively
    walked) jaxpr."""
    findings = []
    callback_prims = set()
    f64_prims = set()
    for jx in _iter_jaxprs(jaxpr):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if any(tok in name for tok in _CALLBACK_PRIMS):
                callback_prims.add(name)
            for var in eqn.outvars:
                dtype = getattr(var.aval, "dtype", None)
                if dtype is not None and dtype == jnp.float64:
                    f64_prims.add(name)
    if callback_prims:
        findings.append(Finding(
            "host-callback", SEVERITY_ERROR, path, 0,
            f"{label}: host callback primitive(s) "
            f"{sorted(callback_prims)} in the jaxpr — a callback inside "
            f"the decode scan serializes every chunk on the host"))
    if f64_prims:
        findings.append(Finding(
            "f64-promotion", SEVERITY_ERROR, path, 0,
            f"{label}: float64 values produced by {sorted(f64_prims)} — "
            f"a silent x64 promotion doubles cache bandwidth"))
    return findings


# -- per-family audits -------------------------------------------------------

def _family(arch):
    from ..configs import get_config
    from ..models import get_model
    cfg = get_config(arch, reduced=True)
    return cfg, get_model(cfg)


def _shapes(model, batch: int, seq: int):
    params_shapes = jax.eval_shape(lambda k: model.init(k)[0],
                                   jax.random.PRNGKey(0))
    cache_spec = model.cache_spec(batch, seq)
    n_params = len(jax.tree.leaves(params_shapes))
    n_cache = len(jax.tree.leaves(cache_spec))
    return params_shapes, cache_spec, n_params, n_cache


def audit_decode_fused(arch: str, *, batch: int = BATCH_SHAPES[0],
                       seq: int = AUDIT_SEQ,
                       chunk: int = DECODE_CHUNKS[1]) -> list:
    """Donation + jaxpr findings for one family's ``decode_fused``."""
    _, model = _family(arch)
    params_shapes, cache_spec, _, _ = _shapes(model, batch, seq)
    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((batch,), jnp.int32)
    label = f"{arch}: decode_fused(B={batch}, k={chunk})"
    lowered = model.decode_fused.lower(params_shapes, tok, pos, cache_spec,
                                       chunk)
    findings = donation_findings(lowered.as_text(),
                                 cache_leaf_names(cache_spec), label)
    jaxpr = jax.make_jaxpr(model.decode_fused, static_argnums=4)(
        params_shapes, tok, pos, cache_spec, chunk)
    findings += jaxpr_findings(jaxpr.jaxpr, label)
    return findings


def audit_prefill_chunk(arch: str, *, batch: int = 1, seq: int = AUDIT_SEQ,
                        chunk_t: int = PREFILL_CHUNK_T) -> list:
    """Donation + jaxpr findings for one family's ``prefill_chunk``
    (empty list for families without a chunkable prefill)."""
    _, model = _family(arch)
    if model.prefill_chunk is None:
        return []
    params_shapes, cache_spec, _, _ = _shapes(model, batch, seq)
    tokens = jax.ShapeDtypeStruct((batch, chunk_t), jnp.int32)
    start = jax.ShapeDtypeStruct((batch,), jnp.int32)
    qlen = jax.ShapeDtypeStruct((batch,), jnp.int32)
    label = f"{arch}: prefill_chunk(B={batch}, T={chunk_t})"
    lowered = model.prefill_chunk.lower(params_shapes, tokens, cache_spec,
                                        start, qlen)
    findings = donation_findings(lowered.as_text(),
                                 cache_leaf_names(cache_spec), label)
    jaxpr = jax.make_jaxpr(model.prefill_chunk)(
        params_shapes, tokens, cache_spec, start, qlen)
    findings += jaxpr_findings(jaxpr.jaxpr, label)
    return findings


def audit_retrace(arch: str, *, batch_shapes=BATCH_SHAPES,
                  chunks=DECODE_CHUNKS, seq: int = AUDIT_SEQ) -> list:
    """``retrace-budget``: run the fused decode across every supported
    (batch, chunk) cell on a FRESH model (fresh jit cache) and require the
    compile-cache miss count to equal the cell count."""
    cfg, _ = _family(arch)
    from ..models import get_model
    model = get_model(cfg)                      # fresh executables
    if not hasattr(model.decode_fused, "_cache_size"):
        return []                               # jit cache not introspectable
    params, _ = model.init(jax.random.PRNGKey(0))
    for batch in batch_shapes:
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             model.cache_spec(batch, seq))
        tok = jnp.zeros((batch, 1), jnp.int32)
        pos = jnp.zeros((batch,), jnp.int32)
        for k in chunks:
            # two calls per cell: the second must hit the cache
            _, tok, pos, cache = model.decode_fused(params, tok, pos,
                                                    cache, k)
            _, tok, pos, cache = model.decode_fused(params, tok, pos,
                                                    cache, k)
    budget = len(batch_shapes) * len(chunks)
    misses = model.decode_fused._cache_size()
    if misses > budget:
        return [Finding(
            "retrace-budget", SEVERITY_ERROR, _MODELS_PATH, 0,
            f"{arch}: decode_fused compiled {misses} executables across "
            f"{budget} (chunk x batch) cells — something unstable leaks "
            f"into the trace and every extra compile is a serving stall")]
    return []


def audit_family(arch: str, retrace: bool = True) -> list:
    findings = audit_decode_fused(arch)
    findings += audit_prefill_chunk(arch)
    if retrace:
        findings += audit_retrace(arch)
    return findings


def run_audit(archs=None, retrace: bool = True) -> list:
    """The full layer-2 audit over every family (the CI entry point)."""
    findings = []
    for arch in (archs or FAMILY_ARCHS):
        findings += audit_family(arch, retrace=retrace)
    return findings

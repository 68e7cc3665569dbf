"""Operations and bytes of the serving path's kernels and model steps.

Everything here counts *useful* work only, from shapes and from the live
positions the harness saw at dispatch: live KV rows, live query rows and
kept tokens.  Idle slots, padding rows, block rounding and the surplus
tokens a decode chunk computes past a request's end do not count.  So the
counted work never exceeds what the chip really did, and a roofline share
built on it can pass 100% only if the device time is read too short.

Sizes come from the configuration file's published keys, read by its
architecture file (``bench/archs/<arch>.py``, ``dims``), never from the
program.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_per_s: float
    bytes_per_s: float
    hbm_bytes: float
    source: str


def peaks_for(device_kind: str, path: pathlib.Path = PEAKS) -> Peaks:
    """The chip's published peaks; an unknown device kind is an error."""
    table = json.loads(path.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    p = table[device_kind]
    return Peaks(flops_per_s=float(p["bf16_flops_per_s"]),
                 bytes_per_s=float(p["hbm_bytes_per_s"]),
                 hbm_bytes=float(p["hbm_bytes"]), source=p["source"])


@dataclasses.dataclass(frozen=True)
class Dims:
    """What the counts read of a configuration, from its architecture
    file's ``dims``: the attention sizes the kernels see, the number of
    attention layers, the LM head's sizes, and ``matmul_flops_per_token``,
    the weight-matmul FLOPs of one token through the layers this chip
    holds (attention and the LM head excluded)."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    matmul_flops_per_token: float
    kv_bytes: int = 2          # bf16 cache and kernel inputs
    out_bytes: int = 4         # the kernels return float32


@dataclasses.dataclass(frozen=True)
class Cost:
    flops: float
    bytes: float

    def least_s(self, peaks: Peaks) -> float:
        """The least time the chip could take: the larger of operations
        over peak rate and bytes over HBM bandwidth."""
        return max(self.flops / peaks.flops_per_s,
                   self.bytes / peaks.bytes_per_s)


def ragged_decode_call(d: Dims, rows: list[int]) -> Cost:
    """One ``ragged_decode`` call (one layer, one decode token per slot).

    ``rows[i]`` is how many KV rows live slot ``i`` attends: its position
    plus one.  Per slot: QK^T and PV are ``2 * 2 * Hq * hd * rows`` FLOPs;
    the kernel reads K and V once per KV head, ``2 * Hkv * hd * rows``
    elements, and reads q and writes the output once."""
    n = sum(rows)
    flops = 4 * d.heads * d.head_dim * n
    byt = (2 * d.kv_heads * d.head_dim * d.kv_bytes * n
           + len(rows) * d.heads * d.head_dim * (d.kv_bytes + d.out_bytes))
    return Cost(flops, byt)


def ragged_prefill_call(d: Dims, start: int, qlen: int) -> Cost:
    """One ``ragged_prefill`` call (one layer, one chunk of one prompt):
    ``qlen`` live query rows at positions ``start .. start + qlen - 1``,
    causal.  Query row ``i`` attends ``start + i + 1`` rows; K and V are
    read once per KV head up to the chunk's horizon ``start + qlen``."""
    attended = qlen * start + qlen * (qlen + 1) // 2
    flops = 4 * d.heads * d.head_dim * attended
    byt = (2 * d.kv_heads * d.head_dim * d.kv_bytes * (start + qlen)
           + qlen * d.heads * d.head_dim * (d.kv_bytes + d.out_bytes))
    return Cost(flops, byt)


def lm_head_flops(d: Dims) -> float:
    return 2.0 * d.d_model * d.vocab


def decode_model_flops(d: Dims, rows: list[int]) -> float:
    """Model FLOPs of kept decode tokens; ``rows[i]`` is the KV rows token
    ``i`` attended (its position plus one).  Each token pays every layer's
    matmuls, its attention, and the LM head."""
    attn = 4.0 * d.heads * d.head_dim * sum(rows) * d.layers
    return len(rows) * (d.matmul_flops_per_token + lm_head_flops(d)) + attn


def prefill_model_flops(d: Dims, start: int, qlen: int,
                        last_chunk: bool) -> float:
    """Model FLOPs of one prompt chunk's ``qlen`` live tokens.  The LM head
    counts once, on the chunk that ends the prompt: that is the one row
    whose logits are used."""
    attn = ragged_prefill_call(d, start, qlen).flops * d.layers
    head = lm_head_flops(d) if last_chunk else 0.0
    return qlen * d.matmul_flops_per_token + attn + head

"""Logical-axis sharding rules (t5x/MaxText-style).

Model code annotates tensors with *logical* axis names
(``constrain(x, "batch", "seq", None)``); a thread-local :class:`AxisRules`
maps logical names to mesh axes.  Outside any rules context the annotations
are no-ops, so the same model code runs on a laptop CPU (smoke tests) and on
a 512-chip mesh (dry-run/production) unchanged.

Divisibility fallback: if a tensor dimension is not divisible by the mapped
mesh-axis size, that dimension falls back to replication and the event is
recorded (surfaced in DESIGN.md / dry-run reports) — e.g. qwen2-0.5b's 14
query heads cannot shard over a 16-way model axis, but its flattened
``d_head*heads=896`` projections can.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LogicalAxis = Optional[str]

# default logical -> mesh-axis mapping for the production meshes
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),     # data parallel (pod axis folds into DP)
    "seq": (),                    # sequences unsharded by default
    "seq_mp": ("model",),         # long-context KV / MoE token sharding
    # sequence parallelism for the residual stream: scan carries, norms and
    # logits live seq-sharded; attention/MLP regions gather the sequence and
    # shard heads/ff instead (GSPMD inserts the boundary collectives)
    "seq_sp": ("model",),
    "d_model": (),                # residual activations replicated on model
    "heads": ("model",),          # TP over attention heads
    "kv_heads": ("model",),
    "qkv": ("model",),            # flattened q/k/v projection out-dim
    "ff": ("model",),             # TP over FFN hidden
    "vocab": ("model",),          # TP over vocab (embed + lm head)
    "experts": ("model",),        # expert parallelism
    "fsdp": ("data",),            # ZeRO-3 parameter sharding
    "img": (),
}


@dataclasses.dataclass
class AxisRules:
    mesh: Mesh
    rules: dict[str, tuple[str, ...]]
    fallbacks: list[str] = dataclasses.field(default_factory=list)

    def axes_for(self, name: LogicalAxis, dim: int) -> tuple[str, ...] | None:
        """Mesh axes for one logical axis, with divisibility fallback."""
        if name is None:
            return None
        mesh_axes = tuple(a for a in self.rules.get(name, ())
                          if a in self.mesh.shape)
        if not mesh_axes:
            return None
        total = 1
        for a in mesh_axes:
            total *= self.mesh.shape[a]
        if dim % total != 0:
            # retry with a prefix of the axes (e.g. drop 'model', keep 'data')
            for cut in range(len(mesh_axes) - 1, 0, -1):
                sub = mesh_axes[:cut]
                t = 1
                for a in sub:
                    t *= self.mesh.shape[a]
                if dim % t == 0:
                    self.fallbacks.append(
                        f"{name}: dim {dim} % {total} != 0 -> {sub}")
                    return sub
            self.fallbacks.append(f"{name}: dim {dim} !% {total} -> replicated")
            return None
        return mesh_axes

    def spec(self, names: Sequence[LogicalAxis],
             shape: Sequence[int]) -> P:
        used: set[str] = set()
        parts = []
        for name, dim in zip(names, shape):
            axes = self.axes_for(name, dim)
            if axes and any(a in used for a in axes):
                axes = tuple(a for a in axes if a not in used) or None
                if axes and dim % _size(self.mesh, axes) != 0:
                    axes = None
            if axes:
                used.update(axes)
                parts.append(axes if len(axes) > 1 else axes[0])
            else:
                parts.append(None)
        return P(*parts)


def _size(mesh: Mesh, axes: tuple[str, ...]) -> int:
    t = 1
    for a in axes:
        t *= mesh.shape[a]
    return t


_tls = threading.local()


def set_rules(rules: AxisRules | None) -> None:
    _tls.rules = rules


def current_rules() -> AxisRules | None:
    return getattr(_tls, "rules", None)


class use_rules:
    """``with use_rules(mesh): ...`` activates logical-axis constraints."""

    def __init__(self, mesh: Mesh,
                 overrides: dict[str, tuple[str, ...]] | None = None):
        rules = dict(DEFAULT_RULES)
        if overrides:
            rules.update(overrides)
        self.rules = AxisRules(mesh=mesh, rules=rules)

    def __enter__(self) -> AxisRules:
        self._prev = current_rules()
        set_rules(self.rules)
        return self.rules

    def __exit__(self, *exc) -> None:
        set_rules(self._prev)


def constrain(x: jax.Array, *names: LogicalAxis) -> jax.Array:
    """with_sharding_constraint by logical names; no-op without rules."""
    r = current_rules()
    if r is None:
        return x
    if len(names) != x.ndim:
        raise ValueError(f"{len(names)} names for rank-{x.ndim} tensor")
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(r.mesh, r.spec(names, x.shape)))


def spec_for(names: Sequence[LogicalAxis], shape: Sequence[int]) -> P:
    """PartitionSpec for a param with the active rules (P() if none)."""
    r = current_rules()
    if r is None:
        return P()
    return r.spec(names, shape)


def logical_sharding(mesh: Mesh, names: Sequence[LogicalAxis],
                     shape: Sequence[int],
                     overrides: dict[str, tuple[str, ...]] | None = None
                     ) -> NamedSharding:
    rules = dict(DEFAULT_RULES)
    if overrides:
        rules.update(overrides)
    return NamedSharding(mesh, AxisRules(mesh, rules).spec(names, shape))

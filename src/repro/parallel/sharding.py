"""Logical-axis sharding rules (DP / TP / EP / SP / ZeRO).

Tensors are annotated with *logical* axis names; a rule table maps each to
mesh axes.  The production mesh is ``('data','model')`` single-pod or
``('pod','data','model')`` multi-pod; the rules below keep every sharding
expressible for both by treating "dp" as ``('pod','data')`` when the pod
axis exists.

Logical axes used by the model stack:

  batch      data-parallel batch                   -> (pod,) data
  seq        sequence (SP for long prefill)        -> None (or data for SP)
  vocab      embedding/logit vocabulary            -> model
  heads      attention query heads                 -> model
  kv_heads   KV heads (sharded iff divisible)      -> model | None
  d_ff       MLP hidden                            -> model
  experts    MoE experts (EP iff divisible)        -> model | None
  d_model    residual stream                       -> None (replicated)
  zero       optimizer-state / master-param shard  -> (pod, data, model) flat

``kv_heads``/``experts`` fall back to replication when not divisible by the
model-axis size; the MoE layer then shards ``d_ff_expert`` instead (TP
inside experts), and attention falls back to sharding the head_dim.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class AxisRules:
    data_axes: tuple[str, ...]        # ('data',) or ('pod', 'data')
    model_axis: str = "model"
    # Megatron-style sequence parallelism: the inter-layer residual stream
    # shards its sequence dim over the model axis (boundary activations
    # /tp; GSPMD inserts the AG/RS pairs around attention/MLP).
    seq_axis: Optional[str] = None

    @property
    def dp(self):
        return self.data_axes if len(self.data_axes) > 1 else self.data_axes[0]


@dataclass
class ShardingCtx:
    mesh: Mesh
    rules: AxisRules

    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.rules.model_axis]

    @property
    def data_size(self) -> int:
        n = 1
        for a in self.rules.data_axes:
            n *= self.mesh.shape[a]
        return n

    def spec(self, *logical_axes: Optional[str], **kw) -> P:
        return logical(self.rules, *logical_axes, **kw)

    def shard(self, *logical_axes: Optional[str]) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(*logical_axes))

    def divisible(self, n: int) -> bool:
        return n % self.model_size == 0


def logical(rules: AxisRules, *axes: Optional[str], divisible=None) -> P:
    """Map logical axis names to a PartitionSpec under ``rules``."""
    out: list[Any] = []
    for a in axes:
        if a is None or a in ("d_model", "state"):
            out.append(None)
        elif a == "seq":
            out.append(rules.seq_axis)
        elif a == "batch":
            out.append(rules.dp)
        elif a in ("vocab", "heads", "d_ff", "experts", "kv_heads", "head_dim"):
            out.append(rules.model_axis)
        elif a == "zero":
            out.append(tuple(rules.data_axes) + (rules.model_axis,))
        else:
            raise ValueError(f"unknown logical axis {a!r}")
    return P(*out)


def make_ctx(mesh: Mesh, sequence_parallel: bool = False) -> ShardingCtx:
    names = mesh.axis_names
    data_axes = tuple(a for a in names if a in ("pod", "data"))
    return ShardingCtx(mesh=mesh, rules=AxisRules(
        data_axes=data_axes,
        seq_axis="model" if sequence_parallel else None))


def with_sharding(ctx: Optional[ShardingCtx], x, *axes: Optional[str]):
    """``lax.with_sharding_constraint`` if a mesh is active, else identity."""
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(x, ctx.shard(*axes))

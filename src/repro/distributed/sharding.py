"""Logical-axis sharding rules and mesh utilities.

Logical axes used across the model code:
  batch   -> ('pod', 'data')  (or ('data',) on a single-pod mesh)
  fsdp    -> 'data'           (params ZeRO-3 sharded *within* a pod; replicated
                               across pods so the only cross-pod traffic is the
                               gradient all-reduce)
  tp      -> 'model'          (tensor parallel / expert parallel / seq-parallel)
  seq     -> 'model'          (decode-time KV sequence sharding)
  (None)  -> replicated

A `ShardingCtx` bundles the mesh with resolver helpers so model code never
hard-codes mesh axis names (the same code runs on a 1x1 test mesh, the 16x16
single-pod mesh and the 2x16x16 multi-pod mesh).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def logical_to_mesh(mesh: Mesh) -> dict[str, Any]:
    axes = mesh.axis_names
    has_pod = "pod" in axes
    return {
        "batch": ("pod", "data") if has_pod else ("data",),
        "fsdp": "data",
        "tp": "model",
        "seq": "model",
        "expert": "model",
        None: None,
    }


@dataclass(frozen=True)
class ShardingCtx:
    mesh: Mesh

    @cached_property
    def rules(self) -> dict[str, Any]:
        return logical_to_mesh(self.mesh)

    @cached_property
    def axis_sizes(self) -> dict[str, int]:
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    @property
    def n_data(self) -> int:
        n = self.axis_sizes.get("data", 1)
        n *= self.axis_sizes.get("pod", 1)
        return n

    @property
    def n_model(self) -> int:
        return self.axis_sizes.get("model", 1)

    @property
    def batch_axes(self):
        return self.rules["batch"]

    def spec(self, *logical: str | None) -> P:
        """Translate logical axis names into a PartitionSpec."""
        return P(*(self.rules.get(l, None) for l in logical))

    def sharding(self, *logical: str | None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(*logical))

    def constrain(self, x, *logical: str | None):
        """with_sharding_constraint against logical axes (no-op off-mesh)."""
        return jax.lax.with_sharding_constraint(x, self.sharding(*logical))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())


def make_test_mesh(data: int = 1, model: int = 1, pod: int | None = None) -> Mesh:
    """Tiny mesh over available devices (CPU tests use 1x1)."""
    devs = np.array(jax.devices())
    if pod is None:
        n = data * model
        return Mesh(devs[:n].reshape(data, model), ("data", "model"))
    n = pod * data * model
    return Mesh(devs[:n].reshape(pod, data, model), ("pod", "data", "model"))


def tree_shardings(ctx: ShardingCtx, spec_tree):
    """Map a pytree of PartitionSpecs to NamedShardings."""
    return jax.tree.map(
        lambda s: NamedSharding(ctx.mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def chain_carry_shardings(ctx: ShardingCtx, carry: dict, K: int) -> dict:
    """Mesh shardings for a fused-sampler scan carry (`uq.fused`): leaves
    with a leading chain axis of length `K` shard over the logical batch
    axes — the same discipline the evaluate path applies to its [N, d]
    waves — while scalars (step size, step counter) and the PRNG key
    replicate. Keyed by the carry dict's own structure so RWM ({key, xs,
    lps, acc}) and MALA ({... gs, eps, i}) both resolve without a
    per-sampler spec table."""
    batch = ctx.sharding("batch")
    rep = ctx.replicated()
    return {
        k: batch if (hasattr(v, "ndim") and v.ndim >= 1 and v.shape[0] == K)
        else rep
        for k, v in carry.items()
    }


def sanitize_spec(spec: P, shape: Sequence[int], ctx: ShardingCtx) -> P:
    """Drop mesh axes that do not divide the corresponding dimension
    (e.g. kv_heads=8 cannot shard over model=16 -> replicate)."""
    out = []
    for i, ax in enumerate(spec):
        if ax is None or i >= len(shape):
            out.append(ax)
            continue
        names = ax if isinstance(ax, tuple) else (ax,)
        prod = 1
        for name in names:
            prod *= ctx.axis_sizes.get(name, 1)
        out.append(ax if shape[i] % prod == 0 else None)
    return P(*out)


def sanitized_shardings(ctx: ShardingCtx, abstract_tree, spec_tree):
    """NamedShardings with per-leaf divisibility sanitization."""

    def f(a, s):
        return NamedSharding(ctx.mesh, sanitize_spec(s, a.shape, ctx))

    return jax.tree.map(
        f, abstract_tree, spec_tree,
    )


def shard_size_bytes(shape: Sequence[int], dtype, spec: P, ctx: ShardingCtx) -> int:
    """Per-device bytes of an array with the given spec (for napkin math)."""
    size = np.dtype(dtype).itemsize
    for i, dim in enumerate(shape):
        size *= dim
    denom = 1
    for i, ax in enumerate(spec):
        if ax is None:
            continue
        names = ax if isinstance(ax, tuple) else (ax,)
        for name in names:
            denom *= ctx.axis_sizes.get(name, 1)
    return int(size // max(denom, 1))

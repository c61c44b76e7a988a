"""Mesh-aware sharding resolution.

Parameters carry *logical* axis names ("model", "fsdp", "expert", ...).
This module resolves them against a concrete mesh with divisibility checks:
an axis is only applied when the dimension divides the mesh axis size,
otherwise the dim falls back to replication (best-effort sharding). This is
what lets one config system serve a (16,16) single-pod mesh, a (2,16,16)
multi-pod mesh, and the 1-device CPU test mesh without per-arch edits.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.nn.param import ParamSpec

# Logical axis -> mesh axis-name tuple. "dp" covers pod+data (pure DP);
# "fsdp" shards parameters/optimizer state over the data axis (ZeRO-3 style);
# "expert"/"model" are tensor/expert parallel over the model axis.
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "dp": ("pod", "data"),
    "data": ("data",),
    "fsdp": ("data",),
    "model": ("model",),
    "expert": ("model",),
    "seq": ("pod", "data"),  # long-context KV/sequence sharding (batch=1)
    # decode KV caches: batch shards over dp, sequence over the model axis
    # (kv heads < TP width, so the seq dim is the shardable one; attention
    # over the sharded cache becomes a flash-decoding-style distributed
    # softmax, with the partial max/sum reductions inserted by GSPMD).
    "kv_seq": ("model",),
}


# FSDP-only plan (no tensor parallelism): batch shards over every mesh
# axis, parameters ZeRO-3-shard over (data, model). The right plan for
# ≤13B dense models at 4k context — Megatron-TP's per-layer activation
# all-reduces dominate their collective term (§Perf iteration 4).
FSDP_ONLY_RULES: dict[str, tuple[str, ...]] = {
    "dp": ("pod", "data", "model"),
    "data": ("data",),
    "fsdp": ("data", "model"),
    "model": (),
    "expert": (),
    "seq": ("pod", "data"),
    "kv_seq": (),
}


@dataclasses.dataclass(frozen=True)
class ShardingConfig:
    rules: dict[str, tuple[str, ...]] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_RULES)
    )
    # ZeRO-3/FSDP: additionally shard params over the data axis when the
    # logical spec asks for "fsdp".
    enable_fsdp: bool = True

    @staticmethod
    def fsdp_only() -> "ShardingConfig":
        return ShardingConfig(rules=dict(FSDP_ONLY_RULES))

    @staticmethod
    def fsdp_hybrid() -> "ShardingConfig":
        """No-TP plan with batch over data only (leaves room for grad
        accumulation): params ZeRO-3 over all chips, batch 16-way + mu."""
        rules = dict(FSDP_ONLY_RULES)
        rules["dp"] = ("pod", "data")
        return ShardingConfig(rules=rules)

    def mesh_axes(self, logical: Any) -> tuple[str, ...]:
        if logical is None:
            return ()
        if isinstance(logical, (tuple, list)):
            out: list[str] = []
            for item in logical:
                out.extend(self.mesh_axes(item))
            return tuple(out)
        if logical == "fsdp" and not self.enable_fsdp:
            return ()
        return self.rules.get(logical, ())


def axis_size(mesh: Mesh, names: tuple[str, ...]) -> int:
    size = 1
    for n in names:
        size *= mesh.shape.get(n, 1)
    return size


def resolve_pspec(
    mesh: Mesh, spec_axes: tuple[Any, ...], shape: tuple[int, ...],
    cfg: ShardingConfig | None = None,
) -> P:
    """Resolve logical axes to a PartitionSpec, dropping non-divisible axes."""
    cfg = cfg or ShardingConfig()
    entries: list[Any] = []
    used: set[str] = set()
    if not spec_axes:
        return P()
    for dim, logical in zip(shape, spec_axes):
        names = [
            n for n in cfg.mesh_axes(logical)
            if n in mesh.shape and n not in used
        ]
        # keep the largest prefix of axis names whose product divides the dim
        kept: list[str] = []
        prod = 1
        for n in names:
            if dim % (prod * mesh.shape[n]) == 0:
                kept.append(n)
                prod *= mesh.shape[n]
        used.update(kept)
        if not kept:
            entries.append(None)
        elif len(kept) == 1:
            entries.append(kept[0])
        else:
            entries.append(tuple(kept))
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def param_pspec(mesh: Mesh, spec: ParamSpec, cfg: ShardingConfig | None = None) -> P:
    return resolve_pspec(mesh, spec.axes, spec.shape, cfg)


def named(mesh: Mesh, *entries) -> NamedSharding:
    return NamedSharding(mesh, P(*entries))


class ShardCtx:
    """Carries the mesh + rules through model apply functions.

    ``constrain(x, *logical_axes)`` applies a with_sharding_constraint with
    the same best-effort divisibility resolution used for params. On a
    1-device test mesh every constraint resolves to replication, so the same
    model code runs in unit tests and in the 512-chip dry-run.
    """

    def __init__(self, mesh: Mesh | None, cfg: ShardingConfig | None = None):
        self.mesh = mesh
        self.cfg = cfg or ShardingConfig()

    def pspec(self, logical_axes: tuple[Any, ...], shape: tuple[int, ...]) -> P:
        if self.mesh is None:
            return P()
        return resolve_pspec(self.mesh, logical_axes, shape, self.cfg)

    def constrain(self, x: jax.Array, *logical_axes: Any) -> jax.Array:
        if self.mesh is None:
            return x
        ps = self.pspec(tuple(logical_axes), x.shape)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, ps)
        )

    def dp_size(self) -> int:
        if self.mesh is None:
            return 1
        return axis_size(self.mesh, self.cfg.mesh_axes("dp"))

    def tp_size(self) -> int:
        if self.mesh is None:
            return 1
        return axis_size(self.mesh, self.cfg.mesh_axes("model"))


def make_test_mesh() -> Mesh:
    """1-device mesh with the production axis names (for tests)."""
    dev = jax.devices()[:1]
    import numpy as np

    return Mesh(np.array(dev).reshape(1, 1), ("data", "model"))

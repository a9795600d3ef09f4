"""Trace-time sharding context.

Model code is mesh-agnostic: it calls ``shard(x, *dims)`` to attach sharding
constraints and consults ``get_ctx()`` for mesh-dependent code paths (e.g.
flash-decoding via shard_map). With no active mesh everything is a no-op, so
the same model runs single-device on CPU for smoke tests.

``dims`` vocabulary (resolved against the active mesh):
    "dp"    -> the data-parallel axes ("data",) or ("pod", "data")
    "tp"    -> the tensor-parallel axis "model"
    None    -> replicated
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class ShardCtx:
    mesh: Mesh | None = None
    dp_axes: tuple[str, ...] = ("data",)
    tp_axis: str | None = "model"
    # batch sharding disabled when global batch < |dp| (e.g. long_500k B=1)
    shard_batch: bool = True
    # sequence-parallel residual stream (shard seq over tp between blocks)
    seq_parallel: bool = True
    # FSDP "mcast" mode: explicit per-layer param gather using the paper's
    # collectives (sharding/fsdp.make_param_gather); None = XLA-inserted.
    gather_params: object = None


_CTX: list[ShardCtx] = [ShardCtx(mesh=None)]


def get_ctx() -> ShardCtx:
    return _CTX[-1]


@contextlib.contextmanager
def use_ctx(ctx: ShardCtx):
    _CTX.append(ctx)
    try:
        yield ctx
    finally:
        _CTX.pop()


def _resolve(dim) -> object:
    c = get_ctx()
    if dim is None:
        return None
    if dim == "dp":
        return c.dp_axes if c.shard_batch else None
    if dim == "tp":
        return c.tp_axis
    if dim == "sp":  # sequence dim sharded over tp when seq_parallel
        return c.tp_axis if c.seq_parallel else None
    raise ValueError(dim)


def maybe_gather_params(tree):
    """Hook called inside layer-scan bodies: explicit FSDP gather (paper
    schedule, under the named scope ``fsdp_gather``) when active, identity
    otherwise (XLA auto-gather)."""
    c = get_ctx()
    if c.gather_params is None:
        return tree
    with jax.named_scope("fsdp_gather"):
        return c.gather_params(tree)


def shard(x: jax.Array, *dims) -> jax.Array:
    """with_sharding_constraint if a mesh is active; no-op otherwise."""
    c = get_ctx()
    if c.mesh is None:
        return x
    spec = P(*(_resolve(d) for d in dims))
    return jax.lax.with_sharding_constraint(x, NamedSharding(c.mesh, spec))


def spec(*dims) -> P:
    return P(*(_resolve(d) for d in dims))


def mesh_axis_size(axis: str) -> int:
    c = get_ctx()
    if c.mesh is None:
        return 1
    if axis == "dp":
        n = 1
        for a in c.dp_axes:
            n *= c.mesh.shape[a]
        return n
    return c.mesh.shape[c.tp_axis] if c.tp_axis else 1

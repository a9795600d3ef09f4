"""Collective (allgather-fused) matmul.

The FSDP hot loop is allgather(weights-or-activations) -> matmul. The paper's
DPA thesis — hide data-movement latency behind parallel workers — maps to the
MXU as: consume each ring shard on the MXU while the next shard is in flight.

Two layers:
  - ``matmul_pallas``: the MXU-tiled matmul kernel (pl.pallas_call with
    explicit (bm, bk, bn) BlockSpec VMEM tiling and an f32 VMEM accumulator).
    MXU-aligned tile defaults (128x128x128).
  - ``allgather_matmul_local``: runs inside shard_map over a ring axis;
    at step s it matmuls the shard received at step s-1 while ppermuting the
    next shard — compute/communication overlap at the schedule level (on TPU
    the async collective-permute makes this the classic "collective matmul").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_pallas(x: jax.Array, w: jax.Array, *, bm: int = 128, bk: int = 128,
                  bn: int = 128, interpret: bool | None = None) -> jax.Array:
    """(m, k) @ (k, n) with MXU-aligned VMEM tiles and f32 accumulation."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)
    nk = k // bk
    return pl.pallas_call(
        functools.partial(_matmul_kernel, nk=nk),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w)


def allgather_matmul_local(x_shard: jax.Array, w: jax.Array, axis: str, *,
                           use_pallas: bool = True, bm: int = 128,
                           bk: int = 128, bn: int = 128) -> jax.Array:
    """Inside shard_map: computes allgather(x, axis) @ w with the matmul of
    shard s overlapped with the transfer of shard s+1.

    x_shard: (m_loc, k) local shard; returns (P*m_loc, n) (replicated value).
    """
    p = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    mm = (
        functools.partial(matmul_pallas, bm=bm, bk=bk, bn=bn)
        if use_pallas
        else lambda a, b: jnp.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype)
    )
    n = w.shape[1]
    out = jnp.zeros((p, x_shard.shape[0], n), x_shard.dtype)

    def step(carry, s):
        out, cur = carry
        nxt = lax.ppermute(cur, axis, [(i, (i + 1) % p) for i in range(p)])
        y = mm(cur, w)                       # compute overlaps the permute
        out = out.at[(idx - s) % p].set(y)
        return (out, nxt), None

    (out, _), _ = lax.scan(step, (out, x_shard), jnp.arange(p))
    return out.reshape(p * x_shard.shape[0], n)


def make_allgather_matmul(mesh, axis: str, **kw):
    """Jitted global version: x (M, K) sharded on dim0 over ``axis``; w
    replicated. Returns allgather(x) @ w, replicated."""
    from jax.sharding import PartitionSpec as P

    local = functools.partial(allgather_matmul_local, axis=axis, **kw)
    sm = jax.shard_map(
        local, mesh=mesh, in_specs=(P(axis, None), P(None, None)),
        out_specs=P(None, None), check_vma=False,
    )
    return jax.jit(sm)

"""The paper's collective algorithms as jax-native shard_map kernels.

Mapping (DESIGN.md §2): hardware multicast does not exist on a TPU torus, so
"bandwidth-optimal" is restated per-link: every byte crosses every ring link
at most once per direction. The pieces:

  pipelined_broadcast   constant-time Broadcast (§III): chain-pipelined chunks;
                        T ~ (C + P - 2)/C * N/B -> N/B, independent of P.
  bcast_allgather       Allgather as composition of Broadcasts with M parallel
                        chains (§IV-A / Appendix A). M=P degenerates to the
                        fully-pipelined ring; M<P keeps the chain-sequential
                        activation semantics (used on the switched pod axis).
  ring_allgather        the degenerate M=P schedule (baseline).
  bidi_ring_allgather   Fig. 1's "two parallel multicast trees" analogue: the
                        buffer is split across both ring directions (M=2
                        direction-chains), halving completion time on
                        full-duplex ICI links.
  ring_reduce_scatter / bidi_ring_reduce_scatter
  concurrent_ag_rs      Insight 2: AG streams one direction while RS streams
                        the opposite direction -> no shared link bottleneck
                        for interleaved FSDP collectives.

All functions with the ``_local`` suffix run *inside* shard_map (per-device
shards + lax.ppermute); ``make_*`` wrappers build jitted global-array versions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def _perm(p: int, direction: int):
    return [(i, (i + direction) % p) for i in range(p)]


# ----------------------------------------------------------------- broadcast


def pipelined_broadcast_local(x: jax.Array, axis: str, *, root: int = 0,
                              n_chunks: int = 8) -> jax.Array:
    """Chain-pipelined broadcast of ``x`` (defined on root; other devices pass
    anything of the same shape). Returns the full buffer everywhere.

    Per-link bytes: N * (1 + (P-2)/C); schedule time constant in P for C >> P.
    """
    p = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    dist = (idx - root) % p
    n = x.shape[0]
    assert n % n_chunks == 0, (n, n_chunks)
    xc = x.reshape(n_chunks, n // n_chunks)
    steps = n_chunks + p - 2

    def step(carry, t):
        out, cur = carry
        send = jnp.where(dist == 0, xc[jnp.clip(t, 0, n_chunks - 1)], cur)
        recv = lax.ppermute(send, axis, _perm(p, +1))
        c_idx = t - (dist - 1)
        write = (dist > 0) & (c_idx >= 0) & (c_idx < n_chunks)
        ci = jnp.clip(c_idx, 0, n_chunks - 1)
        out = out.at[ci].set(jnp.where(write, recv, out[ci]))
        return (out, recv), None

    out0 = jnp.where(dist == 0, xc, jnp.zeros_like(xc))
    (out, _), _ = lax.scan(step, (out0, jnp.zeros_like(xc[0])), jnp.arange(steps))
    return out.reshape(n)


# ----------------------------------------------------------------- allgather


def ring_allgather_local(x: jax.Array, axis: str, *, direction: int = +1) -> jax.Array:
    """Unidirectional ring allgather: P-1 forwarding steps. x: (n, ...)
    shard. Returns (P*n, ...) in rank order."""
    p = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    out = jnp.zeros((p,) + x.shape, x.dtype).at[idx].set(x)

    def step(carry, s):
        out, cur = carry
        recv = lax.ppermute(cur, axis, _perm(p, direction))
        src = (idx - direction * (s + 1)) % p
        out = out.at[src].set(recv)
        return (out, recv), None

    (out, _), _ = lax.scan(step, (out, x), jnp.arange(p - 1))
    return out.reshape((p * x.shape[0],) + x.shape[1:])


def bidi_ring_allgather_local(x: jax.Array, axis: str) -> jax.Array:
    """Bidirectional ring allgather (Fig. 1's two trees): each half of the
    shard's leading dim travels one direction; both directions are
    concurrently active, so the completion time halves on full-duplex links.
    x: (n, ...) shard. Returns (P*n, ...) in rank order."""
    p = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    n = x.shape[0]
    half = n // 2
    xa, xb = x[:half], x[half:]
    out_a = jnp.zeros((p,) + xa.shape, x.dtype).at[idx].set(xa)
    out_b = jnp.zeros((p,) + xb.shape, x.dtype).at[idx].set(xb)

    def step(carry, s):
        oa, ob, ca, cb = carry
        ra = lax.ppermute(ca, axis, _perm(p, +1))
        rb = lax.ppermute(cb, axis, _perm(p, -1))
        oa = oa.at[(idx - (s + 1)) % p].set(ra)
        ob = ob.at[(idx + (s + 1)) % p].set(rb)
        return (oa, ob, ra, rb), None

    (out_a, out_b, _, _), _ = lax.scan(
        step, (out_a, out_b, xa, xb), jnp.arange(p - 1)
    )
    return jnp.concatenate([out_a, out_b], axis=1).reshape((p * n,) + x.shape[1:])


def bcast_allgather_local(x: jax.Array, axis: str, *, n_chains: int) -> jax.Array:
    """Allgather as a composition of Broadcasts with M = n_chains parallel
    chains (Appendix A). Rounds are sequential (chain activation semantics);
    within a round the M chain roots broadcast concurrently around the ring.

    M = P is the fully-parallel degenerate case == ring allgather.
    """
    p = lax.axis_size(axis)
    assert p % n_chains == 0, (p, n_chains)
    rounds = p // n_chains
    idx = lax.axis_index(axis)
    out = jnp.zeros((p,) + x.shape, x.dtype).at[idx].set(x)

    for r in range(rounds):
        # Appendix A: G^r = {r, R + r, 2R + r, ...}; roots inject their shard
        is_root = (idx % rounds) == r
        cur = jnp.where(is_root, x, jnp.zeros_like(x))

        def step(carry, s):
            out, cur = carry
            recv = lax.ppermute(cur, axis, _perm(p, +1))
            src = (idx - (s + 1)) % p
            active = (src % rounds) == r
            out = out.at[src].set(jnp.where(active, recv, out[src]))
            return (out, recv), None

        (out, _), _ = lax.scan(step, (out, cur), jnp.arange(p - 1))
    return out.reshape((p * x.shape[0],) + x.shape[1:])


# ------------------------------------------------------------ reduce-scatter


def ring_reduce_scatter_local(x: jax.Array, axis: str, *, direction: int = +1) -> jax.Array:
    """Ring reduce-scatter. x: (P*n,) full per-device contribution; returns
    (n,) — the sum over devices of shard idx."""
    p = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    n = x.shape[0] // p
    xv = x.reshape((p, n) + x.shape[1:])
    cur = xv[(idx - direction) % p]

    def step(cur, t):
        recv = lax.ppermute(cur, axis, _perm(p, direction))
        cur = recv + xv[(idx - direction * (t + 2)) % p]
        return cur, None

    cur, _ = lax.scan(step, cur, jnp.arange(p - 1))
    return cur


def bidi_ring_reduce_scatter_local(x: jax.Array, axis: str) -> jax.Array:
    """Both directions carry half the shard each."""
    p = lax.axis_size(axis)
    n = x.shape[0] // p
    half = n // 2
    xv = x.reshape(p, n)
    xa = xv[:, :half].reshape(p * half)
    xb = xv[:, half:].reshape(p * (n - half))
    ra = ring_reduce_scatter_local(xa, axis, direction=+1)
    rb = ring_reduce_scatter_local(xb, axis, direction=-1)
    return jnp.concatenate([ra, rb], axis=0)


# ------------------------------------------- Insight 2: direction-split AG+RS


def concurrent_ag_rs_local(ag_shard: jax.Array, rs_full: jax.Array, axis: str):
    """Concurrently progress an Allgather (clockwise) and a Reduce-Scatter
    (counter-clockwise). The two ppermute streams use opposite ICI directions,
    so — like the paper's {AG_mc, RS_inc} pairing — they do not share a link
    bottleneck. Returns (ag_full (P*n,), rs_shard (m,))."""
    p = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    n = ag_shard.shape[0]
    m = rs_full.shape[0] // p
    rsv = rs_full.reshape(p, m)

    ag_out = jnp.zeros((p, n), ag_shard.dtype).at[idx].set(ag_shard)
    rs_cur = rsv[(idx + 1) % p]

    def step(carry, s):
        ag_out, ag_cur, rs_cur = carry
        ag_recv = lax.ppermute(ag_cur, axis, _perm(p, +1))
        rs_recv = lax.ppermute(rs_cur, axis, _perm(p, -1))
        ag_out = ag_out.at[(idx - (s + 1)) % p].set(ag_recv)
        rs_cur = rs_recv + rsv[(idx + s + 2) % p]
        return (ag_out, ag_recv, rs_cur), None

    (ag_out, _, rs_cur), _ = lax.scan(
        step, (ag_out, ag_shard, rs_cur), jnp.arange(p - 1)
    )
    return ag_out.reshape(p * n), rs_cur


# --------------------------------------------------------------- jit wrappers


def _flat_spec(axes):
    return P(axes)


def make_allgather(mesh: Mesh, axis: str, mode: str = "bidi", *, n_chains: int | None = None):
    """Global-array allgather over ``axis``: (P*n,) sharded -> (P*n,) replicated
    on that axis. mode: ring | bidi | bcast | xla."""
    if mode == "xla":
        def fn(x):
            return lax.with_sharding_constraint(x, NamedSharding(mesh, P()))
        return jax.jit(fn)

    local = {
        "ring": functools.partial(ring_allgather_local, axis=axis),
        "bidi": functools.partial(bidi_ring_allgather_local, axis=axis),
        "bcast": functools.partial(
            bcast_allgather_local, axis=axis,
            n_chains=n_chains or mesh.shape[axis],
        ),
    }[mode]
    sm = jax.shard_map(
        local, mesh=mesh, in_specs=P(axis), out_specs=P(), check_vma=False
    )
    return jax.jit(sm)


def make_reduce_scatter(mesh: Mesh, axis: str, mode: str = "bidi"):
    """(P*n,) per-device full contributions (unsharded dim) -> (P*n,) sharded sum."""
    local = {
        "ring": functools.partial(ring_reduce_scatter_local, axis=axis),
        "bidi": functools.partial(bidi_ring_reduce_scatter_local, axis=axis),
    }[mode]
    sm = jax.shard_map(
        local, mesh=mesh, in_specs=P(), out_specs=P(axis), check_vma=False
    )
    return jax.jit(sm)


def make_broadcast(mesh: Mesh, axis: str, *, root: int = 0, n_chunks: int = 8):
    """Global (P*n,) sharded input -> (n,) output = root's shard, replicated."""
    local = functools.partial(
        pipelined_broadcast_local, axis=axis, root=root, n_chunks=n_chunks
    )
    sm = jax.shard_map(local, mesh=mesh, in_specs=P(axis), out_specs=P(), check_vma=False)
    return jax.jit(sm)

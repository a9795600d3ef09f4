"""FSDP (ZeRO-3) parameter gathering with the paper's collectives.

Two modes (CollectiveConfig.fsdp_mode):

  "xla"   — parameters stay sharded (specs.py); XLA/GSPMD inserts all-gather
            before use and reduce-scatter for grads. Baseline.
  "mcast" — the paper's schedule, explicit: inside the layer scan each
            dp-sharded weight is gathered by a shard_map ppermute kernel
            (bidirectional ring = Fig. 1's two trees, or the general M-chain
            broadcast composition). The AD transpose of the gather is the
            matching ring reduce-scatter on the opposite direction, i.e. the
            Insight-2 direction split of grad traffic vs weight traffic
            falls out of the schedule for free.

On the multi-pod mesh the gather is hierarchical: ICI ring over "data" inside
the pod, then the M-chain broadcast composition over the switched "pod" axis —
the axis where the paper's multicast protocol literally applies (DESIGN.md §2).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import CollectiveConfig, MeshConfig
from repro.core import collectives as C
from repro.sharding.specs import _leaf_spec, dp_axes


def _remove_axis(entry, axis):
    if entry is None:
        return None
    if isinstance(entry, str):
        return None if entry == axis else entry
    rest = tuple(a for a in entry if a != axis)
    return rest if len(rest) > 1 else (rest[0] if rest else None)


def _ag_local(x, axis, mode, n_chains):
    """Gather the leading dim of ``x`` over ``axis``. The shard keeps its
    shape: flattening it to 1-D costs the TPU compiler about a minute per
    large weight, to lay the gathered result out in 2-D again."""
    if mode == "bidi" and x.shape[0] % 2 == 0:
        return C.bidi_ring_allgather_local(x, axis)
    if mode == "bcast":
        return C.bcast_allgather_local(x, axis, n_chains=n_chains)
    return C.ring_allgather_local(x, axis)


def gather_dim(x: jax.Array, spec: P, axis: str, dim: int, mesh: Mesh,
               mode: str, n_chains: int) -> tuple[jax.Array, P]:
    """Explicitly allgather mesh axis ``axis`` out of dim ``dim`` of ``x``."""
    out_entries = list(spec) + [None] * (x.ndim - len(spec))
    out_entries[dim] = _remove_axis(out_entries[dim], axis)
    out_spec = P(*out_entries)
    p = mesh.shape[axis]

    def local(xl):
        full = _ag_local(jnp.moveaxis(xl, dim, 0), axis, mode, min(n_chains, p))
        return jnp.moveaxis(full, 0, dim)

    y = jax.shard_map(
        local, mesh=mesh, in_specs=spec, out_specs=out_spec, check_vma=False
    )(x)
    return y, out_spec


def gather_leaf(x: jax.Array, spec: P, mesh: Mesh, dp: tuple[str, ...],
                mode: str, n_chains: int) -> jax.Array:
    """Gather every dp-axis out of a weight slice; tp axes stay sharded.
    Hierarchical: minor (intra-pod "data") ring first, then the "pod" axis
    via the M-chain broadcast composition."""
    entries = list(spec)
    for dim, entry in enumerate(entries):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for a in [ax for ax in reversed(dp) if ax in axes]:
            pod_axis = a == "pod"
            x, spec = gather_dim(
                x, spec, a, dim,
                mesh,
                # the switched pod axis always uses the paper's M-chain
                # broadcast-composed schedule; intra-pod uses `mode`
                "bcast" if pod_axis else mode,
                n_chains,
            )
            entries = list(spec) + [None] * (x.ndim - len(spec))
    return x


def make_param_gather(mesh: Mesh, mesh_cfg: MeshConfig,
                      coll: CollectiveConfig) -> Callable | None:
    """The ShardCtx.gather_params hook: tree-maps the explicit gather over a
    one-layer parameter slice (specs re-derived from leaf names/shapes)."""
    if coll.fsdp_mode == "xla":
        return None
    dp = dp_axes(mesh_cfg)
    mode = {"mcast": "bidi", "mcast_ring": "ring", "mcast_bcast": "bcast"}.get(
        coll.fsdp_mode, "bidi"
    )

    def gather(tree):
        def one(path, leaf):
            spec = _leaf_spec(path, leaf, mesh, dp)
            if all(e is None for e in spec):
                return leaf
            return gather_leaf(leaf, spec, mesh, dp, mode, coll.n_chains)

        return jax.tree_util.tree_map_with_path(one, tree)

    return gather


# ----------------------------------------------------- flat-bucket utilities


def flatten_bucket(tree, pad_to: int = 1):
    """Flatten a pytree into one contiguous padded fp bucket (the paper's
    collectives operate on flat byte buffers; used by benchmarks/examples)."""
    leaves, treedef = jax.tree.flatten(tree)
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
    n = flat.shape[0]
    padded = -(-n // pad_to) * pad_to
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))

    shapes = [(l.shape, l.dtype) for l in leaves]

    def unflatten(buf):
        out, off = [], 0
        for shape, dtype in shapes:
            k = 1
            for s in shape:
                k *= s
            out.append(buf[off : off + k].reshape(shape).astype(dtype))
            off += k
        return jax.tree.unflatten(treedef, out)

    return flat, unflatten

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

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import CollectiveConfig, MeshConfig
from repro.core import collectives as C
from repro.sharding.specs import _leaf_spec, dp_axes


def _ag_local(x, axis, mode, n_chains):
    """Gather the leading dim of ``x`` over ``axis``. The shard keeps its
    shape: flattening it to 1-D costs the TPU compiler about a minute per
    large weight, to lay the gathered result out in 2-D again."""
    if mode == "bidi" and x.shape[0] % 2 == 0:
        return C.bidi_ring_allgather_local(x, axis)
    if mode == "bcast":
        return C.bcast_allgather_local(x, axis, n_chains=n_chains)
    return C.ring_allgather_local(x, axis)


def gather_leaf_local(x: jax.Array, spec: P, dp: tuple[str, ...], mode: str,
                      n_chains: int) -> jax.Array:
    """Gather every dp axis out of ``x``, the local shard of a weight that
    ``spec`` lays out, inside a region manual over the dp axes; tp axes stay
    sharded. Hierarchical: minor (intra-pod "data") ring first, then the
    "pod" axis via the M-chain broadcast composition. Its AD transpose is
    the matching ring reduce-scatter of the local cotangent."""
    for dim, entry in enumerate(spec):
        axes = entry if isinstance(entry, tuple) else (entry,)
        for a in [ax for ax in reversed(dp) if ax in axes]:
            # the switched pod axis always uses the paper's M-chain
            # broadcast-composed schedule; intra-pod uses `mode`
            full = _ag_local(jnp.moveaxis(x, dim, 0), a, "bcast" if a == "pod" else mode,
                             min(n_chains, lax.axis_size(a)))
            x = jnp.moveaxis(full, 0, dim)
    return x


def _keep_axes(spec: P, keep) -> P:
    """``spec`` with every mesh axis outside ``keep`` removed."""
    out = []
    for entry in spec:
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept = tuple(a for a in axes if a is not None and a in keep)
        out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
    return P(*out)


@dataclass(frozen=True)
class ParamGather:
    """The ShardCtx.gather_params hook: the explicit gather of a one-layer
    parameter slice, each leaf's layout re-derived from its name and shape.

    Called on the slice, it is one ``shard_map`` per sharded leaf whose output
    is replicated over dp. ``local`` is the same gather inside a region that is
    already manual over the dp axes (``models/transformer.py``), where each
    weight's cotangent stays a per-device partial until the ring reduce-
    scatters it."""

    mesh: Mesh
    dp: tuple[str, ...]
    mode: str
    n_chains: int

    def specs(self, tree):
        """Each leaf's layout; ``tree`` holds global shapes."""
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: _leaf_spec(path, leaf, self.mesh, self.dp), tree)

    def dp_specs(self, specs):
        """The layouts over the dp axes alone: a dp-manual region's in_specs."""
        return jax.tree.map(lambda s: _keep_axes(s, self.dp), specs)

    def local(self, tree, specs):
        """Gather each leaf's local shard inside a region manual over dp."""
        return jax.tree.map(
            lambda x, s: gather_leaf_local(x, s, self.dp, self.mode, self.n_chains),
            tree, specs)

    def __call__(self, tree):
        def one(x, spec):
            if all(e is None for e in spec):
                return x
            return jax.shard_map(
                partial(gather_leaf_local, spec=spec, dp=self.dp, mode=self.mode,
                        n_chains=self.n_chains),
                mesh=self.mesh, in_specs=spec,
                out_specs=_keep_axes(spec, set(self.mesh.axis_names) - set(self.dp)),
                check_vma=False,
            )(x)

        return jax.tree.map(one, tree, self.specs(tree))


def make_param_gather(mesh: Mesh, mesh_cfg: MeshConfig,
                      coll: CollectiveConfig) -> ParamGather | None:
    """The gather hook of ``coll.fsdp_mode``; None for "xla" (XLA gathers)."""
    if coll.fsdp_mode == "xla":
        return None
    mode = {"mcast": "bidi", "mcast_ring": "ring", "mcast_bcast": "bcast"}.get(
        coll.fsdp_mode, "bidi"
    )
    return ParamGather(mesh, dp_axes(mesh_cfg), mode, coll.n_chains)


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

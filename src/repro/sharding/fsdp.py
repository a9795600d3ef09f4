"""FSDP (ZeRO-3) parameter gathering with the paper's collectives.

Four modes (CollectiveConfig.fsdp_mode):

  "xla"         — weights stay sharded (specs.py); XLA inserts the gathers.
  "mcast"       — the bidirectional ring, Fig. 1's two trees.
  "mcast_ring"  — the one-direction ring.
  "mcast_bcast" — the M-chain broadcast composition (Appendix A).

In the three paper modes each dp-sharded weight is gathered inside the layer
scan by ``shard_map`` ppermutes. The gather's AD transpose is the matching
ring reduce-scatter on the opposite direction, so the Insight-2 direction
split of grad traffic vs weight traffic falls out of the schedule for free.

On the multi-pod mesh the gather is hierarchical: ICI ring over "data" inside
the pod, then the M-chain broadcast composition over the switched "pod" axis —
the axis where the paper's multicast protocol literally applies (DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import CollectiveConfig, MeshConfig
from repro.core import collectives as C
from repro.sharding.ctx import get_ctx, use_ctx
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


@dataclasses.dataclass(frozen=True)
class ParamGather:
    """The ShardCtx.gather_params hook: the explicit gather of a one-layer
    parameter slice, each leaf's layout re-derived from its name and shape.

    Called on the slice, it is one ``shard_map`` per sharded leaf whose output
    is replicated over dp. ``dp_manual`` runs a whole layer, its gather
    included, inside one region manual over the dp axes instead, where each
    weight's cotangent stays a per-device partial until the ring reduce-
    scatters it."""

    mesh: Mesh
    dp: tuple[str, ...]
    mode: str
    n_chains: int

    def _specs(self, tree):
        """Each leaf's layout; ``tree`` holds global shapes."""
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: _leaf_spec(path, leaf, self.mesh, self.dp), tree)

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

        return jax.tree.map(one, tree, self._specs(tree))

    def dp_manual(self, apply, blocks):
        """``(bp, h, positions) -> h``: one layer of the stacked ``blocks``,
        its gather and ``apply(bp, h, positions)``, as one ``shard_map``
        manual over the dp axes (tp stays automatic), ``h`` split over dp by
        rows. The gathered weights are per-device values, so each weight's
        cotangent stays a per-device partial from its matmul to the ring
        reduce-scatter that transposes the gather: the ring is the only
        reduction of a weight gradient. Replicated leaves (the norms) come
        out of the transpose as small psums. ``apply`` sees the region's
        mesh, no dp axes and no gather, so ``shard`` and a ``shard_map``
        nested in it work on the local batch."""
        ctx = get_ctx()
        specs = self._specs(jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), blocks))
        rows = P(self.dp)

        def layer(bp, h, positions):
            local = dataclasses.replace(ctx, mesh=jax.sharding.get_abstract_mesh(),
                                        dp_axes=(), shard_batch=False, gather_params=None)
            with use_ctx(local):
                with jax.named_scope("fsdp_gather"):
                    bp = jax.tree.map(lambda x, s: gather_leaf_local(
                        x, s, self.dp, self.mode, self.n_chains), bp, specs)
                return apply(bp, h, positions)

        dp_specs = jax.tree.map(lambda s: _keep_axes(s, self.dp), specs)
        return jax.shard_map(layer, mesh=self.mesh, in_specs=(dp_specs, rows, P()),
                             out_specs=rows, axis_names=set(self.dp), check_vma=False)


_SCHEDULES = {"mcast": "bidi", "mcast_ring": "ring", "mcast_bcast": "bcast"}


def make_param_gather(mesh: Mesh, mesh_cfg: MeshConfig,
                      coll: CollectiveConfig) -> ParamGather | None:
    """The gather hook of ``coll.fsdp_mode``; None for "xla" (XLA gathers)."""
    if coll.fsdp_mode == "xla":
        return None
    if coll.fsdp_mode not in _SCHEDULES:
        raise ValueError(f"unknown fsdp_mode {coll.fsdp_mode!r}: expected xla or one of "
                         f"{', '.join(_SCHEDULES)}")
    return ParamGather(mesh, dp_axes(mesh_cfg), _SCHEDULES[coll.fsdp_mode], coll.n_chains)

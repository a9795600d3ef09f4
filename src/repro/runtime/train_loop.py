"""Training step factory: FSDP(+TP/SP/EP) train_step with gradient
accumulation, remat, AdamW, and the paper's collective layer wired in through
ShardCtx (fsdp_mode = "xla" | "mcast" | "mcast_ring" | "mcast_bcast").
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import RunConfig
from repro.models import build_model
from repro.optim import adamw
from repro.sharding.ctx import ShardCtx, use_ctx
from repro.sharding.fsdp import make_param_gather
from repro.sharding.specs import batch_pspecs, dp_axes, param_pspecs


class TrainState(NamedTuple):
    params: Any
    opt: adamw.OptState


def _dp_size(run: RunConfig) -> int:
    n = 1
    shape = run.mesh.shape
    axes = run.mesh.axes
    for s, a in zip(shape, axes):
        if a in ("pod", "data"):
            n *= s
    return n


def make_ctx(run: RunConfig, mesh: Mesh | None, *, for_decode: bool = False) -> ShardCtx:
    if mesh is None:
        return ShardCtx(mesh=None)
    shard_batch = run.shape.global_batch % _dp_size(run) == 0
    gather = None
    if not for_decode:
        gather = make_param_gather(mesh, run.mesh, run.collective)
    return ShardCtx(
        mesh=mesh,
        dp_axes=dp_axes(run.mesh),
        tp_axis="model",
        shard_batch=shard_batch,
        seq_parallel=not for_decode,
        gather_params=gather,
    )


def make_train_step(run: RunConfig, mesh: Mesh | None):
    """Returns (api, ctx, train_step). train_step: (state, batch) -> (state, metrics)."""
    cfg, tc = run.model, run.train
    api = build_model(cfg, remat=tc.remat)
    ctx = make_ctx(run, mesh)

    def loss_for(params, batch):
        return api.loss_fn(params, batch)

    def train_step(state: TrainState, batch):
        with use_ctx(ctx):
            params = state.params
            if tc.grad_accum > 1:
                a = tc.grad_accum

                def split(x):
                    return x.reshape((a, x.shape[0] // a) + x.shape[1:])

                micro = jax.tree.map(split, batch)

                def acc_body(carry, mb):
                    g_acc, loss_acc = carry
                    (loss, _), g = jax.value_and_grad(loss_for, has_aux=True)(
                        params, mb
                    )
                    g_acc = jax.tree.map(
                        lambda ga, gg: ga + gg.astype(jnp.float32), g_acc, g
                    )
                    return (g_acc, loss_acc + loss), None

                g0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params
                )
                (grads, loss), _ = jax.lax.scan(acc_body, (g0, jnp.zeros(())), micro)
                grads = jax.tree.map(lambda g: g / a, grads)
                loss = loss / a
                metrics = {"xent": loss}
            else:
                (loss, metrics), grads = jax.value_and_grad(loss_for, has_aux=True)(
                    params, batch
                )
            new_params, new_opt, om = adamw.apply_updates(params, grads, state.opt, tc)
            metrics = dict(metrics)
            metrics.update(om)
            metrics["loss"] = loss
        return TrainState(new_params, new_opt), metrics

    return api, ctx, train_step


def abstract_state(run: RunConfig) -> TrainState:
    """ShapeDtypeStruct state (no allocation) — dry-run / spec derivation."""
    api = build_model(run.model)
    params = jax.eval_shape(api.init_params, jax.random.PRNGKey(0))
    opt = jax.eval_shape(lambda p: adamw.init(p), params)
    return TrainState(params, opt)


def state_pspecs(run: RunConfig, mesh: Mesh):
    st = abstract_state(run)
    pspec = param_pspecs(st.params, mesh, run.mesh)
    mspec = param_pspecs(st.opt.m, mesh, run.mesh)
    return TrainState(
        pspec, adamw.OptState(m=mspec, v=mspec, step=P())
    )


def init_state(run: RunConfig, mesh: Mesh | None, rng) -> TrainState:
    """Materialize params+opt, directly sharded when a mesh is given."""
    api = build_model(run.model)

    def make(rng):
        params = api.init_params(rng)
        return TrainState(params, adamw.init(params))

    if mesh is None:
        return make(rng)
    specs = state_pspecs(run, mesh)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.jit(make, out_shardings=shardings)(rng)


def jit_train_step(run: RunConfig, mesh: Mesh):
    """Fully-specified jitted train step (used by launch/train.py and dryrun)."""
    api, ctx, step = make_train_step(run, mesh)
    specs = state_pspecs(run, mesh)
    state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))
    bspecs = batch_pspecs(run.model, run.shape, mesh, run.mesh)
    batch_sh = {k: NamedSharding(mesh, v) for k, v in bspecs.items()}
    return api, jax.jit(
        step,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, None),
        donate_argnums=(0,),
    )

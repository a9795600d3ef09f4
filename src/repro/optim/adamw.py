"""Sharded AdamW with global-norm clipping and warmup-cosine schedule.

Optimizer state (m, v) inherits the parameter sharding (ZeRO: the sharded
moments live next to the sharded params; no extra collectives beyond the
grad reduce-scatter that AD already emits).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import TrainConfig


class OptState(NamedTuple):
    m: Any
    v: Any
    step: jax.Array


def init(params, dtype=jnp.float32) -> OptState:
    zeros = lambda p: jnp.zeros(p.shape, dtype)
    return OptState(
        m=jax.tree.map(zeros, params),
        v=jax.tree.map(zeros, params),
        step=jnp.zeros((), jnp.int32),
    )


def lr_schedule(step, tc: TrainConfig):
    warm = jnp.minimum(step / jnp.maximum(tc.warmup_steps, 1), 1.0)
    prog = jnp.clip(
        (step - tc.warmup_steps) / jnp.maximum(tc.steps - tc.warmup_steps, 1), 0.0, 1.0
    )
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    return tc.learning_rate * warm * (0.1 + 0.9 * cos)


def global_norm(tree) -> jax.Array:
    sq = sum(
        jnp.sum(jnp.square(l.astype(jnp.float32))) for l in jax.tree.leaves(tree)
    )
    return jnp.sqrt(sq)


def clip_by_global_norm(grads, max_norm: float):
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads), gn


@jax.named_scope("optimizer")
def apply_updates(params, grads, opt: OptState, tc: TrainConfig):
    """One AdamW step, clipping included, under the named scope
    ``optimizer``. Returns (new_params, new_opt, metrics)."""
    grads, gn = clip_by_global_norm(grads, tc.grad_clip)
    step = opt.step + 1
    lr = lr_schedule(step, tc)
    b1, b2 = tc.beta1, tc.beta2
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g32 = g.astype(jnp.float32)
        m = b1 * m + (1 - b1) * g32
        v = b2 * v + (1 - b2) * jnp.square(g32)
        mh = m / c1
        vh = v / c2
        delta = mh / (jnp.sqrt(vh) + tc.eps) + tc.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(opt.m)
    flat_v = jax.tree.leaves(opt.v)
    new_p, new_m, new_v = [], [], []
    for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v):
        np_, nm, nv = upd(p, g, m, v)
        new_p.append(np_)
        new_m.append(nm)
        new_v.append(nv)
    return (
        jax.tree.unflatten(treedef, new_p),
        OptState(jax.tree.unflatten(treedef, new_m), jax.tree.unflatten(treedef, new_v), step),
        {"grad_norm": gn, "lr": lr},
    )

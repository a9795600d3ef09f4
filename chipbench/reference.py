"""Plain reference of the training steps a cell checks.

Imports nothing of the program. The forward and loss are the architecture
module's ``loss_sum`` (``chipbench/arch/``); around it, global-norm clipping
and AdamW with the job's warm-up/cosine schedule, written out here. Every
matrix product runs in float32 at HIGHEST precision. Weights are stored in
the dtype the configuration states (each update is rounded to it, as the
configuration says) and are held here as the float32 values of those numbers.

``precision="fp8"`` is the control: every matrix product's operands are
rounded to float8 e4m3 first, the step below the bfloat16 that the
configuration computes in.

Memory: gradients are summed over blocks of ``ref_rows`` sequences, each
layer is recomputed in the backward pass, and on several devices every leaf
is split over them along one axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from chipbench import model as M
from chipbench import tokens as T


def _shard_dim(shape: tuple[int, ...], n: int, stacked: bool) -> int | None:
    """The largest axis divisible by n, never a per-layer leaf's layer axis."""
    dims = [i for i in range(1 if stacked else 0, len(shape)) if shape[i] % n == 0]
    return max(dims, key=lambda i: shape[i]) if dims else None


def lr_at(step, tc: dict):
    """Linear warm-up, then cosine from the peak down to a tenth of it."""
    w = tc["lr_warmup_steps"]
    warm = jnp.minimum(step / max(w, 1), 1.0)
    prog = jnp.clip((step - w) / max(tc["schedule_steps"] - w, 1), 0.0, 1.0)
    return tc["learning_rate"] * warm * (0.1 + 0.9 * 0.5 * (1.0 + jnp.cos(jnp.pi * prog)))


class Reference:
    """The reference's compiled pieces for one cell, on ``devices``."""

    def __init__(self, arch, dims, job: dict, devices, precision: str = "f32"):
        self.dims = dims
        self.batch, self.seq, self.rows = job["global_batch"], job["seq_len"], job["ref_rows"]
        n = len(devices)
        if self.batch % self.rows or self.rows % n:
            raise ValueError(f"ref_rows {self.rows} must divide the batch "
                             f"{self.batch} and be a multiple of {n} devices")
        mesh = Mesh(np.asarray(devices), ("d",))
        specs = arch.leaf_specs(dims)
        stacked = arch.LAYER_LEAVES
        p_sh = {}
        for name, (shape, _, _) in specs.items():
            dim = _shard_dim(shape, n, name in stacked) if n > 1 else None
            axes = [None] * len(shape)
            if dim is not None:
                axes[dim] = "d"
            p_sh[name] = NamedSharding(mesh, P(*axes))
        self.row_sh = NamedSharding(mesh, P("d", None))
        scalar = NamedSharding(mesh, P())
        dtypes = {k: d for k, (_, d, _) in specs.items()}
        tc = job["train"]

        self._make = jax.jit(
            lambda key: {k: x.astype(jnp.float32)
                         for k, x in M.make_params(specs, key).items()},
            out_shardings=p_sh)
        self._zeros = jax.jit(
            lambda: {k: jnp.zeros(s, jnp.float32) for k, (s, _, _) in specs.items()},
            out_shardings=p_sh)

        def block_grad(acc, p, tok, tgt):
            (tot, cnt), g = jax.value_and_grad(
                lambda q: arch.loss_sum(q, tok, tgt, dims, precision), has_aux=True)(p)
            return jax.tree.map(jnp.add, acc, g), tot, cnt

        self._block_grad = jax.jit(block_grad, donate_argnums=(0,),
                                   out_shardings=(p_sh, scalar, scalar))

        def update(p, acc, mom, vel, count, step):
            g = {k: x / count for k, x in acc.items()}
            gn = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
            scale = jnp.minimum(1.0, tc["grad_clip"] / jnp.maximum(gn, 1e-9))
            g = {k: x * scale for k, x in g.items()}
            lr = lr_at(step, tc)
            b1, b2 = tc["beta1"], tc["beta2"]
            c1 = 1.0 - b1 ** step.astype(jnp.float32)
            c2 = 1.0 - b2 ** step.astype(jnp.float32)
            new_p, new_m, new_v = {}, {}, {}
            for k in p:
                new_m[k] = b1 * mom[k] + (1 - b1) * g[k]
                new_v[k] = b2 * vel[k] + (1 - b2) * g[k] * g[k]
                delta = (new_m[k] / c1) / (jnp.sqrt(new_v[k] / c2) + tc["eps"]) \
                    + tc["weight_decay"] * p[k]
                new_p[k] = (p[k] - lr * delta).astype(dtypes[k]).astype(jnp.float32)
            return new_p, new_m, new_v, M.leaf_norms(g, stacked)

        self._update = jax.jit(update, donate_argnums=(0, 2, 3),
                               out_shardings=(p_sh, p_sh, p_sh, None))
        self._change = jax.jit(
            lambda a, b: M.leaf_norms({k: a[k] - b[k] for k in a}, stacked))

    def run(self, seed: int, steps: int) -> dict:
        """Losses of steps 0..steps-1, per-leaf norms of the first clipped
        gradient, and per-leaf norms of the change after ``steps`` updates."""
        key = M.seed_key(seed)
        p = self._make(key)
        mom, vel = self._zeros(), self._zeros()
        losses, first_grad = [], None
        for t in range(steps):
            tok, tgt = T.batch(seed, t, self.batch, self.seq, self.dims.vocab)
            acc, tot, cnt = self._zeros(), 0.0, 0.0
            for lo in range(0, self.batch, self.rows):
                rows = slice(lo, lo + self.rows)
                acc, s, c = self._block_grad(acc, p, jax.device_put(tok[rows], self.row_sh),
                                             jax.device_put(tgt[rows], self.row_sh))
                tot, cnt = tot + float(s), cnt + float(c)
            losses.append(tot / cnt)
            p, mom, vel, gnorms = self._update(p, acc, mom, vel, jnp.float32(cnt),
                                               jnp.int32(t + 1))
            del acc
            if first_grad is None:
                first_grad = M.flat_norms(jax.device_get(gnorms))
        del mom, vel
        # the starting weights are made again rather than kept through the steps
        change = M.flat_norms(jax.device_get(self._change(p, self._make(key))))
        return {"loss": losses, "grad": first_grad, "change": change}

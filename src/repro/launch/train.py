"""Training launcher.

    python -m repro.launch.train --arch smollm-135m --steps 100 --smoke
    python -m repro.launch.train --arch yi-9b --shape train_4k \
        --mesh production [--multi-pod] --fsdp-mode mcast

--smoke runs the reduced config of the arch on the local devices (CPU-friendly
end-to-end: data pipeline -> FSDP train step -> checkpoint/restart supervisor).
On a real multi-host fleet, set JAX_COORDINATOR/process env and pass
--distributed to jax.distributed.initialize() before mesh construction.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
from jax.sharding import Mesh

from repro.configs import (MeshConfig, RunConfig, ShapeConfig, TrainConfig,
                           make_run_config, reduced)
from repro.data import SyntheticPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import mesh_for
from repro.runtime import init_state, jit_train_step, make_train_step
from repro.runtime.fault import TrainSupervisor


def train(run: RunConfig, mesh: Mesh | None):
    """Initialise from ``run.train.seed`` and take ``run.train.steps`` steps
    under the checkpoint/restart supervisor. ``mesh`` must match ``run.mesh``;
    None runs on the default device. Returns (state, history, supervisor)."""
    if mesh is not None:
        assert dict(mesh.shape) == dict(zip(run.mesh.axes, run.mesh.shape)), (
            mesh.shape, run.mesh)
        api, step_fn = jit_train_step(run, mesh)
    else:
        api, ctx, step_raw = make_train_step(run, None)
        step_fn = jax.jit(step_raw)

    state = init_state(run, mesh, jax.random.PRNGKey(run.train.seed))
    sup = TrainSupervisor(
        step_fn=step_fn, pipeline=SyntheticPipeline(run.model, run.shape),
        ckpt_dir=run.train.checkpoint_dir, ckpt_every=run.train.checkpoint_every,
    )
    state, history = sup.run(state, run.train.steps)
    return state, history, sup


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + local devices (CPU demo)")
    ap.add_argument("--mesh", default="local", choices=["local", "production"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--fsdp-mode", default="xla")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--remat", default="full")
    ap.add_argument("--batch", type=int, default=0, help="override global batch")
    ap.add_argument("--seq", type=int, default=0, help="override seq len")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--distributed", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    if args.distributed:
        jax.distributed.initialize()

    run = make_run_config(args.arch, args.shape, multi_pod=args.multi_pod)
    model = run.model
    shape = run.shape
    if args.smoke:
        model = reduced(model)
        shape = ShapeConfig(shape.name, shape.kind, args.seq or 128, args.batch or 8)
    elif args.batch or args.seq:
        shape = ShapeConfig(
            shape.name, shape.kind, args.seq or shape.seq_len,
            args.batch or shape.global_batch,
        )
    run = run.replace(
        model=model, shape=shape,
        train=TrainConfig(
            steps=args.steps, grad_accum=args.grad_accum, remat=args.remat,
            checkpoint_dir=args.ckpt_dir, checkpoint_every=args.ckpt_every,
        ),
        collective=dataclasses.replace(run.collective, fsdp_mode=args.fsdp_mode),
    )

    mesh = None
    if args.mesh == "production":
        mesh = mesh_for(run.mesh)
    elif jax.device_count() > 1:
        n = jax.device_count()
        dp = max(1, n // 2)
        run = run.replace(mesh=MeshConfig((dp, n // dp), ("data", "model")))
        mesh = mesh_for(run.mesh)

    print(f"[train] {model.name} shape={shape.name} B={shape.global_batch} "
          f"S={shape.seq_len} devices={jax.device_count()} "
          f"fsdp={args.fsdp_mode}", flush=True)

    state, history, sup = train(run, mesh)
    for h in history:
        if h["step"] % args.log_every == 0 or h["step"] == args.steps - 1:
            print(f"step {h['step']:5d} loss {h['loss']:.4f} "
                  f"gnorm {h.get('grad_norm', 0):.3f} dt {h['dt']*1e3:.0f}ms",
                  flush=True)
    print(f"[train] done; stragglers flagged: {len(sup.monitor.events)}")


if __name__ == "__main__":
    main()

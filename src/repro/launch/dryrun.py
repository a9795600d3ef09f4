import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (architecture x input-shape x mesh) cell
lowers, SPMD-partitions, and compiles on the production mesh, and extract the
roofline inputs from the compiled artifact.

The two lines above MUST precede every other import (jax locks the device
count at first init). Do NOT set this flag anywhere global.

Per cell this emits a JSON record with:
  - compiled.memory_analysis()  (fits-in-HBM proof)
  - compiled.cost_analysis()    (raw; loop bodies counted once — cross-check)
  - HLO-parsed collective bytes (launch/hlo_stats.py, loop-scaled)
  - analytic compute/memory/collective models (launch/analytic_costs.py)

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] --out dryrun_results/
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import (  # noqa: E402
    SHAPES,
    MeshConfig,
    cell_supported,
    get_model_config,
    iter_cells,
    make_run_config,
)
from repro.launch import analytic_costs, hlo_stats  # noqa: E402
from repro.launch.mesh import describe, mesh_for  # noqa: E402
from repro.models import batch_dims  # noqa: E402


def _batch_sds(run):
    dims = batch_dims(run.model, run.shape)
    out = {}
    for name, shp in dims.items():
        if name in ("tokens", "targets", "token", "pos"):
            out[name] = jax.ShapeDtypeStruct(shp, jnp.int32)
        else:
            out[name] = jax.ShapeDtypeStruct(shp, jnp.bfloat16)
    return out


def lower_cell(run, mesh):
    """Returns (lowered, loop_chain) for the cell's step function."""
    kind = run.shape.kind
    if kind == "train":
        from repro.runtime.train_loop import abstract_state, jit_train_step

        api, step = jit_train_step(run, mesh)
        state = abstract_state(run)
        lowered = step.lower(state, _batch_sds(run))
        chain = (run.model.num_layers,)
        if run.train.grad_accum > 1:
            chain = (run.train.grad_accum, run.model.num_layers)
        return lowered, chain
    if kind == "prefill":
        from repro.runtime.serve_loop import jit_prefill_step

        api, step = jit_prefill_step(run, mesh)
        lowered = step.lower(_abstract_params(run), _batch_sds(run))
        return lowered, (run.model.num_layers,)
    # decode
    from repro.runtime.serve_loop import ServeState, abstract_cache, jit_decode_step

    api, step = jit_decode_step(run, mesh)
    cache = abstract_cache(run)
    b = run.shape.global_batch
    state = ServeState(cache, jax.ShapeDtypeStruct((b,), jnp.int32))
    token = jax.ShapeDtypeStruct((b,), jnp.int32)
    lowered = step.lower(_abstract_params(run), state, token)
    return lowered, (run.model.num_layers,)


def _abstract_params(run):
    from repro.models import build_model

    api = build_model(run.model)
    return jax.eval_shape(api.init_params, jax.random.PRNGKey(0))


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             fsdp_mode: str = "xla", grad_accum: int = 1,
             remat: str = "full", collect_hlo: bool = True,
             mesh_shape: tuple[int, int] | None = None,
             serve_replicate: bool = False,
             moe_groups: int = 0,
             kv_int8: bool = False) -> dict:
    """mesh_shape: regroup the same 256 chips as (dp, tp) — a §Perf knob
    (the mesh shape is a software view of the physical pod)."""
    t_start = time.monotonic()
    run = make_run_config(arch, shape_name, multi_pod=multi_pod)
    model = run.model
    if moe_groups and model.moe is not None:
        model = dataclasses.replace(
            model, moe=dataclasses.replace(model.moe, routing_groups=moe_groups)
        )
    if kv_int8:
        model = dataclasses.replace(model, kv_cache_dtype="int8")
    run = run.replace(
        model=model,
        train=dataclasses.replace(run.train, grad_accum=grad_accum, remat=remat),
        collective=dataclasses.replace(
            run.collective, fsdp_mode=fsdp_mode,
            serve_params_replicated=serve_replicate,
        ),
    )
    if mesh_shape is not None:
        assert not multi_pod, "mesh regrouping is a single-pod perf knob"
        run = run.replace(mesh=MeshConfig(tuple(mesh_shape), ("data", "model")))
    mesh = mesh_for(run.mesh)
    n_dev = mesh.devices.size
    rec: dict = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "fsdp_mode": fsdp_mode, "grad_accum": grad_accum, "remat": remat,
        "mesh_shape": list(mesh_shape) if mesh_shape else None,
        "serve_replicate": serve_replicate, "moe_groups": moe_groups,
        "mesh": describe(mesh), "ok": False,
    }
    try:
        lowered, chain = lower_cell(run, mesh)
        t_lower = time.monotonic()
        compiled = lowered.compile()
        t_compile = time.monotonic()
        rec["lower_s"] = round(t_lower - t_start, 2)
        rec["compile_s"] = round(t_compile - t_lower, 2)

        try:
            mem = compiled.memory_analysis()
            rec["memory_analysis"] = {
                k: int(getattr(mem, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(mem, k)
            }
        except Exception as e:  # CPU backend may not implement it
            rec["memory_analysis"] = {"error": str(e)}

        try:
            cost = compiled.cost_analysis()
            rec["cost_analysis_raw"] = {
                k: float(v) for k, v in cost.items()
                if isinstance(v, (int, float)) and k in
                ("flops", "bytes accessed", "transcendentals",
                 "utilization operand 0 {}", "optimal_seconds")
            }
        except Exception as e:
            rec["cost_analysis_raw"] = {"error": str(e)}

        if collect_hlo:
            hlo = compiled.as_text()
            st = hlo_stats.collective_stats(hlo, n_dev, loop_chain=chain)
            rec["collectives_hlo"] = st.as_dict()
            rec["hlo_bytes"] = len(hlo)
            del hlo

        # analytic roofline inputs
        cfg, shape = run.model, run.shape
        cc = analytic_costs.cell_cost(
            cfg, shape, n_dev, remat=remat,
            tp=mesh.shape["model"], serve_replicated=serve_replicate,
        )
        tp = mesh.shape["model"]
        dp = n_dev // tp
        epx = 1.0
        if moe_groups and cfg.moe is not None:
            # cross-EP copies per token: bounded by the active group count
            # instead of top_k (DeepSeek-V3 device-limited routing)
            epx = min(cfg.moe.routing_group_topk, cfg.moe.top_k) / cfg.moe.top_k
        cl = analytic_costs.collective_cost(
            cfg, shape, dp=dp, tp=tp, remat=remat, grad_accum=grad_accum,
            ep_crossing_factor=epx, serve_replicated=serve_replicate,
        )
        rec["analytic"] = {
            "model_flops": cc.model_flops,
            "impl_flops": cc.impl_flops,
            "useful_ratio": cc.useful_ratio,
            "hbm_bytes_per_device": cc.hbm_bytes,
            "params_total": cc.params_total,
            "params_active": cc.params_active,
            "collective_bytes_per_device": {
                "fsdp_allgather": cl.fsdp_allgather,
                "grad_reduce_scatter": cl.grad_reduce_scatter,
                "tp_activations": cl.tp_activations,
                "ep_all_to_all": cl.ep_all_to_all,
                "decode_psum": cl.decode_psum,
                "total": cl.total,
            },
        }
        rec["ok"] = True
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.monotonic() - t_start, 2)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--fsdp-mode", default="xla")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--remat", default="full")
    ap.add_argument("--mesh-shape", default=None,
                    help="regroup the pod, e.g. 64x4 (dp x tp)")
    ap.add_argument("--serve-replicate", action="store_true")
    ap.add_argument("--moe-groups", type=int, default=0)
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--no-hlo", action="store_true")
    args = ap.parse_args()
    mesh_shape = None
    if args.mesh_shape:
        mesh_shape = tuple(int(x) for x in args.mesh_shape.split("x"))
        assert len(mesh_shape) == 2 and mesh_shape[0] * mesh_shape[1] == 256

    cells = []
    if args.all:
        for arch, shape, ok, why in iter_cells(include_skipped=True):
            if ok:
                cells.append((arch, shape))
            else:
                print(f"SKIP {arch} x {shape}: {why}", flush=True)
    else:
        ok, why = cell_supported(get_model_config(args.arch), SHAPES[args.shape])
        if not ok:
            print(f"SKIP: {why}")
            sys.exit(0)
        cells.append((args.arch, args.shape))

    results = []
    for arch, shape in cells:
        rec = run_cell(
            arch, shape, args.multi_pod,
            fsdp_mode=args.fsdp_mode, grad_accum=args.grad_accum,
            remat=args.remat, collect_hlo=not args.no_hlo,
            mesh_shape=mesh_shape, serve_replicate=args.serve_replicate,
            moe_groups=args.moe_groups, kv_int8=args.kv_int8,
        )
        status = "OK" if rec["ok"] else f"FAIL ({rec.get('error', '?')})"
        print(f"[dryrun] {arch} x {shape} multi_pod={args.multi_pod}: {status} "
              f"(lower {rec.get('lower_s')}s compile {rec.get('compile_s')}s)",
              flush=True)
        results.append(rec)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            tag = f"{arch}__{shape}__{'2pod' if args.multi_pod else '1pod'}"
            variant = []
            if args.fsdp_mode != "xla":
                variant.append(args.fsdp_mode)
            if args.grad_accum != 1:
                variant.append(f"a{args.grad_accum}")
            if args.remat != "full":
                variant.append(args.remat)
            if mesh_shape:
                variant.append(f"m{mesh_shape[0]}x{mesh_shape[1]}")
            if args.serve_replicate:
                variant.append("srvrep")
            if args.moe_groups:
                variant.append(f"g{args.moe_groups}")
            if args.kv_int8:
                variant.append("kvi8")
            if variant:
                tag += "__" + "_".join(variant)
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                json.dump(rec, f, indent=1)
    n_bad = sum(not r["ok"] for r in results)
    print(f"[dryrun] done: {len(results) - n_bad}/{len(results)} OK", flush=True)
    sys.exit(1 if n_bad else 0)


if __name__ == "__main__":
    main()

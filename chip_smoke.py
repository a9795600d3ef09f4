"""Smoke run of the training and serving paths on a TPU, through the repo's
own entry code, with random weights made from a seed.

    python chip_smoke.py               # one chip: smollm-135m train + decode
    python chip_smoke.py --four-chips  # one 2x2 host: FSDP gathers of yi-9b

One chip: smollm-135m at its published widths takes 5 train steps (B=8,
S=2048, fsdp_mode="xla") and must give finite losses and grad norms; the
step-0 loss the train step returned must match a float32 forward of the same
parameters and batch on the host CPU; then 4 prompts decode 32 new tokens
each, and the decode logits must match a prefill of the same prompts.

Four chips: yi-9b at its published widths, cut to 2 layers, trains on a
data=4 mesh for 3 steps under each FSDP gather mode. The paper's schedules
(mcast, mcast_bcast) must hand every device a bitwise copy of each weight,
their losses and grad norms must match XLA's own sharding (xla), and the
parameter shards must span all four devices.

Every check that fails exits non-zero. Only a run that passes them all
prints, as its last line, {"ok": true, "device": {...}}. There is no CPU
path: without a TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import statistics
import sys
import time
from pathlib import Path

if __name__ == "__main__":
    # the float32 reference runs on the host CPU backend of this same
    # process; JAX reads the platform list when it is imported
    _platforms = os.environ.get("JAX_PLATFORMS")
    if _platforms and "cpu" not in _platforms.split(","):
        os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

# the step-0 loss of the bf16 train step against a float32 forward on the
# CPU: bf16 rounding of weights and activations moved it 1.5e-5 (relative)
# on a v5e; 1e-3 still rejects uniform logits (ln V), about 1 % away
LOSS_RTOL = 1e-3
# relative L2 distance of bf16 activations/logits from their reference
ACT_RTOL = 5e-2
# mcast modes against xla. The gathers are exact copies (checked bitwise
# below), but xla gathers only the LM head: XLA keeps the layer weights
# sharded and runs their matmuls as windowed einsums, whose bf16 partial
# products round differently from one whole-weight matmul. On a v5e 2x2
# that puts xla's step-0 loss 9.07e-6 (relative) from a forward with
# replicated weights; the mcast forward equals that forward bitwise and
# the mcast_bcast one is 9.1e-7 from it. The mcast train steps land 7.3e-6 and 1.0e-5
# from xla, the same on every run. Grad norms and later losses, whose
# gradients the ring reduce-scatters also sum in another order, differ by
# at most 1.1e-5; their limits leave about ten times that.
STEP0_RTOL = 1e-5
GRAD_RTOL = 1e-4
LATER_RTOL = 1e-4


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def rel_l2(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def count_files(path: str) -> int:
    p = Path(path)
    return sum(1 for f in p.rglob("*") if f.is_file()) if p.is_dir() else 0


def gib(n: int | None) -> str:
    return "not reported" if n is None else f"{n / 2**30:.3f} GiB"


def bits(a) -> np.ndarray:
    a = np.asarray(a)
    return a.view(f"u{a.dtype.itemsize}")


def report_steps(tag: str, history: list[dict]) -> None:
    for h in history:
        print(f"[{tag}] step {h['step']} loss {h['loss']:.6f} "
              f"grad_norm {h['grad_norm']:.6f} dt {h['dt']:.4f}s", flush=True)
    steady = [h["dt"] for h in history[1:]]
    print(f"[{tag}] chip time: first step (compile + run) {history[0]['dt']:.3f}s, "
          f"steady step {statistics.median(steady):.4f}s "
          f"(median of {len(steady)})", flush=True)
    for h in history:
        check(math.isfinite(h["loss"]) and math.isfinite(h["grad_norm"]),
              f"{tag}: non-finite loss or grad norm at step {h['step']}")


# ------------------------------------------------------------------ one chip


def one_chip_phase(model, *, batch_size: int = 8, seq: int = 2048, steps: int = 5,
                   ref_seqs: int = 2, decode_batch: int = 4,
                   prompt_len: int = 32, new_tokens: int = 32) -> None:
    from repro.configs import RunConfig, ShapeConfig, TrainConfig
    from repro.data import SyntheticPipeline
    from repro.launch.serve import generate
    from repro.launch.train import train
    from repro.models import build_model
    from repro.runtime import init_state

    dev = jax.devices()[0]
    run = RunConfig(model=model, shape=ShapeConfig("smoke", "train", seq, batch_size),
                    train=TrainConfig(steps=steps, checkpoint_every=0))
    print(f"[train] {model.name}: {model.num_layers} layers, d_model "
          f"{model.d_model}, vocab {model.vocab_size}; B={batch_size} S={seq} "
          f"fsdp={run.collective.fsdp_mode}, {steps} steps on {dev}", flush=True)
    _, history, _ = train(run, None)
    report_steps("train", history)
    print(f"[train] peak_bytes_in_use {gib(peak_bytes(dev))}", flush=True)

    # the loss train() returned at step 0, against a float32 forward of the
    # same initial parameters and batch on the host CPU, ref_seqs sequences
    # at a time; the token mean of the batch is the count-weighted mean
    params = init_state(run, None, jax.random.PRNGKey(run.train.seed)).params
    batch = jax.device_get(SyntheticPipeline(model, run.shape).next_batch(0))
    cpu = jax.devices("cpu")[0]
    api32 = build_model(dataclasses.replace(model, param_dtype="float32",
                                            compute_dtype="float32"))
    params32 = jax.device_put(
        jax.tree.map(lambda x: np.asarray(x, np.float32), jax.device_get(params)), cpu)
    ref_loss_fn = jax.jit(lambda p, b: api32.loss_fn(p, b)[0])
    t0 = time.perf_counter()
    tot = cnt = 0.0
    for lo in range(0, batch_size, ref_seqs):
        part = {k: v[lo:lo + ref_seqs] for k, v in batch.items()}
        n = float((part["targets"] >= 0).sum())
        tot += float(ref_loss_fn(params32, jax.device_put(part, cpu))) * n
        cnt += n
    loss, ref_loss = history[0]["loss"], tot / cnt
    print(f"[ref] step-0 loss of the whole batch: train step (chip, bf16) "
          f"{loss:.6f}, host cpu f32 {ref_loss:.6f} (rel diff "
          f"{abs(loss - ref_loss) / abs(ref_loss):.3e}, tol {LOSS_RTOL}); "
          f"host time {time.perf_counter() - t0:.1f}s", flush=True)
    check(abs(loss - ref_loss) <= LOSS_RTOL * abs(ref_loss),
          "step-0 loss differs from the host float32 forward")

    # the final hidden state of the first sequences, chip bf16 against host f32
    first = {k: v[:ref_seqs] for k, v in batch.items()}
    hidden = jax.jit(build_model(model).forward_fn)(params, first)
    ref_hidden = jax.jit(api32.forward_fn)(params32, jax.device_put(first, cpu))
    d_hidden = rel_l2(hidden, ref_hidden)
    print(f"[ref] final hidden of sequences 0-{ref_seqs - 1}: rel L2 "
          f"{d_hidden:.3e} (tol {ACT_RTOL})", flush=True)
    check(d_hidden <= ACT_RTOL, "final hidden state differs from the host float32 forward")
    del params, params32, hidden, ref_hidden

    # greedy decode through the serving entry code
    r = generate(model, batch=decode_batch, prompt_len=prompt_len,
                 new_tokens=new_tokens)
    new = np.asarray(r["seqs"][:, prompt_len:])
    print(f"[serve] {decode_batch} prompts x {prompt_len} tokens, {new_tokens} new "
          f"tokens each; chip time: first step (compile + run) "
          f"{r['first_step_s']:.3f}s, steady {r['steady_step_s'] * 1e3:.3f} ms/step, "
          f"{decode_batch / r['steady_step_s']:.1f} tokens/s", flush=True)
    print(f"[serve] sequence 0 new tokens: {new[0].tolist()}", flush=True)
    check(new.shape == (decode_batch, new_tokens), f"decoded shape {new.shape}")
    check(bool(((new >= 0) & (new < model.vocab_size)).all()), "token id out of range")
    prefill_logits, _ = jax.jit(build_model(model).prefill_fn)(
        r["params"], {"tokens": r["prompts"]})
    d_logits = rel_l2(r["first_logits"], prefill_logits)
    print(f"[serve] decode vs prefill logits at the first new token: rel L2 "
          f"{d_logits:.3e} (tol {ACT_RTOL})", flush=True)
    check(bool(np.isfinite(np.asarray(r["first_logits"], np.float32)).all()),
          "non-finite decode logits")
    check(d_logits <= ACT_RTOL, "decode logits differ from prefill")
    print(f"[serve] peak_bytes_in_use {gib(peak_bytes(dev))}", flush=True)


# --------------------------------------------------------------- four chips


def four_chip_phase(model, *, layers: int = 2, batch: int = 8, seq: int = 2048,
                    steps: int = 3, modes=("xla", "mcast", "mcast_bcast")) -> None:
    from repro.configs import CollectiveConfig, MeshConfig, RunConfig, ShapeConfig, TrainConfig
    from repro.launch.mesh import mesh_for
    from repro.launch.train import train
    from repro.sharding.fsdp import make_param_gather
    from repro.sharding.specs import param_pspecs

    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, found {len(devices)}")
    print(f"[fsdp] {model.name}: published widths (d_model {model.d_model}, "
          f"d_ff {model.d_ff}, vocab {model.vocab_size}), depth cut "
          f"{model.num_layers} -> {layers} layers", flush=True)
    model = dataclasses.replace(model, num_layers=layers)
    mesh_cfg = MeshConfig((4, 1), ("data", "model"))
    mesh = mesh_for(mesh_cfg)
    readings = {}
    for mode in modes:
        run = RunConfig(model=model, shape=ShapeConfig("smoke", "train", seq, batch),
                        mesh=mesh_cfg,
                        train=TrainConfig(steps=steps, checkpoint_every=0),
                        collective=CollectiveConfig(fsdp_mode=mode))
        state, history, _ = train(run, mesh)
        tag = f"fsdp {mode}"
        report_steps(tag, history)
        readings[mode] = history

        held = {d: 0 for d in devices}
        for leaf in jax.tree.leaves(state.params):
            for shard in leaf.addressable_shards:
                held[shard.device] += shard.data.nbytes
        total = sum(leaf.nbytes for leaf in jax.tree.leaves(state.params))
        print(f"[{tag}] parameter bytes per device: "
              + ", ".join(f"{d.id}: {n}" for d, n in held.items())
              + f" (of {total} in all)", flush=True)
        check(all(n > 0 for n in held.values()), f"{tag}: a device holds no parameters")
        check(max(held.values()) < total, f"{tag}: parameters are not sharded")
        print(f"[{tag}] peak_bytes_in_use per device: "
              + ", ".join(f"{d.id}: {gib(peak_bytes(d))}" for d in devices), flush=True)

        gather = make_param_gather(mesh, mesh_cfg, run.collective)
        if gather is not None:
            # layer 0's weights as the layer scan slices them, gathered by
            # this mode's schedule: every device must hold an exact copy
            layer = jax.tree.map(lambda x: x[0], state.params["blocks"])
            layer = jax.device_put(layer, jax.tree.map(
                lambda s: NamedSharding(mesh, s), param_pspecs(layer, mesh, mesh_cfg),
                is_leaf=lambda x: isinstance(x, P)))
            gathered = jax.jit(gather)(layer)
            n_bytes = 0
            for w, g in zip(jax.tree.leaves(layer), jax.tree.leaves(gathered)):
                whole = bits(jax.device_get(w))
                for shard in g.addressable_shards:
                    check(shard.data.shape == whole.shape and np.array_equal(
                        bits(shard.data), whole),
                        f"{tag}: device {shard.device.id} gathered a weight of "
                        f"shape {w.shape} that is not a bitwise copy")
                n_bytes += whole.nbytes
            print(f"[{tag}] layer-0 gather: {len(jax.tree.leaves(layer))} weights, "
                  f"{n_bytes} bytes; every device's copy is bitwise equal to the "
                  f"sharded weight", flush=True)
            del layer, gathered
        del state

    base = readings["xla"]
    for mode in modes:
        if mode == "xla":
            continue
        rel = [abs(h["loss"] - b["loss"]) / abs(b["loss"])
               for h, b in zip(readings[mode], base)]
        rel_grad = (abs(readings[mode][0]["grad_norm"] - base[0]["grad_norm"])
                    / abs(base[0]["grad_norm"]))
        print(f"[fsdp] {mode} vs xla loss rel diff per step: "
              + ", ".join(f"{x:.3e}" for x in rel)
              + f" (tol step 0 {STEP0_RTOL}, later {LATER_RTOL}); step-0 grad "
              f"norm rel diff {rel_grad:.3e} (tol {GRAD_RTOL})", flush=True)
        check(rel[0] <= STEP0_RTOL, f"{mode}: step-0 loss differs from xla")
        check(rel_grad <= GRAD_RTOL, f"{mode}: step-0 grad norm differs from xla")
        check(all(x <= LATER_RTOL for x in rel[1:]), f"{mode}: later loss differs from xla")


# --------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the FSDP gather phase on a 2x2 host")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing was run",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.configs import get_model_config
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    files_before = count_files(cache_dir)
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    print(f"[device] {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"jax {jax.__version__}; compile cache {cache_dir}", flush=True)
    try:
        if args.four_chips:
            four_chip_phase(get_model_config("yi-9b"))
        else:
            one_chip_phase(get_model_config("smollm-135m"))
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    # a miss is written only when its compile took longer than JAX's
    # minimum, so the directory's file count says what was written
    files_after = count_files(cache_dir)
    print(f"[cache] compile cache {cache_dir}: {cache_events['hits']} hits, "
          f"{cache_events['misses']} misses; files {files_before} before, "
          f"{files_after} after ({files_after - files_before} written)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

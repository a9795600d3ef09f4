"""One run of one cell: set-up, the measured window, the comparison.

The run is assembled as the program's ``launch/train.py:train`` assembles
it: the jitted train step (``make_train_step`` + ``jax.jit`` on one chip,
``jit_train_step`` on the cell's mesh otherwise), the ``SyntheticPipeline``
seeded from the run's seed, and ``TrainSupervisor`` with checkpoints off.
The weights are the benchmark's own, made on the device in one jitted call
from the seed (``chipbench/model.py``). The harness hands the supervisor a
pipeline and a step that only wrap the program's in host spans
(``next_batch``, ``dispatch``, ``wait``).

Set-up drives that one supervisor and state through the first
``check_steps`` steps, which also compile or load the step, and reads from
the state what the reference is compared on. The window is then one
``TrainSupervisor.run`` over as many further steps as fill ``seconds`` at
the set-up's step time. After it, the program's state is freed and the
plain reference (``reference.py``) follows the same first steps.
"""
from __future__ import annotations

import dataclasses
import math
import shutil
import statistics
import sys
import time
from collections import defaultdict
from contextlib import contextmanager
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from chipbench import compare
from chipbench import model as M
from chipbench import trace as TR
from chipbench.cell import ROOT, Cell, metric_reader
from chipbench.reference import Reference

# run-time outputs inside the checkout (listed in .gitignore)
OUT = ROOT / ".chipbench"


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class Spans:
    """The harness's host spans: written into the profiler's trace, and
    their host-clock durations kept while ``recording``."""

    def __init__(self):
        self.durations: dict[str, list[float]] = defaultdict(list)
        self.recording = False

    @contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        if self.recording:
            self.durations[name].append(time.perf_counter() - t0)


class TimedPipeline:
    """The program's pipeline, each ``next_batch`` inside a span."""

    def __init__(self, inner, spans: Spans):
        self.inner, self.spans = inner, spans

    def next_batch(self, step: int):
        with self.spans("next_batch"):
            return self.inner.next_batch(step)


def timed_step(step_fn, spans: Spans):
    """The program's step: its dispatch and the wait for its result, each
    inside a span. The supervisor's own wait then finds the result ready."""

    def step(state, batch):
        with spans("dispatch"):
            out = step_fn(state, batch)
        with spans("wait"):
            return jax.block_until_ready(out)

    return step


class CompileCounter:
    """Counts JAX's compile and compile-cache events while entered."""

    def __init__(self):
        self.count = 0

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event.startswith("/jax/core/compile") or event.startswith("/jax/compilation_cache"):
            self.count += 1

    def _on_event(self, event: str, **kw) -> None:
        if event.startswith("/jax/compilation_cache"):
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
        return False


class Program:
    """The program's train step, state and pipeline for one cell, assembled
    as ``launch/train.py:train`` does."""

    def __init__(self, cell: Cell, devices):
        from repro.configs import (CollectiveConfig, MeshConfig, RunConfig, ShapeConfig,
                                   TrainConfig, get_model_config)
        from repro.launch.mesh import mesh_for
        from repro.optim.adamw import OptState
        from repro.runtime import (TrainState, abstract_state, jit_train_step,
                                   make_train_step, state_pspecs)

        self.cell, self.devices = cell, devices
        self.arch = M.arch(cell.config["model_type"])
        self.dims = self.arch.Dims.from_config(cell.config)
        job, tc = cell.traffic, cell.traffic["train"]
        model = dataclasses.replace(get_model_config(cell.config["registry"]),
                                    **self.arch.program_fields(cell.config))
        mesh_axes = cell.config["mesh"]
        mesh_cfg = (MeshConfig(tuple(mesh_axes.values()), tuple(mesh_axes))
                    if mesh_axes else MeshConfig())
        self.run_cfg = RunConfig(
            model=model, shape=ShapeConfig(cell.name, "train", job["seq_len"], job["global_batch"]),
            mesh=mesh_cfg,
            train=TrainConfig(
                steps=tc["schedule_steps"], learning_rate=tc["learning_rate"],
                warmup_steps=tc["lr_warmup_steps"], weight_decay=tc["weight_decay"],
                beta1=tc["beta1"], beta2=tc["beta2"], eps=tc["eps"], grad_clip=tc["grad_clip"],
                remat=tc["remat"], checkpoint_every=0, checkpoint_dir=str(OUT / "ckpt")),
            collective=CollectiveConfig(fsdp_mode=job["fsdp_mode"]),
        )
        if mesh_axes:
            if len(devices) != mesh_cfg.n_devices:
                raise ValueError(f"mesh {mesh_axes} needs {mesh_cfg.n_devices} devices, "
                                 f"got {len(devices)}")
            mesh = mesh_for(mesh_cfg, devices=devices)
            _, self.step_fn = jit_train_step(self.run_cfg, mesh)
            state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                    state_pspecs(self.run_cfg, mesh),
                                    is_leaf=lambda x: isinstance(x, P))
        else:
            _, _, raw = make_train_step(self.run_cfg, None)
            self.step_fn = jax.jit(raw)
            state_sh = jax.sharding.SingleDeviceSharding(devices[0])
        arch, specs = self.arch, self.arch.leaf_specs(self.dims)

        def make_state(key):
            params = arch.to_program(M.make_params(specs, key))
            zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
            return TrainState(params, OptState(zeros, jax.tree.map(jnp.zeros_like, zeros),
                                               jnp.zeros((), jnp.int32)))

        want = abstract_state(self.run_cfg)
        got = jax.eval_shape(make_state, M.seed_key(0))
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype)
                for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise RuntimeError("the benchmark's weights no longer match the program's "
                               "parameter tree")
        self.make_state = jax.jit(make_state, out_shardings=state_sh)
        stacked = arch.LAYER_LEAVES
        self.copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
        self.norms = jax.jit(lambda t: M.leaf_norms(arch.named(t), stacked))
        self.change = jax.jit(lambda a, b: M.leaf_norms(
            {k: x.astype(jnp.float32) - y.astype(jnp.float32)
             for (k, x), y in zip(arch.named(a).items(), arch.named(b).values())}, stacked))

    def pipeline(self, seed: int):
        from repro.data import SyntheticPipeline
        from repro.data.pipeline import DataConfig

        return SyntheticPipeline(self.run_cfg.model, self.run_cfg.shape, DataConfig(seed=seed))

    def check_steps(self, seed: int, spans: Spans):
        """Build the state from ``seed`` and take the first ``check_steps``
        steps through the supervisor. Returns (state, supervisor, readings,
        history): the loss of each step, the per-leaf norms of the first
        gradient as the optimizer got it (Adam's first moment after one
        step, over 1 - beta1), and of the weights' change over the steps."""
        from repro.runtime.fault import TrainSupervisor

        k = self.cell.traffic["check_steps"]
        if k < 2:
            raise ValueError("check_steps must be at least 2 (one to compile, one to time)")
        state = self.make_state(M.seed_key(seed))
        p0 = self.copy(state.params)
        sup = TrainSupervisor(step_fn=timed_step(self.step_fn, spans),
                              pipeline=TimedPipeline(self.pipeline(seed), spans),
                              ckpt_dir=self.run_cfg.train.checkpoint_dir, ckpt_every=0)
        state, hist = sup.run(state, 1)
        b1 = self.run_cfg.train.beta1
        grad = {n: v / (1.0 - b1) for n, v in
                M.flat_norms(jax.device_get(self.norms(state.opt.m))).items()}
        state, more = sup.run(state, k, start_step=1)
        hist += more
        change = M.flat_norms(jax.device_get(self.change(state.params, p0)))
        del p0
        readings = {"loss": [h["loss"] for h in hist], "grad": grad, "change": change}
        return state, sup, readings, hist


@dataclass
class RunRecord:
    """What a per-layer metric's reader reads."""
    steps: int
    tokens_per_step: int
    window_s: float
    chips: int
    useful_flops_per_token: float
    peak: dict
    spans: dict
    device: dict | None


def peak_memory(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool, devices, peak: dict,
             t0: float) -> dict:
    """One run; returns the result line as a dict (``checks`` last)."""
    job = cell.traffic
    prog = Program(cell, devices)
    spans = Spans()
    t = time.perf_counter()
    state, sup, readings, hist = prog.check_steps(seed, spans)
    k = len(hist)
    dt = statistics.median(h["dt"] for h in hist[1:])
    n = max(1, round(seconds / dt))
    log(f"set-up: {k} check steps in {time.perf_counter() - t:.3f}s (first "
        f"{hist[0]['dt']:.3f}s, then {dt:.4f}s); window of {n} steps")

    trace_dir = OUT / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    spans.recording = True
    with CompileCounter() as compiles:
        t_start = time.perf_counter()
        with jax.profiler.TraceAnnotation(TR.WINDOW_SPAN):
            state, window = sup.run(state, k + n, start_step=k)
        t_end = time.perf_counter()
    spans.recording = False
    if trace:
        jax.profiler.stop_trace()
    setup_s, window_s = t_start - t0, t_end - t_start
    memory = peak_memory(devices)
    del state, sup
    log(f"window: {n} steps in {window_s:.4f}s; set-up {setup_s:.3f}s; "
        f"{compiles.count} compile events in the window")

    t = time.perf_counter()
    ref = Reference(prog.arch, prog.dims, job, devices).run(seed, k)
    log(f"reference: {k} steps in {time.perf_counter() - t:.3f}s")
    gaps = compare.gaps(readings, ref)
    log(f"loss gap of each check step (reported, not compared): {gaps['loss_steps']}")
    nonfinite = sum(not math.isfinite(h["loss"]) for h in hist + window)
    checks = compare.report(gaps, cell.limits)
    checks["compiles_in_window"] = {"value": compiles.count, "limit": 0}
    checks["nonfinite_losses"] = {"value": nonfinite, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    tokens_per_step = job["global_batch"] * job["seq_len"]
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    result = {"correct": correct, "attempted": k + n, "failed": nonfinite}
    if trace:
        reduced = TR.reduce(TR.load(str(trace_dir)))
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        record = RunRecord(
            steps=n, tokens_per_step=tokens_per_step, window_s=window_s, chips=len(devices),
            useful_flops_per_token=prog.arch.useful_flops_per_token(prog.dims, job["seq_len"]),
            peak=peak, spans=dict(spans.durations), device=reduced)
        metrics = {}
        for m in cell.per_layer:
            value = metric_reader(m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        values = {"train_tokens_per_s": tokens_per_step * n / window_s, "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    result["checks"] = checks
    return result

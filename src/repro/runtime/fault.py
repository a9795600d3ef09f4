"""Fault tolerance: checkpoint/restart supervision, failure injection,
straggler detection.

At 1000+ nodes the dominant failure mode is a lost worker: the supervisor
(a) checkpoints every K steps (async, atomic rename), (b) on failure restores
the latest checkpoint and replays the deterministic data stream from the
saved step, and (c) watches per-step wall time against an EMA to flag
stragglers (on a real fleet this triggers hot-spare swap / re-slicing; here
the hook records and optionally calls a user callback).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable

import jax

from repro.checkpoint import latest_step, restore, save


class InjectedFailure(RuntimeError):
    pass


@dataclass
class FailureInjector:
    """Deterministically fail at given steps (tests) or with probability p."""
    fail_at_steps: tuple[int, ...] = ()
    seen: set = field(default_factory=set)

    def check(self, step: int):
        if step in self.fail_at_steps and step not in self.seen:
            self.seen.add(step)
            raise InjectedFailure(f"injected failure at step {step}")


@dataclass
class StragglerMonitor:
    """EMA step-time watchdog. threshold x EMA -> straggler event."""
    ema: float | None = None
    beta: float = 0.9
    threshold: float = 3.0
    events: list = field(default_factory=list)
    on_straggler: Callable[[int, float, float], None] | None = None

    def observe(self, step: int, dt: float) -> bool:
        is_straggler = self.ema is not None and dt > self.threshold * self.ema
        if is_straggler:
            self.events.append((step, dt, self.ema))
            if self.on_straggler:
                self.on_straggler(step, dt, self.ema)
        # don't poison the EMA with the straggler sample
        sample = min(dt, (self.ema or dt) * self.threshold)
        self.ema = sample if self.ema is None else self.beta * self.ema + (1 - self.beta) * sample
        return is_straggler


@dataclass
class TrainSupervisor:
    """Run the train loop with checkpoint/restart fault tolerance."""
    step_fn: Callable           # (state, batch) -> (state, metrics)
    pipeline: Any               # .next_batch(step)
    ckpt_dir: str
    ckpt_every: int = 50
    max_restarts: int = 10
    monitor: StragglerMonitor = field(default_factory=StragglerMonitor)
    injector: FailureInjector | None = None
    async_ckpt: bool = True

    def run(self, state, n_steps: int, start_step: int = 0):
        """Steps ``start_step`` .. ``n_steps - 1``. Each iteration is a
        ``train`` step in the profiler's trace, and its host phases are
        spans on the trace's clock: ``train.next_batch``, ``train.sync``
        (the step's dispatch and the wait for its result), ``train.record``
        (metrics to host floats, the straggler monitor) and
        ``train.checkpoint`` (only when a save starts). With no profiler
        running they are no-ops."""
        history = []
        step = start_step
        restarts = 0
        pending = None
        while step < n_steps:
            try:
                with jax.profiler.StepTraceAnnotation("train", step_num=step):
                    with jax.profiler.TraceAnnotation("train.next_batch"):
                        batch = self.pipeline.next_batch(step)
                    if self.injector:
                        self.injector.check(step)
                    t0 = time.monotonic()
                    # block: dt is the step's device time, not its dispatch
                    with jax.profiler.TraceAnnotation("train.sync"):
                        state, metrics = jax.block_until_ready(self.step_fn(state, batch))
                    dt = time.monotonic() - t0
                    with jax.profiler.TraceAnnotation("train.record"):
                        self.monitor.observe(step, dt)
                        history.append({"step": step, "dt": dt, **{
                            k: float(v) for k, v in metrics.items()
                        }})
                    step += 1
                    if self.ckpt_every and step % self.ckpt_every == 0:
                        if pending is not None and not self.async_ckpt:
                            pending = None
                        with jax.profiler.TraceAnnotation("train.checkpoint"):
                            pending = save(
                                state, self.ckpt_dir, step,
                                blocking=not self.async_ckpt,
                                metadata={"step": step},
                            )
            except InjectedFailure:
                restarts += 1
                if restarts > self.max_restarts:
                    raise
                last = latest_step(self.ckpt_dir)
                if last is None:
                    step = start_step
                    continue  # restart from scratch (state unchanged = rebuilt upstream)
                state, _ = restore(self.ckpt_dir, last, state)
                step = last
        if pending is not None and hasattr(pending, "result"):
            pending.result()
        return state, history

"""Faults planted in the program's timed path, to show that the comparison
catches them (``tests/chipbench/test_faults.py``, ``chipbench/calibrate.py``).

- ``unchanged``: the optimizer returns the state it was given;
- ``half_batch``: the loss is the mean over the first half of the rows;
- ``no_exchange``: the FSDP gathers exchange nothing, each device tiling its
  own shard (cells on several chips);
- ``shift_targets``: the pipeline hands out the tokens as their own targets.

Each patches a module attribute of the program for the ``with`` block; a
step built inside the block is traced with the fault.
"""
from __future__ import annotations

from contextlib import contextmanager

import jax
import jax.numpy as jnp

FAULTS = ("unchanged", "half_batch", "no_exchange", "shift_targets")


def _patches(fault: str):
    from repro.core import collectives
    from repro.data.pipeline import SyntheticPipeline
    from repro.models import model_builder
    from repro.optim import adamw

    if fault == "unchanged":
        def apply_updates(params, grads, opt, tc):
            return params, opt, {"grad_norm": jnp.zeros(()), "lr": jnp.zeros(())}
        return [(adamw, "apply_updates", apply_updates)]
    if fault == "half_batch":
        xent = model_builder.chunked_xent

        def chunked_xent(hidden, head, targets, *args, **kw):
            half = hidden.shape[0] // 2
            return xent(hidden[:half], head, targets[:half], *args, **kw)
        return [(model_builder, "chunked_xent", chunked_xent)]
    if fault == "no_exchange":
        def gather(x, axis, **kw):
            return jnp.concatenate([x] * jax.lax.axis_size(axis), axis=0)
        return [(collectives, name, gather) for name in
                ("ring_allgather_local", "bidi_ring_allgather_local", "bcast_allgather_local")]
    if fault == "shift_targets":
        next_batch = SyntheticPipeline.next_batch

        def shifted(self, step):
            batch = next_batch(self, step)
            return dict(batch, targets=batch["tokens"])
        return [(SyntheticPipeline, "next_batch", shifted)]
    raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")


@contextmanager
def planted(fault: str):
    patches = _patches(fault)
    saved = [(obj, name, getattr(obj, name)) for obj, name, _ in patches]
    try:
        for obj, name, new in patches:
            setattr(obj, name, new)
        yield
    finally:
        for obj, name, old in saved:
            setattr(obj, name, old)

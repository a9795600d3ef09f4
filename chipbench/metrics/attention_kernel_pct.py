"""Attention kernel: the share, in %, of the ``attention`` scope's device self
time (forward, remat recompute and backward together, over the window,
averaged over the devices) that the fused attention kernel's own
instructions take; the rest is the norm, QKV, RoPE, the layout changes
around the kernel, the output projection and the residual add.

The share has no good direction on its own: it rises as the work around the
kernel shrinks, but also as the kernel slows, and falls as the kernel
speeds up. Read it with ``attention_ms_per_step``; the kernel's own ms per
step is logged beside it.

An instruction is the kernel's where a component of its ``op_name`` path,
bare or inside transforms, is the name a Splash attention kernel gives
itself (``splash_<mha|mqa>_<fwd|dq|dkv>[_segmented]_<residuals|no_residuals>``,
``get_kernel_name`` in ``jax.experimental.pallas.ops.tpu.splash_attention``):
the kernel wraps its ``pallas_call`` in a ``named_scope`` of that name, so
the Mosaic custom call and the reads of its outputs carry it. The kernel's
time is the scope's time in ``scopes.reduce`` less the scope's time once the
kernel's instructions lose their ``op_name``. None where the trace is not
this run's or holds no such instruction (a program that runs attention as
scans). ``scopes.for_run`` keeps only its reduction, so the reader loads
the trace once more for the instructions' names.
"""
import dataclasses
import os
import re

from chipbench import scopes

KERNEL = re.compile(
    r"^(?:[\w.\-]+\()*splash_(?:mha|mqa)_(?:fwd|dq|dkv)(?:_segmented)?"
    r"_(?:residuals|no_residuals)\)*$")


def is_kernel(op_name: str | None) -> bool:
    return bool(op_name) and any(KERNEL.match(c) for c in op_name.split("/"))


def split(tr: scopes.Scoped, red: dict) -> tuple[float, float]:
    """(kernel, scope): seconds of self time, averaged over the devices, of
    the kernel's instructions in the ``attention`` scope and of the whole
    scope, in the window of ``tr``; ``red`` is ``scopes.reduce(tr)``."""
    bare = dataclasses.replace(
        tr, op_names={n: op for n, op in tr.op_names.items() if not is_kernel(op)})
    scope = scopes.scope_s(red, "attention")
    return scope - scopes.scope_s(scopes.reduce(bare), "attention"), scope


def kernel_pct(tr: scopes.Scoped, red: dict | None = None) -> float | None:
    """The kernel's share of the ``attention`` scope's self time in the
    window of ``tr``, in %; None where the kernel took none of it."""
    kernel, scope = split(tr, scopes.reduce(tr) if red is None else red)
    return 100 * kernel / scope if kernel > 0 else None


def read(run):
    from chipbench.harness import OUT, log

    red = scopes.for_run(run)
    if red is None or not run.steps:  # no trace, or not this run's
        return None
    tr = scopes.load(os.path.dirname(scopes.newest(OUT / "trace")))
    kernel, scope = split(tr, red)
    if kernel <= 0:
        return None
    log(f"  attention kernel {1e3 * kernel / run.steps:.3f} ms per step of the scope's "
        f"{1e3 * scope / run.steps:.3f}")
    return 100 * kernel / scope

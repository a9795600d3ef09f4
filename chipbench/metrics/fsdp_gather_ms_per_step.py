"""FSDP gathers: device self time of the ops in the program's ``fsdp_gather``
scope (the explicit per-layer weight gathers of ``sharding/ctx.py``) per
window step, averaged over the devices. Forward, remat recompute (the
re-gather for the backward pass) and backward (the gather's AD transpose,
the ring reduce-scatter of the gradients) together, split in the log. Self
time is what the device spends in the gathers' own ops (starting and
awaiting the transfers, placing the received blocks), not the transfers
that run behind other work. None where the trace names no such scope
(``fsdp_mode = xla``, one chip)."""
from chipbench import scopes


def read(run):
    from chipbench.harness import log

    red = scopes.for_run(run)
    if red is None or not run.steps or scopes.scope_s(red, "fsdp_gather") <= 0:
        return None
    split = red["scopes"]["fsdp_gather"]
    log("  fsdp_gather ms per step: " + ", ".join(
        f"{k} {1e3 * split[k] / run.steps:.3f}" for k in scopes.PASSES))
    return 1e3 * scopes.scope_s(red, "fsdp_gather") / run.steps

"""Attention: device self time of the ops in the program's ``attention``
scope (norm, QKV, RoPE, blockwise attention, output projection and the
residual add; forward, remat recompute and backward), per window step,
averaged over the devices. None where the trace names no such scope."""
from chipbench import scopes


def read(run):
    red = scopes.for_run(run)
    if red is None or not run.steps or scopes.scope_s(red, "attention") <= 0:
        return None
    return 1e3 * scopes.scope_s(red, "attention") / run.steps

"""Train step: device self time of the ops in none of the program's scopes,
per window step, averaged over the devices; work moved out of a scope shows
here. None where the trace names no scope at all (a program without them)."""
from chipbench import scopes


def read(run):
    red = scopes.for_run(run)
    if red is None or not run.steps or not any(scopes.scope_s(red, s) > 0
                                               for s in scopes.SCOPES):
        return None
    return 1e3 * red["unscoped_s"] / run.steps

"""Train loop: host time per window step in the loop's own spans
``train.next_batch``, ``train.record`` and ``train.checkpoint``, read from
the profiler trace on the clock the device events share. The loop waits on
each step, so the device waits through these. None where the trace holds
none of them (a program without them)."""
from chipbench import scopes

SPANS = ("train.next_batch", "train.record", "train.checkpoint")


def read(run):
    red = scopes.for_run(run)
    if red is None or not run.steps or not any(red["loop_s"][s] > 0 for s in SPANS):
        return None
    return 1e3 * sum(red["loop_s"][s] for s in SPANS) / run.steps

"""FSDP gathers: device time of collective ops (all-gather, reduce-scatter,
all-reduce, collective-permute, all-to-all) per window step, per device,
averaged over the devices. None where the step runs no collective."""


def read(run):
    if run.device is None or not run.steps or run.device["collective_s"] <= 0:
        return None
    return 1e3 * run.device["collective_s"] / run.steps

"""FSDP gathers: share of the collective ops' device time during which no
other op ran on that device. None where the step runs no collective."""


def read(run):
    if run.device is None or run.device["collective_s"] <= 0:
        return None
    return 100.0 * run.device["exposed_collective_s"] / run.device["collective_s"]

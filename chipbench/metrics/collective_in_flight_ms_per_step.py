"""FSDP gathers: time per window step in which a collective is in flight on a
device (the union of the intervals from each ``*-start`` op to the
``*-done`` that closes it, and of synchronous collectives;
``chipbench/flight.py``), averaged over the devices. The device may compute
through it. None where the trace is not this run's or holds no collective."""
from chipbench import flight


def read(run):
    got = flight.for_run(run)
    if got is None or not run.steps or got[1]["in_flight_s"] <= 0:
        return None
    return 1e3 * got[1]["in_flight_s"] / run.steps

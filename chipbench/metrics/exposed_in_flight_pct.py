"""FSDP gathers: the share, in %, of the collectives' in-flight time
(``collective_in_flight_ms_per_step``) in which the device waits: runs a
collective op as its innermost op (a ``*-done`` waiting on its transfer, a
synchronous collective) or runs nothing. The rest is hidden behind compute.
None where the trace is not this run's or holds no collective."""
from chipbench import flight


def read(run):
    got = flight.for_run(run)
    if got is None or got[1]["in_flight_s"] <= 0:
        return None
    return 100.0 * got[1]["waiting_s"] / got[1]["in_flight_s"]

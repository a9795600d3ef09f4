"""FSDP gathers: the share, in %, of the chips' inter-chip bandwidth that the
per-layer weight traffic of ZeRO-3 fills while collectives are in flight:
``bytes / (in_flight_s x ici_bytes_per_s)`` per window step.

``bytes`` is ZeRO-3's count for the cell's configuration
(``chipbench/zero3.py``: two gathers and one reduce-scatter of the stacked
layer weights, (P-1)/P of them through each chip), fixed by the shapes and
not by how the program gathers. ``in_flight_s`` is the union, on each
device, of every collective's in-flight interval (``chipbench/flight.py``),
averaged over the devices, per window step. The time counts every
collective and the bytes only the layer weights', so the share is a lower
bound on the links' use and cannot pass 100 % at the peak of
``peaks.json``. None where the trace is not this run's, or nothing is in
flight in the window.
"""
from chipbench import flight, zero3


def read(run):
    from chipbench import cell as C
    from chipbench.harness import log

    got = flight.for_run(run)
    if got is None or not run.steps:
        return None
    cell, red = got
    sent = zero3.bytes_per_step(C.load(cell).config)
    in_flight = red["in_flight_s"] / run.steps
    if not sent or in_flight <= 0:
        return None
    log(f"  ZeRO-3 layer traffic {sent} bytes per chip per step, {sent / in_flight / 1e9:.3f} "
        f"GB/s while collectives are in flight")
    return 100.0 * sent / (in_flight * run.peak["ici_bytes_per_s"])

"""Readings that a cell's limits are set from, in one process on its chips.

    python3 chipbench/calibrate.py --workload <cell> --seeds 11,12,... \
        [--control-seeds 11,12,13] [--fault-seeds 11,12,13] \
        [--faults half_batch,shift_targets,no_exchange] [--out readings.jsonl]

For each seed the program takes the cell's first ``check_steps`` steps
through the same set-up path as a run (``harness.Program.check_steps``),
and the float32 reference follows them. The control is the reference in
float8 (``precision="fp8"``) put in the program's place; a fault is planted
in the program (``faults.py``). Each reading is one JSON line of the three
compared numbers; the last line sums them up: the largest of the program's
(the lower reading) and the smallest of the control's and of each fault's.
No measured window is run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _ints(s: str) -> list[int]:
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--fault-seeds", type=_ints, default=[])
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from chipbench import cell as C
    from chipbench import compare
    from chipbench.faults import planted
    from chipbench.harness import Program, Spans, log
    from chipbench.reference import Reference
    from repro.launch.compile_cache import enable_compile_cache

    cell = C.load(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chips, found "
              f"{len(devices)} {devices[0].platform} devices", file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    out = open(args.out, "a") if args.out else None
    k = cell.traffic["check_steps"]
    lines: list[dict] = []

    def emit(kind: str, seed: int, gaps: dict, seconds: float) -> None:
        rec = {"cell": cell.name, "kind": kind, "seed": seed, **gaps,
               "seconds": round(seconds, 3)}
        lines.append(rec)
        print(json.dumps(rec), flush=True)
        if out:
            out.write(json.dumps(rec) + "\n")
            out.flush()

    prog = Program(cell, devices)
    ref = Reference(prog.arch, prog.dims, cell.traffic, devices)
    ctrl = Reference(prog.arch, prog.dims, cell.traffic, devices, precision="fp8") \
        if args.control_seeds else None
    refs = {}
    for seed in dict.fromkeys(args.seeds + args.control_seeds + args.fault_seeds):
        t = time.perf_counter()
        r = ref.run(seed, k)
        refs[seed] = r
        if seed in args.seeds:
            state, sup, readings, _ = prog.check_steps(seed, Spans())
            del state, sup
            emit("program", seed, compare.gaps(readings, r), time.perf_counter() - t)
        if seed in args.control_seeds:
            t = time.perf_counter()
            emit("control", seed, compare.gaps(ctrl.run(seed, k), r), time.perf_counter() - t)
    for fault in [f for f in args.faults.split(",") if f]:
        with planted(fault):
            broken = Program(cell, devices)
            for seed in args.fault_seeds:
                t = time.perf_counter()
                state, sup, readings, _ = broken.check_steps(seed, Spans())
                del state, sup
                emit(fault, seed, compare.gaps(readings, refs[seed]), time.perf_counter() - t)
        del broken

    summary = {"cell": cell.name, "kind": "summary"}
    for kind in dict.fromkeys(rec["kind"] for rec in lines):
        recs = [rec for rec in lines if rec["kind"] == kind]
        pick = max if kind == "program" else min
        summary[kind] = {n: pick(rec[n] for rec in recs) for n in compare.NUMBERS}
        summary[kind]["seeds"] = len(recs)
    log(json.dumps(summary))
    print(json.dumps(summary), flush=True)
    if out:
        out.write(json.dumps(summary) + "\n")
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

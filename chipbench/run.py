"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``. The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared with its limit, also printed as the last
lines of standard error. Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import cell as C

    cell = C.load(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: no TPU (JAX found {devices[0].platform}); nothing was run",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    peaks = json.loads((C.HERE / "peaks.json").read_text())["devices"]
    if devices[0].device_kind not in peaks:
        print(f"chipbench: no peaks for device kind {devices[0].device_kind!r} in "
              f"chipbench/peaks.json", file=sys.stderr)
        return 2

    from chipbench.harness import log, run_cell
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # every program, however quick to compile, goes into the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"{args.workload}: {devices[0].device_kind} x{len(devices)}, jax {jax.__version__}, "
        f"compile cache {cache_dir}")
    result = run_cell(cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                      devices=devices, peak=peaks[devices[0].device_kind], t0=T0)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Parse collective traffic out of lowered/compiled HLO text.

cost_analysis() has FLOPs and memory bytes but no collective traffic, so the
roofline's third term comes from here: every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute instruction is converted to
ring-equivalent *per-device ICI bytes*:

    all-gather        (g-1)/g * result_bytes      (result = gathered buffer)
    reduce-scatter    (g-1)   * result_bytes      (input = g * result)
    all-reduce        2 (g-1)/g * result_bytes    (RS + AG)
    all-to-all        (g-1)/g * result_bytes
    collective-permute          result_bytes

The reported "collective_bytes" is the total over devices (per-device x
group-participating devices), matching the roofline convention
T_coll = collective_bytes / (chips * link_bw).
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# the result is the text between "=" and the op: a TPU layout such as
# {1,0:T(8,128)(2,1)} holds parentheses, so it is not matched as a tuple
_COLL_RE = re.compile(
    r"=\s*(.+?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _start_result_bytes(text: str) -> int:
    """Bytes of what an all-gather- or collective-permute-start delivers:
    its result tuple holds its operands, then its results, then scalar
    u32[] contexts; the second half of the array shapes are the results."""
    shapes = [m.group(0) for m in _SHAPE_RE.finditer(text) if m.group(2)]
    return _shape_bytes(" ".join(shapes[len(shapes) // 2:]))


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        # replica_groups=[n_groups, group_size]<=[...]
        return int(m.group(2))
    return default


@dataclass
class CollectiveStats:
    per_device_bytes: dict[str, int] = field(default_factory=lambda: defaultdict(int))
    total_bytes: int = 0                 # summed over participating devices
    counts: dict[str, int] = field(default_factory=lambda: defaultdict(int))

    def as_dict(self) -> dict:
        return {
            "per_device_bytes": dict(self.per_device_bytes),
            "per_device_total": sum(self.per_device_bytes.values()),
            "total_bytes": self.total_bytes,
            "counts": dict(self.counts),
        }


_OPNAME_RE = re.compile(r'op_name="([^"]*)"')


def _loop_multiplier(line: str, loop_chain: tuple[int, ...]) -> int:
    """XLA cost/HLO text counts while-loop bodies ONCE; collectives inside the
    layer scan (and grad-accum scan) execute trip_count times. The op_name
    metadata preserves the traced scope ("jit(f)/while/body/..."), so the
    nesting depth tells us how many loops enclose the op; the caller passes
    the known loop-length chain outermost-first (e.g. (grad_accum, n_layers)).
    """
    m = _OPNAME_RE.search(line)
    if not m:
        return 1
    depth = m.group(1).count("while/body")
    mult = 1
    for k in range(min(depth, len(loop_chain))):
        mult *= loop_chain[k]
    return mult


def collective_stats(hlo_text: str, n_devices: int,
                     loop_chain: tuple[int, ...] = ()) -> CollectiveStats:
    st = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        result_txt, op, started = m.group(1), m.group(2), m.group(3)
        if started and op in ("all-gather", "collective-permute"):
            rbytes = _start_result_bytes(result_txt)
        else:
            rbytes = _shape_bytes(result_txt)
        g = max(_group_size(line, n_devices), 1)
        if op == "all-gather":
            per_dev = rbytes * (g - 1) // max(g, 1)
        elif op == "reduce-scatter":
            per_dev = rbytes * (g - 1)
        elif op == "all-reduce":
            per_dev = 2 * rbytes * (g - 1) // max(g, 1)
        elif op == "all-to-all":
            per_dev = rbytes * (g - 1) // max(g, 1)
        else:  # collective-permute
            per_dev = rbytes
        per_dev *= _loop_multiplier(line, loop_chain)
        st.per_device_bytes[op] += per_dev
        st.total_bytes += per_dev * g if op != "collective-permute" else per_dev * n_devices
        st.counts[op] += 1
    return st


def scan_trip_counts(hlo_text: str) -> list[int]:
    """While-loop trip counts (scan lengths) — collectives inside loops execute
    trip_count times; used to scale per-iteration collective bytes."""
    return [int(x) for x in re.findall(r'trip_count[":\s=]+(\d+)', hlo_text)]

"""From a profiler trace to the device's busy and idle time, its collective
time, and the host span each idle gap fell in.

``load`` reads the ``.xplane.pb`` a ``jax.profiler`` trace writes into a
plain ``Trace``; ``reduce`` works on that alone, so it can be checked on a
small trace written by hand (``tests/chipbench/test_trace_reduce.py``).
Times are nanoseconds on the trace's own clock, which host and device
events share.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW_SPAN = "chipbench.window"
HOST_SPANS = ("next_batch", "dispatch", "wait")
OPS_LINE = "XLA Ops"
_COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast)")

Interval = tuple[int, int]


@dataclass
class Trace:
    # device id -> [(op name, start, end)]
    device_ops: dict[int, list[tuple[str, int, int]]] = field(default_factory=dict)
    # [(span name, start, end)] of the harness's host spans
    host_spans: list[tuple[str, int, int]] = field(default_factory=list)


def union(intervals) -> list[Interval]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """The parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def is_collective(op_name: str) -> bool:
    return bool(_COLLECTIVE.match(op_name))


def window(trace: Trace) -> Interval:
    spans = [(s, e) for n, s, e in trace.host_spans if n == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span in the trace, found {len(spans)}")
    return spans[0]


def label(gap: Interval, host_spans) -> str:
    """The harness span that overlaps the gap most, else "host"."""
    best, name = 0, "host"
    for n, s, e in host_spans:
        if n == WINDOW_SPAN:
            continue
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best:
            best, name = ov, n
    return name


def reduce(trace: Trace, top: int = 10) -> dict:
    """Device time in the window, averaged over the devices, in seconds:
    ``busy_s``, ``window_s``, ``collective_s`` and ``exposed_collective_s``
    (collective time during which no other op ran on that device); plus the
    ``top`` ops by time and the ``top`` longest idle gaps, each labelled with
    the host span it fell in."""
    if not trace.device_ops:
        raise ValueError("the trace holds no device ops")
    lo, hi = window(trace)
    n = len(trace.device_ops)
    busy = coll = exposed = 0
    op_time: dict[str, int] = defaultdict(int)
    gaps: list[tuple[int, str]] = []
    for ops in trace.device_ops.values():
        inside = [(name, *iv) for name, s, e in ops for iv in clip([(s, e)], lo, hi)]
        merged = union((s, e) for _, s, e in inside)
        busy += total(merged)
        c = union((s, e) for name, s, e in inside if is_collective(name))
        other = union((s, e) for name, s, e in inside if not is_collective(name))
        coll += total(c)
        exposed += total(subtract(c, other))
        for name, s, e in inside:
            op_time[name] += e - s
        idle = subtract([(lo, hi)], merged)
        gaps += [(e - s, label((s, e), trace.host_spans)) for s, e in idle]
    gaps.sort(reverse=True)
    ops_sorted = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n / 1e9,
        "collective_s": coll / n / 1e9,
        "exposed_collective_s": exposed / n / 1e9,
        "devices": n,
        "device_ops": [[name, t / n / 1e9] for name, t in ops_sorted],
        "idle_gaps": [[name, t / 1e9] for t, name in gaps[:top]],
    }


def load(log_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``log_dir``: ops of the ``XLA Ops``
    line of each ``/device:TPU:<n>`` plane, and the harness's host spans."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(files[-1])
    out = Trace()
    wanted = set(HOST_SPANS) | {WINDOW_SPAN}
    for plane in data.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                out.device_ops.setdefault(int(m.group(1)), []).extend(
                    (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                    for ev in line.events)
            elif plane.name.startswith("/host:"):
                out.host_spans.extend(
                    (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                    for ev in line.events if ev.name in wanted)
    return out


def describe(log_dir: str, max_names: int = 8) -> list[str]:
    """Plane and line names with event counts and a few event names: what to
    look at before trusting ``load`` on a new trace."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    data = ProfileData.from_file(files[-1])
    lines = []
    for plane in data.planes:
        lines.append(f"plane {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({ev.name for ev in evs})[:max_names]
            first = min((ev.start_ns for ev in evs), default=None)
            lines.append(f"  line {line.name!r}: {len(evs)} events, first at {first}, "
                         f"names {names}")
    return lines

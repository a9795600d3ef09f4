"""From a traced window to each layer's device self time and the train
loop's host time.

The program names the layers of its train step with ``jax.named_scope``
(``SCOPES``). XLA keeps the scope in the ``op_name`` metadata of every
instruction it became, in the backward pass (``transpose(...)`` in the
path) and the remat recompute (``rematted_computation``) too. Its train
loop writes the ``train.*`` spans (``LOOP_SPANS``) into the profiler's
trace, on the clock the device events share.

On a TPU the ``XLA Ops`` line nests each loop's body under its ``while``
event, so summing events counts the body twice or more. ``self_times``
gives each instant of an op's interval to the innermost op running then,
which makes the self times of a device add up to its busy time exactly.
Each instruction's self time goes to the innermost scope in its
``op_name``, or to ``unscoped``.

``load`` reads the newest ``.xplane.pb`` under a directory: the device ops
by HLO instruction, the ``op_name`` of each instruction from the HLO of the
executable that ran, which the trace's ``/host:metadata`` plane carries,
and the host spans. That HLO is the executable's as compiled; the program's
compile cache keys on the metadata (``repro/launch/compile_cache.py``), so
it is never an older program's. ``reduce`` works on the ``Scoped`` that
``load`` returns alone, so it is checked on traces written by hand
(``tests/chipbench/test_scopes.py``). ``for_run`` is what the metric
readers call.
"""
from __future__ import annotations

import glob
import heapq
import os
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field

from chipbench import trace as T

SCOPES = ("attention", "mlp", "embed", "xent", "optimizer", "fsdp_gather")
LOOP_SPANS = ("train.next_batch", "train.sync", "train.record", "train.checkpoint")
STEP_SPAN = "train"
MODULES_LINE = "XLA Modules"
# the stat that carries a module's HloProto in the metadata plane
HLO_PROTO_STAT = "Hlo Proto"
PASSES = ("forward", "recompute", "backward")
# a path component that names a scope, bare or inside transforms: "mlp",
# "jvp(xent)", "transpose(jvp(embed))"
_COMPONENT = re.compile(r"^(?:[\w.\-]+\()*([\w.\-]+)\)*$")


@dataclass
class Scoped:
    # device id -> [(HLO instruction, start, end)]
    device_ops: dict[int, list[tuple[str, int, int]]] = field(default_factory=dict)
    # HLO instruction -> its op_name
    op_names: dict[str, str] = field(default_factory=dict)
    # [(span name, start, end)]: the window, the harness's and the train loop's spans
    host_spans: list[tuple[str, int, int]] = field(default_factory=list)


def scope_of(op_name: str | None) -> tuple[str | None, str]:
    """The innermost scope named in an ``op_name`` path (None if none), and
    the pass the instruction belongs to."""
    if not op_name:
        return None, "forward"
    scope = None
    for comp in op_name.split("/"):
        m = _COMPONENT.match(comp)
        if m and m.group(1) in SCOPES:
            scope = m.group(1)
    if "rematted_computation" in op_name:
        return scope, "recompute"
    return scope, "backward" if "transpose(" in op_name else "forward"


def self_times(events) -> list[int]:
    """Each event's self time: the instants of its interval at which it is the
    innermost event running, the one that started last (the shorter one on a
    tie). The self times add up to the union of the intervals."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    out = [0] * len(events)
    active: list[tuple[int, int, int]] = []  # heap of (-start, end, index)
    t = None
    for i in order + [None]:
        nxt = None if i is None else events[i][0]
        # hand the time up to the next start to the innermost running event
        while active and (nxt is None or t < nxt):
            _, end, j = active[0]
            if end <= t:
                heapq.heappop(active)
                continue
            upto = end if nxt is None else min(end, nxt)
            out[j] += upto - t
            t = upto
        if i is None:
            return out
        t = nxt
        heapq.heappush(active, (-events[i][0], events[i][1], i))
    return out


def reduce(tr: Scoped, top: int = 10) -> dict:
    """Seconds in the window, averaged over the devices: ``busy_s``, the self
    time of each scope split by pass (``scopes``), ``unscoped_s`` and the
    ``top`` unscoped instructions; per host span name, the summed time of
    the train loop's spans inside the window (``loop_s``) and how many steps
    it ran (``steps``); and the ``top`` longest idle gaps, each named by the
    harness span it overlaps most, else by the innermost train loop span it
    falls in, else "host"."""
    if not tr.device_ops:
        raise ValueError("the trace holds no device ops")
    trace = T.Trace(host_spans=tr.host_spans)
    lo, hi = T.window(trace)
    n = len(tr.device_ops)
    busy = 0
    scopes = {s: dict.fromkeys(PASSES, 0) for s in SCOPES}
    unscoped: dict[str, int] = defaultdict(int)
    gaps: list[tuple[int, str]] = []
    for ops in tr.device_ops.values():
        inside = [(name, *iv) for name, s, e in ops for iv in T.clip([(s, e)], lo, hi)]
        merged = T.union((s, e) for _, s, e in inside)
        busy += T.total(merged)
        for (name, _, _), t in zip(inside, self_times([(s, e) for _, s, e in inside])):
            scope, kind = scope_of(tr.op_names.get(name))
            if scope is None:
                unscoped[name] += t
            else:
                scopes[scope][kind] += t
        gaps += [(e - s, label((s, e), tr.host_spans)) for s, e in T.subtract([(lo, hi)], merged)]
    loop = defaultdict(int)
    steps = 0
    for name, s, e in tr.host_spans:
        if name in LOOP_SPANS:
            loop[name] += T.total(T.clip([(s, e)], lo, hi))
        elif name == STEP_SPAN and lo <= s and e <= hi:
            steps += 1
    gaps.sort(reverse=True)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n / 1e9,
        "devices": n,
        "scopes": {s: {k: v / n / 1e9 for k, v in p.items()} for s, p in scopes.items()},
        "unscoped_s": sum(unscoped.values()) / n / 1e9,
        "unscoped_top": [[name, t / n / 1e9] for name, t in
                         sorted(unscoped.items(), key=lambda kv: -kv[1])[:top]],
        "loop_s": {name: loop[name] / 1e9 for name in LOOP_SPANS},
        "steps": steps,
        "idle_gaps": [[name, t / 1e9] for t, name in gaps[:top]],
    }


def scope_s(reduced: dict, scope: str) -> float:
    return sum(reduced["scopes"][scope].values())


def label(gap: T.Interval, host_spans) -> str:
    """The harness span that overlaps the gap most, as ``trace.label`` names
    it; where none does, the train loop span that overlaps it most (the
    shorter, inner one on a tie); else "host"."""
    name = T.label(gap, [sp for sp in host_spans if sp[0] in T.HOST_SPANS])
    if name != "host":
        return name
    best = (0, 0)
    for n, s, e in host_spans:
        if n in LOOP_SPANS or n == STEP_SPAN:
            key = (min(e, gap[1]) - max(s, gap[0]), -(e - s))
            if key[0] > 0 and key > best:
                best, name = key, n
    return name


# ---------------------------------------------------------------- reading


def _varint(buf, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def proto_fields(buf):
    """(field number, value) of each field of a serialized protobuf message:
    an int for varints, a memoryview for everything else."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, v


def module_op_names(module_proto) -> dict[str, str]:
    """The ``op_name`` of each instruction of a serialized ``HloModuleProto``
    (computations = 3; instruction = 2 of a computation; name = 1 and
    metadata = 7 of an instruction; op_name = 2 of the metadata)."""
    out = {}
    for num, comp in proto_fields(module_proto):
        if num != 3:
            continue
        for num2, ins in proto_fields(comp):
            if num2 != 2:
                continue
            name = op_name = None
            for num3, v in proto_fields(ins):
                if num3 == 1:
                    name = bytes(v).decode()
                elif num3 == 7:
                    op_name = next((bytes(w).decode() for k, w in proto_fields(v) if k == 2),
                                   None)
            if name and op_name:
                out[name] = op_name
    return out


def metadata_hlo_modules(xspace) -> dict[str, bytes]:
    """The serialized ``HloModuleProto`` of each module in the ``/host:metadata``
    plane of a serialized ``XSpace``, by the name the ``XLA Modules`` line
    gives it (``jit_train_step(<program id>)``): the ``Hlo Proto`` stat (an
    ``HloProto``, whose field 1 is the module) of each event metadata."""
    modules = {}
    for num, plane in proto_fields(xspace):
        if num != 1:  # XSpace.planes
            continue
        fields = list(proto_fields(plane))
        if next((bytes(v).decode() for k, v in fields if k == 2), "") != "/host:metadata":
            continue
        stat_names = {}
        for k, entry in fields:
            if k == 5:  # XPlane.stat_metadata: map<int64, XStatMetadata>
                meta = dict(proto_fields(entry)).get(2)
                if meta is not None:
                    m = dict(proto_fields(meta))
                    stat_names[m.get(1, 0)] = bytes(m.get(2, b"")).decode()
        for k, entry in fields:
            if k != 4:  # XPlane.event_metadata: map<int64, XEventMetadata>
                continue
            meta = list(proto_fields(dict(proto_fields(entry)).get(2, b"")))
            name = next((bytes(v).decode() for k2, v in meta if k2 == 2), "")
            for k2, stat in meta:
                if k2 != 5:  # XEventMetadata.stats
                    continue
                st = dict(proto_fields(stat))
                if stat_names.get(st.get(1)) == HLO_PROTO_STAT and 6 in st:  # bytes_value
                    module = dict(proto_fields(st[6])).get(1)
                    if module is not None:
                        modules[name] = bytes(module)
    return modules


def newest(log_dir) -> str:
    files = sorted(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(log_dir) -> Scoped:
    """The newest ``.xplane.pb`` under ``log_dir``: the ops of the ``XLA Ops``
    line of each ``/device:TPU:<n>`` plane by HLO instruction (an event's
    name is the instruction's text, ``%fusion.598 = ...``), the ``op_name``
    of each instruction of the module the ``XLA Modules`` line ran longest,
    and the window's, the harness's and the train loop's host spans."""
    from jax.profiler import ProfileData

    with open(newest(log_dir), "rb") as f:
        raw = f.read()
    out = Scoped()
    wanted = set(T.HOST_SPANS) | set(LOOP_SPANS) | {T.WINDOW_SPAN, STEP_SPAN}
    ran: dict[str, int] = defaultdict(int)
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        for line in plane.lines:
            if m and line.name == T.OPS_LINE:
                out.device_ops.setdefault(int(m.group(1)), []).extend(
                    (ev.name.split(" = ")[0].lstrip("%"), int(ev.start_ns),
                     int(ev.start_ns + ev.duration_ns)) for ev in line.events)
            elif m and line.name == MODULES_LINE:
                for ev in line.events:
                    ran[ev.name] += int(ev.duration_ns)
            elif plane.name.startswith("/host:"):
                out.host_spans.extend(
                    (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                    for ev in line.events if ev.name in wanted)
    modules = metadata_hlo_modules(raw)
    longest = max(ran, key=ran.get, default=None)
    if longest in modules:
        out.op_names = module_op_names(modules[longest])
    return out


_CACHE: dict[tuple[str, float], dict] = {}


def for_run(run) -> dict | None:
    """The reduction of the newest trace the harness wrote, where it is this
    run's: its busy time agrees with the harness's reduction within 1 %.
    None where there is none. Logs the per-scope table once per trace."""
    from chipbench.harness import OUT, log

    if run.device is None:
        return None
    try:
        path = newest(OUT / "trace")
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()  # the readers of one run share one reduction
        _CACHE[key] = reduce(load(os.path.dirname(path)))
        for line in table(_CACHE[key], run.steps):
            log(line)
        log(f"  busy {_CACHE[key]['busy_s']:.6f}s here, {run.device['busy_s']:.6f}s in the "
            f"harness's reduction")
    red = _CACHE[key]
    if abs(red["busy_s"] - run.device["busy_s"]) > 0.01 * run.device["busy_s"]:
        return None
    return red


def table(red: dict, steps: int) -> list[str]:
    """The per-scope self times per window step, split by pass, the sum check
    and the loop's host time, as lines for the log."""
    steps = max(steps, 1)
    ms = lambda s: f"{1e3 * s / steps:.3f}"  # noqa: E731
    lines = [f"scope self time, ms per step over {steps} steps: scope forward recompute "
             f"backward total"]
    for s, p in red["scopes"].items():
        lines.append(f"  {s} {' '.join(ms(p[k]) for k in PASSES)} {ms(sum(p.values()))}")
    scoped = sum(scope_s(red, s) for s in SCOPES)
    lines.append(f"  unscoped {ms(red['unscoped_s'])}; busy {ms(red['busy_s'])}; "
                 f"scoped + unscoped over busy "
                 f"{(scoped + red['unscoped_s']) / red['busy_s'] if red['busy_s'] else 0:.6f}")
    lines.append("  largest unscoped: " + ", ".join(
        f"{n} {ms(t)}" for n, t in red["unscoped_top"]))
    lines.append("  loop spans, ms per step: " + ", ".join(
        f"{n} {ms(t)}" for n, t in red["loop_s"].items()) + f"; {red['steps']} train steps")
    lines.append("  idle gaps (s): " + ", ".join(f"{n} {t:.6f}" for n, t in red["idle_gaps"]))
    return lines


if __name__ == "__main__":
    # python3 -m chipbench.scopes <trace dir> <window steps>, from the checkout's root
    for line in table(reduce(load(sys.argv[1])), int(sys.argv[2])):
        print(line)

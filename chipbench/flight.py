"""Collectives in flight on each device of a traced window.

A TPU runs a collective as a ``*-start`` op, which hands the transfer to the
links, and a ``*-done`` op, which waits for it; the device computes in
between while the bytes move. So a collective's time is its in-flight
interval, from the start's beginning to the end of the done that closes it,
and what of that interval the device spends waiting (in the collective ops
themselves, or idle) is what compute did not hide. A synchronous
collective is in flight, and waited on, for its own interval.

``chipbench/trace.py:reduce`` counts only the collective ops' own
durations, by an event name that must begin with the op's kind; a TPU
event's name is its HLO text (``%collective-permute-done.3 = ...``). This
module reads the instructions ``scopes.load`` gives, without the text.
"""
from __future__ import annotations

import os
import re
from collections import defaultdict
from pathlib import Path

from chipbench import scopes
from chipbench import trace as T

COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast)(?:-(start|done))?(?:\.[\w.\-]*)?$")


def in_flight(ops) -> list[T.Interval]:
    """The merged intervals in which a collective is in flight on one device;
    ``ops`` are (instruction, start, end). A ``-done`` closes an open
    ``-start`` of its kind (paired by count, so no instruction name has to
    match); one with none open (its start fell before the trace) is dropped,
    and a start left open runs to the device's last op."""
    marks, spans = [], []
    for name, s, e in ops:
        m = COLLECTIVE.match(name)
        if m is None:
            continue
        kind, phase = m.groups()
        if phase == "start":
            marks.append((s, 1, kind))
        elif phase == "done":
            marks.append((e, -1, kind))
        else:
            spans.append((s, e))
    opened: dict[str, int] = defaultdict(int)
    n = begin = 0
    for t, step, kind in sorted(marks, key=lambda m: (m[0], -m[1])):
        if step < 0 and not opened[kind]:
            continue
        opened[kind] += step
        n += step
        if step > 0 and n == 1:
            begin = t
        elif n == 0:
            spans.append((begin, t))
    if n:
        spans.append((begin, max(e for _, _, e in ops)))
    return T.union(spans)


def reduce(tr: scopes.Scoped) -> dict:
    """Seconds in the window of ``tr``, averaged over the devices:
    ``in_flight_s``, the union of the collectives' in-flight intervals;
    ``waiting_s``, the part of it in which the device runs a collective op
    as its innermost op or runs nothing."""
    lo, hi = T.window(T.Trace(host_spans=tr.host_spans))
    flight = waiting = 0
    for ops in tr.device_ops.values():
        inside = [(name, *iv) for name, s, e in ops for iv in T.clip([(s, e)], lo, hi)]
        f = T.clip(in_flight(ops), lo, hi)
        flight += T.total(f)
        own = scopes.self_times([(s, e) for _, s, e in inside])
        # collective ops lie inside the in-flight intervals by construction
        waiting += sum(t for (name, _, _), t in zip(inside, own) if COLLECTIVE.match(name))
        waiting += T.total(T.subtract(f, T.union((s, e) for _, s, e in inside)))
    n = len(tr.device_ops)
    return {"in_flight_s": flight / n / 1e9, "waiting_s": waiting / n / 1e9}


_CACHE: dict[tuple[str, float], tuple[str, dict]] = {}


def for_run(run) -> tuple[str, dict] | None:
    """(cell, ``reduce`` of the newest trace) where that trace is this run's
    (``scopes.for_run``); the cell is the directory the harness traced into
    (``.chipbench/trace/<cell>/``). None otherwise."""
    from chipbench.harness import OUT

    if scopes.for_run(run) is None:
        return None
    path = scopes.newest(OUT / "trace")
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()  # the readers of one run share one reduction
        cell = Path(path).relative_to(OUT / "trace").parts[0]
        _CACHE[key] = (cell, reduce(scopes.load(os.path.dirname(path))))
    return _CACHE[key]

"""Reduction of a device trace to the numbers the per-layer readers use.

Everything below the loader is a pure function over ``(start_ns,
duration_ns, name)`` tuples, so the CPU tests need no chip. The loader reads
the profiler's ``.xplane.pb`` with ``jax.profiler.ProfileData`` and picks,
on each device plane, the line of XLA operations and the line of XLA modules
(names as seen in a real v5e trace: PERF.md, "What a trace looks like").
"""

from __future__ import annotations

import glob
import os
import re
from typing import Iterable, NamedTuple, Sequence

Event = tuple  # (start_ns, duration_ns, name)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class DeviceTrace(NamedTuple):
    """One device's events inside the traced window."""

    plane: str
    ops: list  # [Event] every XLA operation that ran on the device
    modules: list  # [Event] one per execution of an XLA module


class Trace(NamedTuple):
    devices: list  # [DeviceTrace]
    host: list  # [Event] host-side events of every host thread


def union_seconds(events: Iterable[Event]) -> float:
    """Seconds covered by at least one event (overlaps counted once)."""
    busy = 0
    end = None
    for start, dur, _ in sorted((e[0], e[1], "") for e in events):
        stop = start + dur
        if end is None or start > end:
            busy += dur
            end = stop
        elif stop > end:
            busy += stop - end
            end = stop
    return busy / 1e9


def clip(events: Iterable[Event], lo_ns: int, hi_ns: int) -> list:
    """Events cut to ``[lo_ns, hi_ns)``; those wholly outside are dropped."""
    out = []
    for start, dur, name in events:
        a, b = max(start, lo_ns), min(start + dur, hi_ns)
        if b > a:
            out.append((a, b - a, name))
    return out


def idle_gaps(events: Iterable[Event], lo_ns: int, hi_ns: int) -> list:
    """``(start_ns, duration_ns)`` of every stretch of ``[lo_ns, hi_ns)`` in
    which no event runs, longest first."""
    gaps = []
    cursor = lo_ns
    for start, dur, _ in sorted(clip(events, lo_ns, hi_ns)):
        if start > cursor:
            gaps.append((cursor, start - cursor))
        cursor = max(cursor, start + dur)
    if hi_ns > cursor:
        gaps.append((cursor, hi_ns - cursor))
    return sorted(gaps, key=lambda g: -g[1])


_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_op_name(name: str) -> str:
    """The trace names an operation by its whole HLO line; keep the
    instruction's name, its opcode and a custom call's target:
    ``%body.6 = (f32[1,1]...) custom-call(...), custom_call_target="x"``
    -> ``%body.6 custom-call x``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    opcode = _OPCODE.search(" " + rest)
    target = _TARGET.search(rest)
    return " ".join(part for part in (
        head, opcode.group(1) if opcode else "",
        target.group(1) if target else "") if part)[:120]


def self_seconds_by_name(events: Iterable[Event]) -> dict:
    """Seconds per operation name, a nested operation's time taken out of
    the operation that encloses it (a ``while`` encloses its body's
    operations on the same line), so the names add up to the busy time."""
    out: dict = {}
    stack: list = []  # [(end_ns, name, self_ns)]

    def close(until):
        while stack and stack[-1][0] <= until:
            _, name, own = stack.pop()
            name = short_op_name(name)
            out[name] = out.get(name, 0) + own

    for start, dur, name in sorted(events, key=lambda e: (e[0], -e[1])):
        close(start)
        if stack:
            end, parent, own = stack[-1]
            stack[-1] = (end, parent, own - min(dur, end - start))
        stack.append((start + dur, name, dur))
    close(float("inf"))
    return {k: v / 1e9 for k, v in out.items()}


def module_name(event_name: str) -> str:
    """``jit__minimize_lbfgs_impl(1234567)`` -> ``jit__minimize_lbfgs_impl``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def seconds_by_module(modules: Iterable[Event]) -> dict:
    """Device seconds per XLA module (its executions summed)."""
    out: dict = {}
    for _, dur, name in modules:
        key = module_name(name)
        out[key] = out.get(key, 0.0) + dur / 1e9
    return out


def module_seconds_matching(modules: Iterable[Event], pattern: str) -> float:
    """Device seconds in the modules whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(s for name, s in seconds_by_module(modules).items()
               if rx.search(name))


def label_gaps(gaps: Sequence[tuple], host: Sequence[Event],
               fallback: str = "unlabelled") -> list:
    """Name each idle gap by what the host was doing in its middle: the
    shortest host event that covers the midpoint."""
    out = []
    for start, dur in gaps:
        mid = start + dur // 2
        covering = [(d, n) for s, d, n in host if s <= mid < s + d]
        out.append((min(covering)[1] if covering else fallback, dur / 1e9))
    return out


def top(pairs: dict, n: int = 10) -> list:
    return [[k, v] for k, v in
            sorted(pairs.items(), key=lambda kv: -kv[1])[:n]]


# ---------------------------------------------------------------------------
# Loading
# ---------------------------------------------------------------------------


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line) -> list:
    return [(int(e.start_ns), int(e.duration_ns), e.name)
            for e in line.events]


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    devices, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            devices.append(DeviceTrace(
                plane.name,
                _events(lines[OPS_LINE]) if OPS_LINE in lines else [],
                _events(lines[MODULES_LINE]) if MODULES_LINE in lines
                else []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    return Trace(devices, host)


def describe(trace_dir: str, samples: int = 6) -> list:
    """Planes, lines and a few event names of a trace: what a person looks
    at before trusting the reduction (call it on a trace directory by hand)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    out = []
    for plane in data.planes:
        for line in plane.lines:
            events = list(line.events)
            names: dict = {}
            for e in events:
                names[e.name] = names.get(e.name, 0) + e.duration_ns
            out.append({
                "plane": plane.name, "line": line.name,
                "events": len(events),
                "top": [[n, s / 1e9] for n, s in sorted(
                    names.items(), key=lambda kv: -kv[1])[:samples]]})
    return out

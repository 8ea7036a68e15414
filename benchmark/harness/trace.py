"""From a profiler trace to device busy and idle time, op time and gaps.

``events(path)`` flattens a ``jax.profiler`` ``.xplane.pb`` into
``(plane, line, name, start_ns, dur_ns)`` rows; ``reduce`` works on such
rows, so a small recorded trace can test it. Only device lanes count
(planes named ``/device:<platform>:<n>``, lines ``XLA Modules`` and
``XLA Ops``): a trace with none is an error, never a fallback to host
events. Busy time is the union of the intervals of those lines' events,
so an op nested inside a module execution on the other line, or two
overlapping events, count once.
The op table takes one line (``XLA Ops``), so each op is counted once.
"""

from __future__ import annotations

import re

#: device planes: "/device:TPU:0" and the like (not "/host:CPU")
DEVICE_PLANE = re.compile(r"^/device:[A-Za-z]+:\d+$")
#: the line whose events are single ops, for the op table
OP_LINE = "XLA Ops"
#: the device lines whose events are work: module executions and the ops
#: inside them (other lines, such as step markers, span idle time too)
BUSY_LINES = ("XLA Modules", OP_LINE)
#: the annotation whose host-clock entry ties spans to the trace's clock
CLOCK_MARK = "benchmark.clock"


class NoDeviceLane(RuntimeError):
    pass


def events(path: str) -> list[tuple]:
    """The trace's device-lane events and the host's clock mark."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in BUSY_LINES:
                continue
            for e in line.events:
                if device or e.name == CLOCK_MARK:
                    out.append((plane.name, line.name, e.name,
                                int(e.start_ns), int(e.duration_ns)))
    return out


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Merge ``[(start, end)]`` into disjoint sorted intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clock_offset(rows: list[tuple], mark_host_ns: int) -> int | None:
    """Host monotonic ns minus trace ns, from the clock mark's entry."""
    for plane, _, name, start, _ in rows:
        if name == CLOCK_MARK and not DEVICE_PLANE.match(plane):
            return mark_host_ns - start
    return None


def reduce(rows: list[tuple], lo_ns: int | None = None,
           hi_ns: int | None = None) -> dict:
    """Busy and idle time of the device lanes within ``[lo_ns, hi_ns]``
    (trace clock; the whole trace when None), averaged over devices, with
    the op table and the idle gaps.

    -> {"devices", "window_ns", "busy_ns", "ops": [(name, ns)],
        "programs": [(name, ns)], "gaps": [(start, end)] (longest first)}
    (ops and programs heaviest first, summed over devices)"""
    dev = [r for r in rows
           if DEVICE_PLANE.match(r[0]) and r[1] in BUSY_LINES]
    if not dev:
        raise NoDeviceLane("the trace has no device lane")
    if lo_ns is None:
        lo_ns = min(r[3] for r in dev)
    if hi_ns is None:
        hi_ns = max(r[3] + r[4] for r in dev)
    planes = sorted({r[0] for r in dev})
    busy_total = 0
    gaps: list[tuple[int, int]] = []
    ops: dict[str, int] = {}
    programs: dict[str, int] = {}
    for plane in planes:
        ivs = []
        for p, line, name, s, d in dev:
            if p != plane:
                continue
            s0, e0 = max(s, lo_ns), min(s + d, hi_ns)
            if e0 <= s0:
                continue
            ivs.append((s0, e0))
            table = ops if line == OP_LINE else programs
            key = short_name(name)
            table[key] = table.get(key, 0) + (e0 - s0)
        merged = union(ivs)
        busy_total += sum(e - s for s, e in merged)
        edge = lo_ns
        for s, e in merged:
            if s > edge:
                gaps.append((edge, s))
            edge = e
        if hi_ns > edge:
            gaps.append((edge, hi_ns))
    n = len(planes)
    return {"devices": n, "window_ns": hi_ns - lo_ns,
            "busy_ns": busy_total / n,
            "ops": sorted(ops.items(), key=lambda kv: -kv[1]),
            "programs": sorted(programs.items(), key=lambda kv: -kv[1]),
            "gaps": sorted(gaps, key=lambda g: g[0] - g[1])}


def short_name(name: str) -> str:
    """An event's name without its HLO text: ``%fusion.12 = u32[9]{0} ...``
    becomes ``fusion.12 u32[9]``, ``jit_step(123)`` becomes ``jit_step``."""
    if " = " in name:
        lhs, rhs = name.split(" = ", 1)
        return f"{lhs.lstrip('%')} {rhs.split('{', 1)[0].split(' ', 1)[0]}"
    return re.sub(r"\(\d+\)$", "", name)


def label_gap(gap: tuple[int, int], spans: list[tuple], offset: int | None
              ) -> str:
    """What the host was doing in an idle gap: the program spans open at
    its midpoint (``spans``: ``(name, start_ns, end_ns)`` on the host
    clock), or ``unattributed`` when the spans cannot be put on the
    trace's clock."""
    if offset is None:
        return "unattributed"
    mid = (gap[0] + gap[1]) // 2 + offset
    names = sorted({n for n, s, e in spans if s <= mid < e})
    return "+".join(names) if names else "no span open"

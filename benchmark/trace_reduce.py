"""From a profiler trace to the device's busy time, its idle gaps and its
dearest operations.  One reduction, kept with the benchmark so that every
PR computes the same numbers the same way.

Busy is the union of the intervals in which an operation ran on a device
(the device plane's op line), clipped to the traced window; the window is
what lies between the harness's two marks, which also tie the harness's
clock to the trace's (the trace counts from its own start).  A gap gets
the names of the harness's spans that were open over half of it or more.
"""

from __future__ import annotations

import glob
import os
import re

MARK_BEGIN = "bench:window_begin"
MARK_END = "bench:window_end"
DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
SHORT_GAP_S = 1e-3
TOP = 10


def union_seconds(intervals) -> float:
    """Total length of the union of (start, end) intervals."""
    busy, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                busy += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy


def idle_gaps(intervals, window) -> list[tuple[float, float]]:
    """The (start, end) stretches of `window` that no interval covers."""
    w0, w1 = window
    gaps, at = [], w0
    for s, e in sorted(intervals):
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if at < w1:
        gaps.append((at, w1))
    return gaps


def idle_share(intervals, window) -> float:
    w0, w1 = window
    clipped = [(max(s, w0), min(e, w1)) for s, e in intervals]
    return 1.0 - union_seconds(clipped) / (w1 - w0)


def label_gap(gap, spans) -> str:
    """Names of the spans (name, start, end) open over half of `gap` or
    more, joined by '+'; 'no_span' where none was."""
    g0, g1 = gap
    open_s: dict[str, float] = {}
    for name, s, e in spans:
        o = min(e, g1) - max(s, g0)
        if o > 0:
            open_s[name] = open_s.get(name, 0.0) + o
    names = sorted(n for n, o in open_s.items() if o >= 0.5 * (g1 - g0))
    return "+".join(names) or "no_span"


def summarize_gaps(gaps, spans) -> list[list]:
    """At most TOP [label, seconds], longest first: each gap of a
    millisecond or more under its label, the shorter ones (the pauses
    between one device operation and the next) summed in one entry."""
    short = sum(e - s for s, e in gaps if e - s < SHORT_GAP_S)
    rows = [[label_gap(g, spans), g[1] - g[0]]
            for g in gaps if g[1] - g[0] >= SHORT_GAP_S]
    if short > 0:
        rows.append(["between_ops_under_1ms", short])
    rows.sort(key=lambda r: -r[1])
    return rows[:TOP]


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def short_name(hlo: str) -> str:
    """An event of the op line is named by its whole HLO instruction,
    kilobytes of shapes: keep the result's name and the opcode
    ('%while.61 while')."""
    head, _, rest = hlo.partition(" = ")
    found = _OPCODE.search(rest)
    return f"{head} {found.group(1)}" if found else head[:120]


def top_ops(events) -> list[list]:
    """At most TOP [name, seconds] of (name, start, end) events by total
    time, dearest first.  An operation that holds others (a `while`, a
    call) counts their time too: the list names where the time is, its
    entries do not add up to the busy time."""
    total: dict[str, float] = {}
    for name, s, e in events:
        name = short_name(name)
        total[name] = total.get(name, 0.0) + (e - s)
    return [[n, t] for n, t in
            sorted(total.items(), key=lambda kv: -kv[1])[:TOP]]


def read_xplane(logdir: str) -> tuple[dict, dict, dict]:
    """(device events by plane, marks, inventory) of the newest trace
    under `logdir`.  Events are (name, start_s, end_s) on the trace's own
    clock; marks are {name: start_s} of the harness's two annotations."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(paths[-1])
    devices: dict[str, list] = {}
    marks: dict[str, float] = {}
    inventory = {"file_bytes": os.path.getsize(paths[-1]), "planes": {}}
    for plane in data.planes:
        lines = {}
        on_device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            events = list(line.events)
            lines[line.name] = len(events)
            if on_device and line.name == OP_LINE:
                # an event's name is its whole HLO instruction, kilobytes
                # long, and a window holds some 10^6 events of some 10^3
                # instructions: keep one string for each instruction
                names: dict[str, str] = {}
                rows = devices[plane.name] = []
                for ev in events:
                    name = ev.name
                    rows.append((names.setdefault(name, name),
                                 ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9))
            elif plane.name.startswith("/host:"):
                for ev in events:
                    if ev.name in (MARK_BEGIN, MARK_END):
                        marks[ev.name] = ev.start_ns * 1e-9
        if plane.name.startswith((DEVICE_PLANE, "/host:CPU")):
            inventory["planes"][plane.name] = {
                k: v for k, v in sorted(lines.items(),
                                        key=lambda kv: -kv[1])[:8]}
    return devices, marks, inventory


def reduce_trace(logdir: str, window_pc, spans_pc) -> dict:
    """The traced window reduced: `busy_s` (mean over the device planes),
    `window_s`, the dearest device operations and the longest idle gaps.
    `window_pc` is (begin, end) and `spans_pc` the harness's spans, both
    on `time.perf_counter`, read where the two marks were written."""
    devices, marks, inventory = read_xplane(logdir)
    out = {"inventory": inventory, "device_planes": len(devices)}
    if not devices or MARK_BEGIN not in marks or MARK_END not in marks:
        raise ValueError(f"the trace lacks a device op line or the "
                         f"harness's marks: {inventory}")
    shift = marks[MARK_BEGIN] - window_pc[0]       # perf_counter -> trace
    window = (marks[MARK_BEGIN], marks[MARK_END])
    spans = [(n, s + shift, e + shift) for n, s, e in spans_pc]
    busy, all_events = [], []
    for events in devices.values():
        clipped = [(n, max(s, window[0]), min(e, window[1]))
                   for n, s, e in events
                   if e > window[0] and s < window[1]]
        busy.append(union_seconds([(s, e) for _, s, e in clipped]))
        all_events.extend(clipped)
    first = [(s, e) for _, s, e in next(iter(devices.values()))]
    gaps = idle_gaps(first, window)
    out.update(
        busy_s=sum(busy) / len(busy),
        window_s=window[1] - window[0],
        idle_share_first_device=idle_share(first, window),
        clock_drift_s=(marks[MARK_END] - marks[MARK_BEGIN])
        - (window_pc[1] - window_pc[0]),
        device_events=len(all_events),
        device_ops=top_ops(all_events),
        idle_gaps=summarize_gaps(gaps, spans),
        gaps_at=[
            {"at_s": g[0] - window[0], "for_s": g[1] - g[0],
             "open": label_gap(g, spans)}
            for g in gaps if g[1] - g[0] >= SHORT_GAP_S])
    return out

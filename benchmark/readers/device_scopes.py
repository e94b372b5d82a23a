"""Device seconds by stage of the verify program, from the traced
operation's xplane.  The program names its stages with `jax.named_scope`
(`drand_tpu.ops.STAGES`) and its Pallas kernels with `pallas_call(name=)`,
so a device operation's `op_name` reads
`jit(run)/miller/.../jit(wrapped)/flat_mul/pallas_call`.  The trace names
an operation by its HLO instruction; the `op_name` is that instruction's
in the compiled program's text.

Every event of a device plane's op line is charged its SELF time (its
interval less what the events it holds cover: a `while` holds its body's
operations; an event that holds none is charged whole) to the first stage
in its path, or to `unscoped` (transfers, relayout, glue between stages);
an operation without an `op_name`, a copy the compiler put in, goes with
the event that holds it.
The stages and `unscoped` then add up to the union of the intervals, which
is `device.busy_s.*`.  Seconds are for every `per_rounds` rounds.  The
traced run also logs, once, the dearest Pallas kernels by name and stage.

`benchmark/run.py` keeps the trace in the run's work directory until
`Run.close` (since PR 28), so it is there when `result` asks.  A program
without the scopes (before PR 25) gives `unscoped` alone, which is no
reading: None.
"""

from __future__ import annotations

import functools
import glob
import os
import re

from benchmark import harness as H
from benchmark import trace_reduce

UNSCOPED = "unscoped"
NO_OP_NAME = "(no op_name)"
PALLAS = "pallas_call"
TOP = 10
TOUCH_S = 1e-9
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?(%[^\s=]+) = [^\n]*?op_name="([^"]*)"', re.M)


def stages() -> tuple[str, ...]:
    try:
        from drand_tpu.ops import STAGES
    except ImportError:
        return ()
    return tuple(STAGES)


def stage_of(path: str, names) -> str:
    for part in path.split("/"):
        if part in names:
            return part
    return UNSCOPED


def kernel_of(path: str) -> str | None:
    """The Pallas kernel's name: the part before `pallas_call`."""
    parts = path.split("/")
    if PALLAS in parts[1:]:
        return parts[parts.index(PALLAS, 1) - 1]
    return None


def self_times(events, window):
    """[(path, self seconds)] of (path, start, end) events of ONE line,
    clipped to `window`: an event's interval less the union of the events
    inside it.  Events of a line nest or lie apart; one that begins
    within a nanosecond of another's end lies apart from it (the two
    times are sums of floats, and an event taken for its neighbour's
    child would be charged twice: to itself and, unsubtracted, to the
    `while` that holds both)."""
    w0, w1 = window
    rows = sorted(((max(s, w0), min(e, w1), p) for p, s, e in events
                   if e > w0 and s < w1), key=lambda r: (r[0], -r[1]))
    out, stack = [], []           # stack: [end, path, own seconds]

    def close(until):
        while stack and stack[-1][0] <= until:
            _end, path, own = stack.pop()
            out.append((path, own))

    for s, e, p in rows:
        close(s + TOUCH_S)
        if stack:
            # what lies inside the holder is the held event's to charge
            stack[-1][2] -= min(e, stack[-1][0]) - s
            if not p:
                # a copy the compiler put into a loop's body has no
                # op_name of its own: it is its holder's stage's time
                p = stack[-1][1] + "/" + NO_OP_NAME
        stack.append([e, p, e - s])
    close(float("inf"))
    return out


def by_stage(charged, names) -> dict[str, float]:
    out = {n: 0.0 for n in (*names, UNSCOPED)}
    for path, own in charged:
        out[stage_of(path, names)] += own
    return out


def top_kernels(charged, names) -> list[list]:
    """At most TOP [kernel, stage, seconds], dearest first."""
    total: dict[tuple, float] = {}
    for path, own in charged:
        kernel = kernel_of(path)
        if kernel is not None:
            key = (kernel, stage_of(path, names))
            total[key] = total.get(key, 0.0) + own
    return [[k, st, t] for (k, st), t in
            sorted(total.items(), key=lambda kv: -kv[1])[:TOP]]


def instruction_paths(hlo_text: str) -> dict[str, str]:
    """{instruction name: op_name} of a compiled program's text."""
    return dict(_INSTRUCTION.findall(hlo_text))


def op_path(event: str, paths: dict[str, str]) -> str:
    """A device event's scope path.  An event of the op line is named by
    its HLO instruction less the metadata, and carries no stat but its
    times (looked at on a v5e, PR 25), so the instruction's name, what
    stands before ` = `, is looked up in the compiled program's text."""
    return paths.get(event.partition(" = ")[0], "")


def load(logdir: str, paths: dict[str, str]):
    """({plane: [(path, start_s, end_s)]}, {mark: start_s}) of the newest
    trace under `logdir`."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(files[-1])
    planes: dict[str, list] = {}
    marks: dict[str, float] = {}
    seen: dict[str, str] = {}     # 10^5-10^6 events of some 10^3 names
    for plane in data.planes:
        device = plane.name.startswith(trace_reduce.DEVICE_PLANE)
        host = plane.name.startswith("/host:")
        if not (device or host):
            continue
        for line in plane.lines:
            if device and line.name == trace_reduce.OP_LINE:
                rows = planes[plane.name] = []
                for ev in line.events:
                    name = ev.name
                    path = seen.get(name)
                    if path is None:
                        path = seen[name] = op_path(name, paths)
                    start = ev.start_ns * 1e-9
                    rows.append((path, start, start + ev.duration_ns * 1e-9))
            elif host:
                for ev in line.events:
                    if ev.name in (trace_reduce.MARK_BEGIN,
                                   trace_reduce.MARK_END):
                        marks[ev.name] = ev.start_ns * 1e-9
    return planes, marks


def reduce(logdir: str, hlo_text: str) -> dict | None:
    """Seconds by stage (mean over the device planes) and the dearest
    kernels of the traced window; None without a device line, the
    harness's marks or a single scoped operation."""
    names = stages()
    planes, marks = load(logdir, instruction_paths(hlo_text))
    if not planes or not names or len(marks) < 2:
        return None
    window = (marks[trace_reduce.MARK_BEGIN], marks[trace_reduce.MARK_END])
    charged = [row for events in planes.values()
               for row in self_times(events, window)]
    seconds = {k: v / len(planes)
               for k, v in by_stage(charged, names).items()}
    if not any(seconds[n] for n in names):
        return None
    busy = sum(trace_reduce.union_seconds(
        [(max(s, window[0]), min(e, window[1])) for _p, s, e in events])
        for events in planes.values()) / len(planes)
    return {"by_stage": seconds, "busy_s": busy,
            "kernels": [[k, st, t / len(planes)]
                        for k, st, t in top_kernels(charged, names)]}


def program_text(run) -> str:
    """The compiled verify programs' text, which holds each
    instruction's `op_name`; empty where the run built none."""
    verifier = getattr(getattr(run, "chain_verifier", None),
                       "_lazy_verifier", None)
    return "\n".join(fn.as_text() for fn in
                     getattr(verifier, "_kernels", {}).values()
                     if hasattr(fn, "as_text"))


@functools.lru_cache(maxsize=1)
def scopes_of(run) -> dict | None:
    """The traced operation's reduction, once a run; None where the trace
    is gone or holds nothing to read."""
    op = getattr(run, "_traced_op", None)
    if not op or not op[3] or not os.path.isdir(op[0]):
        return None
    try:
        got = reduce(op[0], program_text(run))
    except FileNotFoundError:
        return None
    if got is not None:
        got["rounds"] = op[3]
        H.emit(device_scopes=got)
    return got


def read(run, spec: dict):
    got = scopes_of(run)
    if got is None:
        return None
    return got["by_stage"][spec["stage"]] * spec["per_rounds"] / got["rounds"]

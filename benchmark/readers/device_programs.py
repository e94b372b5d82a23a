"""Device seconds under each chain's verify program in the traced
operation, for every `per_rounds` rounds of that chain, where several
programs share one device (`drivers/catchup_multi.py`).

A device runs the programs it is handed in the order of their enqueue,
and the program's own `verify.dispatch` spans say whose each was (the
span's `beacon_id`) and when it was enqueued.  So the k-th run on the
device plane's module line is the k-th dispatch's.  A run is charged the
union of the op line's intervals inside it, so the chains add up to what
`device.busy_s.*` reads of the same line.

A trace without a module line, a module line whose runs are not one to
one with the dispatches, or a program whose spans carry no `beacon_id`
(before PR 36) gives nothing to read: None.  The traced run logs the
split once (`device_programs`), with the module names each chain's runs
carried: a program's runs carry one name, so a chain that shows two was
given another's run.
"""

from __future__ import annotations

import functools
import glob
import os

import numpy as np

from benchmark import harness as H
from benchmark import trace_reduce
from benchmark.readers.chain_skew import spans_in

MODULE_LINE = "XLA Modules"


def load(logdir: str):
    """({plane: (module runs [(name, start_s, end_s)], op intervals
    [(start_s, end_s)])}, {mark: start_s}) of the newest trace."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(files[-1])
    planes: dict[str, tuple[list, list]] = {}
    marks: dict[str, float] = {}
    for plane in data.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PLANE):
            modules, ops = planes.setdefault(plane.name, ([], []))
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    modules.extend(
                        (ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events)
                elif line.name == trace_reduce.OP_LINE:
                    ops.extend(
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in (trace_reduce.MARK_BEGIN,
                                   trace_reduce.MARK_END):
                        marks[ev.name] = ev.start_ns * 1e-9
    return planes, marks


def busy_inside(ops: np.ndarray, window) -> float:
    """Length of the union of the `ops` ([N, 2] starts and ends, sorted
    by start), clipped to `window`."""
    w0, w1 = window
    if w1 <= w0:
        return 0.0
    inside = ops[(ops[:, 1] > w0) & (ops[:, 0] < w1)]
    if not len(inside):
        return 0.0
    starts = np.maximum(inside[:, 0], w0)
    ends = np.minimum(inside[:, 1], w1)
    # sorted by start: an interval adds what lies past every end before it
    covered = np.concatenate([[w0], np.maximum.accumulate(ends)[:-1]])
    return float(np.clip(ends - np.maximum(starts, covered), 0, None).sum())


def split(planes, window, dispatches) -> tuple[dict, dict] | None:
    """({chain: device seconds, mean over the planes}, {chain: the module
    names of its runs}), `dispatches` being (enqueued at, chain); None
    where a plane's module runs are not one to one with them."""
    total: dict[str, float] = {}
    names: dict[str, set] = {}
    for modules, ops in planes.values():
        ops = np.asarray(ops, dtype=np.float64).reshape(-1, 2)
        ops = ops[np.argsort(ops[:, 0], kind="stable")]
        runs = sorted((r for r in modules
                       if r[2] > window[0] and r[1] < window[1]),
                      key=lambda r: r[1])
        if not runs or len(runs) != len(dispatches):
            return None
        chains = [chain for _at, chain in sorted(dispatches)]
        for (name, s, e), chain in zip(runs, chains):
            total[chain] = total.get(chain, 0.0) + busy_inside(
                ops, (max(s, window[0]), min(e, window[1]))) / len(planes)
            names.setdefault(chain, set()).add(name)
    return total, names


@functools.lru_cache(maxsize=1)
def programs_of(run) -> dict | None:
    """The traced operation's device seconds by chain, once a run."""
    op = getattr(run, "_traced_op", None)
    if not op or not os.path.isdir(op[0]):
        return None
    logdir, window_pc = op[0], op[1]
    dispatched = [sp for sp in spans_in(window_pc, ("verify.dispatch",))
                  if getattr(sp, "beacon_id", "")]
    if not dispatched:
        return None
    try:
        planes, marks = load(logdir)
    except FileNotFoundError:
        return None
    if not planes or len(marks) < 2:
        return None
    window = (marks[trace_reduce.MARK_BEGIN], marks[trace_reduce.MARK_END])
    dispatches = [(sp.start_mono + sp.duration_s
                   - sp.attrs.get("enqueue_s", 0.0), sp.beacon_id)
                  for sp in dispatched]
    got = split(planes, window, dispatches)
    if got is None:
        return None
    seconds, names = got
    rounds: dict[str, int] = {}
    for sp in spans_in(window_pc, ("sync.catchup",)):
        chain = getattr(sp, "beacon_id", "")
        rounds[chain] = rounds.get(chain, 0) + int(sp.attrs.get("rounds", 0))
    out = {"seconds": seconds, "rounds": rounds,
           "module_names": {c: sorted(n) for c, n in names.items()}}
    H.emit(device_programs=out)
    return out


def read(run, spec: dict):
    got = programs_of(run)
    if got is None:
        return None
    chain = spec["beacon_id"]
    if chain not in got["seconds"] or not got["rounds"].get(chain):
        return None
    return got["seconds"][chain] * spec["per_rounds"] / got["rounds"][chain]

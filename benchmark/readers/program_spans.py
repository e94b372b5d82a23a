"""Per-layer metrics from the program's own spans
(`drand_tpu.tracing.RECORDER`): those that began inside the traced
operation, on the clock the harness reads (`time.perf_counter`, a span's
`start_mono`).  A layer-metric file says what is read:

    {"names": [...]}                  seconds of SELF time under those
                                      names (a span's duration less what
                                      its child spans cover), for every
                                      `per_rounds` rounds
    {"names": [...], "ratio": [a, b]} sum of attribute a over sum of
                                      attribute b of those spans
    {"idle": "unattributed"}          seconds, for every `per_rounds`
                                      rounds, of the trace's idle gaps of
                                      a millisecond or more over at least
                                      half of which no program span was
                                      open

A program that publishes no `start_mono` (before PR 25) gives nothing to
read: every metric here is then left out of the line.  The traced run
also logs, once, the spans counted by name and every such gap beside the
spans open over it.
"""

from __future__ import annotations

import functools

from benchmark import harness as H
from benchmark import trace_reduce

MIN_GAP_S = trace_reduce.SHORT_GAP_S


def self_seconds(spans) -> dict[str, float]:
    """Seconds of self time by span id: the span's own interval less the
    union of its children's, each clipped to it.  `spans` are
    (span_id, parent_id, name, start, end)."""
    children: dict[str, list] = {}
    for _sid, parent, _name, s, e in spans:
        children.setdefault(parent, []).append((s, e))
    out = {}
    for sid, _parent, _name, s, e in spans:
        inside = [(max(cs, s), min(ce, e)) for cs, ce in
                  children.get(sid, ())]
        out[sid] = (e - s) - trace_reduce.union_seconds(inside)
    return out


def open_over(gap, spans) -> list[str]:
    """Names of the spans open over half of `gap` or more, outermost
    (earliest begun) first; spans of one name add up."""
    g0, g1 = gap
    cover: dict[str, float] = {}
    first: dict[str, float] = {}
    for _sid, _parent, name, s, e in spans:
        o = min(e, g1) - max(s, g0)
        if o > 0:
            cover[name] = cover.get(name, 0.0) + o
            first[name] = min(first.get(name, s), s)
    return sorted((n for n, o in cover.items() if o >= 0.5 * (g1 - g0)),
                  key=first.get)


def unattributed_seconds(gaps, spans) -> float:
    """Seconds of the (start, end) gaps over at least half of which no
    span at all was open."""
    total = 0.0
    for g0, g1 in gaps:
        inside = [(max(s, g0), min(e, g1)) for _i, _p, _n, s, e in spans]
        if trace_reduce.union_seconds(inside) < 0.5 * (g1 - g0):
            total += g1 - g0
    return total


def recorded(window=None) -> list | None:
    """(span_id, parent_id, name, start, end, attrs) of the recorder's
    spans, those that began inside `window` where one is given; None
    where the program has no recorder or its spans no `start_mono`."""
    try:
        from drand_tpu import tracing
    except ImportError:
        return None
    rows = []
    for sp in tracing.RECORDER.spans():
        start = getattr(sp, "start_mono", None)
        if start is None or sp.duration_s is None:
            continue
        if window is None or window[0] <= start <= window[1]:
            rows.append((sp.span_id, sp.parent_id, sp.name, start,
                         start + sp.duration_s, sp.attrs))
    return rows or None


@functools.lru_cache(maxsize=1)
def reduced(run) -> dict | None:
    """The traced operation's program spans reduced, once a run."""
    op = getattr(run, "_traced_op", None)
    if not op:
        return None
    _logdir, window, _harness_spans, rounds = op
    rows = recorded(window)
    if not rows or not rounds:
        return None
    spans = [r[:5] for r in rows]
    own = self_seconds(spans)
    by_name: dict[str, dict] = {}
    for sid, _parent, name, s, e in spans:
        row = by_name.setdefault(name, {"count": 0, "total_s": 0.0,
                                        "self_s": 0.0})
        row["count"] += 1
        row["total_s"] += e - s
        row["self_s"] += own[sid]
    out = {"rows": rows, "spans": spans, "own": own, "rounds": rounds,
           "gaps": None}
    H.emit(program_spans={"count": len(rows), "rounds": rounds,
                          "by_name": by_name})
    trace = run.trace
    if trace and trace.get("gaps_at"):
        # the gaps are given from the window's first mark, which is where
        # the harness read `window[0]`; spans begun before the window
        # (none today) would be missed here
        gaps = [(window[0] + g["at_s"], window[0] + g["at_s"] + g["for_s"])
                for g in trace["gaps_at"]
                if g["for_s"] >= MIN_GAP_S]
        out["gaps"] = gaps
        H.emit(idle_gaps_under_program_spans=[
            {"at_s": g0 - window[0], "for_s": g1 - g0,
             "open": open_over((g0, g1), spans)} for g0, g1 in gaps])
    return out


def read(run, spec: dict):
    got = reduced(run)
    if got is None:
        return None
    scale = spec["per_rounds"] / got["rounds"]
    if spec.get("idle") == "unattributed":
        if got["gaps"] is None:
            return None
        return scale * unattributed_seconds(got["gaps"], got["spans"])
    mine = [r for r in got["rows"] if r[2] in spec["names"]]
    if not mine:
        return None
    if "ratio" in spec:
        over, under = spec["ratio"]
        total = sum(r[5].get(under, 0) for r in mine)
        return sum(r[5].get(over, 0) for r in mine) / total if total else None
    return scale * sum(got["own"][r[0]] for r in mine)

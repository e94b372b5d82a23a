"""The device's two ends: seconds the device stood idle at the head of
the traced operation (from the window's first mark to its first
operation: what the host needs to fill and dispatch the first segment)
and at its tail (from its last operation to the window's second mark:
the last verdicts' way back and the last commit).  From the trace's
`gaps_at` (`trace_reduce.reduce_trace`: the idle gaps of a millisecond
or more on the FIRST device plane, as `idle_gaps` has them), the gap
that begins the window and the one that ends it.

Seconds an OPERATION, not per 65,536 rounds: the ends do not grow with
the backlog (on four chips an operation of 262,144 rounds has one head
and one tail like any other), so a per-round scaling would divide a
constant by the cell's size.

    {"end": "head"}        {"end": "tail"}

A device busy to within a millisecond of the window's edge has no gap
there: 0.  A run without a reduced trace gives nothing to read: None.
"""

from __future__ import annotations

EDGE_S = 1e-6       # a gap at the window's edge begins or ends ON it


def ends(gaps_at, window_s: float) -> dict[str, float]:
    """{"head": s, "tail": s} of `gaps_at` ({at_s, for_s} from the
    window's begin) in a window of `window_s` seconds."""
    out = {"head": 0.0, "tail": 0.0}
    for g in gaps_at:
        if g["at_s"] <= EDGE_S:
            out["head"] += g["for_s"]
        elif g["at_s"] + g["for_s"] >= window_s - EDGE_S:
            out["tail"] += g["for_s"]
    return out


def read(run, spec: dict):
    trace = run.trace
    if not trace or trace.get("gaps_at") is None \
            or not trace.get("window_s"):
        return None
    return ends(trace["gaps_at"], trace["window_s"])[spec["end"]]

"""Seconds between the ends of the chains' catch-ups in the traced
operation: what the last chain ran alone, so how long the operation was
not concurrent.  Read from the program's own spans
(`drand_tpu.tracing.RECORDER`): those named `span` (`sync.catchup`) that
began inside the traced operation, told apart by the span's `beacon_id`.

A program whose spans carry no `beacon_id` there (before PR 36), or a run
with fewer than two chains, gives nothing to read: None.
"""

from __future__ import annotations


def spans_in(window, names) -> list:
    """The recorder's ended spans of `names` that began inside `window`
    (on `time.perf_counter`); empty where the program has no recorder or
    its spans no `start_mono`."""
    try:
        from drand_tpu import tracing
    except ImportError:
        return []
    return [sp for sp in tracing.RECORDER.spans()
            if sp.name in names and sp.duration_s is not None
            and getattr(sp, "start_mono", None) is not None
            and window[0] <= sp.start_mono <= window[1]]


def ends_by_chain(spans) -> dict[str, float]:
    """{beacon_id: when the last of its spans ended}, the spans without
    a beacon_id left out."""
    ends: dict[str, float] = {}
    for sp in spans:
        chain = getattr(sp, "beacon_id", "")
        if chain:
            ends[chain] = max(ends.get(chain, float("-inf")),
                              sp.start_mono + sp.duration_s)
    return ends


def read(run, spec: dict):
    op = getattr(run, "_traced_op", None)
    if not op:
        return None
    ends = ends_by_chain(spans_in(op[1], (spec["span"],)))
    if len(ends) < 2:
        return None
    return max(ends.values()) - min(ends.values())

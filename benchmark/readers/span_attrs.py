"""The sum of ONE attribute of the program's spans of some names, for
every `per_rounds` rounds of the traced operation: what the program
counts where the work happens (two clock reads a message or a row, added
to a counter that rides on the span a segment, a stream or a transaction
already has), read from the spans `program_spans.reduced` took from
`drand_tpu.tracing.RECORDER`.  A layer-metric file says what is read:

    {"names": [...], "attr": "recv_s", "per_rounds": 65536}
    {..., "log_open_over": "loop.lag"}    the traced run also logs,
                                      once, every span of that name
                                      beside the program spans open
                                      over half of it or more (a stall
                                      of the event loop under a commit,
                                      a collection, a build, or under
                                      nothing of the program's)

Where several chains run in one operation (`rounds` is then all of
theirs) this is the mean of the chains, as a `stats` metric is there.

A program whose spans of those names carry no such attribute, or that
has no such span (the parent of the PR that adds one), gives nothing to
read: None, and the line leaves the metric out.
"""

from __future__ import annotations

import functools

from benchmark import harness as H
from benchmark.readers import program_spans


def summed(rows, names, attr: str):
    """Sum of `attr` over the (span_id, parent_id, name, start, end,
    attrs) rows of `names` that carry it; None where none does."""
    values = [r[5][attr] for r in rows if r[2] in names and attr in r[5]]
    return sum(values) if values else None


def open_over_each(spans, name: str, t0: float) -> list[dict]:
    """For every (span_id, parent_id, name, start, end) of `name`: when
    it began (from `t0`), how long it lasted, and the other spans' names
    open over half of it or more, outermost first."""
    others = [sp for sp in spans if sp[2] != name]
    return [{"at_s": s - t0, "for_s": e - s,
             "open": program_spans.open_over((s, e), others)}
            for _sid, _parent, n, s, e in spans if n == name]


@functools.lru_cache(maxsize=1)
def _log_open_over(run, name: str) -> None:
    """Once a run."""
    got = program_spans.reduced(run)
    H.emit(spans_under_program_spans={
        "name": name,
        "spans": open_over_each(got["spans"], name, run._traced_op[1][0])})


def read(run, spec: dict):
    got = program_spans.reduced(run)
    if got is None:
        return None
    if spec.get("log_open_over"):
        _log_open_over(run, spec["log_open_over"])
    total = summed(got["rows"], spec["names"], spec["attr"])
    if total is None:
        return None
    return total * spec["per_rounds"] / got["rounds"]

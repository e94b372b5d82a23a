"""Seconds the device ran an operation, for every `per_rounds` rounds of
the traced operation (`trace_reduce.reduce_trace`)."""


def read(run, spec: dict):
    trace = run.trace
    if not trace or not trace.get("rounds"):
        return None
    return trace["busy_s"] * spec["per_rounds"] / trace["rounds"]

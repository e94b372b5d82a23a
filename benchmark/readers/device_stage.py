"""Device seconds of ONE stage that the program at hand may not name.
`device_scopes.read` looks a stage up in the vocabulary of the program it
runs beside (`drand_tpu.ops.STAGES`); a stage that a later PR added
(`digest`, PR 29) is not in an older program's, and there this reader
gives nothing where that one would raise.  The reduction, and its one
`device_scopes` line a run, are `device_scopes`'s own."""

from __future__ import annotations

from benchmark.readers import device_scopes


def read(run, spec: dict):
    got = device_scopes.scopes_of(run)
    if got is None or spec["stage"] not in got["by_stage"]:
        return None
    return device_scopes.read(run, spec)

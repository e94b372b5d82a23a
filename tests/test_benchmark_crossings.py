"""The crossings of configuration and traffic mix that the accepted
`BENCHMARK.json` leaves out, rehearsed on the CPU from data alone, with
the tier-1 suite.

`benchmark/tests/test_rehearsal.py` counts them (two, when PR 28 wrote
it) and runs every one in one case; each configuration or mix that a PR
appends, as the benchmark's contract has it done, adds crossings (seven
since PR 36, fourteen since PR 41, twenty-four since PR 43).  That case
is held here in the form that stays true under appending, under its own
name: at least the two it began with, and every crossing there is run
as the other runs them, each a case of its own.  The repair of the file
beside the benchmark is a `benchmark` PR's (PERF.md, section 7).  The
cases were in `tests/test_benchmark_rehearsal.py` until PR 43."""

import pytest

from benchmark.tests.test_rehearsal import _crossed, _run


def _crossings() -> list[str]:
    """The crossings' names, as `_crossed` makes them."""
    import tempfile
    from pathlib import Path
    with tempfile.TemporaryDirectory() as tmp:
        return _crossed(Path(tmp))[1]


def test_the_crossings_run_from_data_alone(tmp_path):
    assert len(_crossed(tmp_path)[1]) >= 2


@pytest.mark.parametrize("cell", _crossings())
def test_a_crossing_runs_from_data_alone(tmp_path, cell):
    """Each crossing a case of its own since PR 41."""
    path, added = _crossed(tmp_path)
    assert cell in added
    proc, lines = _run("--workload", cell, "--seed", "11", "--seconds",
                       "6", "--trace", "1", "--rehearse", "host",
                       "--bench-file", path)
    assert proc.returncode == 0, (cell, proc.stderr[-2000:])
    assert lines[-1]["correct"] is True, cell
    assert lines[-1]["metrics"], "per-layer metrics of the traced run"

"""ISSUE 29's two cells rehearsed on the CPU from the real
`BENCHMARK.json`: correct on the program's host tier, not correct on the
stub, on four seeds each, one of each kind of first fault (about 20 s a
case; `test_rehearsal.py` has the one-seed cases of every cell)."""

import pytest

from benchmark.tests import test_rehearsal as R

NEW_CELLS = ("catchup-deep.default-chained", "restart-scan.unchained-g2")
SEEDS = [2**31 + 290, 2**31 + 291, 2**31 + 292, 2**31 + 293]


def _run(cell: str, seed: int, verifier: str):
    return R._run("--workload", cell, "--seed", str(seed), "--seconds", "1",
                  "--trace", "0", "--rehearse", verifier)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", NEW_CELLS)
def test_a_new_cell_is_correct_on_the_host_tier(cell, seed):
    proc, lines = _run(cell, seed, "host")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["checks"] and all(v == limit
                                  for v, limit in last["checks"].values())
    assert "setup_s" in last["metrics"] and len(last["metrics"]) >= 2
    kind = [ln for ln in lines if "check_draw" in ln][-1]["check_draw"]["kind"]
    assert kind == ("ramp", "segment_first", "segment_last",
                    "anywhere")[seed % 4]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", NEW_CELLS)
def test_a_new_cell_is_not_correct_on_the_stub(cell, seed):
    """The control: a verifier that checks less.  (Under the chained
    scheme a draw whose three faults all fall into `previous_sig` is
    caught by `verdicts.*` alone, not by `faulted.*`; one whose damaged
    `previous_sig` the stub lets through to the store, which refuses the
    row, ends with a `reason` and nothing compared.)"""
    proc, lines = _run(cell, seed, "stub")
    assert proc.returncode == 1
    last = lines[-1]
    assert last["correct"] is False
    assert last.get("not_held") or last.get("reason")

"""The configuration, the two cells and the two per-layer metrics ISSUE 34
added to the benchmark run with the tier-1 suite: the cases live beside
the benchmark's other tests.

Two of them pin the lists to the state PR 34 left them in (its two cells
the LAST two of `workloads`, its configuration the last, its two metrics
the last two of `per_layer`), which no PR that appends, as the
benchmark's contract has it done, can keep; PR 36 appended a
configuration, two cells and four metrics and may edit no file under
`benchmark/`.  Those cases are held here in the form that stays true
under appending, under their own names, in the others' place, as
`test_benchmark_new_cells.py` holds PR 29's; the repair of the file
beside the benchmark is a `benchmark` PR's (PERF.md, section 7).  PR 41
added the scan's four-chip cell, `restart-scan.quicknet-g1-x4`."""

from benchmark.tests.test_x4_cells import *  # noqa: F401,F403
from benchmark.tests.test_x4_cells import ONE, X4


def test_four_chips_are_asked_for_once(bench):  # noqa: F811
    # once a traffic mix: the catch-up's, and since PR 41 the scan's
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == [
        X4, "restart-scan.quicknet-g1-x4"]
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[cells.index(X4):][:2] == [X4, ONE]      # appended, in order
    configs = [c["name"] for c in bench["configs"]]
    assert configs.index("quicknet-g1-x4") == 3


def test_the_two_new_metrics_are_the_x4_cells_alone(bench):  # noqa: F811
    names = [m["name"] for m in bench["per_layer"]]
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    at = names.index("verify.shard_put_s")
    assert names[at:at + 2] == ["verify.shard_put_s", "verify.gather_s"]
    for name in names[at:at + 2]:
        assert per_layer[name] == {
            "name": name, "unit": "s", "better": "lower",
            "source": "program_span", "layer": "Verifier dispatch",
            "moves": "catchup_rate", "workloads": [X4]}
    assert not any("roofline" in n or "mfu" in n for n in per_layer)

"""The cells, the configuration and the per-layer metrics ISSUE 29 added
to the benchmark, checked without a run, with the tier-1 suite: the cases
live beside the benchmark's other tests.

One of them pins the lists to the state PR 29 left them in (its four
metrics the LAST four of `per_layer`, each list of cells equal to its
two), which no PR that appends a cell or a metric, as the benchmark's
contract has it done, can keep; PR 34 appended two of each and may edit
no file under `benchmark/`.  That case is held here in the form that
stays true under appending, under its own name, in the other's place;
the repair of the file beside the benchmark is a `benchmark` PR's
(PERF.md, section 7)."""

from benchmark.tests.test_new_cells import *  # noqa: F401,F403
from benchmark.tests.test_new_cells import CATCHUPS, SCANS, STAGES


def test_the_new_per_layer_metrics_and_their_cells(bench):  # noqa: F811
    names = [m["name"] for m in bench["per_layer"]]
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    want = {"program.digest_s.catchup": ("device_trace", CATCHUPS),
            "program.digest_s.scan": ("device_trace", SCANS),
            "verify.genesis_link_s": ("program_span", CATCHUPS[1:]),
            "store.link_check_s": ("program_span", CATCHUPS[1:])}
    at = names.index("program.digest_s.catchup")
    assert names[at:at + 4] == list(want)           # appended, in order
    for name, (source, cells) in want.items():
        assert per_layer[name]["source"] == source
        # what a later PR appends stands behind the cells that were there
        assert per_layer[name]["workloads"][:len(cells)] == cells
    for kind, cells in (("catchup", CATCHUPS), ("scan", SCANS)):
        for stage in ("digest", *STAGES, "unscoped"):
            assert per_layer[f"program.{stage}_s.{kind}"][
                "workloads"][:len(cells)] == cells
    assert not any("roofline" in n or "mfu" in n for n in per_layer)

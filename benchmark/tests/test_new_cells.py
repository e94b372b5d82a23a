"""What ISSUE 29 added to the benchmark, without a run: the two cells
and the configuration as `BENCHMARK.json` and `configs/` state them, and
the four new per-layer metrics through the readers, from their own
`layer_metrics/` files: the `digest` stage by the reader that gives
nothing where a program does not name it, the spans
`verify.genesis_link` and `store.link_check` (a child of `store.commit`)
by `program_spans` as it is.  (The cells' rehearsals are
`test_new_cells_rehearsal.py`'s.)"""

import hashlib
import json
import os

import pytest

from benchmark import harness as H
from benchmark.harness import BENCH_DIR, ROOT
from benchmark.readers import device_scopes as D
from benchmark.readers import device_stage as G
from benchmark.readers import program_spans as S
from benchmark.tests.test_readers import STAGES, _Run   # the parent's four

CATCHUPS = ["catchup-deep.unchained-g2", "catchup-deep.default-chained"]
SCANS = ["restart-scan.quicknet-g1", "restart-scan.unchained-g2"]
NEW_CELLS = {
    "catchup-deep.default-chained": ("default-chained", "catchup-deep",
                                     CATCHUPS[0]),
    "restart-scan.unchained-g2": ("unchained-g2", "restart-scan", SCANS[0]),
}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- BENCHMARK.json and the configuration --------------------------------------

@pytest.mark.parametrize("cell", sorted(NEW_CELLS))
def test_a_new_cell_reports_what_its_traffic_s_accepted_cell_reports(
        bench, cell):
    """One chip, the accepted traffic file, and a place behind the
    accepted cell of the same traffic in every list that names it."""
    config, traffic, beside = NEW_CELLS[cell]
    entry = [w for w in bench["workloads"] if w["name"] == cell]
    assert len(entry) == 1
    assert (entry[0]["config"], entry[0]["traffic"], entry[0]["chips"]) == \
        (config, traffic, 1)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if beside in m.get("workloads", []):
            assert cell in m["workloads"], m["name"]
            assert m["workloads"].index(cell) > m["workloads"].index(beside)
    mine = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert "setup_s" in mine and len(mine) >= 2


def test_the_chained_configuration_states_its_deployment(bench):
    entry = [c for c in bench["configs"] if c["name"] == "default-chained"]
    assert len(entry) == 1 and entry[0]["reduced"] == ["backlog_rounds"]
    with open(os.path.join(ROOT, entry[0]["file"])) as f:
        cfg = json.load(f)
    assert cfg["scheme_id"] == "pedersen-bls-chained" and cfg["chained"]
    assert len(bytes.fromhex(cfg["genesis_seed_hex"])) == 32
    assert (cfg["signature_bytes"], cfg["public_key_bytes"],
            cfg["period_s"]) == (96, 48, 30)
    assert (cfg["backlog_rounds"], cfg["bucket_rounds"],
            cfg["wire_chunk_rounds"], cfg["chips"]) == (65536, 16384, 512, 1)
    assert len(cfg["guarantees"]) == 6 and cfg["deployment"]
    assert set(cfg["reduced"]) == {"backlog_rounds"}
    assert {"source", "public_key_hex", "genesis_seed_hex"} <= \
        set(cfg["assumed"])
    # the test configuration it was copied from differs in the size only
    with open(os.path.join(BENCH_DIR, "tests", "chained",
                           "default-chained.json")) as f:
        test_cfg = json.load(f)
    same = set(cfg) - {"deployment", "backlog_rounds", "fixture", "reduced",
                       "assumed"}
    assert all(cfg[k] == test_cfg[k] for k in same)
    path = os.path.join(BENCH_DIR, "fixtures", cfg["fixture"]["file"])
    with open(path, "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest() == \
            cfg["fixture"]["sha256"]


def test_the_new_per_layer_metrics_and_their_cells(bench):
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    want = {"program.digest_s.catchup": ("device_trace", CATCHUPS),
            "program.digest_s.scan": ("device_trace", SCANS),
            "verify.genesis_link_s": ("program_span", CATCHUPS[1:]),
            "store.link_check_s": ("program_span", CATCHUPS[1:])}
    assert list(per_layer)[-4:] == list(want)       # appended, in order
    for name, (source, cells) in want.items():
        assert per_layer[name]["source"] == source
        assert per_layer[name]["workloads"] == cells
    # the six stages that add up to `device.busy_s.*`, in every cell
    for kind, cells in (("catchup", CATCHUPS), ("scan", SCANS)):
        for stage in ("digest", *STAGES, "unscoped"):
            assert per_layer[f"program.{stage}_s.{kind}"]["workloads"] == \
                cells
    # no share of a roofline: this PR adds no kernel
    assert not any("roofline" in n or "mfu" in n for n in per_layer)


# -- the digest stage ----------------------------------------------------------

def _digest_line():
    """The chained program's head: two SHA blocks a row under `digest`,
    then a stage, then glue under none."""
    return [("jit(run)/digest/while", 0.0, 0.5),
            ("jit(run)/digest/while/body/add", 0.1, 0.4),
            ("jit(run)/sig_decode/jit(wrapped)/mont_mul/pallas_call",
             0.5, 2.5),
            ("jit(run)/and", 2.5, 3.0)]


def _traced(monkeypatch, tmp_path, stages):
    from benchmark import trace_reduce as T
    monkeypatch.setattr(D, "stages", lambda: stages)
    monkeypatch.setattr(D, "load", lambda logdir, paths: (
        {"/device:TPU:0": _digest_line()},
        {T.MARK_BEGIN: 0.0, T.MARK_END: 4.0}))
    return _Run((str(tmp_path), (100.0, 104.0), [], 65536))


@pytest.mark.parametrize("kind", ["catchup", "scan"])
def test_the_digest_stage_leaves_unscoped_and_the_six_add_up(
        monkeypatch, tmp_path, kind):
    """`program.digest_s.*` as their files describe them, beside the five
    metrics that were there: six that add up to the busy time."""
    run = _traced(monkeypatch, tmp_path, ("digest", *STAGES))
    spec = H.load_json("layer_metrics", f"program.digest_s.{kind}.json")
    assert (spec["kind"], spec["reader"], spec["stage"]) == \
        ("reader", "device_stage", "digest")
    assert G.read(run, spec) == pytest.approx(0.5)
    rest = {st: D.read(run, H.load_json(
        "layer_metrics", f"program.{st}_s.{kind}.json"))
        for st in (*STAGES, "unscoped")}
    assert rest["unscoped"] == pytest.approx(0.5)
    assert rest["sig_decode"] == pytest.approx(2.0)
    assert G.read(run, spec) + sum(rest.values()) == pytest.approx(3.0)


def test_a_program_without_the_digest_stage_gives_nothing_and_does_not_raise(
        monkeypatch, tmp_path):
    """The parent commit's vocabulary has four stages: the new metric is
    left out of the line there, where `device_scopes.read` would raise,
    and the digest stays in `unscoped`, as it was."""
    run = _traced(monkeypatch, tmp_path, STAGES)
    spec = H.load_json("layer_metrics", "program.digest_s.catchup.json")
    assert G.read(run, spec) is None
    with pytest.raises(KeyError):
        D.read(run, spec)
    assert D.read(run, dict(spec, stage="unscoped")) == pytest.approx(1.0)
    assert G.read(_Run(), spec) is None         # and without a trace


# -- the chained path's spans --------------------------------------------------

def test_the_chained_spans_as_their_files_describe_them():
    """`verify.genesis_link_s` and `store.link_check_s`; `store.put_s`
    stays `store.commit`'s self time, so the walk is not counted twice."""
    from drand_tpu import tracing
    tracing.RECORDER.clear()
    seg = tracing.begin_span("sync.segment", at=100.0)
    tracing.record_span("verify.dispatch", 100.1, 100.2, parent=seg, n=511,
                        bucket=16384, pad_rows=15873, msg_bytes=104,
                        h2d_bytes=16384 * 200)
    tracing.record_span("verify.genesis_link", 100.2, 100.25, parent=seg,
                        tier="native", ok=True)
    commit = tracing.begin_span("store.commit", parent=seg, at=101.0,
                                rows=512, payload_bytes=512 * 192 - 64)
    tracing.record_span("store.link_check", 101.1, 101.3, parent=commit,
                        rows=512)
    commit.end(at=102.0)
    seg.end(at=102.0)
    run = _Run(("dir", (100.0, 110.0), [], 32768), None)
    read = {name: S.read(run, H.load_json("layer_metrics", name + ".json"))
            for name in ("verify.genesis_link_s", "store.link_check_s",
                         "store.put_s", "verify.dispatch_s")}
    assert read == pytest.approx({"verify.genesis_link_s": 2 * 0.05,
                                  "store.link_check_s": 2 * 0.2,
                                  "store.put_s": 2 * 0.8,
                                  "verify.dispatch_s": 2 * 0.1})
    # asked of the reader: names, which it reads today, and no more
    for name in ("verify.genesis_link_s", "store.link_check_s"):
        spec = H.load_json("layer_metrics", name + ".json")
        assert set(spec) == {"kind", "reader", "names", "per_rounds",
                             "reads"}


def test_an_unchained_catch_up_opens_neither_span():
    from drand_tpu import tracing
    tracing.RECORDER.clear()
    tracing.record_span("store.commit", 101.0, 102.0, rows=512,
                        payload_bytes=512 * 96)
    run = _Run(("dir", (100.0, 110.0), [], 32768), None)
    assert S.read(run, H.load_json(
        "layer_metrics", "store.put_s.json")) == pytest.approx(2.0)
    for name in ("verify.genesis_link_s", "store.link_check_s"):
        assert S.read(run, H.load_json("layer_metrics",
                                       name + ".json")) is None

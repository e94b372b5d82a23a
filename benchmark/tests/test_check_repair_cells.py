"""What ISSUE 41 added to the benchmark: the configuration
`default-chained-damaged`, the cells `check-repair.default-chained-damaged`
(one chip) and `restart-scan.quicknet-g1-x4` (four: the owed one), the
traffic mix `check-repair` with its driver and its seeded damage, the
plain model of the check and the repair, and the four `check.*` metrics
through the readers as they are, on recorded spans.  Every assertion
about a list's order is relative, so that it stays true under
appending."""

import json
import os
import sys

import numpy as np
import pytest

from benchmark import harness as H
from benchmark.drivers import check_repair as D
from benchmark.harness import BENCH_DIR, ROOT
from benchmark.readers import program_spans, span_attrs
from benchmark.reference import check_repair as M
from benchmark.tests import test_rehearsal as R
from benchmark.tests.test_readers import _Run

CHECK = "check-repair.default-chained-damaged"
SCAN_X4 = "restart-scan.quicknet-g1-x4"
CONTROL = "restart-scan.default-chained"
METRICS = ["check.fetch_s", "check.verify_wait_s", "check.overwrite_s",
           "check.pad_share"]


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config(bench, name):
    entry, = [c for c in bench["configs"] if c["name"] == name]
    with open(os.path.join(ROOT, entry["file"])) as f:
        return entry, json.load(f)


# -- BENCHMARK.json and the configuration --------------------------------------

def test_the_two_cells_are_appended_after_the_accepted_eight(bench):
    cells = [w["name"] for w in bench["workloads"]]
    at = cells.index(CHECK)
    assert at >= 8 and cells[at + 1] == SCAN_X4
    by_name = {w["name"]: w for w in bench["workloads"]}
    assert {k: by_name[CHECK][k] for k in ("config", "traffic", "chips")} \
        == {"config": "default-chained-damaged", "traffic": "check-repair",
            "chips": 1}
    assert {k: by_name[SCAN_X4][k] for k in ("config", "traffic", "chips")} \
        == {"config": "quicknet-g1-x4", "traffic": "restart-scan",
            "chips": 4}
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == 2 <= len(cells) // 2
    configs = [c["name"] for c in bench["configs"]]
    assert configs.index("default-chained-damaged") >= 5


def test_both_cells_stand_behind_their_control_in_the_scan_lists(bench):
    named = [m for m in bench["end_to_end"] + bench["per_layer"]
             if CONTROL in m.get("workloads", [])]
    assert {"scan_rate", "scan.read_s", "scan.decode_s", "scan.pack_s",
            "device.busy_s.scan", "program.miller_s.scan"} \
        <= {m["name"] for m in named}
    for m in named:
        for cell in (CHECK, SCAN_X4):
            if cell in m["workloads"]:
                assert m["workloads"].index(cell) \
                    > m["workloads"].index(CONTROL), m["name"]
    rate, = [m for m in bench["end_to_end"] if m["name"] == "scan_rate"]
    assert rate["workloads"][-2:] == [CHECK, SCAN_X4] and rate["bound"] == 0.03
    # no metric of the catch-up's reads either cell
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m.get("moves", m["name"]) in ("catchup_rate",
                                         "chunk_commit_p95_ms"):
            assert not {CHECK, SCAN_X4} & set(m.get("workloads", [CHECK]))


def test_the_four_metrics_are_the_check_cells_alone(bench):
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(METRICS[0])
    assert names[at:at + 4] == METRICS
    for m in bench["per_layer"][at:at + 4]:
        assert m == {"name": m["name"],
                     "unit": "ratio" if m["name"].endswith("share") else "s",
                     "better": "lower",
                     "source": "program_counter"
                     if m["name"].endswith("share") else "program_span",
                     "layer": "check and repair", "moves": "scan_rate",
                     "workloads": [CHECK]}
    assert not any("roofline" in n or "mfu" in n for n in names)


def test_the_configuration_is_default_chained_with_the_damage(bench):
    entry, cfg = _config(bench, "default-chained-damaged")
    _base, base = _config(bench, "default-chained")
    assert entry["reduced"] == ["backlog_rounds"]
    assert set(cfg["reduced"]) == {"backlog_rounds"}
    assert 1 <= len(entry["source"]) <= 200 and "  " not in entry["source"]
    assert "StartCheckChain" in entry["source"] \
        and "util check" in entry["source"]
    differing = {k for k in set(cfg) | set(base) if cfg.get(k) != base.get(k)}
    assert differing == {"name", "source", "deployment", "damage",
                         "guarantees", "reduced", "assumed"}
    assert cfg["fixture"] == base["fixture"] and cfg["env"] == base["env"]
    assert len(cfg["guarantees"]) == 6 and "damage" in cfg["assumed"]
    traffic = H.load_json("traffic", "check-repair.json")
    for key in ("extent_rounds", "sig_flips", "prev_flips"):
        assert cfg["damage"][key] == traffic["damage"][key]
    assert traffic["rehearse_damage"] == {"extent_rounds": 4, "sig_flips": 1,
                                          "prev_flips": 1}
    assert (traffic["driver"], traffic["warmup_rounds"],
            traffic["rehearse_rounds"]) == ("check_repair", 16384, 1024)


# -- the seeded damage ----------------------------------------------------------

def _chain(n=2048, sig_len=96):
    rng = np.random.default_rng(5)
    sigs = rng.integers(0, 256, size=(n, sig_len), dtype=np.uint8)
    prevs = [b"\x01" * 32] + [s.tobytes() for s in sigs[:-1]]
    return sigs, prevs


def test_the_damage_is_the_seeds_alone_and_takes_any_whole_number():
    sigs, prevs = _chain()
    spec = {"extent_rounds": 32, "sig_flips": 4, "prev_flips": 4}
    for seed in (0, 7, 2**31 + 99, 2**32 + 5):
        damage = D.draw_damage(seed, sigs, prevs, spec)
        assert damage == D.draw_damage(seed, sigs, prevs, spec)
        assert len(damage) == 40 and min(damage) >= 2 and max(damage) < 2048
        changed = {"signature": 0, "previous_sig": 0, "both": 0}
        for r, (sig, prev) in damage.items():
            assert len(sig) == 96 and len(prev) == len(prevs[r - 1])
            s, p = sig != sigs[r - 1].tobytes(), prev != prevs[r - 1]
            changed["both" if s and p else
                    "signature" if s else "previous_sig"] += 1
        assert changed == {"signature": 4, "previous_sig": 4, "both": 32}
        runs = M.runs(damage)
        assert sum(1 for a, b in runs if b - a == 31) == 1 and len(runs) == 9
        assert all(b[0] - a[1] > 2 for a, b in zip(runs, runs[1:]))
    assert D.draw_damage(1, sigs, prevs, spec) \
        != D.draw_damage(2, sigs, prevs, spec)
    # an unchained store has no previous_sig to flip
    plain = D.draw_damage(7, sigs, None, spec)
    assert len(plain) == 36 and all(p == b"" for _s, p in plain.values())


def test_the_driver_reads_the_runs_own_seed(monkeypatch):
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "x", "--seed",
                                      str(2**31 + 12), "--trace", "0"])
    assert D.seed_of_run(41) == 2**31 + 12
    monkeypatch.setattr(sys, "argv", ["run.py", "--seed=9"])
    assert D.seed_of_run(41) == 9
    monkeypatch.setattr(sys, "argv", ["check_seeds.py", "--seeds", "1,2"])
    assert D.seed_of_run(41) == 41


# -- the plain model ------------------------------------------------------------

def _store(n=12):
    rows = {0: (b"seed", b"")}
    for r in range(1, n + 1):
        rows[r] = (b"s%d" % r, rows[r - 1][0])
    return rows


def _valid(truth):
    return lambda r, sig, prev: truth[r] == (sig, prev)


def test_the_model_files_each_round_under_one_list():
    truth = _store()
    rows = dict(truth)
    rows[3] = (b"bad", truth[3][1])           # a false signature
    rows[7] = (truth[7][0], b"wrong")         # a previous_sig that is not 6's
    rows[9] = None                            # a row that does not decode
    del rows[11]                              # a round that is not there
    found = M.check(rows, _valid(truth), True)
    assert found == {"corrupt": [9], "missing": [(11, 11)],
                     "unlinked": [4, 7], "bad_sigs": [3],
                     "scanned": 12, "tip_round": 12}
    assert M.to_mend(found) == [3, 4, 7, 9, 11]
    assert M.runs(M.to_mend(found)) == [(3, 4), (7, 7), (9, 9), (11, 11)]
    assert M.check(rows, _valid(truth), True, up_to=6)["unlinked"] == [4]
    # unchained: nothing links, a wrong previous_sig is not looked at
    plain = {r: (s, b"") for r, (s, _p) in truth.items()}
    bad = dict(plain)
    bad[5] = (b"bad", b"")
    assert M.check(bad, _valid(plain), False)["bad_sigs"] == [5]
    assert not M.check(bad, _valid(plain), False)["unlinked"]


def test_the_models_repair_writes_what_verified_over_the_consumers_links():
    truth = _store()
    rows = dict(truth)
    for r in (3, 4, 5, 6):
        rows[r] = (b"torn%d" % r, b"torn")
    rows[1] = (b"bad", truth[1][1])
    found = M.check(rows, _valid(truth), True)
    mend = M.to_mend(found)
    assert mend == [1, 2, 3, 4, 5, 6, 7]
    sound = {r: truth[r][0] for r in mend}
    after, fixed, unfixed = M.repair(rows, mend, sound, _valid(truth), True)
    assert (after, fixed, unfixed) == (truth, mend, [])
    # a lie fails, and so does the replacement after it, verified over it
    after, fixed, unfixed = M.repair(rows, mend, {**sound, 4: b"lie"},
                                     _valid(truth), True)
    assert unfixed == [4, 5] and fixed == [1, 2, 3, 6, 7]
    assert after[4] == rows[4] and after[5] == rows[5]
    # a run's last that the sound row after it does not name stays
    after, fixed, unfixed = M.repair(rows, mend, {**sound, 7: b"lie"},
                                     _valid(truth), True)
    assert unfixed == [7] and after[7] == rows[7]
    # a peer that lacks a round leaves the rest of its run
    short = {r: s for r, s in sound.items() if r != 3}
    assert M.repair(rows, mend, short, _valid(truth), True)[2] \
        == [3, 4, 5, 6, 7]
    # nothing stored before a run, and no genesis seed given
    headless = {r: row for r, row in rows.items() if r}
    assert M.repair(headless, mend, sound, _valid(truth), True)[2] == mend
    assert M.repair(headless, mend, sound, _valid(truth), True,
                    b"seed")[2] == []


# -- the check's spans ----------------------------------------------------------

def _record(with_check: bool):
    """One check of 65,536 rounds as the program records it."""
    from drand_tpu import tracing
    tracing.RECORDER.clear()
    root = tracing.begin_span("check.chain" if with_check else "store.scan",
                              at=100.0)
    for i in range(4):
        tracing.record_span("verify.dispatch", 100.1 + i, 100.2 + i,
                            parent=root, n=16304, bucket=16384, pad_rows=80)
    if with_check:
        tracing.record_span("check.fetch", 104.0, 104.3, parent=root,
                            streams=40, wall_s=0.3)
        wait = tracing.begin_span("check.verify_wait", parent=root, at=104.3)
        tracing.record_span("verify.dispatch", 104.3, 104.35, parent=wait,
                            n=353, bucket=16384, pad_rows=16031)
        wait.set(wall_s=0.9).end(at=105.2)
        tracing.record_span("check.overwrite", 105.2, 105.21, parent=root,
                            rows=353, wall_s=0.01, encode_s=0.001)
    root.end(at=105.3)
    return _Run(("dir", (100.0, 110.0), [], 131072), None)


def _read(run, name):
    spec = H.load_json("layer_metrics", name + ".json")
    reader = {"program_spans": program_spans, "span_attrs": span_attrs}[
        spec["reader"]]
    return reader.read(run, spec)


def test_the_check_spans_as_their_files_describe_them():
    run = _record(with_check=True)
    # per 65,536 rounds of an operation of 131,072
    assert {name: _read(run, name) for name in METRICS} == pytest.approx({
        "check.fetch_s": 0.15, "check.verify_wait_s": 0.45,
        "check.overwrite_s": 0.005,
        "check.pad_share": (4 * 80 + 16031) / (5 * 16384)})


def test_a_program_without_the_check_gives_nothing_and_does_not_raise():
    """The parent commit, and a plain scan: no `check.*` span is opened,
    and the three times are left out of the line."""
    for run in (_record(with_check=False), _Run()):
        for name in METRICS[:3]:
            assert _read(run, name) is None
    assert _read(_Run(), "check.pad_share") is None


# -- both cells rehearsed -------------------------------------------------------

def _rehearse(cell: str, seed: int, verifier: str, trace: int = 0):
    return R._run("--workload", cell, "--seed", str(seed), "--seconds", "1",
                  "--trace", str(trace), "--rehearse", verifier)


@pytest.mark.parametrize("seed", [2**31 + 410, 2**32 + 411])
def test_the_check_cell_is_correct_on_the_host_tier(seed):
    proc, lines = _rehearse(CHECK, seed, "host")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert all(v == limit for v, limit in last["checks"].values())
    assert {"window.filed_differs_from_reference",
            "window.stored_rows_differing",
            "faulted.lies_in_the_store",
            "faulted.rows_differing_from_reference"} <= set(last["checks"])
    assert set(last["metrics"]) == {"setup_s", "scan_rate"}
    damage, = [ln["damage"] for ln in lines if "damage" in ln]
    assert damage["seed"] == seed and damage["rounds"] == 6
    faulted, = [ln["faulted_pass"] for ln in lines if "faulted_pass" in ln]
    assert len(faulted["lies"]) == 3
    assert set(faulted["lies"]) <= set(faulted["unfixed"])
    assert faulted["unfixed"] == faulted["reference_unfixed"]


def test_the_check_cells_traced_line_holds_its_metrics():
    proc, lines = _rehearse(CHECK, 2**31 + 412, "host", trace=1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    metrics = lines[-1]["metrics"]
    assert set(METRICS[:3]) | {"scan.read_s", "scan.decode_s"} <= set(metrics)
    assert all(metrics[m]["value"] > 0 for m in METRICS[:3])


@pytest.mark.parametrize("seed", [7, 2**31 + 413, 2**32 + 414])
def test_the_check_cell_is_not_correct_on_the_stub(seed):
    """3 of 3 seeds: a flipped signature passes the stub, so its row
    stays false and a lie reaches the store."""
    proc, lines = _rehearse(CHECK, seed, "stub")
    assert proc.returncode == 1
    held = {c["name"] for c in lines[-1]["not_held"]}
    assert lines[-1]["correct"] is False
    assert {"window.filed_differs_from_reference",
            "window.stored_rows_differing",
            "faulted.filed_differs_from_reference"} <= held


def test_the_four_chip_scan_rehearses_on_one_device():
    proc, lines = _rehearse(SCAN_X4, 2**31 + 415, "host")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert lines[-1]["correct"] is True
    assert set(lines[-1]["metrics"]) == {"setup_s", "scan_rate"}

"""What ISSUE 36 added to the benchmark: the configuration
`loe-mainnet-2chains` (one daemon, one chip, LoE's `default` and
`quicknet` catching up at once), its cell
`catchup-concurrent.loe-mainnet-2chains`, the owed
`restart-scan.default-chained`, both rehearsed on the CPU from the real
`BENCHMARK.json`, and the four per-layer metrics of two chains in one
process, read from recorded spans through their `layer_metrics/` files."""

import asyncio
import hashlib
import json
import os

import pytest

from benchmark import harness as H
from benchmark import trace_reduce as T
from benchmark.harness import BENCH_DIR, ROOT
from benchmark.readers import chain_skew as K
from benchmark.readers import device_programs as P
from benchmark.readers import program_spans as S
from benchmark.tests import test_rehearsal as R
from benchmark.tests.test_readers import _Run

TWO, SCAN = "catchup-concurrent.loe-mainnet-2chains", \
    "restart-scan.default-chained"
CONTROLS = ("catchup-deep.default-chained", "catchup-deep.quicknet-g1")
NEW_METRICS = {"verify.behind_other_share": ("ratio", "program_counter",
                                             "Verifier dispatch"),
               "sync.chain_skew_s": ("s", "program_span",
                                     "SyncManager pipeline"),
               "device.program_s.default": ("s", "device_trace",
                                            "program and kernels"),
               "device.program_s.quicknet": ("s", "device_trace",
                                             "program and kernels")}
SEEDS = [2**31 + 360, 2**31 + 361]


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config(bench, name):
    entry, = [c for c in bench["configs"] if c["name"] == name]
    with open(os.path.join(ROOT, entry["file"])) as f:
        return entry, json.load(f)


def _metrics(bench) -> list:
    """The metrics up to the last that PR 36 appended: what a later PR
    appends after them is its own to hold."""
    names = [m["name"] for m in bench["per_layer"]]
    return bench["end_to_end"] + bench["per_layer"][
        :names.index(list(NEW_METRICS)[-1]) + 1]


def _lists(bench, cell) -> set:
    return {m["name"] for m in _metrics(bench)
            if cell in m.get("workloads", [])}


# -- BENCHMARK.json and the configuration --------------------------------------

def test_the_configuration_is_both_chains_key_for_key(bench):
    entry, cfg = _config(bench, "loe-mainnet-2chains")
    assert entry["reduced"] == ["backlog_rounds"]
    assert set(cfg["reduced"]) == {"backlog_rounds"}
    assert 1 <= len(entry["source"]) <= 200 and cfg["source"] == \
        entry["source"]
    assert cfg["architecture"] is None and cfg["chips"] == 1
    one_chain = ("scheme_id", "chained", "signature_group", "signature_bytes",
                 "public_key_bytes", "period_s", "public_key_hex",
                 "backlog_rounds", "bucket_rounds", "wire_chunk_rounds",
                 "env", "store", "fixture")
    _e, default = _config(bench, "default-chained")
    _e, quicknet = _config(bench, "quicknet-g1")
    for key in (*one_chain, "genesis_seed_hex", "chips"):
        assert cfg[key] == default[key], key
    for key in one_chain:
        assert cfg["second_chain"][key] == quicknet[key], key
    assert (cfg["beacon_id"], cfg["second_chain"]["beacon_id"]) == \
        ("default", "quicknet")
    # no new fixture: the two controls' own, by their hashes
    for chain in (cfg, cfg["second_chain"]):
        with open(os.path.join(BENCH_DIR, "fixtures",
                               chain["fixture"]["file"]), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == \
                chain["fixture"]["sha256"]
    assert cfg["deployment"] and cfg["reference"]
    assert len(cfg["guarantees"]) == 5 + 4 + 3
    assert {"source", "public_key_hex", "genesis_seed_hex"} <= \
        set(cfg["assumed"])


def test_the_two_cells_are_appended_on_one_chip_each(bench):
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(SCAN) > cells.index(TWO) > cells.index(CONTROLS[1])
    by_name = {w["name"]: w for w in bench["workloads"]}
    assert (by_name[TWO]["config"], by_name[TWO]["traffic"],
            by_name[TWO]["chips"]) == ("loe-mainnet-2chains",
                                       "catchup-concurrent", 1)
    assert (by_name[SCAN]["config"], by_name[SCAN]["traffic"],
            by_name[SCAN]["chips"]) == ("default-chained", "restart-scan", 1)
    assert all(len(by_name[c]["why"]) <= 200 for c in (TWO, SCAN))
    traffic = H.load_json("traffic", "catchup-concurrent.json")
    deep = H.load_json("traffic", "catchup-deep.json")
    assert traffic["driver"] == "catchup_multi"
    for key in ("ramp_rounds", "warmup_rounds", "rehearse_rounds", "check",
                "rehearse_env"):
        assert traffic[key] == deep[key]


def test_the_lists_the_catch_up_cell_stands_in_and_the_six_it_must_not(
        bench):
    mine = _lists(bench, TWO)
    always = {"catchup_rate", "wire.fetch_s", "sync.pack_s",
              "sync.verify_wait_s", "store.commit_s", "store.materialize_s",
              "store.put_s", "store.link_check_s", "verify.dispatch_s",
              "verify.pad_share", "verify.genesis_link_s",
              "device.busy_s.catchup",
              "device.idle_unattributed_s.catchup"} | set(NEW_METRICS)
    # the tail and the metric that moves it: which chain's segment
    # reaches the device first is the host's to toss, and the p95 of six
    # loaded runs spread by 1.18 % against half of the bound's 1 % (the
    # rate by 0.37 %: PERF.md, section 7), so the cell reports the rate
    tail = {"chunk_commit_p95_ms", "sync.queue_wait_s"}
    assert mine == always and not mine & tail
    # `device_scopes.program_text` reads one program's text: the stages
    # of two programs in one trace are not this cell's to report
    assert not any(n.startswith("program.") for n in mine)
    six = [m for m in _metrics(bench)
           if m["name"].startswith("program.")
           and m["name"].endswith(".catchup")]
    assert len(six) == 6 and all(TWO not in m["workloads"] for m in six)
    # appended: after every cell the list had
    for m in _metrics(bench):
        had = [c for c in m.get("workloads", []) if c in CONTROLS]
        if TWO in m.get("workloads", []) and had:
            assert m["workloads"].index(TWO) > max(
                m["workloads"].index(c) for c in had), m["name"]


def test_the_scan_cell_stands_wherever_the_g2_scan_does(bench):
    beside = "restart-scan.unchained-g2"
    assert _lists(bench, SCAN) == _lists(bench, beside) >= {
        "scan_rate", "scan.host_s", "device.busy_s.scan",
        "program.digest_s.scan"}
    for m in _metrics(bench):
        if SCAN in m.get("workloads", []):
            assert m["workloads"].index(SCAN) > m["workloads"].index(beside)


def test_the_four_new_metrics_are_the_catch_up_cells_alone(bench):
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("verify.behind_other_share")
    assert names[at:at + 4] == list(NEW_METRICS)
    assert at > names.index("verify.gather_s")
    for m in bench["per_layer"][at:at + 4]:
        unit, source, layer = NEW_METRICS[m["name"]]
        assert dict(m, workloads=m["workloads"][:1]) == {
            "name": m["name"], "unit": unit, "better": "lower",
            "source": source, "layer": layer, "moves": "catchup_rate",
            "workloads": [TWO]}
    assert not any("roofline" in n or "mfu" in n for n in NEW_METRICS)


# -- the four metrics on recorded spans ----------------------------------------

def _record(chains=("default", "quicknet"), counters=True):
    """One operation of two chains as the program records it: each chain
    a `sync.catchup` over two dispatches, alternating on the device; the
    later chain ends 0.8 s after the other."""
    from drand_tpu import tracing
    tracing.RECORDER.clear()
    for i, chain in enumerate(chains):
        root = tracing.begin_span("sync.catchup", beacon_id=chain,
                                  at=100.0 + 0.01 * i)
        for k in range(2):
            at = 100.1 + 0.02 * i + 1.8 * k
            more = {"dispatches": 1, "in_flight": i + 2 * k,
                    "behind_other": int(bool(i or k))} if counters else {}
            tracing.begin_span(
                "verify.dispatch", parent=root, at=at, n=16384, bucket=16384,
                pad_rows=0, enqueue_s=0.004, **more).end(at=at + 0.005)
            tracing.record_span("verify.resolve", at + 0.01,
                                100.1 + 0.9 * (2 * k + i + 1) + 0.002,
                                parent=root, n=16384, bucket=16384)
        root.set(rounds=32768)
        root.end(at=104.0 + 0.8 * i)
    return _Run(("dir", (100.0, 110.0), [], 65536), None)


def _device(monkeypatch, tmp_path, module_runs=4):
    """The device's side of `_record`: four runs of 0.9 s back to back
    from 0.1 s, `default`'s program (0.85 s busy a run) and `quicknet`'s
    (0.8 s) in turn, and the harness's two marks; the module line holds
    the first `module_runs` of them."""
    runs, ops = [], []
    for k in range(4):
        s = 0.1 + 0.9 * k
        runs.append((f"jit_call({1 + k % 2})", s, s + 0.9))
        busy = 0.85 if k % 2 == 0 else 0.8
        ops += [(s, s + busy), (s + 0.1, s + 0.2)]      # a holder, a leaf
    monkeypatch.setattr(P, "load", lambda logdir: (
        {"/device:TPU:0": (runs[:module_runs], ops)},
        {T.MARK_BEGIN: 0.0, T.MARK_END: 10.0}))
    P.programs_of.cache_clear()
    return str(tmp_path)


def _read(run, name):
    spec = H.load_json("layer_metrics", name + ".json")
    reader = {"program_spans": S, "chain_skew": K,
              "device_programs": P}[spec["reader"]]
    return reader.read(run, spec)


def test_the_share_of_dispatches_behind_another_verifiers_program():
    run = _record()
    assert _read(run, "verify.behind_other_share") == pytest.approx(3 / 4)
    spec = H.load_json("layer_metrics", "verify.behind_other_share.json")
    assert spec["ratio"] == ["behind_other", "dispatches"]
    assert (spec["reader"], spec["names"]) == ("program_spans",
                                               ["verify.dispatch"])


def test_the_skew_is_what_the_later_chain_ran_alone():
    run = _record()
    assert _read(run, "sync.chain_skew_s") == pytest.approx(0.8)


def test_the_devices_seconds_go_to_the_chain_whose_program_ran(
        monkeypatch, tmp_path):
    """The k-th run of the module line is the k-th dispatch's; the two
    chains add up to the busy time."""
    run = _record()
    run._traced_op = (_device(monkeypatch, tmp_path), *run._traced_op[1:])
    got = {c: _read(run, f"device.program_s.{c}")
           for c in ("default", "quicknet")}
    # 32,768 rounds a chain in the traced operation: for every 65,536
    assert got == pytest.approx({"default": 2 * 2 * 0.85,
                                 "quicknet": 2 * 2 * 0.8}, abs=1e-9)
    assert sum(got.values()) == pytest.approx(2 * 2 * 1.65)
    # a program's runs carry one name: each chain was given its own
    assert P.programs_of(run)["module_names"] == {
        "default": ["jit_call(1)"], "quicknet": ["jit_call(2)"]}


@pytest.mark.parametrize("module_runs", [0, 3])
def test_module_runs_that_are_not_the_dispatches_give_nothing(
        monkeypatch, tmp_path, module_runs):
    """A trace with no module line, or one whose runs are not one to one
    with the four dispatches: nothing is guessed."""
    run = _record()
    run._traced_op = (_device(monkeypatch, tmp_path, module_runs),
                      *run._traced_op[1:])
    assert P.programs_of(run) is None
    for chain in ("default", "quicknet"):
        assert _read(run, f"device.program_s.{chain}") is None


def test_a_program_without_the_counters_gives_nothing_and_does_not_raise(
        monkeypatch, tmp_path):
    """The parent commit: no `beacon_id` under a catch-up, no counters on
    a dispatch.  And one chain alone has no skew."""
    from drand_tpu import tracing
    run = _record(chains=("", ""), counters=False)
    run._traced_op = (_device(monkeypatch, tmp_path), *run._traced_op[1:])
    for name in NEW_METRICS:
        assert _read(run, name) is None, name
    run = _record(chains=("default",))
    assert _read(run, "sync.chain_skew_s") is None
    tracing.RECORDER.clear()
    for name in NEW_METRICS:
        assert _read(_Run(), name) is None
        assert _read(_Run(("dir", (100.0, 110.0), [], 65536), None),
                     name) is None


# -- both cells rehearsed -------------------------------------------------------

def _rehearse(cell: str, seed: int, verifier: str):
    return R._run("--workload", cell, "--seed", str(seed), "--seconds", "1",
                  "--trace", "0", "--rehearse", verifier)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", [TWO, SCAN])
def test_a_cell_is_correct_on_the_host_tier(bench, cell, seed):
    proc, lines = _rehearse(cell, seed, "host")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["checks"] and all(v == limit
                                  for v, limit in last["checks"].values())
    assert set(last["metrics"]) == {"setup_s"} | (_lists(bench, cell) & {
        m["name"] for m in bench["end_to_end"]})
    if cell != TWO:
        return
    # each chain against its own fixture, the fault isolated to its chain
    # both ways round, the second chain's verdicts against the reference
    for chain in ("default", "quicknet"):
        assert {f"window.{chain}.committed_rows_differing",
                f"faulted.{chain}.committed_at_or_after_first_bad",
                f"faulted.{chain}.others_failed",
                f"faulted.{chain}.others_rows_differing"} <= \
            set(last["checks"])
    assert {"verdicts.quicknet.served_differs_from_reference",
            "verdicts.quicknet.host_tier_differs_from_reference",
            "verdicts.served_differs_from_reference"} <= set(last["checks"])
    op = [ln for ln in lines if "operation" in ln][-1]["operation"]
    assert set(op["chains"]) == {"default", "quicknet"}
    assert all(c["ok"] and c["rounds"] == 1024
               for c in op["chains"].values())
    passes = [ln for ln in lines if "faulted_passes" in ln][-1][
        "faulted_passes"]
    assert [p["faulted_chain"] for p in passes] == ["default", "quicknet"]
    for p in passes:
        other, = set(p["chains"]) - {p["faulted_chain"]}
        assert p["chains"][other]["ok"]


def test_a_program_older_than_the_cell_ends_it_with_a_reason(monkeypatch):
    """The parent commit's `SyncManager` takes no `beacon_id`: the first
    catch-up, in the warm-up, ends the run with a `BenchFailure` that
    says so, and what was served is closed."""
    from benchmark.run import Run
    from drand_tpu.beacon import sync_manager

    class Older(sync_manager.SyncManager):
        def __init__(self, store, group, verifier, network, peers, clock,
                     insecure_store=None):
            raise AssertionError("not reached")

    monkeypatch.setattr(sync_manager, "SyncManager", Older)

    async def go():
        run = Run(TWO, rehearse="host")
        try:
            await run.prepare()
        finally:
            await run.close()

    with pytest.raises(H.BenchFailure, match="beacon_id"):
        asyncio.run(go())


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", [TWO, SCAN])
def test_a_cell_is_not_correct_on_the_stub(cell, seed):
    """The control: a verifier that checks less, in both programs'
    place.  (In the scan cell a stub that lets a damaged `previous_sig`
    through to the store, which refuses the row, ends with a `reason`;
    a catch-up of the two-chain driver that raises has failed, and its
    pass is compared all the same.)"""
    proc, lines = _rehearse(cell, seed, "stub")
    assert proc.returncode == 1
    last = lines[-1]
    assert last["correct"] is False
    if cell == TWO:
        assert any(c["name"].startswith("faulted.quicknet.")
                   for c in last["not_held"])
    else:
        assert last.get("not_held") or last.get("reason")

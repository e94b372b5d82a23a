"""What ISSUE 43 added to the benchmark: the configuration
`quicknet-g1-peers`, the cell `catchup-failover.quicknet-g1-peers`, the
traffic mix `catchup-failover` with its driver and its script of
failures, the plain model of what a fail-over has to leave behind, and
the four per-layer metrics through the readers as they are, on recorded
spans.  Every assertion about a list's order is relative, so that it
stays true under appending."""

import json
import os
import socket

import numpy as np
import pytest

from benchmark import harness as H
from benchmark.drivers import catchup_failover as D
from benchmark.harness import ROOT
from benchmark.readers import program_spans, span_attrs
from benchmark.reference import failover as M
from benchmark.tests import test_rehearsal as R
from benchmark.tests.test_readers import _Run

CELL = "catchup-failover.quicknet-g1-peers"
CONTROL = "catchup-deep.quicknet-g1"
METRICS = ["sync.failover_s", "sync.probe_s", "sync.refetched_share",
           "verify.discarded_share"]


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config(bench, name):
    entry, = [c for c in bench["configs"] if c["name"] == name]
    with open(os.path.join(ROOT, entry["file"])) as f:
        return entry, json.load(f)


# -- BENCHMARK.json, the configuration, the traffic ------------------------------

def test_the_cell_is_appended_after_the_accepted_ten(bench):
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(CELL) >= 10
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert {k: cell[k] for k in ("config", "traffic", "chips")} == {
        "config": "quicknet-g1-peers", "traffic": "catchup-failover",
        "chips": 1}
    configs = [c["name"] for c in bench["configs"]]
    assert configs.index("quicknet-g1-peers") >= 6
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == 2                       # this PR added none


def test_the_cell_stands_behind_its_control_in_the_catch_ups_lists(bench):
    rate, = [m for m in bench["end_to_end"] if m["name"] == "catchup_rate"]
    assert CELL in rate["workloads"] and rate["bound"] == 0.01
    named = [m for m in bench["per_layer"]
             if CONTROL in m.get("workloads", [])]
    for m in named:
        if m["moves"] == "catchup_rate":
            assert m["workloads"].index(CELL) \
                > m["workloads"].index(CONTROL), m["name"]
        else:                                   # the p95's: not this cell's
            assert CELL not in m["workloads"], m["name"]
    assert {"wire.fetch_s", "verify.pad_share", "verify.dispatch_s",
            "device.busy_s.catchup", "program.miller_s.catchup",
            "loop.lag_s", "store.put_s"} <= {
        m["name"] for m in named if CELL in m["workloads"]}
    p95, = [m for m in bench["end_to_end"]
            if m["name"] == "chunk_commit_p95_ms"]
    assert CELL not in p95["workloads"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m.get("moves", m["name"]) == "scan_rate":
            assert CELL not in m.get("workloads", [CELL])


def test_the_four_metrics_are_this_cells_alone(bench):
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(METRICS[0])
    assert names[at:at + 4] == METRICS
    for m in bench["per_layer"][at:at + 4]:
        share = m["name"].endswith("share")
        assert m == {"name": m["name"], "unit": "ratio" if share else "s",
                     "better": "lower",
                     "source": "program_counter" if share
                     else "program_span",
                     "layer": "Verifier dispatch"
                     if m["name"].startswith("verify.")
                     else "SyncManager peers",
                     "moves": "catchup_rate", "workloads": [CELL]}
    assert not any("roofline" in n or "mfu" in n for n in names)


def test_the_configuration_is_quicknet_g1_but_for_the_peers(bench):
    entry, cfg = _config(bench, "quicknet-g1-peers")
    _base, base = _config(bench, "quicknet-g1")
    assert entry["reduced"] == ["backlog_rounds"]
    assert set(cfg["reduced"]) == {"backlog_rounds"}
    assert 1 <= len(entry["source"]) <= 200 and "  " not in entry["source"]
    assert "Sync/tryNode" in entry["source"] \
        and "quicknet" in entry["source"]
    differing = {k for k in set(cfg) | set(base) if cfg.get(k) != base.get(k)}
    assert differing == {"name", "source", "deployment", "peers",
                         "guarantees", "reduced", "assumed"}
    assert "architecture" in cfg and cfg["architecture"] is None
    assert cfg["fixture"] == base["fixture"] and cfg["env"] == base["env"]
    assert (cfg["bucket_rounds"], cfg["wire_chunk_rounds"], cfg["chips"],
            cfg["backlog_rounds"]) == (16384, 512, 1, 65536)
    peers = cfg["peers"]
    assert (peers["group_nodes"], peers["peers"], peers["unreachable"],
            peers["alive"]) == (23, 22, 2, 20)
    assert cfg["guarantees"][:5] == base["guarantees"]
    assert len(cfg["guarantees"]) == 9
    assert {"group_nodes", "failures", "public_key_hex"} <= set(cfg["assumed"])


def test_the_traffic_states_the_script_to_the_number():
    traffic = H.load_json("traffic", "catchup-failover.json")
    assert (traffic["driver"], traffic["order_seed"], traffic["warmup_rounds"],
            traffic["rehearse_rounds"]) == ("catchup_failover", 43, 16896,
                                            1024)
    assert traffic["check"] == {"samples": 32, "faults": 3}
    assert traffic["min_operations"] == 10 \
        and "min_operations" in traffic["loop"]
    bare = [{k: v for k, v in s.items() if k != "who"}
            for s in traffic["script"]]
    assert bare == [
        {"kind": "abort", "after_messages": 48, "status": "UNAVAILABLE"},
        {"kind": "corrupt_row", "round": 40961},
        {"kind": "liar", "round": 50000},
        {"kind": "sound"}]
    small = traffic["rehearse_script"]
    assert [s["kind"] for s in small] == [s["kind"] for s in bare]
    # the three places, scaled: 24,576, 40,961 and 50,000 of 65,536
    assert small[0]["after_messages"] * 8 == 24576 * 1024 // 65536
    assert small[1]["round"] == 40960 * 1024 // 65536 + 1
    assert small[2]["round"] == -(-50000 * 1024 // 65536)
    assert traffic["faulted_group"]["live"] == 4 \
        and traffic["faulted_group"]["unreachable"] == 2
    assert "ORDER" in traffic["order"] and "order_seed" in traffic["order"]


# -- the plain model ------------------------------------------------------------

SCRIPT = H.load_json("traffic", "catchup-failover.json")["script"]


def _tries(*rows):
    return [{"live": live, "end": end, "height": h} for live, end, h in rows]


def test_the_model_allows_what_a_correct_fail_over_leaves():
    ends = ["dropped", "ended_short", "verify_failed", "done"]
    # the program's segments of 16,384, and a client that commits every
    # round it may: both are correct
    for heights in ([16384, 40960, 40960, 65536],
                    [24576, 40960, 49999, 65536],
                    [0, 40960, 40960, 65536]):
        tries = _tries(*[(True, e, h) for e, h in zip(ends, heights)])
        assert M.violations(SCRIPT, tries, 65536, 512, True, 22) == []
    # the unreachable peers wherever the order puts them
    tries = _tries((False, "unreachable", 0), (True, "dropped", 16384),
                   (True, "ended_short", 40960), (False, "unreachable", 40960),
                   (True, "verify_failed", 40960), (True, "done", 65536))
    assert M.violations(SCRIPT, tries, 65536, 512, True, 22) == []


def test_the_model_refuses_what_no_correct_fail_over_leaves():
    def bad(tries, returned=True, peers=22):
        return M.violations(SCRIPT, tries, 65536, 512, returned, peers)

    good = [(True, "dropped", 16384), (True, "ended_short", 40960),
            (True, "verify_failed", 40960), (True, "done", 65536)]
    assert bad(_tries(*good)) == []
    # the parent's: a stream that ended short counted as the end
    assert bad(_tries(*good[:2])) == [
        "the request returned True with the store at 40960 of 65536"]
    assert any("returned false after 2 of 22" in v
               for v in bad(_tries(*good[:2]), returned=False))
    # a height above what the try was served, at or past the lie, below
    # the height before
    assert bad(_tries((True, "dropped", 24577), *good[1:]))
    assert bad(_tries(*good[:2], (True, "verify_failed", 50000), good[3]))
    assert bad(_tries(good[0], (True, "ended_short", 16000), *good[2:]))
    # a reason that is not the script's
    assert bad(_tries((True, "ended_short", 16384), *good[1:]))
    assert bad(_tries((False, "dropped", 0), *good))
    # a lie behind the height held is never served: the stream is sound
    assert M.served({"kind": "liar", "round": 50000}, 50001, 65536, 512) \
        == ("done", 65536)
    assert M.served({"kind": "corrupt_row", "round": 40961}, 40961, 65536,
                    512) == ("ended_short", 40960)
    assert M.served({"kind": "abort", "after_messages": 48}, 49153, 65536,
                    512) == ("done", 65536)


def test_the_model_of_a_group_in_which_every_live_peer_lies():
    script = [{"kind": "flips", "rounds": [700, 20000, 41000]}]
    tries = _tries(*[(True, "verify_failed", 0)] * 4,
                   *[(False, "unreachable", 0)] * 2)
    assert M.violations(script, tries, 65536, 512, False, 6) == []
    assert M.violations(script, tries[:4], 65536, 512, False, 6) == [
        "the request returned false after 4 of 6 peers"]
    assert M.violations(script, tries, 65536, 512, True, 6)
    worse = _tries((True, "verify_failed", 700), *[(True, "verify_failed",
                                                    700)] * 3)
    assert any("left the store at 700" in v for v in M.violations(
        script, worse, 65536, 512, False, 4))


# -- the stand ------------------------------------------------------------------

def test_a_lying_stream_flips_one_bit_of_its_round_and_no_other():
    from drand_tpu.chain.segment import PackedBeacons
    stand = D._Stand(None, None, 1024)
    stand.begin([{"kind": "liar", "round": 20}], {20: (5, 3), 99: (0, 0)})
    sigs = np.arange(16 * 48, dtype=np.uint8).reshape(16, 48)
    item = PackedBeacons(start_round=17, sigs=sigs.copy(), first_prev=b"",
                         chained=False)
    lied = stand._lie(item)
    diff = np.bitwise_xor(lied.sigs, sigs)
    assert np.count_nonzero(diff) == 1 and diff[3, 5] == 8
    assert (item.sigs == sigs).all()            # the store's rows stand
    assert stand.served == {20: lied.sigs[3].tobytes()}
    other = PackedBeacons(start_round=33, sigs=sigs.copy(), first_prev=b"",
                          chained=False)
    assert stand._lie(other) is other


def test_a_peers_store_is_asked_for_a_message_once_and_answers_as_it_did(
        tmp_path):
    from drand_tpu.chain.store import CorruptRowError, SqliteStore
    import sqlite3
    path = str(tmp_path / "serve.db")
    store = SqliteStore(path)
    sigs = np.arange(32 * 48, dtype=np.uint8).reshape(32, 48)
    H.fill_store(store, H.beacons_of(sigs, None))
    with sqlite3.connect(path) as conn:
        conn.execute("UPDATE beacons SET data = substr(data, 1, "
                     "length(data) - 1) WHERE round = 20")
    asked = []
    real = store.read_fields
    store.read_fields = lambda *key: asked.append(key) or real(*key)
    peer = D._Remembered(store)
    rows = peer.read_fields(1, 8)
    assert rows == real(1, 8) and peer.read_fields(1, 8) is rows
    for _ in range(2):                  # the error it raised, each time
        with pytest.raises(CorruptRowError) as caught:
            peer.read_fields(17, 8)
        assert caught.value.round == 20
    assert peer.read_fields(17, 3) == real(17, 3)   # the good prefix
    assert asked.count((1, 8)) == 1 and asked.count((17, 8)) == 1
    store.close()


def test_the_window_holds_the_traffics_operations_and_a_rehearsal_its_seconds(
        monkeypatch):
    import sys
    monkeypatch.setattr(sys, "argv", ["run.py", "--seed", "7"])
    traffic = H.load_json("traffic", "catchup-failover.json")
    config = {"backlog_rounds": 65536, "bucket_rounds": 16384,
              "env": {"DRAND_TPU_SYNC_WIRE_CHUNK": "512"}}
    ok, failed = {"ok": True}, {"ok": False}

    def driver(rounds):
        return D.Driver(H.Ctx(
            config=config, traffic=traffic,
            sigs=np.zeros((rounds, 48), np.uint8), prevs=None, group=None,
            spans=H.Spans(), verifier=None, workdir=""))
    timed = driver(65536)
    assert timed.wants_more([ok] * 9) and not timed.wants_more([ok] * 10)
    assert not timed.wants_more([ok, failed])       # never past a failure
    assert not driver(1024).wants_more([ok])        # a rehearsal


def test_an_unreachable_peer_refuses_the_connection():
    sock, address = D._unreachable()
    try:
        host, port = address.split(":")
        with pytest.raises(ConnectionRefusedError):
            socket.create_connection((host, int(port)), timeout=2)
    finally:
        sock.close()


def test_the_liars_bit_is_the_seeds(monkeypatch):
    import sys
    flips = set()
    for seed in (7, 2**31 + 12, 2**32 + 5):
        monkeypatch.setattr(sys, "argv", ["run.py", "--seed", str(seed)])
        ctx = H.Ctx(config={"backlog_rounds": 65536, "bucket_rounds": 16384,
                            "env": {"DRAND_TPU_SYNC_WIRE_CHUNK": "512"}},
                    traffic=H.load_json("traffic", "catchup-failover.json"),
                    sigs=np.zeros((65536, 48), np.uint8), prevs=None,
                    group=None, spans=H.Spans(), verifier=None, workdir="")
        driver = D.Driver(ctx)
        assert driver.seed == seed and driver.liar_rounds == [50000]
        assert driver.group_spec["peers"] == 22     # the mix's default
        assert 0 <= driver.liar_flip[0] < 48 and 0 <= driver.liar_flip[1] < 8
        assert driver.liar_flip == D.Driver(ctx).liar_flip
        assert {1, 16385, 32769, 40961, 49153} <= set(
            driver.segment_starts())
        flips.add(driver.liar_flip)
    assert len(flips) > 1


# -- the request's spans --------------------------------------------------------

def _record(with_request: bool):
    """One rejoin of 65,536 rounds as the program records it (the
    parent records the tries alone, each a root)."""
    from drand_tpu import tracing
    tracing.RECORDER.clear()
    root = tracing.begin_span("sync.request", at=100.0) if with_request \
        else None
    rows = [(16384, 0), (16384, 0), (8192, 8192), (16384, 0), (8192, 8192),
            (16384, 0), (8192, 8192)]
    for i, (n, pad) in enumerate(rows):
        tracing.record_span("verify.dispatch", 100.1 + i, 100.2 + i,
                            parent=root, n=n, bucket=16384, pad_rows=pad)
    if with_request:
        tracing.record_span("sync.probe", 100.0, 100.002, parent=root,
                            candidates=3, wall_s=0.002)
        for i in range(3):
            over = tracing.begin_span("sync.failover", parent=root,
                                      at=101.0 + i, reason="dropped")
            tracing.record_span("sync.probe", 101.0 + i, 101.003 + i,
                                parent=over, candidates=3, wall_s=0.003)
            over.set(wall_s=0.01).end(at=101.01 + i)
        root.set(tries=4, reached=True, rounds=65536, rounds_fetched=98304,
                 rounds_refetched=32768, rows_dispatched=90112,
                 rows_discarded=24576)
        root.end(at=107.5)
    return _Run(("dir", (100.0, 110.0), [], 131072), None)


def _read(run, name):
    spec = H.load_json("layer_metrics", name + ".json")
    reader = {"program_spans": program_spans, "span_attrs": span_attrs}[
        spec["reader"]]
    return reader.read(run, spec)


def test_the_requests_spans_as_their_files_describe_them():
    run = _record(with_request=True)
    # the seconds per 65,536 rounds of an operation of 131,072
    assert {name: _read(run, name) for name in METRICS} == pytest.approx({
        "sync.failover_s": 0.015, "sync.probe_s": 0.0055,
        "sync.refetched_share": 32768 / 98304,
        "verify.discarded_share": 24576 / 90112})
    assert _read(run, "verify.pad_share") == pytest.approx(3 * 8192
                                                           / (7 * 16384))


def test_a_program_without_the_request_gives_nothing_and_does_not_raise():
    """The parent commit: no `sync.request`, `sync.failover` or
    `sync.probe` is opened, and the four are left out of the line."""
    for run in (_record(with_request=False), _Run()):
        for name in METRICS:
            assert _read(run, name) is None


# -- the cell rehearsed ---------------------------------------------------------

def _rehearse(seed: int, verifier: str, trace: int = 0):
    return R._run("--workload", CELL, "--seed", str(seed), "--seconds", "1",
                  "--trace", str(trace), "--rehearse", verifier)


def test_the_cell_is_correct_on_the_host_tier():
    seed = 2**32 + 431
    proc, lines = _rehearse(seed, "host")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert all(v == limit for v, limit in last["checks"].values())
    assert {"window.sync_calls_not_true", "window.committed_rows_differing",
            "window.lies_in_a_store", "window.tries_the_model_does_not_allow",
            "verdicts.liars_round_judged_true", "faulted.sync_ok",
            "faulted.live_peers_not_tried",
            "faulted.committed_at_or_after_first_bad",
            "faulted.tries_the_model_does_not_allow"} <= set(last["checks"])
    assert set(last["metrics"]) == {"setup_s", "catchup_rate"}
    group, = [ln["group"] for ln in lines if "group" in ln]
    assert (group["peers"], group["live"], group["unreachable"],
            group["seed"]) == (22, 20, 2, seed)
    assert group["script"] == ["abort", "corrupt_row", "liar", "sound"]
    faulted, = [ln["faulted_pass"] for ln in lines if "faulted_pass" in ln]
    assert faulted["sync_ok"] is False and faulted["streams"] == 4
    assert [t["end"] for t in faulted["tries"]] \
        == ["verify_failed"] * 4 + ["unreachable"] * 2
    assert faulted["committed_rounds"] < faulted["first_bad_round"]


def test_the_cells_traced_line_holds_its_metrics():
    proc, lines = _rehearse(2**31 + 432, "host", trace=1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert lines[-1]["correct"] is True
    metrics = lines[-1]["metrics"]
    assert set(METRICS) | {"wire.fetch_s", "wire.recv_s", "loop.lag_s",
                           "store.put_s"} <= set(metrics)
    assert metrics["sync.failover_s"]["value"] > 0
    assert metrics["sync.probe_s"]["value"] > 0
    # 384 rounds thrown away by the drop (nothing was flushed yet), 384
    # by the lie, of 384 + 640 + 384 + 384 taken off the wire
    assert metrics["sync.refetched_share"]["value"] == pytest.approx(
        768 / 1792)
    # the liar's one segment of 384 rows, of 512 + 128 + 384 + 384
    assert metrics["verify.discarded_share"]["value"] == pytest.approx(
        384 / 1408)


@pytest.mark.parametrize("seed", [7, 2**31 + 433, 2**32 + 434])
def test_the_cell_is_not_correct_on_the_stub(seed):
    """3 of 3 seeds: the liar's segment passes the stub, so its try ends
    `done` with the flipped signature in the store, and the group in
    which every live peer lies is synced from."""
    proc, lines = _rehearse(seed, "stub")
    assert proc.returncode == 1
    held = {c["name"] for c in lines[-1]["not_held"]}
    assert lines[-1]["correct"] is False
    assert {"window.committed_rows_differing", "window.lies_in_a_store",
            "window.tries_the_model_does_not_allow",
            "verdicts.liars_round_judged_true", "faulted.sync_ok"} <= held

"""A chained scheme through the harness (ISSUE 28), on the 1,024-round
`default-chained` fixture and a short prefix of it: the derived
`previous_sig` column and the genesis row, the plain reference's chained
message against the program's host tier, both kinds of planted damage
with the two sets the reference expects of a scan, the rehearsal's host
verifier beside `ChainVerifier`'s linkage, and that an unchained
configuration gets what it got before (beacons, draws, comparison names:
pinned from the parent, 75560ec).  None is slow: the whole rehearsals of
the chained cells are `test_rehearsal.py`'s."""

import asyncio
import hashlib
import importlib
import json
import os
import struct

import numpy as np
import pytest

from benchmark import harness as H
from benchmark.harness import BENCH_DIR

CHAINED = os.path.join(BENCH_DIR, "tests", "chained", "default-chained.json")
SHORT = 48          # rounds of a driver's pass here: 6 ms a row on the host


def _config(name: str) -> dict:
    path = CHAINED if name == "default-chained" else os.path.join(
        BENCH_DIR, "configs", name + ".json")
    with open(path) as f:
        return json.load(f)


def _chain(config: dict, rounds: int):
    sigs = np.load(os.path.join(BENCH_DIR, "fixtures",
                                config["fixture"]["file"]))[:rounds]
    return np.ascontiguousarray(sigs), H.previous_sigs(config, sigs)


def _chain_verifier(config: dict):
    from drand_tpu.chain.scheme import scheme_by_id
    from drand_tpu.chain.verify import ChainVerifier
    return ChainVerifier(scheme_by_id(config["scheme_id"]),
                         bytes.fromhex(config["public_key_hex"]))


def _ctx(config: dict, traffic: str, rounds: int, workdir, stub=False):
    sigs, prevs = _chain(config, rounds)
    spans = H.Spans()
    verifier = H.StubVerifier(config["scheme_id"]) if stub \
        else H.HostVerifier(_chain_verifier(config))
    return H.Ctx(config=config,
                 traffic=H.load_json("traffic", traffic + ".json"),
                 sigs=sigs, prevs=prevs, group=H.group_of(config),
                 spans=spans, verifier=H.SpanVerifier(verifier, spans),
                 workdir=str(workdir))


def _pass(ctx: H.Ctx, faults) -> tuple[dict, dict]:
    """One operation and the faulted pass of the traffic's driver, in
    this process: (window comparisons, faulted comparisons)."""
    module = importlib.import_module(
        f"benchmark.drivers.{ctx.traffic['driver']}")

    async def go():
        driver = module.Driver(ctx)
        try:
            await driver.setup()
            rec = await driver.operate()
            assert rec["ok"], rec
            return (await driver.check_window([rec]),
                    await driver.check_faulted({"faults": faults}))
        finally:
            await driver.close()

    return asyncio.run(go())


# -- the stand ----------------------------------------------------------------

def test_previous_sigs_are_derived_and_round_one_links_to_the_genesis_seed(
        tmp_path):
    config = _config("default-chained")
    sigs, prevs = _chain(config, 1024)
    seed = bytes.fromhex(config["genesis_seed_hex"])
    assert seed == hashlib.sha256(
        b"drand-tpu-bench-chained-genesis").digest()
    assert len(prevs) == 1024 and prevs[0] == seed
    assert all(prevs[i] == sigs[i - 1].tobytes() for i in range(1, 1024))
    beacons = H.beacons_of(sigs, prevs)
    assert [b.round for b in beacons] == list(range(1, 1025))
    assert beacons[0].previous_sig == seed
    assert beacons[7].previous_sig == beacons[6].signature
    store = H.new_node_store(str(tmp_path / "node.db"), H.group_of(config))
    try:
        assert store.last().round == 0 and store.last().signature == seed
        H.fill_store(store, beacons[:SHORT])      # SchemeStore checks links
        rounds, got, got_prevs = H.stored_rows(store.insecure, SHORT, 96)
    finally:
        store.close()
    assert rounds.tolist() == list(range(1, SHORT + 1))
    assert H.rows_differing(got, got_prevs, sigs[:SHORT], prevs[:SHORT]) == 0
    got_prevs[5] = got_prevs[4]
    assert H.rows_differing(got, got_prevs, sigs[:SHORT], prevs[:SHORT]) == 1


@pytest.mark.parametrize("name", ["unchained-g2", "quicknet-g1"])
def test_an_unchained_configuration_gets_the_beacons_it_got(name, tmp_path):
    from drand_tpu.chain.beacon import Beacon
    config = _config(name)
    sigs, prevs = _chain(config, 64)
    assert prevs is None
    assert H.beacons_of(sigs) == H.beacons_of(sigs, prevs) == [
        Beacon(round=i + 1, signature=sigs[i].tobytes()) for i in range(64)]
    assert H.beacons_of(sigs[:2], None, [7, 9])[1] == Beacon(
        round=9, signature=sigs[1].tobytes(), previous_sig=b"")
    group = H.group_of(config)
    assert group.genesis_seed == b"genesis-seed-benchmark"    # the parent's
    store = H.new_node_store(str(tmp_path / "node.db"), group)
    try:
        assert store.last().signature == b"genesis-seed-benchmark"
        H.fill_store(store, H.beacons_of(sigs))
        _rounds, got, got_prevs = H.stored_rows(store.insecure, 64,
                                                sigs.shape[1])
    finally:
        store.close()
    assert got_prevs == [b""] * 64
    assert H.rows_differing(got, got_prevs, sigs, None) == 0


def test_the_draws_and_an_unchained_plant_are_the_parents():
    starts = [1, 513, 16897, 33281, 49665]
    blob = json.dumps([H.draw_check(s, 65536, starts, 512, 32, 3)
                       for s in range(2**31 + 5, 2**31 + 17)],
                      sort_keys=True)
    assert hashlib.sha256(blob.encode()).hexdigest() == \
        "4cdee6b279e6c8e2c8004f1b75dd0799f16803377465ce98a26106d34f8ca074"
    sigs = np.arange(5 * 96, dtype=np.uint8).reshape(5, 96)
    bad, bad_prevs = H.plant(sigs, [(2, 1000, 3), (5, 7, 0)])
    assert bad_prevs is None
    assert hashlib.sha256(bad.tobytes()).hexdigest() == \
        "12423d280801594bd30b503f10be1cf45827c5e70843df9d5860c26886f5ba9b"


# -- the planted faults -------------------------------------------------------

def test_a_fault_lands_in_one_field_of_one_row():
    config = _config("default-chained")
    sigs, prevs = _chain(config, 16)
    # byte 100 of 192 is byte 4 of the previous signature; round 1's is
    # the 32-byte seed, where byte 95 of the upper half is byte 95 % 32
    faults = [(1, 96 + 95, 0), (5, 3, 7), (9, 192 * 7 + 100, 2)]
    bad, bad_prevs = H.plant(sigs, faults, prevs)
    assert H.damaged_fields(faults, 96, True) == {
        1: {"previous_sig"}, 5: {"signature"}, 9: {"previous_sig"}}
    assert H.damaged_fields(faults, 96, False) == {
        r: {"signature"} for r in (1, 5, 9)}
    assert np.flatnonzero((bad != sigs).any(axis=1)).tolist() == [4]
    assert bad[4, 3] == sigs[4, 3] ^ 0x80
    assert [i for i in range(16) if bad_prevs[i] != prevs[i]] == [0, 8]
    assert bad_prevs[0][95 % 32] == prevs[0][95 % 32] ^ 1
    assert bad_prevs[8][4] == prevs[8][4] ^ 4
    assert bad_prevs[5] == prevs[5] == sigs[4].tobytes()   # row 6 keeps it
    assert (sigs == _chain(config, 16)[0]).all() and prevs[8] != bad_prevs[8]


def test_a_dozen_seeds_meet_both_fields_and_all_four_positions():
    fields, kinds = set(), set()
    for traffic, starts in (("catchup-deep", [1, 513]), ("restart-scan", [1])):
        cfg = H.load_json("traffic", traffic + ".json")
        for seed in range(2**31 + 102, 2**31 + 114):
            d = H.draw_check(seed, 1024, starts, cfg["ramp_rounds"],
                             cfg["check"]["samples"], cfg["check"]["faults"])
            kinds.add(d["kind"])
            for f in H.damaged_fields(d["faults"], 96, True).values():
                fields |= f
    assert fields == {"signature", "previous_sig"}
    assert kinds == {"ramp", "segment_first", "segment_last", "anywhere"}


# -- the plain reference ------------------------------------------------------

@pytest.mark.parametrize("round_", [1, 2, 513, 1024])
def test_the_references_chained_message_is_the_host_tiers(round_):
    config = _config("default-chained")
    sigs, prevs = _chain(config, 1024)
    cv = _chain_verifier(config)
    row, prev = sigs[round_ - 1:round_], prevs[round_ - 1]
    other = prevs[round_ % 1024]          # a true signature, of another row
    assert cv.digest_message(round_, prev) == hashlib.sha256(
        prev + struct.pack(">Q", round_)).digest()
    for given, want in ((prev, True), (other, False), (b"", False)):
        beacon, = H.beacons_of(row, [given], [round_])
        assert H.reference_verdicts(config, [round_], row,
                                    [given])[0] == want
        assert cv.verify_beacon(beacon) == want


def test_the_reference_finds_the_two_sets_and_the_scan_reports_them(tmp_path):
    """A flipped signature at r: r is false, r + 1 is unlinked and true by
    its own fields.  A flipped previous_sig at r: r is false and unlinked;
    the scan files it under `unlinked` and sends it to no verifier."""
    config = _config("default-chained")
    ctx = _ctx(config, "restart-scan", SHORT, tmp_path)
    faults = [(7, 5, 1), (20, 96 + 11, 6), (SHORT, 40, 0)]
    bad, bad_prevs = H.plant(ctx.sigs, faults, ctx.prevs)
    invalid, unlinked = H.reference_findings(config, bad, bad_prevs,
                                             {7, 20, SHORT})
    assert invalid == {7, 20, SHORT} and unlinked == {8, 20}
    assert H.reference_verdicts(config, [8], bad[7:8], [bad_prevs[7]])[0]
    assert H.reference_findings(config, ctx.sigs, ctx.prevs, {7}) == (
        set(), set())
    window, faulted = _pass(ctx, faults)
    assert window == {"window.store_missing_rounds": 0,
                      "window.stored_rows_differing": 0}
    assert faulted == {"faulted.bad_sigs_missed": 0,
                       "faulted.bad_sigs_spurious": 0,
                       "faulted.unlinked_spurious": 0,
                       "faulted.verified_tip_off_by": 0,
                       "faulted.other_findings": 0}


def test_a_scan_that_checks_less_fails_the_chained_comparisons(tmp_path):
    """The control: the stub verifier passes the flipped signature, so
    round 7 is in neither list; the linkage, which the scan judges
    itself, is still reported."""
    ctx = _ctx(_config("default-chained"), "restart-scan", SHORT, tmp_path,
               stub=True)
    _window, faulted = _pass(ctx, [(7, 5, 1), (20, 96 + 11, 6)])
    assert faulted["faulted.bad_sigs_missed"] == 1
    assert faulted["faulted.verified_tip_off_by"] == 1
    assert faulted["faulted.unlinked_spurious"] == 0


# -- the catch-up -------------------------------------------------------------

@pytest.mark.parametrize("faults,sync_ok,committed", [
    # a flipped signature: the catch-up fails and commits nothing past it
    ([(30, 5, 1)], False, None),
    # a flipped previous_sig inside a run of rows: the packed wire carries
    # no such column, the consumer links the row to its own tail and
    # commits the chain as it is
    ([(30, 96 + 5, 1)], True, SHORT),
    # both: the signature decides
    ([(12, 96 + 5, 1), (30, 5, 1)], False, None),
])
def test_a_faulted_catch_up_commits_the_chain_or_nothing_past_the_damage(
        faults, sync_ok, committed, tmp_path, capsys):
    ctx = _ctx(_config("default-chained"), "catchup-deep", SHORT, tmp_path)
    window, faulted = _pass(ctx, faults)
    assert window == {"window.stores_missing_rounds": 0,
                      "window.wire_messages_without_commit": 0,
                      "window.committed_rows_differing": 0}
    assert faulted == {"faulted.sync_ok": 0,
                       "faulted.committed_at_or_after_first_bad": 0,
                       "faulted.committed_out_of_order": 0,
                       "faulted.committed_rows_differing": 0}
    line = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith('{"faulted_pass"')][-1]["faulted_pass"]
    assert line["sync_ok"] is sync_ok
    if committed is not None:
        assert line["committed_rounds"] == committed
    else:
        assert line["committed_rounds"] < 30


def test_a_consumer_that_checks_less_fails_the_chained_catch_up(tmp_path):
    ctx = _ctx(_config("default-chained"), "catchup-deep", SHORT, tmp_path,
               stub=True)
    _window, faulted = _pass(ctx, [(30, 5, 1)])
    assert faulted["faulted.sync_ok"] == 1
    assert faulted["faulted.committed_at_or_after_first_bad"] == SHORT - 29
    # round 30's signature, and round 31's previous_sig made from it
    assert faulted["faulted.committed_rows_differing"] == 2


# -- the rehearsal's verifier -------------------------------------------------

def test_the_host_verifier_links_a_segment_as_the_chain_verifier_does():
    """Up to 32 rows `ChainVerifier` itself stays on the host, so its two
    segment entries can stand beside the rehearsal's."""
    from drand_tpu.chain.segment import PackedBeacons
    config = _config("default-chained")
    sigs, prevs = _chain(config, 24)
    cv = _chain_verifier(config)
    host = H.HostVerifier(cv)
    beacons = H.beacons_of(sigs, prevs)
    anchor, wrong = prevs[0], prevs[3]
    bad, bad_prevs = H.plant(sigs, [(5, 2, 2), (11, 96 + 2, 2)], prevs)
    cases = [(beacons, anchor), (beacons, wrong),
             (H.beacons_of(bad, bad_prevs), anchor),
             (beacons[8:], prevs[8]), (beacons[8:], anchor)]
    for seg, given in cases:
        want = cv.verify_chain_segment_async(seg, given)()
        got = host.verify_chain_segment_async(seg, given)()
        assert got.tolist() == want.tolist()
    assert host.verify_chain_segment_async(
        H.beacons_of(bad, bad_prevs), anchor)().tolist() == [
            i not in (4, 5, 10) for i in range(24)]
    for rows, start, given, first_prev in (
            (sigs, 1, anchor, b"not the consumer's to trust"),
            (sigs, 1, wrong, anchor), (bad, 1, anchor, anchor),
            (sigs[8:], 9, prevs[8], b"")):
        packed = PackedBeacons(start_round=start, sigs=rows,
                               first_prev=first_prev, chained=True)
        want = cv.verify_packed_segment_async(packed, given)()
        got = host.verify_packed_segment_async(packed, given)()
        assert got.tolist() == want.tolist()
    assert host.verify_beacons(H.beacons_of(bad, bad_prevs)).tolist() == [
        i not in (4, 10) for i in range(24)]


# -- what an unchained cell is held to ----------------------------------------

PARENT_NAMES = {
    "catchup-deep": (
        ["window.stores_missing_rounds",
         "window.wire_messages_without_commit",
         "window.committed_rows_differing"],
        ["faulted.sync_ok", "faulted.committed_at_or_after_first_bad",
         "faulted.committed_out_of_order",
         "faulted.committed_rows_differing"]),
    "restart-scan": (
        ["window.store_missing_rounds", "window.stored_rows_differing"],
        ["faulted.bad_sigs_missed", "faulted.bad_sigs_spurious",
         "faulted.verified_tip_off_by", "faulted.other_findings"]),
}


@pytest.mark.parametrize("name,traffic", [
    ("unchained-g2", "catchup-deep"), ("quicknet-g1", "restart-scan"),
    ("quicknet-g1", "catchup-deep"), ("unchained-g2", "restart-scan")])
def test_an_unchained_cell_is_held_to_the_parents_comparisons(
        name, traffic, tmp_path):
    """The names, in the parent's order, every one 0 on the host tier;
    with the stub in its place the same names, the faulted ones not 0."""
    ctx = _ctx(_config(name), traffic, SHORT, tmp_path / "host")
    os.makedirs(ctx.workdir)
    faults = [(7, 1000, 3), (30, 192 + 100, 0)]     # both in the signature
    window, faulted = _pass(ctx, faults)
    assert (list(window), list(faulted)) == PARENT_NAMES[traffic]
    assert not any(window.values()) and not any(faulted.values())
    stub = _ctx(_config(name), traffic, SHORT, tmp_path / "stub", stub=True)
    os.makedirs(stub.workdir)
    window, faulted = _pass(stub, faults)
    assert (list(window), list(faulted)) == PARENT_NAMES[traffic]
    assert not any(window.values()) and any(faulted.values())

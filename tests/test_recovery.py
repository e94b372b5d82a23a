"""Crash-safe chain storage (ISSUE 15, drand_tpu/chain/recovery.py).

Pins the durability + recovery contracts end to end, jax-free:

  - durable commits: WAL + explicit synchronous pragma, atomic save_to,
    and damaged rows surfacing as CorruptRowError (round attached) on
    every read path instead of a blind CodecError;
  - the startup scan: gaps, torn writes, round-field bit flips, broken
    prev-sig linkage and (via a fake verifier) bad BLS signatures each
    land in their own IntegrityReport bucket with the right
    verified_tip;
  - repair: damaged rounds quarantined with forensic reasons, the tip
    rolled back, the quarantine counter bumped, and a re-scan coming
    back clean;
  - codec fuzz: a mutated stored row either raises CodecError or
    decodes to exactly the bytes on disk — never a silently-wrong
    beacon;
  - the serve side: a corrupt row ends a sync stream cleanly after the
    last good round (both the chunked and the per-beacon wire).
"""

import asyncio
import json
import random

import numpy as np
import pytest

from drand_tpu.chain import codec
from drand_tpu.chain import recovery
from drand_tpu.chain.beacon import Beacon
from drand_tpu.chain.store import CorruptRowError, SqliteStore, StoreError
from drand_tpu.chaos import faults


def _beacons(n, sig_len=48, start=1, prev=b"\x07" * 32):
    out = []
    for i in range(n):
        sig = bytes([(start + i) % 256]) * sig_len
        out.append(Beacon(round=start + i, signature=sig,
                          previous_sig=prev))
        prev = sig
    return out


def _chain_db(tmp_path, n=10, name="c.db"):
    path = str(tmp_path / name)
    s = SqliteStore(path)
    s.put_many(_beacons(n))
    return s, path


def _scan(store, verifier=None, **kw):
    return asyncio.run(recovery.scan_store(store, verifier, **kw))


# -- durable commits -------------------------------------------------------

def test_wal_and_synchronous_pragma(tmp_path, monkeypatch):
    s = SqliteStore(str(tmp_path / "w.db"))
    conn = s._conn()
    assert conn.execute("PRAGMA journal_mode").fetchone()[0] == "wal"
    assert conn.execute("PRAGMA synchronous").fetchone()[0] == 1  # NORMAL
    s.close()
    monkeypatch.setenv("DRAND_TPU_STORE_SYNC", "FULL")
    s2 = SqliteStore(str(tmp_path / "f.db"))
    assert s2._conn().execute("PRAGMA synchronous").fetchone()[0] == 2
    s2.close()


def test_save_to_atomic_copy(tmp_path):
    s, _ = _chain_db(tmp_path, 5)
    out = str(tmp_path / "backup.db")
    s.save_to(out)
    s.close()
    copy = SqliteStore(out)
    assert copy.last().round == 5
    assert not list(tmp_path.glob("backup.db.*")), "tmp file leaked"
    copy.close()


def test_corrupt_row_raises_typed_error_on_every_read_path(tmp_path):
    s, path = _chain_db(tmp_path, 8)
    faults.torn_write(path, 5)
    with pytest.raises(CorruptRowError) as ei:
        s.get(5)
    assert ei.value.round == 5
    assert isinstance(ei.value, StoreError)
    with pytest.raises(CorruptRowError):
        list(s.iter_range(1))
    with pytest.raises(CorruptRowError):
        s.read_fields(1, 100)
    # rounds below the damage stay readable
    assert s.get(4).round == 4
    # the recovery feed must NOT die on the damaged blob
    assert len(s.raw_rows(0, 100)) == 8
    s.close()


# -- the startup scan ------------------------------------------------------

def test_scan_clean_chain(tmp_path):
    s, _ = _chain_db(tmp_path, 12)
    rep = _scan(s)
    assert rep.ok and not rep.verify_checked
    assert (rep.first_round, rep.tip_round) == (1, 12)
    assert rep.verified_tip == 12 and rep.scanned == 12
    s.close()


def test_scan_empty_store(tmp_path):
    s = SqliteStore(str(tmp_path / "e.db"))
    rep = _scan(s)
    assert rep.ok and rep.scanned == 0 and rep.verified_tip == -1
    s.close()


def test_scan_flags_gap(tmp_path):
    path = str(tmp_path / "g.db")
    s = SqliteStore(path)
    bs = _beacons(8)
    s.put_many(bs[:3])
    for b in bs[5:]:
        s.put(b)
    rep = _scan(s)
    assert rep.missing == [(4, 5)]
    assert rep.verified_tip == 3
    assert not rep.corrupt and not rep.unlinked
    s.close()


def test_scan_flags_torn_write_and_round_flip(tmp_path):
    s, path = _chain_db(tmp_path, 10)
    faults.torn_write(path, 7)           # header cut mid-row
    faults.bit_rot(path, 4, offset=3)    # flip inside the round field
    rep = _scan(s)
    assert sorted(rep.corrupt) == [4, 7]
    assert rep.verified_tip == 3
    s.close()


def test_scan_flags_broken_linkage(tmp_path):
    s, path = _chain_db(tmp_path, 9)
    faults.bit_rot(path, 6)              # last byte = inside previous_sig
    rep = _scan(s)
    assert rep.unlinked == [6] and not rep.corrupt
    assert rep.verified_tip == 5
    # the row's own sig stays a linkage anchor: 7..9 are not flagged
    assert rep.tip_round == 9
    s.close()


class _RecordingVerifier:
    """The two entry points `scan_store` uses, with every dispatch and
    every resolver call written down in the order it happened.  `bad`
    rounds verify false; a packed segment that starts at `dispatch_raises`
    is refused at its dispatch, one that starts at `resolver_raises` by
    its resolver."""

    def __init__(self, bad=(), dispatch_raises=None, resolver_raises=None,
                 rows_raise=None):
        self.bad = set(bad)
        self.dispatch_raises = dispatch_raises
        self.resolver_raises = resolver_raises
        self.rows_raise = rows_raise    # a batch of rows that begins here
        self.calls: list[tuple[str, int]] = []
        self.out: set[int] = set()          # dispatched, not resolved
        self.most_out = 0

    def verify_packed_segment_async(self, packed, anchor):
        start = int(packed.start_round)
        if start == self.dispatch_raises:
            raise RuntimeError(f"no dispatch at {start}")
        self.calls.append(("dispatch", start))
        self.out.add(start)
        self.most_out = max(self.most_out, len(self.out))
        ok = np.array([int(r) not in self.bad for r in packed.rounds()],
                      dtype=bool)

        def resolve():
            self.calls.append(("resolve", start))
            self.out.discard(start)
            if start == self.resolver_raises:
                raise RuntimeError(f"no verdicts at {start}")
            return ok
        return resolve

    def verify_beacons(self, beacons):
        self.calls.append(("singles", beacons[0].round))
        if beacons[0].round == self.rows_raise:
            raise RuntimeError(f"no verdicts of the rows at {self.rows_raise}")
        return np.array([b.round not in self.bad for b in beacons],
                        dtype=bool)


class _AsyncRowsVerifier(_RecordingVerifier):
    """One that can dispatch a batch of rows ahead, as `ChainVerifier`
    does (`verify_beacons_async`)."""

    def verify_beacons_async(self, beacons):
        start = beacons[0].round
        self.calls.append(("dispatch_rows", start))
        self.out.add(start)
        self.most_out = max(self.most_out, len(self.out))
        ok = np.array([b.round not in self.bad for b in beacons], dtype=bool)

        def resolve():
            self.calls.append(("resolve", start))
            self.out.discard(start)
            return ok
        return resolve


def test_scan_bls_stage_flags_bad_signature(tmp_path):
    s, _ = _chain_db(tmp_path, 8)
    rep = _scan(s, _RecordingVerifier([5]))
    assert rep.verify_checked
    assert rep.bad_sigs == [5] and rep.verified_tip == 4
    clean = _scan(s, _RecordingVerifier())
    assert clean.ok and clean.verified_tip == 8
    s.close()


# -- repair ----------------------------------------------------------------

def test_repair_quarantines_and_rolls_back(tmp_path):
    from drand_tpu.metrics import REGISTRY
    s, path = _chain_db(tmp_path, 10)
    faults.torn_write(path, 6)
    before = REGISTRY.get_sample_value("drand_store_quarantined_total") or 0
    rep = _scan(s)
    summary = recovery.repair_store(s, rep)
    assert summary == {"quarantined": 1, "truncated": 4, "verified_tip": 5}
    assert s.last().round == 5
    q = dict(s.quarantined())
    assert q[6] == "corrupt-row"
    assert set(q) == {6, 7, 8, 9, 10}
    assert all(r == "rollback-past-verified-prefix"
               for k, r in q.items() if k != 6)
    after = REGISTRY.get_sample_value("drand_store_quarantined_total") or 0
    assert after - before == 5
    # forensic payload survives, and a re-scan comes back clean
    assert any(r == 6 and data for r, data, _ in s.quarantined_rows())
    assert _scan(s).ok
    s.close()


def test_startup_recovery_sets_gauge_and_skips_clean(tmp_path):
    from drand_tpu.metrics import REGISTRY

    def gauge():
        return REGISTRY.get_sample_value("drand_store_integrity",
                                         {"beacon_id": "t-recov"})

    s, path = _chain_db(tmp_path, 6)
    rep, summary = asyncio.run(
        recovery.startup_recovery(s, None, beacon_id="t-recov"))
    assert rep.ok and summary is None and gauge() == 1
    faults.bit_rot(path, 3, offset=3)
    rep, summary = asyncio.run(
        recovery.startup_recovery(s, None, beacon_id="t-recov"))
    assert not rep.ok and gauge() == 0
    assert summary["verified_tip"] == 2 and s.last().round == 2
    s.close()


# -- codec fuzz ------------------------------------------------------------

def test_codec_fuzz_never_silently_wrong(tmp_path):
    """Random single-byte flips and truncations of a binary row either
    raise CodecError or decode to EXACTLY the mutated bytes (canonical
    re-encode) — a damaged row can never alias to a different valid
    beacon without the difference being on disk."""
    rng = random.Random(1234)
    base = codec.encode_beacon(_beacons(1)[0])
    for _ in range(300):
        blob = bytearray(base)
        if rng.random() < 0.5:
            blob[rng.randrange(len(blob))] ^= rng.randrange(1, 256)
        else:
            blob = blob[:rng.randrange(len(blob))]
        blob = bytes(blob)
        try:
            r, sig, prev = codec.decode_fields(blob)
        except codec.CodecError:
            continue
        assert codec.encode_fields(r, sig, prev) == blob


def test_scan_survives_arbitrary_row_garbage(tmp_path):
    """Fuzzed stored rows never crash the scan: every mutation is either
    flagged (corrupt/unlinked) or bit-identical to a clean decode."""
    rng = random.Random(99)
    import sqlite3
    for trial in range(20):
        path = str(tmp_path / f"fz{trial}.db")
        s = SqliteStore(path)
        s.put_many(_beacons(6))
        victim = rng.randrange(1, 7)
        conn = sqlite3.connect(path)
        with conn:
            blob = bytearray(conn.execute(
                "SELECT data FROM beacons WHERE round=?",
                (victim,)).fetchone()[0])
            blob[rng.randrange(len(blob))] ^= rng.randrange(1, 256)
            conn.execute("UPDATE beacons SET data=? WHERE round=?",
                         (bytes(blob), victim))
        conn.close()
        rep = _scan(s)          # must not raise
        assert rep.scanned == 6
        s.close()


# -- the serve side --------------------------------------------------------

def _collect(gen):
    async def run():
        out = []
        async for item in gen:
            out.append(item)
        return out
    return asyncio.run(run())


def _rounds(items):
    out = []
    for it in items:
        out.extend(it.rounds() if hasattr(it, "rounds") else [it.round])
    return out


def test_serve_sync_chain_stops_cleanly_at_corruption(tmp_path):
    from drand_tpu.beacon.sync_manager import serve_sync_chain
    s, path = _chain_db(tmp_path, 10)
    faults.torn_write(path, 6)
    chunked = _collect(serve_sync_chain(s, 1, chunk_size=4))
    assert _rounds(chunked) == [1, 2, 3, 4, 5]
    per_beacon = _collect(serve_sync_chain(s, 1, chunk_size=0))
    assert _rounds(per_beacon) == [1, 2, 3, 4, 5]
    s.close()


# -- the offline fsck CLI --------------------------------------------------

def test_util_fsck_repairs_and_reports_json(tmp_path, capsys):
    from drand_tpu.cli.main import main as cli_main
    s, path = _chain_db(tmp_path, 9)
    s.close()
    faults.torn_write(path, 4)
    with pytest.raises(SystemExit) as ei:
        cli_main(["util", "fsck", path, "--repair", "--json"])
    assert ei.value.code == 1          # damage found (and repaired)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["corrupt"] == [4] and out["verified_tip"] == 3
    assert out["repair"]["quarantined"] == 1
    with pytest.raises(SystemExit) as ei:
        cli_main(["util", "fsck", path, "--json"])
    assert ei.value.code == 0          # clean after repair
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["ok"] and out["tip_round"] == 3


# -- the scan's span tree (ISSUE 25; the order of a flush: ISSUE 35) ----------

def _scan_spans():
    from drand_tpu import tracing
    return [sp for sp in tracing.RECORDER.spans()
            if sp.name not in ("gc.full", "loop.lag")]


def test_a_scan_is_one_trace_whose_self_times_add_up(tmp_path):
    from benchmark.readers.program_spans import self_seconds
    from drand_tpu import tracing
    s, _ = _chain_db(tmp_path, 40)
    tracing.RECORDER.clear()
    rep = _scan(s, _RecordingVerifier([23]), segment_rounds=16, read_batch=16)
    s.close()
    assert rep.bad_sigs == [23] and rep.scanned == 40
    spans = _scan_spans()
    by_id = {sp.span_id: sp for sp in spans}
    (root,) = [sp for sp in spans if sp.parent_id is None]
    assert root.name == "store.scan"
    assert root.attrs["scanned"] == 40 and root.attrs["flagged"] == 1
    assert {sp.trace_id for sp in spans} == {root.trace_id}
    names = [sp.name for sp in spans]
    # 40 rows in batches of 16: three reads with rows and the empty one
    # that ends the scan; a flush for every 16 good rows and the tail
    assert names.count("scan.read") == 4 and names.count("scan.decode") == 3
    assert names.count("scan.flush") == 3
    assert names.count("scan.pack") == names.count("scan.verify_wait") == 3
    assert set(names) == {"store.scan", "scan.read", "scan.decode",
                          "scan.flush", "scan.pack", "scan.verify_wait"}
    assert len(spans) <= 6 * 4 + 1          # a handful a batch, not a row
    assert sum(sp.attrs["rows"] for sp in spans
               if sp.name == "scan.decode") == 40
    assert sum(sp.attrs["rows"] for sp in spans
               if sp.name == "scan.flush") == 40
    for sp in spans:
        if sp.parent_id is not None:
            p = by_id[sp.parent_id]
            assert p.start_mono - 1e-6 <= sp.start_mono, (sp.name, p.name)
            assert sp.start_mono + sp.duration_s \
                <= p.start_mono + p.duration_s + 1e-6, (sp.name, p.name)
    for p in spans:         # no two siblings overlap
        kids = sorted((sp.start_mono, sp.start_mono + sp.duration_s)
                      for sp in spans if sp.parent_id == p.span_id)
        for (_, end), (start, _) in zip(kids, kids[1:]):
            assert end <= start + 1e-6, p.name
    # a flush inside the row loop is the decode's child, the last one the
    # root's: either way no two siblings overlap, so the self times of
    # the tree add up to the scan
    flushes = sorted((sp for sp in spans if sp.name == "scan.flush"),
                     key=lambda sp: sp.start_mono)
    assert [by_id[sp.parent_id].name for sp in flushes] \
        == ["scan.decode", "scan.decode", "store.scan"]
    # a wait is opened where the scan blocks: in the flush AFTER the one
    # it waits for, which has dispatched by then; the last under the root
    waits = sorted((sp for sp in spans if sp.name == "scan.verify_wait"),
                   key=lambda sp: sp.start_mono)
    assert [sp.parent_id for sp in waits] \
        == [flushes[1].span_id, flushes[2].span_id, root.span_id]
    for flush, wait in zip(flushes[1:], waits):
        (pack,) = [sp for sp in spans if sp.name == "scan.pack"
                   and sp.parent_id == flush.span_id]
        assert pack.start_mono + pack.duration_s <= wait.start_mono
    own = self_seconds([(sp.span_id, sp.parent_id, sp.name, sp.start_mono,
                         sp.start_mono + sp.duration_s) for sp in spans])
    assert all(v >= -1e-9 for v in own.values())
    assert sum(own.values()) == pytest.approx(root.duration_s, rel=0.02)
    assert rep.elapsed_s <= root.duration_s


# -- one segment dispatched ahead (ISSUE 35) ---------------------------------

def test_a_flush_dispatches_before_it_settles_the_one_before(tmp_path):
    s, _ = _chain_db(tmp_path, 72)
    v = _RecordingVerifier()
    rep = _scan(s, v, segment_rounds=16, read_batch=10)
    s.close()
    assert rep.ok and rep.verified_tip == 72
    starts = [1, 17, 33, 49, 65]                  # four full, a tail of 8
    want = [("dispatch", starts[0])]
    for before, start in zip(starts, starts[1:]):
        want += [("dispatch", start), ("resolve", before)]
    want.append(("resolve", starts[-1]))
    assert v.calls == want
    assert v.most_out == 2 and not v.out


def _sequential_report(store, verifier, segment_rounds) -> dict:
    """The plain scan the dispatching one has to equal: every row read
    and judged, then every segment of good rows packed, verified and
    filed before the next is looked at."""
    report = recovery.IntegrityReport(path=store.path, verify_checked=True)
    rows = store.raw_rows(0, 1 << 30)
    good, expected, prev_good = [], None, None
    for r, blob in rows:
        report.scanned += 1
        if report.first_round < 0:
            report.first_round = r
        report.tip_round = r
        if expected is not None and r > expected:
            report.missing.append((expected, r - 1))
        expected = r + 1
        try:
            decoded, sig, prev = codec.decode_fields(blob)
            if decoded != r:
                raise codec.CodecError("round")
        except codec.CodecError:
            report.corrupt.append(r)
            prev_good = None
            continue
        linked = not (prev and prev_good is not None
                      and prev_good[0] == r - 1 and prev != prev_good[1])
        prev_good = (r, sig)
        if not linked:
            report.unlinked.append(r)
        elif r != 0:
            good.append((r, sig, prev))
    from drand_tpu.chain.beacon import Beacon
    from drand_tpu.chain.segment import PackedBeacons, pack_rows
    for at in range(0, len(good), segment_rounds):
        flush = good[at:at + segment_rounds]
        items = list(pack_rows(flush, max_chunk=segment_rounds))
        if len(items) == 1 and isinstance(items[0], PackedBeacons):
            ok = verifier.verify_packed_segment_async(
                items[0], items[0].first_prev)()
        else:
            # what damage leaves of a flush: one batch of rows, in order
            ok = verifier.verify_beacons(
                [Beacon(round=r, signature=sig, previous_sig=prev)
                 for r, sig, prev in flush])
        report.bad_sigs += [r for (r, _s, _p), good_ in zip(flush, ok)
                            if not good_]
    problems = (report.corrupt + report.unlinked + report.bad_sigs
                + [a for a, _ in report.missing])
    report.verified_tip = -1 if not rows else (
        min(problems) - 1 if problems else report.tip_round)
    return report.to_dict()


# 72 rounds in segments of 16: the first is rounds 1-16, a middle one
# 33-48, the last (a tail of 8) 65-72
_DAMAGE = {
    "bad_sig_first_segment_first_row": dict(bad=[1]),
    "bad_sig_first_segment_last_row": dict(bad=[16]),
    "bad_sig_middle_segment_first_row": dict(bad=[33]),
    "bad_sig_middle_segment_last_row": dict(bad=[48]),
    "bad_sig_last_segment_first_row": dict(bad=[65]),
    "bad_sig_last_segment_last_row": dict(bad=[72]),
    "bad_sigs_in_every_segment": dict(bad=[72, 5, 49, 17, 32, 64]),
    # a corrupt row cuts its flush in two runs, two of them two rows
    # apart leave a row alone between them: the flush goes as ONE batch
    # of rows, and its bad rounds are filed in round order
    "corrupt_rows_split_a_segment": dict(torn=[20, 22, 40], bad=[21, 23, 41]),
    # an unlinked row is not verified and leaves a hole in its segment
    "an_unlinked_row_splits_a_segment": dict(rot=[37], bad=[36, 38]),
    "corrupt_unlinked_and_bad_together": dict(
        torn=[3, 5, 70], rot=[18, 52], bad=[4, 19, 51, 53, 71, 72]),
    "a_gap": dict(drop=[30, 31], bad=[29, 32]),
    "shorter_than_one_segment": dict(n=9, bad=[4]),
    "exactly_one_segment": dict(n=16, bad=[16]),
    "an_empty_store": dict(n=0),
}


@pytest.mark.parametrize("case", sorted(_DAMAGE))
def test_the_report_is_the_sequential_scans(tmp_path, case):
    damage = _DAMAGE[case]
    path = str(tmp_path / "d.db")
    s = SqliteStore(path)
    s.put_many([b for b in _beacons(damage.get("n", 72))
                if b.round not in damage.get("drop", ())])
    for r in damage.get("torn", ()):
        faults.torn_write(path, r)
    for r in damage.get("rot", ()):
        faults.bit_rot(path, r)          # last byte: inside previous_sig
    bad = damage.get("bad", ())
    want = _sequential_report(s, _RecordingVerifier(bad), 16)
    v = _RecordingVerifier(bad)
    got = _scan(s, v, segment_rounds=16, read_batch=10).to_dict()
    s.close()
    got.pop("elapsed_s"), want.pop("elapsed_s")
    assert got == want
    assert sorted(got["corrupt"]) == sorted(damage.get("torn", ()))
    assert sorted(got["unlinked"]) == sorted(damage.get("rot", ()))
    assert sorted(got["bad_sigs"]) == sorted(
        r for r in bad if r <= damage.get("n", 72))
    # two flushes out at most: two dispatches where damage splits none
    split = {"torn", "rot", "drop"} & set(damage)
    assert not v.out and (split or v.most_out <= 2)


@pytest.mark.parametrize("fault", [
    dict(dispatch_raises=1), dict(dispatch_raises=33),
    dict(dispatch_raises=65), dict(resolver_raises=1),
    dict(resolver_raises=33), dict(resolver_raises=65)],
    ids=lambda f: "-".join(f"{k}_at_{v}" for k, v in f.items()))
def test_a_scan_that_fails_leaves_no_resolver_behind(tmp_path, fault):
    from drand_tpu import tracing
    s, _ = _chain_db(tmp_path, 72)
    v = _RecordingVerifier(**fault)
    tracing.RECORDER.clear()
    with pytest.raises(RuntimeError, match="no (dispatch|verdicts) at"):
        _scan(s, v, segment_rounds=16, read_batch=10)
    s.close()
    dispatched = [at for what, at in v.calls if what == "dispatch"]
    resolved = [at for what, at in v.calls if what == "resolve"]
    assert not v.out and resolved == dispatched     # each once, in order
    spans = _scan_spans()
    # every span of the scan is ended (the recorder holds ended spans
    # only) and the failure is written on the root
    (root,) = [sp for sp in spans if sp.name == "store.scan"]
    assert root.status == "error"
    assert sum(sp.name == "scan.flush" for sp in spans) \
        == len(dispatched) + ("dispatch_raises" in fault)


def test_a_split_flush_that_fails_resolves_what_is_dispatched(tmp_path):
    # the second flush is two runs (a corrupt row between them) and goes
    # as one batch of rows, which this verifier judges when the flush is
    # settled; that fails with the third flush already out
    s, path = _chain_db(tmp_path, 72)
    faults.torn_write(path, 24)
    v = _RecordingVerifier(rows_raise=17)
    with pytest.raises(RuntimeError, match="no verdicts of the rows at 17"):
        _scan(s, v, segment_rounds=16, read_batch=10)
    s.close()
    assert v.calls == [("dispatch", 1), ("resolve", 1), ("dispatch", 34),
                       ("singles", 17), ("resolve", 34)]
    assert not v.out


def test_a_split_flush_is_one_dispatch_of_rows_one_ahead(tmp_path):
    # a verifier that can dispatch rows ahead gets the split flush as ONE
    # dispatch, before the flush before it is awaited
    s, path = _chain_db(tmp_path, 72)
    faults.torn_write(path, 24)
    faults.torn_write(path, 26)
    v = _AsyncRowsVerifier(bad=[25, 30])
    rep = _scan(s, v, segment_rounds=16, read_batch=10)
    s.close()
    assert rep.corrupt == [24, 26] and rep.bad_sigs == [25, 30]
    assert v.calls[:4] == [("dispatch", 1), ("dispatch_rows", 17),
                           ("resolve", 1), ("dispatch", 35)]
    assert [c for c in v.calls if c[0] == "dispatch_rows"] \
        == [("dispatch_rows", 17)]
    assert not v.out and v.most_out == 2


def test_a_cancelled_scan_leaves_no_resolver_behind(tmp_path):
    import threading
    s, _ = _chain_db(tmp_path, 72)
    v = _RecordingVerifier()
    waiting, go = threading.Event(), threading.Event()
    dispatch = v.verify_packed_segment_async

    def slow(packed, anchor):
        resolver = dispatch(packed, anchor)

        def resolve():
            waiting.set()
            go.wait(10)
            return resolver()
        return resolve
    v.verify_packed_segment_async = slow

    async def run():
        task = asyncio.ensure_future(recovery.scan_store(
            s, v, segment_rounds=16, read_batch=10))
        await asyncio.to_thread(waiting.wait, 10)   # the first wait is on
        task.cancel()
        await asyncio.sleep(0.05)
        assert not task.done()      # it waits its worker out
        go.set()
        with pytest.raises(asyncio.CancelledError):
            await task
    asyncio.run(run())
    s.close()
    assert v.calls == [("dispatch", 1), ("dispatch", 17),
                       ("resolve", 1), ("resolve", 17)]
    assert not v.out


@pytest.mark.parametrize("rounds,flushes", [(64, 4), (72, 5), (9, 1)])
def test_the_flush_says_what_was_in_flight(tmp_path, rounds, flushes):
    from drand_tpu import tracing
    s, _ = _chain_db(tmp_path, rounds)
    tracing.RECORDER.clear()
    assert _scan(s, _RecordingVerifier(), segment_rounds=16,
                 read_batch=10).ok
    s.close()
    spans = _scan_spans()
    in_flight = [sp.attrs["in_flight"] for sp in
                 sorted((sp for sp in spans if sp.name == "scan.flush"),
                        key=lambda sp: sp.start_mono)]
    assert in_flight == [0] + [1] * (flushes - 1)
    (root,) = [sp for sp in spans if sp.name == "store.scan"]
    assert root.attrs["overlapped"] == flushes - 1


def test_the_structural_scan_has_no_verify_spans(tmp_path):
    from drand_tpu import tracing
    s, _ = _chain_db(tmp_path, 5)
    tracing.RECORDER.clear()
    assert _scan(s).ok
    s.close()
    names = {sp.name for sp in tracing.RECORDER.spans()}
    assert names - {"gc.full", "loop.lag"} == {
        "store.scan", "scan.read", "scan.decode"}

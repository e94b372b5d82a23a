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


class _FakeVerifier:
    """Marks a fixed round's signature bad; mirrors the two entry points
    scan_store uses (packed segments + single-beacon batches)."""

    def __init__(self, bad_round):
        self.bad = bad_round

    def verify_packed_segment_async(self, packed, anchor):
        ok = np.array([r != self.bad for r in packed.rounds()], dtype=bool)
        return lambda: ok

    def verify_beacons(self, beacons):
        return np.array([b.round != self.bad for b in beacons], dtype=bool)


def test_scan_bls_stage_flags_bad_signature(tmp_path):
    s, _ = _chain_db(tmp_path, 8)
    rep = _scan(s, _FakeVerifier(5))
    assert rep.verify_checked
    assert rep.bad_sigs == [5] and rep.verified_tip == 4
    clean = _scan(s, _FakeVerifier(-1))
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


# -- the scan's span tree (ISSUE 25) -----------------------------------------

def test_a_scan_is_one_trace_whose_self_times_add_up(tmp_path):
    from benchmark.readers.program_spans import self_seconds
    from drand_tpu import tracing
    s, _ = _chain_db(tmp_path, 40)
    tracing.RECORDER.clear()
    rep = _scan(s, _FakeVerifier(23), segment_rounds=16, read_batch=16)
    s.close()
    assert rep.bad_sigs == [23] and rep.scanned == 40
    spans = [sp for sp in tracing.RECORDER.spans() if sp.name != "gc.full"]
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
    # a flush inside the row loop is the decode's child, the last one the
    # root's: either way no two siblings overlap, so the self times of
    # the tree add up to the scan
    flush_parents = {by_id[sp.parent_id].name for sp in spans
                     if sp.name == "scan.flush"}
    assert flush_parents == {"scan.decode", "store.scan"}
    own = self_seconds([(sp.span_id, sp.parent_id, sp.name, sp.start_mono,
                         sp.start_mono + sp.duration_s) for sp in spans])
    assert all(v >= -1e-9 for v in own.values())
    assert sum(own.values()) == pytest.approx(root.duration_s, rel=0.02)
    assert rep.elapsed_s <= root.duration_s


def test_the_structural_scan_has_no_verify_spans(tmp_path):
    from drand_tpu import tracing
    s, _ = _chain_db(tmp_path, 5)
    tracing.RECORDER.clear()
    assert _scan(s).ok
    s.close()
    names = {sp.name for sp in tracing.RECORDER.spans()}
    assert names - {"gc.full"} == {"store.scan", "scan.read", "scan.decode"}

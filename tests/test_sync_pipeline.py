"""Pipelined sync-manager semantics: one verification kept in flight.

The sync loop dispatches segment k+1's batched verify before settling
segment k (`beacon/sync_manager.py::_try_node`), overlapping transfer
with device compute — the batched evolution of the reference's serial
loop at `chain/beacon/sync_manager.go:397-399`.  These tests pin the
commit-ordering contract that pipelining must not break:

  - beacons reach the store only after THEIR segment settles valid;
  - a failed segment commits nothing from that segment or later, while
    everything before it stays committed;
  - `check_past_beacons` (the `util check` path, pipelined the same way)
    reports exactly the corrupted rounds across chunk boundaries.
"""

import asyncio
import hashlib

import numpy as np
import pytest

import drand_tpu.beacon.sync_manager as SM
import drand_tpu.verify as V
from drand_tpu import fixtures
from drand_tpu.chain.beacon import Beacon
from drand_tpu.chain.scheme import scheme_by_id
from drand_tpu.chain.store import BeaconNotFound
from drand_tpu.chain.verify import ChainVerifier
from drand_tpu.crypto.bls12381 import curve as GC

N = 10
SEED = hashlib.sha256(b"sync-pipeline-genesis").digest()


class MemStore:
    def __init__(self):
        self.by_round = {}

    def put(self, b):
        self.by_round[b.round] = b

    def put_many(self, beacons):
        for b in beacons:
            self.put(b)

    def last(self):
        if not self.by_round:
            raise BeaconNotFound("empty")
        return self.by_round[max(self.by_round)]

    def iter_range(self, start, limit=None):
        for r in sorted(self.by_round):
            if r >= start:
                yield self.by_round[r]


class FakeNet:
    def __init__(self, beacons):
        self.beacons = beacons

    def sync_chain(self, peer, from_round):
        async def gen():
            for b in self.beacons:
                if b.round >= from_round:
                    yield b
        return gen()


class FixedClock:
    def now(self):
        return 0.0


class FakeGroup:
    period = 30


@pytest.fixture(scope="module")
def chain():
    sk, pk = fixtures.fixture_keypair(b"sync-pipeline")
    sigs = fixtures.make_chained_chain(sk, SEED, N)
    beacons = []
    prev = SEED
    for i in range(N):
        sig = bytes(sigs[i])
        beacons.append(Beacon(round=i + 1, signature=sig, previous_sig=prev))
        prev = sig
    verifier = ChainVerifier(scheme_by_id("pedersen-bls-chained"),
                             GC.g1_to_bytes(pk))
    return beacons, verifier


def _manager(beacons, verifier, store):
    return SM.SyncManager(store=store, group=FakeGroup(), verifier=verifier,
                          network=FakeNet(beacons), nodes=[object()],
                          clock=FixedClock())


def _seeded_store():
    store = MemStore()
    store.put(Beacon(round=0, signature=SEED))
    return store


def test_pipelined_sync_commits_all(chain, monkeypatch):
    beacons, verifier = chain
    monkeypatch.setattr(SM, "SYNC_CHUNK", 3)   # force multiple in-flight flushes
    monkeypatch.setattr(SM, "SYNC_CHUNK_GROWTH", 1)   # fixed-size chunks
    store = _seeded_store()
    mgr = _manager(beacons, verifier, store)
    progress = []
    mgr.on_progress = lambda r, target: progress.append(r)
    ok = asyncio.run(mgr._try_node(object(), SM.SyncRequest(1, up_to=N)))
    assert ok
    assert sorted(store.by_round) == list(range(0, N + 1))
    # progress callbacks fire per settled segment, in order
    assert progress == sorted(progress) and progress[-1] == N


def test_failed_segment_commits_nothing_from_it(chain, monkeypatch):
    beacons, verifier = chain
    monkeypatch.setattr(SM, "SYNC_CHUNK", 3)
    monkeypatch.setattr(SM, "SYNC_CHUNK_GROWTH", 1)
    bad = list(beacons)
    sig = bytearray(bad[6].signature)          # round 7, third chunk
    sig[5] ^= 0xFF
    bad[6] = Beacon(round=7, signature=bytes(sig),
                    previous_sig=bad[6].previous_sig)
    store = _seeded_store()
    mgr = _manager(bad, verifier, store)
    ok = asyncio.run(mgr._try_node(object(), SM.SyncRequest(1, up_to=N)))
    # chunks [1-3] and [4-6] settled valid before the corrupt one
    assert set(store.by_round) == {0, 1, 2, 3, 4, 5, 6}
    # a failed segment fails the peer (same contract as the unpipelined
    # loop): the caller moves on to the next peer with the good prefix kept
    assert not ok


def test_stream_drop_commits_in_flight_segment(chain, monkeypatch):
    """A peer dropping mid-stream must not discard the already-dispatched
    (and valid) segment: the finally block settles it into the store."""
    beacons, verifier = chain
    monkeypatch.setattr(SM, "SYNC_CHUNK", 3)
    monkeypatch.setattr(SM, "SYNC_CHUNK_GROWTH", 1)

    class DroppingNet:
        def sync_chain(self, peer, from_round):
            async def gen():
                for b in beacons[:3]:          # exactly one full chunk
                    yield b
                raise RuntimeError("connection dropped")
            return gen()

    store = _seeded_store()
    mgr = SM.SyncManager(store=store, group=FakeGroup(), verifier=verifier,
                         network=DroppingNet(), nodes=[object()],
                         clock=FixedClock())
    with pytest.raises(RuntimeError):
        asyncio.run(mgr._try_node(object(), SM.SyncRequest(1, up_to=N)))
    assert set(store.by_round) == {0, 1, 2, 3}


def test_adaptive_chunk_growth(chain, monkeypatch):
    """A stream that keeps chunks full without idling (deep backlog) must
    grow the segment size toward the throughput bucket; segment sizes are
    observed through the verifier dispatch."""
    beacons, verifier = chain
    monkeypatch.setattr(SM, "SYNC_CHUNK", 2)
    monkeypatch.setattr(SM, "SYNC_CHUNK_GROWTH", 2)
    monkeypatch.setattr(SM, "SYNC_CHUNK_MAX", 8)
    seg_sizes = []
    orig = verifier.verify_chain_segment_async

    class Spy:
        def verify_chain_segment_async(self, seg, anchor):
            seg_sizes.append(len(seg))
            return orig(seg, anchor)

        def __getattr__(self, name):
            return getattr(verifier, name)

    store = _seeded_store()
    mgr = _manager(beacons, Spy(), store)
    ok = asyncio.run(mgr._try_node(object(), SM.SyncRequest(1, up_to=N)))
    assert ok
    assert sorted(store.by_round) == list(range(0, N + 1))
    # 2 (seed) -> 4 (grown) -> the remaining 4 at stream end
    assert seg_sizes == [2, 4, 4], seg_sizes


def test_correct_past_beacons_writes_through_insecure_store(chain):
    """Repair must overwrite via the EXPLICIT insecure store, not by
    unwrapping decorators (VERDICT r3 weak #8): the decorated store here
    rejects overwrites outright, so the test fails if repair ever goes
    through it."""
    beacons, verifier = chain

    class AppendOnly(MemStore):
        def put(self, b):
            if b.round in self.by_round:
                raise AssertionError("append-only store overwritten")
            super().put(b)

    secure = AppendOnly()
    secure.put(Beacon(round=0, signature=SEED))
    for b in beacons:
        secure.put(b)
    # corrupt round 4 in BOTH views (same dict)
    orig = secure.by_round[4]
    bad = bytearray(orig.signature)
    bad[3] ^= 0x42
    secure.by_round[4] = Beacon(round=4, signature=bytes(bad),
                                previous_sig=orig.previous_sig)
    insecure = MemStore()
    insecure.by_round = secure.by_round        # shared backing, no checks
    mgr = SM.SyncManager(store=secure, group=FakeGroup(), verifier=verifier,
                         network=FakeNet(beacons), nodes=[object()],
                         clock=FixedClock(), insecure_store=insecure)
    fixed = asyncio.run(mgr.correct_past_beacons([4]))
    assert fixed == 1
    assert secure.by_round[4].signature == beacons[3].signature


# -- batched sync wire (ISSUE 13): PackedBeacons chunks ---------------------

def _pack(beacons, size):
    """Chunk a beacon run the way a chunk-capable server would."""
    items = []
    for i in range(0, len(beacons), size):
        seg = beacons[i:i + size]
        sigs = np.stack([np.frombuffer(b.signature, dtype=np.uint8)
                         for b in seg])
        items.append(SM.PackedBeacons(start_round=seg[0].round, sigs=sigs,
                                      first_prev=seg[0].previous_sig,
                                      chained=True))
    return items


class ChunkNet:
    def __init__(self, items):
        self.items = items

    def sync_chain(self, peer, from_round):
        async def gen():
            for it in self.items:
                yield it
        return gen()


def test_chunked_wire_commits_identical_store(chain, monkeypatch):
    """A chunked stream must land the SAME store contents as the
    per-beacon wire — rounds, signatures, AND reconstructed prev links."""
    beacons, verifier = chain
    monkeypatch.setattr(SM, "SYNC_CHUNK", 4)
    monkeypatch.setattr(SM, "SYNC_CHUNK_GROWTH", 1)
    ref_store = _seeded_store()
    mgr = _manager(beacons, verifier, ref_store)
    assert asyncio.run(mgr._try_node(object(), SM.SyncRequest(1, up_to=N)))

    store = _seeded_store()
    mgr = SM.SyncManager(store=store, group=FakeGroup(), verifier=verifier,
                         network=ChunkNet(_pack(beacons, 2)),
                         nodes=[object()], clock=FixedClock())
    progress = []
    mgr.on_progress = lambda r, target: progress.append(r)
    assert asyncio.run(mgr._try_node(object(), SM.SyncRequest(1, up_to=N)))
    assert sorted(store.by_round) == sorted(ref_store.by_round)
    for r in store.by_round:
        assert store.by_round[r].equal(ref_store.by_round[r]), r
    assert progress == sorted(progress) and progress[-1] == N


def test_chunked_corrupt_chunk_fails_and_keeps_prefix(chain, monkeypatch):
    beacons, verifier = chain
    monkeypatch.setattr(SM, "SYNC_CHUNK", 4)
    monkeypatch.setattr(SM, "SYNC_CHUNK_GROWTH", 1)
    items = _pack(beacons, 4)                  # [1-4], [5-8], [9-10]
    sigs = items[1].sigs.copy()
    sigs[2, 7] ^= 0xFF                         # corrupt round 7
    items[1] = SM.PackedBeacons(start_round=items[1].start_round, sigs=sigs,
                                first_prev=items[1].first_prev, chained=True)
    store = _seeded_store()
    mgr = SM.SyncManager(store=store, group=FakeGroup(), verifier=verifier,
                         network=ChunkNet(items), nodes=[object()],
                         clock=FixedClock())
    ok = asyncio.run(mgr._try_node(object(), SM.SyncRequest(1, up_to=N)))
    assert not ok
    assert set(store.by_round) == {0, 1, 2, 3, 4}


def test_chunked_stream_drop_commits_in_flight(chain, monkeypatch):
    beacons, verifier = chain
    monkeypatch.setattr(SM, "SYNC_CHUNK", 4)
    monkeypatch.setattr(SM, "SYNC_CHUNK_GROWTH", 1)
    items = _pack(beacons, 4)

    class DroppingChunkNet:
        def sync_chain(self, peer, from_round):
            async def gen():
                yield items[0]                 # exactly one full chunk
                raise RuntimeError("connection dropped")
            return gen()

    store = _seeded_store()
    mgr = SM.SyncManager(store=store, group=FakeGroup(), verifier=verifier,
                         network=DroppingChunkNet(), nodes=[object()],
                         clock=FixedClock())
    with pytest.raises(RuntimeError):
        asyncio.run(mgr._try_node(object(), SM.SyncRequest(1, up_to=N)))
    assert set(store.by_round) == {0, 1, 2, 3, 4}


def test_out_of_order_chunk_drains_and_returns(chain, monkeypatch):
    """A chunk that skips rounds must drain what is buffered (committing
    the contiguous prefix) and give up on the peer, not commit a gap."""
    beacons, verifier = chain
    monkeypatch.setattr(SM, "SYNC_CHUNK", 4)
    monkeypatch.setattr(SM, "SYNC_CHUNK_GROWTH", 1)
    items = _pack(beacons, 4)
    gapped = [items[0], items[2]]              # [1-4] then [9-10]
    store = _seeded_store()
    mgr = SM.SyncManager(store=store, group=FakeGroup(), verifier=verifier,
                         network=ChunkNet(gapped), nodes=[object()],
                         clock=FixedClock())
    ok = asyncio.run(mgr._try_node(object(), SM.SyncRequest(1, up_to=N)))
    assert ok                                  # the prefix DID land
    assert set(store.by_round) == {0, 1, 2, 3, 4}


def test_chunk_truncated_to_up_to(chain, monkeypatch):
    """A server chunk overshooting up_to must be truncated, never
    committing rounds past the requested target."""
    beacons, verifier = chain
    monkeypatch.setattr(SM, "SYNC_CHUNK", 4)
    monkeypatch.setattr(SM, "SYNC_CHUNK_GROWTH", 1)
    store = _seeded_store()
    mgr = SM.SyncManager(store=store, group=FakeGroup(), verifier=verifier,
                         network=ChunkNet(_pack(beacons, 4)),
                         nodes=[object()], clock=FixedClock())
    ok = asyncio.run(mgr._try_node(object(), SM.SyncRequest(1, up_to=6)))
    assert ok
    assert set(store.by_round) == {0, 1, 2, 3, 4, 5, 6}


def test_mixed_wire_chunks_and_singles(chain, monkeypatch):
    """Chunked backlog followed by a per-beacon live tail (exactly what
    the serve side produces) commits everything in order."""
    beacons, verifier = chain
    monkeypatch.setattr(SM, "SYNC_CHUNK", 3)
    monkeypatch.setattr(SM, "SYNC_CHUNK_GROWTH", 1)
    items = _pack(beacons[:6], 3) + beacons[6:]
    store = _seeded_store()
    mgr = SM.SyncManager(store=store, group=FakeGroup(), verifier=verifier,
                         network=ChunkNet(items), nodes=[object()],
                         clock=FixedClock())
    ok = asyncio.run(mgr._try_node(object(), SM.SyncRequest(1, up_to=N)))
    assert ok
    assert sorted(store.by_round) == list(range(0, N + 1))
    for i, b in enumerate(beacons):
        assert store.by_round[b.round].equal(b), b.round


def test_serve_sync_chain_chunked_matches_per_beacon(chain, tmp_path):
    """The serve side: a chunk-capable request over a SqliteStore must
    stream the same rounds/signatures as the per-beacon walk, as packed
    items built from raw rows."""
    from drand_tpu.chain.store import SqliteStore
    beacons, _ = chain
    store = SqliteStore(str(tmp_path / "serve.db"))
    store.put(Beacon(round=0, signature=SEED))
    store.put_many(beacons)

    async def collect(chunk_size):
        out = []
        async for item in SM.serve_sync_chain(store, 1,
                                              chunk_size=chunk_size):
            if isinstance(item, SM.PackedBeacons):
                out.extend(item.beacons())
            else:
                out.append(item)
        return out

    plain = asyncio.run(collect(0))
    chunked = asyncio.run(collect(4))
    assert len(plain) == len(chunked) == N
    for a, b in zip(plain, chunked):
        assert a.equal(b), a.round
    store.close()


def test_check_past_beacons_pipelined_finds_faulty(chain, monkeypatch):
    beacons, verifier = chain
    monkeypatch.setattr(SM, "SYNC_CHUNK", 4)
    store = _seeded_store()
    for b in beacons:
        store.put(b)
    # corrupt stored rounds in different chunks, incl. a chunk boundary
    for r in (4, 9):
        orig = store.by_round[r]
        sig = bytearray(orig.signature)
        sig[11] ^= 0x55
        store.by_round[r] = Beacon(round=r, signature=bytes(sig),
                                   previous_sig=orig.previous_sig)
    mgr = _manager(beacons, verifier, store)
    faulty = mgr.check_past_beacons()
    # a bad stored signature also breaks the NEXT round's linkage
    assert set(faulty) == {4, 5, 9, 10}


# -- the catch-up's span tree (ISSUE 25) -------------------------------------

class _YesVerifier:
    """Says yes without a device: the spans are the pipeline's own."""

    def verify_packed_segment_async(self, packed, anchor_prev_sig):
        n = len(packed)
        return lambda: np.ones(n, dtype=bool)


def test_a_catch_up_is_one_trace_of_bounded_spans(chain, monkeypatch):
    from drand_tpu import tracing
    from drand_tpu.chain.store import CallbackStore
    beacons, _ = chain
    monkeypatch.setattr(SM, "SYNC_CHUNK", 4)
    monkeypatch.setattr(SM, "SYNC_CHUNK_GROWTH", 1)
    items = _pack(beacons, 2)                  # five wire messages
    store = CallbackStore(_seeded_store())     # opens `store.commit`
    mgr = SM.SyncManager(store=store, group=FakeGroup(),
                         verifier=_YesVerifier(), network=ChunkNet(items),
                         nodes=[object()], clock=FixedClock())
    tracing.RECORDER.clear()
    try:
        assert asyncio.run(mgr._try_node(object(),
                                         SM.SyncRequest(1, up_to=N)))
    finally:
        store._pool.shutdown(wait=False)
    # a full collection or a late event loop is the machine's, not the
    # catch-up's: both are spans of their own where they happen
    spans = [s for s in tracing.RECORDER.spans()
             if s.name not in ("gc.full", "loop.lag")]
    by_id = {s.span_id: s for s in spans}
    (root,) = [s for s in spans if s.parent_id is None]
    assert root.name == "sync.catchup"
    assert {s.trace_id for s in spans} == {root.trace_id}
    assert {s.name for s in spans} == {
        "sync.catchup", "sync.fetch", "sync.segment", "sync.queue_wait",
        "sync.pack", "sync.settle", "store.materialize", "store.commit"}
    # every child lies inside its parent's interval
    for s in spans:
        if s.parent_id is not None:
            p = by_id[s.parent_id]
            assert p.start_mono - 1e-6 <= s.start_mono, (s.name, p.name)
            assert s.start_mono + s.duration_s \
                <= p.start_mono + p.duration_s + 1e-6, (s.name, p.name)
    # three segments (4 + 4 + 2 rounds), each with a bounded set of
    # children whatever its size: never a span a round
    segments = [s for s in spans if s.name == "sync.segment"]
    assert [s.attrs["rounds"] for s in segments] == [4, 4, 2]
    assert [s.attrs["first_round"] for s in segments] == [1, 5, 9]
    assert all(s.parent_id == root.span_id and s.status == "ok"
               for s in segments)
    for seg in segments:
        kids = [s for s in spans if s.parent_id == seg.span_id]
        assert sorted(k.name for k in kids) == [
            "store.commit", "store.materialize", "sync.pack",
            "sync.queue_wait", "sync.queue_wait", "sync.settle"]
        waits = {k.attrs["stage"]: k for k in kids
                 if k.name == "sync.queue_wait"}
        assert set(waits) == {"verify", "commit"}
        assert all(0 <= w.attrs["depth"] <= SM.PIPELINE_DEPTH
                   for w in waits.values())
    # and one `sync.fetch` a segment's fill, a child of the root, whose
    # waits are the very readings the stat is made of
    fills = [s for s in spans if s.name == "sync.fetch"]
    assert all(s.parent_id == root.span_id for s in fills)
    assert [(s.attrs["rounds"], s.attrs["messages"]) for s in fills] == [
        (4, 2), (4, 2), (2, 1)]
    assert sum(s.attrs["wait_s"] for s in fills) == pytest.approx(
        mgr.stats["fetch_s"], abs=1e-9)
    assert all(s.duration_s >= s.attrs["wait_s"] - 1e-9 for s in fills)
    assert len(spans) == 1 + 3 * 8
    # the stats and the spans that share their clock reads agree exactly
    settle = sum(s.duration_s for s in spans if s.name == "sync.settle")
    assert settle == pytest.approx(mgr.stats["verify_s"], abs=1e-9)
    assert root.attrs["fetch_s"] == pytest.approx(mgr.stats["fetch_s"])
    assert root.attrs["rounds"] == mgr.stats["rounds"] == N
    assert root.attrs["segments"] == mgr.stats["segments"] == 3
    assert root.attrs["messages"] == len(items)
    # what a stage's stat holds beyond its spans is the hop into the
    # worker thread, so the spans can only be the shorter
    commit = sum(s.duration_s for s in spans
                 if s.name in ("store.materialize", "store.commit"))
    assert 0 < commit <= mgr.stats["commit_s"]


def test_a_failed_segment_ends_its_span_and_its_successors(chain,
                                                            monkeypatch):
    from drand_tpu import tracing

    class _NoAtRound7(_YesVerifier):
        def verify_packed_segment_async(self, packed, anchor_prev_sig):
            ok = packed.rounds() != 7
            return lambda: ok

    beacons, _ = chain
    monkeypatch.setattr(SM, "SYNC_CHUNK", 2)
    monkeypatch.setattr(SM, "SYNC_CHUNK_GROWTH", 1)
    store = _seeded_store()
    mgr = SM.SyncManager(store=store, group=FakeGroup(),
                         verifier=_NoAtRound7(),
                         network=ChunkNet(_pack(beacons, 2)),
                         nodes=[object()], clock=FixedClock())
    tracing.RECORDER.clear()
    assert not asyncio.run(mgr._try_node(object(),
                                         SM.SyncRequest(1, up_to=N)))
    assert set(store.by_round) == {0, 1, 2, 3, 4, 5, 6}
    segments = [s for s in tracing.RECORDER.spans()
                if s.name == "sync.segment"]
    status = {s.attrs["first_round"]: s.status for s in segments}
    assert status[1] == status[3] == status[5] == "ok"
    assert status[7] == "verify_failed"
    # whatever was flushed after it left the pipeline unverified or
    # uncommitted, and says so
    assert all(v == "discarded" for k, v in status.items() if k > 7)
    assert all(s.duration_s is not None for s in segments)


# -- where a segment is cut (ISSUE 30) ----------------------------------------
#
# The device is charged by the program a dispatch is padded into, so a
# catch-up that knows its backlog cuts a segment where that program is
# full.  The fake below is a DEVICE verifier under a real ChainVerifier:
# `Verifier.verify_batch_async` pads with `rows_charged` as on the chip,
# and only the compiled program is replaced (a row is false iff its
# signature's first byte is 0xFF).

BACKLOG = 256


class _FakeDevice(V.Verifier):
    def __init__(self, shape):
        self.shape = shape
        self._pk = None
        self._kernels = {}
        self._single_host = lambda round_, sig, prev: (sig[0] != 0xFF,
                                                       "fake")
        self.dispatches = []       # (n, rows charged) of every dispatch

    def _kernel(self, m):
        def program(msgs, sigs, pk):
            assert msgs.shape[0] == sigs.shape[0] == m
            return np.asarray(sigs)[:, 0] != 0xFF
        return program

    def verify_batch_async(self, rounds, sigs, prev_sigs=None):
        self.dispatches.append((len(rounds), self.rows_charged(len(rounds))))
        return super().verify_batch_async(rounds, sigs, prev_sigs)


class _Unasked:
    """A verifier that does not answer the row question."""

    def __init__(self, inner):
        self.verify_packed_segment_async = inner.verify_packed_segment_async


def _fake_chain(chained: bool, bad_round: int | None):
    """BACKLOG rounds of made-up signatures as 2-round wire messages."""
    sigs = np.random.default_rng(30).integers(
        0, 128, size=(BACKLOG, 96), dtype=np.uint8)
    if bad_round is not None:
        sigs[bad_round - 1, 0] = 0xFF
    return [SM.PackedBeacons(start_round=at + 1, sigs=sigs[at:at + 2],
                             first_prev=SEED if at == 0 else
                             sigs[at - 1].tobytes() if chained else b"",
                             chained=chained)
            for at in range(0, BACKLOG, 2)]


CUT_CASES = {
    # name: (buckets, scheme, up_to, asked, bad round) ->
    #       (segments as (rounds, cut), device dispatches as (n, charged),
    #        rounds committed)
    "a_one_bucket_known_backlog_fills_the_program": (
        ((64,), "pedersen-bls-unchained", BACKLOG, True, None),
        ([(64, "full")] * 3 + [(64, "backlog_end")], [(64, 64)] * 4,
         BACKLOG)),
    "b_chained_from_round_1_sends_63_rows_first": (
        ((64,), "pedersen-bls-chained", BACKLOG, True, None),
        ([(64, "full")] * 3 + [(64, "backlog_end")],
         [(63, 64)] + [(64, 64)] * 3, BACKLOG)),
    "c_a_bucket_at_the_ramp_keeps_the_ramp": (
        ((2, 64), "pedersen-bls-unchained", BACKLOG, True, None),
        ([(2, "full")] + [(64, "full")] * 3 + [(62, "backlog_end")],
         [(2, 2)] + [(64, 64)] * 3 + [(62, 64)], BACKLOG)),
    "d_follow_mode_keeps_the_ramp": (
        ((64,), "pedersen-bls-unchained", 0, True, None),
        ([(2, "target")] + [(64, "target")] * 3 + [(62, "stream_end")],
         [(2, 64)] + [(64, 64)] * 3 + [(62, 64)], BACKLOG)),
    "e_a_backlog_inside_the_program_is_one_segment": (
        ((64,), "pedersen-bls-unchained", 40, True, None),
        ([(40, "backlog_end")], [(40, 64)], 40)),
    "f_a_verifier_that_does_not_answer_keeps_the_ramp": (
        ((64,), "pedersen-bls-unchained", BACKLOG, False, None),
        ([(2, "target")] + [(64, "target")] * 3 + [(62, "backlog_end")],
         [(2, 64)] + [(64, 64)] * 3 + [(62, 64)], BACKLOG)),
    "g_a_false_row_in_the_second_full_segment": (
        ((64,), "pedersen-bls-unchained", BACKLOG, True, 100),
        (None, None, 64)),
    "h_a_false_row_first_in_the_second_full_segment": (
        ((64,), "pedersen-bls-unchained", BACKLOG, True, 65),
        (None, None, 64)),
    "i_a_false_row_last_in_the_first_full_segment": (
        ((64,), "pedersen-bls-unchained", BACKLOG, True, 64),
        (None, None, 0)),
}


@pytest.mark.parametrize("case", sorted(CUT_CASES))
def test_a_segment_is_cut_where_the_program_is_full(chain, monkeypatch,
                                                    case):
    from drand_tpu import tracing
    (buckets, scheme_id, up_to, asked, bad_round), \
        (want_segments, want_dispatches, want_committed) = CUT_CASES[case]
    monkeypatch.setattr(V, "_BUCKETS", buckets)
    monkeypatch.setattr(SM, "SYNC_CHUNK", 2)
    monkeypatch.setattr(SM, "SYNC_CHUNK_MAX", 64)   # the throughput bucket
    scheme = scheme_by_id(scheme_id)
    cv = ChainVerifier(scheme, chain[1].public_key_bytes)
    device = cv._lazy_verifier = _FakeDevice(scheme.shape)
    chained = not scheme.decouple_prev_sig
    store = _seeded_store()
    mgr = SM.SyncManager(
        store=store, group=FakeGroup(),
        verifier=cv if asked else _Unasked(cv),
        network=ChunkNet(_fake_chain(chained, bad_round)),
        nodes=[object()], clock=FixedClock())
    tracing.RECORDER.clear()
    ok = asyncio.run(mgr._try_node(object(), SM.SyncRequest(1, up_to)))
    assert ok is (bad_round is None)
    # in order, and nothing at or after a failing segment's first round
    assert sorted(store.by_round) == list(range(0, want_committed + 1))
    segments = [(s.attrs["first_round"], s.attrs["rounds"], s.attrs["cut"],
                 s.status)
                for s in tracing.RECORDER.spans() if s.name == "sync.segment"]
    if bad_round is not None:
        sound = want_committed // 64           # whole segments before it
        assert segments[:sound + 1] == \
            [(1 + 64 * i, 64, "full", "ok") for i in range(sound)] \
            + [(1 + want_committed, 64, "full", "verify_failed")]
        assert all(s[3] == "discarded" for s in segments[sound + 1:])
        return
    assert [s[1:3] for s in segments] == want_segments
    assert all(s[3] == "ok" for s in segments)
    assert device.dispatches == want_dispatches
    # the spans the benchmark reads `verify.pad_share` from say the same
    assert [(s.attrs["n"], s.attrs["bucket"], s.attrs["pad_rows"])
            for s in tracing.RECORDER.spans() if s.name == "verify.dispatch"] \
        == [(n, m, m - n) for n, m in want_dispatches]
    if chained:
        links = [s for s in tracing.RECORDER.spans()
                 if s.name == "verify.genesis_link"]
        assert [s.round for s in links] == [1]


# -- where a segment is cut on a mesh (ISSUE 34) ------------------------------
#
# On a host of several chips `ChainVerifier` lays a dispatch over a mesh
# (`ShardedVerifier`): every device's slice is padded into the verifier's
# program, so the program is full where the segment holds the bucket times
# the devices.  Below, a real `ChainVerifier` and a real `ShardedVerifier`
# over four of the suite's eight virtual devices; the device program is
# BUILT as the chip's is (`Verifier.build`: exported, put under the mesh's
# `shard_map`, compiled) from a stand-in body: a row is false iff its
# signature's first byte is 0xFF.

MESH, MESH_BUCKET, MESH_BACKLOG = 4, 64, 1024
FULL = MESH * MESH_BUCKET


class _FakeBody(V.Verifier):
    def _run_fn(self, compact=None):
        return lambda msgs_u8, sig_u8, pk: sig_u8[:, 0] != 0xFF


def _mesh_chain(bad_round):
    """(sigs, the chain as 8-round wire messages)."""
    sigs = np.random.default_rng(34).integers(
        0, 128, size=(MESH_BACKLOG, 96), dtype=np.uint8)
    if bad_round is not None:
        sigs[bad_round - 1, 0] = 0xFF
    return sigs, [SM.PackedBeacons(start_round=at + 1, sigs=sigs[at:at + 8],
                                   first_prev=b"", chained=False)
                  for at in range(0, MESH_BACKLOG, 8)]


def _catch_up_on_a_mesh(chain, monkeypatch, tmp_path, up_to, bad_round=None):
    """(ok, store, sigs, the recorder's spans) of one catch-up."""
    import jax

    from drand_tpu import tracing
    from drand_tpu.parallel import ShardedVerifier
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(V, "_BUCKETS", (MESH_BUCKET,))
    monkeypatch.setattr(SM, "SYNC_CHUNK", 2)
    monkeypatch.setattr(SM, "SYNC_CHUNK_MAX", MESH_BUCKET)
    scheme = scheme_by_id("pedersen-bls-unchained")
    cv = ChainVerifier(scheme, chain[1].public_key_bytes)
    cv._lazy_verifier = ShardedVerifier(
        _FakeBody(cv._pk_point, scheme.shape), devices=jax.devices()[:MESH])
    sigs, messages = _mesh_chain(bad_round)
    store = _seeded_store()
    mgr = SM.SyncManager(store=store, group=FakeGroup(), verifier=cv,
                         network=ChunkNet(messages), nodes=[object()],
                         clock=FixedClock())
    tracing.RECORDER.clear()
    ok = asyncio.run(mgr._try_node(object(), SM.SyncRequest(1, up_to)))
    return ok, store, sigs, tracing.RECORDER.spans()


MESH_CASES = {
    # name: up_to -> (segments as (rounds, cut), rounds committed)
    "a_known_backlog_fills_the_mesh": (
        MESH_BACKLOG, [(FULL, "full")] * 3 + [(FULL, "backlog_end")],
        MESH_BACKLOG),
    "b_follow_mode_keeps_the_ramp": (
        0, [(8, "target")] + [(MESH_BUCKET, "target")] * 15
        + [(56, "stream_end")], MESH_BACKLOG),
    "c_a_backlog_that_is_no_multiple_pads_its_last_segment": (
        600, [(FULL, "full")] * 2 + [(88, "backlog_end")], 600),
    "d_a_backlog_inside_the_mesh_is_one_segment": (
        100, [(100, "backlog_end")], 100),
}


@pytest.mark.parametrize("case", sorted(MESH_CASES))
def test_a_segment_is_cut_where_the_mesh_is_full(chain, monkeypatch,
                                                 tmp_path, case):
    up_to, want_segments, want_committed = MESH_CASES[case]
    ok, store, sigs, spans = _catch_up_on_a_mesh(chain, monkeypatch,
                                                 tmp_path, up_to)
    assert ok
    assert sorted(store.by_round) == list(range(0, want_committed + 1))
    assert all(store.by_round[r].signature == sigs[r - 1].tobytes()
               for r in range(1, want_committed + 1))
    segments = [s for s in spans if s.name == "sync.segment"]
    assert [(s.attrs["rounds"], s.attrs["cut"]) for s in segments] \
        == want_segments
    assert all(s.status == "ok" for s in segments)
    # every dispatch is charged the mesh's program, and says so in the
    # attributes the benchmark reads `verify.pad_share` from; a padded
    # row's verdict never reached the store (the count above)
    dispatches = [s for s in spans if s.name == "verify.dispatch"]
    assert [{k: s.attrs[k] for k in ("n", "bucket", "pad_rows", "devices",
                                     "per_dev")} for s in dispatches] \
        == [{"n": n, "bucket": FULL, "pad_rows": FULL - n, "devices": MESH,
             "per_dev": MESH_BUCKET} for n, _cut in want_segments]
    assert all(s.attrs["h2d_bytes"] == FULL * (8 + 96) for s in dispatches)
    # one placement a dispatch, under it; one gather a resolve, under it
    by_id = {s.span_id: s for s in spans}
    for child, parent in (("verify.shard_put", "verify.dispatch"),
                          ("verify.gather", "verify.resolve")):
        mine = [s for s in spans if s.name == child]
        assert len(mine) == len(dispatches)
        assert all(by_id[s.parent_id].name == parent for s in mine)
        assert all(s.attrs["devices"] == MESH for s in mine)
    # the program was built once, for the mesh, from one device's form
    (build,) = [s for s in spans if s.name == "verifier.build"]
    assert build.attrs["bucket"] == MESH_BUCKET
    assert build.attrs["devices"] == MESH


# a false row in the second full segment (rounds 257..512): first, last
# and inside the slice of each of the four devices
FALSE_ROWS = {f"shard{k}_{where}": FULL + MESH_BUCKET * k + at
              for k in range(MESH)
              for where, at in (("first", 1), ("inside", 30),
                                ("last", MESH_BUCKET))}


@pytest.mark.parametrize("where", sorted(FALSE_ROWS))
def test_a_false_row_on_any_device_fails_its_whole_segment(
        chain, monkeypatch, tmp_path, where):
    bad_round = FALSE_ROWS[where]
    ok, store, sigs, spans = _catch_up_on_a_mesh(
        chain, monkeypatch, tmp_path, MESH_BACKLOG, bad_round)
    assert not ok
    # what is committed ends where that segment begins, and is the chain
    assert sorted(store.by_round) == list(range(0, FULL + 1))
    assert all(store.by_round[r].signature == sigs[r - 1].tobytes()
               for r in range(1, FULL + 1))
    segments = [(s.attrs["first_round"], s.status) for s in spans
                if s.name == "sync.segment"]
    assert segments[:2] == [(1, "ok"), (FULL + 1, "verify_failed")]
    assert all(status == "discarded" for _first, status in segments[2:])


# -- the start-up scan on a mesh (ISSUE 34) -----------------------------------

def test_the_scan_flushes_where_the_mesh_is_full(chain, monkeypatch,
                                                 tmp_path):
    """`scan_store` asks the verifier what its throughput segment is
    charged for, as the catch-up's cut does: four devices under one
    bucket of 64 verify 256 stored rounds a flush, no padded row but in
    the last, and `bad_sigs` names exactly the false rows, one in every
    device's slice of the first flush and one in the padded last."""
    import jax

    from drand_tpu import tracing
    from drand_tpu.chain import recovery
    from drand_tpu.chain.store import SqliteStore
    from drand_tpu.parallel import ShardedVerifier
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(V, "_BUCKETS", (MESH_BUCKET,))
    monkeypatch.setattr(recovery, "SCAN_SEGMENT_ROUNDS", MESH_BUCKET)
    scheme = scheme_by_id("pedersen-bls-unchained")
    cv = ChainVerifier(scheme, chain[1].public_key_bytes)
    cv._lazy_verifier = ShardedVerifier(
        _FakeBody(cv._pk_point, scheme.shape), devices=jax.devices()[:MESH])
    stored = 600
    bad = [1, 64 + 30, 128 + 64, 192 + 7, 590]
    sigs, _messages = _mesh_chain(None)
    sigs[np.array(bad) - 1, 0] = 0xFF
    store = SqliteStore(str(tmp_path / "scan.db"))
    store.put_many([Beacon(round=r, signature=sigs[r - 1].tobytes())
                    for r in range(1, stored + 1)])
    tracing.RECORDER.clear()
    try:
        report = asyncio.run(recovery.scan_store(store, cv))
    finally:
        store.close()
    assert report.verify_checked and report.scanned == stored
    assert report.bad_sigs == bad and report.verified_tip == 0
    spans = tracing.RECORDER.spans()
    assert [s.attrs["rows"] for s in spans if s.name == "scan.flush"] \
        == [FULL, FULL, stored - 2 * FULL]
    assert [(s.attrs["n"], s.attrs["bucket"], s.attrs["devices"])
            for s in spans if s.name == "verify.dispatch"] \
        == [(FULL, FULL, MESH), (FULL, FULL, MESH),
            (stored - 2 * FULL, FULL, MESH)]


@pytest.mark.parametrize("who,want", [
    ("one_device_under_its_one_bucket", 64),
    ("a_verifier_that_does_not_answer", 64)])
def test_on_one_device_the_scan_flushes_what_it_did(chain, monkeypatch,
                                                    who, want):
    from drand_tpu.chain import recovery
    monkeypatch.setattr(V, "_BUCKETS", (64,))
    monkeypatch.setattr(recovery, "SCAN_SEGMENT_ROUNDS", 64)
    scheme = scheme_by_id("pedersen-bls-unchained")
    cv = ChainVerifier(scheme, chain[1].public_key_bytes)
    cv._lazy_verifier = _FakeDevice(scheme.shape)
    verifier = cv if who.startswith("one_device") else _Unasked(cv)
    assert recovery._flush_rounds(verifier) == want


def test_a_short_store_is_scanned_without_the_device_verifier(chain,
                                                              tmp_path):
    """The scan asks the verifier for its segment size only with a
    device segment's worth of rows in hand: a store of a few rounds is
    verified on the host tier, and asking sooner would bring the device
    verifier up (and route every later small batch to a program that has
    to be built first)."""
    from drand_tpu.chain import recovery
    from drand_tpu.chain.store import SqliteStore
    beacons, cv = chain
    cv = ChainVerifier(cv.scheme, cv.public_key_bytes)
    store = SqliteStore(str(tmp_path / "short.db"))
    store.put_many([Beacon(round=0, signature=SEED)] + list(beacons))
    try:
        report = asyncio.run(recovery.scan_store(store, cv))
    finally:
        store.close()
    assert report.ok and report.verify_checked and report.scanned == N + 1
    assert cv._lazy_verifier is None


# -- the collector while a segment is committed (ISSUE 34) --------------------
#
# A commit's rows are a Beacon a round, twice over in the store's
# decorators, and die with it; on four chips a segment is 65,536 rounds.
# Left to the cyclic collector they set off two full collections a commit
# (about 50 ms each on the chip's host, every thread stopped), so the
# commit holds the collector off and gives the process's setting back.

def _segment(first_round: int, rows: int):
    sigs = np.zeros((rows, 96), dtype=np.uint8)
    return [SM.PackedBeacons(start_round=first_round, sigs=sigs,
                             first_prev=b"", chained=False)]


@pytest.mark.parametrize("was_on", [True, False])
def test_no_cyclic_collection_while_a_segment_s_rows_are_alive(
        monkeypatch, was_on):
    import gc

    class Watching(MemStore):
        seen = []

        def put_many(self, beacons):
            self.seen.append(gc.isenabled())
            super().put_many(beacons)

    store = Watching()
    store.put(Beacon(round=0, signature=SEED))
    mgr = SM.SyncManager(store=store, group=FakeGroup(),
                         verifier=_YesVerifier(),
                         network=ChunkNet(_segment(1, 8) + _segment(9, 8)),
                         nodes=[object()], clock=FixedClock())
    monkeypatch.setattr(SM, "SYNC_CHUNK", 8)
    (gc.enable if was_on else gc.disable)()
    try:
        assert asyncio.run(mgr._try_node(object(), SM.SyncRequest(1, 16)))
        # the process's own setting is back, whichever it was
        assert gc.isenabled() is was_on
        with SM._collector_paused(), SM._collector_paused():
            assert not gc.isenabled()
        assert gc.isenabled() is was_on
    finally:
        gc.enable()
    assert store.seen == [False, False]
    assert sorted(store.by_round) == list(range(0, 17))


# -- the catch-up's two ends, opened (ISSUE 38) -------------------------------

class _Rows:
    """A store of `read_fields` alone: rounds 1..n, or a reader that
    fails on its second batch."""

    def __init__(self, n, fail=None):
        self.n, self.fail, self.reads = n, fail, 0

    def read_fields(self, start, limit):
        self.reads += 1
        if self.fail is not None and self.reads == 2:
            raise self.fail
        return [(r, bytes([r % 251]) * 48, b"")
                for r in range(start, min(start + limit, self.n + 1))]


def _self_seconds(span, spans):
    kids = sum(k.duration_s for k in spans if k.parent_id == span.span_id)
    return span.duration_s - kids


@pytest.mark.filterwarnings("ignore::pytest.PytestUnraisableExceptionWarning")
def test_the_wire_s_two_ends_and_the_commit_s_parts_over_the_stand(
        tmp_path, monkeypatch):
    """A bounded catch-up over the in-process stand (the real
    `Protocol.SyncChain` over localhost gRPC, sqlite on both sides): the
    consumer stops at `up_to` and closes a stream whose serving side
    has more to send than the transport will take ahead (a backlog
    without end), which is how every bounded catch-up ends."""
    import tools.bench_sync as bs
    from drand_tpu import tracing
    monkeypatch.setenv(bs.WIRE_ENV, "16")
    monkeypatch.delenv(bs.CODEC_ENV, raising=False)
    monkeypatch.setattr(SM, "SYNC_CHUNK", 32)
    monkeypatch.setattr(SM, "SYNC_CHUNK_GROWTH", 1)
    served = _Rows(10 ** 9)

    def of(name):
        return [s for s in tracing.RECORDER.spans() if s.name == name]

    async def main():
        server, addr = await bs._serve(served)
        tracing.RECORDER.clear()
        try:
            ok, _, stats, _, last = await bs.catch_up(
                addr, bs._StubVerifier(), 64)
            # the serving side learns of the close from the transport
            for _ in range(500):
                if of("sync.serve") and of("rpc.Protocol.SyncChain"):
                    break
                await asyncio.sleep(0.01)
        finally:
            await server.stop(None)
        return ok, stats, last

    ok, stats, last = asyncio.run(main())
    assert ok and last == 64 and stats["rounds"] == 64
    spans = tracing.RECORDER.spans()
    (root,) = of("sync.catchup")

    # the consumer's side: one `sync.fetch` a segment's fill
    fills, segments = of("sync.fetch"), of("sync.segment")
    assert len(fills) == len(segments) == 2
    assert [f.attrs["rounds"] for f in fills] == [32, 32]
    assert all(f.parent_id == root.span_id and f.beacon_id == root.beacon_id
               for f in fills)
    assert sum(f.attrs["wait_s"] for f in fills) == pytest.approx(
        stats["fetch_s"], abs=1e-9)
    assert sum(f.attrs["messages"] for f in fills) \
        == root.attrs["messages"] == 4
    for f in fills:
        assert 0 < f.attrs["recv_s"] and 0 < f.attrs["decode_s"]
        assert f.attrs["recv_s"] + f.attrs["decode_s"] <= f.attrs["wait_s"]
        assert f.attrs["bytes"] >= 32 * 48
        assert f.attrs["wait_s"] <= f.duration_s + 1e-9
    # what the network layer counted on the root is what the fills hold
    for key in ("recv_s", "decode_s", "bytes"):
        assert sum(f.attrs[key] for f in fills) == pytest.approx(
            root.attrs[key])

    # the serving side: one span a served stream, under the RPC's, which
    # the consumer's root parents across the wire; the client closed it
    (rpc,), (serve,) = of("rpc.Protocol.SyncChain"), of("sync.serve")
    assert rpc.parent_id == root.span_id and serve.parent_id == rpc.span_id
    assert serve.trace_id == rpc.trace_id == root.trace_id
    assert rpc.status == "closed" and serve.status == "closed"
    a = serve.attrs
    assert a["read_s"] + a["pack_s"] + a["send_s"] == pytest.approx(
        serve.duration_s, abs=1e-3)
    assert 0 < a["read_thread_s"] <= a["read_s"]
    assert a["messages"] >= 4 and a["rows"] >= a["messages"] * 16 - 15
    assert a["bytes"] == a["messages"] * 16 * 48
    assert min(a["read_s"], a["pack_s"], a["send_s"]) > 0

    # the store's commit: three parts inside the span's self time
    commits = [c for c in of("store.commit") if c.attrs.get("rows") == 32]
    assert len(commits) == 2
    for c in commits:
        parts = [c.attrs[k] for k in ("encode_s", "insert_s", "flush_s")]
        assert min(parts) > 0
        assert sum(parts) <= _self_seconds(c, spans) + 1e-9


@pytest.mark.parametrize("how, status, messages", [
    ("to_its_end", "ok", 4),
    ("closed_by_the_client", "closed", 2),
    ("an_exception_of_its_own", "error", 1),
])
def test_a_served_stream_ends_with_its_counters_set(how, status, messages):
    """A stream the client closed is not an error; one that failed on
    this side is.  Either way the parts add up to the span."""
    from drand_tpu import tracing
    store = _Rows(64, RuntimeError("disk") if status == "error" else None)

    async def main():
        gen = SM.serve_sync_chain(store, 1, chunk_size=16)
        got = []
        try:
            async for item in gen:
                got.append(item)
                if how == "closed_by_the_client" and len(got) == 2:
                    await gen.aclose()
                    break
        except RuntimeError:
            assert status == "error"
        return got

    tracing.RECORDER.clear()
    got = asyncio.run(main())
    assert len(got) == messages
    (serve,) = [s for s in tracing.RECORDER.spans() if s.name == "sync.serve"]
    a = serve.attrs
    assert serve.status == status
    assert a["messages"] == messages and a["rows"] >= 16 * messages
    assert a["bytes"] == messages * 16 * 48
    assert a["read_s"] + a["pack_s"] + a["send_s"] == pytest.approx(
        serve.duration_s, abs=1e-6)
    assert 0 < a["read_thread_s"] <= a["read_s"]

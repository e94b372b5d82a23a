"""End-to-end catch-up bench: two in-process nodes over REAL gRPC
(ISSUE 13 acceptance harness).

Earlier rounds drove SyncManager against an in-memory fake peer, so the
wire and the store codec were invisible.  This harness stands up a
SERVING node (a SqliteStore with a deep backlog behind the actual
`Protocol.SyncChain` handler, served by `grpc.aio` on localhost) and a
CONSUMING node (the production `GrpcBeaconNetwork.sync_chain` client
feeding `SyncManager._try_node`), so every layer the PR touches is on
the measured path: capability negotiation, chunked wire packing, the
binary row codec, and the off-loop fetch/pack/commit pipeline.

Three passes, same backlog:

  chunked  - SyncChunk wire (512 rounds/message) + binary codec
  fallback - per-beacon wire (DRAND_TPU_SYNC_WIRE_CHUNK=0) + binary codec;
             its committed store must be BIT-identical to the chunked
             pass (the transparent-fallback correctness gate)
  legacy   - per-beacon wire + JSON+hex codec on BOTH stores (the seed
             behavior this PR replaces)

The headline is NON-verify host seconds per 16384-round segment
(elapsed minus the settle stage's verify wait, from `SyncManager.stats`)
and the chunked-vs-legacy ratio; the acceptance bar is >= 5x.  Verify is
stubbed by default so the metric isolates host work on any machine;
`--mode=real` wires the real ChainVerifier + native-signed fixture chain
for TPU runs (warmed b512 + b16384 executables recommended).

    python tools/bench_sync.py [--epochs N] [--mode stub|real]

Writes BENCH_sync.json at the repo root and prints it.  Reference seam:
the serial per-beacon loop at `chain/beacon/sync_manager.go:326-438`.
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import json
import os
import sqlite3
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BACKLOG = int(os.environ.get("BENCH_SYNC_BACKLOG", "65536"))
SIG_LEN = 96
WIRE_ENV = "DRAND_TPU_SYNC_WIRE_CHUNK"
CODEC_ENV = "DRAND_TPU_STORE_CODEC"


class _Peer:
    tls = False

    def __init__(self, address: str):
        self.address = address


class _Clock:
    def now(self):
        # the bench drives the real SyncManager/DiscrepancyStore stack,
        # whose latency math maps wall time onto the chain schedule
        return time.time()  # lint: disable=no-wall-clock


class _Group:
    period = 3600            # no stall renewals during the measurement
    genesis_time = 0
    scheme_id = "pedersen-bls-unchained"


class _StubVerifier:
    """All-valid verifier: isolates the NON-verify host path, which is
    what the acceptance metric measures.  Matches the two dispatch
    surfaces the catch-up pipeline uses, plus the `.scheme` attribute
    the objectsync client reads for linkage reconstruction."""

    def __init__(self):
        from drand_tpu.chain.scheme import scheme_by_id
        self.scheme = scheme_by_id(_Group.scheme_id)

    def verify_chain_segment_async(self, beacons, anchor_prev_sig):
        n = len(beacons)
        return lambda: np.ones(n, dtype=bool)

    def verify_packed_segment_async(self, packed, anchor_prev_sig):
        n = len(packed)
        return lambda: np.ones(n, dtype=bool)


def _stub_signatures(total: int) -> np.ndarray:
    rng = np.random.default_rng(13)
    return rng.integers(0, 256, size=(total, SIG_LEN), dtype=np.uint8)


def _extend_chain_native(sk, shape, sigs16k: np.ndarray, total: int,
                         pk_tag: str) -> np.ndarray:
    """Rounds len(sigs16k)+1 .. total, signed via the native tier and
    cached on disk (the committed fixture covers 1..16384; golden-model
    signing of another 49k rounds would cost ~35 min of host time where
    native costs ~8, bit-identically — pinned against the golden model
    for the first extension signature)."""
    from drand_tpu import aot, native
    from drand_tpu.verify import rounds_be8
    base = len(sigs16k)
    if total <= base:
        return sigs16k[:total]
    suite = hashlib.sha256(shape.dst).hexdigest()[:8]
    fname = f"bench_sync_sigs_{total}_{suite}_{pk_tag}.npy"
    cache = os.path.join(aot.aot_dir(), "fixtures", fname)
    if os.path.exists(cache):
        ext = np.load(cache)
    else:
        assert native.available(), \
            "native tier required to extend the sync backlog"
        from drand_tpu.crypto import sign as S
        sk32 = sk.to_bytes(32, "big")
        rounds = np.arange(base + 1, total + 1, dtype=np.uint64)
        msgs = [hashlib.sha256(m.tobytes()).digest()
                for m in rounds_be8(rounds)]
        t0 = time.perf_counter()
        ext = np.zeros((len(msgs), SIG_LEN), dtype=np.uint8)
        for i, m in enumerate(msgs):
            h = native.hash_to_g2(m, shape.dst)
            ext[i] = np.frombuffer(
                native.g2_lincomb([h], [sk32]), dtype=np.uint8)
        # anchor: the native extension must match the golden model
        assert bytes(ext[0]) == S.bls_sign(sk, msgs[0]), \
            "native signing diverged from the golden model"
        print(f"bench_sync: natively signed {len(msgs)} rounds in "
              f"{time.perf_counter() - t0:.0f}s", file=sys.stderr)
        os.makedirs(os.path.dirname(cache), exist_ok=True)
        np.save(cache + ".tmp.npy", ext)
        os.replace(cache + ".tmp.npy", cache)
    return np.concatenate([sigs16k, ext], axis=0)


def _fill_store(path: str, beacons, codec: str | None):
    from drand_tpu.chain.store import SqliteStore
    s = SqliteStore(path, codec=codec)
    for i in range(0, len(beacons), 8192):
        s.put_many(beacons[i:i + 8192])
    return s


async def _serve(store):
    """One serving node: the real Protocol.SyncChain handler over the
    given backlog store, on an ephemeral localhost port."""
    import grpc.aio

    from drand_tpu.beacon.sync_manager import serve_sync_chain
    from drand_tpu.chain.segment import WIRE_CHUNK_DEFAULT
    from drand_tpu.core import convert
    from drand_tpu.net.rpc import service_handler

    class _SyncService:
        async def SyncChain(self, request, ctx):
            chunk = min(int(getattr(request, "chunk_size", 0)),
                        WIRE_CHUNK_DEFAULT)
            async for item in serve_sync_chain(
                    store, request.from_round, chunk_size=chunk):
                yield convert.item_to_packet(item)

    server = grpc.aio.server()
    server.add_generic_rpc_handlers(
        (service_handler("Protocol", _SyncService()),))
    port = server.add_insecure_port("127.0.0.1:0")
    await server.start()
    return server, f"127.0.0.1:{port}"


def _dump_rows(db_path: str):
    con = sqlite3.connect(db_path)
    try:
        return [(r, bytes(d)) for r, d in con.execute(
            "SELECT round, data FROM beacons ORDER BY round")]
    finally:
        con.close()


async def catch_up(addr: str, verifier, rounds: int,
                   consumer_codec: str | None = None):
    """One fresh-store catch-up of `rounds` rounds through the real
    client stack (GrpcBeaconNetwork.sync_chain -> SyncManager ->
    verifier -> store commit); returns (ok, elapsed_s, stats,
    consumer_db_path, last_committed_round).  Shared with chip_smoke.py,
    which also drives the pass that must FAIL (a corrupted served
    signature), so nothing is asserted here."""
    from drand_tpu.beacon.sync_manager import SyncManager, SyncRequest
    from drand_tpu.chain.beacon import Beacon
    from drand_tpu.chain.store import new_chain_store
    from drand_tpu.net.client import GrpcBeaconNetwork, PeerClients

    if consumer_codec:
        os.environ[CODEC_ENV] = consumer_codec
    folder = tempfile.mkdtemp(prefix="bench-sync-")
    db_path = os.path.join(folder, "db.sqlite")
    try:
        store = new_chain_store(db_path, _Group())
    finally:
        os.environ.pop(CODEC_ENV, None)
    store.put(Beacon(round=0, signature=b"genesis-seed-bench-sync"))
    peers = PeerClients()
    net = GrpcBeaconNetwork(peers, beacon_id="bench")
    peer = _Peer(addr)
    sm = SyncManager(store, _Group(), verifier, net, [peer], _Clock(),
                     insecure_store=store.insecure)
    t0 = time.perf_counter()
    try:
        ok = await sm._try_node(peer, SyncRequest(1, rounds))
        elapsed = time.perf_counter() - t0
        last = store.last().round
    finally:
        store.close()
        await peers.close()
    return ok, elapsed, dict(sm.stats), db_path, last


async def _one_epoch(addr: str, verifier, rounds: int, wire_chunk: int,
                     consumer_codec: str | None):
    """One timed catch-up that must succeed; returns (elapsed_s, stats,
    consumer_db_path)."""
    os.environ[WIRE_ENV] = str(wire_chunk)
    ok, elapsed, stats, db_path, last = await catch_up(
        addr, verifier, rounds, consumer_codec)
    assert ok, "sync must succeed"
    assert last == rounds, last
    return elapsed, stats, db_path


async def _run_pass(addr: str, verifier, rounds: int, epochs: int,
                    wire_chunk: int, consumer_codec: str | None):
    # warm epoch: touches the 512 ramp AND one big-bucket segment so the
    # timed epochs measure steady state, not first-dispatch costs
    await _one_epoch(addr, verifier, min(512 + 16384, rounds),
                     wire_chunk, consumer_codec)
    elapsed, stats, db = 0.0, None, ""
    per_epoch = []
    for _ in range(epochs):
        e, s, db = await _one_epoch(addr, verifier, rounds,
                                    wire_chunk, consumer_codec)
        per_epoch.append(round(e, 3))
        elapsed += e
        if stats is None:
            stats = s
        else:
            for k in s:
                stats[k] += s[k]
    total_rounds = epochs * rounds
    non_verify = elapsed - stats["verify_s"]
    return {
        "elapsed_s": round(elapsed, 3),
        "epoch_seconds": per_epoch,
        "rounds_per_s": round(total_rounds / elapsed, 1),
        "non_verify_s": round(non_verify, 4),
        "non_verify_s_per_16384": round(non_verify / total_rounds * 16384, 4),
        "stats": {k: (round(v, 4) if isinstance(v, float) else v)
                  for k, v in stats.items()},
    }, db


OBJ_CHAIN_HASH = hashlib.sha256(b"bench-sync-object-chain").digest()


async def _one_object_epoch(obj_root: str, verifier, rounds: int):
    """One fresh-store catch-up of `rounds` rounds from published
    segment objects (ISSUE 18); same consumer store stack as the gRPC
    passes so commit cost compares like for like."""
    from drand_tpu.chain.beacon import Beacon
    from drand_tpu.chain.store import new_chain_store
    from drand_tpu.objectsync import FilesystemBackend, ObjectSyncClient

    folder = tempfile.mkdtemp(prefix="bench-osync-")
    db_path = os.path.join(folder, "db.sqlite")
    store = new_chain_store(db_path, _Group())
    store.put(Beacon(round=0, signature=b"genesis-seed-bench-sync"))
    cli = ObjectSyncClient(FilesystemBackend(obj_root), store, verifier,
                           chain_hash=OBJ_CHAIN_HASH)
    t0 = time.perf_counter()
    res = await cli.sync(up_to=rounds)
    elapsed = time.perf_counter() - t0
    assert res.ok and res.synced_to == rounds, \
        f"object sync stopped at {res.synced_to}: {res.error}"
    store.close()
    return elapsed, dict(cli.stats), db_path


async def _run_object_pass(obj_root: str, verifier, rounds: int,
                           epochs: int):
    await _one_object_epoch(obj_root, verifier, rounds)   # warm epoch
    elapsed, stats, db = 0.0, None, ""
    per_epoch = []
    for _ in range(epochs):
        e, s, db = await _one_object_epoch(obj_root, verifier, rounds)
        per_epoch.append(round(e, 3))
        elapsed += e
        if stats is None:
            stats = s
        else:
            for k in s:
                stats[k] += s[k]
    total_rounds = epochs * rounds
    non_verify = elapsed - stats["verify_s"]
    return {
        "elapsed_s": round(elapsed, 3),
        "epoch_seconds": per_epoch,
        "rounds_per_s": round(total_rounds / elapsed, 1),
        "non_verify_s": round(non_verify, 4),
        "non_verify_s_per_16384": round(non_verify / total_rounds * 16384, 4),
        "stats": {k: (round(v, 4) if isinstance(v, float) else v)
                  for k, v in stats.items()},
    }, db


async def _main_object(args, sigs, verifier) -> dict:
    """--mode=object: publish the backlog once as sealed 16384-round
    segment objects (filesystem backend), then race a fresh-store object
    sync against the chunked gRPC wire over the same rounds.  Gate: the
    object path's non-verify host cost per 16384-round segment within
    2x of the chunked wire, and a bit-identical committed store."""
    from drand_tpu.chain.beacon import Beacon
    from drand_tpu.objectsync import (DEFAULT_SEGMENT_ROUNDS,
                                      FilesystemBackend, ObjectPublisher)

    backlog = sigs.shape[0]
    beacons = [Beacon(round=i + 1, signature=bytes(sigs[i]))
               for i in range(backlog)]
    serve_dir = tempfile.mkdtemp(prefix="bench-sync-serve-")
    store_bin = _fill_store(os.path.join(serve_dir, "bin.db"), beacons, None)
    obj_root = os.path.join(serve_dir, "objects")
    pub = ObjectPublisher(store_bin, FilesystemBackend(obj_root),
                          chain_hash=OBJ_CHAIN_HASH,
                          scheme_id=_Group.scheme_id,
                          segment_rounds=DEFAULT_SEGMENT_ROUNDS)
    await pub.load_manifest()
    t0 = time.perf_counter()
    published = await pub.publish_sealed()
    publish_s = time.perf_counter() - t0
    covered = pub.manifest.tip
    assert covered >= 2 * DEFAULT_SEGMENT_ROUNDS, \
        f"backlog {backlog} seals only {published} segments; " \
        f"raise BENCH_SYNC_BACKLOG"

    srv_bin, addr_bin = await _serve(store_bin)
    try:
        # identical round range on both paths (objects cover only the
        # sealed prefix; the wire would otherwise sync the ragged tail)
        chunked, db_chunked = await _run_pass(
            addr_bin, verifier, covered, args.epochs,
            wire_chunk=512, consumer_codec=None)
        objpass, db_object = await _run_object_pass(
            obj_root, verifier, covered, args.epochs)
    finally:
        await srv_bin.stop(None)
        store_bin.close()

    # correctness gate: a store caught up purely from objects must be
    # BIT-identical to one caught up over the gRPC wire
    assert _dump_rows(db_object) == _dump_rows(db_chunked), \
        "object sync and chunked wire committed different store contents"

    ratio = (objpass["non_verify_s_per_16384"]
             / max(chunked["non_verify_s_per_16384"], 1e-9))
    report = {
        "metric": "non-verify host seconds per 16384-round catch-up "
                  "segment, object-store sync vs chunked gRPC wire",
        "mode": args.mode,
        "device": "stub-verify",
        "backlog": covered,
        "epochs": args.epochs,
        "segments_published": published,
        "publish_s": round(publish_s, 3),
        "passes": {"chunked": chunked, "object": objpass},
        "object_vs_chunked": round(ratio, 2),
        "target_ratio": 2.0,
        "pass": ratio <= 2.0,
        "bit_identical_object_vs_chunked": True,
    }
    return report


def real_fixture(backlog: int):
    """(sigs[backlog, 96], ChainVerifier) of the real scheme
    `pedersen-bls-unchained` under the fixture key: rounds 1..16384 from
    the committed bench fixture, the rest from its committed
    `_extend_chain_native` extension (signed afresh only when absent)."""
    import bench  # noqa: E402  (repo root on path)
    from drand_tpu.chain.scheme import scheme_by_id
    from drand_tpu.chain.verify import ChainVerifier
    from drand_tpu.crypto.bls12381 import curve as GC
    sk, pk, shape, sigs = bench._chain_fixture("unchained", 16384)
    pk_tag = hashlib.sha256(GC.g1_to_bytes(pk)).hexdigest()[:8]
    sigs = _extend_chain_native(sk, shape, sigs, backlog, pk_tag)
    return sigs, ChainVerifier(scheme_by_id(_Group.scheme_id),
                               GC.g1_to_bytes(pk))


async def _main(args) -> dict:
    from drand_tpu.chain.beacon import Beacon

    if args.mode == "object":
        return await _main_object(args, _stub_signatures(BACKLOG),
                                  _StubVerifier())
    if args.mode == "real":
        import bench  # noqa: E402  (repo root on path)
        bench._setup_jax()
        sigs, verifier = real_fixture(BACKLOG)
        import jax
        device = str(jax.devices()[0].platform)
    else:
        sigs = _stub_signatures(BACKLOG)
        verifier = _StubVerifier()
        device = "stub-verify"
    backlog = sigs.shape[0]
    beacons = [Beacon(round=i + 1, signature=bytes(sigs[i]))
               for i in range(backlog)]

    serve_dir = tempfile.mkdtemp(prefix="bench-sync-serve-")
    store_bin = _fill_store(os.path.join(serve_dir, "bin.db"), beacons, None)
    store_json = _fill_store(os.path.join(serve_dir, "json.db"),
                             beacons, "json")
    srv_bin, addr_bin = await _serve(store_bin)
    srv_json, addr_json = await _serve(store_json)
    try:
        chunked, db_chunked = await _run_pass(
            addr_bin, verifier, backlog, args.epochs,
            wire_chunk=512, consumer_codec=None)
        fallback, db_fallback = await _run_pass(
            addr_bin, verifier, backlog, args.epochs,
            wire_chunk=0, consumer_codec=None)
        legacy, _ = await _run_pass(
            addr_json, verifier, backlog, args.epochs,
            wire_chunk=0, consumer_codec="json")
    finally:
        await srv_bin.stop(None)
        await srv_json.stop(None)
        store_bin.close()
        store_json.close()

    # correctness gate: the chunked wire and the per-beacon fallback must
    # commit BIT-identical stores (same rows, same binary codec bytes)
    assert _dump_rows(db_chunked) == _dump_rows(db_fallback), \
        "chunked and fallback wire committed different store contents"

    speedup = (legacy["non_verify_s_per_16384"]
               / max(chunked["non_verify_s_per_16384"], 1e-9))
    report = {
        "metric": "non-verify host seconds per 16384-round catch-up "
                  "segment, two real-gRPC nodes THROUGH SyncManager",
        "mode": args.mode,
        "device": device,
        "backlog": backlog,
        "epochs": args.epochs,
        "passes": {"chunked": chunked, "fallback": fallback,
                   "legacy": legacy},
        "non_verify_speedup_vs_legacy": round(speedup, 1),
        "target_speedup": 5.0,
        "pass": speedup >= 5.0,
        "bit_identical_chunked_vs_fallback": True,
    }
    return report


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--mode", choices=("stub", "real", "object"),
                    default="stub")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_sync.json"))
    args = ap.parse_args()
    result = asyncio.run(_main(args))
    blob = json.dumps(result, indent=1)
    with open(args.out, "w") as f:
        f.write(blob + "\n")
    print(blob)
    if not result["pass"]:
        bar = "2x-of-chunked object-sync" if args.mode == "object" \
            else "5x"
        print(f"bench_sync: below the {bar} acceptance bar",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()

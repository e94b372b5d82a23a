"""Chip smoke: the served catch-up path, once, on a real TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --four-chips # the sharded verify alone, four chips

One process, one import of JAX, no child.  A serving node (a SqliteStore
holding rounds 1..65,536 of the committed `pedersen-bls-unchained`
fixture chain behind the real `Protocol.SyncChain` handler on localhost
gRPC) and a consuming node (`GrpcBeaconNetwork.sync_chain` -> `SyncManager`
-> `ChainVerifier` -> `Verifier.verify_batch_async` -> device -> store
commit): the stack of `tools/bench_sync.py --mode real`, whose harness
this script imports.  It then checks what came out:

  * every round committed, which only happens after its segment verified
    true on the device, and the committed signatures equal the fixture
    byte for byte;
  * a second pass, against a served store with ONE corrupted signature,
    commits neither that round nor any after it;
  * the host tier (`ChainVerifier.verify_beacon`: native C++ or the golden
    model) agrees with the device on a sample of rounds and on the
    corrupted one.

Every line of standard output is one JSON object.  All but the last are
OBSERVATIONS of this one run (versions, cache entries, seconds to build
each program, seconds the catch-up took on the host's clock): they say
the system starts and is right, and are not metrics.  The last line is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`,
or `{"ok": false, "reason": ...}` with a non-zero exit code: when the
platform is not `tpu`, when any check fails, when any phase raises.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

BACKLOG = 65536          # rounds the committed fixture chain holds
SAMPLE = 32              # rounds re-checked on the host tier


class SmokeFailure(Exception):
    """A check of the smoke did not hold; the message is the reason."""


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


def check_device(dev) -> None:
    """The smoke proves the TPU path or nothing: any other platform fails,
    and so does a TPU on which the Pallas kernels are not selected."""
    if dev.platform != "tpu":
        raise SmokeFailure(
            f"platform is {dev.platform!r}, not 'tpu': chip_smoke.py runs "
            "the TPU path and has no CPU fallback")
    from drand_tpu.ops.pallas_field import use_pallas
    if not use_pallas():
        raise SmokeFailure("use_pallas() is False on a TPU")


def check_program(rec: dict) -> None:
    """A verify program without Pallas kernels is the pure-XLA graph."""
    if rec["tpu_custom_calls"] <= 0:
        raise SmokeFailure(
            f"program {rec['program']} holds no tpu_custom_call: it is the "
            "pure-XLA graph, not the kernel path")


def _versions() -> dict:
    from importlib import metadata

    import jax
    import jaxlib
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu, "python": sys.version.split()[0]}


def _cache_entries(d: str) -> int:
    return sum(len(fs) for _, _, fs in os.walk(d))


class _CacheEvents:
    """Counts JAX's own persistent-cache hit and miss events."""

    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1

    def during(self, fn):
        """fn() and the (hits, misses) JAX reported while it ran."""
        h, m = self.hits, self.misses
        out = fn()
        return out, self.hits - h, self.misses - m


def _build(what: str, verifier, bucket: int, events: _CacheEvents) -> dict:
    """Build `bucket`'s program on `verifier` and emit its record: what
    `Verifier.build` measured, plus whether JAX's persistent cache served
    the compile."""
    rec, hits, misses = events.during(lambda: verifier.build(bucket))
    rec.update(
        load=what, from_persistent_cache=hits > 0 and misses == 0,
        tpu_custom_calls=rec.pop("lowered").as_text().count(
            "tpu_custom_call"))
    emit(program=rec)
    check_program(rec)
    return rec


def _committed_sigs(db_path: str, sig_len: int):
    """(rounds[N], sigs[N, sig_len]) of the consumer store, round 0 (the
    genesis row) left out."""
    from drand_tpu.chain.store import SqliteStore
    store = SqliteStore(db_path)
    try:
        rows = [(r, sig) for r, sig, _prev
                in store.read_fields(1, BACKLOG + 1)]
    finally:
        store.close()
    rounds = np.array([r for r, _ in rows], dtype=np.uint64)
    sigs = np.frombuffer(b"".join(s for _, s in rows),
                         dtype=np.uint8).reshape(len(rows), sig_len)
    return rounds, sigs


async def _serve_and_catch_up(sigs: np.ndarray, verifier, label: str):
    """Serve `sigs` as rounds 1..N from a fresh store over localhost gRPC
    and catch a fresh consumer up through the real client stack."""
    from drand_tpu.chain.beacon import Beacon
    from tools import bench_sync as H

    n = sigs.shape[0]
    beacons = [Beacon(round=i + 1, signature=bytes(sigs[i]))
               for i in range(n)]
    with tempfile.TemporaryDirectory(prefix=f"chip-smoke-{label}-") as d:
        store = H._fill_store(os.path.join(d, "serve.db"), beacons, None)
        server, addr = await H._serve(store)
        try:
            ok, elapsed, stats, db, _last = await H.catch_up(addr, verifier,
                                                             n)
        finally:
            await server.stop(None)
            store.close()
    rounds, got = _committed_sigs(db, sigs.shape[1])
    shutil.rmtree(os.path.dirname(db), ignore_errors=True)
    emit(catch_up=label, served_rounds=n, sync_ok=bool(ok),
         committed_rounds=int(len(rounds)), wall_s=elapsed,
         sync_manager_stats=stats)
    return ok, rounds, got


def _check_host_agrees(when: str, chain_verifier, device_verifier,
                       bucket: int, sigs: np.ndarray, bad_round: int,
                       bad_sig: np.ndarray) -> None:
    """SAMPLE valid rounds and the corrupted one, verified by the host
    tier and by the device (one batch, tiled up to a bucket that is
    already built), must get the same verdicts, and the right ones."""
    from drand_tpu.chain.beacon import Beacon
    n = sigs.shape[0]
    take = np.unique(np.linspace(0, n - 1, min(SAMPLE, n)).astype(int))
    rounds = np.concatenate([take + 1, [bad_round]]).astype(np.uint64)
    batch = np.concatenate([sigs[take], bad_sig[None]], axis=0)
    host = np.array([chain_verifier.verify_beacon(
        Beacon(round=int(r), signature=bytes(s)))
        for r, s in zip(rounds, batch)])
    idx = np.resize(np.arange(len(rounds)), bucket)
    dev = device_verifier.verify_batch(rounds[idx], batch[idx])[:len(rounds)]
    want = np.array([True] * len(take) + [False])
    emit(host_vs_device={"when": when, "rounds": int(len(rounds)),
                         "host_true": int(host.sum()),
                         "device_true": int(dev.sum()),
                         "corrupted_round": int(bad_round),
                         "host_on_corrupted": bool(host[-1]),
                         "device_on_corrupted": bool(dev[-1])})
    if not (host == want).all():
        raise SmokeFailure("the host tier disagrees with the fixture")
    if not (host == dev).all():
        raise SmokeFailure(f"{when}: host and device disagree on "
                           f"{int((host != dev).sum())} rounds")


def _start():
    """What both modes begin with: the versions, the platform check, the
    compile cache.  Returns (devices, cache directory)."""
    import jax

    from drand_tpu import aot
    emit(note="every line but the last is an observation of this run, "
              "not a metric", versions=_versions())
    devs = jax.devices()
    check_device(devs[0])
    return devs, aot.enable_persistent_cache()


async def smoke(backlog: int = BACKLOG) -> dict:
    """The one-chip smoke; returns the device description."""
    devs, cache_dir = _start()
    if len(devs) != 1:
        # ChainVerifier shards over every visible device by itself
        raise SmokeFailure(f"the one-chip smoke found {len(devs)} "
                           "devices; the sharded path is --four-chips")
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": 1}
    before = _cache_entries(cache_dir)
    events = _CacheEvents()
    emit(compile_cache={"dir": cache_dir, "entries_before": before,
                        "placed_by": "JAX_COMPILATION_CACHE_DIR"
                        if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                        else "default"})

    import drand_tpu.verify as V
    from drand_tpu.beacon.sync_manager import SYNC_CHUNK_MAX
    from tools import bench_sync as H

    sigs, chain_verifier = H.real_fixture(backlog)
    device_verifier = chain_verifier._verifier
    # ONE program, the deep-backlog bucket: building a verify program
    # takes a large part of this script's time limit (PR 22: about six
    # minutes on the chip's host, most of it Python tracing).  The
    # catch-up asks the verifier what a dispatch is charged for and
    # cuts its segments where this one program is full, so no
    # 512-round first chunk is padded into it.  Built ahead of the
    # traffic so that its cost is read apart from the catch-up's.
    bucket = V._bucket(min(SYNC_CHUNK_MAX, backlog))
    emit(buckets=[bucket], note="one program; the buckets a node would "
         f"choose from are {list(V._BUCKETS)}")
    V._BUCKETS = (bucket,)
    _build("first build", device_verifier, bucket, events)

    # pass 1: the clean chain
    ok, rounds, got = await _serve_and_catch_up(sigs, chain_verifier,
                                                "clean")
    if not ok or len(rounds) != backlog \
            or not (rounds == np.arange(1, backlog + 1)).all():
        raise SmokeFailure(
            f"clean catch-up committed {len(rounds)} of {backlog} rounds "
            f"(sync ok={ok})")
    if not (got == sigs).all():
        raise SmokeFailure("committed signatures differ from the fixture")

    # pass 2: one corrupted signature on the served side
    bad_round = backlog * 5 // 8
    bad = sigs.copy()
    bad[bad_round - 1, 5] ^= 0xFF
    ok2, rounds2, got2 = await _serve_and_catch_up(bad, chain_verifier,
                                                   "corrupted")
    last = int(rounds2[-1]) if len(rounds2) else 0
    if ok2 or last >= bad_round:
        raise SmokeFailure(
            f"corrupted round {bad_round} was served and the consumer "
            f"committed up to round {last} (sync ok={ok2})")
    if not (got2 == sigs[:len(rounds2)]).all():
        raise SmokeFailure("the corrupted pass committed other bytes than "
                           "the fixture's")

    _check_host_agrees("after the catch-up", chain_verifier,
                       device_verifier, bucket, sigs, bad_round,
                       bad[bad_round - 1])

    # does the persistent cache serve a second load of the same program,
    # and is what it serves right?  (ROADMAP D2 waits on this answer.)
    # A fresh Verifier builds the bucket again: the kernel bodies are
    # already traced (PallasField._launch), the program is lowered anew,
    # and the compile asks the cache first; the executable it gives then
    # verifies the sample again.
    again = V.Verifier(device_verifier._pk_golden, device_verifier.shape)
    _build("second build, fresh Verifier", again, bucket, events)
    _check_host_agrees("after the second build", chain_verifier, again,
                       bucket, sigs, bad_round, bad[bad_round - 1])
    emit(compile_cache={"dir": cache_dir, "entries_before": before,
                        "entries_after": _cache_entries(cache_dir)})
    stats = dev.memory_stats() or {}
    emit(device_memory={"peak_bytes_in_use":
                        stats.get("peak_bytes_in_use"),
                        "bytes_limit": stats.get("bytes_limit")})
    return device


def _quicknet_fixture(rows: int):
    """(sigs[rows, 48], ChainVerifier): rounds 1..rows of the committed
    `bls-unchained-g1-rfc9380` bench chain (quicknet's scheme: signatures
    on G1, key on G2) under the fixture key."""
    import bench
    from drand_tpu.chain.scheme import scheme_by_id
    from drand_tpu.chain.verify import ChainVerifier
    from drand_tpu.crypto.bls12381 import curve as GC
    _sk, pk, _shape, sigs = bench._chain_fixture("unchained_g1", rows)
    return sigs, ChainVerifier(scheme_by_id("bls-unchained-g1-rfc9380"),
                               GC.g2_to_bytes(pk))


def four_chips(per_device: int = 16384) -> dict:
    """The sharded verify alone, at the deep catch-up's size (the program
    of the benchmark's `catchup-deep.quicknet-g1-x4`): `ShardedVerifier`
    builds the program of `per_device` rows for every device of the mesh
    (`build`: the only build here that may have to trace), one batch of
    four times that goes through it, and the same rows through the
    one-device `Verifier` on device 0, whose build has to LOAD the form
    the mesh's build used.  Equal verdicts, one corrupted signature in
    every device's slice, and a shard of the input on every device.

    On a four-chip v5e (PR 34; observations, not metrics): the whole
    smoke 199 s with no traced build (the mesh's build loaded the file a
    one-chip process had written; its executable compiled in 98 s, the
    one-device one in 70 s), 65,536 rows in 0.85 s over the mesh and in
    3.44 s as four dispatches on one chip, 65,532 true on both."""
    import jax

    import drand_tpu.verify as V
    from drand_tpu.parallel import ShardedVerifier

    devs, cache_dir = _start()
    if len(devs) != 4:
        raise SmokeFailure(f"--four-chips needs 4 devices, JAX has "
                           f"{len(devs)}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    emit(compile_cache={"dir": cache_dir,
                        "entries_before": _cache_entries(cache_dir)})
    events = _CacheEvents()

    chain, chain_verifier = _quicknet_fixture(per_device)
    V._BUCKETS = (per_device,)        # one program, as `smoke` has it
    sharded = chain_verifier._verifier
    if not isinstance(sharded, ShardedVerifier) or sharded.n_dev != 4:
        raise SmokeFailure("ChainVerifier did not take the sharded path "
                           "on a four-device host")
    # every device is handed rounds 1..per_device, each with another
    # signature corrupted: at its slice's first row, inside it, at its
    # last row
    batch = 4 * per_device
    rounds = np.tile(np.arange(1, per_device + 1, dtype=np.uint64), 4)
    sigs = np.tile(chain, (4, 1))
    bad = [0, per_device + per_device // 3, 2 * per_device + per_device // 2,
           batch - 1]
    for row in bad:
        sigs[row, 5] ^= 0xFF

    _build("the mesh's build", sharded, per_device, events)
    t0 = time.perf_counter()
    ok4 = sharded.verify_batch(rounds, sigs)
    t1 = time.perf_counter()
    sharded.verify_batch(rounds, sigs)
    t2 = time.perf_counter()
    placed = jax.device_put(sigs, sharded._named(sharded.axis, None))
    held = sorted(sh.device.id for sh in placed.addressable_shards)
    rows = {int(sh.data.shape[0]) for sh in placed.addressable_shards}

    # one device, a shard's worth at a time: the same program, from the
    # same exported form, so this build traces nothing either
    one = sharded.verifier
    rec = _build("one device, the same form", one, per_device, events)
    if rec["source"] != "loaded":
        raise SmokeFailure("the one-device build traced the program again "
                           f"(source {rec['source']!r})")
    t3 = time.perf_counter()
    ok1 = np.concatenate([one.verify_batch(
        rounds[i:i + per_device], sigs[i:i + per_device])
        for i in range(0, batch, per_device)])
    t4 = time.perf_counter()
    emit(four_chips={"batch": batch, "devices_holding_a_shard": held,
                     "rows_per_shard": sorted(rows),
                     "sharded_true": int(ok4.sum()),
                     "one_device_true": int(ok1.sum()),
                     "sharded_first_run_s": t1 - t0,
                     "sharded_run_s": t2 - t1,
                     "one_device_four_runs_s": t4 - t3})
    if held != sorted(d.id for d in devs) or rows != {per_device}:
        raise SmokeFailure(f"shards on devices {held} with {rows} rows, "
                           f"want {per_device} rows on each of 4")
    want = np.ones(batch, dtype=bool)
    want[bad] = False
    if not (ok4 == ok1).all() or not (ok1 == want).all():
        raise SmokeFailure("sharded and one-device verdicts differ, or "
                           "differ from the fixture")
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the sharded verify over four chips against "
                         "the one-device verify, and nothing else")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    try:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        if args.four_chips:
            device = four_chips()
        else:
            device = asyncio.run(smoke())
    except Exception as exc:  # the last line must say why
        emit(ok=False, reason=f"{type(exc).__name__}: {exc}"[:2000],
             wall_s=time.perf_counter() - t0)
        return 1
    emit(smoke_wall_s=time.perf_counter() - t0)
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())

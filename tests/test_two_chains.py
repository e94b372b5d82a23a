"""One daemon, two chains (ISSUE 36): what the program owes a process that
carries several beacon processes on one device.

  - two `SyncManager`s in one event loop, each with its own verifier and
    store, catch up two chains at once; a fault in one chain stops that
    chain before it and leaves the other untouched;
  - every span under a catch-up says whose chain it is (`beacon_id`), and
    `verify.dispatch` says what the one device queue held at its enqueue
    (`in_flight`, `behind_other`);
  - overlapping `_collector_paused` blocks leave the collector as they
    found it.
"""

import asyncio
import gc
import json
import os
import threading

import numpy as np
import pytest

import drand_tpu.beacon.sync_manager as SM
import drand_tpu.verify as V
from benchmark import harness as H
from drand_tpu import tracing
from drand_tpu.chain.beacon import Beacon
from drand_tpu.chain.scheme import scheme_by_id
from drand_tpu.chain.verify import ChainVerifier
from drand_tpu.profiling.dispatch import DISPATCH, DispatchRecorder

ROUNDS = 1024
CHAINS = ("default", "quicknet")


def _chain_configs() -> dict[str, dict]:
    """The two chains of the benchmark's `loe-mainnet-2chains`: the
    chained G2 `default` and the G1 `quicknet`, each with its fixture."""
    with open(os.path.join(H.BENCH_DIR, "configs",
                           "loe-mainnet-2chains.json")) as f:
        config = json.load(f)
    return {"default": config, "quicknet": config["second_chain"]}


@pytest.fixture(scope="module")
def chains():
    out = {}
    for name, config in _chain_configs().items():
        sigs = np.load(os.path.join(H.BENCH_DIR, "fixtures",
                                    config["fixture"]["file"]))[:ROUNDS]
        out[name] = (config, np.ascontiguousarray(sigs),
                     H.previous_sigs(config, sigs))
    return out


class _ServedStore:
    """A serving node's side of `SyncChain` without the wire: the stored
    backlog as the packed chunks `serve_sync_chain` makes of it."""

    def __init__(self, store):
        self.store = store

    def sync_chain(self, peer, from_round):
        return SM.serve_sync_chain(self.store, from_round, chunk_size=64)


def _serving(tmp_path, name, sigs, prevs):
    from drand_tpu.chain.store import SqliteStore
    store = SqliteStore(str(tmp_path / f"serve-{name}.db"))
    H.fill_store(store, H.beacons_of(sigs, prevs))
    return store


async def _both_catch_up(tmp_path, chains, served: dict) -> dict:
    """{chain: (ok, stored rounds, sigs, prevs)} of both chains catching
    up at once, each from `served[chain]`, into fresh node stores."""
    stores, managers = {}, {}
    for name, (config, _sigs, _prevs) in chains.items():
        group = H.group_of(config)
        store = H.new_node_store(str(tmp_path / f"node-{name}.db"), group)
        cv = ChainVerifier(scheme_by_id(config["scheme_id"]),
                           bytes.fromhex(config["public_key_hex"]),
                           beacon_id=name)
        stores[name] = store
        managers[name] = SM.SyncManager(
            store, group, H.HostVerifier(cv), _ServedStore(served[name]),
            [object()], H.Clock(), insecure_store=store.insecure,
            beacon_id=name)
    try:
        oks = await asyncio.gather(*(
            managers[name]._try_node(object(), SM.SyncRequest(1, ROUNDS))
            for name in chains))
        return {name: (ok, *H.stored_rows(stores[name], ROUNDS,
                                          chains[name][1].shape[1]))
                for name, ok in zip(chains, oks)}
    finally:
        for store in stores.values():
            store.close()


@pytest.mark.parametrize("faulted", [None, *CHAINS])
def test_two_chains_catch_up_at_once_and_a_fault_stays_in_its_chain(
        tmp_path, chains, faulted):
    bad_round = 700
    served = {}
    for name, (_config, sigs, prevs) in chains.items():
        if name == faulted:
            sigs = sigs.copy()
            sigs[bad_round - 1, 5] ^= 0x10
        served[name] = _serving(tmp_path, name, sigs, prevs)
    try:
        got = asyncio.run(_both_catch_up(tmp_path, chains, served))
    finally:
        for store in served.values():
            store.close()
    for name, (config, sigs, prevs) in chains.items():
        ok, rounds, stored, stored_prevs = got[name]
        n = len(rounds)
        assert (rounds == np.arange(1, n + 1)).all()
        # never a byte that the chain does not hold, in either field
        assert H.rows_differing(stored, stored_prevs, sigs[:n],
                                prevs and prevs[:n]) == 0
        if name == faulted:
            assert not ok and n < bad_round
        else:
            assert ok and n == ROUNDS
            # ... and the chain is what the plain reference says it is
            at = [1, 2, bad_round, ROUNDS]
            assert H.reference_verdicts(
                config, at, stored[np.array(at) - 1],
                prevs and [stored_prevs[r - 1] for r in at]).all()


# -- the spans ------------------------------------------------------------------

class _FakeDevice(V.Verifier):
    """A `Verifier` whose program is a row's first byte."""

    def __init__(self, beacon_id=""):
        self.shape = V.SHAPE_UNCHAINED
        self.beacon_id = beacon_id
        self._pk = None
        self._kernels = {}

    def _kernel(self, m):
        return lambda msgs, sigs, pk: np.asarray(sigs)[:, 0] != 0xFF


def _dispatch(verifier, n=3):
    return verifier.verify_batch_async(
        np.arange(1, n + 1), np.zeros((n, 96), dtype=np.uint8))


def _dispatch_spans():
    return [(s.beacon_id, s.attrs["in_flight"], s.attrs["behind_other"],
             s.attrs["dispatches"])
            for s in tracing.RECORDER.spans() if s.name == "verify.dispatch"]


def test_a_dispatch_says_what_the_device_queue_held(monkeypatch):
    monkeypatch.setattr(V, "_BUCKETS", (8,))
    DISPATCH.clear()
    tracing.RECORDER.clear()
    a, b = _FakeDevice("default"), _FakeDevice("quicknet")
    a1, b1, a2 = _dispatch(a), _dispatch(b), _dispatch(a)
    assert _dispatch_spans() == [("default", 0, 0, 1), ("quicknet", 1, 1, 1),
                                 ("default", 2, 1, 1)]
    assert b1().all() and b1().all()        # resolved, and counted once
    b2 = _dispatch(b)                       # behind both of the other's
    assert _dispatch_spans()[-1] == ("quicknet", 2, 1, 1)
    assert a1().all() and a2().all()
    a3 = _dispatch(a)                       # behind the other's one
    assert _dispatch_spans()[-1] == ("default", 1, 1, 1)
    del b2                                  # dropped unresolved: gone
    a4 = _dispatch(a)
    assert _dispatch_spans()[-1] == ("default", 1, 0, 1)
    assert a3().all() and a4().all()
    assert _dispatch(b)().all()
    assert _dispatch_spans()[-1] == ("quicknet", 0, 0, 1)
    assert DISPATCH.snapshot()["in_flight"] == 0
    resolved = [s.beacon_id for s in tracing.RECORDER.spans()
                if s.name == "verify.resolve"]
    assert resolved == ["quicknet", "default", "default", "default",
                        "default", "quicknet"]


def test_one_verifier_alone_is_never_behind_another(monkeypatch):
    monkeypatch.setattr(V, "_BUCKETS", (8,))
    DISPATCH.clear()
    tracing.RECORDER.clear()
    only = _FakeDevice()
    pending = [_dispatch(only) for _ in range(4)]
    assert all(p().all() for p in pending)
    assert _dispatch_spans() == [("", k, 0, 1) for k in range(4)]


def test_the_count_is_the_recorders_own():
    ring, owner, other = DispatchRecorder(), object(), object()
    t1, ahead, behind = ring.enqueue(owner)
    assert (ahead, behind) == (0, 0)
    t2, ahead, behind = ring.enqueue(other)
    assert (ahead, behind) == (1, 1)
    ring.resolved(t1)
    ring.resolved(t1)                       # twice is once
    t3, ahead, behind = ring.enqueue(other)
    assert (ahead, behind) == (1, 0)
    assert ring.snapshot()["in_flight"] == 2
    del t3                                  # a resolver dropped unresolved
    assert ring.snapshot()["in_flight"] == 1
    ring.clear()
    assert ring.snapshot()["in_flight"] == 0


class _MemStore:
    def __init__(self, seed: bytes):
        self.by_round = {0: Beacon(round=0, signature=seed)}

    def put_many(self, beacons):
        for b in beacons:
            self.by_round[b.round] = b

    def last(self):
        return self.by_round[max(self.by_round)]


def test_every_span_of_a_catch_up_says_whose_chain_it_is(chains, tmp_path,
                                                         monkeypatch):
    monkeypatch.setattr(V, "_BUCKETS", (64,))
    monkeypatch.setattr(SM, "SYNC_CHUNK", 64)
    tracing.RECORDER.clear()
    DISPATCH.clear()

    async def main():
        managers = []
        for name, (config, sigs, prevs) in chains.items():
            scheme = scheme_by_id(config["scheme_id"])
            # the verifier names no chain: the manager's spans hand theirs
            # down, as the benchmark's first chain's do
            cv = ChainVerifier(scheme, bytes.fromhex(
                config["public_key_hex"]))
            cv._lazy_verifier = _FakeDevice()
            cv._lazy_verifier.shape = scheme.shape
            cv._lazy_verifier._single_host = lambda *_: (True, "fake")
            served = _serving(tmp_path, name, sigs[:256],
                              prevs and prevs[:256])
            group = H.group_of(config)
            managers.append(SM.SyncManager(
                _MemStore(group.genesis_seed), group, cv,
                _ServedStore(served), [object()], H.Clock(),
                beacon_id=name))
        return await asyncio.gather(*(
            m._try_node(object(), SM.SyncRequest(1, 256)) for m in managers))

    assert asyncio.run(main()) == [True, True]
    spans = tracing.RECORDER.spans()
    by_name: dict[str, set] = {}
    for s in spans:
        by_name.setdefault(s.name, set()).add(s.beacon_id)
    assert {"sync.catchup", "sync.segment", "sync.queue_wait", "sync.pack",
            "sync.settle", "verify.segment", "verify.dispatch",
            "verify.resolve", "store.materialize",
            "verify.genesis_link"} <= set(by_name)
    for name, ids in by_name.items():
        if name.startswith(("sync.", "verify.", "store.")):
            want = {"default"} if name == "verify.genesis_link" \
                else set(CHAINS)
            assert ids == want, name
    # two pipelines into one queue (whether one found the other's program
    # there is the host's timing: the cases above pin the counting)
    assert all(s.attrs["dispatches"] == 1 and s.attrs["behind_other"] in
               (0, 1) and s.attrs["in_flight"] >= s.attrs["behind_other"]
               for s in spans if s.name == "verify.dispatch")


def _device_verifier(bp):
    """The `Verifier` under a process's `ChainVerifier` (under its mesh,
    on a host of several devices)."""
    v = bp.verifier._verifier
    return getattr(v, "verifier", v)


def test_a_daemon_gives_each_beacon_process_its_own(tmp_path):
    from drand_tpu.core import Config, DrandDaemon
    from drand_tpu.key.group import Group
    from drand_tpu.key.keys import DistPublic, Pair
    from drand_tpu.key.store import FileStore

    daemon = DrandDaemon(Config(folder=str(tmp_path),
                                private_listen="127.0.0.1:0",
                                control_port=0))
    # (a group file's key is a G1 point: both processes get G2-signature
    # schemes here; what is held to is whose each object is)
    key = bytes.fromhex(_chain_configs()["default"]["public_key_hex"])
    for name, scheme_id in zip(CHAINS, ("pedersen-bls-chained",
                                        "pedersen-bls-unchained")):
        pair = Pair.generate(f"127.0.0.1:{4000 + len(name)}",
                             seed=name.encode())
        FileStore(str(tmp_path), name).save_key_pair(pair)
        bp = daemon.instantiate(name)
        bp.load_keypair()
        group = Group(threshold=1, period=3,
                      nodes=Group.sort_nodes([pair.public]),
                      genesis_time=1, scheme_id=scheme_id, beacon_id=name,
                      public_key=DistPublic([key]))
        group.genesis_seed = group.hash()
        bp.set_group(group, None)
    try:
        got = {name: daemon.processes[name] for name in CHAINS}
        for name, bp in got.items():
            assert bp.sync_manager.beacon_id == name
            assert bp.verifier.beacon_id == name
            assert bp.sync_manager.verifier is bp.verifier
            assert _device_verifier(bp).beacon_id == name
        a, b = got.values()
        assert a.sync_manager is not b.sync_manager
        assert _device_verifier(a) is not _device_verifier(b)
        assert a._store is not b._store
    finally:
        for bp in daemon.processes.values():
            bp._store.close()


# -- the collector ----------------------------------------------------------------

@pytest.mark.parametrize("first_out", ["first_in", "last_in"])
def test_overlapping_pauses_leave_the_collector_as_they_found_it(first_out):
    """Two chains' commits in two worker threads: the collector stays
    off until the last of them has ended, whichever ends first."""
    assert gc.isenabled()
    inside = [threading.Event(), threading.Event()]
    leave = [threading.Event(), threading.Event()]
    seen = {}

    def commit(i):
        with SM._collector_paused():
            inside[i].set()
            leave[i].wait(10)
            seen[f"in_{i}"] = gc.isenabled()
        seen[f"after_{i}"] = gc.isenabled()

    threads = [threading.Thread(target=commit, args=(i,)) for i in (0, 1)]
    threads[0].start()
    assert inside[0].wait(10)
    threads[1].start()
    assert inside[1].wait(10) and not gc.isenabled()
    order = (0, 1) if first_out == "first_in" else (1, 0)
    leave[order[0]].set()
    threads[order[0]].join(10)
    assert not gc.isenabled()       # the other's rest runs with it off
    leave[order[1]].set()
    threads[order[1]].join(10)
    assert gc.isenabled()
    assert seen == {"in_0": False, "in_1": False,
                    f"after_{order[0]}": False, f"after_{order[1]}": True}


def test_a_pause_that_found_the_collector_off_leaves_it_off():
    gc.disable()
    try:
        with SM._collector_paused():
            with SM._collector_paused():
                assert not gc.isenabled()
            assert not gc.isenabled()
        assert not gc.isenabled()
    finally:
        gc.enable()


def test_the_shared_counts_hold_under_more_threads_than_cores():
    """The two pieces of state every chain's workers share: the pause's
    depth (no block ever runs with the collector on, and it is back when
    all have ended) and the recorder's count (every enqueue is counted
    until its resolve, none twice)."""
    import sys

    ring = DispatchRecorder()
    workers, rounds = 3 * (os.cpu_count() or 4), 200
    seen_on, most = [], [0]
    start = threading.Barrier(workers)

    def work(i):
        owner = object()
        start.wait(10)
        for _ in range(rounds):
            with SM._collector_paused():
                if gc.isenabled():
                    seen_on.append(i)
                token, ahead, behind = ring.enqueue(owner)
                most[0] = max(most[0], ahead)
                assert 0 <= ahead < workers and behind in (0, 1)
            ring.resolved(token)

    assert gc.isenabled()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert not seen_on and gc.isenabled()
    assert SM._paused["depth"] == 0
    assert ring.snapshot()["in_flight"] == 0 and most[0] >= 1

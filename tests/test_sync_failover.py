"""`SyncManager.sync` against peers that fail: a bounded request goes
through its peers until the store holds `up_to`, and is false only when
every peer was tried.  192 rounds of the benchmark's fixtures a scheme
(the chained one carries its anchor across peers), peers that serve from
stores of their own through the real `serve_sync_chain` over an
in-memory network, eight rounds a message, and the program's host tier
for the verdicts.  What a peer does is set by the ORDER in which the
request opens streams, not by who the peer is: the shuffle decides
nothing here.
"""

import asyncio
import json
import os
import sqlite3

import numpy as np
import pytest

import drand_tpu.beacon.sync_manager as SM
from benchmark import harness as H
from drand_tpu import tracing
from drand_tpu.beacon.clock import SystemClock
from drand_tpu.chain.scheme import scheme_by_id
from drand_tpu.chain.store import SqliteStore, new_chain_store
from drand_tpu.chain.verify import ChainVerifier
from drand_tpu.resilience import Resilience

N = 192
MESSAGE = 8           # rounds a wire message
SEGMENT = 32          # rounds a verified segment
SCHEMES = ("unchained-g2", "quicknet-g1", "default-chained")


class _HostTier(H.HostVerifier):
    """The program's host tier, a row at a time, remembering what it has
    judged: the cases share 192 sound rows a scheme."""

    def __init__(self, cv, memo):
        super().__init__(cv)
        self._memo = memo

    def verify_beacons(self, beacons):
        out = []
        for b in beacons:
            key = (b.round, b.signature, b.previous_sig)
            if key not in self._memo:
                self._memo[key] = self._cv.verify_beacon(b)
            out.append(self._memo[key])
        return np.array(out, dtype=bool)


class _Chain:
    """A configuration of the benchmark cut to 192 rounds."""

    def __init__(self, name: str):
        with open(os.path.join(H.BENCH_DIR, "configs", name + ".json")) as f:
            self.config = json.load(f)
        sigs = np.load(os.path.join(H.BENCH_DIR, "fixtures",
                                    self.config["fixture"]["file"]))
        self.sigs = np.ascontiguousarray(sigs[:N])
        self.prevs = H.previous_sigs(self.config, self.sigs)
        self.group = H.group_of(self.config)
        cv = ChainVerifier(scheme_by_id(self.config["scheme_id"]),
                           bytes.fromhex(self.config["public_key_hex"]))
        self.verifier = _HostTier(cv, {})


_chains: dict[str, _Chain] = {}


@pytest.fixture(params=SCHEMES)
def chain(request, monkeypatch):
    monkeypatch.setattr(SM, "SYNC_CHUNK", SEGMENT)
    monkeypatch.setattr(SM, "SYNC_CHUNK_GROWTH", 1)
    if request.param not in _chains:
        _chains[request.param] = _Chain(request.param)
    tracing.RECORDER.clear()
    return _chains[request.param]


# -- the stand ------------------------------------------------------------------

class _Peer:
    def __init__(self, index: int):
        self.address = f"peer-{index}"


class _Net:
    """Hands the k-th stream the request opens the k-th behaviour of
    the script: ("sound",), ("short", rounds held), ("corrupt", round
    of the damaged row), ("drop", messages before the stream raises),
    ("liar", round whose signature has a bit flipped), ("down",).  Past
    the script's end every stream is the last behaviour's."""

    def __init__(self, tmp_path, chain: _Chain, script, with_status=False,
                 dead=()):
        self.tmp_path, self.chain, self.script = tmp_path, chain, script
        self.opened: list[tuple[str, int, str]] = []   # peer, from, what
        self.dead = {f"peer-{i}" for i in dead}        # fail their probe
        self._stores: dict = {}
        if with_status:
            self.status = self._status

    def _store(self, what: tuple) -> SqliteStore:
        """A serving node's store, made once a behaviour."""
        if what in self._stores:
            return self._stores[what]
        chain = self.chain
        sigs, held = chain.sigs, N
        if what[0] == "short":
            held = what[1]
        elif what[0] == "liar":
            sigs = sigs.copy()
            sigs[what[1] - 1, 5] ^= np.uint8(8)
        path = str(self.tmp_path / f"serve-{len(self._stores)}.db")
        store = SqliteStore(path)
        H.fill_store(store, H.beacons_of(
            sigs[:held], chain.prevs and chain.prevs[:held]))
        if what[0] == "corrupt":
            with sqlite3.connect(path) as conn:     # a torn row on its disk
                conn.execute("UPDATE beacons SET data = substr(data, 1, "
                             "length(data) - 1) WHERE round = ?", (what[1],))
        self._stores[what] = store
        return store

    def sync_chain(self, peer, from_round: int):
        what = self.script[min(len(self.opened), len(self.script) - 1)]
        self.opened.append((peer.address, from_round, what[0]))
        if what[0] == "down":
            return self._raising(ConnectionError(f"{peer.address} is down"))
        served = SM.serve_sync_chain(self._store(what), from_round,
                                     chunk_size=MESSAGE)
        if what[0] == "drop":
            return self._dropping(served, what[1])
        return served

    @staticmethod
    async def _raising(exc):
        raise exc
        yield  # pragma: no cover

    @staticmethod
    async def _dropping(served, messages: int):
        async for item in served:
            if not messages:
                await served.aclose()
                raise ConnectionError("the peer restarted")
            messages -= 1
            yield item

    async def _status(self, peer):
        if peer.address in self.dead:
            raise ConnectionError(f"{peer.address} is down")
        return {"chain_store": {"last_round": N}}

    def close(self):
        for store in self._stores.values():
            store.close()


def _sync(tmp_path, chain: _Chain, script, up_to=N, peers=6,
          with_status=False, dead=()):
    """One `sync()` of a fresh node store -> (returned, the store's
    height, its rows against the chain, the net, the manager)."""
    store = H.new_node_store(str(tmp_path / "node.db"), chain.group)
    net = _Net(tmp_path, chain, script, with_status, dead)
    clock = SystemClock()
    sm = SM.SyncManager(store, chain.group, chain.verifier, net,
                        [_Peer(i) for i in range(peers)], clock,
                        insecure_store=store.insecure,
                        resilience=Resilience(clock) if with_status
                        else None, beacon_id="t")
    try:
        ok = asyncio.run(sm.sync(SM.SyncRequest(1, up_to)))
        height = store.last().round
        _rounds, sigs, prevs = H.stored_rows(store, height,
                                             chain.sigs.shape[1])
        differing = H.rows_differing(
            sigs, prevs, chain.sigs[:height],
            chain.prevs and chain.prevs[:height])
    finally:
        store.close()
        net.close()
    return ok, height, differing, net, sm


def _spans(name: str) -> list:
    return sorted((sp for sp in tracing.RECORDER.spans() if sp.name == name),
                  key=lambda sp: sp.start_mono)


def _ends() -> list[str]:
    return [sp.attrs["end"] for sp in _spans("sync.catchup")]


# -- the cases, each over the three schemes -------------------------------------

def test_a_peer_that_ends_short_is_followed_by_the_next(tmp_path, chain):
    ok, height, differing, net, _sm = _sync(
        tmp_path, chain, [("short", 100), ("sound",)])
    assert ok and height == N and differing == 0
    # the second peer resumes past what the first one's rounds verified
    assert [(f, w) for _p, f, w in net.opened] == [(1, "short"),
                                                   (101, "sound")]
    assert net.opened[0][0] != net.opened[1][0]
    assert _ends() == ["ended_short", "done"]


def test_a_damaged_row_on_the_peers_disk_ends_its_stream_short(tmp_path,
                                                               chain):
    """`serve_sync_chain`'s own path: the good prefix, a clean end."""
    ok, height, differing, net, _sm = _sync(
        tmp_path, chain, [("corrupt", 91), ("sound",)])
    assert ok and height == N and differing == 0
    assert [(f, w) for _p, f, w in net.opened] == [(1, "corrupt"),
                                                   (91, "sound")]
    assert _ends() == ["ended_short", "done"]


def test_every_peer_short_is_false_at_the_highest_sound_height(tmp_path,
                                                               chain):
    ok, height, differing, net, _sm = _sync(
        tmp_path, chain, [("short", 64), ("short", 120), ("short", 96),
                          ("short", 120)], peers=5)
    assert not ok and height == 120 and differing == 0
    assert len(net.opened) == 5                     # every peer was tried
    assert len({p for p, _f, _w in net.opened}) == 5
    assert [f for _p, f, _w in net.opened] == [1, 65, 121, 121, 121]
    assert _ends() == ["ended_short"] * 5
    root, = _spans("sync.request")
    assert root.attrs["tries"] == 5 and root.attrs["reached"] is False
    assert root.attrs["rounds"] == 120
    # the last failover found nobody to deliver a message
    assert _spans("sync.failover")[-1].status == "spent"


def test_a_drop_in_mid_stream_is_followed_by_the_next(tmp_path, chain):
    """Nine messages, then the peer is gone: two segments were flushed
    and are committed, the eight rounds buffered are fetched again."""
    ok, height, differing, net, sm = _sync(
        tmp_path, chain, [("drop", 9), ("sound",)])
    assert ok and height == N and differing == 0
    assert [(f, w) for _p, f, w in net.opened] == [(1, "drop"),
                                                   (65, "sound")]
    assert _ends() == ["dropped", "done"]
    root, = _spans("sync.request")
    assert root.attrs["rounds_fetched"] == 72 + 128
    assert root.attrs["rounds_refetched"] == 8
    assert root.attrs["rows_dispatched"] == N
    assert root.attrs["rows_discarded"] == 0
    assert sm.stats["rounds_refetched"] == 8


def test_a_liar_commits_nothing_from_its_failed_segment_on(tmp_path, chain):
    liar = 75                                       # in segment 65..96
    ok, height, differing, net, _sm = _sync(
        tmp_path, chain, [("liar", liar), ("sound",)])
    assert ok and height == N and differing == 0    # the flip is in no row
    assert [(f, w) for _p, f, w in net.opened] == [(1, "liar"),
                                                   (65, "sound")]
    assert _ends() == ["verify_failed", "done"]
    first, second = _spans("sync.catchup")
    assert first.attrs["rounds"] == 64 and second.attrs["rounds"] == 128
    root, = _spans("sync.request")
    # the failed segment, and whatever was dispatched behind it
    assert root.attrs["rows_discarded"] in (32, 64, 96, 128)
    assert root.attrs["rows_dispatched"] \
        == N + root.attrs["rows_discarded"]
    assert root.attrs["rounds_refetched"] \
        == root.attrs["rounds_fetched"] - N


def test_every_peer_lying_is_false_below_the_first_lie(tmp_path, chain):
    ok, height, differing, net, _sm = _sync(
        tmp_path, chain, [("liar", 40)], peers=3)
    assert not ok and height == 32 and differing == 0
    assert [f for _p, f, _w in net.opened] == [1, 33, 33]
    assert _ends() == ["verify_failed"] * 3


def test_follow_mode_ends_with_the_first_peer_that_committed(tmp_path,
                                                             chain):
    """`up_to == 0` as before: no height to reach, so the first stream
    that committed anything ends the request, true."""
    ok, height, differing, net, _sm = _sync(
        tmp_path, chain, [("down",), ("short", 100), ("sound",)], up_to=0)
    assert ok and height == 100 and differing == 0
    assert [w for _p, _f, w in net.opened] == ["down", "short"]
    assert _ends() == ["unreachable", "done"]
    root, = _spans("sync.request")
    assert root.attrs["tries"] == 2 and root.attrs["reached"] is True


def test_follow_mode_is_false_where_no_peer_committed(tmp_path, chain):
    ok, height, _d, net, _sm = _sync(tmp_path, chain, [("down",)],
                                     up_to=0, peers=3)
    assert not ok and height == 0 and len(net.opened) == 3


def test_a_request_already_at_its_target_tries_nobody(tmp_path, chain):
    ok, height, _d, net, _sm = _sync(tmp_path, chain, [("sound",)], up_to=0,
                                     peers=1)
    assert ok and height == N
    store = new_chain_store(str(tmp_path / "node.db"), chain.group)
    sm = SM.SyncManager(store, chain.group, chain.verifier, net,
                        [_Peer(0)], SystemClock())
    try:
        assert asyncio.run(sm.sync(SM.SyncRequest(1, N)))
    finally:
        store.close()
    assert len(net.opened) == 1
    assert _spans("sync.request")[-1].attrs["tries"] == 0


def test_the_requests_spans_counters_and_reasons(tmp_path, chain):
    """Every way a try ends, in one request: a peer that is down, one
    that restarts after nine messages, one with a damaged row at 121, a
    liar at 150, a sound one."""
    script = [("down",), ("drop", 9), ("corrupt", 121), ("liar", 150),
              ("sound",)]
    ok, height, differing, net, sm = _sync(tmp_path, chain, script)
    assert ok and height == N and differing == 0
    assert [(f, w) for _p, f, w in net.opened] == [
        (1, "down"), (1, "drop"), (65, "corrupt"), (121, "liar"),
        (121, "sound")]
    root, = _spans("sync.request")
    tries = _spans("sync.catchup")
    assert [t.attrs["end"] for t in tries] == [
        "unreachable", "dropped", "ended_short", "verify_failed", "done"]
    assert [t.attrs["try"] for t in tries] == [1, 2, 3, 4, 5]
    assert all(t.parent_id == root.span_id for t in tries)
    assert [t.attrs["rounds"] for t in tries] == [0, 64, 56, 0, 72]
    assert [t.attrs["peer"] for t in tries] == [p for p, _f, _w
                                                in net.opened]
    assert root.attrs["tries"] == 5 and root.attrs["reached"] is True
    assert (root.attrs["from_round"], root.attrs["up_to"],
            root.attrs["peers"]) == (1, N, 6)
    assert root.attrs["rounds"] == N
    assert root.attrs["rounds_refetched"] \
        == root.attrs["rounds_fetched"] - N
    assert 72 + 56 + SEGMENT + 72 <= root.attrs["rounds_fetched"] \
        <= 72 + 56 + 72 + 72
    assert root.attrs["rows_discarded"] in (32, 64, 72)
    # a failover from the end of each try that left the request short to
    # the next peer's first message; the one after the peer that is down
    # stays open over the next try
    overs = _spans("sync.failover")
    assert [(o.attrs["reason"], o.attrs["from_peer"], o.attrs["to_peer"])
            for o in overs] == [
        ("unreachable", net.opened[0][0], net.opened[1][0]),
        ("dropped", net.opened[1][0], net.opened[2][0]),
        ("ended_short", net.opened[2][0], net.opened[3][0]),
        ("verify_failed", net.opened[3][0], net.opened[4][0])]
    assert all(o.parent_id == root.span_id and o.status == "ok"
               and o.attrs["wall_s"] == pytest.approx(o.duration_s)
               for o in overs)
    for over, nxt in zip(overs, tries[1:]):
        assert over.start_mono <= nxt.start_mono \
            <= over.start_mono + over.duration_s \
            <= nxt.start_mono + nxt.duration_s
    snap = sm.snapshot()
    assert snap["try"] == 5 and snap["last_try_end"] == "done"
    assert snap["stats"]["rows_discarded"] == root.attrs["rows_discarded"]


def test_the_hedged_probe_picks_the_next_peer_inside_the_failover(tmp_path,
                                                                  chain):
    """With the daemon's Resilience hub every choice of a peer is a
    `sync.probe`: the first under the request, the later ones under the
    failover they end; a peer whose probe fails is passed over while one
    that answers is left."""
    ok, height, differing, net, _sm = _sync(
        tmp_path, chain, [("short", 100), ("drop", 2), ("sound",)],
        with_status=True, dead=(0, 3))
    assert ok and height == N and differing == 0
    assert not {"peer-0", "peer-3"} & {p for p, _f, _w in net.opened}
    assert _ends() == ["ended_short", "dropped", "done"]
    root, = _spans("sync.request")
    probes, overs = _spans("sync.probe"), _spans("sync.failover")
    assert len(probes) == 3 and len(overs) == 2
    assert probes[0].parent_id == root.span_id
    assert [p.parent_id for p in probes[1:]] == [o.span_id for o in overs]
    for probe, (peer, _f, _w) in zip(probes, net.opened):
        assert probe.attrs["winner"] == peer
        assert 1 <= probe.attrs["candidates"] <= 3
        assert 0 <= probe.attrs["wall_s"] <= probe.duration_s

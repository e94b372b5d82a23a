"""`SyncManager.check_chain` against the benchmark's plain model of the
check and the repair (`benchmark/reference/check_repair.py`, which shares
no code with the program): 1,024 rounds of the benchmark's fixtures in a
store as the daemon builds it, seeded damage planted beneath its
decorators, peers that serve from stores of their own through the real
`serve_sync_chain`, and the program's host tier for the verdicts.  What
the check files, what it mends and leaves, and every row of the store
afterwards have to be the model's, exactly.
"""

import asyncio
import json
import os

import numpy as np
import pytest

import drand_tpu.beacon.sync_manager as SM
from benchmark import harness as H
from benchmark.drivers.check_repair import draw_damage, rows_of
from benchmark.reference import check_repair as M
from drand_tpu.chain.beacon import Beacon
from drand_tpu.chain.scheme import scheme_by_id
from drand_tpu.chain.store import SqliteStore
from drand_tpu.chain.verify import ChainVerifier

N = 1024
LISTS = ("corrupt", "missing", "unlinked", "bad_sigs")


class _HostTier(H.HostVerifier):
    """The program's host tier, a row at a time, remembering what it has
    judged: the cases share 1,024 sound rows a scheme."""

    def __init__(self, cv, memo):
        super().__init__(cv)
        self._memo = memo
        self.seen_prevs: list[bytes] = []

    def verify_beacon(self, b) -> bool:
        key = (b.round, b.signature, b.previous_sig)
        self.seen_prevs.append(b.previous_sig)
        if key not in self._memo:
            self._memo[key] = super().verify_beacon(b)
        return self._memo[key]


class _Chain:
    """A configuration of the benchmark cut to 1,024 rounds."""

    def __init__(self, name: str):
        with open(os.path.join(H.BENCH_DIR, "configs", name + ".json")) as f:
            self.config = json.load(f)
        sigs = np.load(os.path.join(H.BENCH_DIR, "fixtures",
                                    self.config["fixture"]["file"]))
        self.sigs = np.ascontiguousarray(sigs[:N])
        self.prevs = H.previous_sigs(self.config, self.sigs)
        self.group = H.group_of(self.config)
        self.chained = self.config["chained"]
        self.cv = ChainVerifier(scheme_by_id(self.config["scheme_id"]),
                                bytes.fromhex(self.config["public_key_hex"]))
        self.memo: dict = {}
        self.truth = rows_of(self.sigs, self.prevs, self.group.genesis_seed)
        self.judge = M.Judge(bytes.fromhex(self.config["public_key_hex"]),
                             self.config["signature_group"] == "G1",
                             self.chained, self.truth)

    def beacons(self, rows: dict) -> list[Beacon]:
        return [Beacon(round=r, signature=sig, previous_sig=prev)
                for r, (sig, prev) in sorted(rows.items()) if r]


_chains: dict[str, _Chain] = {}


def chain_of(name: str) -> _Chain:
    if name not in _chains:
        _chains[name] = _Chain(name)
    return _chains[name]


class _Net:
    """Peers that serve from their own store through the program's
    `serve_sync_chain`, 64 rounds a message; a peer without a store
    raises."""

    def sync_chain(self, peer, from_round: int):
        if peer.store is None:
            raise ConnectionError(f"{peer.address} is down")
        return SM.serve_sync_chain(peer.store, from_round, chunk_size=64)


class _Peer:
    def __init__(self, address: str, store):
        self.address, self.store = address, store


def flip(data: bytes, at: int = 5, bit: int = 3) -> bytes:
    out = bytearray(data)
    out[at % len(out)] ^= 1 << bit
    return bytes(out)


def extent(chain: _Chain, first: int, last: int, seed: int = 3) -> dict:
    rng = np.random.default_rng(seed)
    return {r: (rng.bytes(chain.sigs.shape[1]),
                rng.bytes(len(chain.prevs[r - 1])) if chain.chained else b"")
            for r in range(first, last + 1)}


def sig_flip(chain: _Chain, r: int) -> dict:
    sig, prev = chain.truth[r]
    return {r: (flip(sig), prev)}


def prev_flip(chain: _Chain, r: int) -> dict:
    sig, prev = chain.truth[r]
    return {r: (sig, flip(prev))}


def _serving(tmp_path, chain: _Chain, label: str, lies: dict | None = None):
    store = SqliteStore(str(tmp_path / f"{label}.db"))
    H.fill_store(store, chain.beacons({**chain.truth, **(lies or {})}))
    return store


def _check(tmp_path, chain: _Chain, damage: dict, peers: list[dict | None],
           up_to=None, delete=(), garble=()):
    """Run `check_chain` over the damaged store against `peers` (each the
    rows it lies about, {} for a sound one, None for one that is down)
    -> (result, rows after, what the model says of the same, verifier)."""
    store = H.new_node_store(str(tmp_path / "node.db"), chain.group)
    H.fill_store(store, chain.beacons(chain.truth))
    store.insecure.put_many(chain.beacons(damage))
    rows = {**chain.truth, **damage}
    for r in delete:
        store.insecure.delete(r)
        del rows[r]
    for r in garble:
        with store.insecure._conn() as conn:
            conn.execute("UPDATE beacons SET data = ? WHERE round = ?",
                         (b"\x07garbled", r))
        rows[r] = None
    serving = [None if lies is None else
               _serving(tmp_path, chain, f"peer{i}", lies)
               for i, lies in enumerate(peers)]
    verifier = _HostTier(chain.cv, chain.memo)
    mgr = SM.SyncManager(
        store, chain.group, verifier, _Net(),
        [_Peer(f"peer{i}", s) for i, s in enumerate(serving)], H.Clock(),
        insecure_store=store.insecure)
    # peers in the order given: the shuffle is not under test
    orig, SM.random.shuffle = SM.random.shuffle, lambda x: None
    try:
        result = asyncio.run(mgr.check_chain(up_to))
    finally:
        SM.random.shuffle = orig
    after = {r: (s, p) for r, s, p in store.insecure.read_fields(0, N + 2)} \
        if not garble else None
    store.close()
    for s in serving:
        if s is not None:
            s.close()
    # the model, peer by peer as the program asks them
    found = M.check(rows, chain.judge, chain.chained, up_to)
    want_rows, want_fixed, left = rows, [], M.to_mend(found)
    for lies in peers:
        if lies is None or not left:
            continue
        served = {r: lies.get(r, chain.truth[r])[0] for r in left}
        want_rows, fixed, left = M.repair(
            want_rows, left, served, chain.judge, chain.chained,
            chain.group.genesis_seed)
        want_fixed += fixed
    model = {"found": found, "rows": want_rows, "fixed": sorted(want_fixed),
             "unfixed": left}
    return result, after, model, verifier


def _agree(result, after, model):
    out = result.to_dict()
    for k in LISTS:
        assert [tuple(x) if isinstance(x, list) else x for x in out[k]] \
            == model["found"][k], k
    assert out["scanned"] == model["found"]["scanned"]
    assert out["fixed"] == model["fixed"]
    assert out["unfixed"] == model["unfixed"]
    if after is not None:
        assert after == model["rows"]


CHAINED = "default-chained"

CASES = {
    # the three kinds of damage, alone and together
    "a torn extent": lambda c: (extent(c, 300, 331), {}),
    "a flipped signature": lambda c: (sig_flip(c, 500), {}),
    "a flipped previous_sig": lambda c: (prev_flip(c, 700), {}),
    "the three together": lambda c: (
        {**extent(c, 300, 331), **sig_flip(c, 500), **prev_flip(c, 700)},
        {}),
    "seeded as the benchmark plants it": lambda c: (
        draw_damage(2**31 + 41, c.sigs, c.prevs,
                    {"extent_rounds": 16, "sig_flips": 3, "prev_flips": 3}),
        {}),
    # the edges
    "an extent that holds round 1": lambda c: (extent(c, 1, 9), {}),
    "an extent that holds the tip": lambda c: (extent(c, N - 7, N), {}),
    "two runs one round apart": lambda c: (
        {**sig_flip(c, 400), **sig_flip(c, 403)}, {}),
    "up_to below a damaged round": lambda c: (
        {**sig_flip(c, 200), **extent(c, 600, 607)}, {"up_to": 512}),
    "up_to on a damaged round": lambda c: (
        {**sig_flip(c, 512)}, {"up_to": 512}),
    # rows that are not there, rows that do not decode
    "missing rounds": lambda c: ({}, {"delete": (250, 251, 252, 900)}),
    "a row that does not decode": lambda c: (
        sig_flip(c, 640), {"garble": (100, 641)}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_check_chain_is_the_models_on_a_chained_store(tmp_path, case):
    chain = chain_of(CHAINED)
    damage, kw = CASES[case](chain)
    result, after, model = _check(tmp_path, chain, damage, [{}], **kw)[:3]
    _agree(result, after, model)
    if "up_to" not in kw:
        assert not result.unfixed and result.flagged
        if after is not None:
            assert after == chain.truth


@pytest.mark.parametrize("name", ["unchained-g2", "quicknet-g1", CHAINED])
def test_check_chain_is_the_models_under_every_scheme(tmp_path, name):
    chain = chain_of(name)
    damage = draw_damage(2**31 + 7, chain.sigs, chain.prevs,
                         {"extent_rounds": 8, "sig_flips": 2,
                          "prev_flips": 2})
    result, after, model = _check(tmp_path, chain, damage, [{}])[:3]
    _agree(result, after, model)
    assert after == chain.truth and not result.unfixed
    if not chain.chained:       # an unchained store has no `unlinked`
        assert not result.report.unlinked
        assert sorted(result.report.bad_sigs) == sorted(damage)


def test_a_lying_peer_then_a_sound_one(tmp_path):
    """The first peer serves three rounds of the repair set with a bit
    flipped: they, and under this chained scheme the replacements after
    them in their runs, stay as they were; the second peer mends them."""
    chain = chain_of(CHAINED)
    damage = {**extent(chain, 300, 331), **sig_flip(chain, 500),
              **prev_flip(chain, 700)}
    lies = {r: (flip(chain.truth[r][0], 17, 1), chain.truth[r][1])
            for r in (310, 501, 700)}
    result, after, model = _check(tmp_path, chain, damage, [lies])[:3]
    _agree(result, after, model)
    assert result.unfixed == [310, 311, 501, 700]
    assert all(after[r] == damage.get(r, chain.truth[r])
               for r in result.unfixed)     # left as they were
    second = tmp_path / "again"
    second.mkdir()
    result, after, model = _check(second, chain, damage, [lies, {}])[:3]
    _agree(result, after, model)
    assert not result.unfixed and after == chain.truth


def test_no_peer_at_all(tmp_path):
    chain = chain_of(CHAINED)
    damage = sig_flip(chain, 500)
    for i, peers in enumerate(([], [None])):    # none known; one down
        folder = tmp_path / str(i)
        folder.mkdir()
        result, after, model = _check(folder, chain, damage, peers)[:3]
        _agree(result, after, model)
        assert result.unfixed == [500, 501] and not result.fixed
        assert after == {**chain.truth, **damage}


def test_a_served_previous_sig_is_never_an_input(tmp_path):
    """The peer's rows carry a WRONG `previous_sig` beside sound
    signatures, alone (so it is served as stored) and at a message's
    first row: the rounds are mended all the same, over the consumer's
    own link, and no served `previous_sig` reaches the verifier."""
    chain = chain_of(CHAINED)
    damage = {**sig_flip(chain, 500), **extent(chain, 640, 643)}
    wrong = {r: (chain.truth[r][0], flip(chain.truth[r][1], 9, 6))
             for r in (500, 501, 641)}
    result, after, model, verifier = _check(tmp_path, chain, damage, [wrong])
    assert not result.unfixed and after == chain.truth
    assert not {p for _s, p in wrong.values()} & set(verifier.seen_prevs)
    _agree(result, after, model)


def test_the_benchmarks_damage_files_what_its_configuration_says():
    """At full size, structure alone (no pairing: a judge that calls every
    row false that is not the chain's): 320 damaged rounds, 353 filed, in
    65 runs."""
    with open(os.path.join(H.BENCH_DIR, "configs",
                           "default-chained-damaged.json")) as f:
        config = json.load(f)
    traffic = H.load_json("traffic", "check-repair.json")
    spec = traffic["damage"]
    assert {k: config["damage"][k] for k in
            ("extent_rounds", "sig_flips", "prev_flips")} \
        == {k: spec[k] for k in ("extent_rounds", "sig_flips", "prev_flips")}
    sigs = np.load(os.path.join(H.BENCH_DIR, "fixtures",
                                config["fixture"]["file"]))
    prevs = H.previous_sigs(config, sigs)
    truth = rows_of(sigs, prevs, bytes.fromhex(config["genesis_seed_hex"]))
    for seed in (1, 2**31 + 99, 2**32 + 5):
        damage = draw_damage(seed, sigs, prevs, spec)
        assert len(damage) == 320 and min(damage) >= 2 \
            and max(damage) < len(sigs)
        assert damage == draw_damage(seed, sigs, prevs, spec)
        found = M.check({**truth, **damage},
                        lambda r, s, p: truth[r] == (s, p), True)
        mend = M.to_mend(found)
        assert (len(found["unlinked"]), len(found["bad_sigs"])) == (321, 32)
        assert len(mend) == 353 and len(M.runs(mend)) == 65


# -- `util check`: the control RPC and the command line ----------------------

class _Process:
    beacon_id = "default"

    def __init__(self, sync_manager):
        self.sync_manager = sync_manager


class _Daemon:
    chain_hashes: dict = {}

    def __init__(self, process):
        self.processes = {"default": process}


def _util_check(tmp_path, peers, capsys, up_to=0):
    """`drand-tpu util check` against a control server whose one beacon
    process holds a damaged store -> (exit message or None, what it
    printed, the store's rows afterwards)."""
    import argparse

    import grpc.aio

    from drand_tpu.cli.main import cmd_util
    from drand_tpu.core.control import ControlService
    from drand_tpu.net.rpc import service_handler

    chain = chain_of(CHAINED)
    damage = {**sig_flip(chain, 500), **extent(chain, 640, 643)}
    store = H.new_node_store(str(tmp_path / "node.db"), chain.group)
    H.fill_store(store, chain.beacons(chain.truth))
    store.insecure.put_many(chain.beacons(damage))
    serving = [_serving(tmp_path, chain, f"peer{i}", lies)
               for i, lies in enumerate(peers)]
    mgr = SM.SyncManager(
        store, chain.group, _HostTier(chain.cv, chain.memo), _Net(),
        [_Peer(f"peer{i}", s) for i, s in enumerate(serving)], H.Clock(),
        insecure_store=store.insecure)

    async def go():
        server = grpc.aio.server()
        server.add_generic_rpc_handlers((service_handler(
            "Control", ControlService(_Daemon(_Process(mgr)))),))
        port = server.add_insecure_port("127.0.0.1:0")
        await server.start()
        try:
            await cmd_util(argparse.Namespace(
                what="check", control=port, beacon_id="default",
                up_to=up_to, target=""))
        except SystemExit as exc:
            return str(exc)
        finally:
            await server.stop(None)

    left = asyncio.run(go())
    after = {r: (s, p) for r, s, p in store.insecure.read_fields(0, N + 2)}
    store.close()
    for s in serving:
        s.close()
    return left, capsys.readouterr().out, after, {**chain.truth, **damage}


def test_util_check_streams_progress_and_prints_the_counts(tmp_path, capsys):
    left, out, after, _ = _util_check(tmp_path, [{}], capsys)
    assert left is None
    assert f"check {N}/{N}" in out      # the scan's progress, streamed
    assert "scanned 1025 / flagged 7 / fixed 7 / unfixed 0" in out
    assert after == chain_of(CHAINED).truth


def test_util_check_exits_non_zero_where_rounds_are_left(tmp_path, capsys):
    chain = chain_of(CHAINED)
    lies = {642: (flip(chain.truth[642][0]), chain.truth[642][1])}
    left, out, after, damaged = _util_check(tmp_path, [lies], capsys)
    assert "2 rounds left unmended" in left and "642" in left
    assert "scanned 1025 / flagged 7 / fixed 5 / unfixed 2" in out
    assert all(after[r] == damaged[r] for r in (642, 643))

"""Closed loop, one daemon's worth of chains: for each chain of the
configuration a fresh node catches up on its whole backlog from its own
serving node over localhost gRPC, all of them at once in one event loop
on one chip, again and again.

The first chain is the configuration's top-level keys: `run.py` loads its
fixture, builds its program and judges its verdicts.  The second chain is
a block of the same keys (`second_chain`), and is this driver's: its
fixture, its `ChainVerifier` (built in `setup()` by the call `run.py`
makes for the first, its record a `program` line), its serving node, its
stores and its verdicts against `benchmark/reference`.  Each chain has
what a `BeaconProcess` gives it in a daemon: a `ChainVerifier`, a store
and a `SyncManager` of its own, told apart in the program's spans by the
chain's `beacon_id`.  The stand, one chain at a time, is
`drivers/catchup.py`'s: its serving node, its two end-to-end metrics and
its rule for one more operation are that driver's own methods.
"""

from __future__ import annotations

import asyncio
import dataclasses
import hashlib
import io
import os
import shutil
import tempfile
import time
import traceback

import numpy as np

from benchmark import harness as H
from benchmark.drivers import catchup


@dataclasses.dataclass
class Chain:
    """One chain of the deployment."""

    name: str               # its beacon id
    config: dict            # the keys `run.py` reads of a configuration
    sigs: np.ndarray
    prevs: "list[bytes] | None"
    group: H.Group
    verifier: object        # what its traffic is verified by, in spans
    host: object            # its ChainVerifier: the program's host tier
                            # (`run.py` keeps the first chain's)
    addr: str = ""          # its serving node

    @property
    def backlog(self) -> int:
        return len(self.sigs)


def _fixture(config: dict, rounds: int) -> np.ndarray:
    fx = config["fixture"]
    with open(os.path.join(H.BENCH_DIR, "fixtures", fx["file"]), "rb") as f:
        raw = f.read()
    digest = hashlib.sha256(raw).hexdigest()
    if digest != fx["sha256"]:
        raise H.BenchFailure(f"fixture {fx['file']} has sha256 {digest}")
    sigs = np.load(io.BytesIO(raw))
    if sigs.shape[0] < rounds or sigs.shape[1] != config["signature_bytes"]:
        raise H.BenchFailure(f"fixture {fx['file']} holds {sigs.shape}")
    return np.ascontiguousarray(sigs[:rounds])


class Driver(catchup.Driver):
    def __init__(self, ctx: H.Ctx):
        super().__init__(ctx)
        self.chains: list[Chain] = []
        # of each timed operation: {chain: its consumer's database}
        self._consumer_dbs: list[dict[str, str]] = []

    # -- set-up ---------------------------------------------------------------

    def _verifier_like_the_first(self, config: dict, name: str):
        """(ChainVerifier, what the traffic is verified by) of the second
        chain, of the kind `run.py` made for the first."""
        from drand_tpu.chain.scheme import scheme_by_id
        from drand_tpu.chain.verify import ChainVerifier
        cv = ChainVerifier(scheme_by_id(config["scheme_id"]),
                           bytes.fromhex(config["public_key_hex"]),
                           beacon_id=name)
        first = self.ctx.verifier._inner
        if isinstance(first, H.StubVerifier):
            return cv, H.StubVerifier(config["scheme_id"])
        if isinstance(first, H.HostVerifier):
            return cv, H.HostVerifier(cv)
        if config["env"] != self.ctx.config["env"]:
            raise H.BenchFailure(
                f"chain {name} states another environment than the "
                "process has")
        rec = cv._verifier.build(config["bucket_rounds"])
        rec["tpu_custom_calls"] = rec.pop("lowered").as_text().count(
            "tpu_custom_call")
        H.emit(program=dict(rec, beacon_id=name))
        if rec["tpu_custom_calls"] <= 0:
            raise H.BenchFailure(
                f"program {rec['program']} holds no tpu_custom_call")
        return cv, cv

    async def setup(self) -> None:
        ctx = self.ctx
        self.chains = [Chain(ctx.config.get("beacon_id", "first"),
                             ctx.config, ctx.sigs, ctx.prevs, ctx.group,
                             ctx.verifier, None)]
        # (under a configuration of one chain, a crossing's, the first
        # is all there is)
        config = ctx.config.get("second_chain")
        if config is not None:
            name = config["beacon_id"]
            rounds = config["backlog_rounds"]
            if self.backlog < ctx.config["backlog_rounds"]:   # a rehearsal
                rounds = self.backlog
            sigs = _fixture(config, rounds)
            host, verifier = self._verifier_like_the_first(config, name)
            self.chains.append(Chain(
                name, config, sigs, H.previous_sigs(config, sigs),
                H.group_of(config), H.SpanVerifier(verifier, ctx.spans),
                host))
        for chain in self.chains:
            chain.addr = await self._serve(chain.sigs, chain.prevs,
                                           f"serve-{chain.name}")

    async def warmup(self) -> None:
        """Both programs, driven together as the window drives them."""
        rounds = min(self.ctx.traffic["warmup_rounds"], self.backlog)
        rec = await self._catch_up_all({}, rounds)
        for db in rec["dbs"].values():
            shutil.rmtree(os.path.dirname(db), ignore_errors=True)
        if not rec["ok"]:
            raise H.BenchFailure("the warm-up catch-up did not succeed: "
                                 f"{rec['chains']}")

    # -- one operation --------------------------------------------------------

    @staticmethod
    def _node_store(db: str, chain: Chain):
        """A chain's node store as its `BeaconProcess` builds it
        (`new_chain_store` under the chain's beacon id, so its
        `store.commit` spans say whose they are), holding the genesis
        row: `harness.new_node_store` with the id."""
        from drand_tpu.chain.beacon import Beacon
        from drand_tpu.chain.store import new_chain_store
        store = new_chain_store(db, chain.group, beacon_id=chain.name)
        store.put(Beacon(round=0, signature=chain.group.genesis_seed))
        return store

    async def _chain_catch_up(self, chain: Chain, addr: str, rounds: int,
                              began: float) -> dict:
        """One chain's fresh-store catch-up of `rounds` rounds through the
        real client stack, as `drivers/catchup.py:_catch_up` makes it;
        nothing is asserted here.  A catch-up that raises (a commit the
        store refuses) has failed, as it has for `SyncManager.sync`, and
        is no reason to leave the other chain's running on its own or
        its own store unread: `error` says what it raised."""
        from drand_tpu.beacon.sync_manager import SyncManager, SyncRequest
        from drand_tpu.net.client import GrpcBeaconNetwork, PeerClients

        ctx = self.ctx
        folder = tempfile.mkdtemp(prefix=f"consumer-{chain.name}-",
                                  dir=ctx.workdir)
        db = os.path.join(folder, "db.sqlite")
        store = H.SpanStore(self._node_store(db, chain), ctx.spans)
        peers = PeerClients()
        net = H.SpanNetwork(GrpcBeaconNetwork(peers, beacon_id=chain.name),
                            ctx.spans)
        peer = H.Peer(addr)
        try:
            sm = SyncManager(store, chain.group, chain.verifier, net, [peer],
                             H.Clock(), insecure_store=store.insecure,
                             beacon_id=chain.name)
        except TypeError as exc:    # a program older than this cell
            store.close()
            raise H.BenchFailure(
                f"this program's SyncManager: {exc}: two chains' spans in "
                "one trace could not be told apart") from exc
        ok, error = False, None
        t0 = time.perf_counter()
        try:
            ok = await sm._try_node(peer, SyncRequest(1, rounds))
        except Exception as exc:  # noqa: BLE001 -- a failed catch-up
            traceback.print_exc()
            error = f"{type(exc).__name__}: {exc}"[:400]
        finally:
            t1 = time.perf_counter()
            last = store.last().round
            store.close()
            await peers.close()
        latencies = H.pair_chunk_commits(net.arrivals, store.commits)
        return {"ok": bool(ok) and last == rounds, "sync_ok": bool(ok),
                "error": error, "rounds": rounds, "last": last,
                "wall_s": t1 - t0, "finished_s": t1 - began, "db": db,
                "stats": dict(sm.stats), "chunks": len(net.arrivals),
                "chunk_commit_s": latencies}

    async def _catch_up_all(self, addrs: dict[str, str],
                            rounds: int | None = None) -> dict:
        """Every chain's catch-up, started together and run to the end of
        the last; a chain is served from `addrs` where it is named there,
        else from its sound node."""
        first_span = len(self.ctx.spans.rows)
        t0 = time.perf_counter()
        recs = await asyncio.gather(*(
            self._chain_catch_up(c, addrs.get(c.name, c.addr),
                                 c.backlog if rounds is None else rounds, t0)
            for c in self.chains))
        wall = time.perf_counter() - t0
        # log only: the device waits for the first dispatch (the head)
        # and the host works on after the last verdicts (the tail)
        rows = self.ctx.spans.rows[first_span:]
        head = min((t1 for n, _, t1 in rows if n == "dispatch"),
                   default=t0) - t0
        tail = t0 + wall - max((t1 for n, _, t1 in rows
                                if n == "verify_wait"), default=t0 + wall)
        stats: dict = {}
        for r in recs:
            for key, value in r["stats"].items():
                stats[key] = stats.get(key, 0) + value
        return {"ok": all(r["ok"] for r in recs),
                "rounds": sum(r["rounds"] for r in recs), "wall_s": wall,
                "head_s": head, "tail_s": tail,
                "stats": stats,
                "spans": self.ctx.spans.totals(first_span),
                "chunks": sum(r["chunks"] for r in recs),
                "chunk_commit_s": [s for r in recs
                                   for s in r["chunk_commit_s"]],
                "dbs": {c.name: r["db"] for c, r in zip(self.chains, recs)},
                "chains": {c.name: {k: r[k] for k in (
                    "ok", "sync_ok", "error", "rounds", "last", "wall_s",
                    "finished_s", "stats")}
                    for c, r in zip(self.chains, recs)}}

    async def operate(self) -> dict:
        rec = await self._catch_up_all({})
        self._consumer_dbs.append(rec["dbs"])
        H.emit(operation={k: rec[k] for k in ("wall_s", "head_s", "tail_s",
                                              "chains")})
        return rec

    # `wants_more` and `end_to_end` are `drivers/catchup.py`'s: all rounds
    # of every chain over the window's seconds, the p95 over the wire
    # messages of every chain

    # -- the output check -----------------------------------------------------

    def _chain_rows(self, chain: Chain, db: str):
        from drand_tpu.chain.store import SqliteStore
        store = SqliteStore(db)
        try:
            return H.stored_rows(store, chain.backlog, chain.sigs.shape[1])
        finally:
            store.close()
            shutil.rmtree(os.path.dirname(db), ignore_errors=True)

    def _against_its_chain(self, chain: Chain, db: str) -> tuple[int, int]:
        """(1 where the store lacks a round of the chain, rows that differ
        from the chain's in either field) of a catch-up that succeeded."""
        rounds, sigs, prevs = self._chain_rows(chain, db)
        if len(rounds) != chain.backlog or not (
                rounds == np.arange(1, chain.backlog + 1)).all():
            return 1, 0
        return 0, H.rows_differing(sigs, prevs, chain.sigs, chain.prevs)

    async def check_window(self, records: list[dict]) -> dict:
        """Every timed operation's stores, each against its own chain: all
        rounds, in order, the served bytes (of both fields, where the
        scheme is chained); every wire message of either chain paired
        with a commit."""
        out = {}
        for chain in self.chains:
            found = [self._against_its_chain(chain, dbs[chain.name])
                     for dbs in self._consumer_dbs]
            out[f"window.{chain.name}.stores_missing_rounds"] = \
                sum(short for short, _ in found)
            out[f"window.{chain.name}.committed_rows_differing"] = \
                sum(differing for _, differing in found)
        self._consumer_dbs = []
        out["window.wire_messages_without_commit"] = sum(
            r["chunks"] - len(r["chunk_commit_s"])
            for r in records if r["ok"])
        return out

    def _verdicts(self, chain: Chain, draw: dict) -> dict:
        """The second chain's sampled and faulted rounds, judged three
        times as `run.py:_verdict_checks` judges the first's: by what its
        traffic is verified by, by its host tier, and by the plain
        reference under the chain's own configuration."""
        sample, faults = draw["sample"], list(draw["faults"])
        if chain.prevs is not None:
            faults += [(r, byte + chain.sigs.shape[1], bit)
                       for r, byte, bit in draw["faults"]]
        at = np.array(sample) - 1
        rounds, sigs = list(sample), [chain.sigs[at]]
        prevs = None if chain.prevs is None else [chain.prevs[i] for i in at]
        for r, byte, bit in faults:     # each into a copy of its own row
            bad, bad_prevs = H.plant(
                chain.sigs[r - 1:r], [(1, byte, bit)],
                chain.prevs and chain.prevs[r - 1:r])
            rounds.append(r)
            sigs.append(bad)
            if prevs is not None:
                prevs += bad_prevs
        batch = np.concatenate(sigs)
        want = np.array([True] * len(sample) + [False] * len(faults))
        beacons = H.beacons_of(batch, prevs, rounds)
        served = np.asarray(chain.verifier.verify_beacons(beacons))
        host = np.array([chain.host.verify_beacon(b) for b in beacons])
        ref = H.reference_verdicts(chain.config, rounds, batch, prevs)
        name = f"verdicts.{chain.name}"
        return {f"{name}.reference_differs_from_construction":
                int((ref != want).sum()),
                f"{name}.served_differs_from_reference":
                int((served != ref).sum()),
                f"{name}.host_tier_differs_from_reference":
                int((host != ref).sum())}

    async def check_faulted(self, draw: dict) -> dict:
        """The draw's faults planted in one chain at a time while every
        other is served sound, all catching up at once; once a chain.
        The faulted chain's catch-up must fail, and commit no round at or
        after its first damaged signature and no byte its chain does not
        hold (`drivers/catchup.py:check_faulted` says what a damaged
        `previous_sig` is); every other chain's must succeed beside it
        and equal its own chain.  Then the second chain's verdicts."""
        ctx, out, passes = self.ctx, {}, []
        for chain in self.chains:
            fields = H.damaged_fields(draw["faults"], chain.sigs.shape[1],
                                      chain.prevs is not None)
            bad_sigs = [r for r, f in sorted(fields.items())
                        if "signature" in f]
            first_bad = bad_sigs[0] if bad_sigs else chain.backlog + 1
            label = f"faulted-{chain.name}"
            addr = await self._serve(
                *H.plant(chain.sigs, draw["faults"], chain.prevs), label)
            try:
                rec = await self._catch_up_all({chain.name: addr})
            finally:
                await self._stop_serving()
                os.remove(os.path.join(ctx.workdir, f"{label}.db"))
            mine = rec["chains"][chain.name]
            rounds, sigs, prevs = self._chain_rows(chain,
                                                   rec["dbs"][chain.name])
            n = len(rounds)
            name = f"faulted.{chain.name}"
            out.update({
                f"{name}.sync_ok": int(mine["sync_ok"] and bool(bad_sigs)),
                f"{name}.committed_at_or_after_first_bad":
                    int((rounds >= first_bad).sum()),
                f"{name}.committed_out_of_order":
                    int((rounds != np.arange(1, n + 1)).sum()),
                f"{name}.committed_rows_differing":
                    H.rows_differing(sigs, prevs, chain.sigs[:n],
                                     chain.prevs and chain.prevs[:n])
                    if n <= chain.backlog else n})
            failed = short = differing = 0
            for other in self.chains:
                if other is chain:
                    continue
                failed += int(not rec["chains"][other.name]["ok"])
                found = self._against_its_chain(other,
                                                rec["dbs"][other.name])
                short += found[0]
                differing += found[1]
            out.update({f"{name}.others_failed": failed,
                        f"{name}.others_missing_rounds": short,
                        f"{name}.others_rows_differing": differing})
            passes.append({"faulted_chain": chain.name,
                           "first_bad_round": first_bad,
                           "damaged": {str(r): sorted(f)
                                       for r, f in fields.items()},
                           "committed_rounds": n, "wall_s": rec["wall_s"],
                           "chains": rec["chains"]})
        H.emit(faulted_passes=passes)
        for chain in self.chains[1:]:
            out.update(self._verdicts(chain, draw))
        return out

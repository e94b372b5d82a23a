"""Closed loop, one consumer: a fresh node catches up on the whole chain
from a serving node over localhost gRPC, again and again.

The stand is `chip_smoke.py`'s and `tools/bench_sync.py --mode real`'s
(copied into `benchmark/harness.py`): a serving `SqliteStore` holding
rounds 1..N behind the real `Protocol.SyncChain` handler, and a consumer
`GrpcBeaconNetwork.sync_chain` -> `SyncManager._try_node` -> verifier ->
`new_chain_store`.  The harness's wrappers around the consumer's network
and store note when each wire message came and when each commit ended.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time

import numpy as np

from benchmark import harness as H


class Driver:
    def __init__(self, ctx: H.Ctx):
        self.ctx = ctx
        self.backlog = len(ctx.sigs)
        self._servers: list = []
        self._stores: list = []
        self._consumer_dbs: list[str] = []
        self.addr = ""

    def segment_starts(self) -> list[int]:
        """SyncManager verifies one ramp segment and then whole ones."""
        ramp = self.ctx.traffic["ramp_rounds"]
        size = self.ctx.config["bucket_rounds"]
        return [1] + list(range(ramp + 1, self.backlog + 1, size))

    async def _serve(self, sigs: np.ndarray, prevs, label: str) -> str:
        from drand_tpu.chain.store import SqliteStore
        store = SqliteStore(os.path.join(self.ctx.workdir, f"{label}.db"))
        H.fill_store(store, H.beacons_of(sigs, prevs))
        server, addr = await H.serve(store)
        self._stores.append(store)
        self._servers.append(server)
        return addr

    async def _stop_serving(self) -> None:
        server, store = self._servers.pop(), self._stores.pop()
        await server.stop(None)
        store.close()

    async def setup(self) -> None:
        self.addr = await self._serve(self.ctx.sigs, self.ctx.prevs, "serve")

    async def warmup(self) -> None:
        rec = await self._catch_up(
            self.addr, min(self.ctx.traffic["warmup_rounds"], self.backlog))
        shutil.rmtree(os.path.dirname(rec["db"]), ignore_errors=True)
        if not rec["ok"]:
            raise H.BenchFailure("the warm-up catch-up did not succeed")

    async def _catch_up(self, addr: str, rounds: int) -> dict:
        """One fresh-store catch-up of `rounds` rounds through the real
        client stack; nothing is asserted here (the faulted pass has to
        fail)."""
        from drand_tpu.beacon.sync_manager import SyncManager, SyncRequest
        from drand_tpu.net.client import GrpcBeaconNetwork, PeerClients

        ctx = self.ctx
        folder = tempfile.mkdtemp(prefix="consumer-", dir=ctx.workdir)
        db = os.path.join(folder, "db.sqlite")
        store = H.SpanStore(H.new_node_store(db, ctx.group), ctx.spans)
        peers = PeerClients()
        net = H.SpanNetwork(GrpcBeaconNetwork(peers, beacon_id="bench"),
                            ctx.spans)
        peer = H.Peer(addr)
        sm = SyncManager(store, ctx.group, ctx.verifier, net, [peer],
                         H.Clock(), insecure_store=store.insecure)
        first_span = len(ctx.spans.rows)
        t0 = time.perf_counter()
        try:
            ok = await sm._try_node(peer, SyncRequest(1, rounds))
            wall = time.perf_counter() - t0
            last = store.last().round
        finally:
            store.close()
            await peers.close()
        latencies = H.pair_chunk_commits(net.arrivals, store.commits)
        return {"ok": bool(ok) and last == rounds, "sync_ok": bool(ok),
                "rounds": rounds, "last": last, "wall_s": wall, "db": db,
                "stats": dict(sm.stats),
                "spans": ctx.spans.totals(first_span),
                "chunks": len(net.arrivals),
                "chunk_commit_s": latencies}

    async def operate(self) -> dict:
        rec = await self._catch_up(self.addr, self.backlog)
        self._consumer_dbs.append(rec["db"])
        return rec

    def wants_more(self, records: list[dict]) -> bool:
        """On a host so slow that the seconds ran out with fewer wire
        messages than the percentile needs (one catch-up gives 128, the
        p95 wants 200), the window goes on for one more catch-up; never
        past a catch-up that failed."""
        if not all(r["ok"] for r in records):
            return False
        try:
            self.end_to_end(records, 1.0)
        except H.BenchFailure:
            return True
        return False

    def end_to_end(self, records: list[dict], elapsed: float) -> dict:
        good = [r for r in records if r["ok"]]
        latencies = [s for r in good for s in r["chunk_commit_s"]]
        return {"catchup_rate": sum(r["rounds"] for r in good) / elapsed,
                "chunk_commit_p95_ms": 1e3 * H.percentile(latencies, 95)}

    def _committed(self, db: str):
        from drand_tpu.chain.store import SqliteStore
        store = SqliteStore(db)
        try:
            return H.stored_rows(store, self.backlog,
                                 self.ctx.sigs.shape[1])
        finally:
            store.close()
            shutil.rmtree(os.path.dirname(db), ignore_errors=True)

    async def check_window(self, records: list[dict]) -> dict:
        """Every timed catch-up's store against the chain: all rounds, in
        order, the served bytes (of both fields, where the scheme is
        chained); every wire message paired with a commit."""
        short = differing = 0
        for db in self._consumer_dbs:
            rounds, sigs, prevs = self._committed(db)
            if len(rounds) != self.backlog or not (
                    rounds == np.arange(1, self.backlog + 1)).all():
                short += 1
            else:
                differing += H.rows_differing(sigs, prevs, self.ctx.sigs,
                                              self.ctx.prevs)
        self._consumer_dbs = []
        unpaired = sum(r["chunks"] - len(r["chunk_commit_s"])
                       for r in records if r["ok"])
        return {"window.stores_missing_rounds": short,
                "window.wire_messages_without_commit": unpaired,
                "window.committed_rows_differing": differing}

    async def check_faulted(self, draw: dict) -> dict:
        """A catch-up from a node whose chain has the faults planted must
        fail, and commit no round at or after the first damaged
        signature, and no byte that the chain does not hold.

        A damaged `previous_sig` (chained schemes) is the serving node's
        own: the packed wire carries signatures, and a consumer links
        each row to its own tail (`chain/segment.py`), so such a row
        either fails the catch-up (served alone, as stored) or is
        committed as the chain has it.  Where no signature is damaged at
        all, the catch-up may therefore succeed; what it commits is held
        to the chain all the same."""
        ctx = self.ctx
        fields = H.damaged_fields(draw["faults"], ctx.sigs.shape[1],
                                  ctx.prevs is not None)
        bad_sigs = [r for r, f in sorted(fields.items()) if "signature" in f]
        first_bad = bad_sigs[0] if bad_sigs else self.backlog + 1
        addr = await self._serve(
            *H.plant(ctx.sigs, draw["faults"], ctx.prevs), "faulted")
        try:
            rec = await self._catch_up(addr, self.backlog)
        finally:
            await self._stop_serving()
            os.remove(os.path.join(ctx.workdir, "faulted.db"))
        rounds, sigs, prevs = self._committed(rec["db"])
        n = len(rounds)
        H.emit(faulted_pass={"first_bad_round": first_bad,
                             "damaged": {str(r): sorted(f)
                                         for r, f in fields.items()},
                             "sync_ok": rec["sync_ok"],
                             "committed_rounds": n, "wall_s": rec["wall_s"]})
        return {
            "faulted.sync_ok": int(rec["sync_ok"] and bool(bad_sigs)),
            "faulted.committed_at_or_after_first_bad":
                int((rounds >= first_bad).sum()),
            "faulted.committed_out_of_order":
                int((rounds != np.arange(1, n + 1)).sum()),
            "faulted.committed_rows_differing":
                H.rows_differing(sigs, prevs, ctx.sigs[:n],
                                 ctx.prevs and ctx.prevs[:n])
                if n <= self.backlog else n}

    async def close(self) -> None:
        while self._servers:
            await self._stop_serving()

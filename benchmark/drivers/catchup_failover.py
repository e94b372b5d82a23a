"""Closed loop, one consumer: a member of a group rejoins from the
group's peers, some of which fail, again and again.

The stand is a group: every live peer a gRPC server of its own on an
ephemeral localhost port, answering `Protocol.SyncChain` through the
real handler (`serve_sync_chain`, as `harness.serve` does) and
`Protocol.Status` with the last round of the store it serves; every
unreachable peer a localhost port that is bound and does not listen.
The live peers share two serving stores, the chain's and its copy with
one corrupt row, each asked for a message's rows once, in set-up
(`_Remembered`).  What a live peer does is set by the ORDER in which an
operation opens `SyncChain` streams, not by who the peer is
(`traffic/catchup-failover.json`: `script`), and Python's `random` is
seeded with the traffic's `order_seed` before each operation: every
operation and every run meets the same failures at the same places,
whatever the program's ranking does.

An operation is ONE `SyncManager.sync(SyncRequest(1, backlog))` on a
fresh node store, the manager built as `core/process.py` builds it (the
group's peers, the daemon's `Resilience` hub, `insecure_store`), with
the harness's span wrappers around network, store and verifier as in
`drivers/catchup.py`, from its call to its return.  A run's window
holds at least the traffic's `min_operations` of them (`wants_more`).

`correct` compares with `benchmark/reference/failover.py`, a plain
model of what a fail-over has to leave behind that shares no code with
the program: the reasons the tries ended for and the store's height
after each, from the program's `sync.request` and `sync.catchup` spans;
the stores against the chain byte for byte; and a pass in which every
live peer of a smaller group lies (`check_faulted`).  A program without
those spans (the parent of the PR that brought them) gives nothing to
compare, which is counted as a difference, not raised.

The harness hands a driver no seed (`harness.Ctx`): the bit the liar
flips comes from the run's own `--seed`, read off the command line as
`drivers/check_repair.py` reads it.
"""

from __future__ import annotations

import os
import random
import shutil
import socket
import sqlite3
import tempfile
import time

import numpy as np

from benchmark import harness as H
from benchmark.drivers.check_repair import seed_of_run
from benchmark.reference import failover as M

SOUND = [{"kind": "sound"}]


class _Stand:
    """What the group's live peers share: the two serving stores, the
    script of the operation at hand, and the streams it has opened."""

    def __init__(self, sound, corrupt, backlog: int):
        self.stores = {"sound": sound, "corrupt": corrupt}
        self.backlog = backlog
        self.script: list[dict] = SOUND
        # round -> (byte, bit): what a `liar` or `flips` stream flips
        self.flips: dict[int, tuple[int, int]] = {}
        self.opened: list[tuple[str, int, str]] = []  # peer, from, kind
        self.served: dict[int, bytes] = {}            # the lies, as served

    def begin(self, script: list[dict], flips: dict | None = None) -> None:
        self.script, self.flips = script, dict(flips or {})
        self.opened, self.served = [], {}

    def _lie(self, item):
        """The item with the script's bits flipped, where it holds any
        of those rounds."""
        mine = [r for r in self.flips
                if item.start_round <= r <= item.end_round]
        if not mine:
            return item
        from drand_tpu.chain.segment import PackedBeacons
        sigs = item.sigs.copy()
        for r in mine:
            byte, bit = self.flips[r]
            row = sigs[r - item.start_round]
            row[byte % len(row)] ^= np.uint8(1 << bit)
            self.served[r] = row.tobytes()
        return PackedBeacons(start_round=item.start_round, sigs=sigs,
                             first_prev=item.first_prev,
                             chained=item.chained)

    async def stream(self, address: str, request, context):
        import grpc

        from drand_tpu.beacon.sync_manager import serve_sync_chain
        from drand_tpu.chain.segment import WIRE_CHUNK_DEFAULT
        from drand_tpu.core import convert
        step = self.script[min(len(self.opened), len(self.script) - 1)]
        self.opened.append((address, int(request.from_round), step["kind"]))
        store = self.stores["corrupt" if step["kind"] == "corrupt_row"
                            else "sound"]
        chunk = min(int(getattr(request, "chunk_size", 0)),
                    WIRE_CHUNK_DEFAULT)
        left = step.get("after_messages")
        serving = serve_sync_chain(store, request.from_round,
                                   chunk_size=chunk)
        try:
            async for item in serving:
                if left == 0:
                    await context.abort(
                        getattr(grpc.StatusCode, step["status"]),
                        "the peer is restarting")
                if left is not None:
                    left -= 1
                if step["kind"] in ("liar", "flips"):
                    item = self._lie(item)
                yield convert.item_to_packet(item)
        finally:
            await serving.aclose()


async def _serve_peer(stand: _Stand):
    """One live peer of the group."""
    import grpc.aio

    from drand_tpu.net.rpc import service_handler
    from drand_tpu.protogen import drand_pb2

    class _Peer:
        address = ""

        def SyncChain(self, request, context):
            return stand.stream(self.address, request, context)

        async def Status(self, request, context):
            resp = drand_pb2.StatusResponse()
            resp.beacon.is_running = resp.beacon.is_serving = True
            resp.chain_store.is_empty = False
            resp.chain_store.last_round = stand.backlog
            resp.chain_store.length = stand.backlog + 1
            return resp

    peer = _Peer()
    server = grpc.aio.server()
    server.add_generic_rpc_handlers((service_handler("Protocol", peer),))
    port = server.add_insecure_port("127.0.0.1:0")
    await server.start()
    peer.address = f"127.0.0.1:{port}"
    return server, peer.address


def _unreachable():
    """A localhost port on which nothing listens, kept for the run: the
    socket is bound and never listens, so a connection is refused."""
    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    sock.bind(("127.0.0.1", 0))
    return sock, f"127.0.0.1:{sock.getsockname()[1]}"


class _Remembered:
    """A peer's store whose `read_fields` answers are remembered: each
    (start, limit) is asked of the store once, the rows it decoded or
    the `CorruptRowError` it raised are what every later stream gets.
    The handler, its packing and the wire stay a stream's own.  Reading
    and decoding its rows is the peer's work, on another machine in a
    deployment; here it runs under the consumer's interpreter lock while
    the device waits for a stream's first segment, and was a quarter of
    what spread the rate from process to process (PERF.md, PR 43)."""

    def __init__(self, store):
        self._store = store
        self._answers: dict[tuple[int, int], tuple] = {}

    def read_fields(self, start_round: int, limit: int):
        from drand_tpu.chain.store import StoreError
        key = (start_round, limit)
        if key not in self._answers:
            try:
                self._answers[key] = (self._store.read_fields(*key), None)
            except StoreError as exc:
                self._answers[key] = (None, exc)
        rows, exc = self._answers[key]
        if exc is not None:
            raise exc
        return rows


class Driver:
    def __init__(self, ctx: H.Ctx):
        self.ctx = ctx
        self.backlog = len(ctx.sigs)
        traffic = ctx.traffic
        self.group_spec = ctx.config.get("peers") or traffic["peers_default"]
        self.rehearsal = self.backlog < ctx.config["backlog_rounds"]
        self.script = traffic["rehearse_script" if self.rehearsal
                              else "script"]
        self.chunk = int(ctx.config["env"]["DRAND_TPU_SYNC_WIRE_CHUNK"])
        if self.rehearsal:
            self.chunk = int(traffic["rehearse_env"]
                             ["DRAND_TPU_SYNC_WIRE_CHUNK"])
        self.seed = seed_of_run(int(traffic["order_seed"]))
        rng = np.random.default_rng([self.seed % (1 << 64), 43])
        self.liar_flip = (int(rng.integers(0, ctx.sigs.shape[1])),
                          int(rng.integers(0, 8)))
        self.liar_rounds = [s["round"] for s in self.script
                            if s["kind"] == "liar"]
        self.stand: _Stand | None = None
        self.live: list[str] = []
        self.dead: list[str] = []
        self._servers: list = []
        self._stores: list = []
        self._sockets: list = []
        self._unchecked: list[dict] = []    # operations whose store stands

    def segment_starts(self) -> list[int]:
        """Where the places of the script and the program's full
        segments begin: the fault kinds of the draw aim at them."""
        size = self.ctx.config["bucket_rounds"]
        places = {1, *range(size + 1, self.backlog + 1, size)}
        places |= {s["round"] for s in self.script
                   if s["kind"] == "corrupt_row" and s["round"] <= self.backlog}
        return sorted(places)

    # -- the stand ------------------------------------------------------------

    def _serving_store(self, label: str, corrupt_rounds=()):
        from drand_tpu.chain.store import SqliteStore, StoreError
        path = os.path.join(self.ctx.workdir, f"{label}.db")
        store = SqliteStore(path)
        H.fill_store(store, H.beacons_of(self.ctx.sigs, self.ctx.prevs))
        if corrupt_rounds:
            # a row torn on the peer's own disk: one byte short of what
            # its header declares (`read_fields`: CorruptRowError)
            with sqlite3.connect(path) as conn:
                conn.executemany(
                    "UPDATE beacons SET data = substr(data, 1, "
                    "length(data) - 1) WHERE round = ?",
                    [(r,) for r in corrupt_rounds])
        self._stores.append(store)
        remembered = _Remembered(store)
        # every message a timed stream will ask for, asked once here:
        # the streams of a rejoin begin where a segment of the consumer's
        # ended, a whole number of messages past round 1
        for start in range(1, self.backlog + 1, self.chunk):
            try:
                remembered.read_fields(start, self.chunk)
            except StoreError:  # the damaged row's, kept for its stream
                pass
        return remembered

    async def setup(self) -> None:
        spec = self.group_spec
        corrupt = [s["round"] for s in self.script
                   if s["kind"] == "corrupt_row"]
        self.stand = _Stand(self._serving_store("serve"),
                            self._serving_store("serve-damaged", corrupt),
                            self.backlog)
        for _ in range(spec["peers"] - spec["unreachable"]):
            server, address = await _serve_peer(self.stand)
            self._servers.append(server)
            self.live.append(address)
        for _ in range(spec["unreachable"]):
            sock, address = _unreachable()
            self._sockets.append(sock)
            self.dead.append(address)
        H.emit(group={"peers": spec["peers"], "live": len(self.live),
                      "unreachable": len(self.dead), "seed": self.seed,
                      "liar_flip": list(self.liar_flip),
                      "script": [s["kind"] for s in self.script]})

    async def warmup(self) -> None:
        """One rejoin of `warmup_rounds` from sound peers: the program's
        one shape, full and padded.  (A rehearsal has no program to warm
        and pays its host tier by the row: four messages.)"""
        rounds = min(self.ctx.traffic["warmup_rounds"], self.backlog)
        if self.rehearsal:
            rounds = 4 * self.chunk
        rec = await self._rejoin(SOUND, rounds, self.live + self.dead)
        shutil.rmtree(os.path.dirname(rec["db"]), ignore_errors=True)
        if not rec["ok"]:
            raise H.BenchFailure("the warm-up sync did not reach its target")

    # -- the operation --------------------------------------------------------

    def _tries(self, t0: float, t1: float):
        """(the request's attributes, its tries as the model reads them)
        from the program's own spans begun in [t0, t1]; (None, None)
        where the program has no `sync.request`."""
        try:
            from drand_tpu import tracing
        except ImportError:
            return None, None
        mine = [sp for sp in tracing.RECORDER.spans()
                if t0 <= getattr(sp, "start_mono", -1.0) <= t1]
        roots = [sp for sp in mine if sp.name == "sync.request"]
        if len(roots) != 1:
            return None, None
        tries, height = [], 0
        for sp in sorted((sp for sp in mine if sp.name == "sync.catchup"
                          and sp.parent_id == roots[0].span_id),
                         key=lambda sp: sp.start_mono):
            height += sp.attrs.get("rounds", 0)
            tries.append({"live": sp.attrs.get("peer") not in self.dead,
                          "end": sp.attrs.get("end"), "height": height})
        return dict(roots[0].attrs), tries

    async def _rejoin(self, script, rounds: int, group: list[str],
                      flips: dict | None = None) -> dict:
        """One `sync()` of a fresh node store against `group`; nothing
        is asserted here (the faulted pass has to come back false)."""
        from drand_tpu.beacon.clock import SystemClock
        from drand_tpu.beacon.sync_manager import SyncManager, SyncRequest
        from drand_tpu.net.client import GrpcBeaconNetwork, PeerClients
        from drand_tpu.resilience import Resilience

        ctx = self.ctx
        folder = tempfile.mkdtemp(prefix="consumer-", dir=ctx.workdir)
        db = os.path.join(folder, "db.sqlite")
        store = H.SpanStore(H.new_node_store(db, ctx.group), ctx.spans)
        clock = SystemClock()
        hub = Resilience(clock)
        peers = PeerClients()
        net = H.SpanNetwork(
            GrpcBeaconNetwork(peers, beacon_id="bench", resilience=hub),
            ctx.spans)
        sm = SyncManager(store, ctx.group, ctx.verifier, net,
                         [H.Peer(a) for a in group], clock,
                         insecure_store=store.insecure, resilience=hub,
                         beacon_id="bench")
        random.seed(int(ctx.traffic["order_seed"]))
        self.stand.begin(script, flips)
        first_span = len(ctx.spans.rows)
        t0 = time.perf_counter()
        try:
            ok = await sm.sync(SyncRequest(1, rounds))
            wall = time.perf_counter() - t0
            last = store.last().round
        finally:
            store.close()
            await peers.close()
        request, tries = self._tries(t0, t0 + wall)
        return {"ok": ok is True and last == rounds, "sync_ok": ok,
                "rounds": rounds, "last": last, "wall_s": wall, "db": db,
                "stats": dict(sm.stats),
                "spans": ctx.spans.totals(first_span),
                "opened": list(self.stand.opened),
                "lies": dict(self.stand.served),
                "request": request, "tries": tries, "group": len(group)}

    async def operate(self) -> dict:
        flips = {r: self.liar_flip for r in self.liar_rounds}
        rec = await self._rejoin(self.script, self.backlog,
                                 self.live + self.dead, flips)
        self._unchecked.append(rec)
        return rec

    def wants_more(self, records: list[dict]) -> bool:
        """The window goes on past its seconds until it holds the
        traffic's `min_operations` rejoins (a rehearsal's host tier pays
        by the row: it stops with the seconds); never past one that
        failed."""
        return (not self.rehearsal and all(r["ok"] for r in records)
                and len(records) < self.ctx.traffic["min_operations"])

    def end_to_end(self, records: list[dict], elapsed: float) -> dict:
        good = [r for r in records if r["ok"]]
        return {"catchup_rate": sum(r["rounds"] for r in good) / elapsed}

    # -- the output check -----------------------------------------------------

    def _committed(self, db: str):
        from drand_tpu.chain.store import SqliteStore
        store = SqliteStore(db)
        try:
            return H.stored_rows(store, self.backlog,
                                 self.ctx.sigs.shape[1])
        finally:
            store.close()
            shutil.rmtree(os.path.dirname(db), ignore_errors=True)

    def _against_model(self, rec: dict, script) -> int:
        """How much of a rejoin's tries the plain model does not allow,
        and by how many the request's count of tries differs from the
        streams the stand saw opened plus the unreachable peers tried."""
        if rec["tries"] is None:
            H.emit(model={"violations": ["the program recorded no one "
                                         "sync.request over the call"]})
            return 1
        found = M.violations(script, rec["tries"], rec["rounds"], self.chunk,
                             rec["sync_ok"] is True, rec["group"])
        counted = rec["request"].get("tries")
        seen = len(rec["opened"]) + sum(not t["live"] for t in rec["tries"])
        if counted != seen:
            found.append(f"sync.request counts {counted} tries, the stand "
                         f"saw {seen}")
        if found:
            H.emit(model={"violations": found, "tries": rec["tries"],
                          "opened": rec["opened"]})
        return len(found)

    def _liars_round_judged_true(self) -> int:
        """Of the device program, the program's host tier and the plain
        reference, how many call round 50,000 true as the liar serves
        it."""
        from drand_tpu.chain.scheme import scheme_by_id
        from drand_tpu.chain.verify import ChainVerifier
        ctx = self.ctx
        rounds = [r for r in self.liar_rounds if r <= self.backlog]
        if not rounds:
            return 0
        sigs, prevs = H.plant(
            ctx.sigs, [(r, *self.liar_flip) for r in rounds], None)
        at = np.array(rounds) - 1
        prevs = None if ctx.prevs is None else [ctx.prevs[i] for i in at]
        beacons = H.beacons_of(sigs[at], prevs, rounds)
        host = ChainVerifier(scheme_by_id(ctx.config["scheme_id"]),
                             bytes.fromhex(ctx.config["public_key_hex"]))
        return int(np.asarray(ctx.verifier.verify_beacons(beacons)).sum()) \
            + sum(bool(host.verify_beacon(b)) for b in beacons) \
            + int(H.reference_verdicts(ctx.config, rounds, sigs[at],
                                       prevs).sum())

    async def check_window(self, records: list[dict]) -> dict:
        """Every timed operation made one `sync()` call that returned
        true; its store holds the chain's rounds in order, byte for
        byte, and the liar's signature nowhere; its tries ended as the
        model says a correct fail-over's may, and were as many as the
        stand saw."""
        short = differing = lies = 0
        for rec in self._unchecked:
            rounds, sigs, prevs = self._committed(rec["db"])
            if len(rounds) != self.backlog or not (
                    rounds == np.arange(1, self.backlog + 1)).all():
                short += 1
            else:
                differing += H.rows_differing(sigs, prevs, self.ctx.sigs,
                                              self.ctx.prevs)
            lies += sum(r <= len(sigs) and sigs[r - 1].tobytes() == lie
                        for r, lie in rec["lies"].items())
        self._unchecked = []
        return {
            "window.sync_calls_not_true":
                sum(r.get("sync_ok") is not True for r in records),
            "window.stores_missing_rounds": short,
            "window.committed_rows_differing": differing,
            "window.lies_in_a_store": lies,
            "window.tries_the_model_does_not_allow":
                sum(self._against_model(r, self.script)
                    for r in records if "tries" in r),
            "verdicts.liars_round_judged_true":
                self._liars_round_judged_true()}

    async def check_faulted(self, draw: dict) -> dict:
        """A rejoin from a smaller group in which EVERY live peer serves
        the draw's three rounds with a bit of the signature flipped: the
        sync comes back false, having tried every peer, with nothing at
        or after the first flipped round committed and what is
        committed the chain's."""
        spec = self.ctx.traffic["faulted_group"]
        group = self.live[:spec["live"]] + self.dead[:spec["unreachable"]]
        flips = {r: (byte, bit) for r, byte, bit in draw["faults"]}
        first_bad = min(flips)
        script = [{"kind": "flips", "rounds": sorted(flips)}]
        rec = await self._rejoin(script, self.backlog, group, flips)
        rounds, sigs, prevs = self._committed(rec["db"])
        n = len(rounds)
        tried = {a for a, _from, _kind in rec["opened"]}
        H.emit(faulted_pass={
            "first_bad_round": first_bad, "sync_ok": rec["sync_ok"],
            "committed_rounds": n, "wall_s": rec["wall_s"],
            "streams": len(rec["opened"]), "tries": rec["tries"]})
        return {
            "faulted.sync_ok": int(rec["sync_ok"] is not False),
            "faulted.live_peers_not_tried":
                len(set(group) - set(self.dead) - tried),
            "faulted.committed_at_or_after_first_bad":
                int((rounds >= first_bad).sum()),
            "faulted.committed_out_of_order":
                int((rounds != np.arange(1, n + 1)).sum()),
            "faulted.committed_rows_differing":
                H.rows_differing(sigs, prevs, self.ctx.sigs[:n],
                                 self.ctx.prevs and self.ctx.prevs[:n])
                if n <= self.backlog else n,
            "faulted.tries_the_model_does_not_allow":
                self._against_model(rec, script)}

    async def close(self) -> None:
        for server in self._servers:
            await server.stop(None)
        self._servers = []
        for store in self._stores:
            store.close()
        self._stores = []
        for sock in self._sockets:
            sock.close()
        self._sockets = []

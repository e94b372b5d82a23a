"""Closed loop, one operator: `drand util check` over a node's damaged
store, again and again.  A store as the daemon builds it holds rounds
1..N; beneath its decorators, as damage on a disk lies, some rows are
overwritten (`draw_damage`, from the run's `--seed`).  One sound peer
serves the chain over localhost gRPC (`harness.serve`).  An operation is
`SyncManager.check_chain()` from its call to its return: the scan of the
whole store on the verifier, the fetch of the flagged rounds, their
verification and the overwrite.  Before each operation but the first the
driver looks at the rows the last one mended and plants the same damage
again: outside the program, inside the window, `redamage_s` of the
operation's record.

`correct` compares with `benchmark/reference/check_repair.py`, a plain
model of the check and the repair that shares no code with the program:
after every timed operation what the check filed, mended and left, and
the rows it wrote; after the window the whole store against the chain;
and a pass against a LYING peer, which serves three rounds of the repair
set with a bit of the signature flipped (`check_faulted`).

The harness hands a driver no seed (`harness.Ctx`), so this one reads the
run's own `--seed` off the command line; a caller that drives `Run` by
hand (`check_seeds.py`, a test) gets the traffic file's `damage.seed`.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

from benchmark import harness as H
from benchmark.reference import check_repair as M

LISTS = ("corrupt", "missing", "unlinked", "bad_sigs")


def seed_of_run(default: int) -> int:
    """The `--seed` of the command line that started this process."""
    argv = sys.argv
    for i, arg in enumerate(argv):
        if arg == "--seed" and i + 1 < len(argv):
            return int(argv[i + 1])
        if arg.startswith("--seed="):
            return int(arg.split("=", 1)[1])
    return default


def draw_damage(seed: int, sigs: np.ndarray, prevs, spec: dict) -> dict:
    """{round: (signature, previous_sig)} of the damaged rows, from the
    seed alone: ONE torn extent of `extent_rounds` contiguous rounds,
    both stored fields overwritten with random bytes of their own
    lengths; `sig_flips` rounds with one bit of `signature` flipped and,
    under a chained scheme, `prev_flips` with one bit of `previous_sig`
    flipped, drawn in 2..N-1 outside the extent, no two within two
    rounds of each other or of the extent (the row after the extent is
    filed with it).  Needs a chain some ten times longer than the
    damage."""
    rng = np.random.default_rng([seed % (1 << 64), 41])
    n, sig_len = sigs.shape
    chained = prevs is not None
    extent = int(spec["extent_rounds"])
    start = int(rng.integers(2, n - extent + 1))      # 2..N-extent
    out = {}
    for r in range(start, start + extent):
        out[r] = (rng.bytes(sig_len),
                  rng.bytes(len(prevs[r - 1])) if chained else b"")
    taken = list(range(start - 3, start + extent + 3))
    flips = int(spec["sig_flips"]) + (int(spec["prev_flips"]) if chained
                                      else 0)
    places: list[int] = []
    while len(places) < flips:
        r = int(rng.integers(2, n))                   # 2..N-1
        if all(abs(r - t) > 2 for t in places) and not (
                taken[0] <= r <= taken[-1]):
            places.append(r)
    for i, r in enumerate(places):
        sig, prev = sigs[r - 1].tobytes(), prevs[r - 1] if chained else b""
        field = bytearray(sig if i < int(spec["sig_flips"]) else prev)
        field[int(rng.integers(0, len(field)))] ^= 1 << int(rng.integers(0, 8))
        out[r] = (bytes(field), prev) if i < int(spec["sig_flips"]) \
            else (sig, bytes(field))
    return out


def rows_of(sigs: np.ndarray, prevs, genesis_seed: bytes) -> dict:
    """The chain as the model's store: {round: (signature, previous_sig)},
    the genesis row at round 0."""
    rows = {0: (genesis_seed, b"")}
    for i, s in enumerate(sigs):
        rows[i + 1] = (s.tobytes(), prevs[i] if prevs is not None else b"")
    return rows


def lists_differing(result: dict, found: dict) -> int:
    """Rounds (or ranges) filed under one list by the program and not by
    the reference, or the other way round, over the four lists."""
    def filed(entries) -> set:      # a range is a list in JSON, a tuple here
        return {tuple(x) if isinstance(x, (list, tuple)) else x
                for x in entries}
    return sum(len(filed(result[k]) ^ filed(found[k])) for k in LISTS)


class _SpanVerifier(H.SpanVerifier):
    """`harness.SpanVerifier` with the harness's two spans around the
    batch of rows too: what damage leaves of a flush, and the repair's
    replacements, go through `verify_beacons_async` where the verifier
    has one (the rehearsal's has none, and the program then verifies
    them as the resolver is called)."""

    def __init__(self, spanned: H.SpanVerifier):
        super().__init__(spanned._inner, spanned._spans)
        if hasattr(spanned._inner, "verify_beacons_async"):
            self.verify_beacons_async = lambda beacons: self._spanned(
                self._inner.verify_beacons_async, beacons)


class Driver:
    def __init__(self, ctx: H.Ctx):
        self.ctx = ctx
        self.backlog = len(ctx.sigs)
        spec = ctx.traffic["damage"]
        self.seed = seed_of_run(int(spec["seed"]))
        rehearsal = self.backlog < ctx.config["backlog_rounds"]
        self.spec = ctx.traffic["rehearse_damage"] if rehearsal else spec
        self.small = ctx.traffic["rehearse_damage"]
        self.verifier = _SpanVerifier(ctx.verifier)
        self.chained = ctx.prevs is not None
        self._stores: list = []
        self._servers: list = []
        self._peers: list = []
        self.store = self.sm = None
        self.addr = ""
        self.damage: dict = {}
        self._clean = False       # the store holds no planted damage
        self._last: dict | None = None      # the last operation's record
        self._model = None

    def segment_starts(self) -> list[int]:
        """The scan flushes every `bucket_rounds` good rows."""
        return list(range(1, self.backlog + 1,
                          self.ctx.config["bucket_rounds"]))

    # -- the stand ------------------------------------------------------------

    def _node(self, rounds: int, label: str):
        """A node's store holding the chain's first `rounds` rounds."""
        ctx = self.ctx
        store = H.new_node_store(
            os.path.join(ctx.workdir, f"{label}.db"), ctx.group)
        self._stores.append(store)
        H.fill_store(store, H.beacons_of(
            ctx.sigs[:rounds], ctx.prevs and ctx.prevs[:rounds]))
        return store

    def _drop(self, store) -> None:
        self._stores.remove(store)
        store.close()
        os.remove(store.insecure.path)

    async def _serve(self, sigs: np.ndarray, prevs, label: str) -> str:
        from drand_tpu.chain.store import SqliteStore
        store = SqliteStore(os.path.join(self.ctx.workdir, f"{label}.db"))
        H.fill_store(store, H.beacons_of(sigs, prevs))
        server, addr = await H.serve(store)
        self._servers.append((server, store))
        return addr

    def _manager(self, store, addr: str):
        """The node's SyncManager, as a `BeaconProcess` builds it, with
        one peer; its insecure store in the harness's spans."""
        from drand_tpu.beacon.sync_manager import SyncManager
        from drand_tpu.net.client import GrpcBeaconNetwork, PeerClients
        if not hasattr(SyncManager, "check_chain"):
            raise H.BenchFailure(
                "the program has no SyncManager.check_chain: this cell's "
                "operation is that call (drand util check as one scan and "
                "one batched repair)")
        ctx = self.ctx
        peers = PeerClients()
        self._peers.append(peers)
        return SyncManager(
            store, ctx.group, self.verifier,
            GrpcBeaconNetwork(peers, beacon_id="bench"), [H.Peer(addr)],
            H.Clock(), insecure_store=H.SpanStore(store.insecure, ctx.spans),
            beacon_id="bench")

    @staticmethod
    def _plant(store, damage: dict) -> None:
        """Beneath the store's decorators, as damage on a disk lies."""
        from drand_tpu.chain.beacon import Beacon
        store.insecure.put_many(
            [Beacon(round=r, signature=sig, previous_sig=prev)
             for r, (sig, prev) in sorted(damage.items())])

    def _stored(self, store, rounds) -> dict:
        """{round: (signature, previous_sig)} of the given rounds as the
        store holds them now, a run a query."""
        out = {}
        for first, last in M.runs(rounds):
            for r, sig, prev in store.insecure.read_fields(
                    first, last - first + 1):
                if r <= last:
                    out[r] = (sig, prev)
        return out

    async def setup(self) -> None:
        ctx = self.ctx
        self.addr = await self._serve(ctx.sigs, ctx.prevs, "serve")
        self.store = self._node(self.backlog, "node")
        self.sm = self._manager(self.store, self.addr)
        self.damage = draw_damage(self.seed, ctx.sigs, ctx.prevs, self.spec)
        self._plant(self.store, self.damage)
        H.emit(damage={"seed": self.seed, "rounds": len(self.damage),
                       "spec": self.spec,
                       "runs": len(M.runs(self.damage))})

    async def warmup(self) -> None:
        """One check of a damaged store of `warmup_rounds` rounds: the
        scan's program, the batch of rows and the repair's dispatch."""
        ctx = self.ctx
        n = min(ctx.traffic["warmup_rounds"], self.backlog)
        store = self._node(n, "warmup")
        damage = draw_damage(self.seed, ctx.sigs[:n], ctx.prevs
                             and ctx.prevs[:n], self.small)
        self._plant(store, damage)
        result = await self._manager(store, self.addr).check_chain()
        self._drop(store)
        # what it mended is the window's to judge (the control's warm-up
        # leaves rows it cannot see)
        if result.report.tip_round != n or not result.flagged:
            raise H.BenchFailure(
                f"the warm-up check found {result.to_dict()}")

    def _truth(self, r: int):
        ctx = self.ctx
        return (ctx.sigs[r - 1].tobytes(),
                ctx.prevs[r - 1] if self.chained else b"")

    # -- the operation --------------------------------------------------------

    def _read_back(self) -> None:
        """The rows the last operation mended, as the store holds them."""
        last = self._last
        if last is not None and "mended_differing" not in last:
            stored = self._stored(self.store, last["result"]["fixed"])
            last["mended_differing"] = sum(
                stored.get(r) != self._truth(r)
                for r in last["result"]["fixed"])

    def _redamage(self) -> float:
        """Between two operations: the last one's rows read back, and
        the same damage planted again."""
        t0 = time.perf_counter()
        self._read_back()
        if self._clean:
            self._plant(self.store, self.damage)
            self._clean = False
        return time.perf_counter() - t0

    async def operate(self) -> dict:
        ctx = self.ctx
        redamage_s = self._redamage()
        first_span = len(ctx.spans.rows)
        t0 = time.perf_counter()
        result = await self.sm.check_chain()
        wall = time.perf_counter() - t0
        self._clean = True
        out = result.to_dict()
        rec = {"ok": not result.unfixed and result.flagged > 0
               and out["tip_round"] == self.backlog,
               "rounds": result.scanned - 1,    # the genesis row is no round
               "wall_s": wall, "redamage_s": redamage_s, "result": out,
               "t0": t0, "spans": ctx.spans.totals(first_span)}
        rec["ok"] = rec["ok"] and rec["rounds"] == self.backlog
        self._last = rec
        return rec

    def end_to_end(self, records: list[dict], elapsed: float) -> dict:
        good = [r for r in records if r["ok"]]
        return {"scan_rate": sum(r["rounds"] for r in good) / elapsed}

    # -- the output check -----------------------------------------------------

    def model(self) -> dict:
        """The plain reference's account of the planted damage, once."""
        if self._model is None:
            ctx = self.ctx
            truth = rows_of(ctx.sigs, ctx.prevs, ctx.group.genesis_seed)
            judge = M.Judge(bytes.fromhex(ctx.config["public_key_hex"]),
                            ctx.config["signature_group"] == "G1",
                            self.chained, truth)
            rows = {**truth, **self.damage}
            found = M.check(rows, judge, self.chained)
            self._model = {"truth": truth, "judge": judge, "rows": rows,
                           "found": found, "mend": M.to_mend(found)}
            H.emit(reference_check={
                **{k: len(found[k]) for k in LISTS},
                "to_mend": len(self._model["mend"]),
                "runs": len(M.runs(self._model["mend"])),
                "pairings": judge.pairings})
        return self._model

    def _dispatches_beyond(self, records: list[dict]) -> int:
        """Verifier dispatches of the timed operations beyond what
        `rows_charged` allows: a flush of the scan's for every
        `bucket_rounds` verified rows and one for the rest, one for the
        replacements.  Counted off the program's `verify.dispatch`
        spans; a verifier that has none (the rehearsal's) gives 0."""
        try:
            from drand_tpu import tracing
        except ImportError:
            return 0
        starts = sorted(sp.start_mono for sp in tracing.RECORDER.spans()
                        if sp.name == "verify.dispatch")
        if not starts:
            return 0
        m = self.model()
        charged = getattr(self.verifier, "rows_charged", None)
        flush = charged(self.ctx.config["bucket_rounds"]) if charged \
            else self.ctx.config["bucket_rounds"]
        verified = self.backlog - len(m["found"]["unlinked"]) \
            - len(m["found"]["corrupt"])
        allowed = -(-verified // flush) + -(-len(m["mend"]) // flush)
        beyond = 0
        for rec in records:
            made = sum(rec["t0"] <= s <= rec["t0"] + rec["wall_s"]
                       for s in starts)
            beyond += abs(made - allowed)
        return beyond

    async def check_window(self, records: list[dict]) -> dict:
        """Every timed check filed what the reference files of the
        planted damage, mended all of it and wrote the chain's rows; the
        store is then the chain, byte for byte in both fields; the
        re-planting took under a hundredth of an operation."""
        m = self.model()
        self._read_back()           # the last operation's
        good = [r for r in records if r["ok"]]
        rounds, sigs, prevs = H.stored_rows(
            self.store.insecure, self.backlog, self.ctx.sigs.shape[1])
        whole = len(rounds) == self.backlog and self._clean
        return {
            "window.filed_differs_from_reference":
                sum(lists_differing(r["result"], m["found"]) for r in good),
            "window.fixed_differs_from_reference":
                sum(len(set(r["result"]["fixed"]) ^ set(m["mend"]))
                    for r in good),
            "window.mended_rows_differing":
                sum(r.get("mended_differing", self.backlog) for r in good),
            "window.store_missing_rounds": int(not whole),
            "window.stored_rows_differing":
                H.rows_differing(sigs, prevs, self.ctx.sigs, self.ctx.prevs)
                if whole else self.backlog - len(rounds),
            "window.redamage_over_a_hundredth":
                sum(r["redamage_s"] > 0.01 * r["wall_s"] for r in good),
            "window.dispatches_beyond_rows_charged":
                self._dispatches_beyond(good)}

    async def check_faulted(self, draw: dict) -> dict:
        """A check of the same damaged store against a LYING peer: three
        seeded rounds of the repair set served with one bit of the
        signature flipped, every other round sound.  The check files
        what the reference files, mends what the reference's repair
        mends of that peer's rows and leaves the rest as it was: a lie
        never reaches the store, and under a chained scheme neither does
        the replacement after a lie, which no signature of the
        consumer's own stands before."""
        ctx = self.ctx
        m = self.model()
        rng = np.random.default_rng(
            [abs(int(x)) for f in draw["faults"] for x in f] + [41])
        lies = {}
        for (_r, byte, bit), r in zip(draw["faults"], sorted(rng.choice(
                m["mend"], size=min(len(draw["faults"]), len(m["mend"])),
                replace=False).tolist())):
            sig = bytearray(ctx.sigs[r - 1].tobytes())
            sig[byte % len(sig)] ^= 1 << bit
            lies[int(r)] = bytes(sig)
        served = {r: lies.get(r, m["truth"][r][0]) for r in m["mend"]}
        rows, fixed, unfixed = M.repair(
            m["rows"], m["mend"], served, m["judge"], self.chained,
            ctx.group.genesis_seed)
        lying = ctx.sigs.copy()
        for r, sig in lies.items():
            lying[r - 1] = np.frombuffer(sig, dtype=np.uint8)
        addr = await self._serve(lying, ctx.prevs, "lying")
        store = self._node(self.backlog, "faulted")
        self._plant(store, self.damage)
        try:
            result = (await self._manager(store, addr).check_chain()
                      ).to_dict()
            r_, sigs, prevs = H.stored_rows(store.insecure, self.backlog,
                                            ctx.sigs.shape[1])
            got = {int(r): (s.tobytes(), p)
                   for r, s, p in zip(r_, sigs, prevs)}
        finally:
            self._drop(store)
            server, serving = self._servers.pop()
            await server.stop(None)
            serving.close()
            os.remove(serving.path)
        H.emit(faulted_pass={
            "lies": sorted(lies), "reference_unfixed": unfixed,
            "unfixed": result["unfixed"][:16],
            "fixed": len(result["fixed"]), "streams": result["streams"],
            "dispatches": result["dispatches"]})
        return {
            "faulted.filed_differs_from_reference":
                lists_differing(result, m["found"]),
            "faulted.fixed_differs_from_reference":
                len(set(result["fixed"]) ^ set(fixed)),
            "faulted.unfixed_differs_from_reference":
                len(set(result["unfixed"]) ^ set(unfixed)),
            "faulted.lies_in_the_store":
                sum(got.get(r, ("",))[0] == sig for r, sig in lies.items()),
            "faulted.rows_differing_from_reference":
                sum(got.get(r) != rows[r] for r in rows if r)
                + abs(len(got) - (len(rows) - 1))}

    async def close(self) -> None:
        for peers in self._peers:
            await peers.close()
        self._peers = []
        while self._servers:
            server, store = self._servers.pop()
            await server.stop(None)
            store.close()
        for store in list(self._stores):
            self._stores.remove(store)
            store.close()

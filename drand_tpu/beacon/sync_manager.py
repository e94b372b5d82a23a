"""Chain catch-up sync (reference `chain/beacon/sync_manager.go`).

Follower side: queued sync requests, shuffled peer iteration until the
store holds a bounded request's target (`SyncManager.sync`), stall
detection at 2x period — but where the reference verifies each streamed
beacon one at a time (`sync_manager.go:397-399`, the serial loop SURVEY.md
§5.7 calls out), this sync manager accumulates stream chunks and verifies
whole contiguous segments in ONE batched device call
(`ChainVerifier.verify_chain_segment`) before appending.

Also implements the local-chain validation/repair pair behind `drand
util check` (`SyncManager.check_chain`): the check (`check_past_beacons`,
`:171-232`) is the start-up scan's `recovery.scan_store` over the
insecure store, the repair (`correct_past_beacons`, `:234-265`) fetches
the flagged rounds in contiguous runs, verifies every replacement of a
check in one batch over the consumer's own links, and overwrites what
verified in one transaction.
"""

from __future__ import annotations

import asyncio
import contextlib
import gc
import itertools
import os
import random
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from drand_tpu import log as dlog
from drand_tpu import tracing
from drand_tpu.chain import codec as row_codec
from drand_tpu.chain import recovery
from drand_tpu.chain.beacon import Beacon
from drand_tpu.chain.segment import PackedBeacons, pack_rows
from drand_tpu.chain.store import BeaconNotFound, StoreError

log = dlog.get("sync")

SYNC_CHUNK = 512          # live-tail beacons per batched verify call
SYNC_CHUNK_MAX = 16384    # deep-backlog ceiling (the throughput bucket)
# One growth step 512 -> 16384: under the default buckets both ends are
# programs of their own, and a hop over 4096 would make a node build a
# third (minutes each, on every start) to verify no round sooner.  Where
# the verifier charges a dispatch of 512 rows for more (one pinned
# bucket), a catch-up that knows its backlog skips the small end too:
# `SyncManager._fetch_stage` cuts where the program is full.
SYNC_CHUNK_GROWTH = 32
STALL_FACTOR = 2          # renew sync if no progress for factor * period
# hedged peer dispatch: launch the next candidate's liveness probe this
# long after the previous one (Dean & Barroso tail-at-scale)
HEDGE_PROBE_DELAY_S = 0.3
HEDGE_PROBE_BOUND_S = 5.0  # real-time bound on the whole probe race
# bounded hand-off depth between catch-up pipeline stages: enough that
# fetch, pack/dispatch, and settle/commit all stay busy on a deep
# backlog, small enough that a failed segment wastes at most a couple
# of already-dispatched successors
PIPELINE_DEPTH = int(os.environ.get("DRAND_TPU_SYNC_PIPELINE_DEPTH", "2"))


_pause_lock = threading.Lock()
_paused = {"depth": 0, "was_on": False}     # under `_pause_lock`


@contextlib.contextmanager
def _collector_paused():
    """No cyclic collection while a segment's rows are alive.

    A commit materializes a Beacon a round and the store's decorators a
    second one (65,536 rounds on four chips: 131,072 objects that hold no
    reference cycle and die with the commit).  Left alone they outlive
    two young collections, and in a process whose heap is small (a loaded
    program: 142,000 objects) that sets off two full collections a
    commit: about 50 ms each in which no Python thread runs, the event
    loop included.  The collector is one for the process and a daemon
    commits for every chain it carries, each from a worker thread of its
    own: where commits overlap, the first to begin notes what it found
    and the last to end puts that back, so none of them runs its rest
    with the collector on because another has ended."""
    with _pause_lock:
        if not _paused["depth"]:
            _paused["was_on"] = gc.isenabled()
            gc.disable()
        _paused["depth"] += 1
    try:
        yield
    finally:
        with _pause_lock:
            _paused["depth"] -= 1
            if not _paused["depth"] and _paused["was_on"]:
                gc.enable()


def _observe_stage(stage: str, seconds: float) -> None:
    try:
        from drand_tpu import metrics as M
        M.SYNC_SEGMENT_SECONDS.labels(stage).observe(seconds)
    except Exception:
        pass


def _item_span(item) -> tuple[int, int, int]:
    """(first_round, last_round, count) of a stream item — a Beacon or a
    PackedBeacons chunk; the fetch stage treats both uniformly."""
    if isinstance(item, PackedBeacons):
        return item.start_round, item.end_round, len(item)
    return item.round, item.round, 1


def _item_tail_sig(item) -> bytes:
    return item.tail_sig if isinstance(item, PackedBeacons) \
        else item.signature


@dataclass
class SyncRequest:
    from_round: int
    up_to: int = 0            # 0 = follow forever / to head


class _CatchupPipeline:
    """Multi-stage off-loop catch-up pipeline (ISSUE 13):

        fetch (event loop) -> pack/dispatch (worker) -> settle/commit

    The fetch stage (the _try_node stream loop) hands flushed segments —
    lists of stream items, Beacons or PackedBeacons chunks — through a
    bounded queue to the pack task, which coalesces them into ONE
    verifier dispatch in a worker thread (`asyncio.to_thread`): columnar
    packing, np.concatenate, and the eager-host small-batch verify all
    leave the event loop, which previously froze for the whole pack +
    sqlite-commit window of every 16384-round segment while live RPCs
    queued behind it.  The settle task resolves each segment's device
    result and commits via `store.put_many` in a worker thread, in
    strict segment order (FIFO queues), so the commit contract of the
    depth-1 pipeline is unchanged:

      - beacons reach the store only after THEIR segment settles valid;
      - a failed segment commits nothing from that segment or later
        (later segments are discarded, not settled);
      - a commit/dispatch error is re-raised to the caller after the
        stages drain.

    Spans (one trace a catch-up, under `SyncManager._try_node`'s
    `sync.catchup`): every flushed segment is a `sync.segment` from its
    flush to the end of its commit, with children `sync.queue_wait`
    (attribute `stage`: enqueue to dequeue in that stage's queue, and the
    queue's depth at the enqueue), `sync.backpressure` (only where a
    full queue made the producer wait), `sync.pack`, the verifier's
    `verify.segment` and `verify.dispatch`, `sync.settle` (the same two
    clock reads as `stats["verify_s"]`) over `verify.resolve`,
    `store.materialize` and the store's `store.commit`.  Never a span a
    round.
    """

    _CLOSE = object()

    @dataclass
    class _Work:
        """One flushed segment on its way through the stages."""
        items: list
        anchor_sig: bytes
        span: tracing.Span           # sync.segment; ends when it leaves
        queued_at: float = 0.0       # perf_counter at the last enqueue
        depth: int = 0               # that queue's depth at that enqueue
        seg: object = None           # coalesced by the pack stage
        resolver: object = None

    def __init__(self, manager, up_to: int):
        self.m = manager
        self.up_to = up_to
        self.got_any = False
        self.failure = False                       # segment verify failed
        self.error: BaseException | None = None    # dispatch/commit error
        self._q_verify: asyncio.Queue = asyncio.Queue(maxsize=PIPELINE_DEPTH)
        self._q_commit: asyncio.Queue = asyncio.Queue(maxsize=PIPELINE_DEPTH)
        self._tasks: list[asyncio.Task] = []

    def start(self) -> None:
        loop = asyncio.get_running_loop()
        self._tasks = [loop.create_task(self._pack_loop()),
                       loop.create_task(self._settle_loop())]

    @property
    def broken(self) -> bool:
        return self.failure or self.error is not None

    async def _enqueue(self, queue: asyncio.Queue, work, stage: str) -> None:
        """Backpressure: a full queue blocks the producer (the fetch
        loop, the pack stage), bounding in-flight memory."""
        work.depth = queue.qsize()
        began = work.queued_at = time.perf_counter()
        if queue.full():
            await queue.put(work)
            # no other task runs between the put and this line
            work.queued_at = time.perf_counter()
            tracing.record_span("sync.backpressure", began, work.queued_at,
                                parent=work.span, stage=stage)
        else:
            queue.put_nowait(work)

    def _dequeued(self, work, stage: str) -> float:
        """Note how long `work` sat in `stage`'s queue; returns the clock
        reading, which is also where the stage's own time starts."""
        now = time.perf_counter()
        tracing.record_span("sync.queue_wait", work.queued_at, now,
                            parent=work.span, stage=stage, depth=work.depth)
        return now

    async def submit(self, items: list, anchor_sig: bytes,
                     rounds: int, cut: str) -> None:
        """Hand a flushed segment of `rounds` rounds to the pack stage;
        `cut` is what ended it (`_fetch_stage`)."""
        sp = tracing.begin_span(  # lint: disable=span-balance
            "sync.segment", beacon_id=self.m.beacon_id,
            first_round=_item_span(items[0])[0],
            rounds=rounds, cut=cut)  # ended where it leaves a stage
        await self._enqueue(self._q_verify,
                            self._Work(items, anchor_sig, sp), "verify")

    async def close(self) -> None:
        """Drain both stages to completion (commits every segment still
        in flight that verifies) and reap the tasks."""
        await self._q_verify.put(self._CLOSE)
        await asyncio.gather(*self._tasks, return_exceptions=True)

    # -- pack/dispatch stage ------------------------------------------------

    def _coalesce(self, items: list, anchor_sig: bytes):
        """Worker thread: merge a flushed run of stream items into one
        verifiable segment — a list[Beacon] (per-beacon wire) or a single
        PackedBeacons (chunked wire).  Mixed runs materialize to beacons,
        chaining prevs from the caller's anchor."""
        if all(isinstance(i, Beacon) for i in items):
            return items
        if len(items) == 1:
            return items[0]
        if (all(isinstance(i, PackedBeacons) for i in items)
                and len({i.sig_len for i in items}) == 1
                and len({i.chained for i in items}) == 1):
            return PackedBeacons(start_round=items[0].start_round,
                                 sigs=np.concatenate(
                                     [i.sigs for i in items]),
                                 first_prev=items[0].first_prev,
                                 chained=items[0].chained)
        out: list[Beacon] = []
        prev = anchor_sig
        for it in items:
            if isinstance(it, Beacon):
                out.append(it)
                prev = it.signature
            else:
                out.extend(it.beacons(anchor_sig=prev))
                prev = it.tail_sig
        return out

    def _dispatch(self, work) -> None:
        """Worker thread, under the segment's span."""
        t0 = time.perf_counter()
        seg = self._coalesce(work.items, work.anchor_sig)
        tracing.record_span("sync.pack", t0, time.perf_counter(),
                            items=len(work.items))
        if isinstance(seg, list):
            resolver = self.m.verifier.verify_chain_segment_async(
                seg, work.anchor_sig)
        else:
            resolver = self.m.verifier.verify_packed_segment_async(
                seg, work.anchor_sig)
        work.seg, work.resolver = seg, resolver

    async def _pack_loop(self) -> None:
        while True:
            work = await self._q_verify.get()
            if work is self._CLOSE:
                await self._q_commit.put(self._CLOSE)
                return
            t0 = self._dequeued(work, "verify")
            if self.broken:
                work.span.end("discarded")   # drain-and-discard
                continue
            try:
                with tracing.under(work.span):
                    await asyncio.to_thread(self._dispatch, work)
            except BaseException as exc:  # noqa: BLE001 — stage must drain
                self.error = exc
                work.span.end("error")
                continue
            dt = time.perf_counter() - t0
            self.m.stats["pack_s"] += dt
            self.m.stats["rows_dispatched"] += len(work.seg)
            _observe_stage("pack", dt)
            await self._enqueue(self._q_commit, work, "commit")

    # -- settle/commit stage ------------------------------------------------

    def _commit(self, seg, anchor_sig: bytes) -> int:
        """Worker thread, under the segment's span: the store's own
        `store.commit` span becomes the segment's child."""
        with _collector_paused():
            if isinstance(seg, list):
                beacons = seg
            else:
                t0 = time.perf_counter()
                beacons = seg.beacons(anchor_sig=anchor_sig)
                tracing.record_span("store.materialize", t0,
                                    time.perf_counter(), rounds=len(beacons))
            self.m.store.put_many(beacons)
            n = len(beacons)
            del beacons          # gone before the collector is back
        return n

    async def _settle_loop(self) -> None:
        while True:
            work = await self._q_commit.get()
            if work is self._CLOSE:
                return
            t0 = self._dequeued(work, "commit")
            seg, anchor_sig = work.seg, work.anchor_sig
            if self.broken:
                # dispatched behind the segment that broke the pipeline:
                # the device verifies it and nobody reads the verdicts
                self.m.stats["rows_discarded"] += len(seg)
                work.span.end("discarded")
                continue
            settle = tracing.begin_span("sync.settle", parent=work.span,
                                        at=t0)
            try:
                with tracing.under(settle):
                    ok = np.asarray(await asyncio.to_thread(work.resolver))
            except BaseException as exc:  # noqa: BLE001
                self.error = exc
                settle.end("error")
                work.span.end("error")
                continue
            t1 = time.perf_counter()
            settle.end(at=t1)
            dt = t1 - t0
            self.m.stats["verify_s"] += dt
            _observe_stage("verify", dt)
            if not bool(np.all(ok)):
                if isinstance(seg, list):
                    bad = [seg[i].round for i in np.nonzero(~ok)[0][:5]]
                else:
                    bad = [int(seg.start_round + i)
                           for i in np.nonzero(~ok)[0][:5]]
                log.warning("segment verify failed at rounds %s", bad)
                self.failure = True
                # its good rows' verdicts commit nothing either
                self.m.stats["rows_discarded"] += len(seg)
                work.span.end("verify_failed")
                continue
            t0 = time.perf_counter()
            try:
                with tracing.under(work.span):
                    n = await asyncio.to_thread(self._commit, seg,
                                                anchor_sig)
            except BaseException as exc:  # noqa: BLE001
                self.error = exc
                work.span.end("error")
                continue
            dt = time.perf_counter() - t0
            work.span.end()
            self.m.stats["commit_s"] += dt
            self.m.stats["segments"] += 1
            self.m.stats["rounds"] += n
            _observe_stage("commit", dt)
            self.got_any = True
            last_round = seg[-1].round if isinstance(seg, list) \
                else seg.end_round
            if self.m.on_progress is not None:
                self.m.on_progress(last_round, self.up_to)


class SyncManager:
    def __init__(self, store, group, verifier, network, nodes, clock,
                 insecure_store=None, resilience=None, beacon_id: str = ""):
        """store: decorated chain store; verifier: ChainVerifier;
        network: BeaconNetwork (sync_chain); nodes: peer identities;
        insecure_store: the UNDECORATED store (no append-only check) that
        correct_past_beacons overwrites repaired rounds through — the
        reference passes the same pair (sync_manager.go:234-265);
        resilience: the daemon's Resilience hub — peer selection becomes
        breaker-aware and dispatch hedged when wired (None keeps the
        plain shuffled iteration for unit-test fakes); beacon_id: whose
        chain this is, on `sync.catchup` and every span under it (a
        daemon runs one manager a chain, all in one trace ring)."""
        self.store = store
        self.group = group
        self.verifier = verifier
        self.net = network
        self.nodes = nodes
        self.clock = clock
        self.insecure_store = insecure_store
        self.resilience = resilience
        self.beacon_id = beacon_id
        # bounded: sync requests are cheap hints (the next sync reads
        # the live tip anyway), so a backlog past this is pure overload
        # — drop visibly rather than queue stale targets
        self._queue: asyncio.Queue[SyncRequest] = asyncio.Queue(maxsize=64)
        self._task: asyncio.Task | None = None
        self.on_progress = None        # callback(round, target)
        # cumulative per-stage host seconds + throughput counters of the
        # catch-up pipeline — the /debug/sync snapshot and the bench's
        # per-stage breakdown both read this
        # `rounds_fetched`: rounds taken off the wire; `rounds_refetched`:
        # those of them at or below the highest round this manager had
        # taken off it before (a peer dropped with a run buffered, a
        # segment failed: the next peer serves them again);
        # `rows_dispatched`: rows handed to the verifier, of which
        # `rows_discarded` are those whose verdicts committed nothing (a
        # segment that verified false, and what was enqueued behind it)
        self.stats = {"fetch_s": 0.0, "pack_s": 0.0, "verify_s": 0.0,
                      "commit_s": 0.0, "segments": 0, "rounds": 0,
                      "rounds_fetched": 0, "rounds_refetched": 0,
                      "rows_dispatched": 0, "rows_discarded": 0}
        self._wire_high = 0            # highest round taken off the wire
        self._current_peer = ""
        self._chunk_target = SYNC_CHUNK
        self._backlog = 0
        self._try = 0                  # which try of the request at hand
        self._try_end = ""             # how the last try ended (`end`)
        # `sync.failover`, open from the end of a try that left the
        # request short to the next peer's first wire message
        self._failover: tracing.Span | None = None

    def snapshot(self) -> dict:
        """Point-in-time sync state for /debug/sync."""
        return {
            "current_peer": self._current_peer,
            "try": self._try,
            "last_try_end": self._try_end,
            "chunk_target": self._chunk_target,
            "pipeline_depth": PIPELINE_DEPTH,
            "backlog_estimate": self._backlog,
            "queued_requests": self._queue.qsize(),
            "stats": dict(self.stats),
        }

    def start(self):
        if self._task is None:
            self._task = asyncio.get_running_loop().create_task(self._loop())

    def stop(self):
        if self._task is not None:
            self._task.cancel()
            self._task = None

    def request_sync(self, from_round: int, up_to: int = 0) -> None:
        try:
            self._queue.put_nowait(SyncRequest(from_round, up_to))
        except asyncio.QueueFull:
            try:
                from drand_tpu import metrics as M
                M.QUEUE_DROPPED.labels("sync_requests").inc()
            except Exception:
                pass

    # -- follower loop ------------------------------------------------------

    async def _loop(self):
        while True:
            req = await self._queue.get()
            try:
                await self.sync(req)
            except Exception as exc:
                log.warning("sync failed: %s", exc)

    async def sync(self, req: SyncRequest) -> bool:
        """One sync request against the group's peers, one after another
        (Sync/tryNode, sync_manager.go:296-438).

        A bounded request (`up_to > 0`) is true only with the store at
        `up_to`: a peer whose try leaves the store short of it (its
        stream ended cleanly before the target, as a serving node with a
        damaged row of its own ends it; it dropped; it stalled; a
        segment of its rounds verified false) is followed by the next,
        which resumes past `store.last()`, and the request is false only
        when every peer was tried, with everything verified so far
        committed.  In follow mode (`up_to == 0`) there is no height to
        reach: the request ends with the first peer whose stream
        committed anything, false where none did.

        Who is next: the shuffled list, re-ranked breaker-aware before
        every try where the daemon's Resilience hub is wired (closed
        first, open last: open peers stay reachable as a last resort so
        a fully-tripped net keeps its liveness path), the head of the
        line going to the first peer that answers a hedged liveness
        probe (`_hedge_probe_order`).

        One trace a request: the root `sync.request` (from_round, up_to,
        peers; at its end `tries`, `reached`, and what the request added
        to `stats`: `rounds`, `rounds_fetched`, `rounds_refetched`,
        `rows_dispatched`, `rows_discarded`) over a `sync.catchup` a try
        (`_try_node`), and
        between a try that left the request short and the next peer's
        first wire message a `sync.failover` (from_peer, reason: that
        try's `end`; to_peer, `wall_s`; `spent` where no peer was left
        to deliver one) over the `sync.probe` that chose the peer."""
        peers = list(self.nodes)
        random.shuffle(peers)
        before = dict(self.stats)
        # NOTE: sync outcomes deliberately do NOT feed the breakers —
        # only RetryPolicy-gated unary traffic does, keeping failure
        # sequences (and so trip points) deterministic in fake time for
        # chaos replay.  Sync READS breaker state (the ranking below)
        # without writing it.
        with tracing.span("sync.request", beacon_id=self.beacon_id,
                          from_round=req.from_round, up_to=req.up_to,
                          peers=len(peers)) as root:
            reached = self._reached(req.up_to)
            self._try = 0
            try:
                while peers and not reached:
                    with tracing.under(self._failover or root):
                        peers = await self._ranked(peers)
                    peer = peers.pop(0)
                    addr = getattr(peer, "address", "") or str(peer)
                    self._try += 1
                    try:
                        ok = await self._try_node(peer, req)
                    except Exception as exc:
                        log.debug("peer %s sync error: %s", addr, exc)
                        ok = False
                    reached = self._reached(req.up_to) if req.up_to else ok
                    if not reached and self._failover is None:
                        self._failover = tracing.begin_span(  # lint: disable=span-balance
                            "sync.failover", parent=root, from_peer=addr,
                            reason=self._try_end)   # ended by `_arrived`
            finally:
                self._arrived("", time.perf_counter(), "spent")
                root.set(tries=self._try, reached=reached,
                         **{k: self.stats[k] - before[k] for k in (
                             "rounds", "rounds_fetched", "rounds_refetched",
                             "rows_dispatched", "rows_discarded")})
        return reached

    def _reached(self, up_to: int) -> bool:
        """The store holds a bounded request's target."""
        if not up_to:
            return False
        try:
            return self.store.last().round >= up_to
        except StoreError:              # empty, or a torn tip
            return False

    def _arrived(self, peer: str, at: float, status: str | None = None):
        """A peer's first wire message came (or no peer is left): the
        end of the open `sync.failover`, if there is one."""
        sp, self._failover = self._failover, None
        if sp is not None:
            sp.set(to_peer=peer, wall_s=at - sp.start_mono)
            sp.end(status, at=at)

    async def _ranked(self, peers: list) -> list:
        """The peers not yet tried, the next one first."""
        if self.resilience is None or len(peers) < 2:
            return peers
        peers = self.resilience.breakers.rank(
            peers, key=lambda n: getattr(n, "address", ""))
        return await self._hedge_probe_order(peers)

    async def _hedge_probe_order(self, peers: list) -> list:
        """Hedged segment dispatch: stagger Status probes across the top
        candidates (delayed secondary launch, first success wins, losers
        cancelled); the winner serves the stream first.  Best-effort —
        any failure falls back to the breaker-ranked order — and bounded
        in real time so a hung probe cannot wedge a sync request.  One
        span a race, `sync.probe` (candidates, winner, `wall_s`)."""
        from drand_tpu.resilience import hedge
        status = getattr(self.net, "status", None)
        if status is None:
            return peers
        top = peers[:3]

        async def probe(p):
            await status(p)
            return p

        with tracing.span("sync.probe", candidates=len(top)) as sp:
            try:
                winner = await asyncio.wait_for(
                    hedge.first_success(
                        "sync.dispatch", [lambda p=p: probe(p) for p in top],
                        delay_s=HEDGE_PROBE_DELAY_S, clock=self.clock),
                    HEDGE_PROBE_BOUND_S)
            except Exception:
                winner = None
            sp.set(winner=getattr(winner, "address", "") if winner else "",
                   wall_s=time.perf_counter() - sp.start_mono)
        if winner is None:
            return peers
        return [winner] + [p for p in peers if p is not winner]

    async def _try_node(self, peer, req: SyncRequest) -> bool:
        """One catch-up from one peer, as one trace: the root span
        `sync.catchup` over `_fetch_stage` (under a request's
        `sync.request`, where `sync` made the call; `try` is its number
        there).  The wire wait stays a
        counter on it (`fetch_s`, `messages`; from the network layer
        `recv_s`, `decode_s`, `bytes`): a catch-up makes a wait for
        every message, which is no span each; `_fetch_stage` files them
        on one `sync.fetch` a segment.  While the root is open the event
        loop's lag is counted on it (`tracing.loop_watched`).  At its
        end it says how the try ended (`end`): `done` (the stream served
        what was asked: a bounded try to `up_to`), `ended_short` (the
        stream ended, or went out of order, before that), `dropped` (it
        raised after its first message), `unreachable` (before it),
        `stalled` (it idled STALL_FACTOR periods), `verify_failed` (a
        segment of its rounds verified false) or `error` (the
        pipeline's own: a dispatch, a commit)."""
        before = dict(self.stats)
        self._try_end = ""
        with tracing.span(
                "sync.catchup", beacon_id=self.beacon_id,
                from_round=req.from_round, up_to=req.up_to,
                peer=getattr(peer, "address", "") or str(peer),
                **{"try": self._try}) as root, \
                tracing.loop_watched(root):
            try:
                return await self._fetch_stage(peer, req, root)
            finally:
                root.set(end=self._try_end or "error",
                         **{k: self.stats[k] - before[k]
                            for k in ("rounds", "segments", "fetch_s")})

    async def _fetch_stage(self, peer, req: SyncRequest, root) -> bool:
        """Consume one peer's stream through the off-loop catch-up
        pipeline (tryNode, sync_manager.go:326-438 — rebuilt, ISSUE 13).

        This coroutine is only the FETCH stage: it consumes stream items
        (per-beacon Beacons from reference peers, PackedBeacons chunks
        from chunk-capable ones), checks contiguity, and hands flushed
        segments to a _CatchupPipeline whose pack/dispatch and
        settle/commit stages run their host-heavy parts
        (np.concatenate packing, resolver blocking, sqlite put_many) in
        worker threads — the event loop stays responsive through a deep
        catch-up instead of freezing per 16384-round segment."""
        try:
            last = self.store.last()
        except BeaconNotFound:
            return False
        # the resumption: a request's second peer begins where the
        # first one's verified rounds end
        from_round = max(req.from_round, last.round + 1)
        # the anchor advances OPTIMISTICALLY at flush time (to the
        # flushed tail) — sound because verify failure or commit error
        # poisons the pipeline: nothing later settles, and _try_node
        # reports failure (same contract as the depth-1 predecessor)
        anchor_round, anchor_sig = last.round, last.signature
        buffer: list = []          # stream items (Beacon | PackedBeacons)
        buffered = 0               # rounds accumulated in `buffer`
        # Where a segment is cut.  The target starts small (SYNC_CHUNK:
        # the live tail verifies in low-latency batches) and a stream
        # that fills it without idling grows it to SYNC_CHUNK_MAX, the
        # throughput program; an idle stream (= we are at the head)
        # resets it.  The device is charged by the program, not by the
        # row: a verifier that pads 512 rows into its one 16,384-row
        # program takes as long over them as over 16,384, and one that
        # lays them over four chips as long as over 65,536.  So a
        # catch-up that knows its backlog (`up_to`) asks the verifier
        # what a dispatch of the target's size is charged for and cuts
        # THERE, where that program is full on every device it runs on,
        # or where the backlog ends (`segment_cut`).  The target never
        # passes SYNC_CHUNK_MAX and the charge grows with the rows, so a
        # segment is at most what the verifier charges for SYNC_CHUNK_MAX
        # rows: its program, times its mesh.  Follow mode (`up_to == 0`)
        # and a verifier that does not answer cut at the target, as does
        # in effect one that charges the target for itself (the default
        # buckets on one device, the host tier).
        chunk_target = SYNC_CHUNK
        rows_charged = getattr(self.verifier, "rows_charged", None)
        address = getattr(peer, "address", "") or str(peer)
        self._current_peer = address
        self._backlog = max(0, req.up_to - last.round) if req.up_to else 0

        pipe = _CatchupPipeline(self, req.up_to)
        pipe.start()

        fetch_acc = 0.0            # wire-wait seconds since the last flush
        messages = 0               # stream items taken off the wire
        # `sync.fetch`, one span a segment's fill: from the first wait
        # after the last flush to the end of the last wait before this
        # one, both readings the loop takes anyway
        fill_began: float | None = None
        fill_ended = 0.0
        # what the network layer has counted on the root so far
        # (`net/client.py:sync_chain`; a fake network counts nothing)
        filed = {"recv_s": 0.0, "decode_s": 0.0, "bytes": 0}
        filed_messages = 0

        def segment_cut() -> tuple[int, str]:
            """(rounds, why): the buffered run is flushed at `rounds`,
            as a segment whose `cut` is `why`."""
            if (rows_charged is None or not req.up_to
                    # the backlog ends inside the target: the stream's
                    # end cuts first, whatever the verifier would say
                    or req.up_to - anchor_round <= chunk_target):
                return chunk_target, "target"
            return rows_charged(chunk_target), "full"

        async def flush(cut: str) -> None:
            """Hand the buffered run to the pipeline as a segment ended
            by `cut`; advance the anchor."""
            nonlocal anchor_round, anchor_sig, buffered, fetch_acc
            nonlocal fill_began, filed_messages
            if not buffer:
                return
            seg = list(buffer)
            buffer.clear()
            n, buffered = buffered, 0
            _observe_stage("fetch", fetch_acc)
            counted = {k: root.attrs.get(k, 0) for k in filed}
            tracing.record_span(
                "sync.fetch", fill_began, fill_ended, parent=root,
                messages=messages - filed_messages, rounds=n,
                wait_s=fetch_acc,
                **{k: counted[k] - filed[k] for k in filed})
            filed.update(counted)
            filed_messages = messages
            fill_began = None
            fetch_acc = 0.0
            from drand_tpu.chaos import failpoints as chaos
            # an injected error aborts this peer try before the device
            # dispatch; the peer loop / a later queued request retries
            last_r = _item_span(seg[-1])[1]
            await chaos.failpoint("sync.segment",
                                  owner=getattr(self.store, "owner", ""),
                                  round=last_r, batch=n)
            sig = anchor_sig
            anchor_round, anchor_sig = last_r, _item_tail_sig(seg[-1])
            await pipe.submit(seg, sig, n, cut)

        gen = self.net.sync_chain(peer, from_round)
        stream = gen.__aiter__()
        idle_s = 0.5
        # Stall detection (sync_manager.go:52-56,152-158): a follow stream
        # that delivers nothing for STALL_FACTOR * period is dead — e.g.
        # the serving node's engine was swapped by a reshare and its live
        # callback died while the RPC stayed open.  Return so the peer
        # loop / queued requests can renew against a live engine; idling
        # forever here wedges every later sync request behind this one.
        stall_at = self.clock.now() + STALL_FACTOR * self.group.period
        # NOTE: the idle timeout must NOT cancel the pending __anext__ —
        # asyncio.wait_for would, and cancelling a gRPC stream's __anext__
        # cancels the RPC itself, killing the live-follow tail on the
        # first idle moment.  Keep one pending read across idle windows.
        pending: asyncio.Future | None = None
        ended_by = "stream_end"    # what cuts the run left when the loop ends
        end = "ended_short"        # how the try ended (`_try_node`)
        try:
            while not pipe.broken:
                cut_at, cut = segment_cut()
                self._chunk_target = cut_at
                if pending is None:
                    pending = asyncio.ensure_future(stream.__anext__())
                t0 = time.perf_counter()
                if fill_began is None:
                    fill_began = t0
                done, _ = await asyncio.wait({pending}, timeout=idle_s)
                fill_ended = time.perf_counter()
                dt = fill_ended - t0
                self.stats["fetch_s"] += dt
                fetch_acc += dt
                if not done:
                    # stream idles at the chain head (follow mode): flush
                    # the partial buffer so progress lands instead of
                    # waiting for a full chunk that may never arrive, and
                    # drop back to the low-latency chunk size
                    chunk_target = SYNC_CHUNK
                    await flush("idle")
                    if self.clock.now() >= stall_at:
                        log.debug("sync stream from %s stalled (%dx period"
                                  " idle); renewing",
                                  getattr(peer, "address", peer), STALL_FACTOR)
                        end = "stalled"
                        break
                    continue
                try:
                    item = pending.result()
                except StopAsyncIteration:
                    pending = None
                    break
                except Exception:
                    end = "dropped" if messages else "unreachable"
                    raise
                pending = None
                messages += 1
                if messages == 1:
                    self._arrived(address, fill_ended)
                stall_at = self.clock.now() + STALL_FACTOR * self.group.period
                first_r, last_r, n = _item_span(item)
                self.stats["rounds_fetched"] += n
                self.stats["rounds_refetched"] += max(
                    0, min(last_r, self._wire_high) - first_r + 1)
                self._wire_high = max(self._wire_high, last_r)
                expected = (_item_span(buffer[-1])[1] + 1 if buffer
                            else anchor_round + 1)
                if first_r != expected:
                    # out-of-order stream: flush what we have; if the item
                    # does not restart exactly past the (optimistic)
                    # anchor, give up on this peer
                    await flush("out_of_order")
                    if first_r != anchor_round + 1:
                        break
                if req.up_to:
                    self._backlog = max(0, req.up_to - anchor_round
                                        - buffered)
                buffer.append(item)
                buffered += n
                if req.up_to and last_r >= req.up_to:
                    if isinstance(item, PackedBeacons) \
                            and last_r > req.up_to:
                        # never pass rounds beyond the requested target
                        # to the store, however the server chunked them
                        buffer[-1] = item.truncate(req.up_to)
                        buffered -= last_r - req.up_to
                    ended_by = "backlog_end"
                    break
                if buffered >= cut_at:
                    await flush(cut)
                    # the stream kept a full chunk buffered without
                    # idling: deep backlog — grow toward the big bucket
                    chunk_target = min(chunk_target * SYNC_CHUNK_GROWTH,
                                       SYNC_CHUNK_MAX)
            if not pipe.broken:
                await flush(ended_by)
                if ended_by == "backlog_end":
                    end = "done"
        except BaseException:
            if end not in ("dropped", "unreachable"):
                end = "error"       # not the stream's: a failpoint, a cancel
            raise
        finally:
            # A mid-stream exception (peer drop, RPC error) must not
            # discard in-flight segments: they were dispatched against a
            # data anchor and are safe to commit, and the pre-pipelining
            # loop would have committed them before reading further.
            # close() drains the pack and settle stages to completion.
            root.set(messages=messages)
            if pending is not None:
                pending.cancel()
            try:
                await pipe.close()
            except Exception:
                log.exception("draining catch-up pipeline failed")
            self._current_peer = ""
            self._backlog = 0
            aclose = getattr(gen, "aclose", None)
            if aclose is not None:
                try:
                    await aclose()
                except Exception:
                    pass
            if pipe.error is not None:
                end = "error"
            elif pipe.failure:
                end = "verify_failed"
            elif not req.up_to and end == "ended_short" and pipe.got_any:
                end = "done"        # follow mode asks for no height
            self._try_end = end
        if pipe.error is not None:
            raise pipe.error
        if pipe.failure:
            return False
        return pipe.got_any

    def _repair_store(self):
        """Where repaired beacons are overwritten: the EXPLICIT insecure
        store (no append-only decorator — the reference passes the same
        pair, sync_manager.go:234-265).  Constructions that predate the
        parameter fall back to unwrapping the decorator stack (the
        pre-round-4 behavior) rather than writing through an append-only
        decorator, which would raise and silently abort the repair."""
        if self.insecure_store is not None:
            return self.insecure_store
        base = self.store
        if hasattr(base, "inner"):
            log.warning("correct_past_beacons: no insecure_store passed; "
                        "falling back to decorator unwrapping")
            while hasattr(base, "inner"):
                base = base.inner
        return base

    # -- local validation & repair (sync_manager.go:171-265) ----------------

    def _scannable(self):
        """The store the check reads: the insecure one, whose `raw_rows`
        show damaged rows instead of dying on them."""
        store = self._repair_store()
        return store if hasattr(store, "raw_rows") else _RawRows(store)

    async def _scan(self, up_to: int | None, on_progress=None):
        target = up_to or 0
        if on_progress is not None and not target:
            with contextlib.suppress(StoreError):   # empty, or a torn tip
                target = self.store.last().round
        return await recovery.scan_store(
            self._scannable(), self.verifier, beacon_id=self.beacon_id,
            up_to=up_to or None,
            on_progress=None if on_progress is None
            else lambda current: on_progress(current, max(target, current)))

    def check_past_beacons(self, up_to: int | None = None,
                           on_progress=None) -> list[int]:
        """Verify the whole local chain; returns the damaged rounds.  For
        callers outside an event loop: the check is `check_chain`'s, the
        start-up scan's one scanner (`recovery.scan_store`), and what it
        files under `missing` is not in a bare list of rounds."""
        return asyncio.run(self._scan(up_to, on_progress)).damaged_rounds

    async def correct_past_beacons(self, faulty: list[int]) -> int:
        """Re-fetch the given rounds from peers and overwrite them
        (sync_manager.go:234-265); how many were mended.  The rounds
        before and after each run of them are the caller's word: they
        anchor the replacements."""
        return len((await self._mend(faulty))["fixed"])

    async def check_chain(self, up_to: int | None = None,
                          on_progress=None) -> "CheckResult":
        """`drand util check`: scan the stored chain (to `up_to`, where
        one is given) and mend in place what the scan files.

        The check is `recovery.scan_store` over the insecure store; its
        `on_progress(current, target)` is the scan's.  To mend are the
        rounds of the report's four lists, every missing round among
        them.  They are fetched in contiguous runs (`_fetch_runs`), all
        replacements of a check are verified in as few dispatches as the
        verifier's `rows_charged` allows, each over the signature the
        CONSUMER holds before it (the stored signature of the sound row
        before a run, inside a run the replacement before: a served
        `previous_sig` is never an input), and those that verified, and
        only those, are written in one transaction through the insecure
        store.  A replacement that fails, or a run's last that the sound
        row after it does not link to, leaves its row as it was; what one
        peer could not mend the next is asked for, and what no peer
        could is `unfixed`.  Nothing is deleted, and a row the scan
        found sound is never written.

        One trace a check: the root `check.chain` (scanned, flagged,
        runs, streams, fixed, unfixed; the event loop's lag while it is
        open) over the scan's `store.scan` tree, then for every peer
        asked `check.fetch` (runs, streams, rounds, messages, bytes,
        `wait_s`, and the network layer's `recv_s`, `decode_s`; the
        peer's `sync.serve` joins it through the request's metadata),
        `check.edges` (the stored rows around the runs), `check.verify_wait`
        over the verifier's `verify.dispatch` and `verify.resolve`, and
        `check.overwrite` (rows; `encode_s`, `insert_s`, `statements`,
        `flush_s` from `SqliteStore.put_many`)."""
        with tracing.span("check.chain", beacon_id=self.beacon_id,
                          up_to=up_to or 0) as root, \
                tracing.loop_watched(root):
            report = await self._scan(up_to, on_progress)
            want = report.damaged_rounds
            for first, last in report.missing:
                want.extend(range(first, last + 1))
            mended = await self._mend(want)
            result = CheckResult(report=report, **mended)
            root.set(scanned=report.scanned, flagged=len(want),
                     runs=result.runs, streams=result.streams,
                     fixed=len(result.fixed), unfixed=len(result.unfixed))
        if result.unfixed:
            log.warning("check chain: %d rounds flagged, %d left unmended "
                        "(first %s)", len(want), len(result.unfixed),
                        result.unfixed[:8])
        elif want:
            log.info("check chain: %d rounds flagged, all mended", len(want))
        return result

    async def _mend(self, rounds) -> dict:
        """Mend `rounds` in place, peer by peer -> what `CheckResult`
        says of the repair."""
        want = sorted(set(rounds))
        out = {"fixed": [], "unfixed": want, "runs": len(_runs(want)),
               "streams": 0, "dispatches": 0}
        peers = list(self.nodes)
        random.shuffle(peers)
        for peer in peers:
            if not out["unfixed"]:
                break
            runs = _runs(out["unfixed"])
            got: dict[int, bytes] = {}
            with tracing.span(
                    "check.fetch", runs=len(runs), rounds=len(out["unfixed"]),
                    peer=getattr(peer, "address", "") or str(peer)) as sp:
                try:
                    await self._fetch_runs(peer, runs, got, sp)
                except Exception as exc:
                    # what came before the failure is still verified
                    log.warning(
                        "check chain: peer %s failed after %d of %d rounds "
                        "(runs %s...): %s", sp.attrs["peer"], len(got),
                        len(out["unfixed"]), runs[:4], exc)
                    sp.set(error=f"{type(exc).__name__}: {exc}"[:200])
                sp.set(wall_s=time.perf_counter() - sp.start_mono)
                out["streams"] += sp.attrs.get("streams", 0)
            if not got:
                continue
            fixed, dispatches = await self._overwrite_verified(runs, got)
            out["dispatches"] += dispatches
            out["fixed"] = sorted(out["fixed"] + fixed)
            out["unfixed"] = sorted(set(out["unfixed"]) - set(fixed))
        return out

    async def _fetch_runs(self, peer, runs, got: dict, sp) -> None:
        """The peer's signatures of the rounds of `runs` (ascending
        (first, last)) into `got`.  A stream is opened at a run's first
        round and closed when the run is in hand, unless the next run
        begins within REPAIR_STREAM_REACH rounds of what the stream has
        delivered: then the stream is read on to it (a message in hand
        is cheaper than a stream opened).  The counters go on `sp`."""
        sp.set(streams=0, messages=0, wait_s=0.0)
        i = 0
        while i < len(runs):
            gen = self.net.sync_chain(peer, runs[i][0])
            sp.add(streams=1)
            delivered = runs[i][0] - 1
            try:
                stream = gen.__aiter__()
                while i < len(runs) \
                        and runs[i][0] <= delivered + 1 + REPAIR_STREAM_REACH:
                    t0 = time.perf_counter()
                    try:
                        item = await stream.__anext__()
                    except StopAsyncIteration:
                        # the peer's chain ends here: it has no more of
                        # this run, nor of any after it
                        return
                    finally:
                        sp.add(wait_s=time.perf_counter() - t0)
                    sp.add(messages=1)
                    first, last, _n = _item_span(item)
                    if last <= delivered:
                        raise StoreError(
                            f"the stream went back to round {first}")
                    delivered = last
                    while i < len(runs) and runs[i][0] <= last:
                        lo, hi = max(runs[i][0], first), min(runs[i][1], last)
                        if isinstance(item, PackedBeacons):
                            for r in range(lo, hi + 1):
                                got[r] = item.sigs[r - first].tobytes()
                        elif lo <= hi:
                            got[item.round] = item.signature
                        if runs[i][1] > last:
                            break       # the rest is in a later message
                        i += 1
            finally:
                await gen.aclose()

    def _edges(self, runs) -> dict:
        """{run: (the stored signature before it or None, the stored
        `previous_sig` after it or None)}: worker thread."""
        store = self._scannable()

        def fields(round_: int):
            rows = store.raw_rows(round_, 1)
            if not rows or rows[0][0] != round_:
                return None
            try:
                return row_codec.decode_fields(rows[0][1])
            except row_codec.CodecError:
                return None

        out = {}
        for first, last in runs:
            before, after = fields(first - 1), fields(last + 1)
            out[first, last] = (before and before[1], after and after[2])
        return out

    async def _overwrite_verified(self, runs, got: dict):
        """Verify the replacements in hand and overwrite what verified
        -> (the rounds mended, the dispatches it took)."""
        chained = not self.verifier.scheme.decouple_prev_sig
        with tracing.span("check.edges", runs=len(runs)):
            edges = await asyncio.to_thread(self._edges, runs)
        beacons: list[Beacon] = []
        unlinked_last = set()      # a run's last that the row after denies
        for run in runs:
            first, last = run
            before, after = edges[run]
            if first == 1 and before is None:
                before = getattr(self.group, "genesis_seed", None)
            prev = before
            for r in range(first, last + 1):
                sig = got.get(r)
                if sig is None or (chained and prev is None):
                    # nothing to verify it over: the rest of the run waits
                    # for a peer that serves the round before it
                    break
                beacons.append(Beacon(round=r, signature=sig,
                                      previous_sig=prev if chained else b""))
                prev = sig
            else:
                if chained and after and after != prev:
                    unlinked_last.add(last)
        if not beacons:
            return [], 0
        cap = min(len(beacons), SYNC_CHUNK_MAX)
        rows_charged = getattr(self.verifier, "rows_charged", None)
        if rows_charged is not None:
            cap = max(cap, rows_charged(cap))
        with tracing.span("check.verify_wait", rows=len(beacons)) as sp:
            batches = [beacons[i:i + cap]
                       for i in range(0, len(beacons), cap)]
            ok = await recovery._in_worker(self._verify_batches, batches)
            sp.set(dispatches=len(batches),
                   wall_s=time.perf_counter() - sp.start_mono)
        verified = [b for b, fine in zip(beacons, ok) if fine]
        good = [b for b in verified if b.round not in unlinked_last]
        denied = [b.round for b in verified if b.round in unlinked_last]
        if denied:
            log.warning("check chain: replacements of %s verified but the "
                        "stored rows after them do not link to them; left "
                        "as they were", denied)
        if good:
            with tracing.span("check.overwrite", rows=len(good)) as sp, \
                    _collector_paused():
                await asyncio.to_thread(self._repair_store().put_many, good)
                sp.set(wall_s=time.perf_counter() - sp.start_mono)
        return [b.round for b in good], len(batches)

    def _verify_batches(self, batches) -> list[bool]:
        """Worker thread: every batch dispatched, then every one
        awaited."""
        resolvers = [recovery.dispatch_rows(self.verifier, batch)
                     for batch in batches]
        return [bool(v) for resolve in resolvers for v in resolve()]


# a repair's stream is read on to the next run where that begins within
# this many rounds of what it has delivered, two wire messages: opening a
# stream costs about as much as the peer's read and send of a message
# (PERF.md, PR 41)
REPAIR_STREAM_REACH = 1024


def _runs(rounds) -> list[tuple[int, int]]:
    """(first, last) of the contiguous runs of ascending `rounds`."""
    out: list[tuple[int, int]] = []
    for r in rounds:
        if out and r == out[-1][1] + 1:
            out[-1] = (out[-1][0], r)
        else:
            out.append((r, r))
    return out


class _RawRows:
    """A store that has no `raw_rows` (an in-memory fake) as the scan
    reads one: its beacons, encoded as sqlite would hold them."""

    path = ""

    def __init__(self, store):
        self._store = store
        self._encode = row_codec.make_encoder(None)

    def raw_rows(self, start_round: int, limit: int):
        return [(b.round, self._encode(b)) for b in itertools.islice(
            self._store.iter_range(start_round), limit)]


@dataclass
class CheckResult:
    """What `SyncManager.check_chain` found and what it did about it."""

    report: "recovery.IntegrityReport"
    fixed: list[int] = field(default_factory=list)      # rounds mended
    unfixed: list[int] = field(default_factory=list)    # flagged, left
    runs: int = 0           # contiguous runs of the flagged rounds
    streams: int = 0        # SyncChain streams opened for them
    dispatches: int = 0     # verifier dispatches of the replacements

    @property
    def scanned(self) -> int:
        return self.report.scanned

    @property
    def flagged(self) -> int:
        return len(self.fixed) + len(self.unfixed)

    def counts(self) -> dict[str, int]:
        """What `util check` prints, in its order."""
        return {"scanned": self.scanned, "flagged": self.flagged,
                "fixed": len(self.fixed), "unfixed": len(self.unfixed)}

    def to_dict(self) -> dict:
        rep = self.report.to_dict()
        return {"scanned": self.scanned, "flagged": self.flagged,
                **{k: rep[k] for k in ("corrupt", "unlinked", "bad_sigs",
                                       "missing", "tip_round")},
                "fixed": list(self.fixed), "unfixed": list(self.unfixed),
                "runs": self.runs, "streams": self.streams,
                "dispatches": self.dispatches}


def _payload_bytes(item) -> int:
    if isinstance(item, PackedBeacons):
        return item.sigs.nbytes + len(item.first_prev)
    return len(item.signature) + len(item.previous_sig)


async def serve_sync_chain(store, from_round: int, live_queue=None,
                           chunk_size: int = 0):
    """Server side: cursor-walk from the requested round, then attach to
    live callbacks (SyncChain, sync_manager.go:455-525).  Async generator
    the network layer streams out.

    chunk_size > 0 (a chunk-capable client) serves the stored backlog as
    PackedBeacons built straight from raw store rows — `read_fields`
    batches in a worker thread, so a deep catch-up never materializes
    per-round Beacon objects on the serve side and never blocks the
    event loop on sqlite.  Stores without `read_fields` (in-memory
    fakes) and the live tail fall back to per-beacon items, which the
    wire layer sends as plain BeaconPackets — the transparent-fallback
    half of the capability negotiation.

    One span a served backlog, `sync.serve` (under the RPC's server
    span, and so under the consumer's `sync.catchup` where both ends
    share a process), never one a message: every second of it goes to
    one of three counters, `read_s` (the awaited reads: thread hop,
    sqlite, row decode; `read_thread_s` is the same reads timed inside
    the worker, so the hop is the difference), `pack_s` (`pack_rows`)
    and `send_s` (suspended at `yield`: the packet's conversion and
    serialisation, the transport's write and its flow control), beside
    `messages`, `rows` and `bytes` (the items' payload).  It ends
    `closed` where the client closed the stream, which is how every
    bounded catch-up ends, `error` only on an exception of this side's
    own, and before the live tail begins."""
    last_sent = from_round - 1
    reader = getattr(store, "read_fields", None) if chunk_size > 0 else None
    sp = tracing.begin_span("sync.serve", from_round=from_round,
                            chunk_size=chunk_size)
    n = {"read_s": 0.0, "pack_s": 0.0, "send_s": 0.0, "read_thread_s": 0.0,
         "messages": 0, "rows": 0, "bytes": 0}
    lapped, part = sp.start_mono, "read_s"   # the last reading; the part since

    def lap(then: str) -> None:
        nonlocal lapped, part
        now = time.perf_counter()
        n[part] += now - lapped
        lapped, part = now, then

    def sending(item):
        """An item on its way out: what ran since the last lap made it."""
        lap("send_s")
        n["messages"] += 1
        n["bytes"] += _payload_bytes(item)
        return item

    def read(start: int, limit: int):
        """Worker thread."""
        t0 = time.perf_counter()
        rows = reader(start, limit)
        n["read_thread_s"] += time.perf_counter() - t0
        return rows

    async def read_rows(start: int, limit: int):
        try:
            rows = await asyncio.to_thread(read, start, limit)
        finally:
            lap("pack_s")
        n["rows"] += len(rows)
        return rows

    status = "ok"
    try:
        if reader is not None:
            next_round = from_round
            while True:
                try:
                    rows = await read_rows(next_round, chunk_size)
                except StoreError as exc:
                    # A damaged row on OUR disk must not error the stream:
                    # the CorruptRowError carries the offending round, so
                    # re-read the good prefix below it, serve that, and end
                    # the stream cleanly.  A bounded request of the client's
                    # then goes on to its next peer for the rest
                    # (`SyncManager.sync`: the try ends `ended_short`);
                    # a follower at the head commits the prefix and asks
                    # again on its next tick.  The startup scan / fsck
                    # deals with the damage here.
                    bad = getattr(exc, "round", None)
                    rows = []
                    if bad is not None and bad > next_round:
                        lap("read_s")
                        try:
                            rows = await read_rows(next_round,
                                                   bad - next_round)
                        except StoreError:
                            rows = []
                    log.warning("serve: corrupt row at round %s; ending "
                                "stream after last good round", bad)
                    for item in pack_rows(rows, max_chunk=chunk_size):
                        yield sending(item)
                        lap("pack_s")
                    return
                if not rows:
                    break
                for item in pack_rows(rows, max_chunk=chunk_size):
                    if isinstance(item, PackedBeacons):
                        last_sent = item.end_round
                    else:
                        last_sent = item.round
                    yield sending(item)
                    lap("pack_s")
                lap("read_s")
                next_round = rows[-1][0] + 1
        else:
            try:
                # the store's iterator is the read, on the event loop
                for beacon in store.iter_range(from_round):
                    last_sent = beacon.round
                    n["rows"] += 1
                    yield sending(beacon)
                    lap("read_s")
            except StoreError as exc:
                log.warning("serve: store error mid-stream (%s); ending "
                            "stream at round %d", exc, last_sent)
                return
    except tracing.STREAM_CLOSED:
        status = "closed"
        raise
    except BaseException:
        status = "error"
        raise
    finally:
        lap(part)
        sp.set(**n)
        sp.end(status, at=lapped)
    if live_queue is not None:
        while True:
            beacon = await live_queue.get()
            if beacon.round > last_sent:
                last_sent = beacon.round
                yield beacon

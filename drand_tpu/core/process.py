"""Per-beacon process: one chain's full state and engine.

Counterpart of `core/drand_beacon.go`: keypair + group + share loading
(`Load()`, :106-149), store/handler/sync wiring (`newBeacon`, :220-233,
292-335), DKG result harvesting (`WaitDKG`, :154-216) and reshare
transitions (`transition`, :243-279).
"""

from __future__ import annotations

import asyncio
import os

from drand_tpu import log as dlog
from drand_tpu.beacon.chain import ChainStore, PartialPacket
from drand_tpu.beacon.node import Handler, HandlerConfig
from drand_tpu.beacon.sync_manager import SyncManager, serve_sync_chain
from drand_tpu.chain.scheme import scheme_by_id
from drand_tpu.chain.store import new_chain_store
from drand_tpu.chain.verify import ChainVerifier
from drand_tpu.key.store import FileStore
from drand_tpu.net.client import GrpcBeaconNetwork, PeerClients

log = dlog.get("core")

# Startup integrity scan (ISSUE 15): "1" (default) = full scan, BLS
# through the batched verifier; "structural" = decode/contiguity/linkage
# only; "0"/"off" = skip entirely (bench stores, throwaway nets).
SCAN_ENV = "DRAND_TPU_STARTUP_SCAN"

# Opt-in objectsync publishing (ISSUE 18): a directory path enables a
# per-beacon ObjectPublisher writing content-addressed segment objects
# under {dir}/{beacon_id}/ (serve it statically / rsync it to a bucket).
# SEGMENT overrides the sealed-segment size (default 16384).
OBJECTSYNC_DIR_ENV = "DRAND_TPU_OBJECTSYNC_DIR"
OBJECTSYNC_SEGMENT_ENV = "DRAND_TPU_OBJECTSYNC_SEGMENT"


def objectsync_settings(config) -> tuple[str, int]:
    """Resolve the objectsync opt-in (publisher root dir, segment size).
    Precedence: env var > Config field (which itself folds in
    {folder}/daemon.toml via Config.apply_daemon_toml) > disabled.
    Both orders are pinned by tests/test_objectsync.py."""
    root = os.environ.get(OBJECTSYNC_DIR_ENV, "") or \
        str(getattr(config, "objectsync_dir", "") or "")
    seg = int(os.environ.get(OBJECTSYNC_SEGMENT_ENV, "0") or 0) or \
        int(getattr(config, "objectsync_segment", 0) or 0)
    return root, seg


class BeaconProcess:
    """One beacon chain inside the daemon (core/drand_beacon.go:28-77)."""

    def __init__(self, beacon_id: str, config, key_store: FileStore,
                 peers: PeerClients | None = None, network=None,
                 resilience=None):
        from drand_tpu.resilience import Resilience
        self.beacon_id = beacon_id
        self.config = config
        self.key_store = key_store
        self.peers = peers or PeerClients()
        # per-daemon resilience hub (retry policy + per-peer breakers on
        # the injected clock); standalone processes build their own
        self.resilience = resilience or Resilience(clock=config.clock)
        self.network = network or GrpcBeaconNetwork(
            self.peers, beacon_id, resilience=self.resilience)
        self.keypair = None
        self.group = None
        self.share = None
        self.verifier: ChainVerifier | None = None
        self.chain_store: ChainStore | None = None
        self.handler: Handler | None = None
        self.sync_manager: SyncManager | None = None
        self._store = None
        self.response_cache = None    # built with the engine (ISSUE 14)
        self.object_publisher = None  # owner: lifecycle (start/teardown caller); opt-in objectsync tier (ISSUE 18)
        self.health_sink = None       # daemon's health.Watchdog (SLO feed)
        self._live_queues: list[asyncio.Queue] = []
        self.integrity_report = None  # owner: startup task (last scan IntegrityReport)
        self._pending_repair = None   # (from_round, up_to) re-sync after heal
        self._started = False  # owner: lifecycle (start/stop/transition caller)
        self._engine_closed = False
        self._swap_task: asyncio.Task | None = None
        # DKG state (populated by core.dkg while a ceremony runs)
        self.setup_manager = None     # leader-side collector
        self.setup_receiver = None    # follower-side group waiter
        self.dkg_board = None         # echo-broadcast board
        self.dkg_status = None        # CeremonyStatus: outlives the board
                                      # for /debug/dkg post-mortems
        # fires (bp) after a reshare swapped group state in — the daemon
        # wires its chains_version bump here so hash-addressed routing
        # caches refresh even though the chain hash itself is unchanged
        self.on_group_transition = None

    # -- state loading (core/drand_beacon.go:106-149) -----------------------

    def load_keypair(self):
        self.keypair = self.key_store.load_key_pair()
        return self.keypair

    def load(self) -> bool:
        """Restore group + share from disk; returns True when this process
        can serve its chain."""
        self.load_keypair()
        if not self.key_store.has_group():
            return False
        self.group = self.key_store.load_group()
        if self.key_store.has_share():
            self.share = self.key_store.load_share()
        self._build_engine()
        return True

    def set_group(self, group, share) -> None:
        """Install a fresh DKG result (WaitDKG harvest, :154-216)."""
        self.group = group
        self.share = share
        self.key_store.save_group(group)
        if share is not None:
            self.key_store.save_share(share)
        self._build_engine()

    # -- engine wiring (newBeacon, :292-335) --------------------------------

    def db_path(self) -> str:
        folder = os.path.join(self.config.multibeacon_folder, self.beacon_id,
                              "db")
        os.makedirs(folder, mode=0o700, exist_ok=True)
        return os.path.join(folder, "drand.db")

    def _build_engine(self) -> None:
        self._engine_closed = False
        group = self.group
        self.verifier = ChainVerifier(scheme_by_id(group.scheme_id),
                                      group.public_key.key_bytes(),
                                      beacon_id=self.beacon_id)
        from drand_tpu import metrics as M
        own_addr = self.keypair.public.address if self.keypair else ""
        # chaos identity: the network's `src` and the store's `owner`
        # carry this node's address so seeded faults can target one node
        # of an in-process multi-node net
        self.network.local_addr = own_addr
        self._store = new_chain_store(
            self.db_path(), group, clock=self.config.clock.now,
            on_latency=self._note_latency,
            on_segment=lambda n: M.SYNC_ROUNDS_COMMITTED.labels(
                self.beacon_id).inc(n),
            beacon_id=self.beacon_id, owner=own_addr)
        # encode-once serve fast lane (ISSUE 14): the response cache
        # encodes each committed beacon ONCE, on the committing thread,
        # so the HTTP hot path serves memory bytes with zero store reads.
        # Registered FIRST among the tail callbacks: the cache must be
        # fresh before any watch wake-up marshals a long-poll back onto
        # the loop to read it.
        from drand_tpu.http.response_cache import ResponseCache
        self.response_cache = ResponseCache()
        if hasattr(self._store, "add_tail_callback"):
            self._store.add_tail_callback("serve-cache",
                                          self.response_cache.note_beacon)
        # seed genesis so sync/serve paths have an anchor from the start
        # (reference NewHandler inserts it, chain/beacon/node.go:63-96)
        from drand_tpu.chain.beacon import genesis_beacon
        from drand_tpu.chain.store import BeaconNotFound, StoreError
        try:
            self._store.last()
        except BeaconNotFound:
            self._store.put(genesis_beacon(group.get_genesis_seed()))
        except StoreError:
            # damaged tip row: the store is non-empty (no genesis to
            # seed) and the startup scan quarantines it right after this
            pass
        # warm the cache from the stored tip (restart path: the tail
        # callback only sees commits made after registration)
        try:
            self.response_cache.note_beacon(self._store.last())
        except Exception:
            pass
        self._store.add_callback("live-streams", self._fanout_live)
        self.chain_store = ChainStore(self._store, group, self.share,
                                      self.verifier,
                                      on_beacon=self._on_new_beacon)
        # reshare-in-place (update_group) invalidates the pre-encoded
        # bodies alongside the signer-table epoch bump
        self.chain_store.on_group_update = self.response_cache.invalidate
        conf = HandlerConfig(group=group, share=self.share,
                             public_identity=self.keypair.public,
                             clock=self.config.clock)
        self.handler = Handler(conf, self.chain_store, self.network,
                               self.verifier)
        others = [n for n in group.nodes
                  if n.address != self.keypair.public.address]
        self.sync_manager = SyncManager(
            self._store, group, self.verifier, self.network, others,
            self.config.clock,
            insecure_store=getattr(self._store, "insecure", None),
            resilience=self.resilience, beacon_id=self.beacon_id)
        self.handler.on_sync_needed = self.sync_manager.request_sync

    def _note_latency(self, round_: int, latency_ms: float) -> None:
        """Per-commit lateness: the shared gauges/histogram, plus this
        daemon's SLO tracker (health/slo.py) when a watchdog is wired."""
        from drand_tpu import metrics as M
        M.observe_beacon(self.beacon_id, round_, latency_ms)
        sink = self.health_sink
        if sink is not None:
            try:
                sink.note_round(self.beacon_id, round_, latency_ms,
                                self.group)
            except Exception:
                pass              # judging must never block committing

    def _on_new_beacon(self, beacon) -> None:
        if self.config.on_beacon is not None:
            from drand_tpu import tracing
            with tracing.span("beacon.fanout", beacon_id=self.beacon_id,
                              round_=beacon.round):
                try:
                    self.config.on_beacon(self.beacon_id, beacon)
                except Exception:
                    pass

    def _fanout_live(self, beacon) -> None:
        """Runs on the CallbackStore WORKER POOL thread: asyncio queues are
        not thread-safe, so the put must marshal onto each subscriber's
        event loop — a bare put_nowait from here appends to the deque but
        can fail to wake the loop-side `await q.get()`, silently starving
        live SyncChain/PublicRandStream watchers."""
        for q, loop in list(self._live_queues):
            try:
                loop.call_soon_threadsafe(self._offer, q, beacon)
            except RuntimeError:
                pass  # subscriber's loop already closed

    @staticmethod
    def _offer(q, beacon) -> None:
        try:
            q.put_nowait(beacon)
        except asyncio.QueueFull:
            pass

    def subscribe_live(self) -> asyncio.Queue:
        q: asyncio.Queue = asyncio.Queue(maxsize=64)
        self._live_queues.append((q, asyncio.get_running_loop()))
        return q

    def unsubscribe_live(self, q) -> None:
        self._live_queues = [(qq, l) for qq, l in self._live_queues
                             if qq is not q]

    # -- lifecycle (StartBeacon, :220-233) ----------------------------------

    async def start(self, catchup: bool = False) -> None:
        if self._started or self.handler is None:
            return
        if self._engine_closed:
            # a stopped engine closed its store/pool; rebuild like the
            # reference's restart path (Load + StartBeacon)
            self._build_engine()
        self._pending_repair = None
        await self._startup_integrity()
        self._started = True
        self.sync_manager.start()
        await self._start_object_publisher()
        if self._pending_repair is not None:
            # heal the rolled-back suffix from peers through the normal
            # chunked sync wire — repair IS a catch-up sync
            self.sync_manager.request_sync(*self._pending_repair)
        if catchup:
            await self.handler.catchup()
        else:
            await self.handler.start()

    async def _startup_integrity(self) -> None:
        """Boot-time store integrity scan + self-heal (ISSUE 15): stream
        the stored chain through the batched verifier before serving it.
        On damage: quarantine + roll back to the verified prefix, then
        REBUILD the engine — ChainStore cached the old (higher) tip at
        construction, and every cached view must re-read the repaired
        store — and queue a re-sync of the rolled-back range."""
        mode = os.environ.get(SCAN_ENV, "1").lower()
        if mode in ("0", "off", "no"):
            return
        base = getattr(self._store, "insecure", None)
        if base is None:
            return
        if await asyncio.to_thread(len, base) <= 1:
            return                  # empty / genesis-only: nothing to judge
        from drand_tpu.chain import recovery
        verifier = None if mode == "structural" else self.verifier
        report, summary = await recovery.startup_recovery(
            base, verifier, beacon_id=self.beacon_id)
        self.integrity_report = report
        if summary is None:
            return
        old_tip = report.tip_round
        self._teardown_engine()
        self._build_engine()
        self._pending_repair = (report.verified_tip + 1, old_tip)

    async def transition(self, new_group, new_share) -> None:
        """Reshare transition (core/drand_beacon.go:243-279): the OLD
        engine keeps producing (and validating old-group partials) until
        the transition round; the engine swap happens just before the
        boundary (the reference swaps the share via a store callback at
        that round, chain/beacon/node.go:228-247)."""
        import asyncio

        from drand_tpu.chain.time import current_round, time_of_round
        t_round = current_round(new_group.transition_time, new_group.period,
                                new_group.genesis_time)
        t_time = time_of_round(new_group.period, new_group.genesis_time,
                               t_round)
        if self.handler is not None and self._started:
            old_handler = self.handler
            old_sync = self.sync_manager
            old_handler.stop_at(t_round - 1)
            # persist the new state now; swap engines at the boundary
            self.key_store.save_group(new_group)
            if new_share is not None:
                self.key_store.save_share(new_share)

            async def swap():
                await self.config.clock.sleep_until(
                    t_time - new_group.period / 2)
                # old-engine teardown is best-effort: a failing close must
                # not prevent the swap below (a dead swap leaves the node on
                # the old group forever, rejecting every new-group partial).
                # keep_chain: the store, ChainStore, and response cache
                # survive into the new engine — a public read racing the
                # swap must never see a closed store (zero-blip, ISSUE 20)
                try:
                    old_handler.stop(keep_chain=True)
                    if old_sync is not None:
                        old_sync.stop()
                except asyncio.CancelledError:
                    raise
                except Exception:
                    log.exception("%s: old-engine teardown failed",
                                  self.beacon_id)
                # zero-blip path: swap key material + topology in place
                try:
                    self._swap_group_in_place(new_group, new_share)
                    self.sync_manager.start()
                    await self.handler.transition(None)
                    self._note_group_transition()
                    return
                except asyncio.CancelledError:
                    raise
                except Exception:
                    log.exception(
                        "%s: in-place reshare swap failed; rebuilding",
                        self.beacon_id)
                # fallback: full engine rebuild, retried once with the
                # half-built engine torn down first
                for attempt in (0, 1):
                    try:
                        self._teardown_engine()
                        self.set_group(new_group, new_share)
                        self.sync_manager.start()
                        await self.handler.transition(None)
                        self._note_group_transition()
                        return
                    except asyncio.CancelledError:
                        raise
                    except Exception:
                        log.exception(
                            "%s: reshare engine swap failed (attempt %d)",
                            self.beacon_id, attempt)

            # hold a strong reference: the event loop only weakly references
            # pending tasks, and a GC'd swap wedges the node on the old group
            self._swap_task = asyncio.get_running_loop().create_task(swap())
            return
        # fresh joiner: build now; the handler's wait-round gate holds
        # production until the transition while sync fetches the history
        self.set_group(new_group, new_share)
        self.sync_manager.start()
        self.sync_manager.request_sync(1)
        await self.handler.transition(None)
        self._started = True

    def _swap_group_in_place(self, new_group, new_share) -> None:
        """Zero-blip reshare swap (ISSUE 20): the chain continues across
        the transition, so everything chain-scoped survives — the store
        connection, the pre-encoded ResponseCache, and the ChainStore
        with its live aggregation task.  Only key material and the
        group-topology-derived parts (Handler, SyncManager) rebuild.
        The epoch seams fire together inside `chain_store.update_group`:
        the signer-table epoch bump (backend.update_group) and the serve
        cache invalidation (on_group_update); the daemon's
        chains_version bump rides `_note_group_transition` after the new
        handler is live."""
        self.group = new_group
        self.share = new_share
        self.verifier = ChainVerifier(scheme_by_id(new_group.scheme_id),
                                      new_group.public_key.key_bytes(),
                                      beacon_id=self.beacon_id)
        cs = self.chain_store
        cs.share = new_share
        cs.verifier = self.verifier
        cs.update_group(new_group)
        conf = HandlerConfig(group=new_group, share=new_share,
                             public_identity=self.keypair.public,
                             clock=self.config.clock)
        self.handler = Handler(conf, cs, self.network, self.verifier)
        others = [n for n in new_group.nodes
                  if n.address != self.keypair.public.address]
        self.sync_manager = SyncManager(
            self._store, new_group, self.verifier, self.network, others,
            self.config.clock,
            insecure_store=getattr(self._store, "insecure", None),
            resilience=self.resilience, beacon_id=self.beacon_id)
        self.handler.on_sync_needed = self.sync_manager.request_sync

    def _note_group_transition(self) -> None:
        """Tell the daemon a reshare landed (chains_version bump for
        hash-addressed routing caches); never fails the swap."""
        hook = self.on_group_transition
        if hook is not None:
            try:
                hook(self)
            except Exception:
                log.exception("%s: group-transition hook failed",
                              self.beacon_id)

    async def _start_object_publisher(self) -> None:
        """Opt-in objectsync tier (ISSUE 18): when the daemon config (or
        the OBJECTSYNC_DIR_ENV override) names a directory, publish this
        chain as content-addressed segment objects under
        {dir}/{beacon_id}/.  Failure to start is logged, never fatal —
        publishing is an export path, not part of the protocol engine."""
        root, seg = objectsync_settings(self.config)
        if not root or self.object_publisher is not None:
            return
        from drand_tpu.objectsync import (FilesystemBackend, ObjectPublisher,
                                          format as ofmt)
        info = self.group.chain_info()
        pub = ObjectPublisher(
            self._store,
            FilesystemBackend(os.path.join(root, self.beacon_id)),
            chain_hash=info.hash(), scheme_id=self.group.scheme_id,
            segment_rounds=seg or ofmt.DEFAULT_SEGMENT_ROUNDS,
            beacon_id=self.beacon_id)
        try:
            await pub.start()
        except Exception:
            log.exception("%s: objectsync publisher failed to start",
                          self.beacon_id)
            return
        self.object_publisher = pub

    def _teardown_engine(self) -> None:
        """Best-effort stop of a (possibly half-built) engine: handler,
        sync manager, object publisher, store connection + callback
        worker pool."""
        pub, self.object_publisher = self.object_publisher, None
        if pub is not None:
            try:
                pub.cancel()
            except Exception:
                pass
        for part, closer in ((self.handler, "stop"),
                             (self.sync_manager, "stop"),
                             (self._store, "close")):
            if part is not None:
                try:
                    getattr(part, closer)()
                except Exception:
                    pass

    def stop(self) -> None:
        if getattr(self, "_swap_task", None) is not None:
            self._swap_task.cancel()
            self._swap_task = None
        self._teardown_engine()
        self._started = False
        self._engine_closed = True

    # -- service entry points ------------------------------------------------

    async def process_partial(self, round_: int, previous_sig: bytes,
                              partial_sig: bytes) -> None:
        if self.handler is None:
            raise RuntimeError("beacon not running")
        from drand_tpu import tracing
        with tracing.span("partial.receive", beacon_id=self.beacon_id,
                          round_=round_):
            await self.handler.process_partial(PartialPacket(
                round=round_, previous_signature=previous_sig,
                partial_sig=partial_sig, beacon_id=self.beacon_id))

    def sync_chain_source(self, from_round: int, follow: bool = True,
                          chunk_size: int = 0):
        """Async generator serving SyncChain (server side).  chunk_size
        > 0 serves the stored backlog as packed chunks (ISSUE 13); the
        live tail is always per-beacon."""
        live = self.subscribe_live() if follow else None
        return serve_sync_chain(self._store, from_round, live_queue=live,
                                chunk_size=chunk_size)

    def chain_info(self):
        if self.group is None:
            raise RuntimeError("no group")
        return self.group.chain_info()

    def status(self) -> dict:
        st = {"is_running": self._started, "last_round": 0, "length": 0,
              "is_empty": True}
        if self._store is not None:
            try:
                last = self._store.last()
                st.update(last_round=last.round, length=len(self._store),
                          is_empty=False)
            except Exception:
                pass
        return st

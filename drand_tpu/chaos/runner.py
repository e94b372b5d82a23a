"""Seeded chaos scenarios over the in-process multi-node harness.

:class:`ScenarioNet` is the library form of the scenario discipline the
test suite pioneered (tests/test_scenario.py, which now imports it from
here): n full daemons with real gRPC on localhost ports, one shared
:class:`~drand_tpu.beacon.clock.FakeClock` advanced manually — the
reference's ``DrandTestScenario``/``BatchNewDrand``
(core/util_test.go:48-150) plus the clockwork discipline (SURVEY §4).

On top of it, :func:`run_scenario` executes one named, seeded chaos
scenario: arm a deterministic failpoint :class:`Schedule`
(drand_tpu/chaos/failpoints.py), drive the net through the fault window
(including node-level crash/restart actions the inline sites cannot
express), heal, settle, and assert every protocol invariant
(drand_tpu/chaos/invariants.py).  The same entry point backs
``drand-tpu chaos run/replay`` and the tier-1 scenario matrix
(tests/test_chaos_scenarios.py).

Replay contract: node identities are aliased to stable ``node<i>``
labels before decision hashing and logging, so
``run_scenario(name, seed)`` yields the same injection summary across
runs and across machines despite OS-assigned ports.
"""

from __future__ import annotations

import asyncio
import os
import random
import tempfile
from dataclasses import dataclass, field

from drand_tpu import sanitizer
from drand_tpu.beacon.clock import Clock, FakeClock
from drand_tpu.chain.time import current_round
from drand_tpu.chaos import failpoints, faults, invariants
from drand_tpu.resilience import policy as res_policy

PERIOD = 4          # fake seconds per round
DKG_TIMEOUT = 20    # real-seconds backstop; fast-sync path finishes sooner


class TipWaiter:
    """Commit-driven settle: await the stores' tail callbacks instead of
    polling with wall-clock budgets (the flake source VERDICT r5 #5
    called out).  Each commit marshals onto the loop and wakes waiters;
    readers re-check tips on wake, so a wake per COMMIT is enough."""

    def __init__(self, stores, loop=None):
        self.loop = loop or asyncio.get_running_loop()
        self._event = asyncio.Event()
        self._stores = list(stores)
        self._ids: list[tuple[object, str]] = []
        for i, s in enumerate(self._stores):
            cb_id = f"tipwaiter-{id(self):x}-{i}"
            if hasattr(s, "add_tail_callback"):
                s.add_tail_callback(cb_id, self._on_commit)
            else:
                s.add_callback(cb_id, self._on_commit)
            self._ids.append((s, cb_id))

    def _on_commit(self, _beacon) -> None:
        try:
            self.loop.call_soon_threadsafe(self._fire)
        except RuntimeError:
            pass                       # loop closed during teardown

    def _fire(self) -> None:
        ev, self._event = self._event, asyncio.Event()
        ev.set()

    def rounds(self) -> list[int]:
        out = []
        for s in self._stores:
            try:
                out.append(s.last().round)
            except Exception:
                out.append(-1)
        return out

    async def wait_min(self, target: int, timeout: float) -> bool:
        """True once every store's tip >= target; False on timeout.
        Wakes on commits, not on a polling cadence."""
        deadline = self.loop.time() + timeout
        while True:
            ev = self._event       # grab BEFORE reading (no lost wakeup)
            if min(self.rounds()) >= target:
                return True
            remaining = deadline - self.loop.time()
            if remaining <= 0:
                return False
            try:
                await asyncio.wait_for(ev.wait(), remaining)
            except asyncio.TimeoutError:
                return False

    async def wait_commit(self, timeout: float) -> bool:
        """True when ANY store commits within `timeout` (the per-step
        settle for clock-driving loops)."""
        ev = self._event
        try:
            await asyncio.wait_for(ev.wait(), timeout)
            return True
        except asyncio.TimeoutError:
            return False

    def close(self) -> None:
        for s, cb_id in self._ids:
            try:
                s.remove_callback(cb_id)
            except Exception:
                pass


class ScenarioNet:
    """n in-process daemons, real gRPC, one shared fake clock.

    `beacon_ids` grows the net PAST one chain per daemon: each id is a
    full beacon process (own keypair, own DKG, own store) multiplexed
    on the shared daemon runtime — the reference's multibeacon folder
    layout (core/drand_daemon.go:248-275) driven at k>2 scale."""

    def __init__(self, n: int, thr: int, scheme_id: str,
                 clock: Clock | None = None,
                 node_clocks: "dict[int, Clock] | None" = None,
                 beacon_ids=("default",)):
        self.n, self.thr, self.scheme_id = n, thr, scheme_id
        self.beacon_ids = list(beacon_ids)
        self.clock = clock or FakeClock(start=1_700_000_000.0)
        # per-node clock overrides (e.g. a faults.SkewClock over the
        # shared base): the clock-skew fault at the injection seam
        self.node_clocks = dict(node_clocks or {})
        self.daemons: list = []
        self.dirs: list[str] = []
        self.schedule: failpoints.Schedule | None = None

    async def start_daemons(self):
        from drand_tpu.core import Config, DrandDaemon
        from drand_tpu.key.keys import Pair
        from drand_tpu.key.store import FileStore
        for i in range(self.n):
            folder = tempfile.mkdtemp(prefix=f"drand-node{i}-")
            cfg = Config(folder=folder, private_listen="127.0.0.1:0",
                         control_port=0,
                         clock=self.node_clocks.get(i, self.clock),
                         dkg_timeout_s=DKG_TIMEOUT)
            d = DrandDaemon(cfg)
            await d.start()
            addr = d.private_addr()
            for bid in self.beacon_ids:
                ks = FileStore(folder, bid)
                # "default" keeps its pre-multibeacon key seed so seeded
                # single-chain scenarios replay unchanged
                key_seed = f"node{i}" if bid == "default" \
                    else f"node{i}-{bid}"
                ks.save_key_pair(Pair.generate(addr,
                                               seed=key_seed.encode()))
                d.instantiate(bid)
            self.daemons.append(d)
            self.dirs.append(folder)

    async def run_dkg(self, beacon_id: str = "default") -> list:
        from drand_tpu.net.client import make_metadata
        from drand_tpu.protogen import drand_pb2
        secret = f"scenario-secret-{beacon_id}".encode() \
            if beacon_id != "default" else b"scenario-secret"
        leader = self.daemons[0]
        leader_addr = leader.private_addr()

        def init_packet(is_leader):
            info = drand_pb2.SetupInfoPacket(
                leader=is_leader, leader_address=leader_addr,
                nodes=self.n, threshold=self.thr, timeout=DKG_TIMEOUT,
                secret=secret)
            return drand_pb2.InitDKGPacket(
                info=info, beacon_period=PERIOD, catchup_period=1,
                schemeID=self.scheme_id,
                metadata=make_metadata(beacon_id))

        svc = [d._control_service for d in self.daemons]
        tasks = [asyncio.create_task(svc[0].InitDKG(init_packet(True), None))]
        await asyncio.sleep(0.05)
        for s in svc[1:]:
            tasks.append(asyncio.create_task(s.InitDKG(init_packet(False),
                                                       None)))
        groups = await asyncio.wait_for(asyncio.gather(*tasks), 90)
        return groups

    async def run_all_dkgs(self) -> dict:
        """One DKG per beacon id (sequential — the reference's operator
        flow starts beacons one `drand share` at a time on the shared
        daemon); returns {beacon_id: groups}."""
        return {bid: await self.run_dkg(bid) for bid in self.beacon_ids}

    # -- chaos plumbing -----------------------------------------------------

    def process(self, i: int, beacon_id: str = "default"):
        return self.daemons[i].processes[beacon_id]

    def aliases(self) -> dict[str, str]:
        """Ephemeral host:port -> stable node<i> labels (replay contract)."""
        return {d.private_addr(): f"node{i}"
                for i, d in enumerate(self.daemons)}

    def arm(self, seed: int, rules) -> failpoints.Schedule:
        """Build, alias, and arm a seeded schedule over this net.  The
        resilience decision log shares the aliases so retry/breaker
        entries replay with stable node labels too."""
        sched = failpoints.Schedule(seed, rules)
        sched.set_aliases(self.aliases())
        res_policy.LOG.set_aliases(self.aliases())
        failpoints.arm(sched)
        self.schedule = sched
        return sched

    async def wait_for_injections(self, pred, timeout: float = 20.0,
                                  nudge_s: float = 0.5,
                                  max_nudge: float = 0.0) -> bool:
        """Event-driven fault-window closure: poll the armed schedule's
        injection log until ``pred(log)`` holds.  Replay determinism
        needs the SET of injections closed before a drive disarms —
        "advance N rounds and hope everything fired" was the flake
        shape this replaces.  ``max_nudge`` > 0 additionally advances
        the fake clock in ``nudge_s`` steps (bounded, so the nudging
        cannot cross into the next round and mint NEW injections) for
        clock-cadenced traffic such as watchdog pings."""
        loop = asyncio.get_running_loop()
        deadline = loop.time() + timeout
        nudged = 0.0
        while True:
            log = self.schedule.injection_log() if self.schedule else []
            if pred(log):
                return True
            if loop.time() > deadline:
                return False
            if nudged + nudge_s <= max_nudge:
                nudged += nudge_s
                await self.clock.advance(nudge_s)
            await asyncio.sleep(0.05)   # let in-flight RPCs land

    async def drain_retries(self, timeout: float = 30.0) -> None:
        """Advance the fake clock until no retry backoff is sleeping:
        every retry chain runs to its logged conclusion, which keeps the
        decision log deterministic across replays (a chain truncated by
        scenario teardown would log a different tail per run)."""
        loop = asyncio.get_running_loop()
        deadline = loop.time() + timeout
        while res_policy.inflight() and loop.time() < deadline:
            await self.clock.advance(1.0)
            await asyncio.sleep(0.02)   # let woken retries issue their RPC

    def crash(self, i: int) -> None:
        """Kill node i's beacon engine (the orchestrator-style node
        failure, demo/lib/orchestrator.go:530-577)."""
        self.process(i).stop()

    async def restart(self, i: int) -> None:
        """Rejoin node i in catch-up mode and queue a sync request."""
        bp = self.process(i)
        await bp.start(catchup=True)
        bp.sync_manager.request_sync(self.last_rounds()[i] + 1)

    # -- observation / clock driving ---------------------------------------

    def stores(self, beacon_id: str = "default"):
        return [d.processes[beacon_id]._store for d in self.daemons]

    def last_rounds(self, beacon_id: str = "default"):
        out = []
        for s in self.stores(beacon_id):
            try:
                out.append(s.last().round)
            except Exception:
                out.append(-1)
        return out

    def _rounds_of(self, daemons, beacon_id: str = "default"):
        out = []
        for d in daemons:
            try:
                out.append(d.processes[beacon_id]._store.last().round)
            except Exception:
                out.append(-1)
        return out

    async def advance_to_round(self, target: int, timeout: float = 60.0,
                               daemons=None, beacon_id: str = "default"):
        """Advance the fake clock period by period until every (selected)
        daemon's store holds `target`."""
        daemons = daemons if daemons is not None else self.daemons
        group = daemons[0].processes[beacon_id].group
        loop = asyncio.get_running_loop()
        deadline = loop.time() + timeout
        while True:
            rounds = self._rounds_of(daemons, beacon_id)
            if all(r >= target for r in rounds):
                return
            if loop.time() > deadline:
                raise AssertionError(
                    f"timeout waiting for round {target}: {rounds}")
            now = self.clock.now()
            next_time = group.genesis_time if now < group.genesis_time \
                else now + group.period
            await self.clock.set_time(next_time)
            # Crypto runs OFF the event loop (crypto_backend worker thread),
            # so real time keeps flowing while partials verify/aggregate.
            # Wait for this tick's round to land everywhere before advancing
            # again — advancing early would push in-flight partials outside
            # the handler's (current, current+1) round window.
            tick_round = current_round(next_time, group.period,
                                       group.genesis_time)
            settle = loop.time() + 10.0
            while loop.time() < deadline:
                rounds = self._rounds_of(daemons, beacon_id)
                want = min(target, tick_round)
                if all(r >= want for r in rounds):
                    break
                if loop.time() >= settle and any(r >= want for r in rounds):
                    # at least one member landed this tick's round: the
                    # network works; remaining laggards are structurally
                    # behind (e.g. waiting for a future transition round)
                    # and will gap-sync — advance the clock again.  While
                    # NOBODY has landed it (crypto still grinding in the
                    # worker thread under machine load), advancing would
                    # push in-flight partials outside the round window.
                    break
                await asyncio.sleep(0.02)

    async def advance_until(self, target: int, step: float | None = None,
                            timeout: float = 60.0, daemons=None,
                            settle_s: float = 1.0):
        """Advance the fake clock `step` seconds at a time (default: one
        period) until every selected daemon's tip holds `target`,
        settling between steps on store-commit EVENTS rather than fixed
        wall-clock budgets.  The right driver for catchup-cadence
        recovery: step=group.catchup_period walks the fast-forward path
        one commit at a time."""
        daemons = daemons if daemons is not None else self.daemons
        group = daemons[0].processes["default"].group
        step = step if step is not None else group.period
        waiter = TipWaiter(
            [d.processes["default"]._store for d in daemons])
        loop = asyncio.get_running_loop()
        deadline = loop.time() + timeout
        try:
            while min(waiter.rounds()) < target:
                if loop.time() > deadline:
                    raise AssertionError(
                        f"timeout waiting for round {target}: "
                        f"{waiter.rounds()}")
                now = self.clock.now()
                t = group.genesis_time if now < group.genesis_time \
                    else now + step
                await self.clock.set_time(t)
                # commit-driven settle: wake the moment a beacon lands;
                # a short bound covers steps that land nothing (e.g.
                # sub-period steps walking toward the next boundary)
                await waiter.wait_commit(settle_s)
        finally:
            waiter.close()

    async def run_reshare(self, new_n: int, new_thr: int,
                          beacon_id: str = "default",
                          timeout_s: float | None = None) -> list:
        """Reshare the running chain to a resized group (the reference's
        `drand share --transition` flow, tests/test_reshare.py's driving
        pattern made a library helper).  Growing brings up joiner
        daemons (appended to `self.daemons`) that receive the previous
        group file; shrinking keeps only the first `new_n` daemons as
        participants — the tail's dealers go dark and the deal phase
        closes on its timeout.  Returns the participants' InitReshare
        results (leader first)."""
        import os

        from drand_tpu.core import Config, DrandDaemon
        from drand_tpu.key.keys import Pair
        from drand_tpu.key.store import FileStore
        from drand_tpu.net.client import make_metadata
        from drand_tpu.protogen import drand_pb2

        old_group = self.process(0, beacon_id).group
        joiners = []
        while len(self.daemons) < new_n:
            j = len(self.daemons)
            folder = tempfile.mkdtemp(prefix=f"drand-joiner{j}-")
            cfg = Config(folder=folder, private_listen="127.0.0.1:0",
                         control_port=0, clock=self.clock,
                         dkg_timeout_s=DKG_TIMEOUT)
            d = DrandDaemon(cfg)
            await d.start()
            ks = FileStore(folder, beacon_id)
            ks.save_key_pair(Pair.generate(
                d.private_addr(), seed=f"joiner{j}-{beacon_id}".encode()))
            d.instantiate(beacon_id)
            self.daemons.append(d)
            self.dirs.append(folder)
            joiners.append(d)
        participants = self.daemons[:new_n]
        timeout = timeout_s or DKG_TIMEOUT
        secret = b"scenario-reshare-" + beacon_id.encode()
        leader_addr = self.daemons[0].private_addr()
        old_path = ""
        if joiners:
            old_path = os.path.join(self.dirs[-1], "old_group.toml")

            def _write(path=old_path, text=old_group.to_toml()):
                with open(path, "w") as f:
                    f.write(text)
            await asyncio.to_thread(_write)

        def pkt(is_leader, old=""):
            info = drand_pb2.SetupInfoPacket(
                leader=is_leader, leader_address=leader_addr,
                nodes=new_n, threshold=new_thr, timeout=int(timeout),
                secret=secret)
            p = drand_pb2.InitResharePacket(
                info=info, metadata=make_metadata(beacon_id))
            if old:
                p.old.path = old
            return p

        svc = [d._control_service for d in participants]
        tasks = [asyncio.create_task(svc[0].InitReshare(pkt(True), None))]
        await asyncio.sleep(0.05)
        for d, s in zip(participants[1:], svc[1:]):
            tasks.append(asyncio.create_task(s.InitReshare(
                pkt(False, old_path if d in joiners else ""), None)))
        return await asyncio.wait_for(asyncio.gather(*tasks),
                                      timeout * 6 + 120)

    async def stop(self):
        for d in self.daemons:
            try:
                await d.stop()
            except Exception:
                pass


# -- scenario definitions ---------------------------------------------------

@dataclass(frozen=True)
class ScenarioSpec:
    name: str
    doc: str
    drive: object          # async (net, seed, rng) -> expected final round
    slow: bool = False     # excluded from the tier-1 matrix / smoke
    # ceremony scenarios run on chaos/ceremony.CeremonyNet (no daemons,
    # no clock, no chain invariants) with drive signature
    # async (seed, rng, nodes, thr, **kw) -> (CeremonyNet, [invariant])
    ceremony: bool = False


async def _drive_partition_heal(net: ScenarioNet, seed: int,
                                rng: random.Random) -> int:
    """Symmetric partition isolates a seeded victim; the majority keeps
    producing through it; heal; the victim gap-syncs back."""
    victim = rng.randrange(net.n)
    vic = f"node{victim}"
    others = [f"node{i}" for i in range(net.n) if i != victim]
    net.arm(seed, faults.partition([vic], others))
    base = max(net.last_rounds())
    majority = [d for i, d in enumerate(net.daemons) if i != victim]
    await net.advance_to_round(base + 3, daemons=majority)
    if net.last_rounds()[victim] >= base + 3:
        raise AssertionError(
            f"partition had no effect: victim node{victim} kept up "
            f"({net.last_rounds()})")

    # Close the fault window on EVENTS before healing: the victim's
    # gap-triggered sync must have been cut by every donor, and every
    # partitioned pair's watchdog ping must have been dropped.  Those
    # are the injections the seeded schedule deterministically owes;
    # disarming on a round count alone left their arrival racing the
    # disarm (the replay-test flake).
    want_pings = {(d, vic) for d in others} | {(vic, d) for d in others}

    def closed(log) -> bool:
        sync_srcs = {e["src"] for e in log
                     if e["site"] == "net.sync_recv" and e["dst"] == vic}
        pings = {(e["src"], e["dst"]) for e in log
                 if e["site"] == "net.ping"}
        return set(others) <= sync_srcs and want_pings <= pings

    if not await net.wait_for_injections(closed, timeout=20.0,
                                         max_nudge=PERIOD - 1.0):
        raise AssertionError(
            "fault window never closed: "
            f"{net.schedule.injection_summary()}")
    failpoints.disarm()     # heal
    target = base + 4
    await net.advance_to_round(target, timeout=90.0)
    return target


async def _drive_leader_crash(net: ScenarioNet, seed: int,
                              rng: random.Random) -> int:
    """The DKG leader dies mid-round at a seeded height; t-of-n keeps the
    chain alive; the leader rejoins via catch-up sync."""
    crash_at = max(net.last_rounds()) + 1 + rng.randrange(2)
    await net.advance_to_round(crash_at)
    net.crash(0)
    survivors = net.daemons[1:]
    await net.advance_to_round(crash_at + 2, daemons=survivors)
    if net.last_rounds()[0] >= crash_at + 2:
        raise AssertionError("crash had no effect: node0 kept appending")
    await net.restart(0)
    target = crash_at + 3
    await net.advance_to_round(target, timeout=120.0)
    return target


async def _drive_store_errors_catchup(net: ScenarioNet, seed: int,
                                      rng: random.Random) -> int:
    """A node rejoins from downtime onto a failing disk: its first
    catch-up commit attempts raise StoreError; the sync retry path must
    absorb the burst and still close the gap."""
    base = max(net.last_rounds())
    victim = net.n - 1
    net.crash(victim)
    survivors = net.daemons[:victim]
    await net.advance_to_round(base + 2, daemons=survivors)
    burst = 1 + rng.randrange(2)
    net.arm(seed, faults.store_commit_errors(owner=f"node{victim}",
                                             times=burst))
    await net.restart(victim)
    target = base + 3
    await net.advance_to_round(target, timeout=120.0)
    failpoints.disarm()
    if not net.schedule.injection_log():
        raise AssertionError("store-error schedule never fired")
    return target


async def _drive_skewed_node(net: ScenarioNet, seed: int,
                             rng: random.Random) -> int:
    """One node's clock runs ahead of the group (installed at net build
    via faults.SkewClock, below the one-round drift the partial window
    tolerates): rounds must keep flowing and agreeing."""
    target = max(net.last_rounds()) + 4
    await net.advance_to_round(target, timeout=90.0)
    return target


async def _drive_retry_storm(net: ScenarioNet, seed: int,
                             rng: random.Random) -> int:
    """Acceptance (a) for the resilience layer: a seeded (src, dst) pair's
    partial send for one round is dropped a bounded number of times; the
    RetryPolicy's seeded-backoff retries must push it through within the
    round's deadline budget, visible in the decision log as
    retry → retry → success."""
    base = max(net.last_rounds())
    r0 = base + 2
    src = rng.randrange(net.n)
    dst = rng.choice([i for i in range(net.n) if i != src])
    # times=2 < RetryPolicy max attempts (4) and < breaker trip (5): the
    # third attempt must land, with the breaker still closed
    net.arm(seed, [failpoints.Rule.make(
        "net.send_partial", "drop", rounds=(r0, r0), times=2,
        match={"src": f"node{src}", "dst": f"node{dst}"})])
    await net.advance_to_round(r0)
    # Walk the clock through the retry window in sub-budget steps (with
    # real time between steps for the resent RPC's roundtrip): a whole-
    # period jump would strand the resend — dispatched at T+backoff but
    # processed server-side after the fake clock already passed the
    # period/2 deadline, i.e. shed as doomed work.  Sub-second steps
    # keep the server's view of the budget live, which is exactly how
    # real time behaves.
    loop = asyncio.get_running_loop()
    bound = loop.time() + 20.0
    while res_policy.inflight() or not any(
            e.get("outcome") == "success" and e.get("key") == f"r{r0}"
            for e in res_policy.LOG.entries()):
        if loop.time() > bound:
            break               # the assertions below report the log
        await net.clock.advance(0.2)
        await asyncio.sleep(0.05)
    failpoints.disarm()
    target = r0 + 2
    await net.advance_to_round(target, timeout=90.0)
    retries = [e for e in res_policy.LOG.entries()
               if e.get("kind") == "retry"
               and e.get("site") == "net.send_partial"
               and e.get("peer") == f"node{dst}"]
    if not any(e["outcome"] == "retry" for e in retries):
        raise AssertionError(f"dropped send never retried: {retries}")
    if not any(e["outcome"] == "success" for e in retries):
        raise AssertionError(
            f"retries never succeeded within the budget: {retries}")
    return target


async def _drive_breaker_trip_heal(net: ScenarioNet, seed: int,
                                   rng: random.Random) -> int:
    """Acceptance (b): a partitioned peer's breakers trip OPEN on the
    surviving side (observed via the metrics port's drand_breaker_state
    gauge), then heal back to CLOSED after the partition lifts, with the
    full transition cycle in the decision log."""
    import aiohttp

    from drand_tpu.metrics import MetricsServer
    victim = rng.randrange(net.n)
    observer = next(i for i in range(net.n) if i != victim)
    victim_addr = net.daemons[victim].private_addr()
    ms = MetricsServer(net.daemons[observer], 0)
    await ms.start()

    async def breaker_gauge() -> float:
        url = f"http://127.0.0.1:{ms.port}/metrics"
        async with aiohttp.ClientSession() as s:
            async with s.get(url) as resp:
                text = await resp.text()
        needle = f'drand_breaker_state{{peer="{victim_addr}"}}'
        for line in text.splitlines():
            if line.startswith(needle):
                return float(line.rsplit(" ", 1)[1])
        raise AssertionError(f"{needle} not in exposition")

    async def wait_gauge(value: float, note: str) -> None:
        """Poll (real time — a half-open probe settles without clock
        movement) until the gauge reads `value`."""
        loop = asyncio.get_running_loop()
        deadline = loop.time() + 10.0
        while True:
            v = await breaker_gauge()
            if v == value:
                return
            if loop.time() > deadline:
                raise AssertionError(f"{note}: drand_breaker_state is "
                                     f"{v}, wanted {value}")
            await asyncio.sleep(0.1)

    try:
        others = [f"node{i}" for i in range(net.n) if i != victim]
        net.arm(seed, faults.partition([f"node{victim}"], others))
        base = max(net.last_rounds())
        majority = [d for i, d in enumerate(net.daemons) if i != victim]
        # enough rounds of failed sends (plus failed watchdog pings) to
        # cross the trip threshold on every survivor
        await net.advance_to_round(base + 3, daemons=majority)
        await net.drain_retries()
        await wait_gauge(1.0, "breaker for the partitioned peer did "
                              "not OPEN")
        failpoints.disarm()     # heal
        # past the breaker reset timeout: half-open probes (and watchdog
        # pings) must close the breakers, and the victim must gap-sync
        target = base + 7
        await net.advance_to_round(target, timeout=120.0)
        await net.drain_retries()
        await wait_gauge(0.0, "breaker did not CLOSE after heal")
        trans = [(e["from"], e["to"]) for e in res_policy.LOG.entries()
                 if e.get("kind") == "breaker"
                 and e.get("peer") == f"node{victim}"]
        if ("closed", "open") not in trans:
            raise AssertionError(f"no closed->open transition: {trans}")
        if not any(t[1] == "closed" for t in trans):
            raise AssertionError(f"breaker never healed to closed: {trans}")
        return target
    finally:
        await ms.stop()


async def _drive_crash_recover(net: ScenarioNet, seed: int,
                               rng: random.Random) -> int:
    """Crash-safe storage acceptance (ISSUE 15), clean-crash half: a
    seeded node goes down; while it is down a REAL subprocess
    (drand_tpu/chaos/crashwriter.py) replays a survivor's rows into its
    closed db as catch-up-shaped put_many segments and is SIGKILLed
    mid-write — an actual kill -9, not an injected exception.  On
    restart the startup integrity scan must find a verified prefix at a
    segment boundary, quarantine NOTHING (WAL + one-transaction-per-
    segment means a torn segment is never visible), and the node must
    heal to the tip via peer re-sync.  Counter-asserted on
    drand_store_integrity and drand_store_quarantined_total."""
    import os
    import sys

    import drand_tpu as _pkg
    from drand_tpu.metrics import REGISTRY
    victim = rng.randrange(net.n)
    base = max(net.last_rounds())
    await net.advance_to_round(base + 1)
    net.crash(victim)
    survivors = [d for i, d in enumerate(net.daemons) if i != victim]
    await net.advance_to_round(base + 4, daemons=survivors)
    donor = next(i for i in range(net.n) if i != victim)
    q_before = REGISTRY.get_sample_value(
        "drand_store_quarantined_total") or 0.0
    kill_after = 1 + rng.randrange(2)     # seeded kill point (segments)
    repo_root = os.path.dirname(os.path.dirname(
        os.path.abspath(_pkg.__file__)))
    proc = await asyncio.create_subprocess_exec(
        sys.executable, "-m", "drand_tpu.chaos.crashwriter",
        net.process(donor).db_path(), net.process(victim).db_path(),
        "--segment", "1", "--sleep-s", "0.1",
        stdout=asyncio.subprocess.PIPE,
        stderr=asyncio.subprocess.DEVNULL, cwd=repo_root)
    seen = 0
    try:
        while seen < kill_after:
            line = await asyncio.wait_for(proc.stdout.readline(), 20.0)
            if not line or line.startswith(b"DONE"):
                raise AssertionError(
                    f"crashwriter finished before the kill point "
                    f"({seen}/{kill_after} segments)")
            if line.startswith(b"SEGMENT"):
                seen += 1
        proc.kill()                       # SIGKILL — the real thing
    finally:
        if proc.returncode is None:
            try:
                proc.kill()
            except ProcessLookupError:
                pass
        await proc.wait()
    if proc.returncode != -9:
        raise AssertionError(
            f"crashwriter exited {proc.returncode}, expected SIGKILL (-9)")
    await net.restart(victim)
    bp = net.process(victim)
    rep = bp.integrity_report
    if rep is None or not rep.ok:
        raise AssertionError(
            "startup scan after a clean kill -9 found damage: "
            f"{rep and rep.to_dict()}")
    if bp._store.insecure.quarantined():
        raise AssertionError("clean crash quarantined rows")
    q_after = REGISTRY.get_sample_value(
        "drand_store_quarantined_total") or 0.0
    if q_after != q_before:
        raise AssertionError(
            "drand_store_quarantined_total moved on a clean crash: "
            f"{q_before} -> {q_after}")
    integ = REGISTRY.get_sample_value("drand_store_integrity",
                                      {"beacon_id": "default"})
    if integ != 1.0:
        raise AssertionError(f"drand_store_integrity={integ}, wanted 1")
    target = base + 5
    await net.advance_to_round(target, timeout=120.0)
    return target


async def _drive_torn_write_heal(net: ScenarioNet, seed: int,
                                 rng: random.Random) -> int:
    """Crash-safe storage acceptance (ISSUE 15), corruption half: a
    seeded node goes down and its closed db suffers a torn write plus a
    bit flip (faults.torn_write / faults.bit_rot — direct disk surgery,
    the damage failpoints cannot express).  On restart the startup scan
    must quarantine EXACTLY the damaged rounds, roll the tip back to the
    verified prefix, and heal the suffix from peers with bit-identical
    restored rows."""
    from drand_tpu.metrics import REGISTRY
    victim = rng.randrange(net.n)
    base = max(net.last_rounds())
    await net.advance_to_round(base + 2)
    vic_tip = net.last_rounds()[victim]
    net.crash(victim)
    survivors = [d for i, d in enumerate(net.daemons) if i != victim]
    await net.advance_to_round(base + 4, daemons=survivors)
    db = net.process(victim).db_path()
    torn, rotted = rng.sample(range(2, vic_tip + 1), 2)
    faults.torn_write(db, torn)
    faults.bit_rot(db, rotted, offset=3)   # flip inside the round field
    q_before = REGISTRY.get_sample_value(
        "drand_store_quarantined_total") or 0.0
    await net.restart(victim)
    bp = net.process(victim)
    rep = bp.integrity_report
    if rep is None or rep.ok:
        raise AssertionError("startup scan missed injected corruption: "
                             f"{rep and rep.to_dict()}")
    if set(rep.corrupt) != {torn, rotted}:
        raise AssertionError(f"wrong corrupt set {rep.corrupt}, wanted "
                             f"{sorted((torn, rotted))}")
    want_tip = min(torn, rotted) - 1
    if rep.verified_tip != want_tip:
        raise AssertionError(f"verified_tip {rep.verified_tip}, wanted "
                             f"{want_tip}")
    quarantined = {r for r, _ in bp._store.insecure.quarantined()}
    if not {torn, rotted} <= quarantined:
        raise AssertionError(f"damaged rounds not quarantined: "
                             f"{sorted(quarantined)}")
    q_after = REGISTRY.get_sample_value(
        "drand_store_quarantined_total") or 0.0
    if q_after - q_before != vic_tip - want_tip:
        raise AssertionError(
            f"quarantine counter moved {q_after - q_before}, wanted "
            f"{vic_tip - want_tip} (tip {vic_tip} -> {want_tip})")
    integ = REGISTRY.get_sample_value("drand_store_integrity",
                                      {"beacon_id": "default"})
    if integ != 0.0:
        raise AssertionError(f"drand_store_integrity={integ}, wanted 0")
    target = base + 5
    await net.advance_to_round(target, timeout=120.0)
    # the healed rows must be bit-identical to the donor's stored bytes
    donor = next(i for i in range(net.n) if i != victim)
    vic_store = bp._store.insecure
    don_store = net.process(donor)._store.insecure
    for r in sorted((torn, rotted)):
        a = vic_store.raw_rows(r, 1)
        b = don_store.raw_rows(r, 1)
        if not a or not b or a[0] != b[0]:
            raise AssertionError(f"healed round {r} not bit-identical "
                                 f"to the donor's row")
    return target


def _truncate_object(path: str, keep: int) -> None:
    """Seeded object damage (worker thread): cut the file to `keep`
    bytes — what a half-replicated CDN edge serves."""
    with open(path, "r+b") as f:
        f.truncate(keep)


def _flip_object_byte(path: str, off: int) -> None:
    """Seeded object damage (worker thread): flip one byte in place —
    storage-layer bit rot."""
    with open(path, "r+b") as f:
        f.seek(off)
        byte = f.read(1)
        f.seek(off)
        f.write(bytes([byte[0] ^ 0xFF]))


async def _drive_object_sync_poisoned(net: ScenarioNet, seed: int,
                                      rng: random.Random) -> int:
    """Objectsync acceptance (ISSUE 18): a seeded donor node publishes
    its chain as content-addressed segment objects; a fresh client store
    syncs purely from those objects with the donor's REAL verifier.
    Then the object tier is poisoned by direct file surgery — a stale
    manifest, a truncated segment object, a bit-rotted one — and the
    client must stop at EXACTLY the verified segment boundary with zero
    damaged rounds committed, recovering bit-identically once clean
    objects reappear.  No failpoints: a dumb object store has no inline
    sites, damage is what the disk serves."""
    from drand_tpu.chain.beacon import Beacon
    from drand_tpu.chain.scheme import scheme_by_id
    from drand_tpu.chain.store import AppendStore, SchemeStore, SqliteStore
    from drand_tpu.objectsync import (FilesystemBackend, Manifest,
                                      ObjectPublisher, ObjectSyncClient,
                                      content_hash, encode_segment)
    from drand_tpu.objectsync import format as ofmt

    seg_rounds = 2
    base = max(net.last_rounds())
    target = base + 6                     # >= 3 sealed 2-round segments
    await net.advance_to_round(target)

    donor_i = rng.randrange(net.n)
    bp = net.process(donor_i)
    donor_store = bp._store.insecure
    info = bp.group.chain_info()
    root = tempfile.mkdtemp(prefix="chaos-objectsync-")
    backend = FilesystemBackend(os.path.join(root, "objects"))
    pub = ObjectPublisher(donor_store, backend, chain_hash=info.hash(),
                          scheme_id=bp.group.scheme_id,
                          segment_rounds=seg_rounds)
    await pub.load_manifest()
    await pub.publish_sealed()
    segs = pub.manifest.segments
    if len(segs) < 3:
        raise AssertionError(f"only {len(segs)} sealed segments at tip "
                             f"{max(net.last_rounds())}; drive needs 3")
    full_manifest = pub.manifest.to_json()

    def fresh_client(path):
        cbase = SqliteStore(os.path.join(root, path))
        scheme = scheme_by_id(bp.group.scheme_id)
        cstore = SchemeStore(AppendStore(cbase), scheme.decouple_prev_sig)
        # anchor: round 0 carrying round 1's prev linkage (genesis seed
        # for chained schemes, empty for unchained)
        cstore.put(Beacon(round=0,
                          signature=donor_store.read_fields(1, 1)[0][2]))
        return cbase, cstore

    # phase 1 — stale manifest (a CDN edge serving yesterday's index):
    # NOT an error, just a shorter verified chain
    stale = Manifest.from_json(full_manifest)
    stale.segments = stale.segments[:1]
    stale.tip = stale.segments[-1].end
    await backend.put(ofmt.MANIFEST_NAME, stale.to_json())
    cbase, cstore = fresh_client("client.sqlite")
    cli = ObjectSyncClient(backend, cstore, bp.verifier,
                           chain_hash=info.hash())
    res = await cli.sync()
    if not res.ok or res.synced_to != segs[0].end:
        raise AssertionError(f"stale-manifest sync: ok={res.ok} "
                             f"synced_to={res.synced_to} "
                             f"(wanted {segs[0].end}): {res.error}")

    # phase 2 — fresh manifest, but two seeded later segments damaged on
    # disk: one truncated, one bit-rotted.  FIFO commit must stop at the
    # boundary BEFORE the first damaged segment.
    await backend.put(ofmt.MANIFEST_NAME, full_manifest)
    vt, vr = sorted(rng.sample(range(1, len(segs)), 2))
    objdir = os.path.join(root, "objects")
    t_path = os.path.join(objdir, segs[vt].name)
    keep = rng.randrange(1, os.path.getsize(t_path))
    await asyncio.to_thread(_truncate_object, t_path, keep)
    r_path = os.path.join(objdir, segs[vr].name)
    off = rng.randrange(os.path.getsize(r_path))
    await asyncio.to_thread(_flip_object_byte, r_path, off)
    res = await cli.sync()
    want_tip = segs[vt].start - 1
    if res.ok or res.synced_to != want_tip:
        raise AssertionError(f"poisoned sync: ok={res.ok} "
                             f"synced_to={res.synced_to} "
                             f"(wanted stop at {want_tip}): {res.error}")
    if "content hash mismatch" not in res.error:
        raise AssertionError(f"poisoned sync failed for the wrong "
                             f"reason: {res.error}")
    if cstore.last().round != want_tip:
        raise AssertionError(f"client tip {cstore.last().round} != "
                             f"verified prefix {want_tip}")
    if cbase.read_fields(want_tip + 1, 8):
        raise AssertionError("rounds past the verified prefix committed")
    for r in range(1, want_tip + 1):
        a, b = cbase.raw_rows(r, 1), donor_store.raw_rows(r, 1)
        if not a or not b or a[0] != b[0]:
            raise AssertionError(f"verified prefix round {r} not "
                                 f"bit-identical to the donor's row")

    # phase 3 — clean objects reappear (re-encoded from the donor:
    # content addressing makes them byte-identical, hash and all)
    for vi in (vt, vr):
        blob = encode_segment(info.hash(), bp.group.scheme_id,
                              donor_store.read_fields(segs[vi].start,
                                                      segs[vi].count))
        if content_hash(blob) != segs[vi].hash:
            raise AssertionError(f"re-encoded segment {segs[vi].name} "
                                 f"hash drifted")
        await backend.put(segs[vi].name, blob)
    res = await cli.sync()
    if not res.ok or res.synced_to != segs[-1].end:
        raise AssertionError(f"healed sync: ok={res.ok} "
                             f"synced_to={res.synced_to}: {res.error}")
    for r in range(1, segs[-1].end + 1):
        a, b = cbase.raw_rows(r, 1), donor_store.raw_rows(r, 1)
        if not a or not b or a[0] != b[0]:
            raise AssertionError(f"healed round {r} not bit-identical "
                                 f"to the donor's row")
    cbase.close()
    return target


async def _drive_fork_detect(net: ScenarioNet, seed: int,
                             rng: random.Random) -> int:
    """Fleet-observatory acceptance (ISSUE 19): one seeded probe sample
    is answered with a forged divergent signature (probe.sample / error
    — an injected equivocation), and the observing node's consistency
    prober must record a typed ForkReport within a bounded number of
    rounds.  The forged bytes derive only from the sampled round and the
    probe.sample ctx carries no round/time, so the injection log replays
    byte-identically."""
    observer = rng.randrange(net.n)
    peer = rng.choice([i for i in range(net.n) if i != observer])
    net.arm(seed, [failpoints.Rule.make(
        "probe.sample", "error", times=1,
        match={"src": f"node{observer}", "dst": f"node{peer}"})])
    base = max(net.last_rounds())
    bound = base + 4               # detection must land inside this window
    peer_addr = net.daemons[peer].private_addr()
    prober = net.daemons[observer].consistency

    def forged(log) -> bool:
        return any(e["site"] == "probe.sample" and e["kind"] == "error"
                   for e in log)

    target = base
    while True:
        target += 1
        await net.advance_to_round(target)
        # The prober is clock-cadenced: advancing rounds walked the fake
        # clock past its wake-ups; give the in-flight samples real time
        # to land before deciding this round's tick missed.
        if await net.wait_for_injections(forged, timeout=5.0):
            break
        if target >= bound:
            raise AssertionError(
                f"forged probe.sample never fired by round {bound}: "
                f"{net.schedule.injection_summary()}")
    # the forged signature is diffed synchronously after the failpoint
    # raises, but the probe coroutine needs a beat to finish its tick
    loop = asyncio.get_running_loop()
    settle = loop.time() + 5.0
    while not prober.forks and loop.time() < settle:
        await asyncio.sleep(0.05)
    if not prober.forks:
        raise AssertionError("forged sample fired but no ForkReport "
                             f"recorded: {prober.snapshot()}")
    rep = prober.forks[0]
    if rep.peer != peer_addr:
        raise AssertionError(
            f"fork attributed to {rep.peer}, wanted {peer_addr}")
    if not 1 <= rep.round <= bound:
        raise AssertionError(
            f"fork at round {rep.round}, outside (0, {bound}]")
    snap = prober.snapshot()
    if snap["fork_count"] != 1 or len(snap["forks"]) != 1:
        raise AssertionError(f"fork bookkeeping off: {snap}")
    failpoints.disarm()
    # the fork is observational — the chain itself must keep flowing
    target += 1
    await net.advance_to_round(target, timeout=90.0)
    return target


async def _await_counted(net: ScenarioNet, nodes, signers, round_: int,
                         timeout: float) -> None:
    """Wait, with a deadline, until the ledger of every node in `nodes`
    has every signer in `signers` on its books for `round_`.  A round
    lands in a store at the threshold, while the remaining partials are
    still in flight; the next recovery seals the round's margin without
    them.  So a drive that asserts on margins lets the stragglers land
    before it moves the clock on.  Past the deadline it goes on, and the
    drive's own assertions say what is missing."""
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while loop.time() < deadline:
        if all(net.process(i).handler.ledger.is_counted(s, round_)
               for i in nodes for s in signers):
            return
        await asyncio.sleep(0.02)


async def _drive_signer_loss(net: ScenarioNet, seed: int,
                             rng: random.Random) -> int:
    """Fleet-observatory acceptance (ISSUE 19): a seeded signer dies and
    EVERY survivor's participation ledger must move — the victim's rate
    drops, its miss streak crosses the chronic threshold, and the FINAL
    threshold margin falls from n-t to (n-1)-t — then heal back once the
    victim rejoins.  An ordinary outage must raise no fork reports."""
    healthy_margin = net.n - net.thr
    base = max(net.last_rounds())
    group = net.process(0).group
    all_signers = [n.index for n in group.nodes]
    # a few healthy rounds first: every ledger must show the full margin
    for r in range(base + 1, base + 4):
        await net.advance_to_round(r)
        await _await_counted(net, range(net.n), all_signers, r, timeout=10.0)
    victim = rng.randrange(1, net.n)          # keep the DKG leader alive
    vic_addr = net.daemons[victim].private_addr()
    surv_idx = [i for i in range(net.n) if i != victim]
    survivors = [net.daemons[i] for i in surv_idx]
    vic_signer = next(n.index for n in group.nodes
                      if n.address == vic_addr)
    for i in surv_idx:
        led = net.process(i).handler.ledger
        if led.last_final_margin != healthy_margin:
            raise AssertionError(
                f"node{i} healthy margin {led.last_final_margin}, "
                f"wanted {healthy_margin}")
    crash_at = max(net.last_rounds())
    net.crash(victim)
    # enough sealed rounds for the chronic-miss threshold (3) to trip
    down_end = crash_at + 5
    await net.advance_to_round(down_end, daemons=survivors, timeout=120.0)
    if net.last_rounds()[victim] >= down_end:
        raise AssertionError("crash had no effect: victim kept appending")
    for i in surv_idx:
        led = net.process(i).handler.ledger
        if led.rate(vic_signer) >= 1.0:
            raise AssertionError(
                f"node{i}: dead signer {vic_signer} rate did not drop "
                f"({led.snapshot(limit=8)})")
        if led.miss_streak(vic_signer) < 3:
            raise AssertionError(
                f"node{i}: miss streak {led.miss_streak(vic_signer)} < 3")
        if vic_signer not in led.missing_signers():
            raise AssertionError(
                f"node{i}: signer {vic_signer} not chronically missing")
        if led.last_final_margin != healthy_margin - 1:
            raise AssertionError(
                f"node{i}: outage margin {led.last_final_margin}, "
                f"wanted {healthy_margin - 1}")
    await net.restart(victim)
    # heal: the margin must return to n-t on every survivor once the
    # victim's partials flow again (bounded rounds, not "eventually")
    heal_bound = down_end + 6
    target = down_end
    while True:
        target += 1
        await net.advance_to_round(target, timeout=120.0)
        # short: the victim may still be syncing and owe this round
        await _await_counted(net, surv_idx, all_signers, target, timeout=2.0)
        if all(net.process(i).handler.ledger.last_final_margin ==
               healthy_margin for i in surv_idx):
            break
        if target >= heal_bound:
            snaps = {i: net.process(i).handler.ledger.snapshot(limit=4)
                     for i in surv_idx}
            raise AssertionError(
                f"margin never healed to {healthy_margin} by round "
                f"{heal_bound}: {snaps}")
    for i in surv_idx:
        led = net.process(i).handler.ledger
        if led.miss_streak(vic_signer) != 0:
            raise AssertionError(
                f"node{i}: healed signer still streaking "
                f"({led.miss_streak(vic_signer)})")
        if vic_signer in led.missing_signers():
            raise AssertionError(
                f"node{i}: healed signer still chronically missing")
        forks = net.daemons[i].consistency.snapshot()["fork_count"]
        if forks:
            raise AssertionError(
                f"node{i}: ordinary outage raised {forks} fork report(s)")
    return target


async def _drive_reshare_mid_traffic(net: ScenarioNet, seed: int,
                                     rng: random.Random) -> int:
    """Zero-blip reshare acceptance (ISSUE 20): the group reshares to a
    grown membership WHILE a bench_serve-style HTTP load hammers
    /public/latest + /info on a member — zero failed public reads,
    beacon cadence uninterrupted (every round present, no holes), and
    the three epoch-invalidation seams observed firing exactly once,
    together, on every original member:

      1. signer-key table epoch (ChainStore.update_group ->
         backend.update_group -> SignerKeyTable.update),
      2. ResponseCache.invalidate (via chain_store.on_group_update),
      3. the daemon's chains_version bump (bp.on_group_transition ->
         daemon.note_group_update).

    The in-place engine swap must also have held: same store object,
    same ResponseCache object across the transition (a full rebuild
    would pass the read checks but reset the cache epoch)."""
    import aiohttp

    from drand_tpu.http.server import PublicHTTPServer

    originals = list(net.daemons)
    observed = rng.randrange(net.n)
    d_obs = net.daemons[observed]
    srv = PublicHTTPServer(d_obs, "127.0.0.1:0")
    await srv.start()
    base_url = f"http://127.0.0.1:{srv.port}"

    before = []
    for d in originals:
        bp = d.processes["default"]
        before.append({
            "store": bp._store,
            "cache": bp.response_cache,
            "cache_epoch": bp.response_cache.epoch,
            "table_epoch": bp.chain_store.backend.table.epoch,
            "chains_version": d.chains_version,
        })

    stats = {"reads": 0, "failures": []}
    stop = asyncio.Event()

    async def load():
        async with aiohttp.ClientSession() as s:
            i = 0
            while not stop.is_set():
                path = "/public/latest" if i % 3 else "/info"
                try:
                    async with s.get(base_url + path) as r:
                        body = await r.read()
                        stats["reads"] += 1
                        if r.status != 200:
                            stats["failures"].append(
                                (path, r.status, body[:160]))
                except Exception as exc:     # noqa: BLE001 - recorded
                    stats["failures"].append((path, repr(exc)))
                i += 1
                # paced load generator, not a retry loop
                await asyncio.sleep(0.01)  # lint: disable=no-adhoc-retry

    loader = asyncio.get_running_loop().create_task(load())
    try:
        groups = await net.run_reshare(net.n + 1, net.thr + 1)
        # the engine swap fires at the transition round (~3 DKG
        # timeouts out, group_setup.compute_genesis) — cross it with
        # traffic still flowing, plus two post-transition rounds on
        # the new group
        g = originals[0].processes["default"].group
        t_round = current_round(groups[0].transition_time, g.period,
                                g.genesis_time)
        target = t_round + 2
        await net.advance_to_round(target, timeout=240.0,
                                   daemons=originals)
        # a settle beat of pure serving on the post-reshare engine
        await asyncio.sleep(0.3)
    finally:
        stop.set()
        await loader
        await srv.stop()

    if stats["failures"]:
        raise AssertionError(
            f"{len(stats['failures'])} failed public reads during the "
            f"reshare: {stats['failures'][:5]}")
    if stats["reads"] < 10:
        raise AssertionError(f"load too thin to prove anything: "
                             f"{stats['reads']} reads")

    # cadence: every round present on the observed member, no holes
    store = d_obs.processes["default"]._store
    tip = store.last().round
    missing = [r for r in range(1, tip + 1)
               if not _has_round(store, r)]
    if missing:
        raise AssertionError(f"rounds dropped across the reshare: "
                             f"{missing}")

    for i, (d, b) in enumerate(zip(originals, before)):
        bp = d.processes["default"]
        if bp._store is not b["store"]:
            raise AssertionError(
                f"node{i}: store object swapped — the zero-blip "
                f"in-place transition did not hold")
        if bp.response_cache is not b["cache"]:
            raise AssertionError(
                f"node{i}: ResponseCache rebuilt instead of invalidated")
        seams = {
            "response-cache epoch":
                bp.response_cache.epoch - b["cache_epoch"],
            "signer-table epoch":
                bp.chain_store.backend.table.epoch - b["table_epoch"],
            "chains_version": d.chains_version - b["chains_version"],
        }
        wrong = {k: v for k, v in seams.items() if v != 1}
        if wrong:
            raise AssertionError(
                f"node{i}: epoch seams must each fire exactly once, "
                f"got deltas {seams}")
    return target


def _has_round(store, r: int) -> bool:
    try:
        return store.get(r) is not None
    except Exception:
        return False


async def _drive_random_soak(net: ScenarioNet, seed: int,
                             rng: random.Random) -> int:
    """Seeded random fault mix over a longer horizon: lossy/slow network
    plus a bounded store-error burst, then heal and settle."""
    base = max(net.last_rounds())
    rules = (faults.message_drop(pct=rng.uniform(5, 20))
             + faults.message_delay(pct=rng.uniform(10, 30),
                                    delay_s=rng.uniform(0.01, 0.1))
             + faults.store_commit_errors(
                 pct=50, owner=f"node{rng.randrange(net.n)}",
                 times=rng.randrange(1, 4)))
    net.arm(seed, rules)
    await net.advance_to_round(base + 8, timeout=240.0)
    failpoints.disarm()
    target = base + 9
    await net.advance_to_round(target, timeout=120.0)
    return target


async def _drive_dkg_under_fire(seed: int, rng: random.Random,
                                nodes: int, thr: int, **kw):
    # lazy import: chaos/ceremony.py pulls the crypto stack, which the
    # daemon-scenario path never needs at module load
    from drand_tpu.chaos import ceremony
    return await ceremony.drive_dkg_under_fire(seed, rng, nodes, thr, **kw)


SCENARIOS: dict[str, ScenarioSpec] = {
    "partition-heal": ScenarioSpec(
        "partition-heal",
        "symmetric partition isolates one seeded node for 3 rounds, "
        "then heals; the victim must gap-sync back",
        _drive_partition_heal),
    "leader-crash": ScenarioSpec(
        "leader-crash",
        "the DKG leader crashes mid-round at a seeded height and "
        "rejoins via catch-up",
        _drive_leader_crash),
    "store-errors-catchup": ScenarioSpec(
        "store-errors-catchup",
        "a rejoining node's catch-up commits fail with StoreError for a "
        "seeded burst; sync retries must close the gap",
        _drive_store_errors_catchup),
    "skewed-node": ScenarioSpec(
        "skewed-node",
        "one node's clock runs a seeded sub-round offset ahead of the "
        "group; rounds keep flowing and agreeing",
        _drive_skewed_node),
    "retry-storm": ScenarioSpec(
        "retry-storm",
        "a seeded peer pair's partial send is dropped a bounded number "
        "of times; seeded-backoff retries must land it within the "
        "round's deadline budget (decision log shows retry->success)",
        _drive_retry_storm),
    "breaker-trip-heal": ScenarioSpec(
        "breaker-trip-heal",
        "a partitioned peer's circuit breakers trip OPEN (observed on "
        "the metrics port), then heal to CLOSED after the partition "
        "lifts; the victim gap-syncs back",
        _drive_breaker_trip_heal),
    "crash-recover": ScenarioSpec(
        "crash-recover",
        "a real subprocess writer (crashwriter.py) is SIGKILLed "
        "mid-catchup-segment against a downed node's db; the restart "
        "scan must find a verified prefix, quarantine nothing, and the "
        "node heals to the tip via peer re-sync",
        _drive_crash_recover),
    "torn-write-heal": ScenarioSpec(
        "torn-write-heal",
        "a downed node's db suffers a torn row write plus a round-field "
        "bit flip; the restart scan quarantines exactly those rounds, "
        "rolls back to the verified prefix, and peers restore the "
        "suffix bit-identically",
        _drive_torn_write_heal),
    "object-sync-poisoned": ScenarioSpec(
        "object-sync-poisoned",
        "a donor publishes content-addressed segment objects; a stale "
        "manifest, a truncated object, and a bit-rotted object must "
        "stop a fresh client at exactly the verified segment boundary "
        "with zero damage committed, then heal bit-identically once "
        "clean objects reappear",
        _drive_object_sync_poisoned),
    "fork-detect": ScenarioSpec(
        "fork-detect",
        "one seeded probe sample is answered with a forged divergent "
        "signature (injected equivocation); the observer's consistency "
        "prober must record a typed ForkReport within a bounded number "
        "of rounds, replay-deterministically",
        _drive_fork_detect),
    "signer-loss": ScenarioSpec(
        "signer-loss",
        "a seeded signer dies; every survivor's participation ledger "
        "must show the dropped rate, chronic miss streak, and shrunken "
        "threshold margin, then heal after the victim rejoins",
        _drive_signer_loss),
    "dkg-under-fire": ScenarioSpec(
        "dkg-under-fire",
        "n-node DKG ceremony under seeded fanout drops/delays, a seeded "
        "one-way partition, crashed dealers, and a cross-ceremony "
        "stale-nonce replay injection; QUAL >= t with identical group "
        "keys and typed phase outcomes on every live node "
        "(--nodes 128 --threshold 65 is the acceptance shape)",
        _drive_dkg_under_fire, ceremony=True),
    "reshare-mid-traffic": ScenarioSpec(
        "reshare-mid-traffic",
        "reshare to a grown group while an HTTP load hammers a member: "
        "zero failed public reads, no dropped rounds, and the three "
        "epoch-invalidation seams (signer-table epoch, response-cache "
        "invalidate, chains_version) fire exactly once, together, on "
        "every original member",
        _drive_reshare_mid_traffic),
    "random-soak": ScenarioSpec(
        "random-soak",
        "seeded random drop/delay/store-error mix over ~8 rounds, then "
        "heal (longer; not in the tier-1 matrix)",
        _drive_random_soak, slow=True),
}


@dataclass
class ChaosReport:
    """One scenario run's verdict: what fired, what held.  `decisions`
    is the resilience layer's half of the replay contract: every retry
    backoff and breaker transition the run produced (aliased, seeded —
    byte-identical across replays like `summary`)."""
    scenario: str
    seed: int
    nodes: int
    threshold: int
    scheme: str
    final_rounds: list[int] = field(default_factory=list)
    invariants_passed: list[str] = field(default_factory=list)
    injections: list[dict] = field(default_factory=list)
    summary: list[tuple] = field(default_factory=list)
    decisions: list[dict] = field(default_factory=list)
    decision_summary: list[tuple] = field(default_factory=list)
    sanitized: bool = False
    sanitizer_reports: list[dict] = field(default_factory=list)

    def to_dict(self) -> dict:
        return {"scenario": self.scenario, "seed": self.seed,
                "nodes": self.nodes, "threshold": self.threshold,
                "scheme": self.scheme, "final_rounds": self.final_rounds,
                "invariants_passed": self.invariants_passed,
                "injected": len(self.injections),
                "injections": self.injections,
                "summary": [list(t) for t in self.summary],
                "decisions": self.decisions,
                "decision_summary": [list(t) for t in
                                     self.decision_summary],
                "sanitized": self.sanitized,
                "sanitizer_reports": self.sanitizer_reports}


# Loop-block threshold while a chaos run is sanitized: chaos schedules
# legitimately make loop callbacks slower than a serving daemon's (fault
# bookkeeping, seeded delays resolved inline), so the default is looser
# than the sanitizer's; DRAND_TPU_ASYNC_SANITIZE_THRESHOLD still wins.
CHAOS_SANITIZE_THRESHOLD_S = 1.0


async def run_ceremony_scenario(spec: ScenarioSpec, seed: int, nodes: int,
                                threshold: int | None, scheme: str,
                                **drive_kw) -> ChaosReport:
    """Ceremony scenarios: no daemons, no fake clock, no chain
    invariants — the drive runs a chaos/ceremony.CeremonyNet DKG and
    returns ``(net, invariant_names)``.  The asyncio sanitizer is
    deliberately NOT armed: a host-path ceremony blocks the loop in the
    crypto by design (the compute runs inline at n^2 scale), which is
    exactly the noise the sanitizer exists to flag on SERVING daemons.
    ``final_rounds`` carries each live node's QUAL size instead of a
    chain tip."""
    rng = random.Random(seed)
    thr = threshold or (nodes // 2 + 1)
    report = ChaosReport(spec.name, seed, nodes, thr, scheme)
    res_policy.LOG.reset()
    res_policy.set_seed_override(seed)
    try:
        net, passed = await spec.drive(seed, rng, nodes, thr, **drive_kw)
        report.invariants_passed = list(passed)
        report.final_rounds = [
            len(net.bps[i].dkg_status.qual)
            if net.bps[i].dkg_status is not None else -1
            for i in net.live]
        if net.schedule is not None:
            report.injections = net.schedule.injection_log()
            report.summary = net.schedule.injection_summary()
        report.decisions = res_policy.LOG.entries()
        report.decision_summary = res_policy.LOG.summary()
        return report
    finally:
        res_policy.set_seed_override(None)
        failpoints.disarm()


async def run_scenario(name: str, seed: int, nodes: int = 3,
                       threshold: int | None = None,
                       scheme: str = "pedersen-bls-unchained",
                       sanitize: bool | None = None,
                       **drive_kw) -> ChaosReport:
    """Run one named scenario under `seed`; raises InvariantViolation /
    AssertionError when the protocol contract does not survive it.

    `sanitize` (default: DRAND_TPU_ASYNC_SANITIZE) arms the runtime
    asyncio sanitizer across the fault window — every schedule doubles
    as a dynamic race probe; reports land in the returned
    :class:`ChaosReport`, they do not fail the run by themselves.
    Ceremony scenarios (``spec.ceremony``) take the daemon-less path;
    `drive_kw` (e.g. ``k_crash``, ``dkg_timeout``) is forwarded to
    their drive."""
    spec = SCENARIOS[name]
    if spec.ceremony:
        return await run_ceremony_scenario(spec, seed, nodes, threshold,
                                           scheme, **drive_kw)
    rng = random.Random(seed)
    thr = threshold or (nodes // 2 + 1)
    node_clocks = {}
    base_clock = FakeClock(start=1_700_000_000.0)
    if name == "skewed-node":
        # skew stays under half a period: within the one-round drift
        # window the partial handler tolerates by design
        node_clocks[rng.randrange(nodes)] = faults.SkewClock(
            base_clock, rng.uniform(0.3, PERIOD / 2 - 0.5))
    net = ScenarioNet(nodes, thr, scheme, clock=base_clock,
                      node_clocks=node_clocks)
    report = ChaosReport(name, seed, nodes, thr, scheme)
    # one seed pins everything: injection decisions (Schedule) AND retry
    # backoff hashing (resilience policies in every daemon), so the
    # decision log replays byte-identically even for decisions taken
    # after a mid-scenario disarm (heal)
    res_policy.LOG.reset()
    res_policy.set_seed_override(seed)
    if sanitize is None:
        sanitize = sanitizer.enabled_by_env()
    san = None
    try:
        await net.start_daemons()
        res_policy.LOG.set_aliases(net.aliases())
        await net.run_dkg()
        await net.advance_to_round(2)
        if sanitize:
            # armed AFTER warm-up: DKG runs one-time crypto and JAX
            # compilation whose loop cost is not what the probe hunts
            thr_s = sanitizer.env_threshold() \
                if os.environ.get(sanitizer.ENV_THRESHOLD) \
                else CHAOS_SANITIZE_THRESHOLD_S
            san = sanitizer.arm(sanitizer.AsyncSanitizer(
                block_threshold_s=thr_s))
        expected = await spec.drive(net, seed, rng)
        failpoints.disarm()
        await net.drain_retries()
        if san is not None:
            sanitizer.disarm()
            report.sanitized = True
            report.sanitizer_reports = [vars(r) for r in san.reports]
            san = None
        report.final_rounds = net.last_rounds()
        report.invariants_passed = invariants.run_all(
            [net.process(i) for i in range(net.n)], expected)
        if net.schedule is not None:
            report.injections = net.schedule.injection_log()
            report.summary = net.schedule.injection_summary()
        report.decisions = res_policy.LOG.entries()
        report.decision_summary = res_policy.LOG.summary()
        return report
    finally:
        if san is not None:          # a failed drive: capture then disarm
            sanitizer.disarm()
            report.sanitized = True
            report.sanitizer_reports = [vars(r) for r in san.reports]
        res_policy.set_seed_override(None)
        failpoints.disarm()
        await net.stop()

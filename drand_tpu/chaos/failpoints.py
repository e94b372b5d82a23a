"""Named fault-injection sites (the gofail/etcd failpoint discipline).

Every failure this repo shipped before PR 3 — blocking I/O on the loop,
dropped coroutines, sync stalls — was found *after* the fact.  This
module makes failure a first-class, test-drivable input: code paths that
can fail in production declare a named **site** (`failpoint("net.send")`
style), and a seeded :class:`Schedule` decides, deterministically, which
hits inject which fault.

Design contract:

  - **Disabled is a guaranteed no-op.**  When nothing is armed, a site
    is one module-global load and an ``is None`` test — no allocation,
    no logging, no lock.  The hygiene gate (tests/test_hygiene.py)
    asserts the default state is disarmed and every literal site name
    used in the tree is declared in :data:`SITES`.
  - **Determinism is structural, not stream-based.**  A decision is a
    pure hash of ``(seed, rule, site, canonical-context)`` — NOT a draw
    from a shared RNG stream — so concurrent sites racing on the event
    loop cannot perturb each other's outcomes.  Same seed + same
    (site, round, src, dst) hit ⇒ same decision, regardless of
    arrival order.  Ephemeral details (localhost ports) are canonicalised
    away through :meth:`Schedule.set_aliases` before hashing/logging, so
    two runs of a scenario produce identical injection logs.
  - **Faults speak the seam's language.**  Each call site passes the
    exception type its callers are hardened against (``StoreError`` at
    store seams, the default :class:`FaultInjectedError` at network
    seams), so injection exercises real recovery paths instead of
    crashing tasks no production fault could crash.

Arming: programmatic (:func:`arm`), environment (:func:`arm_from_env`
reads ``DRAND_CHAOS`` — a JSON schedule spec — at daemon start), or the
localhost ``/debug/chaos`` routes on the metrics port
(drand_tpu/metrics.py).  Injections increment
``drand_chaos_injected_total{site,kind}`` and emit a ``chaos.inject``
span so chaos runs are legible in the PR-2 trace/metrics views.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from dataclasses import dataclass, field

# -- site registry ----------------------------------------------------------

# The canonical list of injection sites.  A site name used at a call
# site but missing here (or vice versa) fails the hygiene gate: the
# registry IS the operator-facing catalogue (`drand-tpu chaos list`).
SITES: dict[str, str] = {
    "net.send_partial": "outbound partial-beacon RPC (net/client.py); "
                        "ctx: src, dst, round",
    "net.sync_recv":    "one wire message received on a SyncChain stream "
                        "(net/client.py); ctx: src, dst, round (a chunk "
                        "logs its START round — the replay-stable cut "
                        "position)",
    "partial.recv":     "inbound partial accepted for verification "
                        "(beacon/node.py); ctx: src, dst, round",
    "net.ping":         "outbound peer status/health ping "
                        "(net/client.py); ctx: src, dst",
    "dkg.fanout":       "one DKG echo-broadcast send (core/broadcast.py); "
                        "ctx: src, dst",
    "store.commit":     "chain-store append transaction (chain/store.py); "
                        "ctx: owner, beacon_id, round; raises StoreError",
    "store.read":       "chain-store point read (chain/store.py); "
                        "ctx: owner, round; raises StoreError",
    "sync.segment":     "batched segment verify dispatch "
                        "(beacon/sync_manager.py); ctx: owner, round, batch",
    "tick.fire":        "round-boundary tick before subscriber fan-out "
                        "(beacon/ticker.py); error = missed tick; "
                        "ctx: round",
    "relay.mesh_recv":  "one round received on a gossip-mesh pump "
                        "(relay/gossip.py); drop = suppress delivery, "
                        "stream stays up; ctx: src, dst, round",
    "relay.exchange":   "outbound gossip peer-exchange RPC "
                        "(relay/gossip.py); ctx: src, dst",
    "probe.sample":     "one consistency-probe signature sample "
                        "(observatory/consistency.py); drop = probe "
                        "suppressed, error = the sampled peer serves a "
                        "forged divergent signature (the fork-detect "
                        "injection vector); ctx: src, dst",
}

KINDS = ("delay", "error", "drop")

MAX_LOG = 10_000      # injection-log ring bound (soaks must not OOM)


class FaultInjectedError(Exception):
    """A fault injected by an armed chaos schedule (kind=error)."""

    def __init__(self, site: str, kind: str = "error"):
        super().__init__(f"chaos: injected {kind} at {site}")
        self.site = site
        self.kind = kind


class PacketDropped(FaultInjectedError):
    """A message dropped by an armed chaos schedule (kind=drop)."""

    def __init__(self, site: str):
        super().__init__(site, "drop")


@dataclass(frozen=True)
class Rule:
    """One injection rule: WHERE (site + match), WHEN (round window),
    WHAT (kind), and HOW OFTEN (pct, times)."""

    site: str
    kind: str                       # delay | error | drop
    pct: float = 100.0              # decision probability, hash-derived
    rounds: tuple[int, int] | None = None   # inclusive ctx-round window
    # ctx equality filter; values may be a scalar or a collection
    # (membership).  Matched AFTER aliasing, so node labels work.
    match: tuple[tuple[str, object], ...] = ()
    delay_s: float = 0.05           # kind=delay: fixed, deterministic
    times: int | None = None        # fire at most N times (None = ∞)

    def __post_init__(self):
        if self.site not in SITES:
            raise ValueError(f"unknown failpoint site {self.site!r} "
                             f"(known: {sorted(SITES)})")
        if self.kind not in KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r}")

    @classmethod
    def make(cls, site: str, kind: str, *, pct: float = 100.0,
             rounds: tuple[int, int] | None = None,
             match: dict | None = None, delay_s: float = 0.05,
             times: int | None = None) -> "Rule":
        items = tuple(sorted((k, _freeze(v)) for k, v in
                             (match or {}).items()))
        return cls(site=site, kind=kind, pct=pct,
                   rounds=tuple(rounds) if rounds else None,
                   match=items, delay_s=delay_s, times=times)

    def to_spec(self) -> dict:
        d: dict = {"site": self.site, "kind": self.kind, "pct": self.pct}
        if self.rounds:
            d["rounds"] = list(self.rounds)
        if self.match:
            d["match"] = {k: (list(v) if isinstance(v, tuple) else v)
                          for k, v in self.match}
        if self.kind == "delay":
            d["delay_s"] = self.delay_s
        if self.times is not None:
            d["times"] = self.times
        return d


def _freeze(v):
    if isinstance(v, (list, set, tuple)):
        return tuple(sorted(str(x) for x in v))
    return v


class Schedule:
    """A seeded, deterministic injection schedule over the site registry.

    Decisions are pure functions of (seed, rule index, site, canonical
    context) — see the module docstring.  The schedule also keeps the
    injection log (bounded) and per-rule fire counts."""

    def __init__(self, seed: int, rules: list[Rule]):
        self.seed = int(seed)
        self.rules = list(rules)
        self.aliases: dict[str, str] = {}
        self._log: list[dict] = []
        self._fired: dict[int, int] = {}       # rule index -> count
        self._lock = threading.Lock()          # sites fire on many threads

    # -- canonicalisation --------------------------------------------------

    def set_aliases(self, aliases: dict[str, str]) -> None:
        """Map ephemeral identifiers (host:port with OS-assigned ports)
        to stable labels (``node0``…): applied to ctx values before both
        decision hashing and logging, so seeded runs replay identically
        across processes."""
        self.aliases = dict(aliases)

    def _alias(self, v):
        return self.aliases.get(v, v) if isinstance(v, str) else v

    def _canon(self, ctx: dict) -> dict:
        return {k: self._alias(v) for k, v in sorted(ctx.items())}

    # -- decisions ---------------------------------------------------------

    def _decide(self, idx: int, rule: Rule, site: str, canon: dict) -> bool:
        if rule.pct >= 100.0:
            return True
        key = ",".join(f"{k}={v}" for k, v in canon.items())
        h = hashlib.sha256(
            f"{self.seed}|{idx}|{site}|{key}".encode()).digest()
        return int.from_bytes(h[:8], "big") % 1_000_000 \
            < int(rule.pct * 10_000)

    def _matches(self, rule: Rule, site: str, canon: dict) -> bool:
        if rule.site != site:
            return False
        if rule.rounds is not None:
            r = canon.get("round")
            if r is None or not (rule.rounds[0] <= r <= rule.rounds[1]):
                return False
        for k, want in rule.match:
            got = canon.get(k)
            if isinstance(want, tuple):
                if got not in want:
                    return False
            elif got != want:
                return False
        return True

    def plan(self, site: str, ctx: dict) -> list[tuple[str, Rule]]:
        """The (kind, rule) actions this hit triggers, in rule order.
        Consumes `times` budgets under the lock."""
        canon = self._canon(ctx)
        out: list[tuple[str, Rule]] = []
        for idx, rule in enumerate(self.rules):
            if not self._matches(rule, site, canon):
                continue
            if not self._decide(idx, rule, site, canon):
                continue
            with self._lock:
                fired = self._fired.get(idx, 0)
                if rule.times is not None and fired >= rule.times:
                    continue
                self._fired[idx] = fired + 1
            out.append((rule.kind, rule))
        return out

    # -- logging -----------------------------------------------------------

    def _note(self, site: str, kind: str, ctx: dict) -> None:
        entry = {"site": site, "kind": kind, **self._canon(ctx)}
        with self._lock:
            if len(self._log) < MAX_LOG:
                self._log.append(entry)
        try:
            from drand_tpu import metrics as M
            M.CHAOS_INJECTED.labels(site, kind).inc()
        except Exception:
            pass
        try:
            from drand_tpu import tracing
            with tracing.span("chaos.inject",
                              beacon_id=str(ctx.get("beacon_id", "")),
                              round_=ctx.get("round"),
                              site=site, kind=kind):
                pass
        except Exception:
            pass

    def injection_log(self) -> list[dict]:
        """Every injection, in arrival order (aliased ctx)."""
        with self._lock:
            return [dict(e) for e in self._log]

    def injection_summary(self) -> list[tuple]:
        """Sorted, deduplicated injections — the replay-comparison form.
        Arrival ORDER is scheduling-dependent (two nodes race on the
        loop); the SET of (site, kind, ctx) injections is the seeded
        schedule's deterministic output."""
        seen = {tuple(sorted((k, str(v)) for k, v in e.items()))
                for e in self.injection_log()}
        return sorted(seen)

    # -- firing ------------------------------------------------------------

    def fire_sync(self, site: str, exc: type | None, ctx: dict) -> None:
        for kind, rule in self.plan(site, ctx):
            self._note(site, kind, ctx)
            if kind == "delay":
                # sync sites run off the loop (store pool / crypto
                # thread) or model a slow-disk stall ON it; real, short
                time.sleep(min(rule.delay_s, 0.25))
            elif kind == "drop":
                raise PacketDropped(site)
            else:
                raise (exc or FaultInjectedError)(site)

    async def fire(self, site: str, exc: type | None, ctx: dict) -> None:
        import asyncio
        for kind, rule in self.plan(site, ctx):
            self._note(site, kind, ctx)
            if kind == "delay":
                # real-time delay, NOT the protocol clock: fake-clock
                # scenarios advance rounds explicitly, and a fault must
                # not deadlock against the advancing test
                await asyncio.sleep(min(rule.delay_s, 0.25))
            elif kind == "drop":
                raise PacketDropped(site)
            else:
                raise (exc or FaultInjectedError)(site)

    # -- spec form (env / control route / CLI) -----------------------------

    @classmethod
    def from_spec(cls, spec: "dict | str") -> "Schedule":
        """Build from the JSON spec form:
        ``{"seed": 7, "rules": [{"site": ..., "kind": ..., "pct": 50,
        "rounds": [3, 6], "match": {"src": "node2"}, "delay_s": 0.05,
        "times": 2}, ...], "aliases": {...}}``"""
        if isinstance(spec, str):
            spec = json.loads(spec)
        rules = [Rule.make(r["site"], r["kind"],
                           pct=float(r.get("pct", 100.0)),
                           rounds=tuple(r["rounds"]) if r.get("rounds")
                           else None,
                           match=r.get("match"),
                           delay_s=float(r.get("delay_s", 0.05)),
                           times=r.get("times"))
                 for r in spec.get("rules", [])]
        sched = cls(int(spec.get("seed", 0)), rules)
        if spec.get("aliases"):
            sched.set_aliases(dict(spec["aliases"]))
        return sched

    def to_spec(self) -> dict:
        return {"seed": self.seed,
                "rules": [r.to_spec() for r in self.rules],
                "aliases": dict(self.aliases)}


# -- module arming state ----------------------------------------------------

_schedule: Schedule | None = None


def arm(schedule: Schedule) -> None:
    """Install `schedule` as the process-wide active schedule."""
    global _schedule
    _schedule = schedule


def disarm() -> None:
    global _schedule
    _schedule = None


def is_armed() -> bool:
    return _schedule is not None


def active() -> Schedule | None:
    return _schedule


def arm_from_env() -> bool:
    """Arm from the ``DRAND_CHAOS`` env var (JSON schedule spec) if set.
    Called once at daemon start; returns True when something was armed."""
    spec = os.environ.get("DRAND_CHAOS", "")
    if not spec:
        return False
    arm(Schedule.from_spec(spec))
    return True


# -- the injection sites' entry points --------------------------------------

def failpoint_sync(site: str, exc: type | None = None, **ctx) -> None:
    """Synchronous site (store/thread seams).  Disabled ⇒ exact no-op."""
    sch = _schedule
    if sch is None:
        return
    sch.fire_sync(site, exc, ctx)


async def failpoint(site: str, exc: type | None = None, **ctx) -> None:
    """Async site (network/loop seams).  Disabled ⇒ exact no-op."""
    sch = _schedule
    if sch is None:
        return
    await sch.fire(site, exc, ctx)

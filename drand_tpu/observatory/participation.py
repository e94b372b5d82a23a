"""Signer participation ledger (ISSUE 19, tentpole surface 1).

The aggregation path always knew which signer indices stood behind every
recovered round — ``PartialCache`` keys partials by index and
``_recover`` Lagrange-combines exactly that set — but nothing recorded
it.  This ledger is the single book of record for signer liveness:

  - the Handler's accept seam feeds every VALID partial (on-time and
    late) through :meth:`note_partial` / :meth:`note_late`;
  - the aggregator's recovery hook feeds the recovered contributor set
    and the time-to-threshold through :meth:`note_recovery`.

From those two feeds it derives, per round, a contributor bitmap, the
threshold margin at recovery (``partials_at_recovery − t``), the FINAL
margin (distinct on-time ∪ late contributors − t, sealed when a later
round recovers — the robust "how close did we come" signal, since
recovery triggers exactly at threshold so the at-recovery margin is
almost always 0), and per-signer participation rates over a bounded
rolling window.

The watchdog's per-peer partial recency reads :attr:`newest` through
``Handler.partial_seen`` — the ledger IS that feed now, so the two
surfaces can never disagree (ISSUE 19 satellite: one accept-event feed).

Everything here runs on the event loop (accept path, aggregator hook,
watchdog tick, debug routes) — no locks needed.
"""

from __future__ import annotations

from collections import OrderedDict, deque
from dataclasses import dataclass, field

from drand_tpu import log as dlog
from drand_tpu import metrics as M

log = dlog.get("observatory", "participation")

DEFAULT_WINDOW = 256
# keep at most this many un-recovered rounds of on-time observations
# (partials for rounds that never recover — e.g. during a stall — must
# not grow the ledger unboundedly)
MAX_OPEN_ROUNDS = 64


@dataclass
class RoundRecord:
    """One recovered round's participation picture."""

    round: int
    on_time: set[int] = field(default_factory=set)   # accepted pre-recovery
    recovered: tuple[int, ...] = ()                  # indices in the combine
    late: set[int] = field(default_factory=set)      # accepted post-recovery
    count_at_recovery: int = 0
    margin_at_recovery: int = 0
    time_to_threshold_s: float = 0.0
    final_margin: int | None = None                  # sealed by a later round

    @property
    def contributors(self) -> set[int]:
        return self.on_time | self.late | set(self.recovered)

    def to_dict(self) -> dict:
        return {
            "round": self.round,
            "contributors": sorted(self.contributors),
            "recovered": sorted(self.recovered),
            "late": sorted(self.late),
            "count_at_recovery": self.count_at_recovery,
            "margin_at_recovery": self.margin_at_recovery,
            "time_to_threshold_s": round(self.time_to_threshold_s, 6),
            "final_margin": self.final_margin,
        }


class ParticipationLedger:
    """Bounded rolling book of per-round signer participation."""

    def __init__(self, group_size: int, threshold: int,
                 beacon_id: str = "default", own_index: int = -1,
                 window: int = DEFAULT_WINDOW):
        self.group_size = group_size
        self.threshold = threshold
        self.beacon_id = beacon_id
        self.own_index = own_index
        self.window = max(int(window), 1)
        # newest round a VALID partial (or recovery membership) was seen
        # from, per signer index — the watchdog's missed-partials feed
        self.newest: dict[int, int] = {}
        # on-time observations for rounds not yet recovered
        self._open: dict[int, set[int]] = {}
        # recovered-but-not-finalized + finalized records, newest last
        self._records: "OrderedDict[int, RoundRecord]" = OrderedDict()
        # finalized window: per-record contributor sets, oldest first
        self._final: deque[tuple[int, frozenset[int]]] = deque()
        self._contrib_count: dict[int, int] = {}     # signer -> hits in window
        self._miss_streak: dict[int, int] = {}       # consecutive misses
        self.rounds_recovered = 0
        self.late_partials = 0
        self.last_final_margin: int | None = None
        self.last_time_to_threshold_s: float | None = None

    # -- feeds (Handler accept seam + aggregator recovery hook) -------------

    def note_partial(self, idx: int, round_: int) -> None:
        """A VALID partial accepted for a live (unsettled) round."""
        if round_ in self._records:
            # accepted as live, but the round recovered from the others
            # while this one's signature was being checked
            self.note_late(idx, round_)
            return
        self.newest[idx] = max(round_, self.newest.get(idx, 0))
        obs = self._open.get(round_)
        if obs is None:
            if len(self._open) >= MAX_OPEN_ROUNDS:
                self._open.pop(min(self._open), None)
            obs = self._open[round_] = set()
        obs.add(idx)

    def note_late(self, idx: int, round_: int) -> None:
        """A VALID partial that arrived after its round settled."""
        self.newest[idx] = max(round_, self.newest.get(idx, 0))
        self.late_partials += 1
        rec = self._records.get(round_)
        if rec is not None and rec.final_margin is None:
            rec.late.add(idx)

    def note_recovery(self, round_: int, indices, count: int,
                      elapsed_s: float) -> None:
        """Round ``round_`` recovered from ``count`` cached partials whose
        signer indices are ``indices``; ``elapsed_s`` is seconds from the
        round's scheduled time to recovery (time-to-threshold)."""
        recovered = tuple(sorted(int(i) for i in indices))
        for i in recovered:
            self.newest[i] = max(round_, self.newest.get(i, 0))
        rec = RoundRecord(
            round=round_,
            on_time=self._open.pop(round_, set()),
            recovered=recovered,
            count_at_recovery=count,
            margin_at_recovery=count - self.threshold,
            time_to_threshold_s=max(elapsed_s, 0.0))
        self._records[round_] = rec
        self._records.move_to_end(round_)
        self.rounds_recovered += 1
        self.last_time_to_threshold_s = rec.time_to_threshold_s
        M.TIME_TO_THRESHOLD.labels(self.beacon_id).observe(
            rec.time_to_threshold_s)
        # observations for rounds at/below the new tip can never grow
        self._open = {r: s for r, s in self._open.items() if r > round_}
        self._finalize_before(round_)
        while len(self._records) > 2 * self.window:
            self._records.popitem(last=False)

    # -- finalization (a later recovery seals earlier rounds) ----------------

    def _finalize_before(self, round_: int) -> None:
        for r in list(self._records):
            rec = self._records[r]
            if r >= round_ or rec.final_margin is not None:
                continue
            contributors = frozenset(rec.contributors)
            rec.final_margin = len(contributors) - self.threshold
            self.last_final_margin = rec.final_margin
            self._final.append((r, contributors))
            for i in contributors:
                self._contrib_count[i] = self._contrib_count.get(i, 0) + 1
            for i in range(self.group_size):
                if i in contributors:
                    self._miss_streak[i] = 0
                else:
                    self._miss_streak[i] = self._miss_streak.get(i, 0) + 1
            while len(self._final) > self.window:
                _, old = self._final.popleft()
                for i in old:
                    n = self._contrib_count.get(i, 0) - 1
                    if n <= 0:
                        self._contrib_count.pop(i, None)
                    else:
                        self._contrib_count[i] = n
            M.THRESHOLD_MARGIN.labels(self.beacon_id).set(rec.final_margin)
            for i in range(self.group_size):
                M.SIGNER_PARTICIPATION.labels(
                    self.beacon_id, str(i)).set(self.rate(i))

    # -- derived views -------------------------------------------------------

    def is_counted(self, idx: int, round_: int) -> bool:
        """True when this signer is already on the books for this round
        — the Handler's late-path dedup (one signature check per
        (signer, round), ever)."""
        rec = self._records.get(round_)
        if rec is None:
            return False
        return idx in rec.on_time or idx in rec.late or idx in rec.recovered

    def rate(self, idx: int) -> float:
        """Fraction of the finalized window this signer contributed to."""
        n = len(self._final)
        if n == 0:
            return 1.0            # nothing judged yet: presume innocent
        return self._contrib_count.get(idx, 0) / n

    def miss_streak(self, idx: int) -> int:
        return self._miss_streak.get(idx, 0)

    def missing_signers(self, min_rounds: int = 3) -> list[int]:
        """Indices absent from the last ``min_rounds`` finalized rounds
        (chronically missing — the watchdog's loud-transition feed)."""
        if len(self._final) < min_rounds:
            return []
        return sorted(i for i in range(self.group_size)
                      if self._miss_streak.get(i, 0) >= min_rounds)

    def snapshot(self, limit: int = 32) -> dict:
        recent = [rec.to_dict()
                  for rec in list(self._records.values())[-limit:]]
        return {
            "beacon_id": self.beacon_id,
            "group_size": self.group_size,
            "threshold": self.threshold,
            "own_index": self.own_index,
            "window": self.window,
            "rounds_recovered": self.rounds_recovered,
            "finalized": len(self._final),
            "late_partials": self.late_partials,
            "last_final_margin": self.last_final_margin,
            "last_time_to_threshold_s": self.last_time_to_threshold_s,
            "signers": {
                str(i): {
                    "rate": round(self.rate(i), 4),
                    "newest_round": self.newest.get(i, 0),
                    "miss_streak": self.miss_streak(i),
                } for i in range(self.group_size)},
            "missing": self.missing_signers(),
            "rounds": recent,
        }

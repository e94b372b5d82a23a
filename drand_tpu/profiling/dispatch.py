"""Dispatch flight recorder: per-dispatch records around batched seams.

Every batched crypto seam pads the requested work up to a bucket shape
(drand_tpu/verify.py `_bucket`, DeviceBackend's partial buckets,
parallel/sharded per-device rounding) — a chronically under-filled
bucket wastes device time that no aggregate counter surfaces.  This
module keeps a bounded ring of per-dispatch records capturing the
requested n, the chosen bucket, the fill ratio, the padding-rounds
wasted, queue-wait vs the host's wall time around the call (dispatch
plus blocking resolve: a host clock, NOT device time, which only a
profiler trace gives), and the amortized per-round cost — the flight-recorder view behind `/debug/dispatch`, the Watchdog
"device" snapshot key, and the `drand_dispatch_*` metrics.

Seams:
  verify     Verifier.verify_batch_async (chain catch-up batches)
  partials   DeviceBackend/HostBackend.verify_partials (one round)
  rounds     DeviceBackend.verify_partials_rounds (multi-round table)
  sharded    parallel/sharded.py multi-device dispatch
  aggregate  AsyncPartialVerifier coalescing (queue-wait measured here)
  native     native C++ single-verify (n = bucket = 1)

Recording is O(1), lock-guarded, and never raises into the caller — a
broken metrics backend must not fail a verification.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from dataclasses import dataclass, field

SEAMS = ("verify", "partials", "rounds", "sharded", "aggregate", "native")


@dataclass
class DispatchRecord:
    """One batched dispatch through a padded seam."""
    seam: str
    n: int                      # rounds/partials actually requested
    bucket: int                 # padded dispatch size the kernel saw
    host_wall_s: float          # host wall seconds inside the backend call
    queue_wait_s: float = 0.0   # enqueue -> dispatch (coalescing seams)
    wall: float = 0.0           # wall-clock stamp (operator correlation)
    attrs: dict = field(default_factory=dict)

    @property
    def fill_ratio(self) -> float:
        return (self.n / self.bucket) if self.bucket > 0 else 0.0

    @property
    def padding_rounds(self) -> int:
        return max(self.bucket - self.n, 0)

    @property
    def us_per_round(self) -> float:
        """Amortized host-wall microseconds per REQUESTED round — padding
        makes this worse than host_wall_s/bucket, which is the point."""
        return (self.host_wall_s / self.n * 1e6) if self.n > 0 else 0.0

    def to_dict(self) -> dict:
        return {
            "seam": self.seam, "n": self.n, "bucket": self.bucket,
            "fill_ratio": round(self.fill_ratio, 4),
            "padding_rounds": self.padding_rounds,
            "host_wall_s": round(self.host_wall_s, 9),
            "queue_wait_s": round(self.queue_wait_s, 9),
            "us_per_round": round(self.us_per_round, 3),
            "wall": round(self.wall, 6),
            "attrs": dict(self.attrs),
        }


class Enqueued:
    """One device dispatch between its enqueue and its resolve, held by
    its resolver alone: a resolver dropped unresolved (a catch-up that
    failed discards the segments behind the bad one) takes it along."""

    __slots__ = ("owner", "__weakref__")

    def __init__(self, owner):
        self.owner = owner


class DispatchRecorder:
    """Bounded ring of DispatchRecords plus per-seam running totals, and
    the one process-wide count of device dispatches enqueued and not yet
    resolved (`enqueue`/`resolved`): every verifier of a daemon feeds one
    device queue, and none of them sees the others' programs on it.

    Thread-safe: dispatches land from the event loop, the crypto worker
    thread, and batched-verify resolvers alike."""

    def __init__(self, maxlen: int = 2048):
        self._ring: deque[DispatchRecord] = deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self._in_flight: weakref.WeakSet[Enqueued] = weakref.WeakSet()
        # seam -> running totals since process start (the ring forgets;
        # the totals are what the watchdog and perf deltas read)
        self._totals: dict[str, dict] = {}

    def record(self, seam: str, n: int, bucket: int, host_wall_s: float,
               queue_wait_s: float = 0.0, **attrs) -> DispatchRecord:
        rec = DispatchRecord(seam=seam, n=int(n), bucket=int(bucket),
                             host_wall_s=float(host_wall_s),
                             queue_wait_s=float(queue_wait_s),
                             wall=_wall_stamp(), attrs=attrs)
        with self._lock:
            self._ring.append(rec)
            tot = self._totals.setdefault(seam, {
                "dispatches": 0, "rounds": 0, "padding_rounds": 0,
                "host_wall_s": 0.0, "queue_wait_s": 0.0})
            tot["dispatches"] += 1
            tot["rounds"] += rec.n
            tot["padding_rounds"] += rec.padding_rounds
            tot["host_wall_s"] += rec.host_wall_s
            tot["queue_wait_s"] += rec.queue_wait_s
        try:
            from drand_tpu import metrics as M
            M.DISPATCH_SECONDS.labels(seam, str(rec.bucket)) \
                .observe(rec.host_wall_s)
            M.DISPATCH_FILL_RATIO.labels(seam).set(rec.fill_ratio)
            if rec.padding_rounds:
                M.DISPATCH_PADDING.labels(seam).inc(rec.padding_rounds)
        except Exception:
            pass    # metrics must never fail a dispatch
        return rec

    def enqueue(self, owner) -> tuple[Enqueued, int, int]:
        """Count a dispatch that `owner` (a verifier) is about to put on
        the device queue: (its token, `in_flight`, `behind_other`).
        `in_flight` is how many dispatches of the whole process were
        enqueued before it and are not resolved yet, `behind_other` 1
        where at least one of those is another owner's, else 0.  The
        resolver keeps the token and gives it back to `resolved`."""
        token = Enqueued(owner)
        with self._lock:
            ahead = list(self._in_flight)
            self._in_flight.add(token)
        return token, len(ahead), int(any(t.owner is not owner
                                          for t in ahead))

    def resolved(self, token: Enqueued) -> None:
        with self._lock:
            self._in_flight.discard(token)

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def records(self, seam: str | None = None,
                limit: int = 100) -> list[DispatchRecord]:
        with self._lock:
            recs = list(self._ring)
        if seam is not None:
            recs = [r for r in recs if r.seam == seam]
        return recs[-limit:]

    def seam_summary(self) -> dict:
        """Per-seam totals with derived efficiency numbers — the view a
        chronically under-filled bucket is visible in."""
        with self._lock:
            totals = {seam: dict(tot) for seam, tot in self._totals.items()}
        for seam, tot in totals.items():
            dispatched = tot["rounds"] + tot["padding_rounds"]
            tot["avg_fill_ratio"] = round(
                tot["rounds"] / dispatched, 4) if dispatched else 0.0
            tot["amortized_us_per_round"] = round(
                tot["host_wall_s"] / tot["rounds"] * 1e6, 3) \
                if tot["rounds"] else 0.0
            tot["host_wall_s"] = round(tot["host_wall_s"], 6)
            tot["queue_wait_s"] = round(tot["queue_wait_s"], 6)
        return totals

    def snapshot(self, limit: int = 50) -> dict:
        with self._lock:
            in_flight = len(self._in_flight)
        return {
            "in_flight": in_flight,
            "seams": self.seam_summary(),
            "recent": [r.to_dict() for r in self.records(limit=limit)][::-1],
        }

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._totals.clear()
            self._in_flight.clear()


def _wall_stamp() -> float:
    """Wall stamp for operator correlation only (never a duration);
    routed through tracing's injectable clock so fake-clock tests stay
    coherent across spans and dispatch records."""
    try:
        from drand_tpu import tracing
        return tracing._wall()
    except Exception:
        return time.time()  # lint: disable=no-wall-clock


DISPATCH = DispatchRecorder()


def record_dispatch(seam: str, n: int, bucket: int, host_wall_s: float,
                    queue_wait_s: float = 0.0, **attrs) -> None:
    """Module-level convenience used by the instrumented seams; never
    raises (the flight recorder is an observer, not a participant)."""
    try:
        DISPATCH.record(seam, n, bucket, host_wall_s,
                        queue_wait_s=queue_wait_s, **attrs)
    except Exception:
        pass


class timed_dispatch:
    """Context manager timing one device call for a seam:

        with timed_dispatch("verify", n=n, bucket=m):
            ok = kernel(...)

    `.extend()` lets split dispatch/resolve paths add the resolver's
    blocking wall before the record is cut (see verify.py)."""

    def __init__(self, seam: str, n: int, bucket: int,
                 queue_wait_s: float = 0.0, **attrs):
        self.seam = seam
        self.n = n
        self.bucket = bucket
        self.queue_wait_s = queue_wait_s
        self.attrs = attrs
        self._t0 = 0.0
        self.host_wall_s = 0.0

    def __enter__(self) -> "timed_dispatch":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.host_wall_s = time.perf_counter() - self._t0
        record_dispatch(self.seam, self.n, self.bucket, self.host_wall_s,
                        queue_wait_s=self.queue_wait_s, **self.attrs)

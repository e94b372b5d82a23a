"""Startup integrity scan + self-healing repair (ISSUE 15).

The chain store is the only durable state a beacon node has, and until
this module nothing verified what sqlite hands back after a kill -9, a
torn write, or disk bit-rot.  The reference daemon treats startup chain
validation as a first-class operation (boltdb semantics, SURVEY §2
`chain.Store`); here the batched TPU verifier makes it nearly free —
full-chain BLS validation in 16k-round segments is exactly the workload
the catch-up kernels were built for, so crash recovery is a catch-up
sync against your own disk.

Three layers, composed by `startup_recovery` at daemon boot and by
`drand-tpu util fsck` offline:

  `scan_store`   — stream the stored chain once: codec-decode validation
                   (torn writes / bit-rot surface per-row, never abort
                   the scan), round contiguity, chained `previous_sig`
                   linkage, and — when a verifier is given — full BLS
                   verification through
                   `ChainVerifier.verify_packed_segment_async`.
                   Produces a typed `IntegrityReport`.
  `repair_store` — quarantine every damaged round to the sidecar table
                   (forensics: nothing is silently deleted) and roll the
                   tip back to the last verified prefix.
  re-sync        — the caller hands `(verified_tip + 1, old_tip)` to
                   `SyncManager.request_sync`, so the rolled-back suffix
                   heals from peers through the existing chunked wire.

This module must stay importable without jax (the fsck CLI runs in the
jax-free lane): the structural scan uses only the codec + numpy, and
the BLS stage is reached only when a caller passes a verifier.
"""

from __future__ import annotations

import asyncio
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from drand_tpu import log as dlog
from drand_tpu.chain import codec as row_codec
from drand_tpu.chain.beacon import GENESIS_ROUND, Beacon
from drand_tpu.chain.segment import PackedBeacons, pack_rows

log = dlog.get("chain.recovery")

# one batched-verify dispatch per this many stored rounds — the
# throughput bucket the catch-up kernels are warmed for (BENCH_sync) —
# or per what the verifier charges for that many where it says
# (`rows_charged`: its program times its mesh, 65,536 on four chips), as
# the catch-up cuts its segments (`SyncManager._fetch_stage`)
SCAN_SEGMENT_ROUNDS = 16384
# raw rows fetched per worker-thread sqlite crossing
SCAN_READ_BATCH = 4096
# flushes a scan keeps dispatched and not yet settled while it reads and
# decodes on: one runs on the device, the next is packed and enqueued
# behind it before the scan waits for the first
SCAN_DISPATCH_AHEAD = 1


@dataclass
class IntegrityReport:
    """Typed outcome of one integrity scan.

    `verified_tip` is the last round of the longest clean prefix: every
    round at or below it decoded, is contiguous from the first stored
    round, links to its predecessor, and (when `verify_checked`) carries
    a valid BLS signature.  −1 means no clean prefix exists (empty
    store, or damage at the very first row)."""

    beacon_id: str = ""
    path: str = ""
    scanned: int = 0                 # rows examined
    first_round: int = -1            # first stored round (−1 if empty)
    tip_round: int = -1              # last stored round (−1 if empty)
    verified_tip: int = -1
    corrupt: list[int] = field(default_factory=list)      # decode failures
    missing: list[tuple[int, int]] = field(default_factory=list)  # gaps
    unlinked: list[int] = field(default_factory=list)     # prev-sig breaks
    bad_sigs: list[int] = field(default_factory=list)     # BLS failures
    verify_checked: bool = False     # BLS stage ran (a verifier was given)
    elapsed_s: float = 0.0

    @property
    def ok(self) -> bool:
        return not (self.corrupt or self.missing or self.unlinked
                    or self.bad_sigs)

    @property
    def damaged_rounds(self) -> list[int]:
        """Every round that must leave the live chain (quarantine set) —
        missing ranges have no rows to move, so they are not included."""
        return sorted(set(self.corrupt) | set(self.unlinked)
                      | set(self.bad_sigs))

    def to_dict(self) -> dict:
        return {
            "beacon_id": self.beacon_id,
            "path": self.path,
            "ok": self.ok,
            "scanned": self.scanned,
            "first_round": self.first_round,
            "tip_round": self.tip_round,
            "verified_tip": self.verified_tip,
            "corrupt": list(self.corrupt),
            "missing": [[a, b] for (a, b) in self.missing],
            "unlinked": list(self.unlinked),
            "bad_sigs": list(self.bad_sigs),
            "verify_checked": self.verify_checked,
            "elapsed_s": round(self.elapsed_s, 6),
        }


def _flush_rounds(verifier) -> int:
    """Good rows a verified segment where the caller names no number:
    the program that SCAN_SEGMENT_ROUNDS rows are padded into, full."""
    rows_charged = getattr(verifier, "rows_charged", None)
    if rows_charged is None:
        return SCAN_SEGMENT_ROUNDS
    return rows_charged(SCAN_SEGMENT_ROUNDS)


def dispatch_rows(verifier, beacons: list[Beacon]):
    """Rows that are no contiguous run (what damage leaves of a flush,
    the replacements of a repair) as ONE batch, each row over its own
    `previous_sig`: a zero-arg resolver of bool[B].  Dispatched here
    where the verifier can (`ChainVerifier.verify_beacons_async`: one
    program for all of them), else verified when the resolver is
    called."""
    dispatch = getattr(verifier, "verify_beacons_async", None)
    if dispatch is not None:
        return dispatch(beacons)
    return lambda: verifier.verify_beacons(beacons)


async def scan_store(store, verifier=None, *, beacon_id: str = "",
                     segment_rounds: int | None = None,
                     read_batch: int = SCAN_READ_BATCH,
                     on_progress=None,
                     up_to: int | None = None) -> IntegrityReport:
    """One streaming pass over the stored chain -> IntegrityReport.
    Rows above `up_to`, where one is given, are neither read nor judged
    (`drand util check --up-to`): the report's tip is the last row at
    or below it.

    `store` is the UNDECORATED SqliteStore (its `raw_rows` feed sees
    damaged blobs instead of dying on them).  With `verifier=None` only
    the structural checks run (decode, contiguity, linkage) — the
    jax-free fsck mode; with a ChainVerifier the good rows additionally
    stream through the batched device verifier in `segment_rounds`
    segments (left out: what the verifier charges for
    SCAN_SEGMENT_ROUNDS, its program full on every device).  All sqlite
    reads and every potentially-blocking verifier dispatch happen in
    worker threads; the event loop stays live.

    A flush dispatches one segment ahead (SCAN_DISPATCH_AHEAD): it
    packs its rows, dispatches them (the resolver comes back once the
    work is enqueued), and only then settles the flush BEFORE it: waits
    for that one's verdicts and files its `bad_sigs`.  The row loop then
    reads and decodes the next segment while the device runs this one.
    After the last row what is pending is dispatched and the flushes
    still out are settled, oldest first: the report is the sequential
    scan's, list for list.  Whatever ends the scan (a dispatch, a
    resolver or the row loop raising, the task cancelled), every
    resolver handed out has been called before `scan_store` returns.

    One trace a scan: the root `store.scan` (scanned, flagged;
    `overlapped`: flushes that dispatched with another in flight, 3 of 4
    over 65,536 rounds on one chip); under it a `scan.read` for every
    `raw_rows` batch and a `scan.decode` for that batch's per-row loop
    (rows, corrupt and unlinked counted as attributes, never a span a
    row); a `scan.flush` (rows; `in_flight`: flushes dispatched and not
    settled as this one dispatched, 0 on the first and 1 after) for
    every verified segment, holding `scan.pack` (`pack_rows`), the
    verifier's own `verify.dispatch`, and `scan.verify_wait` where the
    scan blocks: around the wait for the flush before, over its
    `verify.resolve`; the last flush's `scan.verify_wait` lies under the
    root.  The verifier's `verify.segment` runs from a segment's
    dispatch to its resolver, so it outlives the flush it began under,
    as it crosses the catch-up's stages.  While the root is open the
    event loop's lag is counted on it (`tracing.loop_watched`).
    """
    from drand_tpu import tracing
    with tracing.span("store.scan", beacon_id=beacon_id,
                      verify=verifier is not None) as root, \
            tracing.loop_watched(root):
        ahead = _DispatchedAhead(verifier)
        try:
            report = await _scan_store(store, ahead, beacon_id,
                                       segment_rounds, read_batch,
                                       on_progress, up_to)
        except BaseException:
            await ahead.abandon()
            raise
        root.set(scanned=report.scanned, flagged=len(report.damaged_rounds),
                 overlapped=ahead.overlapped)
    return report


async def _in_worker(fn, *args):
    """`asyncio.to_thread` that a cancelled caller waits out: a thread
    cannot be stopped, and what it dispatches has to be on record
    before the scan cleans up after itself."""
    work = asyncio.ensure_future(asyncio.to_thread(fn, *args))
    try:
        return await asyncio.shield(work)
    except asyncio.CancelledError:
        await asyncio.wait([work])
        if not work.cancelled():
            work.exception()        # retrieved: the cancellation wins
        raise


class _DispatchedAhead:
    """The flushes a scan has dispatched and not settled, oldest first.
    A flush is ONE dispatch: its (rounds, resolver), taken off as the
    resolver is called."""

    def __init__(self, verifier):
        self.verifier = verifier
        self.flushes: deque[deque] = deque()
        self.overlapped = 0     # flushes dispatched with another out

    def __len__(self) -> int:
        return len(self.flushes)

    async def dispatch(self, items, rows) -> None:
        """Enqueue a flush behind what is out: `rows` are its (round,
        sig, prev), `items` what `pack_rows` made of them."""
        self.overlapped += bool(self.flushes)
        # on record before its dispatch: a resolver handed out is then
        # found by whatever ends the scan
        self.flushes.append(deque())
        await _in_worker(self._dispatch, items, rows, self.flushes[-1])

    def _dispatch(self, items, rows, resolvers: deque) -> None:
        """Either way the batch checks pure signature validity over
        exactly the bytes on disk, each row over its own STORED prev:
        linkage against the actual predecessor sig was already judged
        structurally."""
        if len(items) == 1 and isinstance(items[0], PackedBeacons):
            # the rows are one run that links from its first stored
            # prev on: they stay in their matrix
            item = items[0]
            resolvers.append((item.rounds(),
                              self.verifier.verify_packed_segment_async(
                                  item, item.first_prev)))
            return
        # damage has taken rows out of the flush: what is left is
        # several runs, and goes as one batch all the same (the device
        # is charged by the program, not by the run)
        resolvers.append((
            np.array([r for r, _sig, _prev in rows], dtype=np.uint64),
            dispatch_rows(self.verifier,
                          [Beacon(round=r, signature=sig, previous_sig=prev)
                           for r, sig, prev in rows])))

    async def settle_oldest(self) -> list[int]:
        """Wait for the oldest flush's verdicts -> its bad rounds.
        `scan.verify_wait` is opened here, where the scan blocks, and
        not in the worker."""
        from drand_tpu import tracing
        with tracing.span("scan.verify_wait"):
            bad = await _in_worker(self._settle, self.flushes[0])
        self.flushes.popleft()
        return bad

    def _settle(self, resolvers: deque) -> list[int]:
        bad: list[int] = []
        while resolvers:
            rounds, resolver = resolvers.popleft()
            ok = np.asarray(resolver(), dtype=bool)
            bad.extend(int(r) for r in rounds[~ok])
        return bad

    async def abandon(self) -> None:
        """Call every resolver still out: the verifier's `verify.segment`
        is closed by its resolver alone, and none stays open behind a
        scan that failed or was cancelled."""
        if self.flushes:
            await _in_worker(self._abandon)

    def _abandon(self) -> None:
        for resolvers in self.flushes:
            while resolvers:
                _, resolver = resolvers.popleft()
                try:
                    resolver()
                except Exception:
                    log.debug("an abandoned segment's resolver raised",
                              exc_info=True)


async def _scan_store(store, ahead: _DispatchedAhead, beacon_id: str,
                      segment_rounds: int | None, read_batch: int,
                      on_progress, up_to: int | None) -> IntegrityReport:
    from drand_tpu import tracing
    began = time.perf_counter()
    verifier = ahead.verifier
    report = IntegrityReport(beacon_id=beacon_id,
                             path=getattr(store, "path", ""),
                             verify_checked=verifier is not None)
    expected: int | None = None      # next contiguous round
    prev_good: tuple[int, bytes] | None = None   # (round, sig) last good row
    pending: list[tuple[int, bytes, bytes]] = []  # BLS backlog (r, sig, prev)

    async def flush_bls() -> None:
        if verifier is None or not pending:
            return
        with tracing.span("scan.flush", rows=len(pending),
                          in_flight=len(ahead)):
            t0 = time.perf_counter()
            rows = pending[:]
            items = list(pack_rows(
                rows, max_chunk=segment_rounds or len(rows)))
            tracing.record_span("scan.pack", t0, time.perf_counter(),
                                items=len(items))
            pending.clear()
            await ahead.dispatch(items, rows)
            if len(ahead) > SCAN_DISPATCH_AHEAD:
                report.bad_sigs.extend(await ahead.settle_oldest())

    next_round = GENESIS_ROUND
    last_read = False           # this batch reached `up_to`
    while not last_read:
        t0 = time.perf_counter()
        rows = await asyncio.to_thread(store.raw_rows, next_round, read_batch)
        tracing.record_span("scan.read", t0, time.perf_counter(),
                            from_round=next_round, rows=len(rows))
        if up_to is not None and rows and rows[-1][0] >= up_to:
            rows = [row for row in rows if row[0] <= up_to]
            last_read = True
        if not rows:
            break
        flagged = len(report.corrupt), len(report.unlinked)
        with tracing.span("scan.decode", rows=len(rows)) as decode:
            for r, blob in rows:
                report.scanned += 1
                if report.first_round < 0:
                    report.first_round = r
                report.tip_round = r
                if expected is not None and r > expected:
                    report.missing.append((expected, r - 1))
                expected = r + 1
                try:
                    decoded_round, sig, prev = row_codec.decode_fields(blob)
                    if decoded_round != r:
                        raise row_codec.CodecError(
                            f"row decodes to round {decoded_round}")
                except row_codec.CodecError:
                    report.corrupt.append(r)
                    prev_good = None
                    continue
                if prev and prev_good is not None \
                        and prev_good[0] == r - 1 and prev != prev_good[1]:
                    # the stored prev contradicts the actual predecessor
                    # sig: damage localized to THIS row (its sig may still
                    # be the true chain sig, so it stays a linkage anchor
                    # for r+1)
                    report.unlinked.append(r)
                    prev_good = (r, sig)
                    continue
                prev_good = (r, sig)
                if r != GENESIS_ROUND:   # genesis is an anchor, not a sig
                    pending.append((r, sig, prev))
                if len(pending) >= (segment_rounds or SCAN_SEGMENT_ROUNDS):
                    if segment_rounds is None and verifier is not None:
                        # asked only now, with a device segment's worth
                        # in hand: the answer may bring the verifier's
                        # device tier up, which a short store, verified
                        # on the host, never needs
                        segment_rounds = _flush_rounds(verifier)
                    if len(pending) >= (segment_rounds
                                        or SCAN_SEGMENT_ROUNDS):
                        await flush_bls()
            decode.set(corrupt=len(report.corrupt) - flagged[0],
                       unlinked=len(report.unlinked) - flagged[1])
        if on_progress is not None:
            on_progress(report.tip_round)
        next_round = rows[-1][0] + 1
    await flush_bls()
    while len(ahead):
        report.bad_sigs.extend(await ahead.settle_oldest())

    problems = (report.corrupt + report.unlinked + report.bad_sigs
                + [a for (a, _) in report.missing])
    if report.scanned == 0:
        report.verified_tip = -1
    elif problems:
        report.verified_tip = min(problems) - 1
    else:
        report.verified_tip = report.tip_round
    report.elapsed_s = time.perf_counter() - began
    return report


def repair_store(store, report: IntegrityReport,
                 truncate: bool = True) -> dict:
    """Quarantine + rollback (sync; callers off-loop via to_thread).

    Damaged rounds move to the quarantine sidecar table per-category
    (reason strings are the forensic record), then every live row past
    `verified_tip` rolls back too — the suffix above the last verified
    prefix cannot be trusted even where individually well-formed,
    because its linkage anchor is gone.  Returns a summary dict."""
    moved = 0
    for rounds, reason in ((report.corrupt, "corrupt-row"),
                           (report.unlinked, "unlinked-prev-sig"),
                           (report.bad_sigs, "bad-signature")):
        if rounds:
            moved += store.quarantine_rounds(rounds, reason)
    truncated = 0
    if truncate:
        truncated = store.truncate_after(report.verified_tip,
                                         "rollback-past-verified-prefix")
    total = moved + truncated
    if total:
        try:
            from drand_tpu import metrics as M
            M.STORE_QUARANTINED.inc(total)
        except Exception:
            pass
        log.warning("store repair: quarantined %d damaged + %d rolled-back "
                    "rows; tip now %d", moved, truncated,
                    report.verified_tip)
    return {"quarantined": moved, "truncated": truncated,
            "verified_tip": report.verified_tip}


async def startup_recovery(store, verifier, *, beacon_id: str = "",
                           segment_rounds: int | None = None,
                           ) -> tuple[IntegrityReport, dict | None]:
    """Boot-time scan + (if damaged) repair, with the scan's and the
    repair's spans and the `drand_store_integrity` gauge.  Returns
    (report, repair summary or None).  The CALLER owns what follows a repair: rebuilding the
    engine over the rolled-back store and queueing the re-sync of
    `(verified_tip + 1 .. old tip)` from peers."""
    from drand_tpu import tracing
    report = await scan_store(store, verifier, beacon_id=beacon_id,
                              segment_rounds=segment_rounds)
    try:
        from drand_tpu import metrics as M
        M.STORE_INTEGRITY.labels(beacon_id or "default").set(
            1 if report.ok else 0)
    except Exception:
        pass
    if report.ok:
        log.info("store integrity: %d rows clean, tip %d (%.3fs%s)",
                 report.scanned, report.tip_round, report.elapsed_s,
                 "" if report.verify_checked else ", structural only")
        return report, None
    log.warning(
        "store integrity: damage found — %d corrupt, %d unlinked, %d bad "
        "sigs, %d missing ranges; verified prefix ends at %d",
        len(report.corrupt), len(report.unlinked), len(report.bad_sigs),
        len(report.missing), report.verified_tip)
    with tracing.span("store.repair", beacon_id=beacon_id):
        summary = await asyncio.to_thread(repair_store, store, report)
    return report, summary

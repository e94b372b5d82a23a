"""Beacon chain storage: sqlite-backed store + decorator stack.

Counterpart of `chain/boltdb/store.go` (bbolt KV, one bucket keyed by
big-endian round) and the decorator pipeline built in
`chain/beacon/chain.go:41-90`:

  sqlite -> AppendStore (monotonic round+1, store.go:31-56)
         -> SchemeStore (chained/unchained prev-sig handling, store.go:59-97)
         -> DiscrepancyStore (latency metrics, store.go:99-133)
         -> CallbackStore (fan-out to watchers, store.go:136-214)

sqlite3 replaces bbolt: same embedded, single-file, transactional semantics,
already in the Python stdlib (SURVEY.md §7 step 4).
"""

from __future__ import annotations

import functools
import os
import sqlite3
import threading
import time as _time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Iterator, Optional

from drand_tpu.chain import codec as row_codec
from drand_tpu.chain.beacon import Beacon

# sqlite cursors yield one row per C call; batching the fetch amortizes
# the per-row crossing on deep scans (iter_range over a 16384-round
# segment) without holding more than this many decoded rows at once
_FETCH_BATCH = 1024

# Rows a statement of `put_many`: 998 bound variables, under the 999 of
# sqlite's oldest SQLITE_MAX_VARIABLE_NUMBER.
_ROWS_A_STATEMENT = 499


@functools.cache
def _insert_rows_sql(n: int) -> str:
    return ("INSERT OR REPLACE INTO beacons (round, data) VALUES "
            + ", ".join(["(?, ?)"] * n))


# PRAGMA synchronous policy (DRAND_TPU_STORE_SYNC): NORMAL is the WAL
# crash-safe default — with WAL journaling, NORMAL survives process kill
# (kill -9) with transaction atomicity intact; FULL additionally survives
# OS/power loss at the cost of an fsync per commit.  OFF is for
# throwaway benchmark stores only.
SYNC_ENV = "DRAND_TPU_STORE_SYNC"
_SYNC_LEVELS = ("OFF", "NORMAL", "FULL", "EXTRA")


class StoreError(Exception):
    pass


class BeaconNotFound(StoreError):
    pass


class CorruptRowError(StoreError):
    """A stored row failed to decode (torn write, bit-rot) or decoded to
    a beacon whose round disagrees with its key.  Carries the offending
    round so readers (serve_sync_chain, the integrity scan) can stop at
    — or quarantine — exactly the damaged row instead of aborting with a
    bare CodecError."""

    def __init__(self, round_: int, detail: str):
        super().__init__(f"corrupt row at round {round_}: {detail}")
        self.round = round_
        self.detail = detail


class Store:
    """Abstract store interface (reference chain/store.go:15-24).

    `put_many` is the batched-commit seam the TPU build adds: a deep
    catch-up verifies thousands of rounds in one device call, and
    committing them one `put` at a time costs a sqlite transaction PLUS
    a decorator-stack `last()` query per beacon (~2-3 ms each — measured
    at ~45-60 s per 16384-round chunk, swamping the 0.93 s verify).  The
    default implementation loops `put`; stores/decorators override it to
    amortize."""

    def put(self, beacon: Beacon) -> None:
        raise NotImplementedError

    def put_many(self, beacons) -> None:
        for b in beacons:
            self.put(b)

    def last(self) -> Beacon:
        raise NotImplementedError

    def get(self, round_: int) -> Beacon:
        raise NotImplementedError

    def delete(self, round_: int) -> None:
        raise NotImplementedError

    def __len__(self) -> int:
        raise NotImplementedError

    def cursor(self) -> "Cursor":
        raise NotImplementedError

    def close(self) -> None:
        pass

    def save_to(self, path: str) -> None:
        raise NotImplementedError


class Cursor:
    """Iteration over rounds (reference chain/store.go:26-39)."""

    def __init__(self, store: "SqliteStore"):
        self._store = store

    def first(self) -> Optional[Beacon]:
        return self._store._edge("ASC")

    def last(self) -> Optional[Beacon]:
        return self._store._edge("DESC")

    def seek(self, round_: int) -> Optional[Beacon]:
        try:
            return self._store.get(round_)
        except BeaconNotFound:
            return None

    def iter_from(self, round_: int) -> Iterator[Beacon]:
        yield from self._store.iter_range(round_)


class SqliteStore(Store):
    """The base physical store.

    Rows are written with the versioned binary codec
    (drand_tpu/chain/codec.py) and read through its sniff-byte dispatch,
    so databases written by older JSON-row builds keep working with no
    migration step; `codec="json"` pins the legacy writer (bench A/B).

    Crash-consistency invariant (WAL + synchronous>=NORMAL + one
    transaction per commit): a partially-applied segment is NEVER
    visible after a restart.  `put_many` writes a whole verified
    segment in one transaction, so a kill -9 mid-catchup
    leaves the database at a segment boundary — either the segment is
    fully there or fully absent.  The startup integrity scan
    (drand_tpu/chain/recovery.py) depends on, and the chaos
    `crash-recover` scenario falsifies, exactly this contract.

    Rows that fail to decode on the way OUT (torn write that slipped
    past sqlite, disk bit-rot) surface as `CorruptRowError` carrying the
    offending round — never as a bare `CodecError` that aborts a reader
    blind.  The `quarantine` sidecar table preserves damaged or
    rolled-back rows for forensics; nothing is silently deleted."""

    def __init__(self, path: str, codec: str | None = None):
        self.path = path
        if os.path.dirname(path):
            os.makedirs(os.path.dirname(path), exist_ok=True)
        self._local = threading.local()
        self._lock = threading.Lock()
        self._encode = row_codec.make_encoder(codec)
        sync = os.environ.get(SYNC_ENV, "NORMAL").upper()
        self._sync_level = sync if sync in _SYNC_LEVELS else "NORMAL"
        conn = self._conn()
        with conn:
            conn.execute(
                "CREATE TABLE IF NOT EXISTS beacons ("
                "round INTEGER PRIMARY KEY, data BLOB NOT NULL)")
            conn.execute(
                "CREATE TABLE IF NOT EXISTS quarantine ("
                "round INTEGER PRIMARY KEY, data BLOB, reason TEXT)")

    def _conn(self) -> sqlite3.Connection:
        conn = getattr(self._local, "conn", None)
        if conn is None:
            conn = sqlite3.connect(self.path, timeout=30)
            conn.execute("PRAGMA journal_mode=WAL")
            # explicit durability policy — sqlite's compiled-in default
            # is build-dependent, so pin it: NORMAL (WAL) = transactions
            # are atomic across process kill; FULL = also across power
            # loss (see SYNC_ENV above)
            conn.execute(f"PRAGMA synchronous={self._sync_level}")
            self._local.conn = conn
        return conn

    @staticmethod
    def _decode_row(round_: int, data: bytes) -> Beacon:
        """Decode one stored row, cross-checking the decoded round
        against the row key — a bit flip inside the round field must
        surface as corruption, never as a wrong beacon."""
        try:
            b = row_codec.decode_beacon(data)
        except row_codec.CodecError as exc:
            raise CorruptRowError(round_, str(exc)) from exc
        if b.round != round_:
            raise CorruptRowError(
                round_, f"row decodes to round {b.round}")
        return b

    def put(self, beacon: Beacon) -> None:
        with self._conn() as conn:
            conn.execute(
                "INSERT OR REPLACE INTO beacons (round, data) VALUES (?, ?)",
                (beacon.round, self._encode(beacon)))

    def put_many(self, beacons) -> None:
        """ONE transaction for a whole verified segment (one commit/fsync
        instead of per-beacon), its rows in multi-row statements of at
        most `_ROWS_A_STATEMENT`, in the order given (a round given
        twice keeps the later row).  Its parts go as counters to the
        span that encloses the call (`store.commit`, in the worker that
        commits): `encode_s` (the rows' values built in Python),
        `insert_s` (all the statements, of which the first opens the
        transaction), their number `statements`, and `flush_s` (its
        COMMIT: the WAL's write, at the `synchronous` level the
        connection has); an exception rolls the whole segment back, as
        the connection's context manager did.

        `sqlite3` gives the interpreter lock up around every step of a
        statement, and a row of `executemany` is a step: a 16,384-row
        segment handed the lock over 16,384 times, and beside another
        thread that does the same (a reader's `fetchall`) each handing
        over waited for a thread to wake (PERF.md, PR 45).  A statement
        of 499 rows is ONE step: 33 a segment."""
        from drand_tpu import tracing
        enc = self._encode
        conn = self._conn()
        t0 = _time.perf_counter()
        values = [v for b in beacons for v in (b.round, enc(b))]
        t1 = _time.perf_counter()
        step = 2 * _ROWS_A_STATEMENT
        statements = 0
        try:
            for at in range(0, len(values), step):
                part = values[at:at + step]
                conn.execute(_insert_rows_sql(len(part) // 2), part)
                statements += 1
            t2 = _time.perf_counter()
            conn.commit()
        except BaseException:
            conn.rollback()
            raise
        tracing.count(encode_s=t1 - t0, insert_s=t2 - t1,
                      flush_s=_time.perf_counter() - t2,
                      statements=statements)

    def last(self) -> Beacon:
        row = self._conn().execute(
            "SELECT round, data FROM beacons "
            "ORDER BY round DESC LIMIT 1").fetchone()
        if row is None:
            raise BeaconNotFound("empty store")
        return self._decode_row(row[0], row[1])

    def get(self, round_: int) -> Beacon:
        row = self._conn().execute(
            "SELECT data FROM beacons WHERE round = ?", (round_,)).fetchone()
        if row is None:
            raise BeaconNotFound(f"round {round_} not stored")
        return self._decode_row(round_, row[0])

    def delete(self, round_: int) -> None:
        with self._conn() as conn:
            conn.execute("DELETE FROM beacons WHERE round = ?", (round_,))

    def __len__(self) -> int:
        return self._conn().execute("SELECT COUNT(*) FROM beacons").fetchone()[0]

    def _edge(self, order: str) -> Optional[Beacon]:
        row = self._conn().execute(
            f"SELECT round, data FROM beacons "
            f"ORDER BY round {order} LIMIT 1").fetchone()
        return self._decode_row(row[0], row[1]) if row else None

    def iter_range(self, start_round: int, limit: int | None = None) -> Iterator[Beacon]:
        q = "SELECT round, data FROM beacons WHERE round >= ? ORDER BY round ASC"
        args: tuple = (start_round,)
        if limit is not None:
            q += " LIMIT ?"
            args = (start_round, limit)
        cur = self._conn().execute(q, args)
        while True:
            rows = cur.fetchmany(_FETCH_BATCH)
            if not rows:
                return
            for (r, data) in rows:
                yield self._decode_row(r, data)

    def read_fields(self, start_round: int,
                    limit: int) -> list[tuple[int, bytes, bytes]]:
        """Raw-segment read: up to `limit` (round, sig, prev) tuples from
        `start_round` in ONE query, no Beacon materialization — the
        serve-side feed for packed sync chunks.  Safe to call from a
        worker thread (per-thread sqlite connections).  A damaged row
        raises CorruptRowError with its round, so callers can serve the
        good prefix and stop exactly there."""
        rows = self._conn().execute(
            "SELECT round, data FROM beacons WHERE round >= ? "
            "ORDER BY round ASC LIMIT ?", (start_round, limit)).fetchall()
        out = []
        for (r, data) in rows:
            try:
                fields = row_codec.decode_fields(data)
            except row_codec.CodecError as exc:
                raise CorruptRowError(r, str(exc)) from exc
            if fields[0] != r:
                raise CorruptRowError(r, f"row decodes to round {fields[0]}")
            out.append(fields)
        return out

    # -- recovery surface (drand_tpu/chain/recovery.py) ---------------------

    def raw_rows(self, start_round: int,
                 limit: int) -> list[tuple[int, bytes]]:
        """Stored (round, blob) pairs with NO decoding — the integrity
        scan's feed (it must see damaged rows, not die on them) and the
        bit-identity probe for repair verification."""
        return [(r, bytes(d)) for (r, d) in self._conn().execute(
            "SELECT round, data FROM beacons WHERE round >= ? "
            "ORDER BY round ASC LIMIT ?", (start_round, limit)).fetchall()]

    def quarantine_rounds(self, rounds, reason: str) -> int:
        """Move the given rounds from the live chain into the quarantine
        sidecar table — one transaction, rows preserved for forensics,
        never silently deleted.  Returns how many rows actually moved."""
        rounds = sorted(set(rounds))
        if not rounds:
            return 0
        moved = 0
        with self._conn() as conn:
            for r in rounds:
                cur = conn.execute(
                    "INSERT OR REPLACE INTO quarantine (round, data, reason) "
                    "SELECT round, data, ? FROM beacons WHERE round = ?",
                    (reason, r))
                moved += cur.rowcount
                conn.execute("DELETE FROM beacons WHERE round = ?", (r,))
        return moved

    def truncate_after(self, round_: int, reason: str) -> int:
        """Roll the tip back to `round_`: every live row ABOVE it moves
        to quarantine (forensics — a rolled-back suffix is evidence, not
        garbage).  Returns how many rows moved."""
        with self._conn() as conn:
            cur = conn.execute(
                "INSERT OR REPLACE INTO quarantine (round, data, reason) "
                "SELECT round, data, ? FROM beacons WHERE round > ?",
                (reason, round_))
            moved = cur.rowcount
            conn.execute("DELETE FROM beacons WHERE round > ?", (round_,))
        return moved

    def quarantined(self) -> list[tuple[int, str]]:
        """(round, reason) for every quarantined row, ascending."""
        return [(r, reason or "") for (r, reason) in self._conn().execute(
            "SELECT round, reason FROM quarantine ORDER BY round ASC")]

    def quarantined_rows(self) -> list[tuple[int, bytes, str]]:
        """(round, data, reason) for every quarantined row, ascending —
        the forensic payload (`quarantined` is the cheap summary)."""
        return [(r, bytes(d) if d is not None else b"", reason or "")
                for (r, d, reason) in self._conn().execute(
                    "SELECT round, data, reason FROM quarantine "
                    "ORDER BY round ASC")]

    def cursor(self) -> Cursor:
        return Cursor(self)

    def save_to(self, path: str) -> None:
        """Hot backup (reference BackupDatabase -> bolt tx.WriteTo,
        `chain/boltdb/store.go:154-159`).  Atomic: the backup lands in a
        temp file next to the target and is os.replace()d into place, so
        a crash mid-backup can never leave a half-written database at
        `path`."""
        tmp = f"{path}.tmp.{os.getpid()}"
        dst = sqlite3.connect(tmp)
        try:
            with self._lock:
                self._conn().backup(dst)
            dst.close()
            os.replace(tmp, path)
        except BaseException:
            dst.close()
            if os.path.exists(tmp):
                os.remove(tmp)
            raise

    def close(self) -> None:
        conn = getattr(self._local, "conn", None)
        if conn is not None:
            conn.close()
            self._local.conn = None


class StoreDecorator(Store):
    def __init__(self, inner: Store):
        self.inner = inner

    def put(self, beacon: Beacon) -> None:
        self.inner.put(beacon)

    def last(self) -> Beacon:
        return self.inner.last()

    def get(self, round_: int) -> Beacon:
        return self.inner.get(round_)

    def delete(self, round_: int) -> None:
        self.inner.delete(round_)

    def __len__(self) -> int:
        return len(self.inner)

    def cursor(self) -> Cursor:
        return self.inner.cursor()

    def close(self) -> None:
        self.inner.close()

    def save_to(self, path: str) -> None:
        self.inner.save_to(path)

    def iter_range(self, start_round: int, limit=None):
        return self.inner.iter_range(start_round, limit)

    def read_fields(self, start_round: int, limit: int):
        return self.inner.read_fields(start_round, limit)

    def put_many(self, beacons) -> None:
        self.inner.put_many(beacons)


class AppendStore(StoreDecorator):
    """Only round = last+1 may be appended (store.go:31-56)."""

    def __init__(self, inner: Store):
        super().__init__(inner)
        self._lock = threading.Lock()

    def put(self, beacon: Beacon) -> None:
        with self._lock:
            try:
                last = self.inner.last()
            except BeaconNotFound:
                last = None
            if last is not None:
                if beacon.round == last.round and beacon.equal(last):
                    return  # idempotent re-put
                if beacon.round != last.round + 1:
                    raise StoreError(
                        f"non-appendable round {beacon.round} after {last.round}")
            self.inner.put(beacon)

    def put_many(self, beacons) -> None:
        """Same invariant, ONE last() query: the segment must be
        contiguous internally and link to the stored head.  Idempotent
        re-puts (a duplicate of the stored head, or a consecutive
        duplicate inside the segment) are skipped exactly as the
        per-beacon path skips them."""
        beacons = list(beacons)
        if not beacons:
            return
        with self._lock:
            try:
                prev = self.inner.last()
            except BeaconNotFound:
                prev = None
            keep = []
            for b in beacons:
                if prev is not None and b.round == prev.round \
                        and b.equal(prev):
                    continue       # idempotent re-put
                if prev is not None and b.round != prev.round + 1:
                    raise StoreError(
                        f"non-appendable round {b.round} after {prev.round}")
                keep.append(b)
                prev = b
            self.inner.put_many(keep)


class SchemeStore(StoreDecorator):
    """Scheme-specific invariants (store.go:59-97): unchained schemes store
    no previous signature; chained schemes must link prev_sig to the last
    stored beacon's signature."""

    def __init__(self, inner: Store, decouple_prev_sig: bool):
        super().__init__(inner)
        self.decouple = decouple_prev_sig

    def put(self, beacon: Beacon) -> None:
        if self.decouple:
            beacon = Beacon(round=beacon.round, signature=beacon.signature,
                            previous_sig=b"")
        else:
            try:
                last = self.inner.last()
            except BeaconNotFound:
                last = None
            if last is not None and beacon.round == last.round + 1 \
                    and beacon.previous_sig != last.signature:
                raise StoreError(
                    f"round {beacon.round} previous-sig does not link to chain")
        self.inner.put(beacon)

    def put_many(self, beacons) -> None:
        beacons = list(beacons)
        if not beacons:
            return
        if self.decouple:
            self.inner.put_many([
                Beacon(round=b.round, signature=b.signature,
                       previous_sig=b"") for b in beacons])
            return
        try:
            last = self.inner.last()
        except BeaconNotFound:
            last = None
        # `store.link_check`: the walk over every appended row, one span
        # a segment, on this (the chained) branch only
        from drand_tpu import tracing
        with tracing.span("store.link_check", rows=len(beacons)):
            prev = last
            for b in beacons:
                if prev is not None and b.round == prev.round + 1 \
                        and b.previous_sig != prev.signature:
                    raise StoreError(f"round {b.round} previous-sig does "
                                     "not link to chain")
                prev = b
        self.inner.put_many(beacons)


class DiscrepancyStore(StoreDecorator):
    """Emits beacon latency (now - expected round time) on every put
    (store.go:99-133)."""

    def __init__(self, inner: Store, group, clock=None, on_latency=None,
                 on_segment=None):
        super().__init__(inner)
        self.group = group
        # system-clock fallback IS the injection seam's default: every
        # protocol caller passes the node's injected clock; only
        # undecorated operator/tool use falls through to wall time
        self.clock = clock or _time.time  # lint: disable=no-wall-clock
        self.on_latency = on_latency
        # Catch-up commits emit ONE latency sample per segment (the head),
        # a density change vs the per-beacon live path (ADVICE r4):
        # on_segment(n_rounds) carries the segment size so rate-based
        # consumers can reconstruct the true commit rate.
        self.on_segment = on_segment

    def put(self, beacon: Beacon) -> None:
        self.inner.put(beacon)
        if self.on_latency is not None:
            from drand_tpu.chain.time import time_of_round
            expected = time_of_round(self.group.period, self.group.genesis_time,
                                     beacon.round)
            self.on_latency(beacon.round, (self.clock() - expected) * 1000.0)

    def put_many(self, beacons) -> None:
        beacons = list(beacons)
        self.inner.put_many(beacons)
        if self.on_segment is not None and beacons:
            self.on_segment(len(beacons))
        # a catch-up segment's latency is only meaningful for its head
        if self.on_latency is not None and beacons:
            from drand_tpu.chain.time import time_of_round
            b = beacons[-1]
            expected = time_of_round(self.group.period,
                                     self.group.genesis_time, b.round)
            self.on_latency(b.round, (self.clock() - expected) * 1000.0)


class CallbackStore(StoreDecorator):
    """Fan-out of stored beacons to registered callbacks on a worker pool
    (store.go:136-214).  Callbacks never block the chain-append path.

    As the outermost decorator it also owns the `store.commit` tracing
    span: one span per put/put_many covering the WHOLE stack underneath
    (append check, scheme linkage, latency gauge, sqlite transaction) —
    the store-side stage of the round trace."""

    # per-beacon callbacks on a 16384-round segment used to cost 16384
    # pool submissions per callback; batching `_safe_many` runs keeps
    # submission-order (= round-order) semantics at ~1/512 the overhead
    FANOUT_CHUNK = 512

    def __init__(self, inner: Store, workers: int | None = None,
                 beacon_id: str = "", owner: str = ""):
        super().__init__(inner)
        self.beacon_id = beacon_id
        # which node this store belongs to (its protocol address) — the
        # `owner` half of chaos failpoint contexts, so seeded store
        # faults can target one node of an in-process multi-node net
        self.owner = owner
        self._cbs: dict[str, Callable[[Beacon], None]] = {}
        self._tail_cbs: dict[str, Callable[[Beacon], None]] = {}
        self._segment_cbs: dict[str, Callable[[list], None]] = {}
        self._lock = threading.Lock()
        self._pool = ThreadPoolExecutor(
            max_workers=workers or min(8, (os.cpu_count() or 2)))

    def add_callback(self, cb_id: str, cb: Callable[[Beacon], None]) -> None:
        with self._lock:
            self._cbs[cb_id] = cb

    def add_segment_callback(self, cb_id: str,
                             cb: Callable[[list], None]) -> None:
        """Register a callback that observes each commit as ONE list (the
        whole segment per put_many, a singleton per put), submitted once
        per commit to the worker pool — for consumers that can batch
        (metrics, export pipelines), where per-beacon fan-out of a deep
        catch-up is pure submission overhead."""
        with self._lock:
            self._segment_cbs[cb_id] = cb

    def add_tail_callback(self, cb_id: str,
                          cb: Callable[[Beacon], None]) -> None:
        """Register a callback that observes only the LAST beacon of each
        commit (the one per put, the segment tail per put_many), invoked
        SYNCHRONOUSLY on the committing thread — for O(1) bookkeeping
        like tip tracking, where fanning a 16384-round segment through
        the worker pool per-beacon would be 16384 submissions to compute
        `segment[-1]`.  Callbacks must be cheap and non-blocking."""
        with self._lock:
            self._tail_cbs[cb_id] = cb

    def remove_callback(self, cb_id: str) -> None:
        with self._lock:
            self._cbs.pop(cb_id, None)
            self._tail_cbs.pop(cb_id, None)
            self._segment_cbs.pop(cb_id, None)

    def put(self, beacon: Beacon) -> None:
        from drand_tpu import tracing
        from drand_tpu.chaos import failpoints as chaos
        with tracing.span("store.commit", beacon_id=self.beacon_id,
                          round_=beacon.round):
            # injected errors are StoreError: the exact failure class
            # every append caller is already hardened against
            chaos.failpoint_sync("store.commit", exc=StoreError,
                                 owner=self.owner, beacon_id=self.beacon_id,
                                 round=beacon.round)
            self.inner.put(beacon)
        with self._lock:
            cbs = list(self._cbs.values())
            tails = list(self._tail_cbs.values())
            segs = list(self._segment_cbs.values())
        for cb in cbs:
            self._pool.submit(self._safe, cb, beacon)
        for cb in segs:
            self._pool.submit(self._safe, cb, [beacon])
        for cb in tails:
            self._safe(cb, beacon)

    def put_many(self, beacons) -> None:
        from drand_tpu import tracing
        from drand_tpu.chaos import failpoints as chaos
        beacons = list(beacons)
        # counted before the span opens: its time stays what it was
        payload = sum(len(b.signature) + len(b.previous_sig)
                      for b in beacons)
        with tracing.span("store.commit", beacon_id=self.beacon_id,
                          round_=beacons[-1].round if beacons else None,
                          batch=len(beacons), rows=len(beacons),
                          payload_bytes=payload):
            if beacons:
                chaos.failpoint_sync("store.commit", exc=StoreError,
                                     owner=self.owner,
                                     beacon_id=self.beacon_id,
                                     round=beacons[-1].round)
            self.inner.put_many(beacons)
        with self._lock:
            cbs = list(self._cbs.values())
            tails = list(self._tail_cbs.values())
            segs = list(self._segment_cbs.values())
        # callbacks still see every beacon off the append path (submission
        # order is round order; the multi-worker pool does not guarantee
        # EXECUTION order, same as the per-beacon path) — but fanned out
        # as FANOUT_CHUNK-sized slices, not one pool task per beacon
        for cb in cbs:
            for i in range(0, len(beacons), self.FANOUT_CHUNK):
                self._pool.submit(self._safe_many, cb,
                                  beacons[i:i + self.FANOUT_CHUNK])
        if beacons:
            for cb in segs:
                self._pool.submit(self._safe, cb, beacons)
            for cb in tails:
                self._safe(cb, beacons[-1])

    def get(self, round_: int) -> Beacon:
        from drand_tpu.chaos import failpoints as chaos
        chaos.failpoint_sync("store.read", exc=StoreError,
                             owner=self.owner, round=round_)
        return self.inner.get(round_)

    @staticmethod
    def _safe(cb, beacon):
        try:
            cb(beacon)
        except Exception:
            pass

    @staticmethod
    def _safe_many(cb, beacons):
        # per-beacon semantics inside one pool task: one raising beacon
        # must not starve the rest of its slice
        for b in beacons:
            try:
                cb(b)
            except Exception:
                pass

    def close(self) -> None:
        self._pool.shutdown(wait=False)
        self.inner.close()


def new_chain_store(db_path: str, group, clock=None, on_latency=None,
                    on_segment=None, workers=None,
                    beacon_id: str = "", owner: str = "") -> CallbackStore:
    """Build the full decorator stack (chain/beacon/chain.go:41-90).

    The returned store exposes the UNDECORATED base as `.insecure` —
    the explicit no-append-only-check handle repair paths write through
    (the reference passes the same pair to its sync manager,
    chain/beacon/sync_manager.go:234-265)."""
    from drand_tpu.chain.scheme import scheme_by_id
    scheme = scheme_by_id(group.scheme_id)
    base = SqliteStore(db_path)
    stack = AppendStore(base)
    stack = SchemeStore(stack, scheme.decouple_prev_sig)
    stack = DiscrepancyStore(stack, group, clock=clock,
                             on_latency=on_latency, on_segment=on_segment)
    out = CallbackStore(stack, workers=workers, beacon_id=beacon_id,
                        owner=owner)
    out.insecure = base
    return out

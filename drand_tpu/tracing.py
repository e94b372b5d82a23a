"""Per-round distributed tracing: spans, context propagation, recording.

The reference daemon answers "where did round N spend its time?" with
pprof-on-metrics (metrics/pprof/pprof.go) plus zap's hierarchical
loggers; neither survives a network hop or lines up with an XLA device
timeline.  This module is the TPU-native replacement (SURVEY §5.1):

  - `Span`: one timed stage.  Durations come from `time.perf_counter`
    (monotonic — fake-clock tests advance protocol time without
    corrupting measured latencies); the wall-clock *start stamp* is kept
    separately so operators can correlate a span with their incident
    timeline, and is injectable for tests (`set_wall_clock`).
  - per-round trace identity: `round_trace_id(beacon_id, round)` is a
    deterministic hash, so the partial-aggregation task, the store
    commit thread, and the batched-verify resolver all join round N's
    trace without threading a context object through every queue hop.
  - asyncio `contextvars` propagation: `span(...)` installs itself as
    the current span for the enclosing task; children parent to it.
  - RPC propagation: `inject()` stamps the current span into the
    protobuf `Metadata` every node-to-node request already carries
    (net/client.py make_metadata); `server_span()` re-roots the
    handler's context from it (net/rpc.py), so a peer's spans record
    the caller's span as parent.
  - `SpanRecorder`: bounded in-process ring buffer behind the
    `/debug/spans` routes on the metrics port (drand_tpu/metrics.py).
  - one clock with the device trace: a span publishes its monotonic
    start (`start_mono`, on `time.perf_counter`), and `clock_mark()`
    writes that clock's reading into a profiler capture as one
    `TraceAnnotation`, so `/debug/spans` lines up with the xplane of
    `/debug/jax-profile` without a profiler event per span.
    `span(..., device=True)` still shows ONE lexically scoped stage by
    name in the capture; it is opened and closed on the entering thread
    (a TraceMe is per-thread), which is why `begin_span` has no such
    option.
  - `gc.full`: a `gc.callbacks` hook records every generation-2
    collection (after a program build one stops all Python threads for
    seconds) as a span of its own trace.
  - counters on a span: what happens once a message or a row is no span
    (an operation takes hundreds of messages, the ring holds 4,096
    spans); it is two clock reads added to an attribute of the span a
    segment or a stream already has (`Span.add`, `count`).
  - the event loop's lag: `loop_watched(root)` keeps one task a running
    loop that sleeps `LOOP_LAG_TICK_S` and reads by how much it woke
    late, while a root span of a catch-up or a scan is open; the
    overshoots add up on every such root, and one of `LOOP_LAG_SPAN_S`
    or more is also a `loop.lag` span, so the spans open over it (a
    commit in a worker, `gc.full`, a build) say what held the loop, or
    that nothing of the program did.

Every ended span also feeds the `drand_stage_duration_seconds{stage,
beacon_id}` Prometheus histogram (drand_tpu/metrics.py), which is how
perf PRs get their before/after stage numbers for free.

Non-context-manager use MUST balance `begin_span()` with `Span.end()`
(the tools/lint `span-balance` rule enforces this mechanically); prefer
`with tracing.span(...)` wherever the stage is lexically scoped.
"""

from __future__ import annotations

import asyncio
import contextlib
import contextvars
import gc
import hashlib
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field

from drand_tpu import log as dlog
log = dlog.get("tracing")

TRACE_ID_LEN = 16      # bytes; hex-encoded in span dicts and metadata
SPAN_ID_LEN = 8

# wall-clock stamps exist purely so operators can line a span up with
# logs / incident timelines; durations never touch this — injectable
# for tests via set_wall_clock
_wall = time.time  # lint: disable=no-wall-clock

_current: contextvars.ContextVar["Span | None"] = contextvars.ContextVar(
    "drand_tpu_current_span", default=None)


def set_wall_clock(fn) -> None:
    """Inject the wall-clock source (tests pass a fake; None resets)."""
    global _wall
    _wall = fn if fn is not None else time.time  # lint: disable=no-wall-clock


def new_trace_id() -> str:
    return os.urandom(TRACE_ID_LEN).hex()


def new_span_id() -> str:
    return os.urandom(SPAN_ID_LEN).hex()


def round_trace_id(beacon_id: str, round_: int) -> str:
    """Deterministic trace id for one (beacon chain, round): every node
    in the group derives the same id, so even spans with no causal RPC
    link (each node's own broadcast, verify, commit) collate into one
    cross-cluster view of round N."""
    h = hashlib.sha256(f"round:{beacon_id}:{round_}".encode()).digest()
    return h[:TRACE_ID_LEN].hex()


@dataclass
class Span:
    """One timed stage of a round (or request) lifecycle."""
    name: str
    trace_id: str
    span_id: str
    parent_id: str | None = None
    beacon_id: str = ""
    round: int | None = None
    attrs: dict = field(default_factory=dict)
    status: str = "ok"
    start_wall: float = 0.0
    start_mono: float = 0.0             # time.perf_counter at start()
    duration_s: float | None = None     # set by end()
    _ended: bool = False

    def start(self, at: float | None = None) -> "Span":
        """`at` is a `time.perf_counter` reading the caller already made
        (a stage whose stat and span share their clock reads)."""
        now = time.perf_counter()
        self.start_mono = now if at is None else at
        self.start_wall = _wall() - (now - self.start_mono)
        return self

    def end(self, status: str | None = None,
            at: float | None = None) -> "Span":
        """Close the span: fix the duration (to `at`, a `perf_counter`
        reading, where the caller has one), record it, feed the stage
        histogram.  Idempotent."""
        if self._ended:
            return self
        self._ended = True
        self.duration_s = (time.perf_counter() if at is None else at) \
            - self.start_mono
        if status is not None:
            self.status = status
        RECORDER.record(self)
        try:
            from drand_tpu import metrics as M
            M.STAGE_DURATION.labels(self.name, self.beacon_id or "-") \
                .observe(self.duration_s)
        except Exception:
            log.debug("stage histogram observe failed", exc_info=True)
        # journey hops ride the same close: the collator ignores spans
        # that are not hop material (profiling/journey._SPAN_HOPS)
        try:
            from drand_tpu.profiling import journey
            journey.feed_span(self)
        except Exception:
            log.debug("journey feed failed", exc_info=True)
        return self

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def add(self, **counters) -> "Span":
        """Add to numeric attributes (absent ones count from 0): the
        form of whatever happens more often than a span may."""
        for key, value in counters.items():
            self.attrs[key] = self.attrs.get(key, 0) + value
        return self

    def to_dict(self) -> dict:
        return {
            "trace_id": self.trace_id, "span_id": self.span_id,
            "parent_id": self.parent_id, "name": self.name,
            "beacon_id": self.beacon_id, "round": self.round,
            "start": round(self.start_wall, 6),
            "start_mono": round(self.start_mono, 9),
            "duration_s": (round(self.duration_s, 9)
                           if self.duration_s is not None else None),
            "status": self.status, "attrs": dict(self.attrs),
        }

    # context-manager protocol: `with begin_span(...) as sp:` also works
    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.end("error" if exc_type is not None else None)


class SpanRecorder:
    """Bounded in-process ring buffer of ended spans.

    Thread-safe: spans end on the event loop, the crypto worker thread,
    and the store callback pool alike.  Reads scan the ring — it is a
    debug surface sized in the low thousands, not a query engine."""

    def __init__(self, maxlen: int = 4096):
        self._spans: deque[Span] = deque(maxlen=maxlen)
        self._lock = threading.Lock()

    def record(self, span: Span) -> None:
        _drain_full_collections()
        with self._lock:
            self._spans.append(span)

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)

    def spans(self) -> list[Span]:
        _drain_full_collections()
        with self._lock:
            return list(self._spans)

    def trace(self, trace_id: str) -> list[Span]:
        return [s for s in self.spans() if s.trace_id == trace_id]

    def traces(self, limit: int = 50, offset: int = 0) -> dict:
        """Newest-first trace summaries with explicit pagination state
        (total + truncated flag — never a silent cap)."""
        by_trace: dict[str, list[Span]] = {}
        order: list[str] = []
        for s in self.spans():
            if s.trace_id not in by_trace:
                by_trace[s.trace_id] = []
                order.append(s.trace_id)
            by_trace[s.trace_id].append(s)
        order.reverse()                  # newest trace first
        page = order[offset:offset + limit]
        out = []
        for tid in page:
            spans = by_trace[tid]
            out.append({
                "trace_id": tid,
                "beacon_id": next((s.beacon_id for s in spans
                                   if s.beacon_id), ""),
                "round": next((s.round for s in spans
                               if s.round is not None), None),
                "spans": len(spans),
                "stages": sorted({s.name for s in spans}),
                "start": min(s.start_wall for s in spans),
                "total_duration_s": round(
                    sum(s.duration_s or 0.0 for s in spans), 9),
            })
        return {"traces": out, "total": len(order), "offset": offset,
                "truncated": offset + limit < len(order)}

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()


RECORDER = SpanRecorder()


# What ends a server's stream when its CLIENT closed it (the handler's
# generator is closed, or the RPC's task cancelled): no failure of the
# serving side's, so its spans end `closed`, not `error`.
STREAM_CLOSED = (GeneratorExit, asyncio.CancelledError)


def current() -> Span | None:
    return _current.get()


def count(**counters) -> None:
    """Add to counters on the context's current span, where there is
    one: a layer below the span's own (the wire's client under
    `sync.catchup`, sqlite under `store.commit`) hands up what it timed
    without knowing who asked."""
    sp = _current.get()
    if sp is not None:
        sp.add(**counters)


def begin_span(name: str, *, beacon_id: str = "", round_: int | None = None,
               trace_id: str | None = None, parent_id: str | None = None,
               parent: Span | None = None, at: float | None = None,
               **attrs) -> Span:
    """Start a span WITHOUT making it the context's current span — the
    split start/end form for stages whose close happens in a different
    scope (e.g. a batched verify's dispatch vs its resolver).  Callers
    MUST balance with `.end()` (lint: span-balance).

    Trace identity resolves in order: explicit trace_id > `parent`, or
    else the current context span (parent link) > the deterministic
    per-round trace > a fresh random trace.  `at` is the span's start
    where the caller has already read `time.perf_counter`."""
    if parent is None:
        parent = _current.get()
    if trace_id is None:
        if parent is not None:
            trace_id = parent.trace_id
            if parent_id is None:
                parent_id = parent.span_id
        elif round_ is not None:
            trace_id = round_trace_id(beacon_id, round_)
        else:
            trace_id = new_trace_id()
    if parent is not None and not beacon_id:
        beacon_id = parent.beacon_id
    if parent is not None and round_ is None:
        round_ = parent.round
    return Span(name=name, trace_id=trace_id, span_id=new_span_id(),
                parent_id=parent_id, beacon_id=beacon_id, round=round_,
                attrs=dict(attrs)).start(at)


def record_span(name: str, start_mono: float, end_mono: float, *,
                parent: Span | None = None, **attrs) -> Span:
    """A finished span from two `time.perf_counter` readings the caller
    made anyway (a queue wait, a stage that already feeds a stat): the
    span and the stat cannot disagree, and the stage pays no further
    clock read."""
    return begin_span(name, parent=parent, at=start_mono,
                      **attrs).end(at=end_mono)


@contextlib.contextmanager
def under(sp: Span | None):
    """Make an open span the context's current one for a block without
    ending it there: a pipeline stage works under the span of the
    segment it was handed, and `asyncio.to_thread` carries that into
    the worker."""
    token = _current.set(sp)
    try:
        yield sp
    finally:
        _current.reset(token)


@contextlib.contextmanager
def span(name: str, *, beacon_id: str = "", round_: int | None = None,
         trace_id: str | None = None, parent_id: str | None = None,
         device: bool = False, **attrs):
    """Context-managed span, installed as the task's current span so
    children (including RPCs via `inject`) parent to it.  `device=True`
    also shows the stage by name in a profiler capture: one
    `TraceAnnotation`, entered and left here, on the entering thread."""
    sp = begin_span(name, beacon_id=beacon_id, round_=round_,
                    trace_id=trace_id, parent_id=parent_id, **attrs)
    annotation = _annotation(name) if device else None
    token = _current.set(sp)
    try:
        yield sp
    except BaseException:
        sp.end("error")
        raise
    finally:
        try:
            _current.reset(token)
        except ValueError:
            # a span wrapping an async generator (server streams,
            # net/rpc.stream_traced) can be finalized by athrow() from a
            # DIFFERENT context than the one that entered it — e.g. a
            # mesh client dropping mid-stream under churn.  The token is
            # unusable there; the contextvar died with the origin
            # context, so there is nothing to restore.
            pass
        if annotation is not None:
            annotation.__exit__(None, None, None)
        sp.end()


# -- the profiler's clock -------------------------------------------------

CLOCK_MARK = "drand:clock_mark perf_counter_ns="


def _annotation(name: str):
    """An entered `jax.profiler.TraceAnnotation`, or None where the
    profiler cannot be had: tracing must not break the traced."""
    try:
        import jax
        annotation = jax.profiler.TraceAnnotation(name)
        annotation.__enter__()
        return annotation
    except Exception:
        return None


def clock_mark() -> int:
    """Write the spans' clock into a running profiler capture: one
    annotation named `CLOCK_MARK` + the reading of
    `time.perf_counter_ns()` taken as it opens.  Its place on the
    capture's own axis gives the offset between the two clocks, so every
    span's `start_mono` can be laid beside the device's operations; two
    marks (`profiling.trace` writes one at each end) also show the
    drift.  Returns the reading."""
    now = time.perf_counter_ns()
    annotation = _annotation(f"{CLOCK_MARK}{now}")
    if annotation is not None:
        annotation.__exit__(None, None, None)
    return now


# -- full garbage collections ---------------------------------------------
#
# The hook runs inside the collector, possibly while this very thread
# holds the recorder's or a metric's lock: it only notes the two clock
# reads, and the span is made at the next record or read of the ring.

_full_collections: list[tuple[float, float, int]] = []
_full_began = [0.0]


def _on_gc(phase: str, info: dict) -> None:
    if info["generation"] != 2:
        return
    if phase == "start":
        _full_began[0] = time.perf_counter()
    else:
        _full_collections.append((_full_began[0], time.perf_counter(),
                                  info["collected"]))


def _drain_full_collections() -> None:
    while _full_collections:
        try:
            began, ended, collected = _full_collections.pop(0)
        except IndexError:          # another thread drained it
            return
        Span(name="gc.full", trace_id=new_trace_id(),
             span_id=new_span_id(), attrs={"collected": collected}
             ).start(began).end(at=ended)


gc.callbacks.append(_on_gc)


# -- the event loop's lag ---------------------------------------------------
#
# Two constants and no knob: a tick short enough that the wait of one
# wire message (3 ms) shows, a span only for what a run's clock would
# show as a stall.

LOOP_LAG_TICK_S = 0.005
LOOP_LAG_SPAN_S = 0.020


class _LoopWatch:
    """The one monitor of one running loop, and the roots it counts for
    (opened first, first)."""

    def __init__(self, loop):
        self.roots: list[Span] = []
        self.task = loop.create_task(self._run())

    async def _run(self) -> None:
        while True:
            due = time.perf_counter() + LOOP_LAG_TICK_S
            await asyncio.sleep(LOOP_LAG_TICK_S)
            now = time.perf_counter()
            late = max(0.0, now - due)
            for root in self.roots:
                root.add(loop_lag_s=late, loop_ticks=1)
                if late > root.attrs.get("loop_lag_max_s", 0.0):
                    root.attrs["loop_lag_max_s"] = late
            if late >= LOOP_LAG_SPAN_S and self.roots:
                # from when the task was due to when it ran; the child
                # of a root, never of a stage whose self time is read
                record_span("loop.lag", due, now, parent=self.roots[0],
                            roots=len(self.roots))


_loop_watches: dict = {}        # running loop -> its _LoopWatch


@contextlib.contextmanager
def loop_watched(root: Span):
    """While the block runs, the running loop's lag is counted on `root`
    (`loop_lag_s`, `loop_lag_max_s`, `loop_ticks`).  Overlapping blocks
    on one loop (two chains' catch-ups) share one monitor: the first to
    begin starts its task and the last to end cancels it.  Outside a
    running loop there is nothing to watch."""
    try:
        loop = asyncio.get_running_loop()
    except RuntimeError:
        yield
        return
    watch = _loop_watches.get(loop)
    if watch is None:
        watch = _loop_watches[loop] = _LoopWatch(loop)
    watch.roots.append(root)
    try:
        yield
    finally:
        watch.roots[:] = [r for r in watch.roots if r is not root]
        if not watch.roots:
            del _loop_watches[loop]
            watch.task.cancel()


# -- RPC propagation (protobuf Metadata fields 4/5) -----------------------


def inject(metadata) -> None:
    """Stamp the current span's context onto an outgoing request's
    Metadata (called by net.client.make_metadata on every RPC)."""
    sp = _current.get()
    if sp is None:
        return
    try:
        metadata.trace_id = bytes.fromhex(sp.trace_id)
        metadata.span_id = bytes.fromhex(sp.span_id)
    except (AttributeError, ValueError):
        pass    # pre-upgrade Metadata or malformed ids: send untraced


def extract(metadata) -> tuple[str | None, str | None]:
    """(trace_id, parent_span_id) carried by an incoming request's
    Metadata, or (None, None) when the caller sent no trace context."""
    try:
        tid = bytes(metadata.trace_id)
        sid = bytes(metadata.span_id)
    except (AttributeError, TypeError):
        return None, None
    return (tid.hex() if len(tid) == TRACE_ID_LEN else None,
            sid.hex() if len(sid) == SPAN_ID_LEN else None)


@contextlib.contextmanager
def server_span(name: str, metadata, round_: int | None = None):
    """Server-side RPC span re-rooted from the caller's trace context
    (net/rpc.py wraps every service method in one).  With no inbound
    context the span still joins the per-round trace when the request
    names a round."""
    trace_id, parent_id = (None, None) if metadata is None \
        else extract(metadata)
    beacon_id = getattr(metadata, "beaconID", "") if metadata is not None \
        else ""
    with span(name, beacon_id=beacon_id, round_=round_, trace_id=trace_id,
              parent_id=parent_id) as sp:
        yield sp

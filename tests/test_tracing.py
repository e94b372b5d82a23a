"""Per-round distributed tracing (drand_tpu/tracing.py).

Unit coverage for the span model / recorder / context propagation, plus
the two acceptance drives from the tracing ISSUE: a live round whose
trace covers partial -> aggregate -> verify -> store -> fanout with
nonzero stage durations (served by /debug/spans/{trace_id}), and RPC
trace context crossing a real gRPC hop so the peer's span parents to
the caller's.
"""

import asyncio

import pytest

from drand_tpu import tracing
from tests.test_scenario import Scenario


@pytest.fixture(autouse=True)
def _fresh_recorder():
    tracing.RECORDER.clear()
    yield
    tracing.RECORDER.clear()
    tracing.set_wall_clock(None)


# -- span model ---------------------------------------------------------


def test_span_nesting_and_context_propagation():
    with tracing.span("outer", beacon_id="b", round_=7) as outer:
        assert tracing.current() is outer
        with tracing.span("inner") as inner:
            # children inherit trace, beacon, and round via contextvars
            assert inner.parent_id == outer.span_id
            assert inner.trace_id == outer.trace_id
            assert inner.beacon_id == "b" and inner.round == 7
        assert tracing.current() is outer
    assert tracing.current() is None
    spans = tracing.RECORDER.trace(outer.trace_id)
    assert {s.name for s in spans} == {"outer", "inner"}
    assert all(s.duration_s > 0 for s in spans)


def test_round_trace_is_deterministic_and_shared():
    # two causally-unlinked spans for the same round land in one trace
    with tracing.span("a", beacon_id="default", round_=5):
        pass
    with tracing.span("b", beacon_id="default", round_=5):
        pass
    tid = tracing.round_trace_id("default", 5)
    assert {s.name for s in tracing.RECORDER.trace(tid)} == {"a", "b"}
    # a different beacon's round 5 is a different trace
    assert tracing.round_trace_id("other", 5) != tid


def test_error_status_and_begin_end_idempotence():
    with pytest.raises(ValueError):
        with tracing.span("boom"):
            raise ValueError("x")
    assert tracing.RECORDER.spans()[-1].status == "error"

    sp = tracing.begin_span("stage", beacon_id="b", round_=1)
    sp.end()
    d = sp.duration_s
    sp.end("error")       # second end is a no-op
    assert sp.duration_s == d and sp.status == "ok"
    assert len([s for s in tracing.RECORDER.spans() if s is sp]) == 1


def test_recorder_ring_buffer_bound_and_wall_clock_injection():
    rec = tracing.SpanRecorder(maxlen=8)
    tracing.set_wall_clock(lambda: 1234.5)
    for i in range(20):
        sp = tracing.Span(name=f"s{i}", trace_id="t", span_id=str(i)).start()
        sp.duration_s = 0.0
        rec.record(sp)
    assert len(rec) == 8
    assert rec.spans()[0].name == "s12"          # oldest evicted
    assert rec.spans()[0].start_wall == 1234.5   # injected wall clock


def test_traces_pagination_reports_truncation():
    for i in range(6):
        with tracing.span("s", beacon_id="b", round_=i):
            pass
    page = tracing.RECORDER.traces(limit=2, offset=0)
    assert len(page["traces"]) == 2 and page["total"] == 6
    assert page["truncated"] is True
    # newest-first: the last-recorded round leads
    assert page["traces"][0]["round"] == 5
    tail = tracing.RECORDER.traces(limit=10, offset=4)
    assert len(tail["traces"]) == 2 and tail["truncated"] is False


def test_stage_histogram_observed_on_end():
    from drand_tpu import metrics as M
    before = M.STAGE_DURATION.labels("unit.stage", "b")._sum.get()
    with tracing.span("unit.stage", beacon_id="b"):
        pass
    assert M.STAGE_DURATION.labels("unit.stage", "b")._sum.get() > before


# -- metadata propagation (no network) ----------------------------------


def test_inject_extract_roundtrip_through_wire_bytes():
    from drand_tpu.net.client import make_metadata
    from drand_tpu.protogen import common_pb2

    with tracing.span("caller", beacon_id="default", round_=3) as sp:
        md = make_metadata("default")
        assert md.trace_id == bytes.fromhex(sp.trace_id)
        assert md.span_id == bytes.fromhex(sp.span_id)
        wire = md.SerializeToString()

    got = common_pb2.Metadata.FromString(wire)
    tid, pid = tracing.extract(got)
    assert tid == sp.trace_id and pid == sp.span_id

    # outside any span the metadata carries no context
    md2 = make_metadata("default")
    assert tracing.extract(md2) == (None, None)


def test_server_span_adopts_remote_context():
    from drand_tpu.protogen import common_pb2
    md = common_pb2.Metadata(
        beaconID="default",
        trace_id=bytes.fromhex("ab" * tracing.TRACE_ID_LEN),
        span_id=bytes.fromhex("cd" * tracing.SPAN_ID_LEN))
    with tracing.server_span("rpc.Test.Method", md, round_=9) as sp:
        assert sp.trace_id == "ab" * tracing.TRACE_ID_LEN
        assert sp.parent_id == "cd" * tracing.SPAN_ID_LEN
        assert sp.beacon_id == "default" and sp.round == 9
    # malformed / absent context falls back to the per-round trace
    with tracing.server_span("rpc.Test.Method", None, round_=9) as sp:
        assert sp.trace_id == tracing.round_trace_id("", 9)


# -- acceptance drives --------------------------------------------------


def test_round_lifecycle_trace_and_span_routes():
    """One live round's trace covers the full pipeline with nonzero
    durations, retrievable over /debug/spans/{trace_id}; the stage
    histogram shows up in /metrics exposition."""
    async def main():
        sc = Scenario(2, 2, "pedersen-bls-unchained")
        try:
            await sc.start_daemons()
            await sc.run_dkg()
            await sc.advance_to_round(2)

            tid = tracing.round_trace_id("default", 2)
            stages = {s.name for s in tracing.RECORDER.trace(tid)}
            # partial -> aggregate -> verify -> store -> fanout
            assert {"partial.broadcast", "partial.send",
                    "partial.aggregate", "verify.beacon",
                    "store.commit"} <= stages, stages
            assert all(s.duration_s > 0
                       for s in tracing.RECORDER.trace(tid))

            from drand_tpu.metrics import MetricsServer
            ms = MetricsServer(sc.daemons[0], 0)
            await ms.start()
            try:
                import aiohttp
                base = f"http://127.0.0.1:{ms.port}"
                async with aiohttp.ClientSession() as http:
                    async with http.get(f"{base}/debug/spans/{tid}") as r:
                        assert r.status == 200
                        body = await r.json()
                        got = {s["name"] for s in body["spans"]}
                        assert "partial.aggregate" in got
                        assert all(s["duration_s"] > 0
                                   for s in body["spans"])
                    async with http.get(f"{base}/debug/spans/feed"
                                        "beeffeedbeef") as r:
                        assert r.status == 404
                    async with http.get(f"{base}/debug/spans?limit=2") as r:
                        page = await r.json()
                        assert len(page["traces"]) <= 2
                        assert "truncated" in page and "total" in page
                    async with http.get(f"{base}/debug/spans?limit=0") as r:
                        assert r.status == 400
                    async with http.get(f"{base}/debug/spans?offset=-1") as r:
                        assert r.status == 400
                    async with http.get(f"{base}/metrics") as r:
                        text = await r.text()
                        assert "drand_stage_duration_seconds_bucket" in text
                        assert 'stage="store.commit"' in text
            finally:
                await ms.stop()
        finally:
            await sc.stop()

    asyncio.run(main())


def test_rpc_trace_context_crosses_nodes():
    """The span a peer opens while serving PartialBeacon parents to the
    SENDER's partial.send span — context carried in request metadata
    over a real gRPC hop (both daemons share the in-process recorder,
    which is what lets one test see both halves)."""
    async def main():
        sc = Scenario(2, 2, "pedersen-bls-unchained")
        try:
            await sc.start_daemons()
            await sc.run_dkg()
            await sc.advance_to_round(1)

            spans = tracing.RECORDER.spans()
            by_id = {s.span_id: s for s in spans}
            served = [s for s in spans
                      if s.name == "rpc.Protocol.PartialBeacon"
                      and s.parent_id in by_id]
            assert served, [s.name for s in spans]
            parent = by_id[served[0].parent_id]
            assert parent.name == "partial.send"
            assert parent.trace_id == served[0].trace_id
            # and the sender's span descends from its broadcast span
            assert parent.parent_id in by_id
            assert by_id[parent.parent_id].name == "partial.broadcast"
        finally:
            await sc.stop()

    asyncio.run(main())


# -- one clock with the device trace (ISSUE 25) ----------------------------


def test_a_span_publishes_its_monotonic_start():
    import time
    before = time.perf_counter()
    with tracing.span("stage") as sp:
        pass
    after = time.perf_counter()
    assert before <= sp.start_mono <= after
    d = sp.to_dict()
    assert d["start_mono"] == pytest.approx(sp.start_mono, abs=1e-6)
    assert sp.start_mono + d["duration_s"] <= after + 1e-6
    # the wall stamp stays what it was: an operator's clock, injectable
    tracing.set_wall_clock(lambda: 1000.0)
    with tracing.span("stamped") as sp2:
        pass
    assert sp2.to_dict()["start"] == pytest.approx(1000.0, abs=1e-3)


def test_a_span_from_two_clock_reads_the_caller_made():
    with tracing.span("outer") as outer:
        sp = tracing.record_span("waited", 10.0, 12.5, stage="verify")
    assert (sp.start_mono, sp.duration_s) == (10.0, 2.5)
    assert sp.parent_id == outer.span_id and sp.trace_id == outer.trace_id
    assert sp.attrs == {"stage": "verify"}
    assert sp in tracing.RECORDER.spans()
    # an explicit parent wins over the context's
    other = tracing.begin_span("other")
    with tracing.span("ctx"):
        child = tracing.record_span("c", 1.0, 2.0, parent=other)
    other.end()
    assert child.parent_id == other.span_id


def test_clock_mark_carries_perf_counter_into_a_capture(tmp_path):
    """The mark is one annotation whose name holds the clock's reading;
    `profiling.trace` writes one at each end of a capture."""
    import time

    from jax.profiler import ProfileData

    from drand_tpu import profiling
    before = time.perf_counter_ns()
    with profiling.trace(str(tmp_path)):
        inside = tracing.clock_mark()
    after = time.perf_counter_ns()
    assert before <= inside <= after
    files = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert files
    marks = []
    for plane in ProfileData.from_file(str(files[0])).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(tracing.CLOCK_MARK):
                    marks.append((ev.start_ns,
                                  int(ev.name[len(tracing.CLOCK_MARK):])))
    assert len(marks) == 3                  # start, mine, end
    marks.sort()
    assert [m[1] for m in marks] == sorted(m[1] for m in marks)
    assert before <= marks[0][1] and marks[-1][1] <= after
    # both clocks tick alike: the offset is the same at every mark
    offsets = [ns - at for at, ns in marks]
    assert max(offsets) - min(offsets) < 50e6


def test_a_span_closed_on_another_thread_keeps_parent_and_trace():
    """The batched verify's shape: begun in a worker under the segment's
    span, ended by whoever resolves it."""
    import threading

    async def main():
        with tracing.span("sync.catchup") as root:
            seg = tracing.begin_span("sync.segment", rounds=4)
            holder = {}

            def dispatch():
                holder["sp"] = tracing.begin_span("verify.segment")

            with tracing.under(seg):
                await asyncio.to_thread(dispatch)
            assert tracing.current() is root      # `under` restored it
            t = threading.Thread(target=holder["sp"].end)
            t.start()
            t.join()
            seg.end()
        return root, seg, holder["sp"]

    root, seg, sp = asyncio.run(main())
    assert seg.parent_id == root.span_id
    assert sp.parent_id == seg.span_id
    assert sp.trace_id == seg.trace_id == root.trace_id
    assert sp.duration_s is not None and sp.start_mono >= seg.start_mono
    assert {s.name for s in tracing.RECORDER.trace(root.trace_id)} == {
        "sync.catchup", "sync.segment", "verify.segment"}


def test_a_lexical_span_can_show_by_name_in_a_capture(tmp_path):
    """`span(device=True)`: one TraceAnnotation, entered and left in the
    `with`, so on one thread; the split form has no such option."""
    from jax.profiler import ProfileData

    from drand_tpu import profiling
    with profiling.trace(str(tmp_path)):
        with tracing.span("partial.aggregate", device=True) as sp:
            pass
    assert sp.duration_s is not None
    files = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    names = {ev.name for plane in ProfileData.from_file(str(files[0])).planes
             for line in plane.lines for ev in line.events}
    assert "partial.aggregate" in names
    assert "device" not in tracing.begin_span.__kwdefaults__


def test_full_collections_are_spans_and_young_ones_are_not():
    import gc

    def fulls():
        return [s for s in tracing.RECORDER.spans() if s.name == "gc.full"]
    # the collector's own full collections, before this test in its
    # worker or in the middle of it, are spans too (the driver's run of
    # PR 32 found one): count from here, and let none come below
    was = gc.isenabled()
    gc.disable()
    try:
        before = len(fulls())
        gc.collect(0)
        gc.collect(1)
        assert len(fulls()) == before
        junk = [[i] for i in range(1000)]
        for j in junk:
            j.append(j)                     # cycles for the collector
        del junk, j
        gc.collect()
        full = fulls()[before:]
    finally:
        if was:
            gc.enable()
    assert len(full) == 1
    assert full[0].duration_s > 0 and full[0].parent_id is None
    assert full[0].attrs["collected"] >= 1000
    assert full[0].start_mono > 0


# -- counters on a span, and the event loop's lag (ISSUE 38) -----------------

def test_counters_add_up_on_the_current_span_and_nowhere_else():
    tracing.count(recv_s=1.0)               # no span: nothing to count on
    with tracing.span("sync.catchup") as root:
        tracing.count(recv_s=0.25, bytes=100)
        tracing.count(recv_s=0.5, bytes=20)
        with tracing.span("store.commit") as inner:
            tracing.count(flush_s=0.125)
    assert root.attrs == {"recv_s": 0.75, "bytes": 120}
    assert inner.attrs == {"flush_s": 0.125}
    assert root.add(bytes=1).attrs["bytes"] == 121


def _warm_span_end():
    """A process's first `Span.end` imports the stage histogram and the
    journey feed, a stall of its own that is not the test's."""
    tracing.record_span("warm", 0.0, 1.0)
    tracing.RECORDER.clear()


def _lags():
    return [s for s in tracing.RECORDER.spans() if s.name == "loop.lag"]


def test_a_blocked_loop_is_a_lag_span_under_the_root(monkeypatch):
    import time
    _warm_span_end()

    async def main():
        with tracing.span("sync.catchup", beacon_id="b") as root, \
                tracing.loop_watched(root):
            await asyncio.sleep(0.03)           # the monitor is ticking
            before = time.perf_counter()
            asyncio.get_running_loop().call_soon(time.sleep, 0.05)
            await asyncio.sleep(0.03)
        return root, before

    root, before = asyncio.run(main())
    longest = max(_lags(), key=lambda s: s.duration_s)
    # from when the monitor was due (inside the block, at most a tick
    # after it began) to when it ran: about the 50 ms the loop stood
    assert 0.04 <= longest.duration_s < 0.5
    assert longest.start_mono >= before - tracing.LOOP_LAG_TICK_S
    assert longest.parent_id == root.span_id
    assert longest.trace_id == root.trace_id and longest.beacon_id == "b"
    assert longest.attrs == {"roots": 1}
    assert root.attrs["loop_lag_max_s"] == pytest.approx(longest.duration_s)
    assert root.attrs["loop_lag_s"] >= longest.duration_s
    assert root.attrs["loop_ticks"] >= 3


def test_a_quiet_loop_ticks_and_is_no_span(monkeypatch):
    _warm_span_end()
    # a loaded machine's own hiccups are not this test's: only what
    # would be a stall of a quarter of a second is a span here
    monkeypatch.setattr(tracing, "LOOP_LAG_SPAN_S", 0.25)

    async def main():
        with tracing.span("store.scan") as root, \
                tracing.loop_watched(root):
            await asyncio.sleep(0.1)
        return root

    root = asyncio.run(main())
    assert _lags() == []
    assert 3 <= root.attrs["loop_ticks"] <= 0.1 / tracing.LOOP_LAG_TICK_S
    assert 0 <= root.attrs["loop_lag_max_s"] <= root.attrs["loop_lag_s"] \
        < 0.25 * root.attrs["loop_ticks"]


def test_two_roots_share_one_monitor_and_the_last_to_end_stops_it():
    _warm_span_end()

    async def chain(name, seconds, seen):
        with tracing.span("sync.catchup", beacon_id=name) as root, \
                tracing.loop_watched(root):
            await asyncio.sleep(seconds)
            seen[name] = (len(tracing._loop_watches),
                          tracing._loop_watches[
                              asyncio.get_running_loop()].task)
        return root

    async def main():
        seen = {}
        tasks_before = len(asyncio.all_tasks())
        a, b = await asyncio.gather(chain("a", 0.05, seen),
                                    chain("b", 0.12, seen))
        await asyncio.sleep(0)              # the cancelled monitor ends
        return a, b, seen, len(asyncio.all_tasks()) - tasks_before

    a, b, seen, tasks_left = asyncio.run(main())
    # one monitor, the same task for both, still running after the first
    # root had ended and gone once the last has
    assert seen["a"][0] == seen["b"][0] == 1
    assert seen["a"][1] is seen["b"][1]
    assert seen["a"][1].cancelled()
    assert tasks_left == 0 and tracing._loop_watches == {}
    # each root counts what happened while IT was open
    assert 3 <= a.attrs["loop_ticks"] < b.attrs["loop_ticks"]


def test_outside_a_running_loop_there_is_nothing_to_watch():
    with tracing.span("store.scan") as root, tracing.loop_watched(root):
        pass
    assert "loop_ticks" not in root.attrs and tracing._loop_watches == {}

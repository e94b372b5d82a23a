"""The two readers ISSUE 38 adds, on hand-made spans and gaps: an
attribute of some spans summed and scaled (`span_attrs`), and the
device's idle head and tail from the window's gaps (`device_ends`); and
the eleven metrics that read through them, against `BENCHMARK.json`."""

import json
import os

import pytest

from benchmark.readers import device_ends as E
from benchmark.readers import program_spans
from benchmark.readers import span_attrs as A
from benchmark.tests.test_readers import _Run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CATCHUPS = ["catchup-deep.unchained-g2", "catchup-deep.default-chained",
            "catchup-deep.quicknet-g1-x4", "catchup-deep.quicknet-g1",
            "catchup-concurrent.loe-mainnet-2chains"]
SCANS = ["restart-scan.quicknet-g1", "restart-scan.unchained-g2",
         "restart-scan.default-chained"]
# metric -> (reader, what it reads, layer, moves, cells)
ADDED = {
    "wire.recv_s": ("span_attrs", ("sync.fetch", "recv_s"), "wire",
                    "catchup_rate", CATCHUPS),
    "wire.decode_s": ("span_attrs", ("sync.fetch", "decode_s"), "wire",
                      "catchup_rate", CATCHUPS),
    "wire.serve_read_s": ("span_attrs", ("sync.serve", "read_s"), "wire",
                          "catchup_rate", CATCHUPS),
    "wire.serve_send_s": ("span_attrs", ("sync.serve", "send_s"), "wire",
                          "catchup_rate", CATCHUPS),
    "loop.lag_s": ("span_attrs", ("sync.catchup", "loop_lag_s"),
                   "SyncManager pipeline", "catchup_rate", CATCHUPS),
    "store.encode_s": ("span_attrs", ("store.commit", "encode_s"), "store",
                       "catchup_rate", CATCHUPS),
    "store.insert_s": ("span_attrs", ("store.commit", "insert_s"), "store",
                       "catchup_rate", CATCHUPS),
    "store.flush_s": ("span_attrs", ("store.commit", "flush_s"), "store",
                      "catchup_rate", CATCHUPS),
    "device.idle_head_s.catchup": ("device_ends", "head",
                                   "program and kernels", "catchup_rate",
                                   CATCHUPS),
    "device.idle_tail_s.catchup": ("device_ends", "tail",
                                   "program and kernels", "catchup_rate",
                                   CATCHUPS),
    "device.idle_head_s.scan": ("device_ends", "head",
                                "program and kernels", "scan_rate", SCANS),
}


def _recorded_run(monkeypatch, with_attrs=True):
    """The recorder holding one catch-up of two segments on a window
    100..110 (32,768 rounds), a fill of an earlier operation and a
    commit of a later one."""
    from drand_tpu import tracing
    program_spans.reduced.cache_clear()
    tracing.RECORDER.clear()

    def attrs(**kw):
        return kw if with_attrs else {}

    root = tracing.begin_span("sync.catchup", at=100.0,
                              **attrs(loop_lag_s=0.25, loop_ticks=1800))
    tracing.record_span("sync.fetch", 100.0, 101.0, parent=root, rounds=16384,
                        **attrs(wait_s=0.9, recv_s=0.5, decode_s=0.125))
    tracing.record_span("sync.fetch", 103.0, 104.0, parent=root, rounds=16384,
                        **attrs(wait_s=0.8, recv_s=0.25, decode_s=0.125))
    tracing.record_span("sync.serve", 100.0, 104.0, parent=root,
                        **attrs(read_s=1.0, pack_s=0.5, send_s=2.5))
    tracing.record_span("store.commit", 104.0, 105.0, parent=root,
                        rows=16384, **attrs(encode_s=0.25, insert_s=0.5,
                                            flush_s=0.125))
    tracing.record_span("store.commit", 108.0, 109.0, parent=root,
                        rows=16384, **attrs(encode_s=0.25, insert_s=0.5,
                                            flush_s=0.0625))
    root.end(at=110.0)
    tracing.record_span("sync.fetch", 50.0, 51.0, recv_s=7.0)
    tracing.record_span("store.commit", 120.0, 121.0, flush_s=7.0)
    return _Run(("dir", (100.0, 110.0), [], 32768))


def _spec(names, attr):
    return {"names": names, "attr": attr, "per_rounds": 65536}


def test_an_attribute_is_summed_over_the_operation_s_spans_and_scaled(
        monkeypatch):
    run = _recorded_run(monkeypatch)
    # 32,768 rounds in the operation: every sum doubles per 65,536
    assert A.read(run, _spec(["sync.fetch"], "recv_s")) == \
        pytest.approx(2 * 0.75)
    assert A.read(run, _spec(["sync.fetch"], "decode_s")) == \
        pytest.approx(2 * 0.25)
    assert A.read(run, _spec(["sync.serve"], "send_s")) == \
        pytest.approx(2 * 2.5)
    assert A.read(run, _spec(["sync.catchup"], "loop_lag_s")) == \
        pytest.approx(2 * 0.25)
    assert A.read(run, _spec(["store.commit"], "flush_s")) == \
        pytest.approx(2 * 0.1875)
    # of two names, those that carry the attribute count
    assert A.read(run, _spec(["sync.fetch", "sync.serve"], "recv_s")) == \
        pytest.approx(2 * 0.75)
    # the parts of a whole stay its parts under the scaling
    parts = sum(A.read(run, _spec(["sync.serve"], a))
                for a in ("read_s", "pack_s", "send_s"))
    assert parts == pytest.approx(2 * 4.0)


def test_a_late_loop_is_logged_beside_the_spans_open_over_it(monkeypatch,
                                                            capsys):
    """`loop.lag_s`'s file asks for it: every `loop.lag` span of the
    traced operation with what was open over it, once a run."""
    from drand_tpu import tracing
    run = _recorded_run(monkeypatch)
    A._log_open_over.cache_clear()
    # one stall under the second commit, one under nothing but the root
    tracing.record_span("loop.lag", 108.25, 108.75, roots=1)
    tracing.record_span("loop.lag", 106.0, 106.5, roots=1)
    program_spans.reduced.cache_clear()
    spec = dict(_spec(["sync.catchup"], "loop_lag_s"),
                log_open_over="loop.lag")
    assert A.read(run, spec) == pytest.approx(0.5)
    assert A.read(run, spec) == pytest.approx(0.5)
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith('{"spans_under_program_spans"')]
    (logged,) = lines                               # once a run
    got = logged["spans_under_program_spans"]
    assert got["name"] == "loop.lag"
    assert [(g["at_s"], g["for_s"], g["open"]) for g in got["spans"]] == [
        (pytest.approx(8.25), pytest.approx(0.5),
         ["sync.catchup", "store.commit"]),
        (pytest.approx(6.0), pytest.approx(0.5), ["sync.catchup"])]
    assert A.open_over_each([("a", None, "x", 0.0, 1.0)], "loop.lag",
                            0.0) == []


def test_a_program_without_the_attributes_gives_nothing(monkeypatch):
    """The parent of the PR that adds them: the spans are there (or not),
    the counters are not; nothing raises and the metric is left out."""
    run = _recorded_run(monkeypatch, with_attrs=False)
    assert A.read(run, _spec(["sync.fetch"], "recv_s")) is None
    assert A.read(run, _spec(["store.commit"], "flush_s")) is None
    assert A.read(run, _spec(["sync.catchup"], "loop_lag_s")) is None
    assert A.read(run, _spec(["no.such_span"], "recv_s")) is None
    program_spans.reduced.cache_clear()
    assert A.read(_Run(), _spec(["sync.fetch"], "recv_s")) is None
    assert A.summed([], ["sync.fetch"], "recv_s") is None


@pytest.mark.parametrize("gaps, head, tail", [
    # one end gap: the head alone, the device busy to the window's end
    ([{"at_s": 0.0, "for_s": 0.114}], 0.114, 0.0),
    # two: a catch-up's fetch and its last commit; a gap in the middle
    # is neither
    ([{"at_s": 0.0, "for_s": 0.114}, {"at_s": 1.5, "for_s": 0.002},
      {"at_s": 2.906, "for_s": 0.094}], 0.114, 0.094),
    # none: the only gap lies inside the window
    ([{"at_s": 1.5, "for_s": 0.002}], 0.0, 0.0),
    ([], 0.0, 0.0),
    # the tail alone
    ([{"at_s": 2.5, "for_s": 0.5}], 0.0, 0.5),
])
def test_the_gap_that_begins_the_window_and_the_one_that_ends_it(
        gaps, head, tail):
    got = E.ends(gaps, 3.0)
    assert got == pytest.approx({"head": head, "tail": tail})
    run = _Run(trace={"gaps_at": gaps, "window_s": 3.0, "busy_s": 2.9})
    assert E.read(run, {"end": "head"}) == pytest.approx(head)
    assert E.read(run, {"end": "tail"}) == pytest.approx(tail)


def test_the_ends_and_the_gaps_between_them_are_the_window_s_idle_time():
    gaps = [{"at_s": 0.0, "for_s": 0.25}, {"at_s": 1.0, "for_s": 0.125},
            {"at_s": 3.5, "for_s": 0.5}]
    got = E.ends(gaps, 4.0)
    assert got["head"] + got["tail"] + 0.125 == pytest.approx(
        sum(g["for_s"] for g in gaps))


def test_without_a_reduced_trace_there_are_no_ends():
    assert E.read(_Run(), {"end": "head"}) is None
    assert E.read(_Run(trace={"busy_s": 1.0}), {"end": "tail"}) is None


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", list(ADDED))
def test_a_new_metric_reads_what_it_says_where_it_says(bench, name):
    reader, reads, layer, moves, cells = ADDED[name]
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["layer"] == layer and entry["moves"] == moves
    assert entry["unit"] == "s" and entry["better"] == "lower"
    assert entry["source"] == ("device_trace" if reader == "device_ends"
                               else "program_counter")
    # what a later PR appends stands behind the cells that were there
    assert entry["workloads"][:len(cells)] == cells
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec["kind"] == "reader" and spec["reader"] == reader
    if reader == "span_attrs":
        assert (spec["names"], spec["attr"]) == ([reads[0]], reads[1])
        assert spec["per_rounds"] == 65536
    else:
        # seconds an operation: the ends do not grow with the backlog
        assert spec["end"] == reads and "per_rounds" not in spec


def test_the_new_metrics_are_appended_in_order_behind_what_was_there(bench):
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("wire.recv_s")
    assert names[at:at + len(ADDED)] == list(ADDED)
    assert names.index("device.program_s.quicknet") == at - 1
    # the metrics that time the same layers from outside stay
    assert {"wire.fetch_s", "store.put_s", "store.commit_s", "sync.pack_s",
            "device.idle_unattributed_s.catchup"} <= set(names[:at])

"""The two readers ISSUE 25 adds, on a hand-made device line and span
list: a `while` that holds kernels, a gap under a span, a gap under none."""

import pytest

from benchmark.readers import device_scopes as D
from benchmark.readers import program_spans as S

STAGES = ("sig_decode", "h2c", "miller", "final_exp")


class _Run:
    """What a reader sees of `run.Run`."""

    def __init__(self, traced_op=None, trace=None):
        self._traced_op, self.trace = traced_op, trace


def _line():
    """One device op line, seconds: a copy outside every stage; under
    `miller` a `while` 1..5 whose body ran two kernels twice with 0.1 s
    of loop overhead around them; a kernel under `final_exp` that crosses
    the window's end; an event before the window."""
    w = "jit(run)/miller/while"
    mul = w + "/body/closed_call/jit(wrapped)/flat_mul/pallas_call"
    sqr = w + "/body/closed_call/jit(wrapped)/flat_sqr/pallas_call"
    return [
        ("jit(run)/h2c/early", -1.0, -0.5),
        ("jit(run)/copy", 0.0, 0.5),
        (w, 1.0, 5.0),
        (mul, 1.1, 2.0), (sqr, 2.0, 2.9),
        (mul, 3.1, 4.0), (sqr, 4.0, 4.8),
        ("jit(run)/final_exp/jit(wrapped)/flat_mul/pallas_call", 6.0, 9.0),
    ]


def test_an_event_is_charged_what_the_events_inside_it_leave():
    charged = D.self_times(_line(), (0.0, 8.0))
    own = {}
    for path, t in charged:
        own[path] = own.get(path, 0.0) + t
    assert own["jit(run)/miller/while"] == pytest.approx(0.5)
    assert "jit(run)/h2c/early" not in own
    assert sum(own.values()) == pytest.approx(0.5 + 4.0 + 2.0)   # the union
    by = D.by_stage(charged, STAGES)
    assert by == pytest.approx({"sig_decode": 0.0, "h2c": 0.0,
                                "miller": 4.0, "final_exp": 2.0,
                                "unscoped": 0.5})
    assert D.top_kernels(charged, STAGES) == [
        ["flat_mul", "final_exp", pytest.approx(2.0)],
        ["flat_mul", "miller", pytest.approx(1.8)],
        ["flat_sqr", "miller", pytest.approx(1.7)]]


def test_nesting_three_deep_and_events_that_touch():
    line = [("a/miller/outer", 0, 10), ("a/miller/inner", 2, 8),
            ("a/h2c/leaf", 3, 4), ("a/h2c/leaf", 4, 5), ("", 8.5, 9),
            ("tail", 10, 11), ("", 12, 13)]
    own = dict()
    for path, t in D.self_times(line, (0, 20)):
        own[path] = own.get(path, 0.0) + t
    # an event without an op_name goes with the event that holds it, and
    # nowhere when none does
    assert own == pytest.approx({"a/miller/outer": 3.5, "a/miller/inner": 4,
                                 "a/h2c/leaf": 2, "tail": 1, "": 1,
                                 "a/miller/outer/(no op_name)": 0.5})
    assert D.by_stage(D.self_times(line, (0, 20)), STAGES) == pytest.approx(
        {"sig_decode": 0, "h2c": 2, "miller": 8, "final_exp": 0,
         "unscoped": 2})


def test_neighbours_whose_times_round_apart_are_not_nested():
    """Start plus duration of one kernel and the start of the next are
    two float sums: a hair of overlap is no nesting."""
    hair = 3e-13
    line = [("a/miller/while", 1.0, 2.0),
            ("a/miller/k1", 1.1, 1.4 + hair), ("a/miller/k2", 1.4, 1.7),
            ("a/final_exp/k3", 2.0 - hair, 3.0)]
    own = dict(D.self_times(line, (0, 10)))
    assert own == pytest.approx({"a/miller/while": 0.4, "a/miller/k1": 0.3,
                                 "a/miller/k2": 0.3, "a/final_exp/k3": 1.0},
                                abs=1e-9)
    assert sum(own.values()) == pytest.approx(2.0, abs=1e-9)    # the union


def test_paths_stages_and_kernel_names():
    """An event is named by its instruction less the metadata; the
    compiled program's text has the instruction's `op_name`."""
    text = """HloModule jit_run, entry_computation_layout={()->pred[8]{0}}

%body (p: (s32[], s32[16,384,8,128])) -> (s32[], s32[16,384,8,128]) {
  %g2_dbl_line.3 = s32[16,384,8,128]{3,2,1,0} custom-call(%p.1), custom_call_target="tpu_custom_call", backend_config={"body": "AAAA=="}, metadata={op_name="jit(run)/miller/while/body/closed_call/jit(wrapped)/g2_dbl_line/pallas_call" stack_frame_id=7}
  ROOT %tuple.9 = (s32[], s32[16,384,8,128]{3,2,1,0}) tuple(%i, %g2_dbl_line.3)
}

ENTRY %main.1 (a: u8[8,48]) -> pred[8] {
  %while.45 = (s32[], s32[16,384,8,128]{3,2,1,0}) while(%tuple.4), condition=%cond, body=%body, metadata={op_name="jit(run)/miller/while" stack_frame_id=3}
  %copy.5176 = s32[16384,32]{0,1:T(8,128)S(1)} copy(%x)
  ROOT %and.7 = pred[8]{0} and(%a.1, %b.1), metadata={op_name="jit(run)/and"}
}
"""
    paths = D.instruction_paths(text)
    assert paths == {
        "%g2_dbl_line.3": "jit(run)/miller/while/body/closed_call/"
                          "jit(wrapped)/g2_dbl_line/pallas_call",
        "%while.45": "jit(run)/miller/while", "%and.7": "jit(run)/and"}
    event = ("%g2_dbl_line.3 = s32[16,384,8,128]{3,2,1,0} "
             "custom-call(s32[16,384,8,128]{3,2,1,0} %p.1)")
    path = D.op_path(event, paths)
    assert D.stage_of(path, STAGES) == "miller"
    assert D.kernel_of(path) == "g2_dbl_line"
    assert D.op_path("%while.45 = (s32[]) while(%tuple.4)", paths) == \
        "jit(run)/miller/while"
    # a copy the compiler put in has no op_name: outside every stage
    assert D.op_path("%copy.5176 = s32[16384,32]{0,1} copy(%x)", paths) == ""
    assert D.stage_of("", STAGES) == D.stage_of("jit(run)/millerx/y",
                                                STAGES) == "unscoped"
    assert D.kernel_of("jit(run)/h2c/add") is None


def _fake_run(monkeypatch, tmp_path, line, marks=(0.0, 8.0), rounds=32768):
    from benchmark import trace_reduce as T
    monkeypatch.setattr(D, "stages", lambda: STAGES)
    monkeypatch.setattr(D, "load", lambda logdir, paths: (
        {"/device:TPU:0": line},
        {T.MARK_BEGIN: marks[0], T.MARK_END: marks[1]}))
    return _Run((str(tmp_path), (100.0, 108.0), [], rounds))


def test_the_stage_metrics_add_up_to_the_busy_time(monkeypatch, tmp_path,
                                                   capsys):
    from benchmark import trace_reduce as T
    run = _fake_run(monkeypatch, tmp_path, _line())
    spec = {"per_rounds": 65536}
    got = {st: D.read(run, dict(spec, stage=st))
           for st in (*STAGES, "unscoped")}
    assert got == pytest.approx({"sig_decode": 0.0, "h2c": 0.0,
                                 "miller": 8.0, "final_exp": 4.0,
                                 "unscoped": 1.0})      # per 65,536 rounds
    busy = T.union_seconds([(max(s, 0.0), min(e, 8.0))
                            for _p, s, e in _line()])
    assert sum(got.values()) == pytest.approx(2 * busy)
    out = capsys.readouterr().out
    assert out.count('"device_scopes"') == 1                   # once
    assert f'"busy_s": {busy}' in out


def test_no_trace_no_scope_no_reading(monkeypatch, tmp_path):
    # the harness deleted the trace before it asked (run.py today)
    gone = _Run(("/nonexistent", (0, 1), [], 8))
    assert D.read(gone, {"stage": "miller", "per_rounds": 8}) is None
    assert D.read(_Run(), {"stage": "miller", "per_rounds": 8}) is None
    # a program without the scopes: everything unscoped is no reading
    bare = _fake_run(monkeypatch, tmp_path,
                     [("jit(run)/while", 0.0, 3.0), ("", 4.0, 5.0)])
    assert D.read(bare, {"stage": "unscoped", "per_rounds": 8}) is None


# -- program spans ------------------------------------------------------------

def _spans():
    """(id, parent, name, start, end): a root 0..10, a decode 1..6 that
    holds a flush 2..5 that holds a pack 2..3, and a read 6..7."""
    return [("r", None, "store.scan", 0.0, 10.0),
            ("d", "r", "scan.decode", 1.0, 6.0),
            ("f", "d", "scan.flush", 2.0, 5.0),
            ("p", "f", "scan.pack", 2.0, 3.0),
            ("a", "r", "scan.read", 6.0, 7.0)]


def test_self_time_is_the_duration_less_what_children_cover():
    own = S.self_seconds(_spans())
    assert own == pytest.approx({"r": 4.0, "d": 2.0, "f": 2.0, "p": 1.0,
                                 "a": 1.0})
    assert sum(own.values()) == pytest.approx(10.0)
    # children that overlap each other, or stick out of their parent,
    # are covered once and clipped
    own = S.self_seconds([("r", None, "x", 0, 4), ("a", "r", "y", 1, 3),
                          ("b", "r", "y", 2, 6)])
    assert own["r"] == pytest.approx(1.0)


def test_a_gap_under_a_span_and_a_gap_under_none():
    spans = _spans()
    assert S.open_over((2.2, 2.8), spans) == ["store.scan", "scan.decode",
                                              "scan.flush", "scan.pack"]
    assert S.open_over((6.9, 7.3), spans) == ["store.scan"]
    assert S.open_over((10.0, 11.0), spans) == []
    gaps = [(2.2, 2.8), (9.8, 10.6), (12.0, 12.5)]
    # the second is three quarters outside every span, the third wholly
    assert S.unattributed_seconds(gaps, spans) == pytest.approx(0.8 + 0.5)


def _recorded_run(monkeypatch, with_trace=True):
    """The recorder holding one scan's spans on a window 100..110, with
    one span of an earlier operation and one of a later one."""
    from drand_tpu import tracing
    tracing.RECORDER.clear()
    made = {}
    for sid, parent, name, s, e in _spans():
        made[sid] = tracing.begin_span(name, parent=made.get(parent),
                                       at=100.0 + s)
        made[sid].end(at=100.0 + e)
    tracing.record_span("scan.read", 50.0, 51.0)
    tracing.record_span("scan.read", 120.0, 121.0)
    tracing.record_span("verify.dispatch", 102.0, 102.5, n=512,
                        bucket=16384, pad_rows=15872)
    tracing.record_span("verify.dispatch", 104.0, 104.5, n=16384,
                        bucket=16384, pad_rows=0)
    trace = {"gaps_at": [
        {"at_s": 2.2, "for_s": 0.6, "open": "no_span"},
        {"at_s": 10.4, "for_s": 0.4, "open": "no_span"},
        {"at_s": 5.0, "for_s": 0.0004, "open": "no_span"}]}
    return _Run(("dir", (100.0, 110.0), [], 32768),
                trace if with_trace else None)


def test_metrics_from_the_spans_of_the_traced_operation(monkeypatch, capsys):
    run = _recorded_run(monkeypatch)
    spec = {"per_rounds": 65536}
    assert S.read(run, dict(spec, names=["scan.read"])) == pytest.approx(2.0)
    assert S.read(run, dict(spec, names=["scan.decode"])) == \
        pytest.approx(4.0)                      # self time, per 65,536
    assert S.read(run, dict(spec, names=["scan.pack", "scan.read"])) == \
        pytest.approx(4.0)
    assert S.read(run, dict(spec, names=["sync.queue_wait"])) is None
    assert S.read(run, dict(spec, names=["verify.dispatch"],
                            ratio=["pad_rows", "bucket"])) == \
        pytest.approx(15872 / 32768)
    # of the two gaps of a millisecond or more, the one past the root's
    # end lies under no span
    assert S.read(run, dict(spec, idle="unattributed")) == \
        pytest.approx(2 * 0.4)
    out = capsys.readouterr().out
    assert out.count('"program_spans"') == 1
    assert out.count('"idle_gaps_under_program_spans"') == 1
    assert '"scan.pack"' in out


def test_without_a_trace_or_a_traced_operation_there_is_less_to_read(
        monkeypatch):
    run = _recorded_run(monkeypatch, with_trace=False)
    spec = {"per_rounds": 65536}
    assert S.read(run, dict(spec, names=["scan.read"])) == pytest.approx(2.0)
    assert S.read(run, dict(spec, idle="unattributed")) is None
    assert S.read(_Run(), dict(spec, names=["scan.read"])) is None


def test_a_program_whose_spans_publish_no_start_gives_nothing(monkeypatch):
    """The parent commit's `Span` keeps its monotonic start private."""
    from drand_tpu import tracing
    run = _recorded_run(monkeypatch)

    class Old:
        def __init__(self, sp):
            self.span_id, self.parent_id = sp.span_id, sp.parent_id
            self.name, self.duration_s = sp.name, sp.duration_s
            self.attrs = sp.attrs

    old = [Old(sp) for sp in tracing.RECORDER.spans()]
    monkeypatch.setattr(tracing.RECORDER, "spans", lambda: old)
    assert S.read(run, {"per_rounds": 8, "names": ["scan.read"]}) is None

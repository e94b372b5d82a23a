"""Dispatch flight recorder units (drand_tpu/profiling/dispatch.py):
record math, ring bounds, per-seam totals, metrics feed, and the
never-raise contract of the module-level helpers."""

from drand_tpu.profiling.dispatch import (DISPATCH, DispatchRecord,
                                          DispatchRecorder, record_dispatch,
                                          timed_dispatch)


def test_record_math():
    rec = DispatchRecord(seam="verify", n=10, bucket=16, host_wall_s=0.004)
    assert rec.fill_ratio == 10 / 16
    assert rec.padding_rounds == 6
    assert rec.us_per_round == 0.004 / 10 * 1e6
    d = rec.to_dict()
    assert d["fill_ratio"] == 0.625 and d["padding_rounds"] == 6
    # exact-bucket dispatch wastes nothing
    full = DispatchRecord(seam="verify", n=16, bucket=16, host_wall_s=0.004)
    assert full.fill_ratio == 1.0 and full.padding_rounds == 0
    # degenerate shapes must not divide by zero
    empty = DispatchRecord(seam="verify", n=0, bucket=0, host_wall_s=0.0)
    assert empty.fill_ratio == 0.0 and empty.us_per_round == 0.0


def test_ring_bounds_and_totals_survive_eviction():
    ring = DispatchRecorder(maxlen=4)
    for i in range(10):
        ring.record("verify", n=1, bucket=2, host_wall_s=0.001)
    assert len(ring) == 4                      # ring forgot 6
    tot = ring.seam_summary()["verify"]
    assert tot["dispatches"] == 10             # totals did not
    assert tot["rounds"] == 10
    assert tot["padding_rounds"] == 10
    assert tot["avg_fill_ratio"] == 0.5


def test_seam_summary_amortized_cost():
    ring = DispatchRecorder()
    ring.record("verify", n=10, bucket=16, host_wall_s=0.004)
    ring.record("verify", n=16, bucket=16, host_wall_s=0.004)
    ring.record("aggregate", n=3, bucket=3, host_wall_s=0.001,
                queue_wait_s=0.5, backend="host")
    s = ring.seam_summary()
    assert s["verify"]["avg_fill_ratio"] == round(26 / 32, 4)
    assert s["verify"]["amortized_us_per_round"] == round(
        0.008 / 26 * 1e6, 3)
    assert s["aggregate"]["queue_wait_s"] == 0.5
    # per-seam filtering and newest-first snapshot
    assert [r.n for r in ring.records(seam="verify")] == [10, 16]
    snap = ring.snapshot(limit=2)
    assert [r["seam"] for r in snap["recent"]] == ["aggregate", "verify"]
    assert snap["recent"][0]["attrs"] == {"backend": "host"}


def test_record_feeds_prometheus():
    from drand_tpu import metrics as M
    before = M.DISPATCH_PADDING.labels("verify")._value.get()
    ring = DispatchRecorder()
    ring.record("verify", n=10, bucket=16, host_wall_s=0.004)
    assert M.DISPATCH_PADDING.labels("verify")._value.get() == before + 6
    assert M.DISPATCH_FILL_RATIO.labels("verify")._value.get() == 0.625
    hist = M.DISPATCH_SECONDS.labels("verify", "16")
    assert hist._sum.get() > 0.0


def test_module_helpers_never_raise():
    # garbage that would blow up int()/float() inside record() must be
    # swallowed: the flight recorder is an observer, not a participant
    record_dispatch("verify", "not-a-number", 16, 0.001)
    record_dispatch("verify", 4, 8, "also-not-a-number")
    # and a well-formed record through the singleton does land — assert
    # on the newest record, not on length growth: the process-global
    # ring may already be at capacity from earlier tests' dispatches
    before = DISPATCH.seam_summary().get("verify", {}).get("dispatches", 0)
    record_dispatch("verify", 4, 8, 0.001, path="test")
    rec = DISPATCH.records(seam="verify")[-1]
    assert rec.n == 4 and rec.bucket == 8 and rec.attrs["path"] == "test"
    assert DISPATCH.seam_summary()["verify"]["dispatches"] == before + 1


def test_timed_dispatch_context_manager():
    ring = DispatchRecorder()
    orig = DISPATCH._ring, DISPATCH._totals
    # timed_dispatch records through the module singleton; swap its
    # storage so the test observes exactly one record
    DISPATCH._ring, DISPATCH._totals = ring._ring, ring._totals
    try:
        with timed_dispatch("partials", n=6, bucket=8, path="tabled") as td:
            pass
        assert td.host_wall_s >= 0.0
        recs = ring.records(seam="partials")
        assert len(recs) == 1
        assert recs[0].n == 6 and recs[0].bucket == 8
        assert recs[0].attrs == {"path": "tabled"}
    finally:
        DISPATCH._ring, DISPATCH._totals = orig

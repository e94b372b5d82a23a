"""Unit tests for bench.py's primed steady-state timing protocol.

The real measurements run on the TPU; these pin the protocol's
bookkeeping — dispatch/settle interleaving, primer/timed split, resolve
order — so a refactor cannot silently change what the recorded numbers
mean.  Round 4 made the protocol a true depth-`primers` pipeline
(ADVICE r3: the old version dispatched every rep before the clock
started, excluding all dispatch cost from the window); these tests pin
the new shape: only the pipe fill precedes the clock, and every timed
settle dispatches its successor first.
"""

import bench


class _FakeClock:
    """Ticks only when a resolver runs, so `elapsed` counts exactly the
    resolves inside the timed window."""

    def __init__(self):
        self.t = 0.0

    def time(self):
        return self.t


def _recorder(events, clock):
    def dispatch(i):
        events.append(("dispatch", i))

        def resolve():
            events.append(("resolve", i))
            clock.t += 1.0          # each resolve costs one fake second
            return i
        return resolve
    return dispatch


def test_timed_primed_single_primer(monkeypatch):
    clock = _FakeClock()
    monkeypatch.setattr(bench.time, "time", clock.time)
    events = []
    elapsed, oks = bench._timed_primed(_recorder(events, clock), reps=3)
    # depth-1 pipeline: ONE dispatch fills the pipe; each settle first
    # dispatches its successor (so rep k+1's host prep/dispatch overlaps
    # rep k's compute INSIDE the timed window)
    assert events == [
        ("dispatch", 0),                       # pipe fill
        ("resolve", 0), ("dispatch", 1),       # primer settles, refill
        ("dispatch", 2), ("resolve", 1),       # timed: dispatch-then-settle
        ("dispatch", 3), ("resolve", 2),
        ("resolve", 3),
    ]
    assert oks == [0, 1, 2, 3]
    # the clock starts AFTER the primer resolves: elapsed covers exactly
    # the 3 timed resolves (a regression that times the primer -> 4.0)
    assert elapsed == 3.0


def test_timed_primed_multi_primer(monkeypatch):
    """Multichain shape: k primers (one full rep across chains) = a
    depth-k pipeline."""
    clock = _FakeClock()
    monkeypatch.setattr(bench.time, "time", clock.time)
    k, reps = 2, 6          # REPS=3 across k=2 chains -> 6 timed units
    events = []
    elapsed, oks = bench._timed_primed(_recorder(events, clock),
                                       reps=reps, primers=k)
    assert len([e for e in events if e[0] == "dispatch"]) == k + reps
    # exactly k dispatches precede the first resolve: the pipe depth is
    # `primers`, never the full rep count
    first_resolve = next(i for i, e in enumerate(events)
                         if e[0] == "resolve")
    assert first_resolve == k
    # FIFO settle order, all results returned
    resolves = [e[1] for e in events if e[0] == "resolve"]
    assert resolves == list(range(k + reps))
    assert oks == list(range(k + reps))
    # all k primer resolves are excluded from the timed window
    assert elapsed == float(reps)


def test_bench_partials_bookkeeping(monkeypatch, tmp_path, capsys):
    """bench_partials on a stub backend: the rebuilt config's
    bookkeeping — rounds-major dispatch, negative control, distinct-
    message/table accounting, and the BENCH_partials-shaped --json
    artifact — pinned without device work (on the chip the aggregation
    path is not measured: ROADMAP M2)."""
    import json

    from drand_tpu.crypto import tbls

    class _StubBackend:
        def __init__(self, pub, t, n):
            self.pub, self.threshold, self.n = pub, t, n
            self.stats = {"batches": 0, "partials": 0,
                          "distinct_messages": 0, "table_hits": 0,
                          "table_fallbacks": 0}

        def verify_partials_rounds(self, msgs, by_round):
            k = sum(len(p) for p in by_round)
            self.stats["batches"] += 1
            self.stats["partials"] += k
            self.stats["distinct_messages"] += len(msgs)
            self.stats["table_hits"] += k
            out = []
            for m, parts in zip(msgs, by_round):
                out.append([tbls.verify_partial(self.pub, m, p)
                            for p in parts])
            return out

        def recover_rounds(self, msgs, by_round):
            return [tbls.recover(self.pub, m, list(p), self.threshold,
                                 self.n, verified=True)
                    for m, p in zip(msgs, by_round)]

    import drand_tpu.beacon.crypto_backend as cb
    monkeypatch.setattr(cb, "DeviceBackend", _StubBackend)
    monkeypatch.setattr(bench, "CONFIG", "partials")
    monkeypatch.setattr(bench, "REPS", 1)
    monkeypatch.setenv("BENCH_PARTIAL_ROUNDS", "2")
    out_path = tmp_path / "BENCH_partials.json"
    monkeypatch.setattr(bench, "_JSON_OUT", str(out_path))
    bench.bench_partials()
    line = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(line)
    assert rec["unit"] == "partials/sec"
    assert rec["rounds"] == 2 and rec["signers"] == 16
    assert rec["batch"] == 32 and rec["distinct_messages"] == 2
    assert rec["table_fallbacks"] == 0 and rec["table_hits"] == 32
    assert rec["hash_dedup_factor"] == 16.0
    assert rec["recoveries_per_sec"] > 0
    assert "vs_baseline" in rec and rec["config"] == "partials"
    on_disk = json.loads(out_path.read_text())
    assert on_disk == rec

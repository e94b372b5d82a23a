"""The percentile rule, the pairing of arrivals with commits, the seeded
draw of the output check."""

import numpy as np
import pytest

from benchmark import harness as H


def test_p95_needs_ten_samples_beyond_it():
    vals = list(range(1, 257))                 # two catch-ups' messages
    assert H.percentile(vals, 95) == 244       # rank ceil(0.95 * 256)
    assert H.percentile(list(range(1, 201)), 95) == 190
    with pytest.raises(H.BenchFailure):
        H.percentile(list(range(1, 200)), 95)  # 199 leave 9 beyond
    with pytest.raises(H.BenchFailure):
        H.percentile(list(range(128)), 95)     # one catch-up is too few
    with pytest.raises(H.BenchFailure):
        H.percentile([], 95)


def test_arrivals_pair_with_the_commit_that_reaches_their_last_round():
    arrivals = [(512, 0.1), (1024, 0.2), (1536, 0.3), (2048, 0.4)]
    commits = [(512, 1.0), (2048, 3.0)]
    assert H.pair_chunk_commits(arrivals, commits) == pytest.approx(
        [0.9, 2.8, 2.7, 2.6])
    # a message no commit reached has no latency
    assert H.pair_chunk_commits(arrivals, [(1024, 1.0)]) == pytest.approx(
        [0.9, 0.8])
    assert H.pair_chunk_commits(arrivals, []) == []


def test_the_draw_is_the_seeds_alone_and_meets_every_kind_of_round():
    starts = [1, 513, 16897, 33281, 49665]
    kinds = {}
    for seed in range(2**31 + 5, 2**31 + 17):      # past 32 signed bits
        d = H.draw_check(seed, 65536, starts, 512, 32, 3)
        assert d == H.draw_check(seed, 65536, starts, 512, 32, 3)
        rounds = [f[0] for f in d["faults"]]
        assert len(rounds) == 3 == len(set(rounds))
        assert rounds == sorted(rounds) and d["kind_round"] in rounds
        assert not set(rounds) & set(d["sample"])
        assert 29 <= len(d["sample"]) <= 32
        assert all(1 <= r <= 65536 for r in rounds + d["sample"])
        kinds.setdefault(d["kind"], []).append(d["kind_round"])
    assert set(kinds) == {"ramp", "segment_first", "segment_last",
                          "anywhere"}
    assert all(r <= 512 for r in kinds["ramp"])
    assert all(r in starts[1:] for r in kinds["segment_first"])
    assert all(r in (512, 16896, 33280, 49664, 65536)
               for r in kinds["segment_last"])


def test_plant_flips_one_bit_of_each_faulted_round():
    sigs = np.arange(5 * 48, dtype=np.uint8).reshape(5, 48)
    bad, bad_prevs = H.plant(sigs, [(2, 1000, 3), (5, 7, 0)])
    assert bad_prevs is None
    diff = np.bitwise_xor(bad, sigs)
    assert np.count_nonzero(diff) == 2
    assert diff[1, 1000 % 48] == 8 and diff[4, 7] == 1
    assert (sigs == np.arange(5 * 48, dtype=np.uint8).reshape(5, 48)).all()


def test_an_unknown_device_kind_is_an_error():
    assert H.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] > 0
    with pytest.raises(H.BenchFailure):
        H.peaks_for("TPU v9 imaginary")


def test_a_thin_window_goes_on_for_one_more_catch_up():
    from benchmark.drivers.catchup import Driver
    ctx = H.Ctx(config={}, traffic={}, sigs=np.zeros((4, 96), np.uint8),
                prevs=None, group=None, spans=H.Spans(), verifier=None,
                workdir="")
    one = {"ok": True, "rounds": 65536, "chunk_commit_s": [1.0] * 128}
    assert Driver(ctx).wants_more([one])            # 128 carry no p95
    assert not Driver(ctx).wants_more([one, one])   # 256 do
    failed = dict(one, ok=False)
    assert not Driver(ctx).wants_more([failed])     # never past a failure


def test_any_whole_number_is_a_seed():
    starts = [1, 513, 16897, 33281, 49665]
    for seed in (0, -7, 2**31 + 9, 2**63 + 1, 2**64 + 5):
        d = H.draw_check(seed, 65536, starts, 512, 32, 3)
        assert len(d["faults"]) == 3

"""`ShardedVerifier` is a verifier like the other (ISSUE 34): `build(n)`
for `n` rows a device takes `Verifier.build`'s path (one algorithm, one
record, the same spans), the body under its `shard_map` is the one-device
program's exported form, and a process on a host of four chips reads the
very file a one-chip process writes.

(b) uses a small stand-in body under two of the program's scopes; (c)
the real G1 program (`bls-unchained-g1-rfc9380`, compact ladders) at 8
rows a device, its verdicts held to the benchmark's plain reference
(marked slow: its build takes over two minutes; `--runslow`).
Four of the suite's eight virtual devices; the cache directory is the
test's own.
"""

import json
import os
import re

import jax
import numpy as np
import pytest

import drand_tpu.verify as V
from benchmark import harness as H
from drand_tpu import aot, ops, tracing
from drand_tpu.chain.scheme import scheme_by_id
from drand_tpu.chain.verify import ChainVerifier
from drand_tpu.crypto.bls12381 import curve as GC
from drand_tpu.ops.field import compact_scope
from drand_tpu.parallel import ShardedVerifier
from test_exported_program import StandIn, cache  # noqa: F401  (its stand-in
# body under two of the program's scopes, and its own cache directory)

N = 8               # rows a device
MESH = 4
RECORD = {"program", "bucket", "devices", "miller_lines", "tracing",
          "source", "load_s", "blob_bytes", "trace_s", "lower_s",
          "compile_s", "lowered"}


def _one():
    return StandIn(GC.G1_GEN, V.SHAPE_UNCHAINED)


def _mesh():
    return ShardedVerifier(_one(), devices=jax.devices()[:MESH])


def _want(rounds, sigs):
    msgs = V.rounds_be8(rounds)
    return (3 * (3 * (3 * msgs.sum(axis=1, dtype=np.int64) + 1) + 1) + 1
            + sigs.sum(axis=1)) % 2 == 0


@pytest.mark.parametrize("first", ["the_mesh", "one_device"])
def test_build_traces_once_whichever_host_came_first(cache, first):
    """One file a program: the mesh's build writes what a one-device
    verifier loads, and the other way round."""
    a, b = (_mesh(), _one()) if first == "the_mesh" else (_one(), _mesh())
    rec_a, rec_b = a.build(N), b.build(N)
    assert (rec_a["source"], rec_b["source"]) == ("traced", "loaded")
    assert rec_a["blob_bytes"] == rec_b["blob_bytes"] > 0
    assert rec_a["program"] == rec_b["program"] == _one()._aot_name(N)
    path, key = aot.exported_path(_one()._aot_name(N), False,
                                  _one()._body_tag())
    assert os.listdir(cache) == [os.path.basename(path)]
    # the key names one device, whatever this process holds (eight)
    assert f"-{jax.devices()[0].device_kind}-1-jax" in key
    assert len(jax.devices()) == 8


@pytest.mark.parametrize("source", ["traced", "loaded"])
def test_the_mesh_build_keeps_the_record_and_the_spans(cache, source):
    if source == "loaded":
        _mesh().build(N)
    tracing.RECORDER.clear()
    sv = _mesh()
    rec = sv.build(N)
    assert set(rec) == RECORD and rec["source"] == source
    assert (rec["bucket"], rec["devices"]) == (N, MESH)
    assert 0 <= rec["load_s"] <= rec["trace_s"]
    assert rec["lower_s"] > 0 and rec["compile_s"] > 0
    json.dumps({k: v for k, v in rec.items() if k != "lowered"})
    spans = {s.name: s.to_dict() for s in tracing.RECORDER.spans()}
    build = spans["verifier.build"]
    assert build["attrs"]["source"] == source
    assert build["attrs"]["devices"] == MESH
    phases = {"build.load", "build.lower", "build.compile"} | (
        {"build.trace"} if source == "traced" else set())
    assert {n for n in spans if n.startswith("build.")} == phases
    assert all(spans[n]["parent_id"] == build["span_id"] for n in phases)
    # installed for the mesh's rows, with text the stage reader can use,
    # and nothing installed for one device
    assert list(sv._kernels) == [MESH * N] and not sv.verifier._kernels
    paths = re.findall(r'op_name="([^"]*)"', sv._kernels[MESH * N].as_text())
    for stage in (ops.MILLER, ops.FINAL_EXP):
        assert any(stage in p.split("/") for p in paths), stage
    assert "shard_map" in rec["lowered"].as_text(debug_info=True)


def test_the_built_program_is_the_one_a_dispatch_runs(cache, monkeypatch):
    """A dispatch of the mesh's rows finds the program `build` installed
    (no second build), its inputs laid over the four devices and its
    verdicts back in round order."""
    monkeypatch.setattr(V, "_BUCKETS", (N,))
    sv = _mesh()
    sv.build(N)
    rng = np.random.default_rng(34)
    rounds = np.arange(1, MESH * N + 1, dtype=np.uint64)
    sigs = rng.integers(0, 256, (MESH * N, 96), dtype=np.uint8)
    tracing.RECORDER.clear()
    got = sv.verify_batch(rounds, sigs)
    assert (got == _want(rounds, sigs)).all() and got.shape == (MESH * N,)
    names = [s.name for s in tracing.RECORDER.spans()]
    assert "verifier.build" not in names
    assert names.count("verify.shard_put") == names.count("verify.gather") \
        == 1
    out = sv._kernels[MESH * N](
        *jax.device_put((V.rounds_be8(rounds), sigs),
                        sv._named("rounds", None)), sv._pk_placed)
    assert len(out.sharding.device_set) == MESH
    assert out.sharding.is_equivalent_to(sv._named("rounds"), out.ndim)


# -- (c) the real G1 program over the mesh ------------------------------------

@pytest.fixture(scope="module")
def quicknet(tmp_path_factory):
    """(config, rounds 1..32 of the benchmark's quicknet fixture, a
    ChainVerifier over four devices with the 8-row program built)."""
    with open(os.path.join(H.BENCH_DIR, "configs", "quicknet-g1.json")) as f:
        config = json.load(f)
    sigs = np.ascontiguousarray(np.load(os.path.join(
        H.BENCH_DIR, "fixtures", config["fixture"]["file"]))[:MESH * N])
    cv = ChainVerifier(scheme_by_id(config["scheme_id"]),
                       bytes.fromhex(config["public_key_hex"]))
    assert cv.scheme.shape == V.SHAPE_UNCHAINED_G1
    cv._lazy_verifier = ShardedVerifier(
        V.Verifier(cv._pk_point, cv.scheme.shape,
                   single_host=cv._verify_single),
        devices=jax.devices()[:MESH])
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(V, "_BUCKETS", (N,))
        with compact_scope(True):
            rec = cv._verifier.build(N)
        assert rec["program"].startswith("verify-g1sig-un-")
        assert (rec["tracing"], rec["devices"]) == ("compact", MESH)
        # the key's table of lines is the replicated argument (ISSUE 37)
        assert (rec["miller_lines"], rec["line_steps"]) == ("table", 68)
        assert cv._verifier.verifier._pk.shape == (68, 2, 6, 32)
        yield config, sigs, cv


FLIPS = {"sound": (), "one_bit_in_each_shard": (3, 8, 22, 31)}


@pytest.mark.slow   # the build is 126 s of a worker cold (sandbox, PR 34)
@pytest.mark.parametrize("case", sorted(FLIPS))
def test_the_g1_program_over_the_mesh_equals_the_plain_reference(quicknet,
                                                                 case):
    config, sigs, cv = quicknet
    bad = sigs.copy()
    for row in FLIPS[case]:
        bad[row, 17] ^= np.uint8(1 << 3)
    rounds = list(range(1, MESH * N + 1))
    flipped = sorted(FLIPS[case])
    assert sorted({row // N for row in flipped}) \
        == ([0, 1, 2, 3] if flipped else [])
    # the reference judges the flipped rows and a sample of the sound
    # ones (a sixth of a second a pairing in pure Python)
    judged = sorted(set(flipped) | {0, 9, 18, MESH * N - 1})
    want = H.reference_verdicts(config, [rounds[i] for i in judged],
                                bad[judged])
    assert list(want) == [i not in flipped for i in judged]
    served = np.asarray(cv.verify_beacons(H.beacons_of(bad, None, rounds)))
    assert served.shape == (MESH * N,)
    assert list(served[judged]) == list(want)
    assert list(np.nonzero(~served)[0]) == flipped

"""`pedersen-bls-chained` through the batched PROGRAM (ISSUE 29): the
scheme whose message is sha256(previous_sig || uint64_be(round)), from
round 1 over the 32-byte genesis seed, by `Verifier(SHAPE_CHAINED)` at
the tests' one bucket (64 rows; the ladders traced compact, as the chip's
program has them), reached as the served path reaches it:
`ChainVerifier.verify_packed_segment_async`, and `SyncManager` over it
into the store stack `new_chain_store` builds.

Every verdict is held to the benchmark's plain reference
(`benchmark/reference`, which imports nothing of the program), exactly:
all true on a sound chain; one signature bit flipped at r gives false at
r and at r + 1 (whose message holds the flipped bytes) and true
elsewhere; a wrong anchor gives false at the first row only.  Round 1 is
the one row verified off the device, and its verdict gates its segment.

The chain is the benchmark's own 1,024-round test fixture
(`benchmark/tests/chained/default-chained.json`).
"""

import asyncio
import functools
import json
import os

import numpy as np
import pytest

import drand_tpu.beacon.sync_manager as SM
import drand_tpu.verify as V
from benchmark import harness as H
from drand_tpu import tracing
from drand_tpu.chain.scheme import scheme_by_id
from drand_tpu.chain.segment import PackedBeacons
from drand_tpu.chain.verify import ChainVerifier
from drand_tpu.ops.field import compact_scope

N = 80              # rounds of the chain used here
SEGMENT = 40        # rows a dispatch: over the host path's 32, under 64
# a dispatch of the 64-row program takes the CPU twelve seconds and its
# build two minutes: the cases below share one build and ten dispatches


@pytest.fixture(scope="module")
def chained():
    """(config, sigs[N, 96], genesis seed, ChainVerifier with the
    bucket's program built, the build's lowered text)."""
    with open(os.path.join(H.BENCH_DIR, "tests", "chained",
                           "default-chained.json")) as f:
        config = json.load(f)
    sigs = np.ascontiguousarray(np.load(os.path.join(
        H.BENCH_DIR, "fixtures", config["fixture"]["file"]))[:N])
    cv = ChainVerifier(scheme_by_id(config["scheme_id"]),
                       bytes.fromhex(config["public_key_hex"]))
    assert cv.scheme.shape == V.SHAPE_CHAINED
    # the tests' eight virtual devices would shard the batch; the cell's
    # machine holds one chip, where `ChainVerifier` builds this verifier
    cv._lazy_verifier = V.Verifier(cv._pk_point, cv.scheme.shape,
                                   single_host=cv._verify_single)
    with compact_scope(True):
        rec = cv._verifier.build(V._bucket(SEGMENT))
    assert rec["program"].startswith("verify-g2sig-ch-")
    assert rec["tracing"] == "compact"
    return (config, sigs, bytes.fromhex(config["genesis_seed_hex"]), cv,
            rec["lowered"].as_text(debug_info=True))


@functools.lru_cache(maxsize=None)
def _reference_verdict(config_json: str, round_: int, sig: bytes,
                       prev: bytes) -> bool:
    """One row by the plain reference (a sixth of a second in pure
    Python: a row is judged once however many cases hold it)."""
    return bool(H.reference_verdicts(
        json.loads(config_json), [round_],
        np.frombuffer(sig, dtype=np.uint8)[None], [prev])[0])


def _reference(config, start: int, sigs: np.ndarray, anchor: bytes):
    """The reference's verdicts on a segment as a consumer holds it: each
    row over the row before, the first over the consumer's own anchor."""
    rows = [s.tobytes() for s in sigs]
    prevs = [anchor] + rows[:-1]
    cfg = json.dumps(config, sort_keys=True)
    return np.array([_reference_verdict(cfg, start + i, rows[i], prevs[i])
                     for i in range(len(rows))])


def _flipped(sigs: np.ndarray, *rows: int, byte: int = 17, bit: int = 3):
    bad = sigs.copy()
    for row in rows:
        bad[row, byte] ^= np.uint8(1 << bit)
    return bad


def _served(cv, start: int, sigs: np.ndarray, anchor: bytes) -> np.ndarray:
    packed = PackedBeacons(start_round=start, sigs=sigs,
                           first_prev=b"\x00" * 96, chained=True)
    return np.asarray(cv.verify_packed_segment_async(packed, anchor)())


CASES = {
    # name: (first row of the segment, flipped rows, wrong anchor)
    "sound_from_round_1": (0, (), False),
    "flips_at_round_1_in_the_middle_and_at_the_last_row":
        (0, (0, 11, SEGMENT - 1), False),
    "wrong_genesis_seed": (0, (), True),
    "wrong_anchor_and_a_flip_past_round_1": (SEGMENT, (SEGMENT + 5,), True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_verdict_of_the_program_equals_the_plain_reference(chained,
                                                                 case):
    config, sigs, seed, cv, _text = chained
    first, flips, wrong_anchor = CASES[case]
    chain = _flipped(sigs, *flips)
    seg = chain[first:first + SEGMENT]
    anchor = seed if first == 0 else sigs[first - 1].tobytes()
    if wrong_anchor:
        anchor = bytes([anchor[0] ^ 1]) + anchor[1:]
    tracing.RECORDER.clear()
    got = _served(cv, first + 1, seg, anchor)
    want = np.ones(SEGMENT, dtype=bool)
    for flip in flips:
        want[flip - first:flip - first + 2] = False
    if wrong_anchor:
        want[0] = False
    assert (_reference(config, first + 1, seg, anchor) == want).all()
    assert (got == want).all(), np.nonzero(got != want)[0]
    # who verified what: round 1 on the host, once, every other row in
    # one dispatch of the chained program (104-byte messages)
    spans = {}
    for sp in tracing.RECORDER.spans():
        spans.setdefault(sp.name, []).append(sp)
    dispatch, = spans["verify.dispatch"]
    rows = SEGMENT - 1 if first == 0 else SEGMENT
    assert dispatch.attrs["n"] == rows and dispatch.attrs["bucket"] == 64
    assert dispatch.attrs["msg_bytes"] == 96 + 8
    assert dispatch.attrs["h2d_bytes"] == 64 * (104 + 96)
    if first == 0:
        link, = spans["verify.genesis_link"]
        assert link.round == 1 and link.attrs["ok"] == bool(want[0])
        assert link.attrs["tier"] in ("native", "golden")
        # enqueued first, checked while the device works
        assert dispatch.start_mono < link.start_mono
    else:
        assert "verify.genesis_link" not in spans


def test_round_1_alone_is_judged_off_the_device(chained):
    """A segment of one row over the genesis seed: no dispatch at all."""
    config, sigs, seed, cv, _text = chained
    inner = cv._verifier
    for chain, want in ((sigs, True), (_flipped(sigs, 0), False)):
        tracing.RECORDER.clear()
        got = inner.verify_chain_segment(
            1, chain[:1], np.frombuffer(seed, dtype=np.uint8))
        assert got.tolist() == [want]
        assert _reference(config, 1, chain[:1], seed).tolist() == [want]
        names = [sp.name for sp in tracing.RECORDER.spans()]
        assert "verify.genesis_link" in names
        assert "verify.dispatch" not in names


def test_the_digest_has_a_stage_of_its_own_in_the_chained_program(chained):
    """In the lowered text of the program the cases above ran: the two
    SHA blocks a row under `digest`, beside the four stages that were
    there."""
    from drand_tpu import ops
    text = chained[4]
    assert ops.STAGES[0] == ops.DIGEST == "digest"
    for stage in ops.STAGES:
        assert f'"jit(run)/{stage}/' in text, stage


def test_a_second_verifier_loads_the_program_the_first_one_left(chained):
    """(ISSUE 32) The fixture's build wrote the program's exported form
    beside JAX's cache, or found it there.  A verifier with nothing
    built, as a started process has it, takes the program from that
    file, lowers the same module, and judges a flipped segment as the
    reference does."""
    _config, sigs, _seed, cv, text = chained
    v = V.Verifier(cv._pk_point, cv.scheme.shape)
    with compact_scope(True):
        rec = v.build(V._bucket(SEGMENT))
    assert rec["source"] == "loaded" and "load_error" not in rec
    assert rec["blob_bytes"] > 1_000_000
    assert rec["load_s"] <= rec["trace_s"]
    # the same module but for the call stacks in its locations (a whole
    # comparison of two 17 MB texts is not for an assertion to print)
    loaded = rec["lowered"].as_text(debug_info=True)
    for part in ("func.func", "stablehlo.while", "stablehlo.case",
                 '"jit(run)/digest/', '"jit(run)/miller/'):
        assert loaded.count(part) == text.count(part) > 0, part
    seg = _flipped(sigs, SEGMENT + 5)[SEGMENT:2 * SEGMENT]
    got = v.verify_chain_segment(SEGMENT + 1, seg, sigs[SEGMENT - 1])
    want = np.ones(SEGMENT, dtype=bool)
    want[5:7] = False
    assert (got == want).all(), np.nonzero(got != want)[0]


# -- SyncManager over the program, into the store stack ------------------------

class _PackedNet:
    """A peer that serves the chain in packed 8-round messages, as the
    chunked wire does: signatures only, `first_prev` as stored."""

    def __init__(self, sigs: np.ndarray, seed: bytes, chunk: int = 8):
        self.sigs, self.seed, self.chunk = sigs, seed, chunk

    def sync_chain(self, peer, from_round):
        async def gen():
            for at in range(from_round - 1, len(self.sigs), self.chunk):
                prev = self.seed if at == 0 else self.sigs[at - 1].tobytes()
                yield PackedBeacons(start_round=at + 1,
                                    sigs=self.sigs[at:at + self.chunk],
                                    first_prev=prev, chained=True)
        return gen()


class _Clock:
    def now(self):
        return 0.0


def _catch_up(chained, served: np.ndarray, tmp_path, monkeypatch):
    """(ok, rounds, sigs, prevs committed) of one catch-up from round 1
    to N with a 40-round target: the backlog is known, so the first
    segment is cut where the 64-row program is full (ISSUE 30)."""
    config, _sigs, seed, cv, _text = chained
    monkeypatch.setattr(SM, "SYNC_CHUNK", SEGMENT)
    monkeypatch.setattr(SM, "SYNC_CHUNK_GROWTH", 1)
    group = H.group_of(config)
    store = H.new_node_store(str(tmp_path / "consumer.db"), group)
    try:
        mgr = SM.SyncManager(store, group, cv, _PackedNet(served, seed),
                             [object()], _Clock(),
                             insecure_store=store.insecure)
        ok = asyncio.run(mgr._try_node(object(), SM.SyncRequest(1, N)))
        last = store.last().round
        rounds, got, prevs = H.stored_rows(store.insecure, last, 96) \
            if last else (np.zeros(0, dtype=np.uint64),
                          np.zeros((0, 96), dtype=np.uint8), [])
    finally:
        store.close()
    return ok, rounds, got, prevs


FULL = 64           # V._bucket(SEGMENT): where the catch-up cuts


@pytest.mark.parametrize("flip", [None, 0, 70],
                         ids=["sound", "round_1", "second_segment"])
def test_a_catch_up_commits_nothing_at_or_after_a_flipped_signature(
        chained, tmp_path, monkeypatch, flip):
    """Two segments, 64 rounds (round 1 on the host, 63 rows on the
    device) and the 16 left, each one dispatch of the 64-row program.
    What is committed equals the true chain in both fields."""
    config, sigs = chained[:2]
    assert V._bucket(SEGMENT) == FULL
    served = sigs if flip is None else _flipped(sigs, flip)
    tracing.RECORDER.clear()
    ok, rounds, got, prevs = _catch_up(chained, served, tmp_path,
                                       monkeypatch)
    # a failed segment commits nothing of itself: the committed rounds
    # end where the flipped row's segment begins
    committed = N if flip is None else (flip // FULL) * FULL
    assert ok is (flip is None)
    assert rounds.tolist() == list(range(1, committed + 1))
    assert H.rows_differing(got, prevs, sigs[:committed],
                            H.previous_sigs(config, sigs)[:committed]) == 0
    by_name = {}
    for sp in tracing.RECORDER.spans():
        by_name.setdefault(sp.name, []).append(sp)
    assert len(by_name["verify.genesis_link"]) == 1
    commits = [sp for sp in by_name.get("store.commit", ())
               if sp.attrs.get("rows")]
    checks = by_name.get("store.link_check", [])
    assert len(commits) == len(checks) == -(-committed // FULL)
    segments = [(sp.attrs["rounds"], sp.attrs["cut"])
                for sp in by_name["sync.segment"]]
    assert segments == [(FULL, "full"), (N - FULL, "backlog_end")]
    # round 1 is the host's: 63 rows of the first segment on the device
    assert [sp.attrs["n"] for sp in by_name["verify.dispatch"]] \
        == [FULL - 1, N - FULL]
    assert sum(sp.attrs["rows"] for sp in checks) == committed
    # two fields a row; round 1's previous signature is the 32-byte seed
    assert sum(sp.attrs["payload_bytes"] for sp in commits) == \
        (2 * 96 * committed - 64 if committed else 0)
    ids = {sp.span_id for sp in commits}
    assert all(sp.parent_id in ids for sp in checks)

"""What the benchmark's drivers share: the harness's own spans and the
thin wrappers that record them, the two-node stand (a copy of
`tools/bench_sync.py`'s `_fill_store`, `_serve`, `_Group`, `_Peer`,
`_Clock` and `_StubVerifier`, which PR 22 ran on the chip: later PRs may
change `tools/`, not the yardstick), the percentile rule, the pairing of
chunk arrivals with commits, the seeded draw of sampled and faulted
rounds, and the plain reference's verdicts and findings.

A chain is an `[N, signature_bytes]` array of signatures, row i round
i + 1.  Under a chained scheme (`"chained": true` in the configuration)
a row also has a `previous_sig`, which is derived and never stored in a
fixture: the signature of the row before, and for round 1 the chain's
genesis seed (`genesis_seed_hex`).  `prevs` below is that column as a
list of bytes, or None under an unchained scheme, where every function
does what it did before it knew of one.

Nothing here imports JAX.  `drand_tpu` is imported inside the functions
that need it, after `run.py` has placed the configuration's environment.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import struct
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class BenchFailure(Exception):
    """The run cannot give a sound result; the message is the reason."""


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


def load_json(*parts: str) -> dict:
    path = os.path.join(BENCH_DIR, *parts)
    if not os.path.exists(path):
        raise BenchFailure(f"no file benchmark/{'/'.join(parts)}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Ctx:
    """What `run.py` hands a driver."""

    config: dict            # configs/<name>.json
    traffic: dict           # traffic/<name>.json
    sigs: np.ndarray        # the chain: row i is round i + 1
    prevs: "list[bytes] | None"   # its previous_sig column (chained only)
    group: "Group"
    spans: "Spans"
    verifier: object        # what the traffic is verified by, in spans
    workdir: str            # scratch directory of this run


# -- spans --------------------------------------------------------------------

class Spans:
    """The harness's own spans: (name, start, end) on `time.perf_counter`,
    kept in memory.  Worker threads append too (list.append is atomic)."""

    def __init__(self):
        self.rows: list[tuple[str, float, float]] = []

    def add(self, name: str, t0: float, t1: float) -> None:
        self.rows.append((name, t0, t1))

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.rows.append((name, t0, time.perf_counter()))

    def totals(self, since: int = 0) -> dict[str, float]:
        """Seconds under each name over the rows from index `since` on."""
        out: dict[str, float] = {}
        for name, t0, t1 in self.rows[since:]:
            out[name] = out.get(name, 0.0) + (t1 - t0)
        return out


class SpanVerifier:
    """The verifier as `SyncManager` and `scan_store` see it: every call
    goes to the wrapped one, and a segment's dispatch and the wait on its
    result get a span each (two clock reads a segment)."""

    def __init__(self, inner, spans: Spans):
        self._inner = inner
        self._spans = spans

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def _spanned(self, call, *args):
        with self._spans.span("dispatch"):
            resolver = call(*args)

        def resolve():
            with self._spans.span("verify_wait"):
                return resolver()
        return resolve

    def verify_packed_segment_async(self, packed, anchor_prev_sig):
        return self._spanned(self._inner.verify_packed_segment_async,
                             packed, anchor_prev_sig)

    def verify_chain_segment_async(self, beacons, anchor_prev_sig):
        return self._spanned(self._inner.verify_chain_segment_async,
                             beacons, anchor_prev_sig)


class SpanStore:
    """A store as the program sees it, with a span around each segment
    commit and each raw read, and the time each commit ended beside the
    last round it made durable (`commits`)."""

    def __init__(self, inner, spans: Spans):
        self._inner = inner
        self._spans = spans
        self.commits: list[tuple[int, float]] = []

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def put_many(self, beacons) -> None:
        beacons = beacons if isinstance(beacons, list) else list(beacons)
        with self._spans.span("commit"):
            self._inner.put_many(beacons)
        if beacons:
            self.commits.append((beacons[-1].round, time.perf_counter()))

    def raw_rows(self, start_round: int, limit: int):
        with self._spans.span("read"):
            return self._inner.raw_rows(start_round, limit)


class SpanNetwork:
    """The beacon network as `SyncManager` sees it: the time each wire
    message came off the stream beside its last round (`arrivals`), and a
    span over each wait for the next message."""

    def __init__(self, inner, spans: Spans):
        self._inner = inner
        self._spans = spans
        self.arrivals: list[tuple[int, float]] = []

    def __getattr__(self, name):
        return getattr(self._inner, name)

    async def sync_chain(self, peer, from_round: int):
        gen = self._inner.sync_chain(peer, from_round)
        try:
            stream = gen.__aiter__()
            while True:
                t0 = time.perf_counter()
                try:
                    item = await stream.__anext__()
                except StopAsyncIteration:
                    return
                t1 = time.perf_counter()
                self._spans.add("fetch", t0, t1)
                last = getattr(item, "end_round", None)
                self.arrivals.append(
                    (int(item.round if last is None else last), t1))
                yield item
        finally:
            await gen.aclose()


# -- the two-node stand (copied from tools/bench_sync.py) ---------------------

class Peer:
    tls = False

    def __init__(self, address: str):
        self.address = address


class Clock:
    def now(self):
        # the real SyncManager/DiscrepancyStore stack maps wall time onto
        # the chain's schedule
        return time.time()


class Group:
    """What the store stack and SyncManager read of a group file."""

    genesis_time = 0

    def __init__(self, scheme_id: str, period: int,
                 genesis_seed: bytes = b"genesis-seed-benchmark"):
        self.scheme_id = scheme_id
        self.period = period
        self.genesis_seed = genesis_seed    # round 0's "signature"


def group_of(config: dict) -> Group:
    """The group of a configuration; a chained one states its genesis
    seed, which is round 1's previous signature."""
    if config["chained"]:
        return Group(config["scheme_id"], config["period_s"],
                     bytes.fromhex(config["genesis_seed_hex"]))
    return Group(config["scheme_id"], config["period_s"])


def previous_sigs(config: dict, sigs: np.ndarray):
    """The chain's `previous_sig` column: for a chained configuration the
    genesis seed and then each row before, else None."""
    if not config["chained"]:
        return None
    return [bytes.fromhex(config["genesis_seed_hex"])] \
        + [s.tobytes() for s in sigs[:-1]]


def beacons_of(sigs: np.ndarray, prevs=None, rounds=None) -> list:
    """Beacons of the rows of `sigs` (and of `prevs`, where the scheme is
    chained): rounds 1.. where none are given."""
    from drand_tpu.chain.beacon import Beacon
    if rounds is None:
        rounds = range(1, len(sigs) + 1)
    if prevs is None:
        return [Beacon(round=int(r), signature=s.tobytes())
                for r, s in zip(rounds, sigs)]
    return [Beacon(round=int(r), signature=s.tobytes(), previous_sig=p)
            for r, s, p in zip(rounds, sigs, prevs)]


def fill_store(store, beacons) -> None:
    for i in range(0, len(beacons), 8192):
        store.put_many(beacons[i:i + 8192])


def new_node_store(db_path: str, group: Group):
    """A node's chain store as the daemon builds it (`new_chain_store`:
    WAL, synchronous=NORMAL, the whole decorator stack), holding the
    genesis row."""
    from drand_tpu.chain.beacon import Beacon
    from drand_tpu.chain.store import new_chain_store
    store = new_chain_store(db_path, group)
    store.put(Beacon(round=0, signature=group.genesis_seed))
    return store


async def serve(store):
    """One serving node: the real Protocol.SyncChain handler over the
    given backlog store, on an ephemeral localhost port."""
    import grpc.aio

    from drand_tpu.beacon.sync_manager import serve_sync_chain
    from drand_tpu.chain.segment import WIRE_CHUNK_DEFAULT
    from drand_tpu.core import convert
    from drand_tpu.net.rpc import service_handler

    class _SyncService:
        async def SyncChain(self, request, ctx):
            chunk = min(int(getattr(request, "chunk_size", 0)),
                        WIRE_CHUNK_DEFAULT)
            async for item in serve_sync_chain(
                    store, request.from_round, chunk_size=chunk):
                yield convert.item_to_packet(item)

    server = grpc.aio.server()
    server.add_generic_rpc_handlers(
        (service_handler("Protocol", _SyncService()),))
    port = server.add_insecure_port("127.0.0.1:0")
    await server.start()
    return server, f"127.0.0.1:{port}"


def stored_rows(store, count: int, sig_len: int):
    """(rounds[N], sigs[N, sig_len], prevs: N bytes) of a store's rounds
    1..count, the genesis row left out."""
    rows = store.read_fields(1, count + 1)
    rounds = np.array([r for r, _, _ in rows], dtype=np.uint64)
    sigs = np.frombuffer(b"".join(s for _, s, _ in rows),
                         dtype=np.uint8).reshape(len(rows), sig_len)
    return rounds, sigs, [p for _, _, p in rows]


def rows_differing(sigs, prevs, want_sigs, want_prevs) -> int:
    """How many of the rows differ from the wanted ones in either field
    (an unchained row's `previous_sig` is wanted empty)."""
    differs = (sigs != want_sigs).any(axis=1)
    if want_prevs is None:
        want_prevs = [b""] * len(prevs)
    differs |= np.array([p != w for p, w in zip(prevs, want_prevs)],
                        dtype=bool)
    return int(differs.sum())


# -- verifiers that are not the program's -------------------------------------

class StubVerifier:
    """Says yes to everything (copy of `tools/bench_sync.py`'s): the
    verifier that checks less.  It is the run that `correct` has to fail:
    with it the faulted pass commits, or passes, the planted rounds."""

    def __init__(self, scheme_id: str):
        from drand_tpu.chain.scheme import scheme_by_id
        self.scheme = scheme_by_id(scheme_id)

    def verify_chain_segment_async(self, beacons, anchor_prev_sig):
        n = len(beacons)
        return lambda: np.ones(n, dtype=bool)

    def verify_packed_segment_async(self, packed, anchor_prev_sig):
        n = len(packed)
        return lambda: np.ones(n, dtype=bool)

    def verify_beacons(self, beacons):
        return np.ones(len(beacons), dtype=bool)

    def verify_beacon(self, beacon) -> bool:
        return True


class HostVerifier:
    """For the CPU rehearsal only: real verdicts with no device program,
    each row through the program's host tier over the row's own
    `previous_sig`.  Under a chained scheme a segment's linkage is held
    as `ChainVerifier` holds it (`verify_chain_segment_async`,
    `verify_packed_segment_async`; no second semantics): a list of
    beacons links each `previous_sig` to the signature before it, the
    first to the caller's anchor; a packed segment has no such column,
    its rows are given the anchor and then each other's signatures, so a
    server's `first_prev` is never trusted."""

    def __init__(self, chain_verifier):
        self._cv = chain_verifier
        self.scheme = chain_verifier.scheme

    def verify_beacon(self, beacon) -> bool:
        return self._cv.verify_beacon(beacon)

    def verify_beacons(self, beacons):
        return np.array([self._cv.verify_beacon(b) for b in beacons],
                        dtype=bool)

    def verify_chain_segment_async(self, beacons, anchor_prev_sig):
        if self.scheme.decouple_prev_sig:
            return lambda: self.verify_beacons(beacons)
        want = [anchor_prev_sig] + [b.signature for b in beacons[:-1]]
        linked = np.array([b.previous_sig == w
                           for b, w in zip(beacons, want)], dtype=bool)
        return lambda: self.verify_beacons(beacons) & linked

    def verify_packed_segment_async(self, packed, anchor_prev_sig):
        if self.scheme.decouple_prev_sig:
            return lambda: self.verify_beacons(packed.beacons())
        sigs = [row.tobytes() for row in packed.sigs]
        return self.verify_chain_segment_async(beacons_of(
            packed.sigs, [anchor_prev_sig] + sigs[:-1], packed.rounds()),
            anchor_prev_sig)


# -- metric arithmetic --------------------------------------------------------

def percentile(values, q: float) -> float:
    """The q-th percentile (nearest rank), given only where ten samples
    or more lie beyond it; else the sample cannot carry that percentile
    and the run fails."""
    vals = sorted(values)
    n = len(vals)
    rank = int(np.ceil(q / 100.0 * n))        # 1-based nearest rank
    if n - rank < 10:
        raise BenchFailure(
            f"p{q:g} needs ten samples beyond it: {n} samples leave "
            f"{max(n - rank, 0)}")
    return float(vals[rank - 1])


def pair_chunk_commits(arrivals, commits) -> list[float]:
    """Seconds from each wire message's arrival to the commit that made
    its last round durable.  `arrivals` and `commits` are (last round,
    time) lists in stream order; a message whose last round no commit
    reached has no latency and is left out (the caller counts it)."""
    out = []
    commits = sorted(commits)
    j = 0
    for last_round, t_arrival in sorted(arrivals):
        while j < len(commits) and commits[j][0] < last_round:
            j += 1
        if j == len(commits):
            break
        out.append(commits[j][1] - t_arrival)
    return out


# -- the seeded draw of the output check ---------------------------------------

def draw_check(seed: int, backlog: int, starts: list[int], ramp: int,
               samples: int, faults: int) -> dict:
    """The rounds the output check looks at, from the seed alone:
    `samples` rounds whose verdict must be true (the first and the last
    round always among them), and `faults` planted (round, byte, bit).
    `starts` are the first rounds of the segments the traffic verifies
    in.  The first fault's kind goes round with the seed, so that a dozen
    seeds meet a round of the ramp, the first and the last round of a
    segment, and a round anywhere."""
    rng = np.random.default_rng(seed % (1 << 64))   # any whole number
    inner = np.arange(2, backlog)
    sample = set(rng.choice(inner, size=min(samples - 2, len(inner)),
                            replace=False).tolist()) | {1, backlog}
    ends = [s - 1 for s in starts[1:]] + [backlog]
    kind = ("ramp", "segment_first", "segment_last", "anywhere")[seed % 4]
    if kind == "ramp":
        first = int(rng.integers(1, min(ramp, backlog) + 1))
    elif kind == "segment_first":
        first = int(rng.choice(starts[1:] or starts))
    elif kind == "segment_last":
        first = int(rng.choice(ends))
    else:
        first = int(rng.integers(1, backlog + 1))
    rounds = {first}
    while len(rounds) < min(faults, backlog):
        rounds.add(int(rng.integers(1, backlog + 1)))
    planted = [(r, int(rng.integers(0, 1 << 30)), int(rng.integers(0, 8)))
               for r in sorted(rounds)]
    return {"kind": kind, "kind_round": first,
            "sample": sorted(sample - rounds), "faults": planted}


def fault_at(byte: int, sig_len: int, chained: bool) -> tuple[str, int]:
    """The field a fault's byte index falls into, and where in it: taken
    modulo the signature's length where the rows have no `previous_sig`;
    where they have, modulo twice that, the upper half being the
    `previous_sig`."""
    at = byte % (2 * sig_len if chained else sig_len)
    return ("previous_sig", at - sig_len) if at >= sig_len \
        else ("signature", at)


def plant(sigs: np.ndarray, faults, prevs=None):
    """(sigs, prevs): a copy of the chain with one bit of each faulted
    round flipped, in the field `fault_at` names (round 1's
    `previous_sig` is the shorter genesis seed: modulo its length).  A
    row's damage is its own: the row after a flipped signature keeps the
    true one as its `previous_sig`."""
    bad = sigs.copy()
    bad_prevs = None if prevs is None else list(prevs)
    for round_, byte, bit in faults:
        field, at = fault_at(byte, sigs.shape[1], prevs is not None)
        if field == "signature":
            bad[round_ - 1, at] ^= np.uint8(1 << bit)
        else:
            prev = bytearray(bad_prevs[round_ - 1])
            prev[at % len(prev)] ^= 1 << bit
            bad_prevs[round_ - 1] = bytes(prev)
    return bad, bad_prevs


def damaged_fields(faults, sig_len: int, chained: bool) -> dict:
    """{round: the fields `plant` damages there}, from the faults alone."""
    out: dict[int, set] = {}
    for round_, byte, _bit in faults:
        out.setdefault(round_, set()).add(
            fault_at(byte, sig_len, chained)[0])
    return out


# -- the plain reference ------------------------------------------------------

def reference_verdicts(config: dict, rounds, sigs: np.ndarray,
                       prevs=None) -> np.ndarray:
    """Verdicts of the benchmark's own copy of the golden model
    (`benchmark/reference`: pure Python, imports nothing of the program)
    on rows under the configuration's public key: the message is
    sha256(uint64_be(round)), and under a chained scheme
    sha256(previous_sig || uint64_be(round)) of the row's own
    `previous_sig`."""
    from benchmark.reference import sign as S
    from benchmark.reference.bls12381 import curve as C
    pk_bytes = bytes.fromhex(config["public_key_hex"])
    on_g1 = config["signature_group"] == "G1"
    pk = C.g2_from_bytes(pk_bytes) if on_g1 else C.g1_from_bytes(pk_bytes)
    check = S.bls_verify_g1 if on_g1 else S.bls_verify
    if not config["chained"]:
        prevs = [b""] * len(sigs)
    out = []
    for r, sig, prev in zip(rounds, sigs, prevs):
        msg = hashlib.sha256(prev + struct.pack(">Q", int(r))).digest()
        out.append(bool(check(pk, msg, bytes(sig))))
    return np.array(out, dtype=bool)


def reference_findings(config: dict, sigs: np.ndarray, prevs,
                       damaged) -> tuple[set, set]:
    """Two independent facts of the rows of a (damaged) store holding
    rounds 1..N, as sets of rounds: *invalid*, the signature is false
    over the row's own `previous_sig` and round; *unlinked*, the stored
    `previous_sig` is not the stored signature of the row before (the
    genesis seed for round 1; never, under an unchained scheme).  Linkage
    is read off every row.  A pairing in pure Python takes a sixth of a
    second, so validity is judged on the `damaged` rounds alone: every
    other row is the fixture's, true when it was made."""
    damaged = sorted(damaged)
    at = np.array(damaged, dtype=np.int64) - 1
    ok = reference_verdicts(
        config, damaged, sigs[at],
        None if prevs is None else [prevs[i] for i in at])
    invalid = {r for r, good in zip(damaged, ok) if not good}
    unlinked = set()
    if prevs is not None:
        want = previous_sigs(config, sigs)
        unlinked = {i + 1 for i, (p, w) in enumerate(zip(prevs, want))
                    if p != w}
    return invalid, unlinked


def peaks_for(device_kind: str) -> dict:
    """The device's published peaks; an unknown kind is an error."""
    table = load_json("peaks.json")["device_kinds"]
    if device_kind not in table:
        raise BenchFailure(f"device kind {device_kind!r} is not in "
                           "benchmark/peaks.json")
    return table[device_kind]

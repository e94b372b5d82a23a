"""What the benchmark's drivers share: the harness's own spans and the
thin wrappers that record them, the two-node stand (a copy of
`tools/bench_sync.py`'s `_fill_store`, `_serve`, `_Group`, `_Peer`,
`_Clock` and `_StubVerifier`, which PR 22 ran on the chip: later PRs may
change `tools/`, not the yardstick), the percentile rule, the pairing of
chunk arrivals with commits, the seeded draw of sampled and faulted
rounds, and the plain reference's verdicts.

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

    def __init__(self, scheme_id: str, period: int):
        self.scheme_id = scheme_id
        self.period = period


def beacons_of(sigs: np.ndarray, rounds=None) -> list:
    """Beacons of the rows of `sigs`: rounds 1.. where none are given."""
    from drand_tpu.chain.beacon import Beacon
    if rounds is None:
        rounds = range(1, len(sigs) + 1)
    return [Beacon(round=int(r), signature=s.tobytes())
            for r, s in zip(rounds, sigs)]


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
    store.put(Beacon(round=0, signature=b"genesis-seed-benchmark"))
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


def stored_sigs(store, count: int, sig_len: int):
    """(rounds[N], sigs[N, sig_len]) of a store's rounds 1..count, the
    genesis row left out."""
    rows = store.read_fields(1, count + 1)
    rounds = np.array([r for r, _, _ in rows], dtype=np.uint64)
    sigs = np.frombuffer(b"".join(s for _, s, _ in rows),
                         dtype=np.uint8).reshape(len(rows), sig_len)
    return rounds, sigs


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
    each row through the program's host tier.  Unchained schemes only
    (no linkage is checked)."""

    def __init__(self, chain_verifier):
        self._cv = chain_verifier
        self.scheme = chain_verifier.scheme
        if not self.scheme.decouple_prev_sig:
            raise BenchFailure("the rehearsal's host verifier knows "
                               "unchained schemes only")

    def verify_beacon(self, beacon) -> bool:
        return self._cv.verify_beacon(beacon)

    def verify_beacons(self, beacons):
        return np.array([self._cv.verify_beacon(b) for b in beacons],
                        dtype=bool)

    def verify_chain_segment_async(self, beacons, anchor_prev_sig):
        return lambda: self.verify_beacons(beacons)

    def verify_packed_segment_async(self, packed, anchor_prev_sig):
        return lambda: self.verify_beacons(packed.beacons())


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


def plant(sigs: np.ndarray, faults) -> np.ndarray:
    """A copy of the chain with one bit of each faulted round's signature
    flipped (the byte index is taken modulo the signature's length)."""
    bad = sigs.copy()
    for round_, byte, bit in faults:
        bad[round_ - 1, byte % sigs.shape[1]] ^= np.uint8(1 << bit)
    return bad


# -- the plain reference ------------------------------------------------------

def reference_verdicts(config: dict, rounds, sigs: np.ndarray) -> np.ndarray:
    """Verdicts of the benchmark's own copy of the golden model
    (`benchmark/reference`: pure Python, imports nothing of the program)
    on (round, signature) pairs of an unchained scheme under the
    configuration's public key."""
    from benchmark.reference import sign as S
    from benchmark.reference.bls12381 import curve as C
    if config["chained"]:
        raise BenchFailure("the reference knows unchained schemes only")
    pk_bytes = bytes.fromhex(config["public_key_hex"])
    on_g1 = config["signature_group"] == "G1"
    pk = C.g2_from_bytes(pk_bytes) if on_g1 else C.g1_from_bytes(pk_bytes)
    check = S.bls_verify_g1 if on_g1 else S.bls_verify
    out = []
    for r, sig in zip(rounds, sigs):
        msg = hashlib.sha256(struct.pack(">Q", int(r))).digest()
        out.append(bool(check(pk, msg, bytes(sig))))
    return np.array(out, dtype=bool)


def peaks_for(device_kind: str) -> dict:
    """The device's published peaks; an unknown kind is an error."""
    table = load_json("peaks.json")["device_kinds"]
    if device_kind not in table:
        raise BenchFailure(f"device kind {device_kind!r} is not in "
                           "benchmark/peaks.json")
    return table[device_kind]

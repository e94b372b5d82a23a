"""Makes the benchmark's fixture chains (run once, by hand; the outputs
are committed beside this script).

    python benchmark/fixtures/make_fixtures.py

`unchained-g2_65536.npy`: rounds 1..65,536 of `pedersen-bls-unchained`
under the key of seed b"drand-tpu-bench": the repo's committed
`aot/fixtures/bench_sigs_unchained_16384_*` and its committed extension
`bench_sync_sigs_65536_*`, concatenated (a copy: later PRs may change
`aot/`, not the yardstick).

`quicknet-g1_65536.npy`: rounds 1..65,536 of `bls-unchained-g1-rfc9380`
under the key of seed b"drand-tpu-bench-g1sig": the committed
`bench_sigs_unchained_g1_16384_*`, extended by rounds 16,385..65,536
signed here with the benchmark's copy of the golden model
(`benchmark/reference`, about 7 ms a signature, spread over the cores;
the native tier has no G1 scalar multiplication).  Pinned: the first
extension signature equals the program's own golden model's, and the
program's native tier accepts the first, the last and a sample of the
extension.

Prints each file's sha256, which the configuration's file records.

    python benchmark/fixtures/make_fixtures.py --chained 1024

signs a third chain and nothing else: `default-chained_<N>.npy`, rounds
1..N of `pedersen-bls-chained` under the key of seed
b"drand-tpu-bench-chained".  A round's message is
sha256(previous_sig || uint64_be(round)), round 1's previous signature is
the genesis seed sha256(b"drand-tpu-bench-chained-genesis"), so the
rounds are signed one after another, by the program's native tier (its
hash to G2 and one scalar multiplication).  Pinned: the first two
signatures equal those of the benchmark's copy of the golden model,
which also verifies a sample under the printed key.  Prints what the
signing took, the sha256, the public key and the genesis seed.
"""

from __future__ import annotations

import argparse
import concurrent.futures as cf
import hashlib
import multiprocessing as mp
import os
import struct
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BACKLOG = 65536
G1_SEED = b"drand-tpu-bench-g1sig"
CHAINED_SEED = b"drand-tpu-bench-chained"


def _digest(round_: int) -> bytes:
    return hashlib.sha256(struct.pack(">Q", round_)).digest()


def _sign_g1(args) -> np.ndarray:
    sk, rounds = args
    sys.path.insert(0, ROOT)
    from benchmark.reference import sign as S
    return np.stack([np.frombuffer(S.bls_sign_g1(sk, _digest(r)),
                                   dtype=np.uint8) for r in rounds])


def _sha256(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def main() -> None:
    sys.path.insert(0, ROOT)
    src = os.path.join(ROOT, "aot", "fixtures")
    g2 = np.concatenate([
        np.load(os.path.join(
            src, "bench_sigs_unchained_16384_d6a762f2_ff8a2cc7.npy")),
        np.load(os.path.join(
            src, "bench_sync_sigs_65536_d6a762f2_ff8a2cc7.npy"))])
    assert g2.shape == (BACKLOG, 96) and g2.dtype == np.uint8, g2.shape
    out_g2 = os.path.join(HERE, "unchained-g2_65536.npy")
    np.save(out_g2, g2)
    print("unchained-g2_65536.npy", _sha256(out_g2))

    from benchmark.reference import sign as S
    g1_head = np.load(os.path.join(
        src, "bench_sigs_unchained_g1_16384_5b84a3cd_d7754ef6.npy"))
    sk, pk = S.keygen_g2(G1_SEED)
    base = len(g1_head)
    rounds = list(range(base + 1, BACKLOG + 1))
    workers = os.cpu_count() or 4
    parts = [rounds[i::workers] for i in range(workers)]
    with cf.ProcessPoolExecutor(
            max_workers=workers, mp_context=mp.get_context("spawn")) as pool:
        signed = list(pool.map(_sign_g1, [(sk, p) for p in parts]))
    ext = np.zeros((len(rounds), 48), dtype=np.uint8)
    for i, part in enumerate(signed):
        ext[i::workers] = part
    g1 = np.concatenate([g1_head, ext])
    assert g1.shape == (BACKLOG, 48), g1.shape

    # pins: the program's golden model signs the same bytes, and its
    # native tier (an independent C++ implementation) accepts them
    from drand_tpu import native
    from drand_tpu.crypto import sign as PS
    from drand_tpu.crypto.bls12381 import curve as GC
    from drand_tpu.crypto.bls12381.constants import DST_G1
    assert bytes(ext[0]) == PS.bls_sign_g1(sk, _digest(base + 1)), \
        "the extension's first signature differs from the golden model's"
    assert bytes(g1[0]) == PS.bls_sign_g1(sk, _digest(1)), \
        "the committed head was not signed under this key"
    pk96 = GC.g2_to_bytes(pk)
    if native.available():
        for r in [base + 1, BACKLOG] + list(range(base + 7, BACKLOG, 4099)):
            assert native.verify_g1(pk96, _digest(r), bytes(g1[r - 1]),
                                    DST_G1), f"native rejects round {r}"
    out_g1 = os.path.join(HERE, "quicknet-g1_65536.npy")
    np.save(out_g1, g1)
    print("quicknet-g1_65536.npy", _sha256(out_g1))
    print("public key (G2, 96 B):", pk96.hex())


def chained(count: int) -> None:
    """`default-chained_<count>.npy`, signed serially."""
    sys.path.insert(0, ROOT)
    from benchmark.reference import sign as S
    from benchmark.reference.bls12381 import curve as C
    from benchmark.reference.bls12381.constants import DST_G2
    from drand_tpu import native
    assert native.available(), "the native tier signs the chained chain"
    sk, pk = S.keygen(CHAINED_SEED)
    sk32 = sk.to_bytes(32, "big")
    genesis = hashlib.sha256(CHAINED_SEED + b"-genesis").digest()

    def message(prev: bytes, round_: int) -> bytes:
        return hashlib.sha256(prev + struct.pack(">Q", round_)).digest()

    sigs = np.zeros((count, 96), dtype=np.uint8)
    prev = genesis
    t0 = time.perf_counter()
    for r in range(1, count + 1):
        h = native.hash_to_g2(message(prev, r), DST_G2)
        prev = native.g2_lincomb([h], [sk32])
        sigs[r - 1] = np.frombuffer(prev, dtype=np.uint8)
    took = time.perf_counter() - t0
    assert bytes(sigs[0]) == S.bls_sign(sk, message(genesis, 1)), \
        "round 1 differs from the golden model's signature"
    assert bytes(sigs[1]) == S.bls_sign(sk, message(bytes(sigs[0]), 2)), \
        "round 2 differs from the golden model's signature"
    for r in sorted({1, 2, count} | set(range(3, count, max(count // 8, 1)))):
        before = genesis if r == 1 else bytes(sigs[r - 2])
        assert S.bls_verify(pk, message(before, r), bytes(sigs[r - 1])), \
            f"the reference rejects round {r}"
    out = os.path.join(HERE, f"default-chained_{count}.npy")
    np.save(out, sigs)
    print(f"signed {count} rounds serially in {took:.1f} s "
          f"({1e3 * took / count:.2f} ms a round)")
    print(os.path.basename(out), _sha256(out))
    print("public key (G1, 48 B):", C.g1_to_bytes(pk).hex())
    print("genesis seed:", genesis.hex())


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chained", type=int, default=0, metavar="ROUNDS",
                    help="sign only the chained chain, of this many rounds")
    args = ap.parse_args()
    if args.chained:
        chained(args.chained)
    else:
        main()

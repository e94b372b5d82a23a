"""Makes the benchmark's two fixture chains (run once, by hand; the
outputs are committed beside this script).

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
"""

from __future__ import annotations

import concurrent.futures as cf
import hashlib
import multiprocessing as mp
import os
import struct
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BACKLOG = 65536
G1_SEED = b"drand-tpu-bench-g1sig"


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


if __name__ == "__main__":
    main()

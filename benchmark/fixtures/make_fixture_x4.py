"""Makes the four-chip cell's fixture chain (run once, by hand; the output
is committed beside this script).

    python benchmark/fixtures/make_fixture_x4.py

`quicknet-g1_262144.npy`: rounds 1..262,144 of `bls-unchained-g1-rfc9380`
under the key of seed b"drand-tpu-bench-g1sig": the committed
`quicknet-g1_65536.npy`, byte for byte, extended by rounds
65,537..262,144 signed here as `make_fixtures.py` signed its own
extension (the benchmark's copy of the golden model, `benchmark/reference`,
spread over the cores), with its pins: the first extension signature
equals the program's own golden model's, and the program's native tier
accepts the first, the last and a sample of the extension.

Prints what the signing took and the file's sha256, which the
configuration's file records.
"""

from __future__ import annotations

import concurrent.futures as cf
import multiprocessing as mp
import os
import sys
import time

import numpy as np

import make_fixtures as M    # beside this script: sys.path[0] when run

BACKLOG = 4 * M.BACKLOG


def main() -> None:
    sys.path.insert(0, M.ROOT)
    from benchmark.reference import sign as S
    head = np.load(os.path.join(M.HERE, "quicknet-g1_65536.npy"))
    assert head.shape == (M.BACKLOG, 48) and head.dtype == np.uint8
    sk, pk = S.keygen_g2(M.G1_SEED)
    rounds = list(range(M.BACKLOG + 1, BACKLOG + 1))
    workers = os.cpu_count() or 4
    parts = [rounds[i::workers] for i in range(workers)]
    t0 = time.perf_counter()
    with cf.ProcessPoolExecutor(
            max_workers=workers, mp_context=mp.get_context("spawn")) as pool:
        signed = list(pool.map(M._sign_g1, [(sk, p) for p in parts]))
    took = time.perf_counter() - t0
    ext = np.zeros((len(rounds), 48), dtype=np.uint8)
    for i, part in enumerate(signed):
        ext[i::workers] = part
    g1 = np.concatenate([head, ext])
    assert g1.shape == (BACKLOG, 48), g1.shape

    from drand_tpu import native
    from drand_tpu.crypto import sign as PS
    from drand_tpu.crypto.bls12381 import curve as GC
    from drand_tpu.crypto.bls12381.constants import DST_G1
    assert bytes(ext[0]) == PS.bls_sign_g1(sk, M._digest(M.BACKLOG + 1)), \
        "the extension's first signature differs from the golden model's"
    pk96 = GC.g2_to_bytes(pk)
    if native.available():
        for r in [M.BACKLOG + 1, BACKLOG] \
                + list(range(M.BACKLOG + 7, BACKLOG, 4099)):
            assert native.verify_g1(pk96, M._digest(r), bytes(g1[r - 1]),
                                    DST_G1), f"native rejects round {r}"
    out = os.path.join(M.HERE, f"quicknet-g1_{BACKLOG}.npy")
    np.save(out, g1)
    print(f"signed {len(rounds)} rounds on {workers} cores in {took:.1f} s "
          f"({1e3 * took * workers / len(rounds):.2f} ms a signature)")
    print(os.path.basename(out), M._sha256(out))
    print("public key (G2, 96 B):", pk96.hex())


if __name__ == "__main__":
    main()

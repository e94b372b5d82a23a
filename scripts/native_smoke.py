"""check.sh stage: native single-verify latency harness + parity smoke.

ISSUE 12 closes the host-latency axis: the native tier's hot arithmetic
was rebuilt (unrolled CIOS Montgomery mul, dedicated squaring, lazy
tower reduction, inversion-free Jacobian Miller loop) for a >=3x
single-verify win.  This harness measures it on a live build and holds
the line:

  1. parity — native verdicts equal the golden model on valid AND
     corrupted beacons for every scheme shape, across repeated calls
     (the cached/warm path must be bit-identical to the cold path);
  2. latency — cold (first call per key: decompress + prepare) vs warm
     (cached), p50/p99 over N reps per scheme, printed for the ledger
     and written to BENCH_native.json in the BENCH_serve convention,
     alongside the build flags that produced the library
     (native.build_info());
  3. the targets — warm G2-scheme single verify <= 5 ms and warm
     short-sig (G1) verify <= 3 ms on this container.  A miss is a
     FAILURE exit, not a note.

Exit 0 on success; exits 0 with a SKIP note when no C++ toolchain built
the library (the golden fallback path is covered by tier-1).

Usage:  python scripts/native_smoke.py [--reps N] [--json PATH]
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

WARM_TARGET_MS = {"g2": 5.0, "g1": 3.0}
DEFAULT_REPS = 50


def _pct(sorted_vals: list[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    i = min(int(q * (len(sorted_vals) - 1) + 0.5), len(sorted_vals) - 1)
    return sorted_vals[i]


def _tails_ms(vals: list[float]) -> dict:
    s = sorted(vals)
    return {"p50": round(_pct(s, 0.50) * 1e3, 3),
            "p99": round(_pct(s, 0.99) * 1e3, 3),
            "max": round((s[-1] if s else 0.0) * 1e3, 3),
            "n": len(s)}


def _bench(verify, cases) -> tuple[float, dict]:
    """One cold sample (first call on a fresh key) + warm tails over the
    rest.  `cases` is [(msg, sig), ...]; every call must verify."""
    (m0, s0), rest = cases[0], cases[1:]
    t0 = time.perf_counter()
    assert verify(m0, s0), "cold verify failed"
    cold = time.perf_counter() - t0
    warm = []
    for m, s in rest:
        t0 = time.perf_counter()
        assert verify(m, s), "warm verify failed"
        warm.append(time.perf_counter() - t0)
    return cold, _tails_ms(warm)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=DEFAULT_REPS,
                    help="warm verifications per scheme")
    ap.add_argument("--json", dest="json_out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_native.json"))
    args = ap.parse_args()

    try:
        from drand_tpu import native
        if not native.available():
            print("native_smoke: SKIP (native tier unavailable)")
            return 0
    except Exception as e:  # pragma: no cover - environment-specific
        print(f"native_smoke: SKIP (import failed: {e})")
        return 0

    from drand_tpu.crypto import sign as S
    from drand_tpu.crypto import tbls
    from drand_tpu.crypto.bls12381 import curve as GC
    from drand_tpu.crypto.poly import PriPoly
    from drand_tpu.verify import SHAPE_CHAINED, SHAPE_UNCHAINED_G1

    sk = 0x1DEA * 7919 + 3
    n = max(args.reps + 1, 4)       # +1: first call is the cold sample
    msgs = [hashlib.sha256(b"native-smoke-%d" % i).digest()
            for i in range(n)]

    # --- G2-sig scheme (pedersen-bls: pk on G1, cached decompression) ---
    pk = GC.g1_mul(GC.G1_GEN, sk)
    pk48 = GC.g1_to_bytes(pk)
    dst = SHAPE_CHAINED.dst
    sigs = [S.bls_sign(sk, m) for m in msgs]
    cold_g2, warm_g2 = _bench(
        lambda m, s: native.verify_g2(pk48, m, s, dst), list(zip(msgs, sigs)))
    bad = sigs[0][:5] + bytes([sigs[0][5] ^ 0xFF]) + sigs[0][6:]
    assert not native.verify_g2(pk48, msgs[0], bad, dst), \
        "g2 negative control failed"
    assert native.verify_g2(pk48, msgs[0], sigs[0], dst), \
        "g2 re-verify after negative failed (cache corruption?)"

    # --- G1 short-sig scheme (pk on G2, cached line precomputation) ---
    pk2 = GC.g2_mul(GC.G2_GEN, sk)
    pk96 = GC.g2_to_bytes(pk2)
    dst1 = SHAPE_UNCHAINED_G1.dst
    sigs1 = [S.bls_sign_g1(sk, m) for m in msgs]
    cold_g1, warm_g1 = _bench(
        lambda m, s: native.verify_g1(pk96, m, s, dst1),
        list(zip(msgs, sigs1)))
    bad1 = sigs1[0][:5] + bytes([sigs1[0][5] ^ 0xFF]) + sigs1[0][6:]
    assert not native.verify_g1(pk96, msgs[0], bad1, dst1), \
        "g1 negative control failed"

    # --- threshold partial (the beacon node's per-partial check) -------
    poly = PriPoly.random(3, secret=sk)
    pub = poly.commit()
    commits48 = [GC.g1_to_bytes(c) for c in pub.commits]
    share = poly.shares(5)[0]
    parts = [tbls.sign_partial(share, m) for m in msgs]
    cold_pt, warm_pt = _bench(
        lambda m, p: native.verify_partial(commits48, m, p, dst),
        list(zip(msgs, parts)))
    bad_pt = parts[0][:10] + bytes([parts[0][10] ^ 0xFF]) + parts[0][11:]
    assert not native.verify_partial(commits48, msgs[0], bad_pt, dst), \
        "partial negative control failed"

    # golden cross-check on one verdict per scheme (full parity lives in
    # tests/test_native.py; this pins the PREPARED path end to end)
    assert S.bls_verify(pk, msgs[3], sigs[3])
    assert S.bls_verify_g1(pk2, msgs[3], sigs1[3])
    assert tbls.verify_partial(pub, msgs[3], parts[3])

    info = native.build_info() or {}
    per_scheme = {
        "g2": {"cold_ms": round(cold_g2 * 1e3, 3), "warm_ms": warm_g2},
        "g1": {"cold_ms": round(cold_g1 * 1e3, 3), "warm_ms": warm_g1},
        "partial": {"cold_ms": round(cold_pt * 1e3, 3), "warm_ms": warm_pt},
    }
    misses = [f"{sch} warm p50 {per_scheme[sch]['warm_ms']['p50']:.2f}ms "
              f"> target {tgt:.1f}ms"
              for sch, tgt in WARM_TARGET_MS.items()
              if per_scheme[sch]["warm_ms"]["p50"] > tgt]

    report = {
        # BENCH_*.json-shaped headline (bench.py parsed form)
        "metric": "native single-verify warm p50 latency (G2 scheme)",
        "value": per_scheme["g2"]["warm_ms"]["p50"],
        "unit": "ms",
        "config": f"flags={' '.join(info.get('flags') or ['?'])} "
                  f"reps={args.reps}",
        "build": {k: info.get(k)
                  for k in ("flags", "hash", "cached", "override")},
        "reps": args.reps,
        "per_scheme": per_scheme,
        "targets_warm_p50_ms": WARM_TARGET_MS,
        "pass": not misses,
    }
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")

    print(f"native_smoke: {'OK' if not misses else 'FAIL'}  "
          f"g2 cold={cold_g2 * 1e3:.2f}ms "
          f"warm p50={warm_g2['p50']:.2f}ms p99={warm_g2['p99']:.2f}ms  "
          f"g1 cold={cold_g1 * 1e3:.2f}ms "
          f"warm p50={warm_g1['p50']:.2f}ms p99={warm_g1['p99']:.2f}ms  "
          f"partial warm p50={warm_pt['p50']:.2f}ms  "
          f"[{' '.join(info.get('flags') or ['prebuilt'])}]")
    for miss in misses:
        print(f"native_smoke: TARGET MISS: {miss}")
    return 1 if misses else 0


if __name__ == "__main__":
    sys.exit(main())

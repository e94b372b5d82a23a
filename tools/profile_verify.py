"""Capture a JAX device trace of the batched verify (perf work harness).

    python tools/profile_verify.py [batch] [out_dir]

Uses the persistent compile cache (`drand_tpu.aot.persistent_cache_dir`).
Inspect with TensorBoard or xprof.
"""

import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

BATCH = int(sys.argv[1]) if len(sys.argv) > 1 else 16384
OUT = sys.argv[2] if len(sys.argv) > 2 else f"/tmp/drand_tpu_trace_{BATCH}"

os.environ["BENCH_BATCH"] = str(BATCH)

from drand_tpu import aot  # noqa: E402

aot.enable_persistent_cache()

import numpy as np  # noqa: E402

from drand_tpu import profiling  # noqa: E402
from drand_tpu.verify import SHAPE_UNCHAINED, Verifier  # noqa: E402

# bench.py owns the fixture cache discipline (repo aot/fixtures first,
# pk+suite keyed); reuse it so profiling always measures the bench shape
import bench  # noqa: E402

sk, pk, _shape, sigs = bench._chain_fixture("unchained", BATCH)
rounds = np.arange(1, BATCH + 1, dtype=np.uint64)

v = Verifier(pk, SHAPE_UNCHAINED)
t0 = time.perf_counter()
ok = v.verify_batch(rounds, sigs)
print(f"warmup (compile+run): {time.perf_counter()-t0:.1f}s ok={int(ok.sum())}/{BATCH}")

t0 = time.perf_counter()
v.verify_batch(rounds, sigs)
steady = time.perf_counter() - t0
print(f"steady: {steady:.2f}s = {BATCH/steady:.0f} verifies/sec")

with profiling.trace(OUT):
    with profiling.annotate("verify_batch"):
        v.verify_batch(rounds, sigs)
print(f"trace written to {OUT}")

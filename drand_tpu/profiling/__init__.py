"""Tracing / profiling hooks (SURVEY §5.1).

The reference mounts net/http/pprof on its metrics mux
(`metrics/pprof/pprof.go:12-23`, wired at `core/drand_daemon.go:271`).
The TPU-native equivalent is the JAX profiler: XLA device traces (op
timelines, HBM usage, fusion boundaries) captured on demand, plus the
same "debug handler on the metrics port" pattern (drand_tpu.metrics
mounts `/debug/jax-profile`).

Beyond the capture hooks this package carries the always-on performance
observability layer:

  - `dispatch`: the dispatch flight recorder — a bounded ring of
    per-dispatch records around every batched seam (verify buckets,
    partial coalescing, sharded fan-out, native single-verify), feeding
    `drand_dispatch_*` metrics and the `/debug/dispatch` route.
  - `journey`: per-round hop timelines collated from the tracing spans
    (tick → broadcast → partials → aggregate → commit → serve), feeding
    `drand_round_journey_seconds{hop}` and `/debug/journey`.

Usage:
  - programmatic: `with profiling.trace("/tmp/trace"): run_kernels()`
  - one-shot:     `profiling.capture("/tmp/trace", seconds=2.0)`
  - daemon:       GET /debug/jax-profile?seconds=2  on the metrics port
  - perf work:    `python benchmark/run.py --workload <cell> --trace 1`
                  captures one whole operation of a benchmark cell and
                  reduces it (device busy and idle, time per stage).

A capture starts and ends with `tracing.clock_mark()`: one annotation
that carries the reading of `time.perf_counter_ns()`, the clock of every
span's `start_mono`, so `/debug/spans` can be laid beside the device's
operations.  The verify program's operations carry their stage
(`drand_tpu.ops.STAGES`) and their Pallas kernel's name in `op_name`.

Traces are TensorBoard-compatible (`xplane.pb` under the out dir).
"""

from __future__ import annotations

import contextlib
import os
import time

from drand_tpu.profiling import dispatch, journey  # noqa: F401
from drand_tpu.profiling.dispatch import DISPATCH, record_dispatch  # noqa: F401
from drand_tpu.profiling.journey import JOURNEY  # noqa: F401


@contextlib.contextmanager
def trace(out_dir: str):
    """Capture a JAX profiler trace around a block."""
    import jax

    from drand_tpu import tracing
    os.makedirs(out_dir, exist_ok=True)
    jax.profiler.start_trace(out_dir)
    try:
        tracing.clock_mark()
        yield out_dir
    finally:
        tracing.clock_mark()
        jax.profiler.stop_trace()


def capture(out_dir: str, seconds: float = 2.0) -> str:
    """Record whatever device activity happens in the next `seconds`."""
    with trace(out_dir):
        time.sleep(seconds)
    return out_dir


def manifest(out_dir: str) -> dict:
    """Describe a captured trace directory: the files the profiler wrote
    (relative paths + sizes), for the `/debug/jax-profile` response."""
    files = []
    total = 0
    for root, _dirs, names in os.walk(out_dir):
        for name in sorted(names):
            path = os.path.join(root, name)
            try:
                size = os.path.getsize(path)
            except OSError:
                size = 0
            total += size
            files.append({"path": os.path.relpath(path, out_dir),
                          "bytes": size})
    return {"trace_dir": out_dir, "files": files,
            "num_files": len(files), "total_bytes": total}

#!/bin/sh
# Round-8 warm/measure chain — run on a TPU-attached host.
#
# The round-7 shell chain (warm_r7.sh) is now the `warm_r8` pipeline
# spec (drand_tpu/warm/specs.py): same stages, same protocol —
#   catchup (strict reps-3), catchup10, chained b16384, partials
#   new-path -> BENCH_partials.json, partials-old-shape, dryrun
#   parity, g1, single, multichain
# — but orchestrated: environment preflight (doctor) before anything
# runs, per-stage timeouts and auto-retry on transient failures
# (dropped connections, environment resets), checkpointed state in
# warm_logs/state.json, heartbeat progress lines, and per-stage
# spans/metrics.
#
# If this chain dies for ANY reason, continue it with:
#     drand-tpu warm resume warm_r8
# (completed stages are skipped; a kernel edit re-dirties downstream
# stages automatically).  Inspect progress with:
#     drand-tpu warm status warm_r8
cd "$(dirname "$0")/.."
exec python -m drand_tpu.cli warm run warm_r8 "$@"

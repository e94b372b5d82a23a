#!/usr/bin/env bash
# CI-style check run (the reference's `make test-unit` with -race +
# golangci-lint, SURVEY §5.2).  Python's closest analogs:
#   - compileall: syntax/import sanity over the whole tree
#   - PYTHONASYNCIODEBUG=1: asyncio's built-in race/misuse detector
#     (un-awaited coroutines, slow callbacks blocking the loop, cross-loop
#     primitive use) promoted to errors via -W
#   - the default test suite, which runs the multi-node protocol tests
#     under fake clocks
# Usage: scripts/check.sh [extra pytest args]
set -euo pipefail
cd "$(dirname "$0")/.."

python -m compileall -q drand_tpu tests demo tools

# project linter (tools/lint): the golangci-lint stage — the local
# rules (async-blocking, wall-clock, jit-tracing, unawaited-coroutine,
# secret-logging, bare-except, span-balance, log-hierarchy,
# admission-guard) PLUS the whole-program analyzers on the two-pass
# engine: await-race (stale-read-across-await / guard-act races, the
# static half of go's -race) and domain-flow (canonical-vs-Montgomery /
# tile-vs-row-major / tower-level mismatches in drand_tpu/ops).  Fails
# on any non-baselined finding, on a suppression comment that no longer
# suppresses anything, and on a stale baseline entry — the debt surface
# only shrinks.  Warm runs reuse the .lint_cache/ index sidecar.
python -m tools.lint

# analyzer self-test: the fixture corpora that PROVE the analyzers
# still catch the shapes they exist for (the PR 3 partial-cache race,
# a canonical operand into mont_mul, an uncounted tile-seam crossing)
# plus the runtime sanitizer's probe tests — a silently lobotomized
# analyzer dies here, not in review
JAX_PLATFORMS=cpu python -m pytest tests/test_lint.py tests/test_sanitizer.py \
    -q -p no:cacheprovider

PYTHONASYNCIODEBUG=1 python -W "error::RuntimeWarning" -m pytest tests/ -q "$@"

# chaos smoke (drand_tpu/chaos): one seeded 3-node scenario — partition,
# heal, gap-sync — through the failpoint layer with every protocol
# invariant asserted.  Deterministic (fake clock, seeded schedule) and
# <30 s with the XLA cache the suite above just warmed.  --sanitize arms
# the runtime asyncio sanitizer (drand_tpu/sanitizer.py): a callback
# blocking the loop or an unlocked/cross-task mutation of an
# instrumented object fails the stage with the captured stack — the
# dynamic half of go's -race leg over a real fault schedule.
JAX_PLATFORMS=cpu python -m drand_tpu.cli chaos run partition-heal --seed 7 \
    --sanitize

# health smoke (drand_tpu/health): one node serving /health, verdict
# flipped 200 -> 503 by a seeded missed-ticks failpoint (dead ticker),
# healed back to 200 at catchup cadence.
JAX_PLATFORMS=cpu python scripts/health_smoke.py

# resilience smoke (drand_tpu/resilience): a partitioned peer trips the
# per-peer circuit breakers OPEN (asserted over the metrics port's
# drand_breaker_state gauge), the partition heals, half-open probes
# close them again, and the victim gap-syncs back — with every protocol
# invariant asserted and the retry/breaker decision log recorded.
# Exit-coded like the chaos stage above.
JAX_PLATFORMS=cpu python -m drand_tpu.cli chaos run breaker-trip-heal --seed 11

# serve smoke (drand_tpu/resilience/admission + tools/bench_serve): a
# live node behind tiny admission limits takes a client burst — ≥1
# deliberate shed (503 + Retry-After) with /health green throughout
# (probe lane never queues behind public), p99 bounded, then an
# in-bounds load recovers to zero shed.
JAX_PLATFORMS=cpu python scripts/serve_smoke.py

# partials smoke (beacon/signer_table + crypto_backend, ISSUE 7): the
# rebuilt aggregation path at small shape — signer-key table eval parity
# at every index + unknown-index fallback, mixed-batch verdict parity
# against raw tbls, reshare epoch invalidation, message dedup, and
# recovery agreement.  On a TPU host it additionally runs the tabled
# device kernel at bucket 4 and asserts verdicts match the legacy path.
JAX_PLATFORMS=cpu python scripts/partials_smoke.py

# mesh smoke: seeded kill/restart/one-way-partition churn over a
# 24-node gossip relay mesh with the monotonic/no-fork/liveness/
# mesh-degree invariant sweep (drand_tpu/chaos/mesh.py; 100 nodes
# rides in `pytest -m slow`).
JAX_PLATFORMS=cpu python -m drand_tpu.cli chaos run mesh-churn --seed 7

# merged-kernel sim-KAT parity (ISSUE 9): the merged Miller-iteration
# kernels (dbl + add, with and without the sparse line merge) and the
# standalone line-merge product, bit-identical to the trio path through
# the eager Pallas simulator.  Fast-marked subset runs in tier-1; this
# stage runs the FULL parity set (slow-marked included) so a kernel
# edit cannot land without the bit-exactness proof.
JAX_PLATFORMS=cpu python -m pytest tests/test_sim_kats.py -q --runslow \
    -p no:cacheprovider

# sync smoke (ISSUE 13): two nodes over real gRPC — chunked and
# per-beacon wire passes with REAL BLS verification over the committed
# fixture chain must commit bit-identical stores, a server-side
# corrupted signature must stop the sync at its segment boundary, and
# the chunked wire's non-crypto host overhead per round must hold both
# the absolute budget and <0.5x the per-beacon fallback's.
JAX_PLATFORMS=cpu python scripts/sync_smoke.py

# recovery smoke (ISSUE 15): a fixture chain suffers a torn row write
# and a round-field bit flip; `util fsck --repair` must quarantine
# exactly those rounds and roll back to the verified prefix, a peer
# re-sync must restore the suffix bit-identically, and the structural
# scan's CPU throughput floor is pinned.  Jax-free (the operator lane).
python scripts/recovery_smoke.py

# objectsync smoke (ISSUE 18): a donor publishes 2048 fixture rounds as
# content-addressed 512-round segment objects into a tmpdir, a dumb
# aiohttp static server fronts it, and a fresh client catches up purely
# over HTTP with REAL BLS verification — bit-identical to the donor; a
# bit-flipped object must stop a second client at the preceding segment
# boundary with exactly the verified prefix committed, and restoring
# the clean object heals it to the tip.
JAX_PLATFORMS=cpu python scripts/objectsync_smoke.py

# fleet observatory smoke (ISSUE 19): a live 3-node group on real
# metrics ports — one signer killed must drop its participation ratio
# and shrink the threshold margin to 0 on EVERY survivor's
# /debug/participation, heal back to 1 after restart; /debug/fleet on
# one member must cover all group peers over the gRPC metrics channel;
# and the real `util fleet` CLI renders the same fleet as a table.
JAX_PLATFORMS=cpu python scripts/observatory_smoke.py

# native latency harness (ISSUE 12, was the ISSUE 9 prepared-pairing
# smoke): parity on valid + corrupted beacons for all scheme shapes,
# cold vs warm p50/p99 per scheme over N reps written to
# BENCH_native.json (with the recorded build flags), and the warm
# single-verify targets ENFORCED — g2 <= 5 ms, short-sig <= 3 ms.
JAX_PLATFORMS=cpu python scripts/native_smoke.py

# native sanitizer stage (ISSUE 12): a second bls381.cpp build under
# -fsanitize=address,undefined -O1, the full native parity suite run
# against it via the DRAND_TPU_NATIVE_LIB override — lazy-reduction
# bound overflows and out-of-bounds limb reads die here, not as silent
# garbage in the optimized build.
bash scripts/native_asan.sh

# ceremony smoke (ISSUE 20): 16 in-process daemons on real gRPC run a
# full DKG with one dealer crashing after group formation (its fanout
# black-holed, its ceremony task cancelled) — the survivors must close
# the deal/response phases on their timeouts and land QUAL=15 — then
# shrink-reshare to n=12 t=7 WHILE an HTTP client hammers
# /public/latest + /info on a member: zero failed reads, zero dropped
# rounds across the transition, and the epoch-invalidation seams
# (signer table, response cache, chains_version) fire exactly once.
JAX_PLATFORMS=cpu python scripts/dkg_smoke.py

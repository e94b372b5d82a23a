"""Warm-pipeline orchestrator: resumable, retrying, checkpointed
AOT warm/measure chains (ROADMAP item 4 — the velocity unlock).

Every kernel or shape experiment used to price at a ~96-minute
hand-shepherded warm cycle run as a `stage()`-shell-function chain
(scripts/warm_r5.sh / warm_r7.sh): a stage that died 76 minutes in to a
dropped connection was re-run by hand, environment resets were survived only
by human relaunching, and the only record was an append-only
`chain.log`.  This package replaces that with a declarative pipeline:

  - :mod:`spec` — a pipeline is data: named stages with argv/env,
    dependencies, a **required** timeout and **required** expected
    artifacts (the hygiene gate rejects specs without either), plus the
    AOT-cache sensitivity that drives done-detection.
  - :mod:`runner` — supervised subprocess execution with per-stage
    auto-retry through the resilience layer's replay-deterministic
    :class:`~drand_tpu.resilience.RetryPolicy`, per-stage tracing spans
    and ``drand_warm_stage_*`` metrics, heartbeat progress lines, and a
    checkpoint to ``<workdir>/state.json`` after every stage so a
    killed or reset chain resumes at the first incomplete stage.
  - :mod:`classify` — transient failures (dropped connection, backend-init
    timeout, rc from a killed process) are retried; real benchmark
    failures (tracebacks, assertion failures, SIGSEGV/SIGILL) stop the
    chain loudly.
  - :mod:`checkpoint` — byte-stable canonical-JSON pipeline state with
    atomic writes; done-detection = recorded success + artifacts exist
    + the AOT cache key still hits, so a kernel edit correctly
    re-dirties downstream stages.
  - :mod:`doctor` — environment preflight (TPU reachable?  backend-init
    CPU fallback?  aot/ writable?  fixtures present?  persistent
    compilation cache live?) with one-line verdicts and a non-zero
    exit, run automatically before any chain — the no-reachable-TPU
    60 s fallback that silently degraded round 7 now fails in seconds,
    not hours.
  - :mod:`specs` — the registry: ``warm_r8`` re-expresses the full
    round-7 measurement protocol; ``smoke3`` is the tiny CPU spec the
    check.sh warm-smoke stage kills and resumes end-to-end.

CLI: ``drand-tpu warm run|resume|status|doctor|list`` (cli/main.py).
"""

from __future__ import annotations

from drand_tpu.warm.checkpoint import PipelineState, StageState
from drand_tpu.warm.classify import FATAL, TRANSIENT, classify_stage
from drand_tpu.warm.runner import (FatalStageError, PipelineRunner,
                                   StageFailure, TransientStageError)
from drand_tpu.warm.spec import PipelineSpec, SpecError, StageSpec

__all__ = ["PipelineSpec", "StageSpec", "SpecError", "PipelineRunner",
           "PipelineState", "StageState", "StageFailure",
           "TransientStageError", "FatalStageError",
           "classify_stage", "TRANSIENT", "FATAL"]

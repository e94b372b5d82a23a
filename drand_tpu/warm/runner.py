"""The pipeline runner: supervised, retrying, checkpointed stages.

Replaces the `stage()` shell chains (scripts/warm_r7.sh) with an
orchestrator that owns the whole lifecycle:

  - each stage runs as a **subprocess in its own session** (so a stage
    timeout can kill the entire process group, not just the leader),
    stdout captured to its declared artifact, stderr to
    ``<workdir>/<stage>.err``;
  - failures are classified (warm/classify.py): transient ones retry
    through the resilience layer's replay-deterministic
    :class:`~drand_tpu.resilience.RetryPolicy` (same full-jitter
    hash-derived backoff, same decision log the chaos subsystem
    prints), real ones stop the chain loudly with the `warm resume`
    command in the error;
  - state checkpoints to ``<workdir>/state.json`` after **every**
    transition (warm/checkpoint.py, atomic + byte-stable), so kill -9
    at any point resumes at the first incomplete stage;
  - done-detection on resume = recorded success + declared artifacts
    exist + the stage definition hash matches + (for AOT-sensitive
    stages) ``drand_tpu.aot.code_hash()`` still matches and every
    declared AOT name still has a cache entry — a kernel edit
    re-dirties the stage and, transitively, everything downstream;
  - per-stage ``warm.stage`` tracing spans (visible at /debug/spans
    when a metrics server is up), ``drand_warm_stage_*`` metrics, and
    heartbeat progress lines on the injected clock replace the
    append-only chain.log.

The module is jax-free: stages pay backend init in their own
subprocesses; the orchestrator must survive precisely the environments
where that init hangs.
"""

from __future__ import annotations

import asyncio
import os
import signal
import sys
import time

from drand_tpu import log as dlog
from drand_tpu import tracing
from drand_tpu.beacon.clock import Clock, SystemClock
from drand_tpu.chaos.failpoints import FaultInjectedError, failpoint
from drand_tpu.resilience.policy import RetryPolicy
from drand_tpu.warm import checkpoint as ckpt
from drand_tpu.warm.classify import TRANSIENT, classify_stage
from drand_tpu.warm.spec import PipelineSpec, StageSpec, repo_root

log = dlog.get("warm", "runner")

STDERR_TAIL_BYTES = 4096        # classification window into a stage's stderr
DEFAULT_HEARTBEAT_S = 30.0


class StageFailure(RuntimeError):
    """A stage attempt that did not succeed."""

    def __init__(self, message: str, *, stage: str = "",
                 rc: int | None = None, reason: str = ""):
        super().__init__(message)
        self.stage = stage
        self.rc = rc
        self.reason = reason or message


class TransientStageError(StageFailure):
    """Classified transient (dropped connection / kill / timeout): retried by
    the stage's RetryPolicy.  Also the exception type the
    ``warm.stage_exec`` chaos failpoint raises, so injected faults
    exercise the real retry path."""


class FatalStageError(StageFailure):
    """Classified real: stops the chain loudly."""


def _default_code_hash() -> str:
    try:
        from drand_tpu import aot
        return aot.code_hash()
    except Exception:
        return ""


def _default_aot_entries(name: str) -> list[str]:
    try:
        from drand_tpu import aot
        return aot.entries_for(name)
    except Exception:
        return []


def _stderr_say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class PipelineRunner:
    """Drives one :class:`PipelineSpec` to completion."""

    def __init__(self, spec: PipelineSpec, workdir: str | None = None, *,
                 clock: Clock | None = None, seed: int = 0,
                 heartbeat_s: float = DEFAULT_HEARTBEAT_S,
                 env: dict | None = None, say=None,
                 code_hash_fn=None, aot_entries_fn=None):
        spec.validate()
        self.spec = spec
        self.repo = repo_root()
        self.workdir = os.path.abspath(workdir or
                                       os.path.join(self.repo, spec.workdir))
        self.state_path = os.path.join(self.workdir, "state.json")
        self.clock = clock or SystemClock()
        self.seed = seed
        self.heartbeat_s = heartbeat_s
        self.extra_env = dict(env or {})
        self._say = say or _stderr_say
        self._code_hash = code_hash_fn or _default_code_hash
        self._aot_entries = aot_entries_fn or _default_aot_entries

    # -- substitution ------------------------------------------------------

    def _subst(self, s: str) -> str:
        from drand_tpu import aot
        return (s.replace("{python}", sys.executable)
                 .replace("{workdir}", self.workdir)
                 .replace("{repo}", self.repo)
                 .replace("{jax_cache}", aot.persistent_cache_dir()))

    def _artifact_path(self, rel: str) -> str:
        rel = self._subst(rel)
        return rel if os.path.isabs(rel) else os.path.join(self.workdir, rel)

    # -- done-detection / planning ----------------------------------------

    def _not_done(self, stage: StageSpec,
                  state: ckpt.PipelineState) -> str:
        """'' when the stage's recorded success still holds; else the
        one-line reason it must re-run."""
        ss = state.stages.get(stage.name)
        if ss is None or ss.status != ckpt.DONE:
            return "not completed"
        if ss.def_hash != stage.def_hash():
            return "stage definition changed"
        for rel in stage.artifacts:
            path = self._artifact_path(rel)
            if not os.path.exists(path) or os.path.getsize(path) == 0:
                return f"artifact {rel} missing or empty"
        if stage.aot_sensitive:
            current = self._code_hash()
            if current and ss.code_hash and ss.code_hash != current:
                return ("kernel sources changed since this stage ran "
                        "(AOT cache key miss)")
        for name in stage.aot_names:
            if not self._aot_entries(name):
                return f"AOT cache entry {name!r} missing"
        return ""

    def plan(self, state: ckpt.PipelineState) -> dict[str, str]:
        """stage name -> reason it will run; stages absent from the map
        are done and will be skipped.  Dirtiness propagates through
        dependencies: a re-running stage drags every dependent with it
        (its outputs — AOT entries, fixtures — feed them)."""
        dirty: dict[str, str] = {}
        for stage in self.spec.order():
            why = self._not_done(stage, state)
            if not why:
                dirty_deps = [d for d in stage.deps if d in dirty]
                if dirty_deps:
                    why = f"dependency {dirty_deps[0]} re-runs"
            if why:
                dirty[stage.name] = why
        return dirty

    # -- state I/O ---------------------------------------------------------

    def load_state(self) -> ckpt.PipelineState | None:
        if not os.path.exists(self.state_path):
            return None
        state = ckpt.PipelineState.load(self.state_path)
        if state.pipeline and state.pipeline != self.spec.name:
            raise FatalStageError(
                f"{self.state_path} belongs to pipeline "
                f"{state.pipeline!r}, not {self.spec.name!r} — pass a "
                "different --workdir", stage="", reason="state mismatch")
        return state

    async def _checkpoint(self, state: ckpt.PipelineState) -> None:
        await asyncio.to_thread(state.save, self.state_path)

    # -- status (CLI `warm status`) ---------------------------------------

    def status(self) -> dict:
        state = self.load_state() or ckpt.PipelineState(
            pipeline=self.spec.name)
        dirty = self.plan(state)
        stages = []
        for stage in self.spec.order():
            ss = state.stages.get(stage.name) or ckpt.StageState()
            stages.append({
                "stage": stage.name, "status": ss.status,
                "attempts": ss.attempts, "rc": ss.rc,
                "duration_s": ss.duration_s, "error": ss.error,
                "next": ("run" if stage.name in dirty else "skip"),
                "why": dirty.get(stage.name, "done"),
            })
        complete = not dirty and all(
            state.stages.get(s.name) is not None
            and state.stages[s.name].status == ckpt.DONE
            for s in self.spec.stages)
        return {"pipeline": self.spec.name, "workdir": self.workdir,
                "state_file": self.state_path, "complete": complete,
                "stages": stages}

    # -- execution ---------------------------------------------------------

    async def run(self, resume: bool = False) -> ckpt.PipelineState:
        """Execute the pipeline.  ``resume=True`` loads the checkpoint
        and skips stages whose recorded success still holds; a fresh
        run starts from an empty state (done-detection then sees every
        stage as dirty)."""
        await asyncio.to_thread(os.makedirs, self.workdir, exist_ok=True)
        state = (self.load_state() if resume else None) \
            or ckpt.PipelineState(pipeline=self.spec.name)
        dirty = self.plan(state)
        order = self.spec.order()
        todo = [s for s in order if s.name in dirty]
        self._say(f"warm {self.spec.name}: {len(order)} stages, "
                  f"{len(order) - len(todo)} already done, "
                  f"{len(todo)} to run (workdir {self.workdir})")
        with tracing.span("warm.pipeline", pipeline=self.spec.name,
                          stages=len(order), to_run=len(todo)):
            for stage in order:
                if stage.name not in dirty:
                    self._count(stage.name, "skipped")
                    self._say(f"warm {self.spec.name}: stage "
                              f"{stage.name}: done — skipping")
                    continue
                self._say(f"warm {self.spec.name}: stage {stage.name}: "
                          f"starting ({dirty[stage.name]})")
                await self._run_stage(stage, state)
        return state

    async def _run_stage(self, stage: StageSpec,
                         state: ckpt.PipelineState) -> None:
        policy = RetryPolicy(max_attempts=stage.max_attempts,
                             clock=self.clock, seed=self.seed)
        site = f"warm.{self.spec.name}.{stage.name}"
        ss = state.stage(stage.name)
        ss.status = ckpt.RUNNING
        ss.error = ""
        ss.rc = None
        ss.completed_wall = None

        async def attempt(i: int):
            ss.attempts += 1
            await self._checkpoint(state)
            with tracing.span("warm.stage", pipeline=self.spec.name,
                              stage=stage.name, attempt=i) as sp:
                # the chaos seam: an armed schedule can kill this
                # attempt exactly like a dropped connection would, and the
                # retry below must recover deterministically
                await failpoint("warm.stage_exec", exc=TransientStageError,
                                pipeline=self.spec.name, stage=stage.name,
                                attempt=i)
                rc, dur, timed_out, err_tail = await self._spawn(stage)
                sp.set(rc=rc, duration_s=round(dur, 3),
                       timed_out=timed_out)
                if rc == 0:
                    missing = [rel for rel in stage.artifacts
                               if not os.path.exists(
                                   self._artifact_path(rel))
                               or os.path.getsize(
                                   self._artifact_path(rel)) == 0]
                    if missing:
                        sp.set(missing_artifacts=missing)
                        raise FatalStageError(
                            f"stage {stage.name} exited 0 but expected "
                            f"artifacts are missing/empty: {missing}",
                            stage=stage.name, rc=0,
                            reason="declared artifact missing after "
                                   "success — spec or stage bug")
                    return rc, dur
                verdict, reason = classify_stage(rc, err_tail, timed_out)
                sp.set(verdict=verdict, reason=reason)
                exc_cls = TransientStageError if verdict == TRANSIENT \
                    else FatalStageError
                raise exc_cls(
                    f"stage {stage.name} failed (rc={rc}): {reason}",
                    stage=stage.name, rc=rc, reason=reason)

        def _retryable(exc: BaseException) -> bool:
            return isinstance(exc, (TransientStageError,
                                    FaultInjectedError))

        t0 = time.perf_counter()
        try:
            _, dur = await policy.call(site, attempt, key=stage.name,
                                       classify=_retryable)
        except Exception as exc:
            ss.status = ckpt.FAILED
            ss.rc = getattr(exc, "rc", ss.rc)
            ss.error = getattr(exc, "reason", "") or str(exc)
            await self._checkpoint(state)
            fatal = isinstance(exc, FatalStageError)
            self._count(stage.name, "fatal" if fatal else "exhausted")
            self._say(f"warm {self.spec.name}: stage {stage.name}: "
                      f"{'FAILED' if fatal else 'retries exhausted'} — "
                      f"{ss.error}\n  fix, then: drand-tpu warm resume "
                      f"{self.spec.name}")
            log.error("stage %s failed after %d attempt(s): %s",
                      stage.name, ss.attempts, ss.error)
            raise
        ss.status = ckpt.DONE
        ss.rc = 0
        ss.duration_s = round(dur, 3)
        ss.completed_wall = round(self.clock.now(), 3)
        ss.def_hash = stage.def_hash()
        ss.code_hash = self._code_hash() if stage.aot_sensitive else ""
        ss.artifacts = sorted(stage.artifacts)
        ss.error = ""
        await self._checkpoint(state)
        self._count(stage.name, "success")
        self._observe(stage.name, dur)
        retried = f" (attempt {ss.attempts})" if ss.attempts > 1 else ""
        self._say(f"warm {self.spec.name}: stage {stage.name}: ok in "
                  f"{dur:.1f}s{retried}")
        log.info("stage %s ok in %.1fs attempts=%d total=%.1fs",
                 stage.name, dur, ss.attempts, time.perf_counter() - t0)

    async def _spawn(self, stage: StageSpec):
        """One supervised subprocess attempt: (rc, duration_s,
        timed_out, stderr_tail)."""
        argv = [self._subst(a) for a in stage.argv]
        env = dict(os.environ)
        env.update({k: self._subst(v) for k, v in stage.env})
        env.update(self.extra_env)
        out_path = (self._artifact_path(stage.artifacts[0])
                    if stage.stdout_artifact
                    else os.path.join(self.workdir, stage.name + ".out"))
        err_path = os.path.join(self.workdir, stage.name + ".err")

        def _open_streams():
            os.makedirs(os.path.dirname(out_path), exist_ok=True)
            return open(out_path, "wb"), open(err_path, "wb")

        out_f, err_f = await asyncio.to_thread(_open_streams)
        t0 = time.perf_counter()
        timed_out = False
        try:
            proc = await asyncio.create_subprocess_exec(
                *argv, stdout=out_f, stderr=err_f, cwd=self.repo,
                env=env, start_new_session=True)
            hb = asyncio.create_task(self._heartbeat(stage, proc.pid, t0))
            try:
                await asyncio.wait_for(proc.wait(),
                                       timeout=stage.timeout_s)
            except asyncio.TimeoutError:
                timed_out = True
                self._kill_group(proc)
                await proc.wait()
            finally:
                hb.cancel()
                try:
                    await hb
                except asyncio.CancelledError:
                    pass
        finally:
            await asyncio.to_thread(self._close_streams, out_f, err_f)
        dur = time.perf_counter() - t0
        tail = await asyncio.to_thread(self._tail, err_path)
        return proc.returncode, dur, timed_out, tail

    @staticmethod
    def _close_streams(*fs) -> None:
        for f in fs:
            try:
                f.close()
            except OSError:
                pass

    @staticmethod
    def _kill_group(proc) -> None:
        """SIGKILL the stage's whole session: a timed-out bench may have
        children the leader's death would orphan."""
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError, OSError):
            try:
                proc.kill()
            except ProcessLookupError:
                pass

    @staticmethod
    def _tail(path: str, nbytes: int = STDERR_TAIL_BYTES) -> str:
        try:
            with open(path, "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                f.seek(max(0, size - nbytes))
                return f.read().decode(errors="replace")
        except OSError:
            return ""

    async def _heartbeat(self, stage: StageSpec, pid: int,
                         t0: float) -> None:
        """Progress lines while a stage runs — the liveness signal the
        hand-run chains never had (a wedged stage looked identical to a
        long one).  Rides the injected clock so fake-clock tests can
        drive it."""
        while True:
            await self.clock.sleep(self.heartbeat_s)
            elapsed = int(time.perf_counter() - t0)
            self._say(f"warm {self.spec.name}: stage {stage.name}: "
                      f"running {elapsed}s / timeout "
                      f"{int(stage.timeout_s)}s (pid {pid})")

    # -- metrics (never fail the chain) -----------------------------------

    def _count(self, stage: str, outcome: str) -> None:
        try:
            from drand_tpu import metrics as M
            M.WARM_STAGE.labels(self.spec.name, stage, outcome).inc()
        except Exception:
            pass

    def _observe(self, stage: str, dur: float) -> None:
        try:
            from drand_tpu import metrics as M
            M.WARM_STAGE_DURATION.labels(self.spec.name, stage) \
                .observe(dur)
        except Exception:
            pass

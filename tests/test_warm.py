"""Warm-pipeline orchestrator (drand_tpu/warm, ISSUE 8).

The acceptance spine: kill -9 of a running chain followed by resume
completes the pipeline with completed stages skipped and the injected
transient failure retried, over byte-stable state.json checkpoints,
with per-stage spans and drand_warm_stage_* metrics visible at
/debug/spans and in exposition.  Plus the transient-vs-real
classification matrix, kernel-edit re-dirtying, chaos-failpoint
injection into a stage attempt, and the doctor's verdict logic with
injected probes.

Everything here is CPU-only and jax-free on the orchestrator side;
stage subprocesses are tiny plain-python commands.
"""

import asyncio
import json
import os
import signal
import subprocess
import sys
import time

import pytest

from drand_tpu.warm import checkpoint as ckpt
from drand_tpu.warm import classify as wclassify
from drand_tpu.warm import specs as wspecs
from drand_tpu.warm.runner import (FatalStageError, PipelineRunner,
                                   TransientStageError)
from drand_tpu.warm.spec import PipelineSpec, SpecError, StageSpec


def _stage(name, code, *, deps=(), timeout_s=60.0, artifacts=None,
           max_attempts=3, aot_sensitive=False, aot_names=()):
    """A toy stage: run `code` with the artifact path in sys.argv[1]."""
    artifacts = tuple(artifacts or (f"{name}.json",))
    return StageSpec(
        name=name, deps=tuple(deps), timeout_s=timeout_s,
        artifacts=artifacts, max_attempts=max_attempts,
        aot_sensitive=aot_sensitive, aot_names=tuple(aot_names),
        stdout_artifact=False,
        argv=("{python}", "-c", code, os.path.join("{workdir}",
                                                   artifacts[0])))


_WRITE = ("import sys, json; open(sys.argv[1], 'w')"
          ".write(json.dumps({'ok': True}))")
# fails once per workdir (sentinel), rc 137 = the shell's SIGKILL form
_FLAKY = ("import sys, os, json\n"
          "s = sys.argv[1] + '.sentinel'\n"
          "if not os.path.exists(s):\n"
          "    open(s, 'w').write('x')\n"
          "    sys.exit(137)\n"
          "open(sys.argv[1], 'w').write(json.dumps({'ok': True}))")
_FATAL = ("import sys; print('boom: assertion failed', file=sys.stderr); "
          "sys.exit(3)")


def _pipe(name, *stages):
    return PipelineSpec(name=name, stages=tuple(stages), slow=False)


def _run(runner, resume=False):
    return asyncio.run(runner.run(resume=resume))


# ---------------------------------------------------------------------------
# spec validation (the hygiene contract)
# ---------------------------------------------------------------------------

def test_spec_requires_timeout_and_artifacts():
    with pytest.raises(SpecError, match="timeout"):
        _pipe("p", StageSpec(name="a", argv=("x",), timeout_s=0,
                             artifacts=("a.json",))).validate()
    with pytest.raises(SpecError, match="artifact"):
        _pipe("p", StageSpec(name="a", argv=("x",), timeout_s=1,
                             artifacts=())).validate()


def test_spec_rejects_cycles_unknown_deps_and_dupes():
    a = _stage("a", _WRITE, deps=("b",))
    b = _stage("b", _WRITE, deps=("a",))
    with pytest.raises(SpecError, match="cycle"):
        _pipe("p", a, b).validate()
    with pytest.raises(SpecError, match="unknown deps"):
        _pipe("p", _stage("a", _WRITE, deps=("ghost",))).validate()
    with pytest.raises(SpecError, match="duplicate"):
        _pipe("p", _stage("a", _WRITE), _stage("a", _WRITE)).validate()


def test_registered_specs_validate_and_order():
    # the registry itself is also gated by test_hygiene; here: ordering
    for spec in wspecs.SPECS.values():
        spec.validate()
    assert [s.name for s in wspecs.SMOKE3.order()] == ["s1", "s2", "s3"]
    assert [s.name for s in wspecs.WARM_R8.order()][0] == "catchup"
    assert wspecs.WARM_R8.dependents("catchup") == {
        s.name for s in wspecs.WARM_R8.stages} - {"catchup"}


# ---------------------------------------------------------------------------
# transient-vs-real classification matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rc,stderr,timed_out,want", [
    # killed-process signatures: the dropped-connection / env-reset pattern
    (-signal.SIGKILL, "", False, wclassify.TRANSIENT),
    (-signal.SIGTERM, "", False, wclassify.TRANSIENT),
    (-signal.SIGHUP, "", False, wclassify.TRANSIENT),
    (137, "", False, wclassify.TRANSIENT),         # 128+SIGKILL
    (143, "", False, wclassify.TRANSIENT),         # 128+SIGTERM
    # declared-timeout kill
    (None, "", True, wclassify.TRANSIENT),
    # crash signals: re-running repeats them (SIGILL = AOT hazard)
    (-signal.SIGSEGV, "", False, wclassify.FATAL),
    (-signal.SIGILL, "", False, wclassify.FATAL),
    (134, "", False, wclassify.FATAL),             # 128+SIGABRT
    # transport/backend markers in stderr rescue a non-zero rc
    (1, "grpc: DEADLINE_EXCEEDED while fetching", False,
     wclassify.TRANSIENT),
    (1, "ConnectionResetError: Connection reset by peer", False,
     wclassify.TRANSIENT),
    (1, "RuntimeError: Unable to initialize backend 'tpu'", False,
     wclassify.TRANSIENT),
    (1, "transport failure: socket closed", False, wclassify.TRANSIENT),
    # a real benchmark failure stops the chain
    (1, "Traceback ...\nAssertionError: verdicts differ", False,
     wclassify.FATAL),
    (3, "", False, wclassify.FATAL),
])
def test_classification_matrix(rc, stderr, timed_out, want):
    verdict, reason = wclassify.classify_stage(rc, stderr, timed_out)
    assert verdict == want, reason
    assert reason     # always an operator-readable explanation


# ---------------------------------------------------------------------------
# checkpoint byte-stability
# ---------------------------------------------------------------------------

def test_checkpoint_byte_stable(tmp_path):
    st = ckpt.PipelineState(pipeline="p")
    ss = st.stage("a")
    ss.status = ckpt.DONE
    ss.attempts = 2
    ss.rc = 0
    ss.duration_s = 1.25
    ss.completed_wall = 1700000000.5
    ss.def_hash = "abc"
    ss.artifacts = ["a.json"]
    assert st.dumps() == st.dumps()
    path = str(tmp_path / "state.json")
    st.save(path)
    on_disk = open(path).read()
    assert on_disk == st.dumps()
    # load -> dumps is the identity on bytes (canonical serialization)
    assert ckpt.PipelineState.load(path).dumps() == on_disk
    # saving the loaded state changes nothing (no save-time stamps)
    ckpt.PipelineState.load(path).save(path)
    assert open(path).read() == on_disk
    assert not os.path.exists(path + ".tmp")


# ---------------------------------------------------------------------------
# run / retry / resume
# ---------------------------------------------------------------------------

def test_pipeline_runs_and_retries_transient(tmp_path):
    spec = _pipe("toy", _stage("a", _WRITE), _stage("b", _FLAKY,
                                                    deps=("a",)),
                 _stage("c", _WRITE, deps=("b",)))
    said = []
    r = PipelineRunner(spec, str(tmp_path), say=said.append)
    state = _run(r)
    assert state.stages["a"].attempts == 1
    assert state.stages["b"].attempts == 2        # transient 137 retried
    assert state.stages["b"].status == ckpt.DONE
    assert state.stages["c"].status == ckpt.DONE
    assert r.status()["complete"]
    # the retry rode the resilience policy's deterministic backoff: the
    # decision log carries the same hash-derived delay a fresh policy
    # computes for the same (seed, site, key, attempt)
    from drand_tpu.resilience.policy import LOG, RetryPolicy
    entries = [e for e in LOG.entries()
               if e.get("site") == "warm.toy.b"
               and e.get("outcome") == "retry"]
    assert entries, "retry decision not logged"
    want_ms = int(RetryPolicy(seed=0).backoff_s(
        "warm.toy.b", 1, key="b") * 1000)
    assert entries[-1]["backoff_ms"] == want_ms


def test_fatal_failure_stops_chain_loudly(tmp_path):
    spec = _pipe("toy", _stage("a", _WRITE),
                 _stage("b", _FATAL, deps=("a",)),
                 _stage("c", _WRITE, deps=("b",)))
    said = []
    r = PipelineRunner(spec, str(tmp_path), say=said.append)
    with pytest.raises(FatalStageError):
        _run(r)
    state = r.load_state()
    assert state.stages["b"].status == ckpt.FAILED
    assert state.stages["b"].attempts == 1        # NOT retried
    assert "no transient signature" in state.stages["b"].error
    assert "c" not in state.stages                # chain stopped
    assert any("warm resume" in line for line in said)
    # fixing the stage then resuming completes, with `a` skipped
    fixed = _pipe("toy", _stage("a", _WRITE),
                  _stage("b", _WRITE, deps=("a",)),
                  _stage("c", _WRITE, deps=("b",)))
    r2 = PipelineRunner(fixed, str(tmp_path))
    state = _run(r2, resume=True)
    assert state.stages["a"].attempts == 1        # skipped, not re-run
    assert all(state.stages[n].status == ckpt.DONE for n in "abc")


def test_timeout_is_transient_and_bounded(tmp_path):
    hang = "import sys, time; time.sleep(30)"
    spec = _pipe("toy", _stage("a", hang, timeout_s=0.5, max_attempts=1))
    r = PipelineRunner(spec, str(tmp_path))
    t0 = time.perf_counter()
    with pytest.raises(TransientStageError):
        _run(r)
    assert time.perf_counter() - t0 < 10
    state = r.load_state()
    assert state.stages["a"].status == ckpt.FAILED
    assert "timeout" in state.stages["a"].error


def test_missing_declared_artifact_is_fatal(tmp_path):
    lies = "import sys; sys.exit(0)"           # exits 0, writes nothing
    spec = _pipe("toy", _stage("a", lies))
    r = PipelineRunner(spec, str(tmp_path))
    with pytest.raises(FatalStageError, match="artifact"):
        _run(r)


def test_sigkill_mid_stage_then_resume_skips_done_stages(tmp_path):
    """THE acceptance path: a real orchestrator process is SIGKILLed
    while its second stage hangs in a subprocess; `warm resume` then
    completes the pipeline — finished stages skipped, and smoke3's
    injected transient failure (exit 137 on s2's next first-attempt)
    retried through the policy."""
    wd = str(tmp_path / "wd")
    driver = ("import asyncio, sys\n"
              "from drand_tpu.warm import runner, specs\n"
              "r = runner.PipelineRunner(specs.SMOKE3, sys.argv[1])\n"
              "asyncio.run(r.run())\n")
    env = dict(os.environ)
    env["WARM_SMOKE_HANG_S"] = "30"
    proc = subprocess.Popen([sys.executable, "-c", driver, wd], env=env,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    try:
        state_path = os.path.join(wd, "state.json")
        deadline = time.perf_counter() + 60
        while time.perf_counter() < deadline:
            try:
                st = json.load(open(state_path))
                if st["stages"].get("s1", {}).get("status") == "done" \
                        and st["stages"].get("s2", {}).get("status") \
                        == "running":
                    break
            except (OSError, ValueError, KeyError):
                pass
            time.sleep(0.1)
        else:
            pytest.fail("pipeline never reached s2")
        time.sleep(0.5)                 # let the s2 subprocess spawn
        proc.kill()                     # SIGKILL, mid-stage
        proc.wait(timeout=10)
    finally:
        if proc.poll() is None:
            proc.kill()
        # reap the orphaned (own-session) hanging stage subprocess
        subprocess.run(["pkill", "-9", "-f", wd], check=False)
    st = json.load(open(state_path))
    assert st["stages"]["s1"]["status"] == "done"
    assert st["stages"]["s2"]["status"] == "running"   # torn mid-flight

    said = []
    r = PipelineRunner(wspecs.SMOKE3, wd, say=said.append)
    state = _run(r, resume=True)
    assert state.stages["s1"].attempts == 1            # skipped
    assert any("s1: done — skipping" in line for line in said)
    # attempt 1 died with the orchestrator; attempt 2 hit smoke3's
    # injected exit-137 transient; attempt 3 completed
    assert state.stages["s2"].attempts == 3
    assert state.stages["s2"].status == ckpt.DONE
    assert state.stages["s3"].status == ckpt.DONE
    assert r.status()["complete"]


# ---------------------------------------------------------------------------
# done-detection: kernel edits, AOT entries, artifacts, definitions
# ---------------------------------------------------------------------------

def test_kernel_edit_redirties_stage_and_downstream(tmp_path):
    spec = _pipe("toy",
                 _stage("a", _WRITE, aot_sensitive=True),
                 _stage("b", _WRITE, deps=("a",), aot_sensitive=True))
    r = PipelineRunner(spec, str(tmp_path), code_hash_fn=lambda: "h1")
    _run(r)
    same = PipelineRunner(spec, str(tmp_path), code_hash_fn=lambda: "h1")
    assert same.plan(same.load_state()) == {}
    edited = PipelineRunner(spec, str(tmp_path),
                            code_hash_fn=lambda: "h2")
    dirty = edited.plan(edited.load_state())
    assert "kernel sources changed" in dirty["a"]
    assert dirty["b"]            # dragged along (its own hash also misses)
    # and only the dirty stages re-run on resume
    state = _run(edited, resume=True)
    assert state.stages["a"].attempts == 2
    assert state.stages["a"].code_hash == "h2"


def test_missing_aot_entry_redirties(tmp_path):
    spec = _pipe("toy", _stage("a", _WRITE, aot_names=("verify-64",)))
    entries = {"verify-64": ["verify-64-abc.aotx"]}
    r = PipelineRunner(spec, str(tmp_path),
                       aot_entries_fn=lambda n: entries.get(n, []))
    _run(r)
    assert r.plan(r.load_state()) == {}
    entries.clear()                      # the executable got pruned
    dirty = r.plan(r.load_state())
    assert "AOT cache entry" in dirty["a"]


def test_artifact_loss_and_definition_change_redirty(tmp_path):
    spec = _pipe("toy", _stage("a", _WRITE))
    r = PipelineRunner(spec, str(tmp_path))
    _run(r)
    assert r.plan(r.load_state()) == {}
    os.remove(str(tmp_path / "a.json"))
    assert "artifact" in r.plan(r.load_state())["a"]
    _run(r, resume=True)                 # heal
    changed = _pipe("toy", _stage("a", _WRITE + " # v2"))
    r2 = PipelineRunner(changed, str(tmp_path))
    assert "definition changed" in r2.plan(r2.load_state())["a"]


# ---------------------------------------------------------------------------
# chaos failpoint in a stage attempt, retried deterministically
# ---------------------------------------------------------------------------

def test_chaos_failpoint_injects_and_policy_recovers(tmp_path):
    from drand_tpu.chaos import failpoints
    spec = _pipe("toy", _stage("a", _WRITE))
    sched = failpoints.Schedule(seed=7, rules=[failpoints.Rule.make(
        "warm.stage_exec", "error", match={"stage": "a"}, times=1)])
    failpoints.arm(sched)
    try:
        r = PipelineRunner(spec, str(tmp_path), seed=7)
        state = _run(r)
    finally:
        failpoints.disarm()
    assert state.stages["a"].status == ckpt.DONE
    assert state.stages["a"].attempts == 2     # injected fault + retry
    log = sched.injection_log()
    assert log and log[0]["site"] == "warm.stage_exec"
    assert log[0]["stage"] == "a"


# ---------------------------------------------------------------------------
# spans + metrics surface (the /debug/spans and exposition acceptance)
# ---------------------------------------------------------------------------

def test_stage_spans_and_metrics_visible(tmp_path):
    async def main():
        import aiohttp

        from drand_tpu import tracing
        from drand_tpu.cli.main import _WarmMetricsShim
        from drand_tpu.metrics import MetricsServer
        tracing.RECORDER.clear()
        spec = _pipe("toy", _stage("a", _WRITE),
                     _stage("b", _FLAKY, deps=("a",)))
        ms = MetricsServer(_WarmMetricsShim(), 0)
        await ms.start()
        try:
            r = PipelineRunner(spec, str(tmp_path))
            await r.run()
            async with aiohttp.ClientSession() as http:
                base = f"http://127.0.0.1:{ms.port}"
                async with http.get(f"{base}/metrics") as resp:
                    assert resp.status == 200
                    text = await resp.text()
                success_lines = [
                    line for line in text.splitlines()
                    if line.startswith("drand_warm_stage_total")
                    and 'pipeline="toy"' in line and 'stage="a"' in line
                    and 'outcome="success"' in line]
                assert success_lines, "warm stage counter not exposed"
                assert "drand_warm_stage_duration_seconds" in text
                async with http.get(f"{base}/debug/spans") as resp:
                    traces = (await resp.json())["traces"]
        finally:
            await ms.stop()
        stages = {s for t in traces for s in t["stages"]}
        assert "warm.pipeline" in stages and "warm.stage" in stages

    asyncio.run(main())


# ---------------------------------------------------------------------------
# doctor verdict logic (probes injected; no subprocesses)
# ---------------------------------------------------------------------------

def test_doctor_backend_verdicts(tmp_path, monkeypatch):
    from drand_tpu.warm import doctor
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    ok = doctor.check_backend(probe=lambda: {"init_s": 0.3,
                                             "platform": "cpu",
                                             "devices": 8})
    assert ok.ok
    # env asks for a device platform, init fell back to CPU: the
    # round-7 trap must FAIL loudly
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    fb = doctor.check_backend(probe=lambda: {"init_s": 61.0,
                                             "platform": "cpu",
                                             "devices": 1})
    assert not fb.ok and "FELL BACK" in fb.verdict
    slow = doctor.check_backend(probe=lambda: {"init_s": 50.0,
                                               "platform": "tpu",
                                               "devices": 4})
    assert not slow.ok and "fallback" in slow.verdict.lower()
    dead = doctor.check_backend(
        probe=lambda: (_ for _ in ()).throw(RuntimeError("probe rc=1")))
    assert not dead.ok


def test_doctor_cache_and_workdir_verdicts(tmp_path, monkeypatch):
    from drand_tpu.warm import doctor
    cache = tmp_path / "cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    empty = doctor.check_compile_cache(
        probe=lambda: {"first_call_s": 0.1})
    assert not empty.ok and "nothing persisted" in empty.verdict
    cache.mkdir()
    (cache / "entry").write_text("x")
    good = doctor.check_compile_cache(
        probe=lambda: {"first_call_s": 0.1})
    assert good.ok
    slow = doctor.check_compile_cache(
        probe=lambda: {"first_call_s": 75.0})
    assert not slow.ok and "60s" in slow.verdict
    assert doctor.check_workdir(str(tmp_path / "new")).ok
    assert doctor.check_fixtures().ok
    results = doctor.run_doctor(str(tmp_path), fast=True,
                                backend_probe=lambda: {
                                    "init_s": 0.1, "platform": "cpu",
                                    "devices": 1})
    lines = []
    assert doctor.print_results(results, say=lines.append)
    assert len(lines) == 4 and all("ok" in line for line in lines)


def test_status_is_read_only(tmp_path):
    spec = _pipe("toy", _stage("a", _WRITE))
    r = PipelineRunner(spec, str(tmp_path))
    _run(r)
    before = open(r.state_path).read()
    r.status()
    r.status()
    assert open(r.state_path).read() == before


def test_warm_is_not_a_jax_command_of_the_cli():
    """One process for each chip: `drand-tpu warm` starts children that
    use the device (doctor probes, stages), so the CLI must not bring a
    backend up for it: `warm` stays out of _NEEDS_JAX.  (PR 22 found that
    this is necessary and not sufficient: importing the CLI module runs
    `drand_tpu/ops/towers.py`'s module-level `jnp.asarray` constants,
    which initializes a backend in ANY drand-tpu process.  On a host with
    a chip the warm parent therefore needs `JAX_PLATFORMS=cpu`; ROADMAP
    D2 owns the repair or the deletion.)"""
    import importlib
    cli = importlib.import_module("drand_tpu.cli.main")
    assert "warm" not in cli._NEEDS_JAX
    assert {"start", "sync", "get"} <= cli._NEEDS_JAX

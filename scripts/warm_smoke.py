"""check.sh warm-smoke stage: the orchestrator's acceptance path over
real processes (drand_tpu/warm, ISSUE 8).

Drives the tiny CPU-only `smoke3` spec end-to-end through the real CLI:

  1. `warm run smoke3` launched with WARM_SMOKE_HANG_S so stage s2
     hangs in its subprocess, then the WHOLE orchestrator is killed
     with SIGKILL mid-stage — the dropped-connection/environment-reset shape
     that used to cost a human relaunch;
  2. `warm status` must show s1 done / s2 torn mid-flight from the
     byte-stable state.json checkpoint;
  3. `warm resume` must complete the pipeline: s1 SKIPPED (attempts
     unchanged), s2 hitting smoke3's injected transient failure (exit
     137 on its next first-attempt) and being RETRIED by the policy,
     s3 run;
  4. a fast doctor pass must verdict this environment ok.

Exit 0 on success, 1 with a reason on any violated expectation.
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = [sys.executable, "-m", "drand_tpu.cli"]


def fail(msg: str) -> None:
    print(f"warm-smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def cli(*args, env=None, check=True) -> subprocess.CompletedProcess:
    proc = subprocess.run([*CLI, *args], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)
    if check and proc.returncode != 0:
        fail(f"`drand-tpu {' '.join(args)}` rc={proc.returncode}:\n"
             f"{proc.stderr[-1200:]}")
    return proc


def status(workdir: str) -> dict:
    proc = cli("warm", "status", "smoke3", "--workdir", workdir, "--json")
    return json.loads(proc.stdout)


def main() -> None:
    workdir = tempfile.mkdtemp(prefix="warm_smoke_")
    try:
        # -- leg 1: run with a hanging s2, SIGKILL the orchestrator ----
        env = dict(os.environ)
        env["WARM_SMOKE_HANG_S"] = "60"
        orch = subprocess.Popen(
            [*CLI, "warm", "run", "smoke3", "--workdir", workdir,
             "--no-doctor"],
            cwd=REPO, env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)
        state_path = os.path.join(workdir, "state.json")
        deadline = time.perf_counter() + 90
        seen_running = False
        while time.perf_counter() < deadline:
            try:
                st = json.load(open(state_path))
                s1 = st["stages"].get("s1", {}).get("status")
                s2 = st["stages"].get("s2", {}).get("status")
                if s1 == "done" and s2 == "running":
                    seen_running = True
                    break
            except (OSError, ValueError):
                pass
            if orch.poll() is not None:
                fail("orchestrator exited before reaching s2")
            time.sleep(0.2)
        if not seen_running:
            orch.kill()
            fail("pipeline never checkpointed s2 as running")
        time.sleep(0.5)                     # let the s2 subprocess spawn
        orch.kill()                         # SIGKILL, mid-stage
        orch.wait(timeout=15)
        # reap the orphaned (own-session) hanging stage subprocess
        subprocess.run(["pkill", "-9", "-f", workdir], check=False)
        print("warm-smoke: orchestrator SIGKILLed mid-stage "
              f"(rc={orch.returncode})")

        # -- leg 2: the checkpoint survived the kill -------------------
        st = status(workdir)
        rows = {r["stage"]: r for r in st["stages"]}
        if st["complete"]:
            fail("status claims complete after a mid-stage kill")
        if rows["s1"]["status"] != "done" or rows["s1"]["next"] != "skip":
            fail(f"s1 should be done+skip after kill, got {rows['s1']}")
        if rows["s2"]["next"] != "run":
            fail(f"s2 should be scheduled to run, got {rows['s2']}")
        raw = open(state_path).read()
        if json.loads(raw) != json.loads(raw):      # paranoia: parseable
            fail("state.json not stable")

        # -- leg 3: resume completes, s1 skipped, s2 retried -----------
        proc = cli("warm", "resume", "smoke3", "--workdir", workdir,
                   "--no-doctor")
        if "s1: done — skipping" not in proc.stderr:
            fail(f"resume did not skip s1:\n{proc.stderr[-800:]}")
        st = status(workdir)
        rows = {r["stage"]: r for r in st["stages"]}
        if not st["complete"]:
            fail(f"pipeline incomplete after resume: {rows}")
        if rows["s1"]["attempts"] != 1:
            fail(f"s1 re-ran on resume (attempts={rows['s1']['attempts']})")
        # attempt 1 died with the orchestrator, attempt 2 = the injected
        # exit-137 transient, attempt 3 succeeded — the retry is REQUIRED
        if rows["s2"]["attempts"] != 3:
            fail("s2 should take exactly 3 attempts (kill + injected "
                 f"transient + success), got {rows['s2']['attempts']}")
        print("warm-smoke: resume completed — s1 skipped, s2 retried "
              f"({rows['s2']['attempts']} attempts), s3 ran")

        # -- leg 4: doctor verdicts this environment -------------------
        proc = cli("warm", "doctor", "--fast-doctor", "--workdir", workdir)
        print("warm-smoke: doctor ok")
        print("warm-smoke: OK")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    main()

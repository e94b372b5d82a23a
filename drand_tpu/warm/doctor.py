"""`warm doctor`: environment preflight before spending hours.

Round 7's chain ran its whole measurement protocol against a container
with **no reachable TPU** — jax's backend init silently fell back to
CPU after a ~60 s stall, and every "device" number was quietly a CPU
number (STATUS.md round-7 deviation).  The doctor makes that class of
failure cost seconds, not hours: each check prints a one-line verdict,
any FAIL exits non-zero, and `warm run`/`warm resume` refuse to start a
chain until the doctor passes (override: --no-doctor).

Checks:

  - **backend** — a *subprocess* imports jax and reports
    platform/device count/init seconds.  Run in a subprocess because
    the pathological case is exactly an import that stalls for 60 s (or
    hangs): the orchestrator itself must never pay it.  Verdicts: FAIL
    when the env asks for a device platform but init fell back to CPU;
    FAIL when init exceeds the fallback threshold; FAIL on
    timeout/import error.
  - **aot-dir** — the AOT executable cache directory exists/is
    writable, plus an entry count (an empty cache before a measure run
    means hours of compiles: say so up front).
  - **workdir** — the pipeline workdir (warm_logs) is writable; the
    checkpoint file must be able to land.
  - **fixtures** — the files a bench stage needs exist in this
    checkout (bench.py, __graft_entry__.py, the fixtures module).
  - **compile-cache** — the persistent XLA compilation-cache probe,
    folded in from the former ``tools/cache_probe.py``: two fresh
    subprocesses jit the same small program against the configured
    cache dir; the second must find a populated cache.  Skipped by
    ``fast=True`` (it costs two interpreter+jax starts).

Every probe subprocess is bounded by a timeout — a doctor that hangs
is a doctor that failed.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from dataclasses import dataclass

from drand_tpu.warm.spec import repo_root

BACKEND_TIMEOUT_S = 150.0       # hard bound on the backend probe
FALLBACK_THRESHOLD_S = 45.0     # init slower than this = the 60 s
#                                 no-reachable-TPU fallback pattern
CACHE_PROBE_TIMEOUT_S = 120.0

# the probe subprocess: report init time + platform as one JSON line
_BACKEND_PROBE = (
    "import json,time\n"
    "t0=time.perf_counter()\n"
    "import jax\n"
    "ds=jax.devices()\n"
    "print(json.dumps({'init_s': round(time.perf_counter()-t0,2),"
    " 'platform': ds[0].platform, 'devices': len(ds),"
    " 'jax': jax.__version__}))\n")

# the compile-cache probe (the former tools/cache_probe.py, shrunk to
# doctor budget): odd shapes dodge unrelated cache hits; min compile
# time 0 so even this small program persists
_CACHE_PROBE = (
    "import json,time\n"
    "t0=time.perf_counter()\n"
    "import jax, jax.numpy as jnp\n"
    "def step(x, w):\n"
    "    def body(c, _):\n"
    "        return jnp.tanh(c @ w) + 0.03125 * c, ()\n"
    "    out, _ = jax.lax.scan(body, x, None, length=37)\n"
    "    return out.sum()\n"
    "x = jnp.ones((8, 131), jnp.float32)\n"
    "w = jnp.ones((131, 131), jnp.float32)\n"
    "t1 = time.perf_counter()\n"
    "jax.jit(step)(x, w).block_until_ready()\n"
    "print(json.dumps({'import_s': round(t1-t0,2),"
    " 'first_call_s': round(time.perf_counter()-t1,2)}))\n")


@dataclass
class CheckResult:
    name: str
    ok: bool
    verdict: str                  # the one-line operator explanation

    def line(self) -> str:
        mark = "ok  " if self.ok else "FAIL"
        return f"doctor: {self.name:14s} {mark}  {self.verdict}"


def _run_probe(code: str, env: dict, timeout_s: float) -> dict:
    """Run `code` in a fresh interpreter, parse its one JSON stdout
    line.  Raises on timeout/crash with the stderr tail attached."""
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout_s, env=env, cwd=repo_root())
    if proc.returncode != 0:
        raise RuntimeError(
            f"probe rc={proc.returncode}: {proc.stderr.strip()[-400:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def check_backend(probe=None) -> CheckResult:
    """Is the configured JAX backend actually reachable, and how long
    does a fresh process pay for it?  `probe` is injectable for tests
    (a callable returning the probe dict or raising)."""
    requested = os.environ.get("JAX_PLATFORMS", "")
    expects_device = bool(requested) and "cpu" not in requested.lower()
    try:
        t0 = time.perf_counter()
        info = (probe or (lambda: _run_probe(
            _BACKEND_PROBE, dict(os.environ), BACKEND_TIMEOUT_S)))()
        wall = time.perf_counter() - t0
    except subprocess.TimeoutExpired:
        return CheckResult(
            "backend", False,
            f"backend init did not answer within {BACKEND_TIMEOUT_S:.0f}s "
            f"(JAX_PLATFORMS={requested or 'unset'}) — unreachable device "
            "or hung backend")
    except Exception as exc:
        return CheckResult("backend", False, f"backend probe failed: {exc}")
    init_s = float(info.get("init_s", wall))
    platform = str(info.get("platform", "?"))
    detail = (f"platform={platform} devices={info.get('devices', '?')} "
              f"init={init_s:.1f}s (JAX_PLATFORMS={requested or 'unset'})")
    if expects_device and platform == "cpu":
        return CheckResult(
            "backend", False,
            f"{detail} — requested a device platform but init FELL BACK "
            "TO CPU: no reachable TPU.  Every 'device' number this chain "
            "takes would silently be a CPU number (the round-7 trap)")
    if init_s > FALLBACK_THRESHOLD_S:
        return CheckResult(
            "backend", False,
            f"{detail} — init slower than {FALLBACK_THRESHOLD_S:.0f}s: "
            "the no-reachable-backend fallback stall pattern")
    return CheckResult("backend", True, detail)


def check_aot_dir() -> CheckResult:
    from drand_tpu import aot
    d = aot.aot_dir()
    try:
        os.makedirs(d, exist_ok=True)
        probe = os.path.join(d, ".doctor_probe")
        with open(probe, "w") as f:
            f.write("ok")
        os.remove(probe)
    except OSError as exc:
        return CheckResult("aot-dir", False, f"{d} not writable: {exc}")
    entries = [fn for fn in os.listdir(d) if fn.endswith(".aotx")]
    note = "" if entries else " — EMPTY: expect cold compiles"
    return CheckResult("aot-dir", True,
                       f"{d} writable, {len(entries)} entries{note}")


def check_workdir(workdir: str) -> CheckResult:
    try:
        os.makedirs(workdir, exist_ok=True)
        probe = os.path.join(workdir, ".doctor_probe")
        with open(probe, "w") as f:
            f.write("ok")
        os.remove(probe)
    except OSError as exc:
        return CheckResult("workdir", False,
                           f"{workdir} not writable: {exc}")
    return CheckResult("workdir", True, f"{workdir} writable")


def check_fixtures() -> CheckResult:
    root = repo_root()
    missing = [rel for rel in ("bench.py", "__graft_entry__.py",
                               "drand_tpu/fixtures.py")
               if not os.path.exists(os.path.join(root, rel))]
    if missing:
        return CheckResult("fixtures", False,
                           f"missing from checkout: {missing}")
    return CheckResult("fixtures", True, "bench/entry/fixtures present")


def check_compile_cache(probe=None) -> CheckResult:
    """The folded cache_probe: does the persistent compilation cache
    survive across processes on this backend?  Two fresh subprocesses
    compile the same program; the cache dir must be populated after the
    first and the second's first-call must come in under the <60 s
    fresh-process bar."""
    from drand_tpu import aot
    cache_dir = aot.persistent_cache_dir()
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    run = probe or (lambda: _run_probe(_CACHE_PROBE, env,
                                       CACHE_PROBE_TIMEOUT_S))
    try:
        cold = run()
        n_files = sum(len(fs) for _, _, fs in os.walk(cache_dir)) \
            if os.path.isdir(cache_dir) else 0
        warm = run()
    except Exception as exc:
        return CheckResult("compile-cache", False, f"probe failed: {exc}")
    detail = (f"{cache_dir}: {n_files} files, cold first-call "
              f"{cold.get('first_call_s', '?')}s, warm "
              f"{warm.get('first_call_s', '?')}s")
    if n_files == 0:
        return CheckResult(
            "compile-cache", False,
            f"{detail} — nothing persisted: fresh processes will pay "
            "full compiles (cache dir misconfigured or backend refuses "
            "serialization)")
    if float(warm.get("first_call_s", 0.0)) >= 60.0:
        return CheckResult(
            "compile-cache", False,
            f"{detail} — warm reload missed the <60s fresh-process bar")
    return CheckResult("compile-cache", True, detail)


def run_doctor(workdir: str, fast: bool = False,
               backend_probe=None, cache_probe=None) -> list[CheckResult]:
    """All checks, in cheapest-first order (a broken workdir should
    fail before a 2-minute backend probe is paid)."""
    results = [
        check_workdir(workdir),
        check_aot_dir(),
        check_fixtures(),
        check_backend(probe=backend_probe),
    ]
    if not fast:
        results.append(check_compile_cache(probe=cache_probe))
    return results


def print_results(results: list[CheckResult], say=None) -> bool:
    say = say or (lambda m: print(m, file=sys.stderr, flush=True))
    for r in results:
        say(r.line())
    ok = all(r.ok for r in results)
    if not ok:
        say("doctor: preflight FAILED — fix the environment (or pass "
            "--no-doctor to proceed anyway, eyes open)")
    return ok


def cache_probe_main() -> int:
    """Back-compat entry for `python tools/cache_probe.py`: run just the
    compile-cache check and exit 0/1 on its verdict."""
    result = check_compile_cache()
    print(result.line(), file=sys.stderr, flush=True)
    return 0 if result.ok else 1

"""Always-on hygiene gate (SURVEY.md §5.2).

The reference runs `go vet`-grade checks and the race detector on every
CI run (`/root/reference/Makefile:47-48`); this repo's fuller analog is
`scripts/check.sh` (asyncio-debug suite + slow KATs), which is opt-in.
This test makes the cheap half ALWAYS-ON in the default suite:

  - every Python file in the package must at least compile, including
    modules no default test imports (CLI subcommands, relays, tools) —
    a syntax error in a rarely-driven corner fails `pytest -q`, not the
    next manual run;
  - the project linter (tools/lint: blocking-in-async, wall-clock,
    jit-tracing, unawaited-coroutine, secret-logging, bare-except)
    must report zero non-baselined findings over the whole tree.
"""

import pathlib
import py_compile
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


def test_package_compiles():
    failed = []
    for top in ("drand_tpu", "demo", "tools"):
        for path in sorted((REPO / top).rglob("*.py")):
            try:
                py_compile.compile(str(path), doraise=True)
            except py_compile.PyCompileError as e:
                failed.append(f"{path}: {e.msg}")
    for single in ("bench.py", "__graft_entry__.py"):
        try:
            py_compile.compile(str(REPO / single), doraise=True)
        except py_compile.PyCompileError as e:
            failed.append(f"{single}: {e.msg}")
    assert not failed, "\n".join(failed)


def test_lint_clean():
    """The AST lint gate: zero non-baselined findings over the package
    (the `golangci-lint run` + the static half of `go test -race` of
    every reference CI pass — the await-race and domain-flow analyzers
    run here as always-on gates, not opt-in tooling).  Budget <3 s warm:
    the two-pass engine reuses the `.lint_cache/` index sidecar, so only
    edited files re-parse.

    Debt is kept honest in both directions: a `# lint: disable=` comment
    that no longer suppresses anything is itself a finding
    (unused-suppression), and a baseline entry whose finding is gone is
    stale and fails here — the suppression surface can only shrink."""
    from tools.lint.baseline import DEFAULT_BASELINE, Baseline
    from tools.lint.cache import IndexCache
    from tools.lint.engine import LintEngine

    engine = LintEngine.from_paths(
        REPO, ["drand_tpu", "demo", "tools"],
        cache=IndexCache(REPO / ".lint_cache"))
    assert not engine.errors, "\n".join(engine.errors)
    run_rules = {r.name for r in engine.rules}
    assert {"await-race", "domain-flow"} <= run_rules, (
        "the concurrency/crypto-domain analyzers must stay in the "
        f"always-on gate (got: {sorted(run_rules)})")
    findings = engine.run()
    baseline = Baseline.load(DEFAULT_BASELINE)
    fresh, stale = baseline.filter(findings)
    msg = "\n".join(f.render() for f in fresh)
    assert not fresh, (
        f"lint findings (fix, or suppress with `# lint: disable=RULE` "
        f"plus a justification, or baseline in tools/lint/baseline.json):"
        f"\n{msg}")
    assert not stale, (
        "stale baseline entries (the finding is gone — delete them, or "
        "run `drand-tpu lint --update-baseline`): "
        + "; ".join(f"{e.path}::{e.rule}" for e in stale))
    unjustified = [e for e in baseline.entries
                   if not e.justification.strip()
                   or e.justification.startswith("TODO")]
    assert not unjustified, (
        "baseline entries without a real justification: "
        + "; ".join(f"{e.path}::{e.rule}" for e in unjustified))


def test_metrics_naming_conventions():
    """Every collector in the shared REGISTRY follows the project's
    naming contract (drand_tpu/metrics.py header): `drand_` prefix on
    everything, histograms are native-seconds (`_seconds` suffix), and
    point-in-time latency/duration gauges are milliseconds (`_ms`).
    Mixed units on a dashboard are how a 250 ms regression hides."""
    import drand_tpu.tracing  # noqa: F401 -- registers STAGE_DURATION feeds
    from drand_tpu import metrics as M

    bad = []
    names = set()
    for family in M.REGISTRY.collect():
        names.add(family.name)
        if not family.name.startswith("drand_"):
            bad.append(f"{family.name}: missing drand_ prefix")
        if family.type == "histogram" and not family.name.endswith("_seconds"):
            bad.append(f"{family.name}: histograms must end in _seconds")
        if family.type == "gauge" and \
                any(k in family.name for k in ("latency", "duration")) and \
                not family.name.endswith("_ms"):
            bad.append(f"{family.name}: duration gauges must end in _ms")
        if family.type == "gauge" and "ratio" in family.name and \
                not family.name.endswith("_ratio"):
            bad.append(f"{family.name}: ratio gauges must end in _ratio")
    assert not bad, "\n".join(bad)
    # the health/SLO surface (drand_tpu/health) registers through the
    # same registry and contract — a rename or a lost registration of a
    # judgment metric must fail loudly, not dim a dashboard
    for required in ("drand_beacon_lag_rounds",
                     "drand_round_lateness_seconds",
                     "drand_group_connectivity",
                     "drand_peer_partial_lag_rounds",
                     "drand_slo_attainment_ratio",
                     "drand_slo_error_budget_burn"):
        assert required in names, f"health metric {required} not registered"
    # the resilience surface (drand_tpu/resilience) registers through
    # the same registry: retries, breakers, hedges, and deadline sheds
    # are SLO inputs — losing one silently blinds the recovery story
    for required in ("drand_retry_attempts", "drand_breaker_state",
                     "drand_hedge_requests", "drand_deadline_shed"):
        assert required in names, \
            f"resilience metric {required} not registered"
    # the serving surface (resilience/admission + the bounded hot-path
    # queues): overload visibility is the contract the load harness and
    # the serve smoke assert over — a lost registration blinds both
    for required in ("drand_serve_inflight", "drand_serve_shed",
                     "drand_serve_latency_seconds",
                     "drand_queue_dropped"):
        assert required in names, \
            f"serve metric {required} not registered"
    # the encode-once serve fast lane (ISSUE 14): lane events and the
    # hot-path store-read counter are what the A/B and the serve smoke
    # counter-assert over — "zero store reads" is only provable while
    # these stay registered
    for required in ("drand_serve_cache", "drand_serve_store_reads"):
        assert required in names, \
            f"serve fast-lane metric {required} not registered"
    # the aggregation hot loop (beacon/crypto_backend + signer_table):
    # batch-size and table-epoch visibility is how a live-wiring
    # regression (fragmented batches, stale reshare table) surfaces
    for required in ("drand_aggregate_batch_size",
                     "drand_signer_table_epoch"):
        assert required in names, \
            f"aggregation metric {required} not registered"
    # the tile-residency accounting (ops/pallas_field TileForm.wrap/
    # unwrap, ISSUE 9): losing the counter blinds the layout-conversion
    # regression check bench.py reports per dispatch
    assert "drand_layout_conversions" in names, \
        "layout-conversion metric not registered"
    # AOT cache economics (drand_tpu/aot): compile-vs-load seconds and
    # hit/miss events of the CPU tier's serialized executables
    for required in ("drand_aot_compile_seconds", "drand_aot_load_seconds",
                     "drand_aot_cache"):
        assert required in names, \
            f"AOT metric {required} not registered"
    # the native tier (ISSUE 12): per-scheme single-verify latency and
    # the availability gauge are how a silent fallback to the ~175 ms
    # golden model (toolchain gone, build broken) surfaces on a dashboard
    for required in ("drand_native_verify_seconds", "drand_native_available"):
        assert required in names, \
            f"native-tier metric {required} not registered"
    # the batched sync wire + off-loop catch-up pipeline (ISSUE 13):
    # rounds-per-wire-shape and per-stage segment seconds are how a
    # silent fallback to the per-beacon wire (or a stage regression)
    # surfaces on a dashboard
    for required in ("drand_sync_rounds", "drand_sync_segment_seconds"):
        assert required in names, \
            f"sync wire metric {required} not registered"
    # crash-safe storage (ISSUE 15): the startup-scan verdict gauge and
    # the quarantine counter are the operator's first signal that a
    # node restarted over a damaged chain and is healing from peers
    for required in ("drand_store_integrity",
                     "drand_store_quarantined"):
        assert required in names, \
            f"storage recovery metric {required} not registered"
    # perf observability (ISSUE 17): the dispatch flight recorder and
    # the round-journey histogram are what /debug/dispatch,
    # /debug/journey read — a lost registration blinds the
    # padding-waste and hop-latency dashboards
    # (counters collect without their _total suffix)
    for required in ("drand_dispatch_seconds", "drand_dispatch_fill_ratio",
                     "drand_dispatch_padding_rounds",
                     "drand_round_journey_seconds"):
        assert required in names, \
            f"perf observability metric {required} not registered"
    # objectsync tier (ISSUE 18): published-segment counter and the
    # store-tip-vs-manifest lag gauge are how a stalled publisher (dead
    # backend, damaged local row) surfaces before clients notice stale
    # manifests
    for required in ("drand_objectsync_published",
                     "drand_objectsync_lag_rounds"):
        assert required in names, \
            f"objectsync metric {required} not registered"
    # fleet observatory (ISSUE 19): per-signer participation, threshold
    # margin, time-to-threshold, cross-node tip skew, and the fork
    # counter are the group-liveness dashboard — a lost registration
    # blinds the "which signer is dying" question the ledger exists to
    # answer (the fork counter collects without its _total suffix)
    for required in ("drand_signer_participation_ratio",
                     "drand_threshold_margin",
                     "drand_time_to_threshold_seconds",
                     "drand_fleet_tip_skew_rounds",
                     "drand_fleet_fork_detected"):
        assert required in names, \
            f"observatory metric {required} not registered"
    # ceremony observability (ISSUE 20): the state gauges plus the typed
    # per-phase duration/outcome pair the hardened phaser feeds — a lost
    # registration makes a timed-out ceremony phase indistinguishable
    # from a completed one on the dashboard (the outcome counter
    # collects without its _total suffix)
    for required in ("drand_dkg_state", "drand_reshare_state",
                     "drand_dkg_phase_seconds",
                     "drand_dkg_phase_outcomes"):
        assert required in names, \
            f"ceremony metric {required} not registered"


def test_check_script_present_and_executable():
    check = REPO / "scripts" / "check.sh"
    assert check.exists()
    assert check.stat().st_mode & 0o111, "scripts/check.sh must be executable"


def test_check_script_runs_only_what_exists():
    """Every `scripts/`, `tools/` and `tests/` path that check.sh hands
    to an interpreter, and every module it runs with `-m`, is in the
    tree: a stage whose script was deleted fails here, not on the next
    manual run of the check."""
    import re

    text = (REPO / "scripts" / "check.sh").read_text()
    commands = "\n".join(ln for ln in text.splitlines()
                         if not ln.lstrip().startswith("#"))
    paths = set(re.findall(
        r"(?<![\w./-])((?:scripts|tools|tests)/[\w./-]+)", commands))
    modules = set(re.findall(r"-m ((?:tools|drand_tpu)[\w.]*)", commands))
    assert len(paths) >= 10 and modules, (paths, modules)
    missing = sorted(p for p in paths if not (REPO / p).exists())
    for mod in sorted(modules):
        base = REPO.joinpath(*mod.split("."))
        if not (base.with_suffix(".py").exists()
                or (base / "__main__.py").exists()):
            missing.append(mod)
    assert not missing, f"scripts/check.sh runs what is not there: {missing}"


def test_readme_layout_names_only_what_exists():
    """Every file or directory the README's "Layout" section lists, in
    its name column or as a `scripts/...`-style path in a description,
    is in the tree (globs may match anything): deleting a module takes
    its entry with it."""
    import glob
    import re

    readme = (REPO / "README.md").read_text()
    block = readme.split("## Layout", 1)[1].split("```")[1]
    named, parent = [], ""
    for line in block.splitlines():
        head, text = line[:21], line[21:]
        for name in head.split():
            if head.startswith("  "):
                named.append(parent + name)
            else:
                named.append(name)
                parent = name if name.endswith("/") else parent
        named += re.findall(
            r"(?<![\w./-])((?:scripts|tools|tests|benchmark|aot)/[\w.*/-]+\w)",
            text)
    assert len(named) >= 30, named
    missing = [n for n in named if not glob.glob(str(REPO / n))]
    assert not missing, f"README Layout names what is not there: {missing}"


def test_every_benchmark_test_module_runs_with_this_suite():
    """The yardstick's own tests guard it only if they run: every
    `benchmark/tests/test_*.py` is star-imported by exactly one module
    of `tests/`, and no two test modules of the two directories share a
    basename (pytest imports them by basename here)."""
    import re

    theirs = sorted(p.stem for p in
                    (REPO / "benchmark" / "tests").glob("test_*.py"))
    assert len(theirs) >= 8
    shimmed = []
    for path in (REPO / "tests").glob("test_*.py"):
        shimmed += re.findall(
            r"^from benchmark\.tests\.(\w+) import \*", path.read_text(),
            re.M)
    assert sorted(shimmed) == theirs
    ours = {p.stem for p in (REPO / "tests").glob("test_*.py")}
    assert not ours & set(theirs)


def test_cli_commands_are_the_parsers_and_the_jax_free_ones_stay_so():
    """The CLI's sub-parsers are exactly what `main` can dispatch:
    `_COMMANDS` plus `lint`, which it runs synchronously.  One process
    for each chip: a command that starts children or reads files only
    must not bring a backend up, so `lint` stays out of `_NEEDS_JAX`,
    and the commands that verify stay in."""
    import argparse
    import importlib

    cli = importlib.import_module("drand_tpu.cli.main")
    subparsers = [a for a in cli.build_parser()._actions
                  if isinstance(a, argparse._SubParsersAction)]
    assert len(subparsers) == 1
    assert set(subparsers[0].choices) == set(cli._COMMANDS) | {"lint"}
    assert cli._NEEDS_JAX <= set(cli._COMMANDS)
    assert {"start", "sync", "get"} <= cli._NEEDS_JAX
    assert "lint" not in cli._NEEDS_JAX


# ROADMAP D7's command as a test: a PR that adds or drops an option has
# to say so here.  `DRAND_TPU_OBJECTSYNC_` is the prefix the publisher's
# settings are read under.
_OPTIONS = """
AGG_MAX_BATCH AOT_DIR AOT_WARM ASYNC_SANITIZE ASYNC_SANITIZE_THRESHOLD
BUCKETS COMPACT DEVICE_CRYPTO DKG_BATCH HOST_CRYPTO HOST_VERIFY_MAX
LINE_MERGE MILLER_MERGED NATIVE_LIB NO_NATIVE OBJECTSYNC_ OBJECTSYNC_DIR
OBJECTSYNC_SEGMENT SERVE_CACHE SERVE_CACHE_ROUNDS STARTUP_SCAN STORE_CODEC
STORE_SYNC SYNC_PIPELINE_DEPTH SYNC_WIRE_CHUNK
""".split()


def test_the_options_are_the_ones_listed():
    """`grep -rho --include='*.py' 'DRAND_TPU_[A-Z_0-9]*' drand_tpu |
    sort -u`: 25 names."""
    import re

    found = set()
    for path in (REPO / "drand_tpu").rglob("*.py"):
        found.update(re.findall(r"DRAND_TPU_[A-Z_0-9]*", path.read_text()))
    listed = {"DRAND_TPU_" + name for name in _OPTIONS}
    assert found == listed, (
        f"new: {sorted(found - listed)}, gone: {sorted(listed - found)}")


def test_chaos_failpoint_hygiene():
    """The failpoint contract (drand_tpu/chaos/failpoints.py):

      - every literal site name at a `failpoint(...)` / `failpoint_sync(...)`
        call is declared in the SITES registry (no orphan sites);
      - every declared site is instrumented somewhere in the package
        (the registry is the operator catalogue — a dead entry lies);
      - site names are passed as string literals (the registry check is
        static, so dynamic names would evade it);
      - fault injection is DISABLED by default: nothing armed at import,
        and no ambient DRAND_CHAOS leaks into test runs.
    """
    import ast

    used: dict[str, list[str]] = {}
    dynamic: list[str] = []
    for path in sorted((REPO / "drand_tpu").rglob("*.py")):
        rel = path.relative_to(REPO).as_posix()
        if "protogen" in rel or "__pycache__" in rel:
            continue
        tree = ast.parse(path.read_text(), filename=rel)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            name = fn.attr if isinstance(fn, ast.Attribute) else \
                getattr(fn, "id", "")
            if name not in ("failpoint", "failpoint_sync"):
                continue
            if not node.args or not isinstance(node.args[0], ast.Constant) \
                    or not isinstance(node.args[0].value, str):
                dynamic.append(f"{rel}:{node.lineno}")
                continue
            used.setdefault(node.args[0].value, []).append(
                f"{rel}:{node.lineno}")

    from drand_tpu.chaos import failpoints
    # module-internal plumbing (fire/fire_sync) is not a call site
    used = {k: v for k, v in used.items()
            if not all(p.startswith("drand_tpu/chaos/") for p in v)}
    assert not dynamic, f"non-literal failpoint site names: {dynamic}"
    unknown = set(used) - set(failpoints.SITES)
    assert not unknown, (
        f"failpoint sites used but not declared in SITES: "
        f"{ {k: used[k] for k in unknown} }")
    dead = set(failpoints.SITES) - set(used)
    assert not dead, f"SITES entries never instrumented: {sorted(dead)}"

    assert not failpoints.is_armed(), (
        "chaos schedule armed outside a chaos run — a leaked arm() or an "
        "ambient DRAND_CHAOS")
    import os
    assert not os.environ.get("DRAND_CHAOS"), (
        "DRAND_CHAOS set in the test environment: tier-1 must run with "
        "fault injection disabled")

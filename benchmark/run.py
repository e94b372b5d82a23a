"""The benchmark's one command:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one import of JAX, no child.  A cell of `BENCHMARK.json`
names a configuration (`configs/<name>.json`) and a traffic mix
(`traffic/<name>.json`, which names its driver `drivers/<kind>.py`);
per-layer metrics are `layer_metrics/<name>.json`.  Nothing below knows
a cell, a configuration, a mix or a metric by name: a later PR adds one
with new files and one entry of `BENCHMARK.json`.

Every line of standard output is one JSON object.  All but the last are
observations of this run; the last is the result the driver reads
(`correct`, `attempted`, `failed`, `metrics`, `device`, with `--trace 1`
`breakdown`, and last `checks`: every number the output check compared,
beside its limit, which are also the last lines of standard error).  Off
the TPU, with another number of chips than the cell asks for, or in a
directory without the program, the run prints no result and exits with
code 2.

`--rehearse stub|host` is for the sandbox: JAX on the CPU, the first
`rehearse_rounds` of the chain, and in the program's place a verifier
that says yes to everything (`stub`: the run `correct` has to fail) or
the program's host tier row by row (`host`).  Its last line names
platform `cpu`; no number of it is a device metric.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()      # process start, as near as Python sees

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import importlib  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# run as a script, sys.path[0] is benchmark/ itself, whose file names
# (trace_reduce, tests, ...) are no business of `import`
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import harness as H  # noqa: E402
from benchmark import trace_reduce  # noqa: E402

REFUSED = 2         # exit code of a run that prints no result


class Refused(Exception):
    """The run may not print a result at all (no chip, no program)."""


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise Refused(f"BENCHMARK.json has no {what} {name!r}")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class _Events:
    """JAX's own counts: persistent-cache requests, hits and misses, and
    backend compilations (each one an event with a duration)."""

    NAMES = {"/jax/compilation_cache/cache_hits": "cache_hits",
             "/jax/compilation_cache/cache_misses": "cache_misses",
             "/jax/compilation_cache/compile_requests_use_cache":
             "cache_requests"}
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.counts = {v: 0 for v in self.NAMES.values()}
        self.counts["backend_compiles"] = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event in self.NAMES:
            self.counts[self.NAMES[event]] += 1

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == self.COMPILE:
            self.counts["backend_compiles"] += 1


class _FullCollections:
    """Seconds spent in full (generation 2) collections of Python's
    garbage collector: after a program build the heap holds the traced
    kernels, and one such pass stops every Python thread for seconds."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self._began = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._began = time.perf_counter()
        else:
            self.count += 1
            self.seconds += time.perf_counter() - self._began


def _versions() -> dict:
    from importlib import metadata

    import jax
    import jaxlib
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu, "python": sys.version.split()[0],
            "numpy": np.__version__}


def _cache_entries(d: str) -> int:
    return sum(len(fs) for _, _, fs in os.walk(d))


def _filesystem(path: str) -> str:
    """Type of the filesystem that holds `path`, from /proc/mounts."""
    best, kind = "", "unknown"
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _dev, mount, fstype = line.split()[:3]
                if (path == mount or path.startswith(
                        mount.rstrip("/") + "/")) and len(mount) > len(best):
                    best, kind = mount, fstype
    except OSError:
        pass
    return kind


class Run:
    """One run of one cell, in the phases `main` drives: `prepare` (all
    of set-up, to the end of the warm-up), `window`, `check`, `result`."""

    def __init__(self, workload: str, rehearse: str | None = None,
                 verifier: str = "program", bench_file: str | None = None):
        with open(bench_file or os.path.join(ROOT, "BENCHMARK.json")) as f:
            self.bench = json.load(f)
        self.cell = find(self.bench["workloads"], workload, "workload")
        entry = find(self.bench["configs"], self.cell["config"], "config")
        with open(os.path.join(ROOT, entry["file"])) as f:
            self.config = json.load(f)
        self.traffic = H.load_json("traffic", self.cell["traffic"] + ".json")
        self.rehearse = rehearse
        self.verifier_kind = rehearse or verifier
        self.spans = H.Spans()
        self.build: dict | None = None
        self.records: list[dict] = []
        self.trace: dict | None = None
        self._traced_op = None
        self.checks: list[dict] = []
        self.device: dict = {}
        self.setup_s = 0.0
        self.elapsed = 0.0
        self.compiles_in_window = 0
        self.workdir = ""

    # -- set-up ---------------------------------------------------------------

    def _place_environment(self) -> None:
        """The configuration's operator settings, before `drand_tpu` is
        imported (its modules read them at import)."""
        for key, value in self.config["env"].items():
            os.environ[key] = str(value)
        if self.rehearse:
            os.environ["JAX_PLATFORMS"] = "cpu"
            for key, value in self.traffic.get("rehearse_env", {}).items():
                os.environ[key] = str(value)
        # the program takes its cache directory from this variable
        # (`aot.persistent_cache_dir`).  The benchmark gives it one inside
        # the checkout, whatever the machine says, and lifts any cap on
        # its size: under a cap smaller than two programs, two cells'
        # runs would evict each other's program and every run would
        # compile
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            ROOT, ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
        # the stand's two nodes talk over the loopback: a proxy that the
        # machine's environment names is not to stand between them
        for key in ("no_proxy", "NO_PROXY"):
            os.environ[key] = ",".join(
                x for x in (os.environ.get(key), "127.0.0.1", "localhost")
                if x)

    def _check_device(self):
        import jax
        for attempt in range(3):
            # a chip that the last run's process is still letting go of
            # is worth a second look; no accelerator at all is no result
            try:
                devs = jax.devices()
                break
            except RuntimeError as exc:
                print(f"benchmark: JAX found no backend ({attempt + 1}/3): "
                      f"{str(exc)[:300]}", file=sys.stderr)
                if attempt == 2:
                    raise Refused("JAX could not initialize a backend: "
                                  f"{str(exc)[:300]}") from exc
                time.sleep(15)
        dev = devs[0]
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(devs)}
        if self.rehearse:
            return dev
        if dev.platform != "tpu":
            raise Refused(f"platform is {dev.platform!r}, not 'tpu': the "
                          "benchmark measures the TPU path and has no CPU "
                          "fallback (the sandbox has --rehearse)")
        if len(devs) != self.cell["chips"]:
            # ChainVerifier shards over every visible device by itself
            raise Refused(f"the cell asks for {self.cell['chips']} chips, "
                          f"JAX has {len(devs)}")
        H.peaks_for(dev.device_kind)
        return dev

    def _load_fixture(self) -> np.ndarray:
        fx = self.config["fixture"]
        path = os.path.join(HERE, "fixtures", fx["file"])
        with open(path, "rb") as f:
            raw = f.read()
        digest = hashlib.sha256(raw).hexdigest()
        if digest != fx["sha256"]:
            raise H.BenchFailure(f"fixture {fx['file']} has sha256 {digest}")
        sigs = np.load(io.BytesIO(raw))
        backlog = self.config["backlog_rounds"]
        if self.rehearse:
            backlog = self.traffic["rehearse_rounds"]
        if sigs.shape[0] < backlog \
                or sigs.shape[1] != self.config["signature_bytes"]:
            raise H.BenchFailure(f"fixture holds {sigs.shape}")
        return np.ascontiguousarray(sigs[:backlog])

    def _make_verifier(self):
        """The program's ChainVerifier under the configuration's key, and
        what the traffic is verified by: the same, its one program built
        here, or in its place the stub or the rehearsal's host tier."""
        from drand_tpu.chain.scheme import scheme_by_id
        from drand_tpu.chain.verify import ChainVerifier
        scheme = scheme_by_id(self.config["scheme_id"])
        cv = ChainVerifier(scheme,
                           bytes.fromhex(self.config["public_key_hex"]))
        if self.verifier_kind == "stub":
            return cv, H.StubVerifier(self.config["scheme_id"])
        if self.verifier_kind == "host":
            return cv, H.HostVerifier(cv)
        import drand_tpu.verify as V
        from drand_tpu.ops.pallas_field import use_pallas
        if not use_pallas():
            raise H.BenchFailure("use_pallas() is False on a TPU")
        bucket = self.config["bucket_rounds"]
        if tuple(V._BUCKETS) != (bucket,):
            raise H.BenchFailure(
                f"the program's buckets are {V._BUCKETS}, the configuration "
                f"states one of {bucket}")
        rec = cv._verifier.build(bucket)
        rec["tpu_custom_calls"] = rec.pop("lowered").as_text().count(
            "tpu_custom_call")
        self.build = rec
        H.emit(program=rec)
        if rec["tpu_custom_calls"] <= 0:
            raise H.BenchFailure(
                f"program {rec['program']} holds no tpu_custom_call: it is "
                "the pure-XLA graph, not the kernel path")
        return cv, cv

    async def prepare(self) -> None:
        self._place_environment()
        try:
            import jax  # noqa: F401

            from drand_tpu import aot
        except ImportError as exc:
            raise Refused(f"the program is not here: {exc}") from exc
        dev = self._check_device()
        self._dev = dev
        self.events = _Events()
        self.cache_dir = aot.enable_persistent_cache()
        self.cache_before = _cache_entries(self.cache_dir)
        self.sigs = self._load_fixture()
        self.chain_verifier, verifier = self._make_verifier()
        self.prevs = H.previous_sigs(self.config, self.sigs)
        self.group = H.group_of(self.config)
        self.workdir = tempfile.mkdtemp(prefix="drand-bench-")
        self.ctx = H.Ctx(config=self.config, traffic=self.traffic,
                         sigs=self.sigs, prevs=self.prevs,
                         group=self.group, spans=self.spans,
                         verifier=H.SpanVerifier(verifier, self.spans),
                         workdir=self.workdir)
        module = importlib.import_module(
            f"benchmark.drivers.{self.traffic['driver']}")
        self.driver = module.Driver(self.ctx)
        await self.driver.setup()
        await self.driver.warmup()
        # the one full collection that the first allocations after a build
        # would set off at some point inside the window (PERF.md, PR 24:
        # 5.5 s in which no Python thread ran) is made here, in set-up
        t0 = time.perf_counter()
        gc.collect()
        H.emit(full_collection_in_setup_s=time.perf_counter() - t0)
        self.collections = _FullCollections()

    # -- the measured window --------------------------------------------------

    async def _traced(self, operate):
        """One operation under the profiler, bracketed by two marks that
        put the harness's clock and the trace's on one axis."""
        from jax import profiler
        logdir = os.path.join(self.workdir, "trace")
        opts = profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        profiler.start_trace(logdir, profiler_options=opts)
        try:
            with profiler.TraceAnnotation(trace_reduce.MARK_BEGIN):
                t0 = time.perf_counter()
            first_span = len(self.spans.rows)
            rec = await operate()
            with profiler.TraceAnnotation(trace_reduce.MARK_END):
                t1 = time.perf_counter()
        finally:
            profiler.stop_trace()
        self._traced_op = (logdir, (t0, t1), self.spans.rows[first_span:],
                           rec["rounds"])
        return rec

    def _reduce_trace(self) -> None:
        """After the window: the traced operation's file to numbers."""
        logdir, window_pc, spans, rounds = self._traced_op
        try:
            self.trace = trace_reduce.reduce_trace(logdir, window_pc, spans)
        except ValueError as exc:
            if not self.rehearse:
                raise
            # the CPU has no device plane: the rehearsal reports no
            # device number, and goes on
            H.emit(trace_not_reduced=str(exc)[:400])
            return
        # the trace stays where it is for the readers that `result` asks
        # (`readers/device_scopes.py`); `close` removes the work directory
        self.trace["rounds"] = rounds
        H.emit(trace={k: v for k, v in self.trace.items()
                      if k not in ("device_ops", "idle_gaps")})

    async def window(self, seconds: float, trace: bool) -> None:
        compiles = self.events.counts["backend_compiles"]
        t0 = time.perf_counter()
        self.setup_s = t0 - T_START
        while True:
            t_op = time.perf_counter()
            try:
                if trace and self._traced_op is None:
                    rec = await self._traced(self.driver.operate)
                else:
                    rec = await self.driver.operate()
            except Exception as exc:  # an operation that raises has failed
                traceback.print_exc()
                rec = {"ok": False, "rounds": 0,
                       "wall_s": time.perf_counter() - t_op,
                       "error": f"{type(exc).__name__}: {exc}"[:400]}
            self.records.append(rec)
            self.elapsed = time.perf_counter() - t0
            # a driver may ask for further operations once the seconds
            # have run out, where its window is too thin to carry its
            # metrics (`wants_more`); a traced run reports none of those
            more = getattr(self.driver, "wants_more", None)
            if self.elapsed >= seconds and (
                    trace or more is None or not more(self.records)):
                break
        self.compiles_in_window = \
            self.events.counts["backend_compiles"] - compiles
        if self._traced_op is not None:
            self._reduce_trace()

    # -- the output check -----------------------------------------------------

    def _compare(self, name: str, value, limit=0) -> None:
        """One number compared, beside its limit (every comparison here is
        exact: a count that has to be `limit`)."""
        self.checks.append({"name": name, "value": value, "limit": limit,
                            "ok": bool(value == limit)})

    def _verdict_checks(self, draw: dict) -> None:
        """The sampled and the faulted rounds, judged three times: by what
        the traffic is verified by (the device program), by the program's
        host tier, and by the benchmark's plain reference.  Under a
        chained scheme every row carries its `previous_sig`, and each
        fault is judged in both fields: as planted, and the same bit in
        the row's other field."""
        sample, faults = draw["sample"], list(draw["faults"])
        if self.prevs is not None:
            faults += [(r, byte + self.sigs.shape[1], bit)
                       for r, byte, bit in draw["faults"]]
        at = np.array(sample) - 1
        rounds, sigs = list(sample), [self.sigs[at]]
        prevs = None if self.prevs is None else [self.prevs[i] for i in at]
        for r, byte, bit in faults:     # each into a copy of its own row
            bad, bad_prevs = H.plant(
                self.sigs[r - 1:r], [(1, byte, bit)],
                self.prevs and self.prevs[r - 1:r])
            rounds.append(r)
            sigs.append(bad)
            if prevs is not None:
                prevs += bad_prevs
        batch = np.concatenate(sigs)
        want = np.array([True] * len(sample) + [False] * len(faults))
        beacons = H.beacons_of(batch, prevs, rounds)
        served = np.asarray(self.ctx.verifier.verify_beacons(beacons))
        host = np.array([self.chain_verifier.verify_beacon(b)
                         for b in beacons])
        ref = H.reference_verdicts(self.config, rounds, batch, prevs)
        self._compare("verdicts.reference_differs_from_construction",
                      int((ref != want).sum()))
        self._compare("verdicts.served_differs_from_reference",
                      int((served != ref).sum()))
        self._compare("verdicts.host_tier_differs_from_reference",
                      int((host != ref).sum()))

    async def check(self, seed: int) -> bool:
        """The output check for one seed, after the window has closed;
        true if every comparison held.  (A second call, with another
        seed, checks the same window's operations again.)"""
        self.checks = []
        cfg = self.traffic["check"]
        draw = H.draw_check(seed, len(self.sigs),
                            self.driver.segment_starts(),
                            self.traffic["ramp_rounds"],
                            cfg["samples"], cfg["faults"])
        H.emit(check_draw={"seed": seed, "kind": draw["kind"],
                           "kind_round": draw["kind_round"],
                           "faults": draw["faults"],
                           "samples": len(draw["sample"])})
        failed_ops = sum(1 for r in self.records if not r["ok"])
        self._compare("window.operations_failed", failed_ops)
        self._compare("window.compilations", self.compiles_in_window)
        for name, value in (await self.driver.check_window(
                self.records)).items():
            self._compare(name, value)
        for name, value in (await self.driver.check_faulted(draw)).items():
            self._compare(name, value)
        self._verdict_checks(draw)
        H.emit(compared=self.checks)
        return all(c["ok"] for c in self.checks)

    # -- the result -----------------------------------------------------------

    def _layer_value(self, spec: dict):
        """A per-layer metric from its file's description; None where
        there is nothing to read."""
        kind = spec["kind"]
        if kind == "build":
            if self.build is None:
                return None
            return float(sum(self.build[k] for k in spec["keys"]))
        if kind == "reader":
            reader = importlib.import_module(
                f"benchmark.readers.{spec['reader']}")
            return reader.read(self, spec)
        ops = [r for r in self.records if r["ok"]]
        per = spec["per_rounds"]
        if kind == "stats":
            vals = [r["stats"][spec["key"]] * per / r["rounds"]
                    for r in ops if spec["key"] in r.get("stats", {})]
        elif kind == "span":
            vals = []
            for r in ops:
                s = sum(r["spans"].get(n, 0.0) for n in spec["spans"])
                if spec.get("wall_less"):
                    s = r["wall_s"] - s
                vals.append(s * per / r["rounds"])
        else:
            raise H.BenchFailure(f"unknown layer metric kind {kind!r}")
        return float(np.median(vals)) if vals else None

    def result(self, correct: bool, trace: bool) -> dict:
        cell = self.cell["name"]
        metrics = {}
        if trace:
            for m in self.bench["per_layer"]:
                if not applies(m, cell):
                    continue
                value = self._layer_value(
                    H.load_json("layer_metrics", m["name"] + ".json"))
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            values = {"setup_s": self.setup_s}
            values.update(self.driver.end_to_end(self.records, self.elapsed))
            for m in self.bench["end_to_end"]:
                if applies(m, cell):
                    if m["name"] not in values:
                        raise H.BenchFailure(
                            f"the run has no value for {m['name']}")
                    metrics[m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
        stats = self._dev.memory_stats() or {}
        device = dict(self.device,
                      memory_peak_bytes=int(stats.get("peak_bytes_in_use",
                                                      0)))
        out = {"correct": bool(correct), "attempted": len(self.records),
               "failed": sum(1 for r in self.records if not r["ok"]),
               "metrics": metrics, "device": device,
               "not_held": [c for c in self.checks if not c["ok"]]}
        if trace and self.trace:
            device["busy_s"] = self.trace["busy_s"]
            device["window_s"] = self.trace["window_s"]
            out["breakdown"] = {"device_ops": self.trace["device_ops"],
                                "idle_gaps": self.trace["idle_gaps"]}
        # last in the line: every number compared, beside its limit
        out["checks"] = {c["name"]: [c["value"], c["limit"]]
                         for c in self.checks}
        return out

    def observe(self) -> None:
        """The earlier line that says under what the numbers were taken."""
        H.emit(run={
            "cell": self.cell["name"], "verifier": self.verifier_kind,
            "versions": _versions(),
            "compile_cache": {
                "dir": self.cache_dir,
                "entries_before": self.cache_before,
                "entries_after": _cache_entries(self.cache_dir),
                "jax_events": self.events.counts},
            "consumer_store_filesystem": _filesystem(self.workdir),
            "host_memory_peak_bytes": 1024 * resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss,
            "compilations inside the window": self.compiles_in_window,
            "full collections since set-up": {
                "count": self.collections.count,
                "seconds": self.collections.seconds},
            "operations": [{k: r[k] for k in ("ok", "rounds", "wall_s",
                                              "error") if k in r}
                           for r in self.records],
            "window_s": self.elapsed})

    async def close(self) -> None:
        driver = getattr(self, "driver", None)
        if driver is not None:
            await driver.close()
        if self.workdir:
            shutil.rmtree(self.workdir, ignore_errors=True)


async def run(args) -> dict:
    r = Run(args.workload, rehearse=args.rehearse,
            bench_file=args.bench_file)
    try:
        await r.prepare()
        await r.window(args.seconds, bool(args.trace))
        correct = await r.check(args.seed)
        r.observe()
        return r.result(correct, bool(args.trace))
    finally:
        await r.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", choices=("stub", "host"), default=None,
                    help="sandbox only: CPU, a short chain, and this "
                         "verifier in the program's place")
    ap.add_argument("--bench-file", default=None,
                    help="another BENCHMARK.json (the rehearsal of cells "
                         "that are not in the accepted one yet)")
    args = ap.parse_args(argv)
    try:
        out = asyncio.run(run(args))
    except Refused as exc:
        print(f"benchmark: no result: {exc}", file=sys.stderr)
        return REFUSED
    except Exception as exc:  # the last line must say why
        traceback.print_exc()
        H.emit(correct=False, attempted=0, failed=0, metrics={}, device={},
               reason=f"{type(exc).__name__}: {exc}"[:2000])
        return 1
    H.emit(**out)
    # the last lines of standard error: each number compared, its limit
    print("benchmark: " + ("correct" if out["correct"] else "NOT CORRECT"),
          file=sys.stderr)
    for name, (value, limit) in out["checks"].items():
        print(f"benchmark: compared {name} {value} limit {limit}"
              + ("" if value == limit else "  NOT HELD"), file=sys.stderr)
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())

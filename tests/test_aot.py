"""AOT executable cache (drand_tpu/aot.py): serialize/deserialize round
trip, cache keying, and miss behavior.

The real payloads (the full verify program, the sharded dryrun step) cost
hours of XLA compile on this 1-core host, so these tests exercise the
mechanism with a small program; `scripts/warm_artifacts.sh` proves the
production entries end-to-end (fresh-process load + run).
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from drand_tpu import aot


def _fn(x, w):
    return jnp.tanh(x @ w).sum()


def _sharded_args():
    # Deserialized executables require inputs explicitly placed with the
    # shardings they were compiled for (a plain uncommitted array is not
    # accepted on a multi-device host) — mirror the production pattern:
    # compile with explicit shardings, device_put the inputs.
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()), ("d",))
    shard = NamedSharding(mesh, P("d", None))
    n = len(jax.devices())
    x = jax.device_put(np.ones((4 * n, 8), np.float32), shard)
    w = jax.device_put(np.ones((8, 8), np.float32),
                       NamedSharding(mesh, P()))
    return (shard, NamedSharding(mesh, P())), (x, w)


def test_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("DRAND_TPU_AOT_DIR", str(tmp_path))
    in_shardings, (x, w) = _sharded_args()
    compiled = aot.compile_and_save("t-roundtrip", _fn, x, w,
                                    in_shardings=in_shardings)
    expect = float(compiled(x, w))

    loaded = aot.load("t-roundtrip")
    assert loaded is not None, "fresh load must hit"
    assert float(loaded(x, w)) == pytest.approx(expect)


def test_miss_returns_none(tmp_path, monkeypatch):
    monkeypatch.setenv("DRAND_TPU_AOT_DIR", str(tmp_path))
    assert aot.load("never-warmed") is None


def test_key_distinguishes_names(tmp_path, monkeypatch):
    monkeypatch.setenv("DRAND_TPU_AOT_DIR", str(tmp_path))
    x = jnp.ones((2, 2), jnp.float32)
    aot.compile_and_save("name-a", _fn, x, x)
    assert aot.load("name-a") is not None
    assert aot.load("name-b") is None


def test_save_prunes_superseded_entries(tmp_path, monkeypatch):
    monkeypatch.setenv("DRAND_TPU_AOT_DIR", str(tmp_path))
    x = jnp.ones((2, 2), jnp.float32)
    aot.compile_and_save("prune-me", _fn, x, x)
    # Simulate a stale entry from an older code hash for the same name.
    stale = tmp_path / "prune-me-0123456789abcdef0123.aotx"
    stale.write_bytes(b"old")
    other = tmp_path / "other-name-0123456789abcdef0123.aotx"
    other.write_bytes(b"unrelated")
    aot.compile_and_save("prune-me", _fn, x, x)
    names = sorted(p.name for p in tmp_path.glob("*.aotx"))
    assert stale.name not in names, "superseded entry must be pruned"
    assert other.name in names, "other names must be untouched"
    assert any(n.startswith("prune-me-") for n in names)


def test_corrupt_entry_is_a_miss(tmp_path, monkeypatch):
    monkeypatch.setenv("DRAND_TPU_AOT_DIR", str(tmp_path))
    x = jnp.ones((2, 2), jnp.float32)
    aot.compile_and_save("corrupt-me", _fn, x, x)
    path = aot.cache_path("corrupt-me")
    with open(path, "wb") as f:
        f.write(b"not a pickle")
    assert aot.load("corrupt-me") is None


def test_code_hash_pins_kernel_sources(tmp_path):
    # The key must cover every module that shapes the compiled graph so a
    # kernel edit can never serve a stale executable.
    h1 = aot.code_hash()
    assert isinstance(h1, str) and len(h1) == 16
    assert aot.code_hash() == h1  # stable within a process

    # Every graph-shaping module must be in the hashed set...
    hashed = {os.path.basename(p) for p in aot._hashed_files()}
    for required in ("field.py", "flat12.py", "h2c.py", "pairing.py",
                     "curve.py", "bls.py", "sha256.py", "pallas_field.py",
                     "towers.py", "verify.py", "fixtures.py"):
        assert required in hashed, f"{required} missing from AOT code hash"
    # ...but NOT the driver entry file: its edits must not invalidate the
    # multi-hour bench executables.  Entries whose graph lives there key
    # themselves via entry_code_hash() passed as cache_path's `extra`.
    assert "__graft_entry__.py" not in hashed
    eh = aot.entry_code_hash()
    assert isinstance(eh, str) and len(eh) == 8
    assert aot.cache_path("x", extra=eh) != aot.cache_path("x")

    # ...and an edit must change the hash (exercised on a scratch file so
    # the repo stays untouched).
    f = tmp_path / "kernel.py"
    f.write_text("A = 1\n")
    before = aot._hash_files([str(f)])
    f.write_text("A = 2\n")
    assert aot._hash_files([str(f)]) != before


def _counter_value(counter, *labels) -> float:
    return counter.labels(*labels)._value.get()


def test_cache_metrics_hit_miss_compile(tmp_path, monkeypatch):
    """drand_aot_cache_total events and the compile/load second gauges
    (ISSUE 8 satellite): every path through load()/compile_and_save()
    is accounted, so a warm chain can see compile-vs-load economics in
    exposition instead of grepping stderr."""
    from drand_tpu import metrics as M
    monkeypatch.setenv("DRAND_TPU_AOT_DIR", str(tmp_path))
    x = jnp.ones((2, 2), jnp.float32)

    miss0 = _counter_value(M.AOT_CACHE, "t-metrics", "miss")
    assert aot.load("t-metrics") is None
    assert _counter_value(M.AOT_CACHE, "t-metrics", "miss") == miss0 + 1

    compile0 = _counter_value(M.AOT_CACHE, "t-metrics", "compile")
    aot.compile_and_save("t-metrics", _fn, x, x)
    assert _counter_value(M.AOT_CACHE, "t-metrics", "compile") \
        == compile0 + 1
    assert M.AOT_COMPILE_SECONDS.labels("t-metrics")._value.get() > 0

    hit0 = _counter_value(M.AOT_CACHE, "t-metrics", "hit")
    assert aot.load("t-metrics") is not None
    assert _counter_value(M.AOT_CACHE, "t-metrics", "hit") == hit0 + 1
    assert M.AOT_LOAD_SECONDS.labels("t-metrics")._value.get() > 0

    err0 = _counter_value(M.AOT_CACHE, "t-metrics", "load_error")
    with open(aot.cache_path("t-metrics"), "wb") as f:
        f.write(b"garbage")
    assert aot.load("t-metrics") is None
    assert _counter_value(M.AOT_CACHE, "t-metrics", "load_error") \
        == err0 + 1


def test_enable_persistent_cache_follows_env(tmp_path, monkeypatch):
    """One function decides the directory: JAX_COMPILATION_CACHE_DIR
    where set, else the fixed git-ignored `.jax_cache` in the checkout
    (never /tmp, a pid or a time); and it is enabled on this backend."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "cache"))
        d = aot.enable_persistent_cache()
        assert d == str(tmp_path / "cache") == aot.persistent_cache_dir()
        assert jax.config.jax_compilation_cache_dir == d
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert aot.persistent_cache_dir() == os.path.join(repo, ".jax_cache")
        assert aot.enable_persistent_cache() == \
            jax.config.jax_compilation_cache_dir == \
            os.path.join(repo, ".jax_cache")
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        # restore the suite-wide cache dir (tests/conftest.py)
        jax.config.update("jax_compilation_cache_dir", before)


_PROBE = """
import json, sys, time
t0 = time.perf_counter()
import jax, jax.numpy as jnp
def step(x, w):
    def body(c, _):
        return jnp.tanh(c @ w) + 0.03125 * c, ()
    out, _ = jax.lax.scan(body, x, None, length=41)
    return out.sum()
x = jnp.ones((8, 139), jnp.float32)   # odd shapes: no unrelated hits
w = jnp.ones((139, 139), jnp.float32)
t1 = time.perf_counter()
jax.jit(step)(x, w).block_until_ready()
print(json.dumps({"first_call_s": time.perf_counter() - t1}))
"""


def test_persistent_cache_fresh_process_reloads_under_60s(tmp_path):
    """The ISSUE-8 probe pin: with the persistent compilation cache
    wired, a FRESH process's first call must come in far under the
    <60 s fresh-process bar on the XLA:CPU tier (VERDICT weak #7 — the
    TPU tier is covered by the aot.py serialized executables instead).
    Two real subprocesses: the first populates the cache, the second
    must find it populated and reload within the bar."""
    import json as _json
    import subprocess
    import sys as _sys
    cache = tmp_path / "cache"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"

    def run_once():
        proc = subprocess.run([_sys.executable, "-c", _PROBE],
                              capture_output=True, text=True, env=env,
                              timeout=300)
        assert proc.returncode == 0, proc.stderr[-800:]
        return _json.loads(proc.stdout.strip().splitlines()[-1])

    run_once()
    files = sum(len(fs) for _, _, fs in os.walk(cache))
    assert files > 0, "persistent cache not populated by a fresh process"
    warm = run_once()
    assert warm["first_call_s"] < 60.0, (
        f"fresh-process reload {warm['first_call_s']:.1f}s misses the "
        "<60s bar")
    assert sum(len(fs) for _, _, fs in os.walk(cache)) == files, (
        "second process recompiled instead of reloading")


def test_cpu_aot_mismatch_classifier():
    """cpu_aot_loader 'feature mismatch' lines: XLA tuning preferences
    (+prefer-no-gather/scatter) are NOT instructions and must classify as
    benign (suppressed with a note), while real ISA mismatches stay loud
    and (in warm runs) force a recompile.  The raw XLA message carries a
    double space ('is not  supported') — the classifier must survive it."""
    from drand_tpu import aot
    benign_line = ("E0802 cpu_aot_loader.cc:210] Loading XLA:CPU AOT "
                   "result. Target machine feature +prefer-no-gather is "
                   "not  supported on the host machine. This could lead "
                   "to execution errors such as SIGILL.")
    real_line = ("E0802 cpu_aot_loader.cc:210] Loading XLA:CPU AOT "
                 "result. Target machine feature +avx512f is not  "
                 "supported on the host machine. This could lead to "
                 "execution errors such as SIGILL.")
    real, benign = aot._classify_mismatch(benign_line + "\n" + real_line)
    assert benign == [benign_line]
    assert real == [real_line]
    assert aot._classify_mismatch("no mismatches here") == ([], [])

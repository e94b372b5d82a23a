"""A started process loads its verify program (ISSUE 32): `Verifier.build`
takes the program's exported form (`jax.export`) from one file beside
JAX's cache, keyed by the sources, and traces only where no usable file
is there; either way the executable is compiled from that form.

The program here is a small stand-in under two of the program's scopes,
built by `Verifier.build` itself (the real program takes the same path
at 64 rows in `test_chained_program.py`).  The cache directory is the
test's own.
"""

import hashlib
import json
import os
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import drand_tpu.verify as V
from drand_tpu import aot, ops, tracing
from drand_tpu.crypto.bls12381 import curve as GC
from drand_tpu.crypto.bls12381.constants import P
from drand_tpu.ops import pallas_field as PFm

N = 8
# what `benchmark/run.py:283-288`, `layer_metrics/build.*.json` and
# `chip_smoke.py:_build` read of the record
READ_BY_THE_BENCHMARK = {"program", "bucket", "tracing", "trace_s",
                         "lower_s", "compile_s", "lowered"}
NEW_IN_THE_RECORD = {"source", "load_s", "blob_bytes",
                     "miller_lines"}        # ISSUE 37: which Miller loop


class StandIn(V.Verifier):
    """`Verifier` with a body of a dozen operations in the place of the
    pairing: the verdict is a parity of bytes, under two scopes."""

    def _run_fn(self, compact=None):
        def run(msgs_u8, sig_u8, pk):
            with jax.named_scope(ops.MILLER):
                acc = jax.lax.fori_loop(
                    0, 3, lambda i, a: a * 3 + 1,
                    msgs_u8.astype(jnp.int32).sum(axis=1))
            with jax.named_scope(ops.FINAL_EXP):
                return (acc + sig_u8.astype(jnp.int32).sum(axis=1)) % 2 == 0
        return run


@pytest.fixture
def cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    return tmp_path


def _verifier():
    return StandIn(GC.G1_GEN, V.SHAPE_UNCHAINED)


def _inputs():
    rng = np.random.default_rng(32)
    return (rng.integers(0, 256, (N, 8), dtype=np.uint8),
            rng.integers(0, 256, (N, 96), dtype=np.uint8))


def _verdicts(v):
    msgs, sigs = _inputs()
    return np.asarray(v._kernels[N](jnp.asarray(msgs), jnp.asarray(sigs),
                                    v._pk))


def _file(v):
    return aot.exported_path(v._aot_name(N), False, v._body_tag())[0]


def test_build_traces_once_and_the_next_process_loads(cache):
    first, second = _verifier(), _verifier()
    rec1 = first.build(N)
    assert rec1["source"] == "traced" and "load_error" not in rec1
    assert os.listdir(cache) == [os.path.basename(_file(first))]
    assert os.path.getsize(_file(first)) > rec1["blob_bytes"] > 0
    rec2 = second.build(N)
    assert rec2["source"] == "loaded" and "load_error" not in rec2
    assert rec2["blob_bytes"] == rec1["blob_bytes"]
    msgs, sigs = _inputs()
    want = (3 * (3 * (3 * msgs.sum(axis=1, dtype=np.int64) + 1) + 1) + 1
            + sigs.sum(axis=1)) % 2 == 0
    assert (_verdicts(first) == want).all()
    assert (_verdicts(second) == want).all()
    # both lowered the same module: JAX's cache keys the executable by it
    assert rec1["lowered"].as_text() == rec2["lowered"].as_text()


@pytest.mark.parametrize("source", ["traced", "loaded"])
def test_the_record_and_the_spans_keep_their_shape(cache, source):
    if source == "loaded":
        _verifier().build(N)
    tracing.RECORDER.clear()
    rec = _verifier().build(N)
    assert rec["source"] == source
    assert READ_BY_THE_BENCHMARK | NEW_IN_THE_RECORD == set(rec)
    assert rec["program"] == _verifier()._aot_name(N) and rec["bucket"] == N
    assert rec["tracing"] == "static"
    # `trace_s` runs to the exported form in hand: it holds the read
    assert 0 <= rec["load_s"] <= rec["trace_s"]
    assert rec["lower_s"] > 0 and rec["compile_s"] > 0
    json.dumps({k: v for k, v in rec.items() if k != "lowered"})
    spans = {s.name: s.to_dict() for s in tracing.RECORDER.spans()}
    build = spans["verifier.build"]
    assert build["attrs"]["source"] == source
    assert build["attrs"]["blob_bytes"] == rec["blob_bytes"]
    # a G2-signature shape: a G2 point a row, no table of lines
    assert build["attrs"]["miller_lines"] == rec["miller_lines"] == "per_row"
    assert "line_steps" not in build["attrs"]
    phases = {"build.load", "build.lower", "build.compile"} | (
        {"build.trace"} if source == "traced" else set())
    assert {n for n in spans if n.startswith("build.")} == phases
    assert all(spans[n]["parent_id"] == build["span_id"] for n in phases)


def test_a_loaded_program_keeps_its_scopes_in_the_compiled_text(cache):
    _verifier().build(N)
    v = _verifier()
    assert v.build(N)["source"] == "loaded"
    paths = re.findall(r'op_name="([^"]*)"', v._kernels[N].as_text())
    for stage in (ops.MILLER, ops.FINAL_EXP):
        assert any(stage in p.split("/") for p in paths), stage
    # the harness's reader finds a stage as any part of the path
    assert any(p.startswith("jit(call)/call_exported/jit(run)/")
               for p in paths)


def test_a_loaded_program_keeps_its_pallas_calls(cache):
    """Lowered for a TPU across platforms (no chip, not the TPU's
    compiler): the module that comes back from the file holds the traced
    one's custom calls, kernel names and stages."""
    pf = PFm.PallasField(P)

    def run(a, b):
        with jax.named_scope(ops.MILLER):
            x = pf.mont_mul(a, b)
        with jax.named_scope(ops.FINAL_EXP):
            return pf.mont_sqr(pf.mont_mul(x, a))
    s = jax.ShapeDtypeStruct((PFm.TILE, 32), jnp.int32)
    traced = jax.jit(run).trace(s, s).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    aot.save_exported("pallas-stand-in", True, jax.export.export(
        jax.jit(run), platforms=("tpu",))(s, s))
    exported, found = aot.load_exported("pallas-stand-in", True)
    assert found["blob_bytes"] > 0
    loaded = jax.jit(exported.call).trace(s, s).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert loaded.count("tpu_custom_call") \
        == traced.count("tpu_custom_call") > 0
    names = r'kernel_name = "([^"]*)"'
    assert sorted(re.findall(names, loaded)) \
        == sorted(re.findall(names, traced)) == ["mont_mul", "mont_sqr"]
    for stage in (ops.MILLER, ops.FINAL_EXP):
        assert f'"jit(run)/{stage}/' in loaded


def _touch_a_hashed_source(tmp_path, monkeypatch):
    src = tmp_path / "kernel.py"
    src.write_bytes(b"x = 1\n")
    monkeypatch.setattr(aot, "_hashed_files", lambda: [str(src)])
    monkeypatch.setattr(aot, "_CODE_HASH", None)
    before = aot.exported_path("p", True)
    src.write_bytes(b"x = 2\n")
    monkeypatch.setattr(aot, "_CODE_HASH", None)
    return before, aot.exported_path("p", True)


KEY_PARTS = {
    "a_byte_of_a_hashed_source": _touch_a_hashed_source,
    "the_body_traced": lambda *_: (
        aot.exported_path("p", True, "drand_tpu.verify.Verifier._run_fn"),
        aot.exported_path("p", True, "tests.stand_in.run_fn")),
    "the_compact_flag": lambda *_: (aot.exported_path("p", True),
                                    aot.exported_path("p", False)),
    "the_name": lambda *_: (aot.exported_path("p-b8", True),
                            aot.exported_path("p-b64", True)),
}


@pytest.mark.parametrize("part", sorted(KEY_PARTS))
def test_the_key_changes_with_each_of_its_parts(cache, monkeypatch, part):
    (path1, key1), (path2, key2) = KEY_PARTS[part](cache, monkeypatch)
    assert key1 != key2 and path1 != path2
    assert os.path.dirname(path1) == str(cache) == aot.persistent_cache_dir()


def test_the_key_names_the_versions_and_the_miller_path(cache, monkeypatch):
    import jaxlib
    key = aot.exported_path("p", True)[1]
    for part in ("p|", f"jax{jax.__version__}", f"jaxlib{jaxlib.__version__}",
                 jax.devices()[0].device_kind, aot.code_hash(), "compact=1",
                 "miller11"):
        assert part in key, part
    monkeypatch.setenv("DRAND_TPU_MILLER_MERGED", "0")
    assert "miller01" in aot.exported_path("p", True)[1]


def _passes_all(self, compact=None):
    return lambda msgs_u8, sig_u8, pk: jnp.ones(msgs_u8.shape[0], bool)


def _replace_on_the_class(v, monkeypatch):
    monkeypatch.setattr(V.Verifier, "_run_fn", _passes_all)


def _replace_on_the_instance(v, monkeypatch):
    v._run_fn = lambda compact=None: _passes_all(v)


@pytest.mark.parametrize("replace", [_replace_on_the_class,
                                     _replace_on_the_instance])
def test_a_body_put_in_the_place_of_the_sources_is_never_read_as_theirs(
        cache, monkeypatch, replace):
    """The key's source hash vouches for `Verifier._run_fn` as the hashed
    files have it.  A verifier whose body was replaced (this one passes
    every row) is built and stored under a key that names the
    replacement, and the sources' own verifier beside it finds no file."""
    real = V.Verifier(GC.G1_GEN, V.SHAPE_UNCHAINED)
    assert real._body_tag() == "drand_tpu.verify.Verifier._run_fn"
    name, theirs = real._aot_name(N), real._body_tag()
    forged = V.Verifier(GC.G1_GEN, V.SHAPE_UNCHAINED)
    with monkeypatch.context() as m:
        replace(forged, m)
        assert forged._body_tag().endswith("_passes_all") \
            or "<lambda>" in forged._body_tag()
        assert forged.build(N)["source"] == "traced"
        assert forged._aot_name(N) == name
        path, key = aot.exported_path(name, False, forged._body_tag())
        assert os.listdir(cache) == [os.path.basename(path)]
        with open(path, "rb") as f:
            assert json.loads(f.readline())["key"] == key
        assert key.split("|")[-1] == forged._body_tag() != theirs
    assert path != aot.exported_path(name, False, theirs)[0]
    assert aot.load_exported(name, False, theirs) == (None, {})


def test_a_new_file_removes_the_ones_it_supersedes(cache, monkeypatch):
    """A source edit leaves the program's old file (28-48 MB at 16,384)
    beside the new one, and JAX's eviction does not count it: the writer
    removes it, with what a killed writer left, and nothing else."""
    v = _verifier()
    v.build(N)
    old = _file(v)
    others = [cache / "jit_run-0123-cache",
              cache / (os.path.basename(old).replace("-b8-", "-b64-"))]
    orphan = cache / (os.path.basename(old) + ".123.456.tmp")
    fresh = cache / (os.path.basename(old) + ".123.789.tmp")
    for p in (*others, orphan, fresh):
        p.write_bytes(b"x")
    long_ago = os.path.getmtime(old) - 2 * aot._STALE_TMP_S
    os.utime(orphan, (long_ago, long_ago))
    monkeypatch.setattr(aot, "_CODE_HASH", "an edited source")
    rec = _verifier().build(N)
    assert rec["source"] == "traced" and "load_error" not in rec
    new = _file(v)
    assert new != old and os.path.exists(new)
    assert not os.path.exists(old) and not orphan.exists()
    assert fresh.exists() and all(p.exists() for p in others)
    assert _verifier().build(N)["source"] == "loaded"


def test_exports_run_one_at_a_time(cache, monkeypatch):
    """The forward-compatibility flag is the process's: a second thread's
    export waits, so no restore lands inside another thread's trace."""
    flag = "jax_export_ignore_forward_compatibility"
    inside, seen = threading.Event(), []
    real_export = jax.export.export

    def slow_export(*a, **k):
        inside.set()
        seen.append(getattr(jax.config, flag))
        assert not aot._EXPORT_LOCK.acquire(timeout=0.2)
        return real_export(*a, **k)

    monkeypatch.setattr(jax.export, "export", slow_export)
    v = _verifier()
    threads = [threading.Thread(target=aot.export_program, args=(
        v._run_fn(), *v._arg_structs(N))) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert seen == [True, True]
    assert aot._EXPORT_LOCK.acquire(timeout=0)
    aot._EXPORT_LOCK.release()


def _cut_short(path):
    with open(path, "rb") as f:
        raw = f.read()
    with open(path, "wb") as f:
        f.write(raw[:len(raw) - 100])


def _restated(**head):
    """The file with these entries of its first line changed (and, with
    `blob`, other bytes under it)."""
    def rewrite(path, blob=head.pop("blob", None)):
        with open(path, "rb") as f:
            first, rest = json.loads(f.readline()), f.read()
        if blob is not None:
            rest = blob
            first.update(bytes=len(blob),
                         sha256=hashlib.sha256(blob).hexdigest())
        with open(path, "wb") as f:
            f.write(json.dumps(dict(first, **head)).encode() + b"\n" + rest)
    return rewrite


def _garbage(path):
    with open(path, "wb") as f:
        f.write(b"\xff\xfe not a header")


UNUSABLE = {
    "cut_short": _cut_short,
    "of_another_key": _restated(key="verify-other|cpu|0|compact=0|miller11|"),
    "of_another_format": _restated(format="drand_tpu.exported.0"),
    "not_an_exported_form": _restated(blob=b"\x00" * 64),
    "garbage": _garbage}


@pytest.mark.parametrize("fault", sorted(UNUSABLE))
def test_a_file_that_cannot_be_used_is_reported_and_replaced(
        cache, capfd, fault):
    first = _verifier()
    first.build(N)
    whole = os.path.getsize(_file(first))
    UNUSABLE[fault](_file(first))
    v = _verifier()
    rec = v.build(N)
    assert rec["source"] == "traced"
    assert 0 < len(rec["load_error"]) <= 200 and "\n" not in rec["load_error"]
    assert capfd.readouterr().err.count("cannot be used") == 1
    assert (_verdicts(v) == _verdicts(first)).all()
    assert os.path.getsize(_file(first)) == whole
    again = _verifier().build(N)
    assert again["source"] == "loaded" and "load_error" not in again


def test_two_writers_leave_one_whole_file(cache):
    v = _verifier()
    exported = aot.export_program(v._run_fn(), *v._arg_structs(N))
    name, errors, stop = v._aot_name(N), [], threading.Event()
    body = v._body_tag()

    def write():
        try:
            for _ in range(40):
                aot.save_exported(name, False, exported, body)
        except Exception as exc:       # pragma: no cover - the failure
            errors.append(exc)

    def read():
        while not stop.is_set():
            got, found = aot.load_exported(name, False, body)
            if found and got is None:
                errors.append(found["load_error"])

    writers = [threading.Thread(target=write) for _ in range(2)]
    reader = threading.Thread(target=read)
    for t in (*writers, reader):
        t.start()
    for t in writers:
        t.join()
    stop.set()
    reader.join()
    assert errors == []
    assert os.listdir(cache) == [os.path.basename(_file(v))]
    assert v.build(N)["source"] == "loaded"


def test_a_directory_that_cannot_be_written_fails_no_build(
        tmp_path, monkeypatch, capfd):
    blocked = tmp_path / "a-file-not-a-directory"
    blocked.write_bytes(b"")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(blocked / "cache"))
    first, second = _verifier(), _verifier()
    for v in (first, second):
        rec = v.build(N)
        assert rec["source"] == "traced" and rec["blob_bytes"] > 0
        assert "load_error" not in rec
    assert capfd.readouterr().err.count("could not be written") == 2
    assert (_verdicts(first) == _verdicts(second)).all()


def test_export_leaves_the_forward_compatibility_flag_as_it_was(cache):
    flag = "jax_export_ignore_forward_compatibility"
    before = getattr(jax.config, flag)
    v = _verifier()
    aot.export_program(v._run_fn(), *v._arg_structs(N))
    assert getattr(jax.config, flag) == before
    with pytest.raises(TypeError):
        aot.export_program(v._run_fn(), "no struct")
    assert getattr(jax.config, flag) == before

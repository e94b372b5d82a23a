"""CPU rehearsal of `chip_smoke.py`'s control flow.

The smoke itself proves the TPU path and refuses every other platform, so
here (a) the script, run as the driver runs it but on the CPU, must exit
non-zero with `"ok": false` and a reason that names the platform, and (b)
its catch-up, corrupted-signature and host-agreement checks must hold at
64 rounds when the platform checks are stubbed IN THE TEST and the device
program is replaced by a host-backed one that gives real verdicts (the
XLA:CPU compile of the whole verify graph takes many minutes and is not
what this file is about).  The program has no option for any of this.
"""

import asyncio
import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

_real_devices = jax.devices

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _json_lines(text: str) -> list[dict]:
    return [json.loads(l) for l in text.splitlines() if l.startswith("{")]


def test_on_the_cpu_the_script_fails_and_names_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert "'cpu'" in last["reason"] and "tpu" in last["reason"]
    assert "device" not in last          # no result is printed


class _NoKernels:
    """The lowered stage of a program that holds no Pallas kernel."""

    def as_text(self):
        return ""


def _host_backed_build(honest: bool):
    """A stand-in for `Verifier.build` whose program checks every
    (message, signature) on the host tier; `honest=False` makes it say
    yes to everything, which is the fault the smoke must catch."""
    from drand_tpu import native
    from drand_tpu.crypto import sign as S
    from drand_tpu.crypto.bls12381 import curve as GC

    def build(self, n):
        pk = GC.g1_to_bytes(self._pk_golden)

        def one(msg, sig):
            digest = hashlib.sha256(bytes(msg)).digest()
            if native.available():
                return native.verify_g2(pk, digest, bytes(sig),
                                        self.shape.dst)
            return S.bls_verify(self._pk_golden, digest, bytes(sig))

        def fn(msgs, sigs, _pk):
            msgs, sigs = np.asarray(msgs), np.asarray(sigs)
            if not honest:
                return np.ones(len(msgs), dtype=bool)
            verdicts = {}         # padding repeats rows: check each once
            for m, s in zip(msgs, sigs):
                key = (bytes(m), bytes(s))
                if key not in verdicts:
                    verdicts[key] = one(m, s)
            return np.array([verdicts[(bytes(m), bytes(s))]
                             for m, s in zip(msgs, sigs)], dtype=bool)

        self._kernels[n] = fn
        return {"program": self._aot_name(n), "bucket": n,
                "tracing": "host-stub", "trace_s": 0.0, "lower_s": 0.0,
                "compile_s": 0.0, "lowered": _NoKernels()}

    return build


@pytest.fixture()
def smoke_on_cpu(monkeypatch):
    import chip_smoke
    import drand_tpu.verify as V
    monkeypatch.setattr(chip_smoke, "check_device", lambda dev: None)
    monkeypatch.setattr(chip_smoke, "check_program", lambda rec: None)
    monkeypatch.setattr(V, "_BUCKETS", V._BUCKETS)   # smoke() narrows it
    # the suite runs on 8 virtual devices; the one-chip smoke is one device
    monkeypatch.setattr(jax, "devices", lambda: _real_devices()[:1])
    return chip_smoke


def test_catch_up_and_corruption_checks_hold_at_64_rounds(
        smoke_on_cpu, monkeypatch, capsys):
    import drand_tpu.verify as V
    monkeypatch.setattr(V.Verifier, "build", _host_backed_build(True))
    device = asyncio.run(smoke_on_cpu.smoke(backlog=64))
    assert device["platform"] == "cpu"        # main() is what refuses it
    lines = _json_lines(capsys.readouterr().out)
    runs = {l["catch_up"]: l for l in lines if "catch_up" in l}
    assert runs["clean"]["sync_ok"] and \
        runs["clean"]["committed_rounds"] == 64
    assert not runs["corrupted"]["sync_ok"]
    assert runs["corrupted"]["committed_rounds"] < 40     # 64 * 5 // 8
    for hv in (l["host_vs_device"] for l in lines if "host_vs_device" in l):
        assert hv["host_true"] == hv["device_true"] == hv["rounds"] - 1
        assert not hv["host_on_corrupted"] and not hv["device_on_corrupted"]
    loads = [l["program"]["load"] for l in lines if "program" in l]
    assert loads == ["first build", "second build, fresh Verifier"]


def test_a_consumer_that_commits_past_a_corrupted_round_fails_the_smoke(
        smoke_on_cpu, monkeypatch, capsys):
    import drand_tpu.verify as V
    monkeypatch.setattr(V.Verifier, "build", _host_backed_build(False))
    with pytest.raises(smoke_on_cpu.SmokeFailure, match="corrupted round"):
        asyncio.run(smoke_on_cpu.smoke(backlog=64))


def test_four_chips_control_flow_on_four_virtual_devices(
        smoke_on_cpu, monkeypatch, capsys, tmp_path):
    """`--four-chips` on four of the suite's virtual CPU devices, at 64
    rows a device, with the verify body replaced by a traceable stand-in
    that rejects exactly the rows the smoke corrupts (byte 5 of a made-up
    signature is under 0x80 until the smoke flips it): the sharded path
    is taken, the mesh's build traces and the one-device build loads the
    same form, every device holds a shard, verdicts agree."""
    import drand_tpu.verify as V
    from drand_tpu.chain.scheme import scheme_by_id
    from drand_tpu.chain.verify import ChainVerifier
    per = 64

    def fake_run_fn(self, compact=None):
        return lambda msgs, sigs, pk: sigs[:, 5] < 0x80

    def made_up_fixture(rows):
        sigs = np.random.default_rng(34).integers(
            0, 128, size=(rows, 48), dtype=np.uint8)
        from drand_tpu.crypto.bls12381 import curve as GC
        return sigs, ChainVerifier(
            scheme_by_id("bls-unchained-g1-rfc9380"),
            GC.g2_to_bytes(GC.G2_GEN))       # any key: no row is verified

    monkeypatch.setattr(V.Verifier, "_run_fn", fake_run_fn)
    monkeypatch.setattr(smoke_on_cpu, "_quicknet_fixture", made_up_fixture)
    monkeypatch.setattr(jax, "devices", lambda: _real_devices()[:4])
    # `Verifier.build` writes the stand-in's exported form beside JAX's
    # cache (under a key that names the stand-in, so never read as the
    # sources' program: test_exported_program.py): not in the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    device = smoke_on_cpu.four_chips(per)
    assert device["count"] == 4
    lines = _json_lines(capsys.readouterr().out)
    out = [l["four_chips"] for l in lines if "four_chips" in l][0]
    assert len(out["devices_holding_a_shard"]) == 4
    assert out["rows_per_shard"] == [per]
    assert out["sharded_true"] == out["one_device_true"] == 4 * per - 4
    builds = [l["program"] for l in lines if "program" in l]
    assert [(b["load"], b["source"], b.get("devices")) for b in builds] == [
        ("the mesh's build", "traced", 4),
        ("one device, the same form", "loaded", None)]
    written = [fn for fn in os.listdir(tmp_path) if fn.endswith(".jaxexport")]
    assert len(written) == 1 and _traced_body(tmp_path / written[0]).endswith(
        "<locals>.fake_run_fn")


def _traced_body(path) -> str:
    """Which function an exported program's file says was traced: the
    last part of the key on its first line."""
    with open(path, "rb") as f:
        return json.loads(f.readline())["key"].split("|")[-1]


def test_the_checkouts_cache_holds_only_the_sources_own_programs():
    """After this file's stand-ins (loadfile keeps a file's tests on one
    worker, in order): whatever exported programs lie in the cache that
    this checkout's processes read, each was traced from
    `Verifier._run_fn` as the hashed sources have it.  PR 32's first
    draft left a stand-in that passes every round but one under the real
    program's key there."""
    from drand_tpu import aot
    directory = aot.persistent_cache_dir()
    found = [fn for fn in (os.listdir(directory)
                           if os.path.isdir(directory) else [])
             if fn.endswith(".jaxexport")]
    for fn in found:
        assert _traced_body(os.path.join(directory, fn)) \
            == "drand_tpu.verify.Verifier._run_fn", fn

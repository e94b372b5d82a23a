"""The verify program names its stages and its kernels on the device
(ISSUE 25): `jax.named_scope` from one vocabulary (`drand_tpu.ops.STAGES`)
in every operation's `op_name`, and the kernel's own name on every Pallas
call.  Metadata only: the kernels lowered are the ones lowered without
the scopes, and the stage is no part of `PallasField._launch`'s key.

Nothing is compiled.  The whole programs are the smallest the suite
builds (the pure-XLA graph at 8 rows, what the CPU tier traces); the
Pallas path is lowered for a TPU across platforms, which needs no chip
and not the TPU's compiler.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

import drand_tpu.verify as V
from drand_tpu import ops
from drand_tpu.crypto.bls12381 import curve as GC
from drand_tpu.crypto.bls12381.constants import P
from drand_tpu.ops import pallas_field as PFm

_LOC = re.compile(r'= loc\("(jit\(run\)[^"]*)"')


def test_one_vocabulary_for_both_schemes():
    assert ops.STAGES == ("digest", "sig_decode", "h2c", "miller",
                          "final_exp")
    assert (ops.DIGEST, ops.SIG_DECODE, ops.H2C, ops.MILLER,
            ops.FINAL_EXP) == ops.STAGES


@pytest.mark.parametrize("shape,pk", [
    (V.SHAPE_UNCHAINED_G1, GC.G2_GEN),      # quicknet: signatures on G1
    (V.SHAPE_UNCHAINED, GC.G1_GEN),         # signatures on G2
], ids=["g1sig", "g2sig"])
def test_every_stage_scopes_the_lowered_verify_program(shape, pk):
    v = V.Verifier(pk, shape)
    text = jax.jit(v._run_fn(compact=True)).trace(
        *v._arg_structs(8)).lower().as_text(debug_info=True)
    paths = _LOC.findall(text)
    assert len(paths) > 1000
    by_stage = {s: 0 for s in ops.STAGES}
    unscoped = []
    for path in paths:
        parts = path.split("/")
        stage = next((p for p in parts if p in by_stage), None)
        if stage is None:
            unscoped.append(path)
        else:
            by_stage[stage] += 1
            # a stage is the program's outermost scope, never nested in
            # another stage
            assert parts[1] == stage, path
    assert all(by_stage.values()), by_stage
    # outside the stages: glue and the verdict's last ANDs (the digest
    # has a stage of its own since ISSUE 29)
    assert len(unscoped) < 0.005 * len(paths), unscoped[:5]
    assert not any("sha256" in p or "digest" in p for p in unscoped)


def _two_stage_program(pf, scoped: bool):
    scope = jax.named_scope if scoped else \
        (lambda _name: contextlib.nullcontext())

    def run(a, b):
        with scope(ops.MILLER):
            x = pf.mont_mul(a, b)
            (y,) = pf.fp2_products([((x, a), (b, x))])
        with scope(ops.FINAL_EXP):
            z = pf.mont_mul(y[0], y[1])     # the same kernel, another stage
            return pf.mont_sqr(z)
    return run


def _lower_for_tpu(fn):
    s = jax.ShapeDtypeStruct((PFm.TILE, 32), jnp.int32)
    return jax.jit(fn).trace(s, s).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)


def test_pallas_calls_carry_their_kernels_names_and_the_callers_stage():
    pf = PFm.PallasField(P)
    text = _lower_for_tpu(_two_stage_program(pf, scoped=True))
    names = re.findall(r'kernel_name = "([^"]*)"', text)
    assert sorted(names) == ["fp2_products", "mont_mul", "mont_sqr"]
    # the kernel's name is a part of the path, before `pallas_call` ...
    for name in names:
        assert f'loc("{name}/pallas_call"' in text
    # ... and the call sites carry the stage; XLA joins the two when it
    # inlines the memoised function (`.../miller/jit(wrapped)/mont_mul/
    # pallas_call`)
    calls = re.findall(r'loc\("jit\(run\)/(\w+)/jit\(wrapped\)"', text)
    assert calls == ["miller", "miller", "final_exp", "final_exp"]
    # one memoised function a kernel: mont_mul is lowered once although
    # two stages call it
    assert len(pf._launchers) == 3
    assert not any(stage in repr(key) for key in pf._launchers
                   for stage in ops.STAGES)


def test_scopes_change_no_kernel():
    counts = {}
    for scoped in (False, True):
        text = _lower_for_tpu(_two_stage_program(PFm.PallasField(P), scoped))
        body = text[:text.index("#loc")] if "#loc" in text else text
        # without the locations the two programs are the same text
        counts[scoped] = (text.count("tpu_custom_call"),
                          re.sub(r"loc\([^)]*\)", "", body))
    assert counts[False][0] == counts[True][0] == 3
    assert counts[False][1] == counts[True][1]

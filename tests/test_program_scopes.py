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
from drand_tpu.ops import pairing as DP
from drand_tpu.ops import pallas_field as PFm

_LOC = re.compile(r'= loc\("(jit\(run\)[^"]*)"')


def test_one_vocabulary_for_both_schemes():
    assert ops.STAGES == ("digest", "sig_decode", "h2c", "miller",
                          "final_exp")
    assert (ops.DIGEST, ops.SIG_DECODE, ops.H2C, ops.MILLER,
            ops.FINAL_EXP) == ops.STAGES


LOOPS = ("miller_loop_fixed_q", "miller_loop_pairs")


@pytest.mark.parametrize("shape,pk,loop", [
    # quicknet: signatures on G1, both G2 arguments the whole batch's
    (V.SHAPE_UNCHAINED_G1, GC.G2_GEN, "miller_loop_fixed_q"),
    (V.SHAPE_UNCHAINED, GC.G1_GEN, "miller_loop_pairs"),  # signatures on G2
], ids=["g1sig", "g2sig"])
def test_every_stage_scopes_the_lowered_verify_program(shape, pk, loop,
                                                       monkeypatch):
    traced = []
    for name in LOOPS:
        def spy(*args, _name=name, _fn=getattr(DP, name), **kw):
            traced.append(_name)
            return _fn(*args, **kw)
        monkeypatch.setattr(DP, name, spy)
    v = V.Verifier(pk, shape)
    text = jax.jit(v._run_fn(compact=True)).trace(
        *v._arg_structs(8)).lower().as_text(debug_info=True)
    # the scheme's structure selects the Miller loop (ISSUE 37), once
    assert traced == [loop]
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


@pytest.mark.parametrize("loop,kernels", [
    ("miller_loop_fixed_q", {"flat_sqr", "mont_mul", "flat_mul",
                             "flat_conj"}),
    ("miller_loop_pairs", {"flat_sqr", "g2_dbl_line", "g2_add_line",
                           "flat_mul", "flat_conj"}),
], ids=["g1sig", "g2sig"])
def test_each_programs_miller_stage_names_its_kernels(loop, kernels):
    """The Miller stage as the TPU traces it (compact ladders), lowered
    for a TPU across platforms: the G1-signature program's loop holds no
    `g2_dbl_line` and no `g2_add_line` (its lines come with the key), a
    G2-signature program's holds both."""
    from unittest import mock

    from drand_tpu.ops.field import compact_scope
    fp = jax.ShapeDtypeStruct((PFm.TILE, 32), jnp.int32)
    if loop == "miller_loop_fixed_q":
        args = ([(fp, fp), (fp, fp)],
                jax.ShapeDtypeStruct((DP.LINE_STEPS, 2, 6, 32), jnp.int32))
    else:
        args = ([((fp, fp), ((fp, fp), (fp, fp)))] * 2,)

    def run(*a):
        with compact_scope(True), jax.named_scope(ops.MILLER):
            return getattr(DP, loop)(*a)

    with mock.patch.object(PFm, "use_pallas", return_value=True):
        traced = jax.jit(run).trace(*args)
        text = traced.lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert set(re.findall(r'kernel_name = "([^"]*)"', text)) == kernels
    sites = re.findall(r'loc\("jit\(run\)/([^"]*)/jit\(wrapped\)"', text)
    assert sites and all(s.split("/")[0] == ops.MILLER for s in sites)
    if loop == "miller_loop_pairs":
        # ISSUE 39: the ladder's body is kernels on tile-layout state.
        # Between them no limb array is joined or cut, and nothing is
        # selected in [..., 32] layout: T, P, Q and the masks crossed
        # into tile layout before the `while`, the line never leaves it
        (scan,) = [e for e in _equations(traced.jaxpr.jaxpr)
                   if e.primitive.name == "scan"]
        body = list(_equations(scan.params["jaxpr"].jaxpr))
        names = [e.primitive.name for e in body]
        assert names.count("pallas_call") == 7      # 4 + 3 on a set bit
        assert "cond" in names
        assert not {"concatenate", "slice", "dynamic_slice", "gather",
                    "transpose", "reshape"} & set(names), names
        assert not [e for e in body if e.primitive.name == "select_n"
                    and e.outvars[0].aval.shape[-1:] == (32,)]


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold (a
    `jit`'s, a `scan`'s body, a `cond`'s branches), a Pallas kernel's own
    body left out: what XLA, not Mosaic, is handed."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


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

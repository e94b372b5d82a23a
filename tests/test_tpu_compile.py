"""The Pallas kernels of the verify path compile for a TPU v5e.

Interpret-mode KATs (test_sim_kats.py) say the kernels compute the right
thing; they cannot say that the chip's compiler accepts them.  PR 22 found
the two merged Miller kernels refused (`RESOURCE_EXHAUSTED: Ran out of
memory in memory space vmem ... Scoped allocation with size 19.78M and
limit 16.00M`, and 23.29M for the add step) after every KAT had passed
since PR 9.  The TPU's compiler is installed here and compiles for a chip
that is described and not attached, so these tests compile each kernel for
a described `v5e:2x2`, one tile (1,024 elements) per call, which is the
shape the 512 bucket gives; a kernel's VMEM need is per grid step and does
not grow with the tile count.  Nothing runs: a compile that passes is not
a chip run.

Left out for time (each was compiled once by hand for PR 22, both at one
tile and at the 16 tiles of the 16,384 bucket; seconds in CHANGES.md):
`mont_reduce`, `fp2_sqr5_mul`/`sqr4_mul`
and the `sqr_chain_mul` family, `g2_point_dbl`/
`g2_point_add` (their G1 twins are in since ISSUE 46: small bodies,
seconds each), `line_merge`, `flat_conj`/`flat_frob`, and the wider
`fp2_products`/`fp2_sqrs` stackings.  The two Miller kernels stay in
although each takes minutes: they are the ones the compiler refused.
`flat_mul`, sparse and dense, is in since ISSUE 45 beside `flat_sqr`:
their product loops' bounds are read from SMEM, which the chip's
compiler has to accept under the default 16 MiB of scoped VMEM (the two
Miller kernels run the same phases under 48).
One composition is compiled too: a compact ladder (`cyclo_sqr` on every
bit, dense `flat_mul` under a `lax.cond` on the set ones), because what
the served program executes rests on the compiler keeping that
conditional (ISSUE 26).  And the G1-signature program's Miller loop
(ISSUE 37), whose lines come from a table indexed by a counter in the
ladder's state.

As the on-chip-measurement guide sets out: the topology is described
inside a module-scoped fixture that skips where it cannot be, nothing
touches the TPU library while a module is imported, the persistent cache
is off around the compiles, and they run in this process.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from drand_tpu.crypto.bls12381.constants import P
from drand_tpu.ops import flat12  # noqa: F401  (its module constants are
# built here, not inside the trace of the first kernel that imports it)
from drand_tpu.ops import pallas_field as PFm

NT = 1                                   # tiles per call


@pytest.fixture(scope="module")
def one_chip():
    """SingleDeviceSharding on the first chip of a described v5e:2x2,
    with JAX's persistent cache off while the module's tests run (a
    compile for a described chip is written to it but cannot be read
    back without the chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _tf(tiles):
    return PFm.TileForm(tiles, (NT * PFm.TILE,), NT * PFm.TILE)


# name -> (function of the PallasField and tile operands, limb rows of
# each operand)
KERNELS = {
    "mont_mul": (lambda pf, a, b: pf.mont_mul(_tf(a), _tf(b)).tiles,
                 (32, 32)),
    "mont_sqr": (lambda pf, a: pf.mont_sqr(_tf(a)).tiles, (32,)),
    "fp2_products": (
        lambda pf, a, b: pf.fp2_products([(_tf(a), _tf(b))])[0].tiles,
        (64, 64)),
    "fp2_sqrs": (lambda pf, a: pf.fp2_sqrs([_tf(a)])[0].tiles, (64,)),
    "flat_mul_sparse": (
        lambda pf, a, b: pf.flat_mul(_tf(a), _tf(b), PFm.LINE_IDX).tiles,
        (384, 192)),
    "flat_mul_dense": (
        lambda pf, a, b: pf.flat_mul(_tf(a), _tf(b),
                                     tuple(range(12))).tiles,
        (384, 384)),
    "flat_sqr": (lambda pf, a: pf.flat_sqr(_tf(a)).tiles, (384,)),
    "cyclo_sqr": (lambda pf, a: pf.cyclo_sqr(_tf(a)).tiles, (384,)),
    "g2_dbl_line": (
        lambda pf, t, p, m: [o.tiles for o in pf.g2_dbl_line(
            _tf(t), _tf(p), m)],
        (192, 64, 1)),
    "g2_add_line": (
        lambda pf, t, q, p, m: [o.tiles for o in pf.g2_add_line(
            _tf(t), _tf(q), _tf(p), m)],
        (192, 128, 64, 1)),
    "miller_dbl_iter": (
        lambda pf, f, t, p, m: [o.tiles for o in pf.miller_dbl_iter(
            _tf(f), _tf(t), _tf(p), _tf(m))],
        (384, 384, 128, 2)),
    "miller_add_iter": (
        lambda pf, f, t, q, p, m: [o.tiles for o in pf.miller_add_iter(
            _tf(f), _tf(t), _tf(q), _tf(p), _tf(m))],
        (384, 384, 256, 128, 2)),
    # the G1 ladders' step on a packed point (ISSUE 46); the addition
    # with its doubling fall-back, the larger of its two bodies
    "g1_point_dbl": (lambda pf, a: pf.g1_point_dbl(_tf(a)).tiles, (96,)),
    "g1_point_add": (
        lambda pf, a, b: pf.g1_point_add(_tf(a), _tf(b), True).tiles,
        (96, 96)),
}


@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, limbs = KERNELS[name]
    pf = PFm.pallas_field(P)
    args = [jax.ShapeDtypeStruct((NT, l, *PFm._ROW), jnp.int32,
                                 sharding=one_chip) for l in limbs]
    lowered = jax.jit(lambda *a: fn(pf, *a)).lower(*args)
    assert "tpu_custom_call" in lowered.as_text(), \
        f"{name} lowered without a Pallas kernel"
    lowered.compile()      # raises what the chip's compiler would raise


def test_compact_ladder_keeps_its_conditional_on_v5e(one_chip):
    """What ISSUE 26 rests on: in compact mode a ladder is one `while`
    whose add step (a Pallas call) sits under a `conditional` on the
    scanned bit, and the chip's compiler keeps it one, neither turning it
    into a select over both results nor refusing a Mosaic call there.
    The ladder is f^|x| as `pairing._unitary_pow_x_abs` runs it."""
    from drand_tpu.ops.field import compact_scope, segmented_ladder
    from drand_tpu.ops.pairing import _X_SEGMENTS
    pf = PFm.pallas_field(P)

    def pow_x_abs(a):
        ft = _tf(a)
        with compact_scope():
            out = segmented_ladder(
                _X_SEGMENTS, ft, pf.cyclo_sqr,
                lambda acc: pf.flat_mul(acc, ft, tuple(range(12))))
        return out.tiles

    arg = jax.ShapeDtypeStruct((NT, 384, *PFm._ROW), jnp.int32,
                               sharding=one_chip)
    text = jax.jit(pow_x_abs).lower(arg).compile().as_text()
    assert len(re.findall(r" while\(", text)) == 1
    assert len(re.findall(r" conditional\(", text)) == 1
    # one call site a kernel: the body is not unrolled, the add step is
    # in one branch only
    assert text.count('custom_call_target="tpu_custom_call"') == 2


def test_the_fixed_q_miller_loop_compiles_for_v5e(one_chip):
    """ISSUE 37's loop as the served program traces it: one `while` with
    the addition's lines under one `conditional`, the table read by a
    dynamic index inside it, the eight Fp products of a step one
    `mont_mul` launch over eight times the tiles, and no G2 kernel."""
    from unittest import mock

    from drand_tpu.ops import pairing as DP
    from drand_tpu.ops.field import compact_scope

    def miller(xp, yp, hx, hy, table, m1, m2):
        with compact_scope():
            return DP.miller_loop_fixed_q([(xp, yp), (hx, hy)], table,
                                          [m1, m2], _keep_tiled=True).tiles

    rows = NT * PFm.TILE
    fp = jax.ShapeDtypeStruct((rows, 32), jnp.int32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip)
    table = jax.ShapeDtypeStruct((DP.LINE_STEPS, 2, 6, 32), jnp.int32,
                                 sharding=one_chip)
    with mock.patch.object(PFm, "use_pallas", return_value=True):
        text = jax.jit(miller).lower(fp, fp, fp, fp, table, mask,
                                     mask).compile().as_text()
    assert len(re.findall(r" while\(", text)) == 1
    assert len(re.findall(r" conditional\(", text)) == 1
    names = re.findall(r"/(\w+)/pallas_call", text)
    assert set(names) == {"flat_sqr", "mont_mul", "flat_mul", "flat_conj"}
    assert f"s32[{8 * NT},32,8,128]" in text      # the step's one launch


def test_the_tiled_g2_miller_loop_compiles_for_v5e(one_chip):
    """ISSUE 39's loop as the served G2 programs trace it: one `while`
    whose body is `flat_sqr`, the two-output `g2_dbl_line` over both
    pairs' tiles, and two `flat_mul`, the second reading its line at an
    offset of the first's tile count; `g2_add_line` and its two
    `flat_mul` under one `conditional`.  In the body the compiler keeps
    nothing in limb layout: what is not a kernel is whole-buffer copies
    of its own placing."""
    from unittest import mock

    from drand_tpu.ops import pairing as DP
    from drand_tpu.ops.field import compact_scope

    def miller(p1, q1, p2, q2, m1, m2):
        with compact_scope():
            return DP.miller_loop_pairs([(p1, q1), (p2, q2)], [m1, m2],
                                        _keep_tiled=True).tiles

    rows = NT * PFm.TILE
    fp = jax.ShapeDtypeStruct((rows, 32), jnp.int32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip)
    g1, g2 = (fp, fp), ((fp, fp), (fp, fp))
    with mock.patch.object(PFm, "use_pallas", return_value=True):
        text = jax.jit(miller).lower(g1, g2, g1, g2, mask,
                                     mask).compile().as_text()
    assert len(re.findall(r" while\(", text)) == 1
    assert len(re.findall(r" conditional\(", text)) == 1
    names = re.findall(r"/(\w+)/pallas_call", text)
    assert sorted(names) == ["flat_conj"] + ["flat_mul"] * 4 + \
        ["flat_sqr", "g2_add_line", "g2_dbl_line"]
    # both pairs in one launch, T' and the line its two outputs
    step = re.escape(f"s32[{2 * NT},192,8,128]") + r"\{[^}]*\}"
    assert re.search(rf"\({step}, {step}\) custom-call\(", text)
    (body,) = re.findall(r" while\(.*body=%?([\w.\-]+)", text)
    in_body = text[text.index(f"{body} ("):]
    in_body = in_body[:in_body.index("\n}")]
    assert "custom-call(" in in_body
    for op in ("concatenate(", " slice(", "select(", "transpose(", "pad("):
        assert op not in in_body, op
    assert ",32]" not in in_body              # no [..., 32] array at all


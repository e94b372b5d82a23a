"""The G1 ladders' fused step since ISSUE 46: `g1_point_dbl` and
`g1_point_add` on a tile-resident point.

The kernels are held to `curve.point_double` / `curve.point_add` over
`FpOps` limb for limb (on the CPU `use_pallas()` is false, so those ARE
the XLA forms), on one simulator tile whose eight rows hold the cases the
branchless formulas have to get right; the packed ladder of
`curve.point_mul_const` is held to the XLA ladder and to the golden model
(`crypto/bls12381/curve.py`), in both tracing modes; and a trace of the
ladder crosses into the tile layout once and out of it once, whatever its
length.
"""

import random
from unittest import mock

import jax
import numpy as np
import pytest

from drand_tpu.crypto.bls12381 import curve as GC
from drand_tpu.crypto.bls12381.constants import P, R, X
from drand_tpu.ops import curve as DC
from drand_tpu.ops import pallas_field as PFm
from drand_tpu.ops.field import compact_scope
from test_exported_program import StandIn, cache  # noqa: F401  (a stand-in
# body in the pairing's place, and its own cache directory)

rng = random.Random(0x46)
X_ABS = -X
TOP = (P - 1,) * 3                  # every coordinate all p-1 limbs
INF = GC.G1_INF


@pytest.fixture()
def sim():
    from pallas_sim import sim_kernels
    with sim_kernels():
        yield


def _rand():
    return GC.g1_mul(GC.G1_GEN, rng.randrange(1, R))


def _scaled(pt):
    """The same point under another Jacobian representative."""
    z = rng.randrange(2, P)
    return (pt[0] * z * z % P, pt[1] * z * z * z % P, pt[2] * z % P)


A1, A2 = _scaled(_rand()), _scaled(_rand())
# (row's name, first operand, second operand): one tile of the simulator
ROWS = [
    ("random", A1, A2),
    ("generator", GC.G1_GEN, A2),
    ("inf_first", INF, A2),
    ("inf_second", A1, INF),
    ("inf_both", INF, (0, 0, 0)),
    ("p_plus_p", A1, _scaled(A1)),
    ("p_minus_p", A1, _scaled(GC.g1_neg(A1))),
    ("all_p_minus_1", TOP, A2),
]
P1 = DC.g1_encode([r[1] for r in ROWS])
P2 = DC.g1_encode([r[2] for r in ROWS])


def _same(got, want):
    got, want = [np.asarray(c) for c in got], [np.asarray(c) for c in want]
    assert [c.shape for c in got] == [c.shape for c in want]
    assert [c.dtype for c in got] == [np.int32] * 3
    bad = {ROWS[r][0] for g, w in zip(got, want)
           for r in np.nonzero((g != w).any(axis=-1))[0]}
    assert not bad, bad


@pytest.mark.parametrize("operand", ["first", "second"])
@pytest.mark.parametrize("form", ["packed", "tuple"])
def test_g1_point_dbl_equals_the_xla_form_limb_for_limb(sim, form, operand):
    """A doubling of every row of the tile, both operands' columns: a
    packed point stays packed and crosses nothing, a tuple goes in and
    out through one pack and one unpack."""
    pf = PFm.pallas_field(P)
    pt = P1 if operand == "first" else P2
    want = DC.point_double(pt, DC.FpOps)
    before = PFm.layout_conversion_counts()
    if form == "packed":
        packed = pf.g1_pack_point(pt)
        mid = PFm.layout_conversion_counts(), PFm.mont_reductions_traced()
        out = pf.g1_point_dbl(packed)
        assert isinstance(out, PFm.TileForm) and out.limbs == 96
        # the kernel only, and 7 coordinates Montgomery-reduced a row:
        # what ISSUE 46's kernel time rests on
        assert mid == (PFm.layout_conversion_counts(),
                       PFm.mont_reductions_traced() - 7)
        got = pf.g1_unpack_point(out)
    else:
        got = pf.g1_point_dbl(pt)
    after = PFm.layout_conversion_counts()
    assert {k: after[k] - before[k] for k in after} == {
        "to_tiles": 1, "from_tiles": 1}
    _same(got, want)
    # the doubling keeps infinity (Z3 = 2YZ) and the curve
    zs = np.asarray(got[2])
    for r, (name, a, b) in enumerate(ROWS):
        src = a if operand == "first" else b
        assert (not zs[r].any()) == (src[2] == 0), name
        if src != TOP:
            assert GC.g1_eq(DC.g1_decode(got, r), GC.g1_double(src)), name


@pytest.mark.parametrize("with_double", [True, False])
@pytest.mark.parametrize("form", ["packed", "tuple", "mixed"])
def test_g1_point_add_equals_the_xla_form_limb_for_limb(sim, form,
                                                        with_double):
    """The whole branchless case handling: either operand at infinity,
    both, P + P (the doubling where `with_double`, the bare formulas'
    zeros where not: the same in both forms), P + (-P) -> (1, 1, 0)."""
    pf = PFm.pallas_field(P)
    want = DC.point_add(P1, P2, DC.FpOps, with_double=with_double)
    if form == "tuple":
        got = pf.g1_point_add(P1, P2, with_double)
    else:
        a = pf.g1_pack_point(P1)
        b = pf.g1_pack_point(P2) if form == "packed" else P2
        before = PFm.layout_conversion_counts()
        reduced = PFm.mont_reductions_traced()
        out = pf.g1_point_add(a, b, with_double)
        after = PFm.layout_conversion_counts()
        assert isinstance(out, PFm.TileForm) and out.limbs == 96
        # 16 reductions an addition, 7 more with the doubling fall-back
        assert PFm.mont_reductions_traced() - reduced == (
            23 if with_double else 16)
        assert after["to_tiles"] - before["to_tiles"] == int(form == "mixed")
        assert after["from_tiles"] == before["from_tiles"]
        got = pf.g1_unpack_point(out)
    _same(got, want)
    one = np.asarray(DC.FpOps.one)
    for r, (name, a, b) in enumerate(ROWS):
        dec = DC.g1_decode(got, r)
        if name == "p_minus_p":
            assert (np.asarray(got[0])[r] == one).all()
            assert (np.asarray(got[1])[r] == one).all()
            assert not np.asarray(got[2])[r].any()
        elif name == "p_plus_p" and not with_double:
            assert dec[2] == 0          # H = 0: the fall-back's absence
        elif a != TOP:
            assert GC.g1_eq(dec, GC.g1_add(a, b)), name


# -- the packed ladder -------------------------------------------------------

# the first operands' tile again (its shapes' XLA operations are compiled)
LADDER_PTS = [r[1] for r in ROWS]
# bits after the leading one: a set bit at once and after a run of
# zeros; a ladder that ends on a run of zeros; the program's two scalars
# (63 doublings and 5 or 6 additions: 1.15 s a simulated step, so slow)
SCALARS = {"0b1101": 0b1101, "0b1010": 0b1010,
           "x_abs": X_ABS, "one_minus_x": 1 - X}


def _xla_ladder(pt, k, compact):
    """The XLA ladder: `point_mul_const` itself for the 64-bit scalars;
    for the short ones its steps in its order, one eager call each (the
    kernel tests above have compiled them; a `scan` of them would
    compile for longer than the ladder runs)."""
    if k.bit_length() > 8:
        with compact_scope(compact):
            return jax.jit(
                lambda p: DC.point_mul_const(p, k, DC.FpOps))(pt)
    acc = pt
    for bit in bin(k)[3:]:
        acc = DC.point_double(acc, DC.FpOps)
        if bit == "1":
            acc = DC.point_add(acc, pt, DC.FpOps, with_double=False)
    return acc


@pytest.mark.parametrize("scalar,compact", [
    ("0b1101", True), ("0b1101", False), ("0b1010", True),
    pytest.param("0b1010", False, marks=pytest.mark.slow),
    pytest.param("x_abs", True, marks=pytest.mark.slow),
    pytest.param("x_abs", False, marks=pytest.mark.slow),
    pytest.param("one_minus_x", True, marks=pytest.mark.slow),
    pytest.param("one_minus_x", False, marks=pytest.mark.slow),
])
def test_the_packed_ladder_is_the_xla_ladder_and_the_golden_model(
        scalar, compact):
    """`point_mul_const` over `FpOps` where the platform has the kernels:
    the point packed once, fused doublings and the set bits' fused
    additions on the packed point, unpacked once."""
    from pallas_sim import sim_kernels
    k = SCALARS[scalar]
    pt = P1
    want = _xla_ladder(pt, k, compact)
    with mock.patch.object(PFm, "use_pallas", return_value=True), \
            sim_kernels(), jax.disable_jit(), compact_scope(compact):
        assert DC.g1_ladder_form() == "fused"
        before = PFm.layout_conversion_counts()
        got = DC.point_mul_const(pt, k, DC.FpOps)
        after = PFm.layout_conversion_counts()
    assert {n: after[n] - before[n] for n in after} == {
        "to_tiles": 1, "from_tiles": 1}
    for g, w in zip(got, want):
        assert (np.asarray(g) == np.asarray(w)).all()
    for r, src in enumerate(LADDER_PTS):
        if src != TOP:
            assert GC.g1_eq(DC.g1_decode(got, r), GC.g1_mul_raw(src, k))
    assert not np.asarray(got[2])[2].any()      # infinity stays infinity


def _traced_crossings(fn, *args):
    """What a trace on the Pallas path adds to the layout counters (the
    real `pallas_call`s, traced and never run)."""
    with mock.patch.object(PFm, "use_pallas", return_value=True):
        before = PFm.layout_conversion_counts()
        jaxpr = jax.make_jaxpr(fn)(*args)
        after = PFm.layout_conversion_counts()
    return {n: after[n] - before[n] for n in after}, jaxpr


@pytest.mark.parametrize("compact", [True, False])
def test_a_ladder_crosses_once_in_and_once_out_whatever_its_length(compact):
    pt = P1
    for k in (0b101, X_ABS, 1 - X):
        with compact_scope(compact):
            crossed, jaxpr = _traced_crossings(
                lambda p: DC.point_mul_const(p, k, DC.FpOps), pt)
        assert crossed == {"to_tiles": 1, "from_tiles": 1}, (compact, k)
    # the last trace, the 64-bit ladder: its steps are the two kernels
    names = set()

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                names.add(eqn.params["name"])
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)
    walk(jaxpr.jaxpr)
    assert names == {"g1_point_dbl", "g1_point_add"}


def test_the_unpacked_forms_go_through_the_same_dispatch():
    """`point_double` and `point_add` over `FpOps` (the dense per-row-bit
    ladders of the partial-signature and DKG programs, `hash_to_g1`'s
    one addition) take the fused step as tuples: a pack and an unpack a
    call, one kernel each."""
    crossed, _ = _traced_crossings(
        lambda a, b: (DC.point_double(a, DC.FpOps),
                      DC.point_add(a, b, DC.FpOps, with_double=False)),
        P1, P2)
    assert crossed == {"to_tiles": 2, "from_tiles": 2}
    # on this platform the XLA forms are the program
    assert DC.g1_ladder_form() == "generic"
    assert DC._point_kernels(DC.FpOps) is None
    assert DC._point_kernels(DC.Fp2Ops) is None


def test_the_build_says_how_the_g1_ladders_step(cache):
    """`Verifier.build`'s record and span: `g1_ladder` beside
    `miller_lines` where the program holds G1 ladders, `generic` on the
    CPU (`fused` is the chip's to show, in the benchmark's `program`
    line); a G2-signature program holds none and says nothing."""
    import drand_tpu.verify as V
    from drand_tpu import tracing

    said = {}
    for shape in (V.SHAPE_UNCHAINED_G1, V.SHAPE_UNCHAINED):
        tracing.RECORDER.clear()
        rec = StandIn(GC.G2_GEN if shape.sig_on_g1 else GC.G1_GEN,
                      shape).build(8)
        span = [s for s in tracing.RECORDER.spans()
                if s.name == "verifier.build"][-1].attrs
        said[shape.sig_on_g1] = (rec.get("g1_ladder"),
                                 span.get("g1_ladder"),
                                 rec["miller_lines"])
    assert said == {True: ("generic", "generic", "table"),
                    False: (None, None, "per_row")}

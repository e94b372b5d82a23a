"""DEFAULT-SUITE fused-kernel KATs via the eager simulator.

VERDICT r3 weak #3: the default suite never executed a fused Pallas
kernel — on CPU `use_pallas()` is False, so `pytest -q` exercised only
the pure-XLA path and a fused-kernel regression surfaced only on a
manual `--runslow` or a warm cycle.  These KATs run every fused kernel
body through tests/pallas_sim.py (eager jnp int32 semantics, bit-exact
vs the interpreter — pinned by test_pallas_field.py::
test_sim_matches_interpreter) against the golden model, with tiny tiles
so the whole file costs seconds, not the interpreter's tens of minutes.

The heavier exhaustive variants stay slow-marked in test_pallas_field.py.
"""

import random

import jax.numpy as jnp
import numpy as np
import pytest

from drand_tpu.crypto.bls12381 import fp as G
from drand_tpu.crypto.bls12381.constants import P
from drand_tpu.ops import flat12 as F
from drand_tpu.ops import pallas_field as PFm
from drand_tpu.ops import towers as T
from drand_tpu.ops.field import FP, int_to_limbs, limbs_to_int

rng = random.Random(0x5EED)


@pytest.fixture()
def sim():
    from pallas_sim import sim_kernels
    with sim_kernels():
        yield


def _r_fp12():
    return (tuple((rng.randrange(P), rng.randrange(P)) for _ in range(3)),
            tuple((rng.randrange(P), rng.randrange(P)) for _ in range(3)))


_EXT12 = (((P - 1, P - 1),) * 3, ((P - 1, P - 1),) * 3)   # all-max element


def test_sim_mont_mul_and_sqr(sim):
    pf = PFm.pallas_field(P)
    va = [rng.randrange(P) for _ in range(5)] + [0, 1, P - 1]
    vb = [rng.randrange(P) for _ in range(5)] + [P - 1, P - 1, P - 1]
    a = jnp.asarray(FP.encode(va))
    b = jnp.asarray(FP.encode(vb))
    got = np.asarray(pf.mont_mul(a, b))
    for i in range(8):
        assert FP.from_limbs_host(got[i]) == va[i] * vb[i] % P
    got = np.asarray(pf.mont_sqr(a))
    for i in range(8):
        assert FP.from_limbs_host(got[i]) == va[i] * va[i] % P


def _max_flat():
    """Flat element whose 12 STORED (Montgomery-domain) coefficients are
    all p-1 — maximizes every conv value.  The tower-built all-(p-1)
    element does NOT do this (tower->flat re-mixes coordinates), which is
    how the round-4 offset under-coverage slipped past the original KAT."""
    row = np.asarray([(P - 1 >> (12 * i)) & 0xFFF for i in range(32)],
                     np.int32)
    return np.tile(row, (12, 1))


def _unitary_fp12(seed):
    rng2 = random.Random(seed)
    f = (tuple((rng2.randrange(P), rng2.randrange(P)) for _ in range(3)),
         tuple((rng2.randrange(P), rng2.randrange(P)) for _ in range(3)))
    f = G.fp12_mul(G.fp12_conj(f), G.fp12_inv(f))
    return G.fp12_mul(G.fp12_frob_n(f, 2), f)


def test_sim_flat_sqr_wide_recombination(sim):
    """The round-4 wide-domain recombination (per-slot value-dominating
    offsets + (8,4,2,1) chain) must stay exact on adversarial inputs:
    all-max stored coefficients (maximal conv values — the case whose
    NEGATIVE slot value wrapped mod 2^768 and corrupted the first warm
    run by exactly +1), plus the exact unitary element that exposed it."""
    pf = PFm.pallas_field(P)
    rinv = pow(1 << 384, -1, P)
    mx = _max_flat()
    zs0 = _unitary_fp12(13)        # the round-4 warm-run failure value
    a = jnp.asarray(np.stack([mx, np.asarray(F.flat_encode([zs0]))[0],
                              np.asarray(F.flat_encode([_r_fp12()]))[0]]))
    out = np.asarray(pf.flat_sqr(a))
    # golden for the max element: decode stored coeffs -> tower -> square
    mx_coeffs = [(P - 1) * rinv % P] * 12
    mx_tower = F.tower_from_flat_coeffs(mx_coeffs)
    for i, x in enumerate([mx_tower, zs0,
                           F.flat_decode(jnp.asarray(a), 2)]):
        assert F.flat_decode(jnp.asarray(out), i) == G.fp12_mul(x, x), i


def test_sim_flat_mul_adversarial(sim):
    """flat_mul twin of the adversarial squaring KAT: max stored
    coefficients on BOTH operands (max conv values for the 12x12 table)."""
    pf = PFm.pallas_field(P)
    rinv = pow(1 << 384, -1, P)
    mx = _max_flat()
    out = pf.flat_mul(jnp.asarray(mx[None]), jnp.asarray(mx[None]),
                      tuple(range(12)))
    mx_tower = F.tower_from_flat_coeffs([(P - 1) * rinv % P] * 12)
    want = G.fp12_mul(mx_tower, mx_tower)
    assert F.flat_decode(jnp.asarray(np.asarray(out)), 0) == want


def test_sim_flat_mul_full_and_sparse(sim):
    pf = PFm.pallas_field(P)
    x = _r_fp12()
    ax = F.flat_encode([x])
    out = pf.flat_mul(ax, F.flat_encode([_EXT12]), tuple(range(12)))
    assert F.flat_decode(jnp.asarray(np.asarray(out)), 0) == \
        G.fp12_mul(x, _EXT12)
    # sparse line layout (Miller loop): slots {0,2,3,6,8,9}
    line_idx = (0, 2, 3, 6, 8, 9)
    coeffs = [rng.randrange(P) for _ in range(6)]
    b = np.stack([np.asarray(FP.to_mont_host(c)) for c in coeffs])[None]
    out = pf.flat_mul(ax, jnp.asarray(b), line_idx)
    bc = [0] * 12
    for i, s in enumerate(line_idx):
        bc[s] = coeffs[i]
    want = G.fp12_mul(x, F.tower_from_flat_coeffs(bc))
    assert F.flat_decode(jnp.asarray(np.asarray(out)), 0) == want


_FP12_MINUS_ONE = G.fp12_neg(G.FP12_ONE)


def _stored_extremes(seed):
    """A flat element whose 12 STORED coefficients are each 0, 1 or p-1."""
    rng2 = random.Random(seed)
    return np.stack([int_to_limbs(rng2.choice([0, 1, P - 1]))
                     for _ in range(12)])


@pytest.mark.parametrize("case", ["cyclotomic", "formula_at_bounds"])
def test_sim_cyclo_sqr(sim, case):
    """The fused Granger-Scott square (ISSUE 42: recombined wide, one
    Montgomery reduction an output coordinate).  In the cyclotomic
    subgroup (what the easy part leaves, the identity with its zero
    cells included) it is the golden model's square.  Off it the
    kernel's contract is the formula: -1 (unitary, of order 2, and so
    outside that subgroup: the formula gives 5) and inputs built to sit
    at the static bounds (every stored coefficient p-1, and mixes of 0,
    1 and p-1) are held to the XLA `flat_cyclo_sqr`, limb for limb."""
    pf = PFm.pallas_field(P)
    if case == "formula_at_bounds":
        a = jnp.asarray(np.concatenate(
            [np.asarray(F.flat_encode([_FP12_MINUS_ONE])), _max_flat()[None]]
            + [_stored_extremes(s)[None] for s in range(4)]))
        assert FP._pallas() is None                 # the XLA form
        want = np.asarray(F.flat_cyclo_sqr(a))
        assert (np.asarray(pf.cyclo_sqr(a)) == want).all()
        return
    zs = [_unitary_fp12(13), _unitary_fp12(42), G.FP12_ONE]
    out = np.asarray(pf.cyclo_sqr(jnp.asarray(F.flat_encode(zs))))
    assert ((0 <= out) & (out < 4096)).all()
    for i, z in enumerate(zs):
        assert F.flat_decode(jnp.asarray(out), i) == G.fp12_mul(z, z), i


def test_cyclo_sqr_traces_twelve_reductions():
    """The count that says the mechanism is in the program: a trace of
    the real `pallas_call` (nothing runs) reduces 12 coordinates a
    launch, one an output; reducing the nine Fp2 squares first took 18."""
    import jax
    pf = PFm.PallasField(P)                # its own launchers: traced here
    before = PFm.mont_reductions_traced()
    out = jax.eval_shape(pf.cyclo_sqr,
                         jax.ShapeDtypeStruct((8, 12, 32), jnp.int32))
    assert out.shape == (8, 12, 32)
    assert PFm.mont_reductions_traced() - before == 12


def test_cyclo_sqr_bounds_are_asserted_at_build():
    """`_cyclo_sqr_plan` holds every bound of the wide recombination as
    an assertion on exact integers: its own offsets pass, cover what each
    output subtracts in value and limb by limb, and an offset one p^2
    short of the subtracted value, or no multiple of p, fails the build."""
    pf = PFm.PallasField(P)
    offs, subs = pf._cyclo_sqr_plan()
    assert subs == (4, 2, 1) and len(offs) == len(PFm._CYCLO_SQR_OUTPUTS)
    assert sum(len(o[2]) for o in PFm._CYCLO_SQR_OUTPUTS) == 12
    assert pf._cyclo_sqr_check(offs) == subs
    value = limbs_to_int
    for out, off in zip(PFm._CYCLO_SQR_OUTPUTS, offs):
        sub_value, add_value, sub_limbs, _ = pf._cyclo_sqr_ranges(out)
        assert value(off) % (P * P) == 0
        assert value(off) >= sub_value > 0
        assert all(o >= s for o, s in zip(off, sub_limbs))
        # with the reduce's own m * p / R, about 1.04 p, on top: between
        # 4p and 8p, so no shorter chain would do
        assert 4 * P < ((value(off) + add_value) >> 384) + P < 8 * P

    def with_offset(i, off):
        return offs[:i] + (tuple(off),) + offs[i + 1:]

    for i, out in enumerate(PFm._CYCLO_SQR_OUTPUTS):
        sub_value = pf._cyclo_sqr_ranges(out)[0]
        short, _ = T.wide_neg_offset(1, min_value=sub_value - P * P)
        assert value(short) < sub_value
        with pytest.raises(AssertionError):
            pf._cyclo_sqr_check(with_offset(i, short))
        bent = list(offs[i])
        bent[0] += 1                                # value no multiple of p
        with pytest.raises(AssertionError):
            pf._cyclo_sqr_check(with_offset(i, bent))
    with pytest.raises(AssertionError):
        pf._cyclo_sqr_check(offs[:-1])


def test_sim_sqr4_mul_lazy(sim):
    """The 4 inner squarings run LAZY (round 4): canonical in/out must
    hold including the p-1 edge."""
    pf = PFm.pallas_field(P)
    va = [rng.randrange(P) for _ in range(2)] + [0, P - 1]
    vt = [rng.randrange(P) for _ in range(3)] + [P - 1]
    a = jnp.asarray(FP.encode(va))
    t = jnp.asarray(FP.encode(vt))
    got = np.asarray(pf.sqr4_mul(a, t))
    for i in range(4):
        assert FP.from_limbs_host(got[i]) == pow(va[i], 16, P) * vt[i] % P


def test_sim_fp2_sqr5_mul(sim):
    """Fused Fp2 chain step (round 4): res^32 * t with lazy inner
    squarings — the body of the direct sqrt/sqrt_ratio chains."""
    pf = PFm.pallas_field(P)
    xs = [(rng.randrange(P), rng.randrange(P)), (P - 1, P - 1), (0, 0)]
    ts = [(rng.randrange(P), rng.randrange(P)) for _ in range(2)] + \
        [(P - 1, P - 1)]
    r0, r1 = pf.fp2_sqr5_mul(T.fp2_encode(xs), T.fp2_encode(ts))
    for i in range(3):
        want = G.fp2_mul(G.fp2_pow(xs[i], 32), ts[i])
        got = (FP.from_limbs_host(np.asarray(r0)[i]),
               FP.from_limbs_host(np.asarray(r1)[i]))
        assert got == want


def test_sim_tileform_parity(sim):
    """TileForm-threaded calls must be bit-identical to the plain-array
    wrappers (same kernels, relayout skipped)."""
    pf = PFm.pallas_field(P)
    va = [rng.randrange(P) for _ in range(3)] + [P - 1]
    vt = [rng.randrange(P) for _ in range(4)]
    a = jnp.asarray(FP.encode(va))
    t = jnp.asarray(FP.encode(vt))
    ta, tt = pf.tile(a), pf.tile(t)
    assert (np.asarray(pf.untile(ta)) == np.asarray(a)).all()
    for name, plain, tiled in [
            ("mont_mul", pf.mont_mul(a, t), pf.mont_mul(ta, tt)),
            ("sqr4_mul", pf.sqr4_mul(a, t), pf.sqr4_mul(ta, tt)),
            ("mont_sqr", pf.mont_sqr(a), pf.mont_sqr(ta))]:
        assert isinstance(tiled, PFm.TileForm), name
        assert (np.asarray(pf.untile(tiled)) == np.asarray(plain)).all(), \
            name
    # flat ops in the packed 12*32 layout
    ax = jnp.asarray(F.flat_encode([_r_fp12()]))
    ft = pf.tile(ax.reshape(ax.shape[:-2] + (12 * 32,)), 12 * 32)
    got = pf.untile(pf.flat_sqr(ft)).reshape(ax.shape)
    assert (np.asarray(got) == np.asarray(pf.flat_sqr(ax))).all()
    got = pf.untile(pf.flat_mul(ft, ax, tuple(range(12)))).reshape(ax.shape)
    assert (np.asarray(got) ==
            np.asarray(pf.flat_mul(ax, ax, tuple(range(12))))).all()


# ---------------------------------------------------------------------------
# ISSUE 9: TileForm seam, packed glue, sparse line merge, merged Miller
# iteration.  The tier-1 subset below stays lean (seconds); the heavy
# merged-iteration parity set is slow-marked and runs in full via the
# check.sh merged-kernel stage (`pytest tests/test_sim_kats.py --runslow`).
# ---------------------------------------------------------------------------


def test_tileform_wrap_unwrap_roundtrip():
    """TileForm.wrap/unwrap: exact roundtrip across shapes/limb widths,
    layout-preserving concat/split, pytree registration, and the
    conversion counters (the accounting the tile-seam lint rule
    protects).  No kernels — runs at production TILE/_ROW."""
    import jax

    PFm.reset_layout_conversions()
    base = PFm.layout_conversion_counts()
    assert base == {"to_tiles": 0, "from_tiles": 0}
    for shape, limbs in [((), 32), ((3,), 32), ((2, 5), 64),
                         ((1,), 12 * 32), ((2049,), 32)]:
        a = jnp.asarray(
            np.random.RandomState(1).randint(0, 4096, shape + (limbs,),
                                             dtype=np.int32))
        tf = PFm.TileForm.wrap(a, limbs)
        assert tf.shape == shape and tf.limbs == limbs
        assert PFm.TileForm.wrap(tf, limbs) is tf      # no double-wrap
        back = np.asarray(tf.unwrap())
        assert back.shape == shape + (limbs,)
        assert (back == np.asarray(a)).all(), (shape, limbs)
    c = PFm.layout_conversion_counts()
    assert c["to_tiles"] == 5 and c["from_tiles"] == 5
    # concat/split along the limb axis never cross the boundary
    x = PFm.TileForm.wrap(jnp.ones((4, 32), jnp.int32))
    y = PFm.TileForm.wrap(jnp.zeros((4, 32), jnp.int32))
    cat = PFm.tile_concat([x, y])
    assert cat.limbs == 64
    xs, ys = PFm.tile_split(cat, [32, 32])
    assert (np.asarray(xs.tiles) == np.asarray(x.tiles)).all()
    assert (np.asarray(ys.tiles) == np.asarray(y.tiles)).all()
    assert PFm.layout_conversion_counts()["to_tiles"] == c["to_tiles"] + 2
    # pytree: scan/cond carry TileForm unchanged
    leaves, treedef = jax.tree_util.tree_flatten(cat)
    assert len(leaves) == 1
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.shape == cat.shape and back.b == cat.b


def test_sim_packed_glue_and_products(sim):
    """Packed-Fp2 tile glue: eq/select/mask wrap-unwrap semantics and
    the packed fp2_products/fp2_sqrs fast path vs the plain-array path
    (same kernel, zero-crossing operands)."""
    pf = PFm.pallas_field(P)
    xs = [(rng.randrange(P), rng.randrange(P)) for _ in range(2)]
    ys = [(rng.randrange(P), rng.randrange(P)) for _ in range(2)]
    ax, ay = T.fp2_encode(xs), T.fp2_encode(ys)
    plain = pf.fp2_products([(ax, ay)])
    packed = pf.fp2_products([(pf.fp2_pack(ax), pf.fp2_pack(ay))])
    assert isinstance(packed[0], PFm.TileForm)
    for pc, pl_ in zip(pf.fp2_unpack(packed[0]), plain[0]):
        assert (np.asarray(pc) == np.asarray(pl_)).all()
    sq_plain = pf.fp2_sqrs([ax])
    sq_packed = pf.fp2_sqrs([pf.fp2_pack(ax)])
    for pc, pl_ in zip(pf.fp2_unpack(sq_packed[0]), sq_plain[0]):
        assert (np.asarray(pc) == np.asarray(pl_)).all()
    # eq/select/mask roundtrip
    a1 = pf.fp2_pack(T.fp2_encode(xs))
    a2 = pf.fp2_pack(T.fp2_encode([xs[0], (1, 2)]))
    eq = pf.fp2_eq_tiles(a1, a2)
    assert np.asarray(pf.mask_unwrap(eq, a1.shape, a1.b)).tolist() == \
        [True, False]
    sel = pf.fp2_select_tiles(eq, a2, a1)
    got = pf.fp2_unpack(sel)
    assert T.fp2_decode(got, 0) == xs[0]
    assert T.fp2_decode(got, 1) == xs[1]      # mask False keeps a1
    mw = pf.mask_wrap(jnp.asarray([False, True]), (2,))
    assert np.asarray(pf.mask_unwrap(mw, (2,), 2)).tolist() == \
        [False, True]


def test_sim_fp2_pow_const_packed(sim):
    """fp2_pow_const keeps a packed input packed end to end (the chain
    form sqrt_cand/sqrt_ratio thread), small-exponent branch."""
    from unittest import mock
    pf = PFm.pallas_field(P)
    xs = [(rng.randrange(P), rng.randrange(P)) for _ in range(2)]
    with mock.patch.object(PFm, "use_pallas", return_value=True):
        out = T.fp2_pow_const(pf.fp2_pack(T.fp2_encode(xs)), 29)
        assert isinstance(out, PFm.TileForm)
        arr = pf.fp2_unpack(out)
    for i, x in enumerate(xs):
        assert T.fp2_decode(arr, i) == G.fp2_pow(x, 29)


def _rand_line():
    return [rng.randrange(P) for _ in range(6)]


def _line_tower(cs):
    full = [0] * 12
    for i, s in enumerate(PFm.LINE_IDX):
        full[s] = cs[i]
    return F.tower_from_flat_coeffs(full)


def _enc_line(cs):
    from drand_tpu.ops.field import FP as _FP
    return jnp.asarray(
        np.stack([np.asarray(_FP.to_mont_host(c)) for c in cs])[None])


def test_sim_line_merge_product(sim):
    """Sparse-sparse line merge (ISSUE 9 lever 3): the dense product of
    two sparse flat lines vs the golden tower multiply."""
    pf = PFm.pallas_field(P)
    l1c, l2c = _rand_line(), _rand_line()
    out = pf.line_merge(_enc_line(l1c), _enc_line(l2c))
    want = G.fp12_mul(_line_tower(l1c), _line_tower(l2c))
    assert F.flat_decode(jnp.asarray(np.asarray(out)), 0) == want


# -- merged Miller-iteration parity (heavy: the check.sh merged-kernel
#    stage and --runslow run these; each kernel call is ~1 min of eager
#    simulation) --------------------------------------------------------


def _miller_state(B=2):
    from drand_tpu.crypto.bls12381 import curve as GC
    from drand_tpu.crypto.bls12381.constants import R
    ts = [[GC.g2_mul(GC.G2_GEN, rng.randrange(1, R)) for _ in range(B)]
          for _ in range(2)]
    qs = [[GC.g2_affine(GC.g2_mul(GC.G2_GEN, rng.randrange(1, R)))
           for _ in range(B)] for _ in range(2)]
    ps = [[GC.g1_affine(GC.g1_mul(GC.G1_GEN, rng.randrange(1, R)))
           for _ in range(B)] for _ in range(2)]
    Tj = [tuple(T.fp2_encode([t[k] for t in ts[i]]) for k in range(3))
          for i in range(2)]
    Q = [tuple(T.fp2_encode([q[k] for q in qs[i]]) for k in range(2))
         for i in range(2)]
    from drand_tpu.ops.field import FP as _FP
    Pc = [(jnp.asarray(_FP.encode([p[0] for p in ps[i]])),
           jnp.asarray(_FP.encode([p[1] for p in ps[i]])))
          for i in range(2)]
    f0 = jnp.asarray(F.flat_encode(
        [(tuple((rng.randrange(P), rng.randrange(P)) for _ in range(3)),
          tuple((rng.randrange(P), rng.randrange(P)) for _ in range(3)))
         for _ in range(B)]))
    masks = [np.array([True, False][:B] + [True] * max(0, B - 2)),
             np.array([True] * B)]
    return Tj, Q, Pc, f0, masks


def _pack_miller(pf, Tj, Q, Pc, f0, masks):
    B = f0.shape[0]
    Tt = pf.pack_coords(
        [Tj[0][0][0], Tj[0][0][1], Tj[0][1][0], Tj[0][1][1],
         Tj[0][2][0], Tj[0][2][1],
         Tj[1][0][0], Tj[1][0][1], Tj[1][1][0], Tj[1][1][1],
         Tj[1][2][0], Tj[1][2][1]])
    Qt = pf.pack_coords(
        [Q[0][0][0], Q[0][0][1], Q[0][1][0], Q[0][1][1],
         Q[1][0][0], Q[1][0][1], Q[1][1][0], Q[1][1][1]])
    Pt = pf.pack_coords([Pc[0][0], Pc[0][1], Pc[1][0], Pc[1][1]])
    Mt = PFm.TileForm.wrap(
        jnp.stack([jnp.asarray(m) for m in masks], -1).astype(jnp.int32),
        2)
    ft = pf.tile(f0.reshape(B, 12 * 32), 12 * 32)
    return ft, Tt, Qt, Pt, Mt


def _ref_dbl_iter(Tj, Pc, f0, masks):
    from drand_tpu.ops import pairing as DP
    f2 = F.flat_sqr(f0)
    newTs = []
    for k in range(2):
        T2x, line = DP._dbl_step(Tj[k], Pc[k][0], Pc[k][1])
        newTs.append(T2x)
        m = jnp.asarray(masks[k])
        line = DP.line_select(m, line, DP.line_one(m.shape))
        f2 = DP.fp12_mul_line(f2, line)
    return f2, newTs


def _ref_add_iter(Tj, Q, Pc, f0, masks):
    from drand_tpu.ops import pairing as DP
    out = f0
    newTs = []
    for k in range(2):
        A2x, line = DP._add_step(Tj[k], Q[k], Pc[k][0], Pc[k][1])
        m = jnp.asarray(masks[k])
        sel = tuple(T.fp2_select(m, x, y) for x, y in zip(A2x, Tj[k]))
        newTs.append(sel)
        line = DP.line_select(m, line, DP.line_one(m.shape))
        out = DP.fp12_mul_line(out, line)
    return out, newTs


def _assert_point_pack(pf, To, refTs):
    got = pf.unpack_coords(To, 12)
    for k, Tref in enumerate(refTs):
        refc = [Tref[0][0], Tref[0][1], Tref[1][0], Tref[1][1],
                Tref[2][0], Tref[2][1]]
        for ci in range(6):
            assert (np.asarray(got[k * 6 + ci]) ==
                    np.asarray(refc[ci])).all(), (k, ci)


@pytest.mark.slow
@pytest.mark.parametrize("line_merge", [True, False],
                         ids=["linemerge", "seqmul"])
def test_sim_miller_dbl_iter_merged(sim, line_merge):
    """The merged doubling-iteration kernel vs the trio composition
    (flat_sqr -> stacked dbl step -> two masked line multiplies), both
    line-multiply variants — bit-identical f' AND T' states."""
    pf = PFm.pallas_field(P)
    Tj, Q, Pc, f0, masks = _miller_state()
    fr, Tsr = _ref_dbl_iter(Tj, Pc, f0, masks)
    ft, Tt, Qt, Pt, Mt = _pack_miller(pf, Tj, Q, Pc, f0, masks)
    before = PFm.layout_conversion_counts()
    fo, To = pf.miller_dbl_iter(ft, Tt, Pt, Mt, line_merge=line_merge)
    # the residency contract: a merged iteration on packed state crosses
    # the layout boundary ZERO times
    assert PFm.layout_conversion_counts() == before
    got_f = np.asarray(pf.untile(fo).reshape(f0.shape))
    assert (got_f == np.asarray(fr)).all()
    _assert_point_pack(pf, To, Tsr)


@pytest.mark.slow
@pytest.mark.parametrize("line_merge", [True, False],
                         ids=["linemerge", "seqmul"])
def test_sim_miller_add_iter_merged(sim, line_merge):
    """The merged addition-step kernel vs the composition (stacked add
    step -> masked T select -> two masked line multiplies)."""
    pf = PFm.pallas_field(P)
    Tj, Q, Pc, f0, masks = _miller_state()
    fr, Tsr = _ref_add_iter(Tj, Q, Pc, f0, masks)
    ft, Tt, Qt, Pt, Mt = _pack_miller(pf, Tj, Q, Pc, f0, masks)
    fo, To = pf.miller_add_iter(ft, Tt, Qt, Pt, Mt,
                                line_merge=line_merge)
    got_f = np.asarray(pf.untile(fo).reshape(f0.shape))
    assert (got_f == np.asarray(fr)).all()
    _assert_point_pack(pf, To, Tsr)


@pytest.mark.slow
def test_sim_miller_executor_mini_ladder(sim, monkeypatch):
    """The merged EXECUTOR (_miller_loop_pairs_merged: packing order,
    masks, ladder wiring, final conj) vs the trio executor on a
    truncated parameter ladder — both paths patched to the same 2-step
    segment list so the whole comparison costs ~2 iterations."""
    import jax
    from unittest import mock

    from drand_tpu.ops import pairing as DP
    mini = [(0, True)]                     # one dbl + one add step
    monkeypatch.setattr(DP, "_X_SEGMENTS", mini)
    Tj, Q, Pc, f0, masks = _miller_state()
    pairs = [(Pc[k], Q[k]) for k in range(2)]
    active = [jnp.asarray(m) for m in masks]
    # reference: the XLA executor (pf None on CPU), eager and fast
    ref = np.asarray(DP.miller_loop_pairs(pairs, active))
    with mock.patch.object(PFm, "use_pallas", return_value=True), \
            jax.disable_jit():
        monkeypatch.setenv("DRAND_TPU_MILLER_MERGED", "1")
        merged = np.asarray(F.flat_untile(
            DP.miller_loop_pairs(pairs, active)))
    assert (merged == ref).all()


@pytest.mark.slow
def test_sim_flat_conj_frob_inv_packed(sim):
    """Packed flat_conj / flat_frob / flat_inv / flat_is_one vs the XLA
    forms — the final-exponentiation residency pieces."""
    from unittest import mock
    vals = [(tuple((rng.randrange(P), rng.randrange(P)) for _ in range(3)),
             tuple((rng.randrange(P), rng.randrange(P)) for _ in range(3)))]
    ax = F.flat_encode(vals)
    ref_conj = np.asarray(F.flat_conj(ax))
    ref_frob = np.asarray(F.flat_frob(ax, 2))
    ref_inv = np.asarray(F.flat_inv(ax))
    with mock.patch.object(PFm, "use_pallas", return_value=True):
        ft = F.flat_tile(ax)
        assert isinstance(ft, PFm.TileForm)
        got = np.asarray(F.flat_untile(F.flat_conj(ft)).reshape(ax.shape))
        assert (got == ref_conj).all()
        got = np.asarray(F.flat_untile(F.flat_frob(ft, 2)
                                       ).reshape(ax.shape))
        assert (got == ref_frob).all()
        got = np.asarray(F.flat_untile(F.flat_inv(ft)).reshape(ax.shape))
        assert (got == ref_inv).all()
        one = F.flat_broadcast(F.FLAT_ONE, (1,))
        mixed = jnp.concatenate([one, ax], 0)
        assert np.asarray(F.flat_is_one(F.flat_tile(mixed))).tolist() == \
            [True, False]


@pytest.mark.slow
def test_sim_packed_g2_ladder(sim):
    """point_mul_const's tile-resident G2 ladder (pack once, fused
    kernels across the scan, unpack once) vs the golden scalar mul."""
    import jax
    from unittest import mock

    from drand_tpu.crypto.bls12381 import curve as GC
    from drand_tpu.crypto.bls12381.constants import R
    from drand_tpu.ops import curve as DC
    k = 11
    pts = [GC.g2_mul(GC.G2_GEN, rng.randrange(1, R)) for _ in range(2)]
    ref = [GC.g2_mul(p, k) for p in pts]
    ptd = tuple(T.fp2_encode([p[i] for p in pts]) for i in range(3))
    PFm.reset_layout_conversions()
    with mock.patch.object(PFm, "use_pallas", return_value=True), \
            jax.disable_jit():
        out = DC.point_mul_const(ptd, k, DC.Fp2Ops)
    for i in range(2):
        assert GC.point_eq(DC.g2_decode(out, i), ref[i], GC.FP2_OPS), i
    c = PFm.layout_conversion_counts()
    # residency invariant: ONE pack at ladder entry, ONE unpack at exit
    assert c["to_tiles"] == 1 and c["from_tiles"] == 1, c


def test_sim_miller_step_kernels(sim):
    """Fused g2_dbl_line/g2_add_line on packed state vs the XLA steps
    (CPU oracle), every row active."""
    from drand_tpu.crypto.bls12381 import curve as GC
    from drand_tpu.crypto.bls12381.constants import R
    from pallas_sim import assert_line_steps_match_xla
    pf = PFm.pallas_field(P)
    ts = [GC.g2_mul(GC.G2_GEN, rng.randrange(1, R))]
    qs = [GC.g2_affine(GC.g2_mul(GC.G2_GEN, rng.randrange(1, R)))]
    ps = [GC.g1_affine(GC.g1_mul(GC.G1_GEN, rng.randrange(1, R)))]
    Tj = tuple(T.fp2_encode([t[k] for t in ts]) for k in range(3))
    Q = tuple(T.fp2_encode([q[k] for q in qs]) for k in range(2))
    xp = jnp.asarray(FP.encode([p[0] for p in ps]))
    yp = jnp.asarray(FP.encode([p[1] for p in ps]))
    assert_line_steps_match_xla(pf, Tj, Q, xp, yp, [True])

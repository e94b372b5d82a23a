"""Parity KATs for the fused Pallas field kernels.

The CPU test suite forces the pure-XLA path, so without these the Pallas
kernels (the path ALL TPU field math routes through) would only be
exercised on real hardware.  `interpret=True` runs the kernel body under
the Pallas interpreter on CPU — slow but bit-exact.
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from drand_tpu.crypto.bls12381.constants import P, R
from drand_tpu.ops import pallas_field as PFm
from drand_tpu.ops.field import FP, FR, int_to_limbs

pytestmark = pytest.mark.slow   # interpreter-mode kernels: ~10 min

rng = random.Random(0xA110C)


@pytest.fixture(scope="module")
def interp():
    """Route pallas_call through the interpreter for this module, with a
    tiny tile so the ~6k-op kernel body interprets in seconds."""
    import functools
    orig_call = PFm.pl.pallas_call
    orig_tile, orig_row = PFm.TILE, PFm._ROW
    PFm.pl.pallas_call = functools.partial(orig_call, interpret=True)
    PFm.TILE, PFm._ROW = 8, (1, 8)
    PFm._CACHE.clear()
    yield
    PFm.pl.pallas_call = orig_call
    PFm.TILE, PFm._ROW = orig_tile, orig_row
    PFm._CACHE.clear()


def _vals(n, mod):
    return [rng.randrange(mod) for _ in range(n - 3)] + [0, 1, mod - 1]


@pytest.mark.parametrize("field,mod", [(FP, P), (FR, R)], ids=["fp", "fr"])
def test_pallas_mont_mul_matches_xla(interp, field, mod):
    pf = PFm.PallasField(mod)
    n = 16
    va, vb = _vals(n, mod), _vals(n, mod)
    a = jnp.asarray(field.encode(va))
    b = jnp.asarray(field.encode(vb))
    got = np.asarray(pf.mont_mul(a, b))
    want = np.asarray(field.mont_mul(a, b))
    assert (got[:n] == want).all()
    for i in range(n):
        assert field.from_limbs_host(got[i]) == va[i] * vb[i] % mod


def test_pallas_fp2_products_matches_golden(interp):
    from drand_tpu.crypto.bls12381 import fp as G
    from drand_tpu.ops import towers as T
    pf = PFm.PallasField(P)
    n = 2
    xs = [(rng.randrange(P), rng.randrange(P)) for _ in range(n)]
    ys = [(rng.randrange(P), rng.randrange(P)) for _ in range(n)]
    pairs = [(T.fp2_encode([x]), T.fp2_encode([y]))
             for x, y in zip(xs, ys)]
    out = pf.fp2_products(pairs)
    for i in range(n):
        got = (FP.from_limbs_host(np.asarray(out[i][0])[0]),
               FP.from_limbs_host(np.asarray(out[i][1])[0]))
        assert got == G.fp2_mul(xs[i], ys[i])


def test_pallas_flat_mul_matches_golden(interp):
    from drand_tpu.crypto.bls12381 import fp as G
    from drand_tpu.ops import flat12 as F
    pf = PFm.PallasField(P)

    def r_fp12():
        return (tuple((rng.randrange(P), rng.randrange(P))
                      for _ in range(3)),
                tuple((rng.randrange(P), rng.randrange(P))
                      for _ in range(3)))

    x, y = r_fp12(), r_fp12()
    ax, ay = F.flat_encode([x]), F.flat_encode([y])
    out = pf.flat_mul(ax, ay, tuple(range(12)))
    assert F.flat_decode(jnp.asarray(np.asarray(out)), 0) == \
        G.fp12_mul(x, y)


def test_pallas_mont_reduce_matches_xla(interp):
    """Regression KAT for the round-2 wrapper bug: mont_reduce's host
    wrapper allocated a 64-limb output block while the kernel writes
    N_LIMBS rows, scrambling every element after the first (fixed in
    round 3 by passing N_LIMBS as limbs_out)."""
    pf = PFm.PallasField(P)
    n = 8
    # wide inputs shaped like flat12's conv output: sums of <=12 products
    wides = []
    for _ in range(n):
        acc = 0
        for _ in range(12):
            acc += rng.randrange(P) * rng.randrange(P)
        wides.append(acc)
    t = np.zeros((n, 64), np.int32)
    for i, w in enumerate(wides):
        for c in range(64):
            t[i, c] = (w >> (12 * c)) & 0xFFF
    tj = jnp.asarray(t)
    got = np.asarray(pf.mont_reduce(tj))
    want = np.asarray(FP.mont_reduce(tj))
    assert (got[:n] == want).all()
    rinv = pow(1 << 384, -1, P)
    for i in range(n):
        assert FP.from_limbs_host(got[i], mont=False) == \
            wides[i] * rinv % P

@pytest.mark.parametrize("field,mod", [(FP, P), (FR, R)], ids=["fp", "fr"])
def test_pallas_mont_sqr_matches_xla(interp, field, mod):
    pf = PFm.PallasField(mod)
    n = 16
    va = _vals(n, mod)
    a = jnp.asarray(field.encode(va))
    got = np.asarray(pf.mont_sqr(a))
    want = np.asarray(field.mont_mul(a, a))
    assert (got[:n] == want).all()
    for i in range(n):
        assert field.from_limbs_host(got[i]) == va[i] * va[i] % mod


def _r_fp12():
    return (tuple((rng.randrange(P), rng.randrange(P)) for _ in range(3)),
            tuple((rng.randrange(P), rng.randrange(P)) for _ in range(3)))


@pytest.fixture()
def sim():
    """Eager-mode kernel simulator (tests/pallas_sim.py): bit-exact jnp
    int32 semantics without the tens-of-minutes XLA:CPU compile the true
    interpreter costs for the big fused kernels on this 1-core host.
    test_sim_matches_interpreter pins sim == interpreter on a shared
    kernel."""
    from pallas_sim import sim_kernels
    with sim_kernels():
        yield


def test_sim_matches_interpreter(interp):
    """Cross-check: the eager simulator and the real Pallas interpreter
    agree on a full fused kernel (mont_mul) over edge-case values."""
    from pallas_sim import sim_kernels
    n = 8
    va, vb = _vals(n, P), _vals(n, P)
    a = jnp.asarray(FP.encode(va))
    b = jnp.asarray(FP.encode(vb))
    got_interp = np.asarray(PFm.pallas_field(P).mont_mul(a, b))
    with sim_kernels(tile=PFm.TILE, row=PFm._ROW):
        got_sim = np.asarray(PFm.pallas_field(P).mont_mul(a, b))
    assert (got_interp == got_sim).all()


def test_pallas_flat_sqr_matches_golden(sim):
    """Slot-symmetric squaring kernel vs golden fp12_mul(x, x)."""
    from drand_tpu.crypto.bls12381 import fp as G
    from drand_tpu.ops import flat12 as F
    pf = PFm.PallasField(P)
    xs = [_r_fp12(), _r_fp12()]
    ax = F.flat_encode(xs)
    out = np.asarray(pf.flat_sqr(jnp.asarray(ax)))
    for i, x in enumerate(xs):
        assert F.flat_decode(jnp.asarray(out), i) == G.fp12_mul(x, x)


def _cyclotomic(n):
    """Outputs of the final exponentiation's easy part."""
    from drand_tpu.crypto.bls12381 import fp as G
    zs = []
    for _ in range(n):
        f = _r_fp12()
        # easy part makes it unitary: f^(p^6-1) then ^(p^2+1)
        f = G.fp12_mul(G.fp12_conj(f), G.fp12_inv(f))
        zs.append(G.fp12_mul(G.fp12_frob_n(f, 2), f))
    return zs


@pytest.mark.parametrize("case", ["random", "identity", "formula_at_bounds",
                                  "pow_x_abs", "final_exp"])
def test_pallas_cyclo_sqr_matches_golden(sim, case):
    """Fused Granger-Scott kernel (ISSUE 42: recombined in the wide
    domain, one Montgomery reduction an output) vs golden fp12_mul(z, z)
    on elements of the cyclotomic subgroup (outputs of the final-exp easy
    part; the identity, whose cells hold zeros); off that subgroup, where
    its contract is the formula, vs the XLA flat_cyclo_sqr on -1 (the
    formula gives 5) and on inputs at the static bounds (every stored
    coefficient p-1, mixes of 0, 1 and p-1); and through its callers:
    `_unitary_pow_x_abs` (63 chained squarings, 5 multiplies) and the
    whole `final_exp`, bit for bit the golden model's."""
    from unittest import mock

    from drand_tpu.crypto.bls12381 import fp as G
    from drand_tpu.crypto.bls12381 import pairing as GP
    from drand_tpu.ops import flat12 as F
    from drand_tpu.ops import pairing as DP
    from drand_tpu.ops.field import compact_scope
    pf = PFm.PallasField(P)
    if case in ("random", "identity"):
        zs = _cyclotomic(2) if case == "random" else [G.FP12_ONE]
        out = np.asarray(pf.cyclo_sqr(jnp.asarray(F.flat_encode(zs))))
        for i, z in enumerate(zs):
            assert F.flat_decode(jnp.asarray(out), i) == G.fp12_mul(z, z)
    elif case == "formula_at_bounds":
        minus_one = G.fp12_neg(G.FP12_ONE)
        stored = [[P - 1] * 12, [0] * 12, [P - 1] * 6 + [0] * 6,
                  [0] * 6 + [P - 1] * 6]
        stored += [[rng.choice([0, 1, P - 1]) for _ in range(12)]
                   for _ in range(3)]
        a = jnp.asarray(np.concatenate(
            [np.asarray(F.flat_encode([minus_one]))]
            + [np.stack([int_to_limbs(v) for v in vs])[None]
               for vs in stored]))
        want = np.asarray(F.flat_cyclo_sqr(a))      # CPU: the XLA form
        assert (np.asarray(pf.cyclo_sqr(a)) == want).all()
    else:
        z = _cyclotomic(1)[0] if case == "pow_x_abs" else _r_fp12()
        a = jnp.asarray(F.flat_encode([z]))
        with mock.patch.object(PFm, "use_pallas", return_value=True), \
                jax.disable_jit(), compact_scope(True):
            if case == "pow_x_abs":
                got = F.flat_untile(DP._unitary_pow_x_abs(F.flat_tile(a)))
                want = G.fp12_pow(z, DP._X_ABS)
            else:
                got = F.flat_untile(DP.final_exp(F.flat_tile(a)))
                want = GP.final_exp(z)
        assert F.flat_decode(jnp.asarray(np.asarray(got)), 0) == want


def test_pallas_miller_step_kernels_match_xla(sim):
    """Fused g2_dbl_line/g2_add_line on packed state vs the XLA
    _dbl_step/_add_step (identical formulas; the CPU suite keeps
    use_pallas() False so the XLA path is the oracle), with one row of
    the two inactive: its line the neutral one, its T kept on the
    addition."""
    from drand_tpu.crypto.bls12381 import curve as GC
    from drand_tpu.crypto.bls12381.constants import R
    from drand_tpu.ops import towers as T
    from pallas_sim import assert_line_steps_match_xla
    pf = PFm.PallasField(P)
    ts = [GC.g2_mul(GC.G2_GEN, rng.randrange(1, R)) for _ in range(2)]
    qs = [GC.g2_affine(GC.g2_mul(GC.G2_GEN, rng.randrange(1, R)))
          for _ in range(2)]
    ps = [GC.g1_affine(GC.g1_mul(GC.G1_GEN, rng.randrange(1, R)))
          for _ in range(2)]
    Tj = tuple(T.fp2_encode([t[k] for t in ts]) for k in range(3))
    Q = tuple(T.fp2_encode([q[k] for q in qs]) for k in range(2))
    xp = jnp.asarray(FP.encode([p[0] for p in ps]))
    yp = jnp.asarray(FP.encode([p[1] for p in ps]))
    assert_line_steps_match_xla(pf, Tj, Q, xp, yp, [True, False])


def test_pallas_point_kernels_match_xla(sim):
    """Fused g2_point_dbl/g2_point_add vs curve.point_double/point_add,
    including the branchless edge cases (infinity operands, P + P with
    the doubling fallback, P + (-P) cancellation)."""
    from drand_tpu.crypto.bls12381 import curve as GC
    from drand_tpu.crypto.bls12381.constants import R
    from drand_tpu.ops import curve as DC
    from drand_tpu.ops import towers as T
    pf = PFm.PallasField(P)

    def enc(pts):
        return tuple(T.fp2_encode([p[k] for p in pts]) for k in range(3))

    def assert_same(a, b):
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            assert (np.asarray(x) == np.asarray(y)).all()

    a1 = GC.g2_mul(GC.G2_GEN, rng.randrange(1, R))
    a2 = GC.g2_mul(GC.G2_GEN, rng.randrange(1, R))
    inf = ((1, 0), (1, 0), (0, 0))
    cases1 = [a1, a1, a1, inf, a2]
    cases2 = [a2, a1, GC.g2_neg(a1), a2, inf]
    p1d, p2d = enc(cases1), enc(cases2)
    assert_same(DC.point_add(p1d, p2d, DC.Fp2Ops, with_double=True),
                pf.g2_point_add(p1d, p2d, True))
    keep = (0, 2, 3, 4)     # drop P + P, undefined without the fallback
    p1n = enc([cases1[i] for i in keep])
    p2n = enc([cases2[i] for i in keep])
    assert_same(DC.point_add(p1n, p2n, DC.Fp2Ops, with_double=False),
                pf.g2_point_add(p1n, p2n, False))
    assert_same(DC.point_double(p1d, DC.Fp2Ops), pf.g2_point_dbl(p1d))


def test_pallas_g1_point_kernels_match_xla(sim):
    """The G1 twin (ISSUE 46): fused g1_point_dbl/g1_point_add vs
    curve.point_double/point_add over Fp, with the same edge cases, under
    the simulator as the G2 test above (the real interpreter's XLA:CPU
    compile of these bodies ran past half an hour in the sandbox;
    tier-1 holds more rows: tests/test_g1_point_kernels.py)."""
    from drand_tpu.crypto.bls12381 import curve as GC
    from drand_tpu.ops import curve as DC
    pf = PFm.PallasField(P)

    def assert_same(a, b):
        for x, y in zip(a, b):
            assert (np.asarray(x) == np.asarray(y)).all()

    a1 = GC.g1_mul(GC.G1_GEN, rng.randrange(1, R))
    a2 = GC.g1_mul(GC.G1_GEN, rng.randrange(1, R))
    top = (P - 1,) * 3
    cases1 = [a1, a1, a1, GC.G1_INF, a2, GC.G1_INF, top]
    cases2 = [a2, a1, GC.g1_neg(a1), a2, GC.G1_INF, GC.G1_INF, a2]
    p1d, p2d = DC.g1_encode(cases1), DC.g1_encode(cases2)
    for with_double in (True, False):
        assert_same(
            DC.point_add(p1d, p2d, DC.FpOps, with_double=with_double),
            pf.g1_point_add(p1d, p2d, with_double))
    assert_same(DC.point_double(p1d, DC.FpOps), pf.g1_point_dbl(p1d))
    packed = pf.g1_point_dbl(pf.g1_pack_point(p2d))
    assert isinstance(packed, PFm.TileForm)
    assert_same(DC.point_double(p2d, DC.FpOps), pf.g1_unpack_point(packed))


def test_pallas_sqr4_mul_matches_xla(sim):
    """Fused windowed-exponentiation step (res^16 * t)."""
    pf = PFm.PallasField(P)
    va = _vals(8, P)
    vt = [rng.randrange(P) for _ in range(8)]
    a = jnp.asarray(FP.encode(va))
    t = jnp.asarray(FP.encode(vt))
    want = np.asarray(
        FP.mont_mul(FP.sqr(FP.sqr(FP.sqr(FP.sqr(a)))), t))
    got = np.asarray(pf.sqr4_mul(a, t))
    assert (got == want).all()


def test_pallas_fp2_sqrs_matches_golden(interp):
    from drand_tpu.crypto.bls12381 import fp as G
    from drand_tpu.ops import towers as T
    pf = PFm.PallasField(P)
    xs = [(rng.randrange(P), rng.randrange(P)) for _ in range(3)]
    xs += [(0, 0), (1, 0), (0, P - 1)]
    items = [T.fp2_encode([x]) for x in xs]
    out = pf.fp2_sqrs(items)
    for i, x in enumerate(xs):
        got = (FP.from_limbs_host(np.asarray(out[i][0])[0]),
               FP.from_limbs_host(np.asarray(out[i][1])[0]))
        assert got == G.fp2_mul(x, x)


def test_pallas_sqr_chain_mul_matches_xla(sim):
    """Fused addition-chain step (res^(2^k) [* t]) — both the unrolled
    (k <= 8) and the in-kernel fori_loop (k > 8) forms, with and
    without the trailing canonical multiply."""
    pf = PFm.PallasField(P)
    va = _vals(8, P)
    vt = [rng.randrange(P) for _ in range(8)]
    a = jnp.asarray(FP.encode(va))
    t = jnp.asarray(FP.encode(vt))
    for k in (1, 3, 8, 9, 17):
        want = a
        for _ in range(k):
            want = FP.sqr(want)
        got = np.asarray(pf.sqr_chain_mul(a, k))
        assert (got == np.asarray(want)).all(), f"k={k} (no mul)"
        want_t = np.asarray(FP.mont_mul(want, t))
        got_t = np.asarray(pf.sqr_chain_mul(a, k, t))
        assert (got_t == want_t).all(), f"k={k} (mul)"


def test_pallas_fp2_sqr_chain_mul_matches_golden(sim):
    from drand_tpu.crypto.bls12381 import fp as G
    from drand_tpu.ops import towers as T
    pf = PFm.PallasField(P)
    xs = [(rng.randrange(P), rng.randrange(P)) for _ in range(2)]
    ts = [(rng.randrange(P), rng.randrange(P)) for _ in range(2)]
    ax = T.fp2_encode(xs)
    at = T.fp2_encode(ts)
    for k in (1, 5, 12):
        for i, (x, t) in enumerate(zip(xs, ts)):
            want = x
            for _ in range(k):
                want = G.fp2_mul(want, want)
            got = pf.fp2_sqr_chain_mul(ax, k)
            assert T.fp2_decode(got, i) == want, f"k={k} (no mul)"
            got_t = pf.fp2_sqr_chain_mul(ax, k, at)
            assert T.fp2_decode(got_t, i) == G.fp2_mul(want, t), \
                f"k={k} (mul)"


def test_pallas_pow_addchain_matches_pow(sim):
    """Field._pow_addchain through the fused chain kernels: the full
    addition-chain executor (odd table + plan) vs python pow, on a
    real-sized exponent small enough for the eager simulator."""
    from unittest import mock

    from drand_tpu.ops.field import addchain_plan
    e = 0xDEADBEEFCAFE1234567890ABCDEF        # 112 bits, mixed runs
    ops, build, n_sqr, n_mul, used_odd = addchain_plan(e)
    va = _vals(4, P)
    a = jnp.asarray(FP.encode(va))
    with mock.patch.object(PFm, "use_pallas", return_value=True):
        assert FP._pallas() is not None     # fused executor path
        out = np.asarray(FP._pow_addchain(a, ops, build, used_odd))
    for i, x in enumerate(va):
        assert FP.from_limbs_host(out[i]) == pow(x, e, P), i

"""Batched G1/G2 Jacobian point arithmetic on TPU (JAX, branchless).

Device-side counterpart of the golden model `drand_tpu/crypto/bls12381/curve.py`
(reference: kyber `Point` ops on bls12-381 via `key/curve.go:26-33`).  Points
are Jacobian (X, Y, Z) pytrees of Montgomery limb arrays; Z == 0 encodes
infinity.  All control flow is masked selects so every function vmaps and
shards over the batch axis.

Formulas preserve infinity through doubling (Z3 = 2*Y*Z == 0 when Z == 0),
so only mixed/general addition needs explicit masks.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from drand_tpu.crypto.bls12381 import curve as GC
from drand_tpu.crypto.bls12381.constants import X as BLS_X
from drand_tpu.ops import towers as T
from drand_tpu.ops.field import FP, N_LIMBS


class FpOps:
    """Fp as a curve coordinate field."""
    add = staticmethod(T.fp_add)
    sub = staticmethod(T.fp_sub)
    neg = staticmethod(T.fp_neg)
    mul = staticmethod(T.fp_mul)
    sqr = staticmethod(T.fp_sqr)
    inv = staticmethod(T.fp_inv)
    select = staticmethod(T.fp_select)
    eq = staticmethod(FP.eq)
    is_zero = staticmethod(FP.is_zero)
    zero = T.FP_ZERO
    one = T.FP_ONE

    @staticmethod
    def products(pairs):
        return FP.products(pairs)

    @staticmethod
    def sums(pairs):
        return FP.sums(pairs)

    @staticmethod
    def diffs(pairs):
        return FP.diffs(pairs)

    @staticmethod
    def mul_small(a, c):
        return FP.mul_small(a, c)

    @staticmethod
    def broadcast(c, shape):
        return jnp.broadcast_to(c, shape + (N_LIMBS,)).astype(jnp.int32)


class Fp2Ops:
    """Fp2 as a curve coordinate field (the G2 twist)."""
    add = staticmethod(T.fp2_add)
    sub = staticmethod(T.fp2_sub)
    neg = staticmethod(T.fp2_neg)
    mul = staticmethod(T.fp2_mul)
    sqr = staticmethod(T.fp2_sqr)
    inv = staticmethod(T.fp2_inv)
    select = staticmethod(T.fp2_select)
    eq = staticmethod(T.fp2_eq)
    is_zero = staticmethod(T.fp2_is_zero)
    zero = T.FP2_ZERO
    one = T.FP2_ONE

    @staticmethod
    def products(pairs):
        return T.fp2_products(pairs)

    @staticmethod
    def sums(pairs):
        return T.fp2_sums(pairs)

    @staticmethod
    def diffs(pairs):
        return T.fp2_diffs(pairs)

    @staticmethod
    def mul_small(a, c):
        return T.fp2_mul_small(a, c)

    @staticmethod
    def broadcast(c, shape):
        return T.fp2_broadcast(c, shape)


# ---------------------------------------------------------------------------
# Generic Jacobian arithmetic
# ---------------------------------------------------------------------------

def point_inf(ops, shape=()):
    return (ops.broadcast(ops.one, shape), ops.broadcast(ops.one, shape),
            ops.broadcast(ops.zero, shape))


def point_is_inf(pt, ops):
    return ops.is_zero(pt[2])


def point_neg(pt, ops):
    return (pt[0], ops.neg(pt[1]), pt[2])


class _PointKernels(NamedTuple):
    """One coordinate field's fused point kernels (ops/pallas_field.py)."""
    pack: Callable
    dbl: Callable
    add: Callable
    unpack: Callable


def _point_kernels(ops):
    """The fused Pallas point kernels of `ops`' coordinate field on a
    TPU, None on any other platform (the XLA forms below are then the
    program, and on the TPU the oracle the kernels are held to)."""
    pf = FP._pallas()
    if pf is None:
        return None
    if ops is Fp2Ops:
        return _PointKernels(pf.g2_pack_point, pf.g2_point_dbl,
                             pf.g2_point_add, pf.g2_unpack_point)
    if ops is FpOps:
        return _PointKernels(pf.g1_pack_point, pf.g1_point_dbl,
                             pf.g1_point_add, pf.g1_unpack_point)
    return None


def g1_ladder_form() -> str:
    """How a G1 ladder steps in a program traced here: `fused` (one
    kernel a step on a tile-resident point) or `generic` (the staged XLA
    formulas); `Verifier.build` records it."""
    return "generic" if _point_kernels(FpOps) is None else "fused"


def point_double(pt, ops):
    """dbl-2009-l in staged stacked products; preserves infinity
    (Z3 = 2YZ = 0).  On TPU it runs as one fused Pallas kernel (the
    cofactor/subgroup ladders scan this body 63+ times), over Fp2 since
    ISSUE 9 and over Fp since ISSUE 46."""
    fused = _point_kernels(ops)
    if fused is not None:
        return fused.dbl(pt)
    x, y, z = pt
    a, b, yz = ops.products([(x, x), (y, y), (y, z)])
    xb = ops.add(x, b)
    c, s2 = ops.products([(b, b), (xb, xb)])
    e = ops.mul_small(a, 3)
    d = ops.sub(s2, ops.add(a, c))
    d = ops.add(d, d)
    f = ops.sqr(e)
    x3 = ops.sub(f, ops.add(d, d))
    (y3t,) = ops.products([(e, ops.sub(d, x3))])
    y3 = ops.sub(y3t, ops.mul_small(c, 8))
    z3 = ops.add(yz, yz)
    return (x3, y3, z3)


def point_add(p1, p2, ops, with_double: bool = True):
    """General Jacobian addition (staged) with full branchless case
    handling: infinities, P + P (doubling fallback), P + (-P) = inf.

    Set with_double=False in loops where p1 == p2 is impossible (e.g.
    double-and-add ladders over canonical scalars) to skip the doubling
    computation.  On TPU it runs as one fused Pallas kernel.
    """
    fused = _point_kernels(ops)
    if fused is not None:
        return fused.add(p1, p2, with_double)
    x1, y1, z1 = p1
    x2, y2, z2 = p2
    z1z1, z2z2, y1z2, y2z1 = ops.products(
        [(z1, z1), (z2, z2), (y1, z2), (y2, z1)])
    u1, u2, s1, s2 = ops.products(
        [(x1, z2z2), (x2, z1z1), (y1z2, z2z2), (y2z1, z1z1)])
    h = ops.sub(u2, u1)
    h2 = ops.add(h, h)
    rr = ops.sub(s2, s1)
    rr = ops.add(rr, rr)
    z12 = ops.add(z1, z2)
    i, rr2, z12sq = ops.products([(h2, h2), (rr, rr), (z12, z12)])
    j, v = ops.products([(h, i), (u1, i)])
    x3 = ops.sub(ops.sub(rr2, j), ops.add(v, v))
    zz = ops.sub(z12sq, ops.add(z1z1, z2z2))
    y3t, s1j, z3 = ops.products([(rr, ops.sub(v, x3)), (s1, j), (zz, h)])
    y3 = ops.sub(y3t, ops.add(s1j, s1j))
    out = (x3, y3, z3)

    inf1 = ops.is_zero(z1)
    inf2 = ops.is_zero(z2)
    eq_u = ops.eq(u1, u2) & ~inf1 & ~inf2
    eq_s = ops.eq(s1, s2)
    if with_double:
        dbl = point_double(p1, ops)
        out = tuple(ops.select(eq_u & eq_s, d, o) for d, o in zip(dbl, out))
    # P + (-P): force infinity by zeroing Z (X, Y arbitrary nonzero)
    cancel = eq_u & ~eq_s
    shape = cancel.shape
    inf = point_inf(ops, shape)
    out = tuple(ops.select(cancel, i_, o) for i_, o in zip(inf, out))
    out = tuple(ops.select(inf1, b, o) for b, o in zip(p2, out))
    out = tuple(ops.select(inf2 & ~inf1, a, o) for a, o in zip(p1, out))
    return out


def point_eq(p1, p2, ops):
    """Projective equality (both-infinite counts as equal)."""
    x1, y1, z1 = p1
    x2, y2, z2 = p2
    z1z1, z2z2, y1z2, y2z1 = ops.products(
        [(z1, z1), (z2, z2), (y1, z2), (y2, z1)])
    a, b, c, d = ops.products(
        [(x1, z2z2), (x2, z1z1), (y1z2, z2z2), (y2z1, z1z1)])
    ex = ops.eq(a, b)
    ey = ops.eq(c, d)
    i1 = ops.is_zero(z1)
    i2 = ops.is_zero(z2)
    return (i1 & i2) | (~i1 & ~i2 & ex & ey)


def point_to_affine(pt, ops):
    """Returns ((x, y), inf_mask); (0, 0) where infinite."""
    x, y, z = pt
    inf = ops.is_zero(z)
    zi = ops.inv(z)
    zi2 = ops.sqr(zi)
    ax = ops.mul(x, zi2)
    ay = ops.mul(y, ops.mul(zi, zi2))
    zero = ops.broadcast(ops.zero, inf.shape)
    return (ops.select(inf, zero, ax), ops.select(inf, zero, ay)), inf


def point_mul_bits(pt, bits, ops):
    """MSB-first double-and-add over a static-length dynamic bit array
    bits[..., L] (int32 0/1).  Scalars must be canonical (< group order) so
    the no-doubling-fallback addition is safe (acc = k*pt with k even can
    never equal pt for pt of odd prime order)."""
    shape = bits.shape[:-1]
    acc = point_inf(ops, shape)
    base = pt

    def body(acc, bit):
        acc = point_double(acc, ops)
        added = point_add(acc, base, ops, with_double=False)
        return tuple(ops.select(bit > 0, a, o) for a, o in zip(added, acc)), None

    bits_t = jnp.moveaxis(bits, -1, 0)
    acc, _ = jax.lax.scan(body, acc, bits_t)
    return acc


def point_mul_const(pt, k: int, ops):
    """Scalar mul by a static non-negative scalar.

    Double-and-add over the scalar's static bits
    (field.segmented_ladder): every bit doubles, only the set bits run
    their point_add — sparse scalars like the BLS parameter |x| (subgroup
    checks, cofactor clearing) skip the ~90% of additions a masked
    per-bit scan would compute and discard.  Safety of the
    no-doubling-fallback add: acc = m*pt with 2 <= m < order can never
    equal +-pt for pt of odd prime order."""
    assert k >= 0
    if k == 0:
        return point_inf(ops, jax.tree_util.tree_leaves(pt)[0].shape[:-1])
    from drand_tpu.ops.field import tail_segments
    segments = tail_segments(bin(k)[3:])
    if len(segments) > 24:
        # dense scalar (e.g. the 255-bit group order): unrolling every set
        # bit would blow up the graph for little skipped work — keep the
        # single-body masked scan
        nbits = np.array([int(b) for b in bin(k)[2:]], dtype=np.int32)

        def body(acc, bit):
            acc = point_double(acc, ops)
            added = point_add(acc, pt, ops, with_double=False)
            return tuple(ops.select(bit > 0, a, o)
                         for a, o in zip(added, acc)), None

        shape = jax.tree_util.tree_leaves(pt)[0].shape[:-1]
        acc, _ = jax.lax.scan(body, point_inf(ops, shape), jnp.asarray(nbits))
        return acc

    from drand_tpu.ops.field import segmented_ladder
    fused = _point_kernels(ops)
    if fused is not None:
        # Tile-resident ladder: the point packs ONCE (entry crossing),
        # every scan step is a fused kernel on the packed TileForm,
        # and the result unpacks once at exit — vs a relayout on both
        # sides of all 63+ point kernels before (ISSUE 9 tentpole; the
        # G1 ladders since ISSUE 46).
        base = fused.pack(pt)
        return fused.unpack(segmented_ladder(
            segments, base, fused.dbl,
            lambda acc: fused.add(acc, base, False)))
    return segmented_ladder(
        segments, pt,  # starting from pt consumes the leading 1 bit
        lambda acc: point_double(acc, ops),
        lambda acc: point_add(acc, pt, ops, with_double=False))


def scalar_to_bits(scalar_limbs, nbits: int = 256):
    """[..., 32] Fr limb array (NON-Montgomery canonical) -> [..., nbits]
    MSB-first bit array."""
    j = np.arange(nbits - 1, -1, -1)
    limb_idx = j // 12
    bit_idx = j % 12
    limbs = jnp.take(scalar_limbs, jnp.asarray(limb_idx), axis=-1)
    return (limbs >> jnp.asarray(bit_idx)) & 1


# ---------------------------------------------------------------------------
# G1 / G2 specializations
# ---------------------------------------------------------------------------

def _enc_fp(x: int):
    return jnp.asarray(FP.to_mont_host(x))


G1_GEN = (_enc_fp(GC.G1_GEN[0]), _enc_fp(GC.G1_GEN[1]), T.FP_ONE)
G2_GEN = (T.fp2_const(GC.G2_GEN[0]), T.fp2_const(GC.G2_GEN[1]), T.FP2_ONE)

_PSI_X = T.fp2_const(GC.PSI_X)
_PSI_Y = T.fp2_const(GC.PSI_Y)

_X_ABS = -BLS_X


def g2_psi(pt):
    """Untwist-Frobenius-twist endomorphism (golden curve.py:309-315)."""
    x, y, z = pt
    return (T.fp2_mul(T.fp2_conj(x), _PSI_X),
            T.fp2_mul(T.fp2_conj(y), _PSI_Y),
            T.fp2_conj(z))


def g2_mul_x_abs(pt):
    """[|x|]Q for the BLS parameter."""
    return point_mul_const(pt, _X_ABS, Fp2Ops)


def g2_clear_cofactor(pt):
    """Budroni-Pintore: [x^2-x-1]Q + [x-1]psi(Q) + psi^2([2]Q), with the
    negative x folded into point negations (golden curve.py:327-338)."""
    ops = Fp2Ops
    xq = point_neg(g2_mul_x_abs(pt), ops)             # [x]Q, x < 0
    x2q = point_neg(g2_mul_x_abs(xq), ops)            # [x^2]Q
    t = point_add(x2q, point_neg(xq, ops), ops)       # [x^2 - x]Q
    t = point_add(t, point_neg(pt, ops), ops)         # [x^2 - x - 1]Q
    p1 = point_add(xq, point_neg(pt, ops), ops)       # [x - 1]Q
    p1 = g2_psi(p1)
    p2 = g2_psi(g2_psi(point_double(pt, ops)))
    return point_add(point_add(t, p1, ops), p2, ops)


def g2_in_subgroup(pt):
    """Bowe's criterion: psi(Q) == [x]Q, plus on-curve check."""
    on = g2_on_curve(pt)
    lhs = g2_psi(pt)
    rhs = point_neg(g2_mul_x_abs(pt), Fp2Ops)
    return on & (point_eq(lhs, rhs, Fp2Ops) | point_is_inf(pt, Fp2Ops))


_B_G1 = _enc_fp(4)
_B_G2 = T.fp2_const((4, 4))


def g1_on_curve(pt):
    """Jacobian on-curve: Y^2 == X^3 + 4 Z^6 (or infinity)."""
    x, y, z = pt
    z2 = T.fp_sqr(z)
    z6 = T.fp_mul(T.fp_sqr(z2), z2)
    lhs = T.fp_sqr(y)
    rhs = T.fp_add(T.fp_mul(T.fp_sqr(x), x), T.fp_mul(z6, _B_G1))
    return FP.eq(lhs, rhs) | FP.is_zero(z)


def g2_on_curve(pt):
    x, y, z = pt
    z2 = T.fp2_sqr(z)
    z6 = T.fp2_mul(T.fp2_sqr(z2), z2)
    lhs = T.fp2_sqr(y)
    rhs = T.fp2_add(T.fp2_mul(T.fp2_sqr(x), x), T.fp2_mul(z6, _B_G2))
    return T.fp2_eq(lhs, rhs) | T.fp2_is_zero(z)


# GLV endomorphism constant: the cube root of unity beta with
# phi(x, y) = (beta x, y) acting as multiplication by -x^2 on G1
# (the OTHER root beta^2 acts as x^2 - 1; pinned by
# tests/test_ops_curve.py against the golden model).
_G1_BETA = _enc_fp(
    0x5f19672fdf76ce51ba69c6076a0f77eaddb3a93be6f89688de17d813620a00022e01fffffffefffe)


def g1_phi(pt):
    """j=0 automorphism (x, y) -> (beta x, y), Jacobian-compatible
    (x/z^2 scales by beta exactly when X does)."""
    x, y, z = pt
    return (T.fp_mul(x, jnp.broadcast_to(_G1_BETA, x.shape).astype(
        jnp.int32)), y, z)


def g1_in_subgroup(pt):
    """On-curve + phi-based order check: phi(P) == [-x^2]P.

    Soundness: on G1, phi acts as the eigenvalue -x^2 (mod r) of
    t^2 + t + 1.  Completeness: phi^2 + phi + 1 = 0 holds on the WHOLE
    j=0 curve, so phi(P) = [-x^2]P forces
    O = phi^2(P) + phi(P) + P = [x^4 - x^2 + 1]P = [r]P, i.e. P is in
    the r-torsion.  Cost: two sparse |x|-ladders (63 doubles + 5 adds
    each) instead of the dense 255-bit [r]-ladder — the short-sig
    scheme's subgroup check at ~1/4 the point work (the same trick as
    g2_in_subgroup's psi criterion)."""
    x2p = point_mul_const(point_mul_const(pt, _X_ABS, FpOps), _X_ABS, FpOps)
    lhs = g1_phi(pt)
    ok = point_eq(lhs, point_neg(x2p, FpOps), FpOps)
    return g1_on_curve(pt) & (ok | point_is_inf(pt, FpOps))


# ---------------------------------------------------------------------------
# Host <-> device point conversion (golden Jacobian tuples of ints)
# ---------------------------------------------------------------------------

def g1_encode(pts):
    """List of golden G1 Jacobian tuples -> batched device point."""
    return (jnp.asarray(FP.encode([p[0] for p in pts])),
            jnp.asarray(FP.encode([p[1] for p in pts])),
            jnp.asarray(FP.encode([p[2] for p in pts])))


def g1_decode(pt, i=None):
    out = []
    for c in pt:
        v = np.asarray(c if i is None else c[i])
        out.append(FP.from_limbs_host(v))
    return tuple(out)


def g2_encode(pts):
    return tuple(T.fp2_encode([p[k] for p in pts]) for k in range(3))


def g2_decode(pt, i=None):
    return tuple(T.fp2_decode(c, i) for c in pt)

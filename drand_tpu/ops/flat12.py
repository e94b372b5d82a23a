"""Flat Fp12 arithmetic: 12 Fp coefficients over the power basis of w.

The tower Fp2->Fp6->Fp12 (towers.py) phrases an Fp12 multiply as ~18 Fp2
multiplies across three Karatsuba levels — dozens of *distinct* stacked ops,
each inlined into the XLA graph.  This module flattens the tower onto the
basis {1, w, ..., w^11} over Fp, where w is the Fp12 generator (w^2 = v,
v^3 = xi = 1+u, u^2 = -1), so that ONE broadcasted Montgomery multiply
computes all 144 coefficient products and two einsums perform the
convolution and the minimal-polynomial reduction:

    u = w^6 - 1  =>  w^12 - 2 w^6 + 2 = 0

An Fp12 multiply is then ~300 XLA ops instead of ~12,000, which is what
makes the pairing and hash-to-curve kernels compile in seconds — and the
coefficient products land in a single [..., 12, 12] stack that keeps the
VPU lanes full.

Basis mapping: the tower element ((a0,a1,a2),(b0,b1,b2)) with Fp2 cells
c = x + y*u occupies slots s(a0)=0, s(b0)=1, s(a1)=2, s(b1)=3, s(a2)=4,
s(b2)=5, with  x + y*u  at slot s  ->  (x - y)*w^s + y*w^(s+6).
Each pair of slots (s, s+6) spans one tower Fp2 cell, so Frobenius (which
maps every cell to conj(cell)*gamma_s, towers.py fp12_frob) is
block-diagonal over these pairs: 24 Fp constants per power.

A flat element is an [..., 12, 32] int32 array (w-power axis, then limbs),
canonical Montgomery form per coefficient.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from drand_tpu.crypto.bls12381 import fp as G
from drand_tpu.crypto.bls12381.constants import P
from drand_tpu.ops.field import (FP, N_LIMBS, _carry, _carry_cheap,
                                 _poly_mul_var)

# ---------------------------------------------------------------------------
# Host-side basis conversion (golden ints <-> flat coefficient lists)
# ---------------------------------------------------------------------------

_SLOT = [0, 2, 4, 1, 3, 5]  # tower cell order a0,a1,a2,b0,b1,b2 -> w-power


def flat_coeffs_from_tower(t) -> list[int]:
    """Golden fp12 tuple -> 12 plain-int coefficients over the w basis."""
    cells = list(t[0]) + list(t[1])          # a0,a1,a2,b0,b1,b2
    out = [0] * 12
    for cell, s in zip(cells, _SLOT):
        x, y = cell
        out[s] = (x - y) % P
        out[s + 6] = y % P
    return out


def tower_from_flat_coeffs(c) -> tuple:
    """12 plain ints -> golden fp12 tuple."""
    cells = []
    for s in _SLOT:
        y = c[s + 6] % P
        x = (c[s] + y) % P
        cells.append((x, y))
    return ((cells[0], cells[1], cells[2]), (cells[3], cells[4], cells[5]))


def flat_encode(vals) -> jnp.ndarray:
    """List of golden fp12 tuples -> [len, 12, 32] Montgomery flat."""
    return jnp.asarray(np.stack([
        np.stack([FP.to_mont_host(c) for c in flat_coeffs_from_tower(v)])
        for v in vals]))


def flat_decode(a, i=None) -> tuple:
    if i is not None:
        a = a[i]
    coeffs = [FP.from_limbs_host(np.asarray(a[k])) for k in range(12)]
    return tower_from_flat_coeffs(coeffs)


# ---------------------------------------------------------------------------
# Reduction matrices (static)
# ---------------------------------------------------------------------------

def _conv_mask(b_idx):
    """One-hot [12, J, K]: product of w^i and w^(b_idx[j]) lands at w-power
    i + b_idx[j]."""
    J = len(b_idx)
    K = 11 + max(b_idx) + 1
    m = np.zeros((12, J, K), np.int32)
    for i in range(12):
        for j, bj in enumerate(b_idx):
            m[i, j, i + bj] = 1
    return m


def _reduce_matrix(K):
    """[K, 12] signed small-int matrix reducing w^k (k < K <= 23) onto the
    basis, via w^12 = 2w^6 - 2 iterated."""
    rows = []
    for k in range(K):
        r = np.zeros(12, np.int64)
        if k < 12:
            r[k] = 1
        elif k < 18:
            r[k - 6] += 2
            r[k - 12] -= 2
        else:  # 18..22: w^k = 2 w^(k-12) - 4 w^(k-18)
            r[k - 12] += 2
            r[k - 18] -= 4
        rows.append(r)
    return np.stack(rows)


# sanity at import: row k of the reduction matrix must equal the flat
# coefficients of w^k computed through the golden tower arithmetic
def _check_reduction():
    w = (((0, 0), (0, 0), (0, 0)), ((1, 0), (0, 0), (0, 0)))
    red = _reduce_matrix(23)
    acc = G.FP12_ONE
    for k in range(23):
        want = flat_coeffs_from_tower(acc)
        got = [int(red[k, j]) % P for j in range(12)]
        assert want == got, (k, want, got)
        acc = G.fp12_mul(acc, w)


_check_reduction()


# ---------------------------------------------------------------------------
# Core ops
# ---------------------------------------------------------------------------

FLAT_ZERO = jnp.asarray(np.zeros((12, N_LIMBS), np.int32))
FLAT_ONE = jnp.asarray(np.stack([FP.one_mont] + [np.zeros(N_LIMBS, np.int32)] * 11))

_ODD = jnp.asarray((np.arange(12) % 2).astype(bool))


def flat_broadcast(a, shape):
    return jnp.broadcast_to(a, shape + (12, N_LIMBS)).astype(jnp.int32)


def flat_select(mask, a, b):
    return jnp.where(mask[..., None, None], a, b)


def flat_eq(a, b):
    return jnp.all(a == b, axis=(-1, -2))


def flat_is_one(a):
    pf = FP._pallas()
    if pf is not None:
        from drand_tpu.ops.pallas_field import TileForm
        if isinstance(a, TileForm):
            # verdict reduction on the packed element: compare in tile
            # layout, cross back once with the boolean mask (the
            # pipeline-exit crossing)
            one = flat_tile(flat_broadcast(FLAT_ONE, a.shape))
            mask = jnp.all(a.tiles == one.tiles, axis=1)
            return pf.mask_unwrap(mask, a.shape, a.b)
    return flat_eq(a, FLAT_ONE.astype(a.dtype))


def _mul_tables(b_idx):
    mask = _conv_mask(b_idx)
    K = mask.shape[-1]
    red = _reduce_matrix(K)
    pos = np.maximum(red, 0).astype(np.int32)
    neg = np.maximum(-red, 0).astype(np.int32)
    bound = int((np.abs(red).sum(axis=0)).max()) + 1
    return mask, pos, neg, bound


_TABLES = {}


def _tables(b_idx):
    key = tuple(b_idx)
    if key not in _TABLES:
        _TABLES[key] = _mul_tables(b_idx)
    return _TABLES[key]


def flat_mul(a, b, b_idx=tuple(range(12))):
    """Flat Fp12 product.  a [..., 12, 32]; b [..., J, 32] holding the
    coefficients of the w-powers listed in static `b_idx` (full element by
    default; Miller-loop lines pass their 6 non-zero powers).

    One broadcasted limb multiply -> convolution einsum -> stacked
    Montgomery reduction (<=12 canonical products per conv coefficient
    keeps the value under the mont_reduce bound) -> signed minimal-poly
    recombination with negatives folded through p - x."""
    pf = FP._pallas()
    if pf is not None:
        return pf.flat_mul(a, b, tuple(b_idx))
    mask, pos, neg, bound = _tables(b_idx)
    cols = _poly_mul_var(a[..., :, None, :], b[..., None, :, :])
    # pad to 64 limbs BEFORE carrying: each raw product spans up to 762
    # bits, and the summed value up to 766 — both past the 63-limb window
    cols = _carry_cheap(jnp.pad(cols, [(0, 0)] * (cols.ndim - 1) + [(0, 1)]))
    conv = jnp.einsum('...ijc,ijk->...kc', cols, jnp.asarray(mask))  # [..., K, 64]
    red = FP.mont_reduce(_carry_cheap(conv))        # [..., K, 32] canonical
    nred = FP.neg(red)
    s = (jnp.einsum('...kc,kj->...jc', red, jnp.asarray(pos))
         + jnp.einsum('...kc,kj->...jc', nred, jnp.asarray(neg)))
    s = _carry(s)
    return FP.reduce_small_multiple(s, bound)


def flat_sqr(a):
    pf = FP._pallas()
    if pf is not None:
        return pf.flat_sqr(a)    # slot-symmetric conv: ~55% of the MACs
    return flat_mul(a, a)


def flat_tile(a):
    """[..., 12, 32] flat element -> packed TileForm on the Pallas path
    (identity elsewhere).  Hot loops (the Miller accumulator, the
    final-exp x-power chains) tile once and thread the TileForm through
    flat_sqr/flat_mul/flat_cyclo_sqr so consecutive kernel calls skip the
    per-call [B, limbs] <-> [tiles, limbs, 8, 128] relayout."""
    pf = FP._pallas()
    if pf is None:
        return a
    from drand_tpu.ops.pallas_field import TileForm
    if isinstance(a, TileForm):
        return a
    shape = a.shape[:-2]
    return pf.tile(a.reshape(shape + (12 * N_LIMBS,)), 12 * N_LIMBS)


def flat_untile(a):
    """Inverse of flat_tile (identity on plain arrays)."""
    pf = FP._pallas()
    if pf is None:
        return a
    from drand_tpu.ops.pallas_field import TileForm
    if not isinstance(a, TileForm):
        return a
    return pf.untile(a).reshape(a.shape + (12, N_LIMBS))


def flat_conj(a):
    """f^(p^6): negate the odd w-powers (packed TileForm stays packed
    via the fused kernel — same canonical values)."""
    pf = FP._pallas()
    if pf is not None:
        from drand_tpu.ops.pallas_field import TileForm
        if isinstance(a, TileForm):
            return pf.flat_conj(a)
    return jnp.where(_ODD[:, None], FP.neg(a), a)


# ---------------------------------------------------------------------------
# Frobenius: block-diagonal over the slot pairs (s, s+6)
# ---------------------------------------------------------------------------

def _w_power_tower(k: int):
    """Golden tower representation of w^k."""
    acc = G.FP12_ONE
    w = (((0, 0), (0, 0), (0, 0)), ((1, 0), (0, 0), (0, 0)))
    for _ in range(k):
        acc = G.fp12_mul(acc, w)
    return acc


def _frob_consts(n: int):
    """Per-slot 2x2 Fp matrices [[A,B],[C,D]]: frob^n maps
    (c_s, c_(s+6)) -> (A c_s + B c_(s+6), C c_s + D c_(s+6))."""
    A = np.zeros((6, N_LIMBS), np.int32)
    B = np.zeros((6, N_LIMBS), np.int32)
    C = np.zeros((6, N_LIMBS), np.int32)
    D = np.zeros((6, N_LIMBS), np.int32)
    for s in range(6):
        for src, (lo_t, hi_t) in (("lo", (A, C)), ("hi", (B, D))):
            k = s if src == "lo" else s + 6
            img = G.fp12_frob_n(_w_power_tower(k), n)
            coeffs = flat_coeffs_from_tower(img)
            for j, c in enumerate(coeffs):
                if c == 0:
                    continue
                assert j in (s, s + 6), (
                    f"frobenius not block-diagonal: slot {k} -> {j}")
            lo_t[s] = FP.to_mont_host(coeffs[s])
            hi_t[s] = FP.to_mont_host(coeffs[s + 6])
    return tuple(jnp.asarray(x) for x in (A, B, C, D))


_FROB = {n: _frob_consts(n) for n in (1, 2, 3)}


def flat_frob(a, n: int = 1):
    """a^(p^n) for n in 1..3 (compose for higher).  Packed TileForm
    inputs run the fused constant-multiply kernel and stay packed."""
    pf = FP._pallas()
    if pf is not None:
        from drand_tpu.ops.pallas_field import TileForm
        if isinstance(a, TileForm):
            return pf.flat_frob(a, n)
    A, B, C, D = _FROB[n]
    lo, hi = a[..., :6, :], a[..., 6:, :]
    st_a = jnp.stack([lo, hi, lo, hi], 0)
    st_b = jnp.stack([jnp.broadcast_to(A, lo.shape), jnp.broadcast_to(B, hi.shape),
                      jnp.broadcast_to(C, lo.shape), jnp.broadcast_to(D, hi.shape)], 0)
    p = FP.mont_mul(st_a.astype(jnp.int32), st_b.astype(jnp.int32))
    out_lo = FP.add(p[0], p[1])
    out_hi = FP.add(p[2], p[3])
    return jnp.concatenate([out_lo, out_hi], axis=-2)


# ---------------------------------------------------------------------------
# Tower <-> flat on device
# ---------------------------------------------------------------------------

def flat_from_tower(t):
    """towers.py fp12 pytree -> [..., 12, 32]."""
    cells = list(t[0]) + list(t[1])
    xs = jnp.stack([cells[i][0] for i in (0, 3, 1, 4, 2, 5)], axis=-2)
    ys = jnp.stack([cells[i][1] for i in (0, 3, 1, 4, 2, 5)], axis=-2)
    lo = FP.sub(xs, ys)
    return jnp.concatenate([lo, ys], axis=-2)


def flat_to_tower(a):
    lo, hi = a[..., :6, :], a[..., 6:, :]
    xs = FP.add(lo, hi)
    cell = lambda i: (xs[..., i, :], hi[..., i, :])
    # slot order 0..5 = a0,b0,a1,b1,a2,b2
    return ((cell(0), cell(2), cell(4)), (cell(1), cell(3), cell(5)))


def flat_inv(a):
    """Inverse via the tower formulas (used once per pairing check).
    Packed input -> packed output; the tower evaluation itself runs on
    plain arrays (2 counted crossings — the one remaining non-resident
    step of the final exponentiation, once per check)."""
    from drand_tpu.ops import towers as T
    pf = FP._pallas()
    if pf is not None:
        from drand_tpu.ops.pallas_field import TileForm
        if isinstance(a, TileForm):
            arr = flat_untile(a)
            out = flat_from_tower(T.fp12_inv(flat_to_tower(arr)))
            return flat_tile(out)
    return flat_from_tower(T.fp12_inv(flat_to_tower(a)))


def flat_cyclo_sqr(a):
    """Granger-Scott cyclotomic squaring for UNITARY elements (outputs of
    the final exponentiation's easy part): ~27 base multiplications
    instead of the full 144-product flat square — the x-power chains in
    the hard part are ~40% of a verification's multiply work.

    Validity requires z^(p^6+1) = 1; everything after the easy part
    satisfies it.  Formulas are the Fp4-squaring decomposition over the
    cells A=(z0,z4), B=(z3,z2), C=(z1,z5), cross-validated against the
    golden model.

    On TPU the whole square runs as ONE fused Pallas kernel
    (PallasField.cyclo_sqr): the round-3 profile showed this XLA form at
    ~85% carry/select glue around a single products call, and the x-power
    chains execute it 63 times per chain, 5+ chains per verify.  The
    kernel computes the same formulas in another order (ISSUE 42): the
    nine squares' convolutions stay un-reduced, the Fp4 recombination,
    the 3t +- 2g folds and the flat encoding below are summed in the
    wide domain, and each of the 12 output coordinates is Montgomery-
    reduced once.  This form reduces the nine squares first (18
    coordinates) and recombines canonical values: it is the oracle the
    kernel is held to, limb for limb.
    """
    pf = FP._pallas()
    if pf is not None:
        return pf.cyclo_sqr(a)
    from drand_tpu.ops import towers as T

    hi = a[..., 6:, :]
    xs = FP.add(a[..., :6, :], hi)          # tower-cell x coordinates

    def cell(slot):
        return (xs[..., slot, :], hi[..., slot, :])

    # tower cells (z0..z5) live at flat slots (0,2,4) + (1,3,5)
    g0, g1, g2 = cell(0), cell(2), cell(4)
    g3, g4, g5 = cell(1), cell(3), cell(5)
    s_a, s_b, s_c = T.fp2_sums([(g0, g4), (g3, g2), (g1, g5)])
    p = T.fp2_products([
        (g0, g0), (g4, g4), (s_a, s_a),
        (g3, g3), (g2, g2), (s_b, s_b),
        (g1, g1), (g5, g5), (s_c, s_c)])
    a2, b2, sa2, c2, d2, sb2, e2, f2, sc2 = p

    def fp4(a_sq, b_sq, s_sq):
        re = T.fp2_add(a_sq, T.fp2_mul_xi(b_sq))
        im = T.fp2_sub(T.fp2_sub(s_sq, a_sq), b_sq)
        return re, im

    re_a, im_a = fp4(a2, b2, sa2)
    re_b, im_b = fp4(c2, d2, sb2)
    re_c, im_c = fp4(e2, f2, sc2)

    def tm(t, g):   # 3t - 2g
        d = T.fp2_sub(t, g)
        return T.fp2_add(T.fp2_add(d, d), t)

    def tp(t, g):   # 3t + 2g
        s = T.fp2_add(t, g)
        return T.fp2_add(T.fp2_add(s, s), t)

    out = {
        0: tm(re_a, g0), 2: tm(re_b, g1), 4: tm(re_c, g2),
        1: tp(T.fp2_mul_xi(im_c), g3), 3: tp(im_a, g4), 5: tp(im_b, g5),
    }
    xs2 = jnp.stack([out[i][0] for i in range(6)], axis=-2)
    ys2 = jnp.stack([out[i][1] for i in range(6)], axis=-2)
    lo = FP.sub(xs2, ys2)
    return jnp.concatenate([lo, ys2], axis=-2)

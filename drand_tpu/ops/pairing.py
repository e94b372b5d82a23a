"""Batched optimal-ate pairing on TPU (JAX).

Device counterpart of the golden model `drand_tpu/crypto/bls12381/pairing.py`
(and of the pairing engine in kilic/bls12-381 used via `key/curve.go:24`).
Computes the same pairing e(P, Q)^3 as the golden model (denominators-cleared
hard part), so the two implementations cross-validate exactly.

TPU-first design decisions (vs the golden model's affine + field-inversion
line steps):
  - Line steps use Jacobian T with denominator-cleared line coefficients —
    the cleared factors live in Fp2, which the final exponentiation kills —
    so the Miller loop contains NO field inversions (an Fp inversion is a
    ~570-multiplication Fermat chain on TPU; the reference's CPU assembly
    uses cheap extended-GCD instead, which doesn't vectorize).
  - The Fp12 accumulator lives in the FLAT representation (ops/flat12.py):
    squarings and line multiplications are single broadcasted Montgomery
    multiplies, not Karatsuba towers of separate ops.
  - The loop over the 64-bit BLS parameter runs the addition step on the
    parameter's 5 set tail bits only (field.segmented_ladder): statically
    segmented, `lax.scan` over each zero run (double-only body) with the
    addition steps unrolled between runs; in compact mode, which is what
    the TPU serves, one scan over the 63 bits whose body puts the
    addition step under a `lax.cond` on the bit.  No multiply is executed
    just to be masked away (a masked per-bit scan wastes the entire
    addition path on 58 of 63 iterations).
  - Lines are sparse flat elements: 3 Fp2 coefficients at w-powers
    {0, 2, 3}, i.e. 6 of 12 flat slots, so a line multiply is a 12x6
    product stack.

Two Miller loops, one a kind of G2 argument, sharing the squaring, the
line multiply and the ladder's driver, and no branch:
  - `miller_loop_pairs` carries a Jacobian G2 point a row and pair
    through the ladder.  The G2-signature programs (`bls.verify_g2_sigs`,
    the partial-signature builders) need it: there Q is the signature and
    H(m), another in every row.  On the TPU the point, like f, is in the
    kernels' tile layout from before the ladder to its end
    (`_tile_halves`).
  - `miller_loop_fixed_q` holds no G2 point.  In the G1-signature program
    (`bls.verify_g1_sigs`) both Qs, the generator and the chain's key,
    are the whole batch's, and T = [k]Q and the line through it depend on
    Q and the step alone: the lines come from `fixed_q_table`, computed
    once a key on the host, and a row's P enters by four Fp products a
    pair and step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from drand_tpu.crypto.bls12381 import fp as G
from drand_tpu.crypto.bls12381.constants import X as _BLS_X
from drand_tpu.ops import FINAL_EXP, MILLER
from drand_tpu.ops import flat12 as F
from drand_tpu.ops import towers as T
from drand_tpu.ops.field import FP

FP_products = FP.products

from drand_tpu.ops.field import (compact_graphs, line_merge_enabled,
                                 miller_merged, segmented_ladder)
from drand_tpu.ops.field import tail_segments as _tail_segments

_X_ABS = -_BLS_X
_X_BITS = bin(_X_ABS)[2:]
# |x| = 0xd201000000010000 has only 5 set tail bits; see field.tail_segments
_X_SEGMENTS = _tail_segments(_X_BITS[1:])


# ---------------------------------------------------------------------------
# Sparse line representation: Fp2 triple (a, b, c) meaning the Fp12 element
# (a + b*v) + (c*v)*w = a + b*w^2 + c*w^3 — flat slots {0,2,3,6,8,9}.
# ---------------------------------------------------------------------------

LINE_IDX = (0, 2, 3, 6, 8, 9)


def line_to_flat(line):
    """Fp2 line triple -> [..., 6, 32] sparse flat coefficients."""
    a, b, c = line
    xs = jnp.stack([a[0], b[0], c[0]], axis=-2)
    ys = jnp.stack([a[1], b[1], c[1]], axis=-2)
    lo = FP.sub(xs, ys)
    return jnp.concatenate([lo, ys], axis=-2)


def fp12_mul_line(f, line):
    """Flat f times a sparse line: one 12x6 product stack."""
    return F.flat_mul(f, line_to_flat(line), LINE_IDX)


def line_one(shape):
    """The neutral line (1, 0, 0) broadcast to a batch shape."""
    one = T.fp2_broadcast(T.FP2_ONE, shape)
    zero = T.fp2_broadcast(T.FP2_ZERO, shape)
    return (one, zero, zero)


def line_select(mask, la, lb):
    return tuple(T.fp2_select(mask, x, y) for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# Miller loop steps (Jacobian T, denominator-cleared lines)
# ---------------------------------------------------------------------------

def _dbl_step(Tj, xp, yp):
    """Doubling step.  Tj = (X, Y, Z) Jacobian over Fp2; (xp, yp) affine Fp.

    Line (scaled by 2YZ^3 in Fp2, killed by final exp):
      a = 3X^3 - 2Y^2,  b = -3X^2 Z^2 * xp,  c = 2YZ^3 * yp.

    The XLA form, which the CPU tier traces.  On TPU the loop never
    calls it: the step is one fused Pallas kernel on packed state
    (PallasField.g2_dbl_line, identical formulas, see `_tile_halves`).
    """
    X, Y, Z = Tj
    XX, YY, ZZ, YZ = T.fp2_products([(X, X), (Y, Y), (Z, Z), (Y, Z)])
    xyy = T.fp2_add(X, YY)
    E = T.fp2_mul_small(XX, 3)
    X3c, YZ3, XXZZ, C, S2, F_ = T.fp2_products(
        [(XX, X), (YZ, ZZ), (XX, ZZ), (YY, YY), (xyy, xyy), (E, E)])
    a = T.fp2_sub(T.fp2_mul_small(X3c, 3), T.fp2_mul_small(YY, 2))
    nb3 = T.fp2_neg(T.fp2_mul_small(XXZZ, 3))
    cc2 = T.fp2_mul_small(YZ3, 2)
    # line coefficients scaled by the Fp coordinates of P (4 Fp products)
    sc = FP_products([(nb3[0], xp), (nb3[1], xp), (cc2[0], yp), (cc2[1], yp)])
    b = (sc[0], sc[1])
    c = (sc[2], sc[3])

    # dbl-2009-l (shares XX, YY)
    D = T.fp2_sub(S2, T.fp2_add(XX, C))
    D = T.fp2_add(D, D)
    X2 = T.fp2_sub(F_, T.fp2_add(D, D))
    (Et,) = T.fp2_products([(E, T.fp2_sub(D, X2))])
    Y2 = T.fp2_sub(Et, T.fp2_mul_small(C, 8))
    Z2 = T.fp2_add(YZ, YZ)
    return (X2, Y2, Z2), (a, b, c)


def _add_step(Tj, Q, xp, yp):
    """Mixed addition step.  Q = (xq, yq) affine Fp2.

    With H = xq Z^2 - X, r = 2(yq Z^3 - Y), line scaled by -2*(mu Z) where
    mu = -H:  a = r*xq - 2HZ*yq,  b = -r*xp,  c = 2HZ*yp.

    The XLA form, as `_dbl_step`; on TPU the loop runs
    PallasField.g2_add_line on packed state instead.
    """
    X, Y, Z = Tj
    xq, yq = Q
    ZZ, yqZ = T.fp2_products([(Z, Z), (yq, Z)])
    U2, S2 = T.fp2_products([(xq, ZZ), (yqZ, ZZ)])
    H = T.fp2_sub(U2, X)
    r = T.fp2_mul_small(T.fp2_sub(S2, Y), 2)
    ZH = T.fp2_add(Z, H)
    HH, rr, ZH2, HZ = T.fp2_products([(H, H), (r, r), (ZH, ZH), (H, Z)])
    I = T.fp2_mul_small(HH, 4)
    HZ2 = T.fp2_mul_small(HZ, 2)
    J, V, rxq, hzyq = T.fp2_products([(H, I), (X, I), (r, xq), (HZ2, yq)])
    X3 = T.fp2_sub(T.fp2_sub(rr, J), T.fp2_mul_small(V, 2))
    rV, YJ = T.fp2_products([(r, T.fp2_sub(V, X3)), (Y, J)])
    Y3 = T.fp2_sub(rV, T.fp2_mul_small(YJ, 2))
    Z3 = T.fp2_sub(ZH2, T.fp2_add(ZZ, HH))

    a = T.fp2_sub(rxq, hzyq)
    nr = T.fp2_neg(r)
    sc = FP_products([(nr[0], xp), (nr[1], xp), (HZ2[0], yp), (HZ2[1], yp)])
    b = (sc[0], sc[1])
    c = (sc[2], sc[3])
    return (X3, Y3, Z3), (a, b, c)


# ---------------------------------------------------------------------------
# Multi-pair Miller loop: one masked scan over the BLS parameter bits
# ---------------------------------------------------------------------------

def miller_loop_pairs(pairs, active=None, _keep_tiled=False):
    """Product of Miller loops over K (P, Q) pairs with shared squarings
    (golden `multi_miller_loop`, pairing.py:103-117).

    pairs: list of ((xp, yp), (xq, yq)) — P affine Fp coords, Q affine Fp2.
    active: optional list of bool[...] masks; inactive pairs contribute 1.
    Returns flat Fp12 f, conjugated for the negative BLS parameter.
    `_keep_tiled` (pairing_check_pairs' seam) returns the packed TileForm
    on the Pallas path so final_exp stays tile-resident.
    """
    shape = pairs[0][0][0].shape[:-1]
    K = len(pairs)
    if active is None:
        active = [None] * K

    pf = FP._pallas()
    if pf is not None and K == 2 and miller_merged() \
            and not compact_graphs():
        # the 2-pair verify shape: whole iterations run as single merged
        # kernels on TileForm state (f, T resident across the ladder)
        return _miller_loop_pairs_merged(pf, pairs, active, shape,
                                         _keep_tiled)

    f = F.flat_tile(F.flat_broadcast(F.FLAT_ONE, shape))
    if pf is not None:
        T0, dbl_half, add_half = _tile_halves(pf, pairs, active, shape)
    else:
        T0, dbl_half, add_half = _xla_halves(pairs, active, shape)
    # The parameter's bits are static (field.tail_segments): every bit
    # runs the doubling half, only the 5 set bits the addition half —
    # nothing is computed just to be masked away.
    f, _ = segmented_ladder(_X_SEGMENTS, (f, T0), dbl_half, add_half)
    f = F.flat_conj(f)                    # x < 0 (packed on Pallas)
    return f if _keep_tiled else F.flat_untile(f)


def _tile_halves(pf, pairs, active, shape):
    """The ladder's two halves on the Pallas path, and the T they start
    from.  The whole state stays in the kernels' tile layout: f as
    `flat_tile` gives it, T one packed TileForm of 6 coordinates with
    the K pairs joined on the tile axis (`tile_stack`), so a step
    launches once over all pairs.  Every coordinate of every pair, T =
    (xq, yq, 1) and the fixed P, crosses the layout boundary in ONE
    `pack_coords` before the ladder, each `active` mask in one
    `mask_wrap`; Q is T's first four coordinates.  A half is then
    kernels only: `flat_sqr`, the step kernel, which writes T' and the K
    masked lines already in `flat_mul`'s sparse layout, and K `flat_mul`,
    each reading its pair's run of the line's tiles in place."""
    from drand_tpu.ops.pallas_field import N_LIMBS, tile_split, tile_stack
    K = len(pairs)
    one = T.fp2_broadcast(T.FP2_ONE, shape)
    coords = []
    for (xp, yp), (xq, yq) in pairs:
        coords += [xq[0], xq[1], yq[0], yq[1], one[0], one[1], xp, yp]
    packed = pf.pack_coords([jnp.broadcast_to(c, shape + c.shape[-1:])
                             for c in coords])
    Tt, Pt = tile_split(tile_stack(tile_split(packed, [8 * N_LIMBS] * K)),
                        [6 * N_LIMBS, 2 * N_LIMBS])
    Qt = tile_split(Tt, [4 * N_LIMBS, 2 * N_LIMBS])[0]
    Mt = jnp.concatenate(
        [pf.mask_wrap(True if m is None else m, shape) for m in active],
        axis=0).astype(jnp.int32)[:, None]

    def mul_lines(f, lines):
        for k in range(K):
            f = pf.flat_mul(f, lines, LINE_IDX, b_run=k)
        return f

    def dbl_half(carry):
        """Shared squaring + all pairs' doubling step (every iteration)."""
        f, Tc = carry
        f = F.flat_sqr(f)
        Tc, lines = pf.g2_dbl_line(Tc, Pt, Mt)
        return mul_lines(f, lines), Tc

    def add_half(carry):
        f, Tc = carry
        Tc, lines = pf.g2_add_line(Tc, Qt, Pt, Mt)
        return mul_lines(f, lines), Tc

    return Tt, dbl_half, add_half


def _xla_halves(pairs, active, shape):
    """The ladder's two halves in XLA (`_dbl_step`/`_add_step`), which
    the CPU tier traces, and the T they start from: a tuple of Jacobian
    points, one a pair."""
    K = len(pairs)
    Ts = tuple((q[0], q[1], T.fp2_broadcast(T.FP2_ONE, shape)) for _, q in pairs)

    def masked_line(line, mask):
        if mask is None:
            return line
        return line_select(mask, line, line_one(mask.shape))

    # The K pairs' curve steps run STACKED on one fresh leading axis (the
    # step formulas are batch-generic), so each Miller iteration traces
    # ONE doubling/addition program instead of K.
    def _stack_pts(pts):
        return tuple(
            tuple(jnp.stack(
                [jnp.broadcast_to(p[c][j],
                                  shape + p[c][j].shape[-1:]).astype(jnp.int32)
                 for p in pts], 0) for j in range(2))
            for c in range(len(pts[0])))

    def _unstack_pts(st, ncoord):
        return [tuple((st[c][0][k], st[c][1][k]) for c in range(ncoord))
                for k in range(K)]

    _P_STACK = tuple(
        jnp.stack([jnp.broadcast_to(pairs[k][0][j],
                                    shape + pairs[k][0][j].shape[-1:])
                   for k in range(K)], 0).astype(jnp.int32)
        for j in range(2))
    _Q_STACK = _stack_pts([q for _, q in pairs])

    def dbl_half(carry):
        """Shared squaring + stacked-pair doubling step (every iteration)."""
        f, Ts = carry
        f = F.flat_sqr(f)
        Tst, lines = _dbl_step(_stack_pts(Ts), *_P_STACK)
        newTs = _unstack_pts(Tst, 3)
        lns = _unstack_pts(lines, 3)
        for k in range(K):
            f = fp12_mul_line(f, masked_line(tuple(lns[k]), active[k]))
        return f, tuple(newTs)

    def add_half(carry):
        f, Ts = carry
        Ast, lines = _add_step(_stack_pts(Ts), _Q_STACK, *_P_STACK)
        newTs = []
        Aks = _unstack_pts(Ast, 3)
        lns = _unstack_pts(lines, 3)
        for k in range(K):
            if active[k] is None:
                Tk = tuple(Aks[k])
            else:
                Tk = tuple(T.fp2_select(active[k], x, y)
                           for x, y in zip(Aks[k], Ts[k]))
            f = fp12_mul_line(f, masked_line(tuple(lns[k]), active[k]))
            newTs.append(Tk)
        return f, tuple(newTs)

    return Ts, dbl_half, add_half


def _miller_loop_pairs_merged(pf, pairs, active, shape, _keep_tiled=False):
    """The merged-kernel executor for the 2-pair pairing check (ISSUE 9
    tentpole): every doubling iteration is ONE Pallas launch
    (PallasField.miller_dbl_iter — f^2, both doubling steps, in-kernel
    flat-line encoding + masking, and the line multiplies, sparse-merged
    when DRAND_TPU_LINE_MERGE), every set-bit addition likewise
    (miller_add_iter).  f and both T states thread the whole ladder as
    TileForm — zero layout-boundary crossings per iteration; only the
    state packs at entry and f unwraps after the loop.

    Bit-exactness vs the trio path: the step bodies ARE
    _g2_dbl_line_rows/_g2_add_line_rows (shared code), the multiply
    phases share _mul_phase/_sqr_phase with the standalone kernels, and
    f^2*(l1*l2) == (f^2*l1)*l2 exactly (field associativity + canonical
    Montgomery-form uniqueness) — pinned by the sim KATs and the
    --runslow mixed-batch pairing test."""
    from drand_tpu.ops.pallas_field import LINE_IDX as _KERNEL_LINE_IDX
    from drand_tpu.ops.pallas_field import TileForm
    assert tuple(_KERNEL_LINE_IDX) == LINE_IDX
    lm = line_merge_enabled()
    one = T.fp2_broadcast(T.FP2_ONE, shape)
    Tc, Qc, Pc = [], [], []
    for (xp, yp), (xq, yq) in pairs:
        Tc += [xq[0], xq[1], yq[0], yq[1], one[0], one[1]]
        Qc += [xq[0], xq[1], yq[0], yq[1]]
        Pc += [xp, yp]
    bc = lambda cs: [jnp.broadcast_to(c, shape + (c.shape[-1],)
                                      ).astype(jnp.int32) for c in cs]
    Tt = pf.pack_coords(bc(Tc))
    Qt = pf.pack_coords(bc(Qc))
    Pt = pf.pack_coords(bc(Pc))
    ms = [a if a is not None else jnp.ones(shape, bool) for a in active]
    Mt = TileForm.wrap(
        jnp.stack([jnp.broadcast_to(m, shape).astype(jnp.int32)
                   for m in ms], axis=-1), 2)
    f = F.flat_tile(F.flat_broadcast(F.FLAT_ONE, shape))

    def dbl(c):
        fc, Tcur = c
        return pf.miller_dbl_iter(fc, Tcur, Pt, Mt, line_merge=lm)

    def add(c):
        fc, Tcur = c
        return pf.miller_add_iter(fc, Tcur, Qt, Pt, Mt, line_merge=lm)

    f, _ = segmented_ladder(_X_SEGMENTS, (f, Tt), dbl, add)
    f = F.flat_conj(f)                    # x < 0, packed conj kernel
    return f if _keep_tiled else F.flat_untile(f)


# ---------------------------------------------------------------------------
# Fixed-Q Miller loop: where a pair's G2 argument is the whole batch's, its
# lines come from a table computed once a key, and the loop holds no G2 point
# ---------------------------------------------------------------------------

# table rows: one a doubling step and one more on each set bit, in the
# ladder's order
LINE_STEPS = len(_X_BITS) - 1 + _X_BITS[1:].count("1")


def _host_dbl(Tj):
    """`_dbl_step` on Python integers, before the scaling by P:
    (2T, (a, nb3, cc2)), so that b = nb3 * xp and c = cc2 * yp."""
    X, Y, Z = Tj
    XX, YY, ZZ, YZ = G.fp2_sqr(X), G.fp2_sqr(Y), G.fp2_sqr(Z), G.fp2_mul(Y, Z)
    xyy = G.fp2_add(X, YY)
    E = G.fp2_mul_fp(XX, 3)
    X3c, YZ3, XXZZ = G.fp2_mul(XX, X), G.fp2_mul(YZ, ZZ), G.fp2_mul(XX, ZZ)
    C, S2, F_ = G.fp2_sqr(YY), G.fp2_sqr(xyy), G.fp2_sqr(E)
    a = G.fp2_sub(G.fp2_mul_fp(X3c, 3), G.fp2_mul_fp(YY, 2))
    nb3 = G.fp2_neg(G.fp2_mul_fp(XXZZ, 3))
    cc2 = G.fp2_mul_fp(YZ3, 2)
    D = G.fp2_mul_fp(G.fp2_sub(S2, G.fp2_add(XX, C)), 2)
    X2 = G.fp2_sub(F_, G.fp2_mul_fp(D, 2))
    Y2 = G.fp2_sub(G.fp2_mul(E, G.fp2_sub(D, X2)), G.fp2_mul_fp(C, 8))
    return (X2, Y2, G.fp2_mul_fp(YZ, 2)), (a, nb3, cc2)


def _host_add(Tj, Q):
    """`_add_step` likewise: (T + Q, (a, -r, 2HZ))."""
    X, Y, Z = Tj
    xq, yq = Q
    ZZ = G.fp2_sqr(Z)
    H = G.fp2_sub(G.fp2_mul(xq, ZZ), X)
    r = G.fp2_mul_fp(G.fp2_sub(G.fp2_mul(G.fp2_mul(yq, Z), ZZ), Y), 2)
    HH, HZ2 = G.fp2_sqr(H), G.fp2_mul_fp(G.fp2_mul(H, Z), 2)
    I = G.fp2_mul_fp(HH, 4)
    J, V = G.fp2_mul(H, I), G.fp2_mul(X, I)
    X3 = G.fp2_sub(G.fp2_sub(G.fp2_sqr(r), J), G.fp2_mul_fp(V, 2))
    Y3 = G.fp2_sub(G.fp2_mul(r, G.fp2_sub(V, X3)),
                   G.fp2_mul_fp(G.fp2_mul(Y, J), 2))
    Z3 = G.fp2_sub(G.fp2_sqr(G.fp2_add(Z, H)), G.fp2_add(ZZ, HH))
    a = G.fp2_sub(G.fp2_mul(r, xq), G.fp2_mul(HZ2, yq))
    return (X3, Y3, Z3), (a, G.fp2_neg(r), HZ2)


def fixed_q_table(qs) -> np.ndarray:
    """The lines of the ladder over |x| for K fixed affine G2 points
    (golden-model Fp2 pairs): int32 [LINE_STEPS, K, 6, 32].

    A row is one step's triple a pair, before its scaling by P (`a`, `nb3`,
    `cc2` of `_dbl_step`; `a`, `-r`, `2HZ` of `_add_step`), by the same
    denominator-cleared Jacobian formulas, in the sparse flat slots of
    `line_to_flat` (`x - y` of the three coefficients, then `y`) and in
    Montgomery limbs.  Scaling by an Fp value commutes with `x - y`, and
    a canonical residue has one limb form, so the line a row of the batch
    gets from this table is the per-row path's to the limb."""
    rows = []
    Ts = [(q[0], q[1], G.FP2_ONE) for q in qs]
    for bit in _X_BITS[1:]:
        steps = [[_host_dbl(t) for t in Ts]]
        if bit == "1":
            steps.append([_host_add(t, q) for (t, _), q in zip(steps[0], qs)])
        for step in steps:
            Ts = [t for t, _ in step]
            rows.append([[(c[0] - c[1]) % G.P for c in line]
                         + [c[1] for c in line] for _, line in step])
    assert len(rows) == LINE_STEPS
    return np.stack([np.stack([np.stack([FP.to_mont_host(v) for v in pair])
                               for pair in row]) for row in rows])


_LINE_ONE_FLAT = F.FLAT_ONE[:6]       # line_to_flat(line_one): (1, 0, 0)


def _line_scaler(ps, active, shape):
    """What turns a table row [K, 6, 32] into that step's K sparse flat
    lines for the batch: the `b` and `c` slots times the row's `xp` and
    `yp` (4 Fp products a pair, all K pairs' in one call), an inactive
    row's line the neutral one."""
    pf = FP._pallas()
    if pf is not None:
        return pf.line_scaler(ps, active, shape, _LINE_ONE_FLAT)
    ps = [[jnp.broadcast_to(c, shape + c.shape[-1:]) for c in p] for p in ps]

    def lines(row):
        sc = FP_products([(row[k, s], ps[k][j]) for k in range(len(ps))
                          for s, j in ((1, 0), (2, 1), (4, 0), (5, 1))])
        out = []
        for k, mask in enumerate(active):
            b_lo, c_lo, b_y, c_y = sc[4 * k:4 * k + 4]
            a_lo, a_y = (jnp.broadcast_to(row[k, s], b_lo.shape)
                         for s in (0, 3))
            line = jnp.stack([a_lo, b_lo, c_lo, a_y, b_y, c_y], axis=-2)
            if mask is not None:
                line = F.flat_select(mask, line, _LINE_ONE_FLAT)
            out.append(line)
        return out
    return lines


def miller_loop_fixed_q(ps, table, active=None, _keep_tiled=False):
    """`miller_loop_pairs` for K pairs whose G2 arguments are the whole
    batch's: `ps` the K affine G1 points (xp, yp), `table` their Qs'
    `fixed_q_table` (a run-time value: one program serves every key),
    `active` as there.  The state is f alone; a step squares f (on a
    doubling), scales the step's table row by the rows' P and multiplies
    the K lines in.  The same f as `miller_loop_pairs`, to the limb."""
    shape = ps[0][0].shape[:-1]
    lines = _line_scaler(ps, active or [None] * len(ps), shape)

    def mul_lines(f, i):
        for line in lines(jax.lax.dynamic_index_in_dim(table, i, 0, False)):
            f = F.flat_mul(f, line, LINE_IDX)
        return f, i + 1

    f = F.flat_tile(F.flat_broadcast(F.FLAT_ONE, shape))
    f, _ = segmented_ladder(_X_SEGMENTS, (f, jnp.int32(0)),
                            lambda c: mul_lines(F.flat_sqr(c[0]), c[1]),
                            lambda c: mul_lines(*c))
    f = F.flat_conj(f)                    # x < 0 (packed on Pallas)
    return f if _keep_tiled else F.flat_untile(f)


# ---------------------------------------------------------------------------
# Final exponentiation (flat)
# ---------------------------------------------------------------------------

def _unitary_pow_x_abs(f):
    """f^|x| with cyclotomic squarings (valid: callers only pass
    post-easy-part elements).  The same ladder as the Miller loop
    (field.segmented_ladder): 63 squarings and, on the 5 set bits only,
    a multiply — a masked scan would execute (and discard) a full Fp12
    multiply on all 58 zero bits.  On the Pallas path the
    chain is tile-resident, and a TileForm input stays packed (the
    whole final exponentiation now threads TileForm; `ft is f` exactly
    when no conversion happened)."""
    ft = F.flat_tile(f)
    out = segmented_ladder(_X_SEGMENTS, ft, F.flat_cyclo_sqr,
                           lambda acc: F.flat_mul(acc, ft))
    return out if ft is f else F.flat_untile(out)


def _pow_x(f):
    """f^x = conj(f^|x|) for unitary f (x < 0)."""
    return F.flat_conj(_unitary_pow_x_abs(f))


def _pow_x_minus_1(f):
    """f^(x - 1) = conj(f^(|x| + 1)) for unitary f (x < 0)."""
    return F.flat_conj(F.flat_mul(_unitary_pow_x_abs(f), f))


def final_exp(f):
    """Same exponent as the golden model (easy part, then the hard part
    3(p^4 - p^2 + 1)/r), computed via the factored form

        (x - 1)^2 * (x + p) * (x^2 + p^2 - 1) + 3

    (Hayashida-Teruya-style; verified to EQUAL 3(p^4-p^2+1)/r for the
    BLS12-381 parameters, so the result is bit-identical to the golden
    model's base-p _L0.._L3 decomposition at pairing.py:159-172).  Both
    run 5 x-power chains — degree 5 in x is irreducible — but this form
    replaces the ~14 small-coefficient multiplies of _poly_pow with 6
    multiplies, 2 Frobenius maps and one cyclotomic square."""
    f = F.flat_mul(F.flat_conj(f), F.flat_inv(f))        # f^(p^6 - 1)
    f = F.flat_mul(F.flat_frob(f, 2), f)                 # ^(p^2 + 1)
    m2 = _pow_x_minus_1(_pow_x_minus_1(f))               # f^((x-1)^2)
    m3 = F.flat_mul(_pow_x(m2), F.flat_frob(m2, 1))      # ^(x + p)
    m4 = F.flat_mul(F.flat_mul(_pow_x(_pow_x(m3)), F.flat_frob(m3, 2)),
                    F.flat_conj(m3))                     # ^(x^2 + p^2 - 1)
    f3 = F.flat_mul(F.flat_cyclo_sqr(f), f)              # the +3 term
    return F.flat_mul(m4, f3)


def pairing_check_pairs(pairs, active=None):
    """bool[...]: prod over pairs of e(P_i, Q_i) == 1, one final exp.

    On the Pallas path the whole check is tile-resident: the Miller loop
    hands final_exp the PACKED accumulator (flat_mul/conj/frob/
    cyclo_sqr/chains all thread TileForm), and the verdict mask crosses
    the layout boundary once at flat_is_one — entry packs + exit mask
    instead of per-call relayout (flat_inv's tower evaluation is the one
    counted interior exception, once per check)."""
    with jax.named_scope(MILLER):
        f = miller_loop_pairs(pairs, active,
                              _keep_tiled=FP._pallas() is not None)
    with jax.named_scope(FINAL_EXP):
        return F.flat_is_one(final_exp(f))


def pairing_check_fixed_q(ps, table, active=None):
    """`pairing_check_pairs` where every pair's Q is the whole batch's:
    prod of e(P_i, Q_i) == 1 with the Qs' lines from `table`
    (`miller_loop_fixed_q`), one final exp."""
    with jax.named_scope(MILLER):
        f = miller_loop_fixed_q(ps, table, active,
                                _keep_tiled=FP._pallas() is not None)
    with jax.named_scope(FINAL_EXP):
        return F.flat_is_one(final_exp(f))

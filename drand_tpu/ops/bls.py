"""Batched BLS12-381 signature verification kernels (JAX, TPU-first).

The device-side heart of the framework: where the reference verifies one
beacon at a time through `chain.Verifier.VerifyBeacon` -> 2 CPU pairings
(`chain/verify.go:38-45`, `key/curve.go:36`), these kernels verify a whole
`[B]` batch of beacons — compressed-point deserialization, subgroup checks,
hash-to-curve, a shared 2-pair Miller loop and one final exponentiation per
element — in a single XLA program, vmapped/shardable over the round axis
(the batching seam identified in SURVEY.md §5.7).

Scheme shapes supported:
  - signatures on G2, public keys on G1 (drand default: pedersen-bls-*)
  - signatures on G1, public keys on G2 (short-sig bls-unchained-g1 scheme)

Round-9 kernel path (ISSUE 9): on TPU the pipeline under these entry
points is tile-resident — decompression square roots and the SSWU
sqrt_ratio run packed (towers), the subgroup/cofactor ladders thread
packed points (curve.point_mul_const), and the 2-pair pairing check runs
merged Miller-iteration kernels with f/T in TileForm through the final
exponentiation (pairing.pairing_check_pairs), so the layout boundary is
crossed at byte-unpack entry and verdict exit instead of per kernel
call.  DRAND_TPU_MILLER_MERGED=0 restores the kernel-trio path
(bit-identical; AOT-keyed separately).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from drand_tpu.crypto.bls12381.constants import P
from drand_tpu.ops import H2C, SIG_DECODE
from drand_tpu.ops import curve as DC
from drand_tpu.ops import h2c as DH
from drand_tpu.ops import pairing as DP
from drand_tpu.ops import towers as T
from drand_tpu.ops.field import FP, N_LIMBS, int_to_limbs
from drand_tpu.ops.sha256 import sha256

_HALF_P_PLUS1 = int_to_limbs((P - 1) // 2 + 1)
_P_LIMBS = int_to_limbs(P)


def _fp_canon(a_mont):
    """Montgomery -> canonical limb form (for lexicographic sign rules)."""
    return FP.from_mont(a_mont)


def _fp_gt_half(a_canon):
    """a > (p-1)/2 on canonical limbs."""
    return FP._lex_ge(a_canon, _HALF_P_PLUS1)


def _fp2_gt_half(a_mont):
    """ZCash Fp2 sign rule: lexicographic, c1 most significant
    (golden curve.py:387-393)."""
    c0, c1 = a_mont
    c0c, c1c = _fp_canon(c0), _fp_canon(c1)
    c1z = FP.is_zero(c1c)
    return jnp.where(c1z, _fp_gt_half(c0c), _fp_gt_half(c1c))


# ---------------------------------------------------------------------------
# Batched compressed-point deserialization (ZCash format, drand wire)
# ---------------------------------------------------------------------------

def _split_flags(first_byte):
    comp = (first_byte >> 7) & 1
    inf = (first_byte >> 6) & 1
    sign = (first_byte >> 5) & 1
    return comp, inf, sign


def g2_decompress(sig_bytes: jnp.ndarray):
    """[..., 96] uint8 compressed G2 -> ((x, y) affine Fp2, inf, valid).

    valid covers: compression flag set, x-coordinates canonical (< p), and
    x on the twist curve (y^2 = x^3 + 4(1+u) solvable).  Subgroup membership
    is checked separately (g2_in_subgroup) because it costs a scalar mul.
    """
    comp, inf, sign = _split_flags(sig_bytes[..., 0].astype(jnp.int32))
    b = sig_bytes.astype(jnp.uint8)
    first = (b[..., 0] & 0x1F).astype(jnp.uint8)
    x1b = jnp.concatenate([first[..., None], b[..., 1:48]], axis=-1)
    x0b = b[..., 48:96]
    x1_limbs = DH._be_bytes_to_limbs(x1b)
    x0_limbs = DH._be_bytes_to_limbs(x0b)
    canon = (~FP._lex_ge(x1_limbs, _P_LIMBS)) & (~FP._lex_ge(x0_limbs, _P_LIMBS))
    zero_hi = jnp.zeros_like(x1_limbs)
    x = (FP.reduce_wide(x0_limbs, zero_hi), FP.reduce_wide(x1_limbs, zero_hi))
    y2 = T.fp2_add(T.fp2_mul(T.fp2_sqr(x), x), T.fp2_const((4, 4)))
    y, on_curve = T.fp2_sqrt_cand(y2)
    flip = _fp2_gt_half(y) != (sign > 0)
    y = T.fp2_select(flip, T.fp2_neg(y), y)
    valid = (comp > 0) & canon & (on_curve | (inf > 0))
    return (x, y), inf > 0, valid


def g1_decompress(sig_bytes: jnp.ndarray):
    """[..., 48] uint8 compressed G1 -> ((x, y) affine Fp, inf, valid)."""
    comp, inf, sign = _split_flags(sig_bytes[..., 0].astype(jnp.int32))
    b = sig_bytes.astype(jnp.uint8)
    first = (b[..., 0] & 0x1F).astype(jnp.uint8)
    xb = jnp.concatenate([first[..., None], b[..., 1:48]], axis=-1)
    x_limbs = DH._be_bytes_to_limbs(xb)
    canon = ~FP._lex_ge(x_limbs, _P_LIMBS)
    x = FP.reduce_wide(x_limbs, jnp.zeros_like(x_limbs))
    y2 = T.fp_add(T.fp_mul(T.fp_sqr(x), x), T.fp_const(4))
    y = T.fp_sqrt_cand(y2)
    on_curve = FP.eq(T.fp_sqr(y), y2)
    flip = _fp_gt_half(_fp_canon(y)) != (sign > 0)
    y = T.fp_select(flip, T.fp_neg(y), y)
    valid = (comp > 0) & canon & (on_curve | (inf > 0))
    return (x, y), inf > 0, valid


# ---------------------------------------------------------------------------
# Batched verification kernels
# ---------------------------------------------------------------------------

def _const_g1_affine(pt_jac):
    """Golden G1 Jacobian point -> affine device constants."""
    from drand_tpu.crypto.bls12381 import curve as GC
    aff = GC.g1_affine(pt_jac)
    return (jnp.asarray(FP.to_mont_host(aff[0])), jnp.asarray(FP.to_mont_host(aff[1])))


def const_g2_lines(pk_jac):
    """Golden G2 Jacobian public key -> the G1-signature program's third
    argument: the lines of the Miller ladder for the check's two fixed G2
    points, the generator and the key (`pairing.fixed_q_table`, pairs in
    that order), computed once a key with Python integers."""
    from drand_tpu.crypto.bls12381 import curve as GC
    return jnp.asarray(DP.fixed_q_table(
        [GC.g2_affine(GC.G2_GEN), GC.g2_affine(pk_jac)]))


def _bcast_fp_pair(pair, shape):
    return tuple(jnp.broadcast_to(c, shape + (N_LIMBS,)).astype(jnp.int32) for c in pair)


def verify_g2_sigs(msgs: jnp.ndarray, sig_bytes: jnp.ndarray, pk_aff, dst: bytes,
                   neg_gen_aff=None):
    """Batched BLS verify, signatures on G2 (drand pedersen-bls schemes).

    msgs [..., L] uint8 (already-digested round messages), sig_bytes
    [..., 96] uint8, pk_aff = ((x, y)) affine G1 device pair broadcastable
    over the batch.  Checks e(-g1, sigma) * e(pk, H(m)) == 1 plus
    deserialization validity and G2 subgroup membership
    (reference: `key.Scheme.VerifyRecovered` at `chain/verify.go:44`).
    """
    shape = msgs.shape[:-1]
    with jax.named_scope(SIG_DECODE):
        (sx, sy), s_inf, s_valid = g2_decompress(sig_bytes)
        sig_jac = (sx, sy, T.fp2_broadcast(T.FP2_ONE, shape))
        in_sub = DC.g2_in_subgroup(sig_jac)

    with jax.named_scope(H2C):
        h_jac = DH.hash_to_g2(msgs, dst)
        (hx, hy), h_inf = DC.point_to_affine(h_jac, DC.Fp2Ops)

    if neg_gen_aff is None:
        from drand_tpu.crypto.bls12381 import curve as GC
        neg_gen_aff = _const_g1_affine(GC.g1_neg(GC.G1_GEN))
    p1 = _bcast_fp_pair(neg_gen_aff, shape)
    p2 = _bcast_fp_pair(pk_aff, shape) if pk_aff[0].ndim == 1 else pk_aff
    ok = DP.pairing_check_pairs(
        [(p1, (sx, sy)), (p2, (hx, hy))],
        active=[~s_inf, ~h_inf])
    return ok & s_valid & ~s_inf & in_sub


def verify_g1_sigs(msgs: jnp.ndarray, sig_bytes: jnp.ndarray, pk_lines, dst: bytes):
    """Batched BLS verify, signatures on G1, public key on G2 (short-sig
    scheme, BASELINE.md config 4).  Checks e(-sigma, g2) * e(H(m), pk) == 1.

    Both G2 arguments are the same in every row, so the program takes
    neither: `pk_lines` is `const_g2_lines(pk)`, int32 [68, 2, 6, 32], the
    Miller loop's lines for the generator and the key, and the loop
    (`pairing.miller_loop_fixed_q`) carries no G2 point.  A run-time
    value: one program serves every key.
    """
    shape = msgs.shape[:-1]
    with jax.named_scope(SIG_DECODE):
        (sx, sy), s_inf, s_valid = g1_decompress(sig_bytes)
        sig_jac = (sx, sy, jnp.broadcast_to(T.FP_ONE, shape + (N_LIMBS,)).astype(jnp.int32))
        in_sub = DC.g1_in_subgroup(sig_jac)

    with jax.named_scope(H2C):
        h_jac = DH.hash_to_g1(msgs, dst)
        (hx, hy), h_inf = DC.point_to_affine(h_jac, DC.FpOps)

    ok = DP.pairing_check_fixed_q(
        [(sx, T.fp_neg(sy)), (hx, hy)], pk_lines, active=[~s_inf, ~h_inf])
    return ok & s_valid & ~s_inf & in_sub


# ---------------------------------------------------------------------------
# Threshold BLS: batched partial-signature verification
# ---------------------------------------------------------------------------

def pubpoly_eval_g1(commits, indices):
    """Horner-in-the-exponent evaluation of the public polynomial at
    x = index + 1 (reference: `share.PubPoly.Eval`, used per partial at
    `chain/beacon/node.go:125`).

    commits: list of t G1 affine device pairs (threshold-many commitments,
    broadcastable constants); indices: int32[...] share indices.
    Returns Jacobian G1 points [...].
    """
    shape = indices.shape
    x = (indices + 1).astype(jnp.int32)
    # 16-bit MSB-first bits of x (share indices are < 2^16 on the wire)
    bits = ((x[..., None] >> jnp.arange(15, -1, -1)) & 1).astype(jnp.int32)
    acc = None
    for cm in reversed(commits):
        cm_jac = (_bcast_one(cm[0], shape), _bcast_one(cm[1], shape),
                  jnp.broadcast_to(T.FP_ONE, shape + (N_LIMBS,)).astype(jnp.int32))
        if acc is None:
            acc = cm_jac
        else:
            acc = DC.point_mul_bits(acc, bits, DC.FpOps)
            acc = DC.point_add(acc, cm_jac, DC.FpOps)
    return acc


def _bcast_one(c, shape):
    return jnp.broadcast_to(c, shape + (N_LIMBS,)).astype(jnp.int32)


def pubpoly_eval_g1_stacked(ctx, cty, indices):
    """Row-stacked Horner-in-the-exponent: row r evaluates ITS OWN
    polynomial (ctx[r], cty[r]) at x = indices[r] + 1 — the DKG deal/
    justification verification shape, where every dealer commits to a
    different polynomial (vs `pubpoly_eval_g1`, one poly at many
    indices).  An n=128/t=65 ceremony's O(n·t) commitment evaluations
    run as one dispatch of this kernel instead of n·(t-1) host ladders.

    ctx, cty: [rows, t, 32] int32 canonical Montgomery affine commit
    coordinates (non-infinite — callers route identity commits to the
    host path, the same exposure `pubpoly_eval_g1` has); indices:
    int32 [rows] share indices.  Returns ((ax, ay), inf) canonical
    Montgomery affine coordinates + infinity mask.  The coefficient loop
    is a `lax.scan` so the graph stays one Horner body at any t (t=65
    unrolled would blow up compile time on every backend).
    """
    rows = ctx.shape[0]
    x = (indices + 1).astype(jnp.int32)
    # 16-bit MSB-first bits of x (share indices are < 2^16 on the wire)
    bits = ((x[:, None] >> jnp.arange(15, -1, -1)) & 1).astype(jnp.int32)
    ones = jnp.broadcast_to(T.FP_ONE, (rows, N_LIMBS)).astype(jnp.int32)
    # highest-degree coefficient seeds the accumulator; the scan folds
    # the remaining coefficients in descending-degree order
    cmx = jnp.flip(ctx, axis=1).transpose(1, 0, 2)       # [t, rows, 32]
    cmy = jnp.flip(cty, axis=1).transpose(1, 0, 2)
    acc0 = (cmx[0].astype(jnp.int32), cmy[0].astype(jnp.int32), ones)

    def body(acc, cm):
        acc = DC.point_mul_bits(acc, bits, DC.FpOps)
        acc = DC.point_add(acc, (cm[0], cm[1], ones), DC.FpOps)
        return acc, None

    acc, _ = jax.lax.scan(body, acc0, (cmx[1:].astype(jnp.int32),
                                       cmy[1:].astype(jnp.int32)))
    return DC.point_to_affine(acc, DC.FpOps)


_pubpoly_eval_g1_stacked_jit = jax.jit(pubpoly_eval_g1_stacked)


def g1_rows_to_limbs(points):
    """Host golden G1 Jacobian points -> (x [n, 32] int32, y [n, 32]
    int32, inf [n] bool) canonical Montgomery affine numpy arrays — the
    same unique representation `signer_table_arrays` stores, so limb
    equality IS point equality."""
    from drand_tpu.crypto.bls12381 import curve as GC
    n = len(points)
    tx = np.zeros((n, N_LIMBS), dtype=np.int32)
    ty = np.zeros((n, N_LIMBS), dtype=np.int32)
    tinf = np.zeros((n,), dtype=bool)
    for i, pt in enumerate(points):
        aff = GC.g1_affine(pt)
        if aff is None:
            tinf[i] = True
            continue
        tx[i] = FP.to_mont_host(aff[0])
        ty[i] = FP.to_mont_host(aff[1])
    return tx, ty, tinf


def dkg_commit_checks(ctx, cty, indices, ex, ey, einf):
    """Batched DKG commitment verification: row r asserts
    poly_r(indices[r] + 1) == expected_r.

    ctx/cty [rows, t, 32] int32 Montgomery affine commit rows (see
    `pubpoly_eval_g1_stacked`), indices int32 [rows], ex/ey [rows, 32] +
    einf [rows] the expected points in the same representation.  Returns
    bool [rows] numpy verdicts.  Canonical Montgomery affine coordinates
    are unique, so the verdict is bit-identical to the host
    `C.g1_eq(poly.eval(i), expected)` scalar path.
    """
    (ax, ay), inf = _pubpoly_eval_g1_stacked_jit(
        jnp.asarray(ctx), jnp.asarray(cty), jnp.asarray(indices))
    einf_j = jnp.asarray(einf)
    eq = jnp.all(ax == jnp.asarray(ex), axis=-1) & \
        jnp.all(ay == jnp.asarray(ey), axis=-1)
    ok = (inf & einf_j) | (~inf & ~einf_j & eq)
    return np.asarray(ok)


def signer_table_arrays(pub_poly, n: int):
    """Host-side build of the per-signer public-key table: the public
    polynomial evaluated at every share index 0..n-1, EXACT golden-model
    Horner (microseconds per index), stored as canonical affine Montgomery
    limb arrays for batch-time gather.

    For a fixed group the eval at index i is a constant — recomputing it
    per partial (the reference's `share.PubPoly.Eval` at
    `chain/beacon/node.go:125`, and this repo's in-batch
    `pubpoly_eval_g1` Horner: t-1 16-bit point-mul ladders PER PARTIAL)
    is the single largest op-count waste in the aggregation hot loop.
    Returns (tx [n, 32] int32, ty [n, 32] int32, tinf [n] bool) numpy
    arrays (device placement is the caller's concern).  Bit-exactness:
    canonical Montgomery affine coordinates are unique, so gathering this
    table feeds the Miller loop the IDENTICAL limbs the in-batch
    eval + point_to_affine path produces.
    """
    from drand_tpu.crypto.bls12381 import curve as GC
    tx = np.zeros((n, N_LIMBS), dtype=np.int32)
    ty = np.zeros((n, N_LIMBS), dtype=np.int32)
    tinf = np.zeros((n,), dtype=bool)
    for i in range(n):
        pt = pub_poly.eval(i)
        if GC.point_is_inf(pt, GC.FP_OPS):
            tinf[i] = True
            continue
        ax, ay = GC.g1_affine(pt)
        tx[i] = FP.to_mont_host(ax)
        ty[i] = FP.to_mont_host(ay)
    return tx, ty, tinf


def _tabled_verify_core(hx, hy, h_inf, sig_bytes, indices, table):
    """Shared tail of the tabled partial-verify kernels: per-partial
    hash-point (already gathered/broadcast), signature decompression +
    subgroup check, table gather at the signer index, 2-pair Miller loop.

    hx/hy: affine Fp2 pairs broadcast to the partial batch shape;
    h_inf bool[...]; indices int32[...]; table = (tx, ty, tinf) with
    leading axis n.  Returns bool[...] verdicts, bit-identical to
    `verify_partial_g2_sigs` for indices in [0, n).
    """
    tx, ty, tinf = table
    n = tx.shape[0]
    shape = indices.shape
    (sx, sy), s_inf, s_valid = g2_decompress(sig_bytes)
    sig_jac = (sx, sy, T.fp2_broadcast(T.FP2_ONE, shape))
    in_sub = DC.g2_in_subgroup(sig_jac)

    idx_ok = (indices >= 0) & (indices < n)
    safe = jnp.clip(indices, 0, n - 1)
    px = jnp.take(tx, safe, axis=0)
    py = jnp.take(ty, safe, axis=0)
    p_inf = jnp.take(tinf, safe, axis=0) | ~idx_ok

    from drand_tpu.crypto.bls12381 import curve as GC
    neg_gen = _const_g1_affine(GC.g1_neg(GC.G1_GEN))
    p1 = _bcast_fp_pair(neg_gen, shape)
    ok = DP.pairing_check_pairs(
        [(p1, (sx, sy)), ((px, py), (hx, hy))],
        active=[~s_inf, ~(h_inf | p_inf)])
    return ok & s_valid & ~s_inf & in_sub & ~p_inf & idx_ok


def verify_partial_g2_sigs_shared(round_msgs, sig_bytes, indices, table,
                                  dst: bytes):
    """Rounds-major tabled tbls VerifyPartial: all n signers of a round
    sign the SAME message, so hash-to-curve runs ONCE per round and
    broadcasts across the signer axis (S-fold fewer `hash_to_g2` ladders
    than the per-partial form), and the public-key eval is a table gather.

    round_msgs [R, L] uint8 (one digest per round), sig_bytes [R, S, 96],
    indices int32 [R, S], table = (tx, ty, tinf) signer-key arrays.
    Returns bool [R, S], bit-identical to `verify_partial_g2_sigs` on the
    flattened batch (canonical Montgomery affine inputs are unique, so
    the Miller loops see identical limbs).
    """
    R, S = indices.shape
    h_jac = DH.hash_to_g2(round_msgs, dst)                       # [R]
    (uhx, uhy), uh_inf = DC.point_to_affine(h_jac, DC.Fp2Ops)

    def _bc(c):
        return jnp.broadcast_to(c[:, None, :], (R, S, N_LIMBS))
    hx = (_bc(uhx[0]), _bc(uhx[1]))
    hy = (_bc(uhy[0]), _bc(uhy[1]))
    h_inf = jnp.broadcast_to(uh_inf[:, None], (R, S))
    return _tabled_verify_core(hx, hy, h_inf, sig_bytes, indices, table)


def verify_partial_g2_sigs_tabled(umsgs, mmap, sig_bytes, indices, table,
                                  dst: bytes):
    """Arrival-order tabled tbls VerifyPartial for the live micro-batcher:
    the batch's DISTINCT messages hash once each and per-partial hash
    points gather through `mmap` (partials of one round burst share one
    hash-to-curve instead of re-running it per packet).

    umsgs [U, L] uint8 (deduplicated messages), mmap int32[B] index into
    the U axis, sig_bytes [B, 96], indices int32[B], table = (tx, ty,
    tinf).  Returns bool [B]."""
    h_jac = DH.hash_to_g2(umsgs, dst)                            # [U]
    (uhx, uhy), uh_inf = DC.point_to_affine(h_jac, DC.Fp2Ops)
    hx = tuple(jnp.take(c, mmap, axis=0) for c in uhx)
    hy = tuple(jnp.take(c, mmap, axis=0) for c in uhy)
    h_inf = jnp.take(uh_inf, mmap, axis=0)
    return _tabled_verify_core(hx, hy, h_inf, sig_bytes, indices, table)


def verify_partial_g2_sigs(msgs, sig_bytes, indices, commits, dst: bytes):
    """Batched tbls VerifyPartial: each signature checked against the public
    polynomial evaluated at its signer index (`chain/beacon/crypto.go:55-59`).

    msgs [..., L] uint8, sig_bytes [..., 96] (index prefix already stripped),
    indices int32[...], commits = list of t G1 affine constant pairs.
    """
    pub_jac = pubpoly_eval_g1(commits, indices)
    (px, py), p_inf = DC.point_to_affine(pub_jac, DC.FpOps)
    shape = msgs.shape[:-1]
    (sx, sy), s_inf, s_valid = g2_decompress(sig_bytes)
    sig_jac = (sx, sy, T.fp2_broadcast(T.FP2_ONE, shape))
    in_sub = DC.g2_in_subgroup(sig_jac)
    h_jac = DH.hash_to_g2(msgs, dst)
    (hx, hy), h_inf = DC.point_to_affine(h_jac, DC.Fp2Ops)
    from drand_tpu.crypto.bls12381 import curve as GC
    neg_gen = _const_g1_affine(GC.g1_neg(GC.G1_GEN))
    p1 = _bcast_fp_pair(neg_gen, shape)
    ok = DP.pairing_check_pairs(
        [(p1, (sx, sy)), ((px, py), (hx, hy))],
        active=[~s_inf, ~(h_inf | p_inf)])
    return ok & s_valid & ~s_inf & in_sub & ~p_inf

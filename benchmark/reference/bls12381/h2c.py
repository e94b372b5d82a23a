"""Hash-to-curve for BLS12-381 G1/G2: RFC 9380 SSWU suites (golden model).

Implements drand's exact wire suites:

  G2: BLS12381G2_XMD:SHA-256_SSWU_RO_  with DST
      BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_NUL_
  G1: BLS12381G1_XMD:SHA-256_SSWU_RO_  with DST
      BLS_SIG_BLS12381G1_XMD:SHA-256_SSWU_RO_NUL_

matching the kilic/bls12-381 hash-to-curve drand calls through
`chain/verify.go:38-45` / `key/curve.go:24-43`.

The SSWU map targets an isogenous curve E'; the isogeny back to E was
RE-DERIVED offline with Velu's formulas (tools/derive_sswu_g2.py,
tools/derive_sswu_g1.py) because this build has zero network egress.  For G2
the derived rational map reproduces RFC 9380 Appendix E.3
coefficient-for-coefficient (pinned in tests/test_h2c_sswu.py); the G2
isogeny is applied in the compact Velu form

    X(x)   = s^2 * (x + v/(x-x0) + w/(x-x0)^2)
    Y(x,y) = s^3 * y * (1 - v/(x-x0)^2 - 2w/(x-x0)^3)

which is algebraically identical to the appendix's coefficient tables.
Points are mapped and ADDED on E' (an isogeny is a group homomorphism), so
the isogeny is evaluated once per hash, then the cofactor is cleared on E.
"""

import hashlib

from . import curve as C
from . import fp as F
from .constants import (DST_G1, DST_G2, ISO1_X_NUM, ISO1_X_DEN, ISO1_Y_NUM,
                        ISO1_Y_DEN, ISO3_S, ISO3_V, ISO3_W, ISO3_X0, P,
                        SSWU_G1_A, SSWU_G1_B, SSWU_G1_Z, SSWU_G2_A, SSWU_G2_B,
                        SSWU_G2_Z)

_L = 64  # bytes per field element draw (ceil((381 + 128)/8))


# ---------------------------------------------------------------------------
# expand_message_xmd (SHA-256)  -- RFC 9380 section 5.3.1
# ---------------------------------------------------------------------------

def expand_message_xmd(msg: bytes, dst: bytes, len_in_bytes: int) -> bytes:
    if len(dst) > 255:
        dst = hashlib.sha256(b"H2C-OVERSIZE-DST-" + dst).digest()
    ell = (len_in_bytes + 31) // 32
    if ell > 255:
        raise ValueError("len_in_bytes too large")
    dst_prime = dst + bytes([len(dst)])
    z_pad = bytes(64)
    l_i_b = len_in_bytes.to_bytes(2, "big")
    b0 = hashlib.sha256(z_pad + msg + l_i_b + b"\x00" + dst_prime).digest()
    out = b""
    bi = hashlib.sha256(b0 + b"\x01" + dst_prime).digest()
    out += bi
    for i in range(2, ell + 1):
        bi = hashlib.sha256(bytes(a ^ b for a, b in zip(b0, bi)) + bytes([i]) + dst_prime).digest()
        out += bi
    return out[:len_in_bytes]


def hash_to_field_fp(msg: bytes, dst: bytes, count: int):
    data = expand_message_xmd(msg, dst, count * _L)
    return [int.from_bytes(data[i * _L:(i + 1) * _L], "big") % P for i in range(count)]


def hash_to_field_fp2(msg: bytes, dst: bytes, count: int):
    data = expand_message_xmd(msg, dst, count * 2 * _L)
    out = []
    for i in range(count):
        c0 = int.from_bytes(data[(2 * i) * _L:(2 * i + 1) * _L], "big") % P
        c1 = int.from_bytes(data[(2 * i + 1) * _L:(2 * i + 2) * _L], "big") % P
        out.append((c0, c1))
    return out


# ---------------------------------------------------------------------------
# Simplified SWU map (RFC 9380 6.6.2) on the isogenous curves
# ---------------------------------------------------------------------------

def _sswu_fp2(u):
    """map_to_curve_simple_swu on E2': y^2 = x^3 + A'x + B' over Fp2."""
    a, b, z = SSWU_G2_A, SSWU_G2_B, SSWU_G2_Z
    u2 = F.fp2_sqr(u)
    zu2 = F.fp2_mul(z, u2)
    tv1 = F.fp2_add(F.fp2_sqr(zu2), zu2)            # Z^2 u^4 + Z u^2
    if tv1 == F.FP2_ZERO:
        x1 = F.fp2_mul(b, F.fp2_inv(F.fp2_mul(z, a)))
    else:
        x1 = F.fp2_mul(F.fp2_neg(F.fp2_mul(b, F.fp2_inv(a))),
                       F.fp2_add(F.FP2_ONE, F.fp2_inv(tv1)))
    gx1 = F.fp2_add(F.fp2_add(F.fp2_mul(F.fp2_sqr(x1), x1), F.fp2_mul(a, x1)), b)
    y1 = F.fp2_sqrt(gx1)
    if y1 is not None:
        x, y = x1, y1
    else:
        x = F.fp2_mul(zu2, x1)
        gx2 = F.fp2_add(F.fp2_add(F.fp2_mul(F.fp2_sqr(x), x), F.fp2_mul(a, x)), b)
        y = F.fp2_sqrt(gx2)
        assert y is not None, "SSWU: g(x2) must be square when g(x1) is not"
    if F.fp2_sgn0(u) != F.fp2_sgn0(y):
        y = F.fp2_neg(y)
    return (x, y)


def _sswu_fp(u):
    """map_to_curve_simple_swu on E1': y^2 = x^3 + A'x + B' over Fp."""
    a, b, z = SSWU_G1_A, SSWU_G1_B, SSWU_G1_Z
    u2 = F.fp_sqr(u)
    zu2 = F.fp_mul(z, u2)
    tv1 = F.fp_add(F.fp_sqr(zu2), zu2)
    if tv1 == 0:
        x1 = F.fp_mul(b, F.fp_inv(F.fp_mul(z, a)))
    else:
        x1 = F.fp_mul(F.fp_neg(F.fp_mul(b, F.fp_inv(a))),
                      F.fp_add(1, F.fp_inv(tv1)))
    gx1 = F.fp_add(F.fp_add(F.fp_mul(F.fp_sqr(x1), x1), F.fp_mul(a, x1)), b)
    y1 = F.fp_sqrt(gx1)
    if y1 is not None:
        x, y = x1, y1
    else:
        x = F.fp_mul(zu2, x1)
        gx2 = F.fp_add(F.fp_add(F.fp_mul(F.fp_sqr(x), x), F.fp_mul(a, x)), b)
        y = F.fp_sqrt(gx2)
        assert y is not None, "SSWU: g(x2) must be square when g(x1) is not"
    if F.fp_sgn0(u) != F.fp_sgn0(y):
        y = F.fp_neg(y)
    return (x, y)


# ---------------------------------------------------------------------------
# Affine addition on a general short-Weierstrass curve (the isogenous curves
# have a != 0, so the production a=0 Jacobian formulas don't apply)
# ---------------------------------------------------------------------------

def _aff_add_fp2(p1, p2, a):
    if p1 is None:
        return p2
    if p2 is None:
        return p1
    (x1, y1), (x2, y2) = p1, p2
    if x1 == x2:
        if F.fp2_add(y1, y2) == F.FP2_ZERO:
            return None
        lam = F.fp2_mul(F.fp2_add(F.fp2_mul_fp(F.fp2_sqr(x1), 3), a),
                        F.fp2_inv(F.fp2_add(y1, y1)))
    else:
        lam = F.fp2_mul(F.fp2_sub(y2, y1), F.fp2_inv(F.fp2_sub(x2, x1)))
    x3 = F.fp2_sub(F.fp2_sub(F.fp2_sqr(lam), x1), x2)
    y3 = F.fp2_sub(F.fp2_mul(lam, F.fp2_sub(x1, x3)), y1)
    return (x3, y3)


def _aff_add_fp(p1, p2, a):
    if p1 is None:
        return p2
    if p2 is None:
        return p1
    (x1, y1), (x2, y2) = p1, p2
    if x1 == x2:
        if F.fp_add(y1, y2) == 0:
            return None
        lam = F.fp_mul(F.fp_add(F.fp_mul(3, F.fp_sqr(x1)), a),
                       F.fp_inv(F.fp_add(y1, y1)))
    else:
        lam = F.fp_mul(F.fp_sub(y2, y1), F.fp_inv(F.fp_sub(x2, x1)))
    x3 = F.fp_sub(F.fp_sub(F.fp_sqr(lam), x1), x2)
    y3 = F.fp_sub(F.fp_mul(lam, F.fp_sub(x1, x3)), y1)
    return (x3, y3)


# ---------------------------------------------------------------------------
# Isogenies E' -> E
# ---------------------------------------------------------------------------

def iso3_map(pt):
    """3-isogeny E2' -> E2 in compact Velu form (equals RFC 9380 E.3)."""
    if pt is None:
        return None
    x, y = pt
    d = F.fp2_sub(x, ISO3_X0)
    if d == F.FP2_ZERO:
        return None  # kernel point maps to infinity
    di = F.fp2_inv(d)
    di2 = F.fp2_sqr(di)
    di3 = F.fp2_mul(di2, di)
    X = F.fp2_add(x, F.fp2_add(F.fp2_mul(ISO3_V, di), F.fp2_mul(ISO3_W, di2)))
    Yfac = F.fp2_sub(F.fp2_sub(F.FP2_ONE, F.fp2_mul(ISO3_V, di2)),
                     F.fp2_mul(F.fp2_add(ISO3_W, ISO3_W), di3))
    Y = F.fp2_mul(y, Yfac)
    s2 = F.fp2_sqr(ISO3_S)
    s3 = F.fp2_mul(s2, ISO3_S)
    return (F.fp2_mul(s2, X), F.fp2_mul(s3, Y))


def _eval_poly_fp(coeffs, x):
    """Horner evaluation, ascending coefficient order."""
    acc = 0
    for c in reversed(coeffs):
        acc = F.fp_add(F.fp_mul(acc, x), c)
    return acc


def iso1_map(pt):
    """11-isogeny E1' -> E1 via the derived rational-map coefficients."""
    if pt is None:
        return None
    x, y = pt
    xd = _eval_poly_fp(ISO1_X_DEN, x)
    yd = _eval_poly_fp(ISO1_Y_DEN, x)
    if xd == 0 or yd == 0:
        return None  # kernel point maps to infinity
    X = F.fp_mul(_eval_poly_fp(ISO1_X_NUM, x), F.fp_inv(xd))
    Y = F.fp_mul(y, F.fp_mul(_eval_poly_fp(ISO1_Y_NUM, x), F.fp_inv(yd)))
    return (X, Y)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def hash_to_g2(msg: bytes, dst: bytes = DST_G2):
    """Hash arbitrary bytes to a G2 subgroup point (Jacobian)."""
    u0, u1 = hash_to_field_fp2(msg, dst, 2)
    q0 = _sswu_fp2(u0)
    q1 = _sswu_fp2(u1)
    s = _aff_add_fp2(q0, q1, SSWU_G2_A)   # add on E2'; isogeny is a hom.
    e = iso3_map(s)
    jac = C.G2_INF if e is None else (e[0], e[1], F.FP2_ONE)
    return C.g2_clear_cofactor(jac)


def hash_to_g1(msg: bytes, dst: bytes = DST_G1):
    """Hash arbitrary bytes to a G1 subgroup point (Jacobian)."""
    u0, u1 = hash_to_field_fp(msg, dst, 2)
    q0 = _sswu_fp(u0)
    q1 = _sswu_fp(u1)
    s = _aff_add_fp(q0, q1, SSWU_G1_A)
    e = iso1_map(s)
    jac = C.G1_INF if e is None else (e[0], e[1], 1)
    return C.g1_clear_cofactor(jac)

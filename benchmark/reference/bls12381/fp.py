"""Field towers for BLS12-381: Fp, Fp2, Fp6, Fp12 (pure-Python golden model).

This is the oracle implementation the TPU (JAX/Pallas) kernels are validated
against.  Representation is deliberately plain for speed and unambiguity:

  Fp   : python int in [0, P)
  Fp2  : (c0, c1)           meaning c0 + c1*u,        u^2 = -1
  Fp6  : (a0, a1, a2)       each Fp2, meaning a0 + a1*v + a2*v^2,  v^3 = xi
  Fp12 : (b0, b1)           each Fp6, meaning b0 + b1*w,           w^2 = v

with xi = 1 + u (the standard BLS12-381 sextic-twist non-residue).

Counterpart of the reference's field tower in kilic/bls12-381 (dep of
`key/curve.go:24`); rebuilt from the mathematical definition, not ported.
"""

from .constants import P

# ---------------------------------------------------------------------------
# Fp
# ---------------------------------------------------------------------------

def fp_add(a, b):
    c = a + b
    return c - P if c >= P else c


def fp_sub(a, b):
    c = a - b
    return c + P if c < 0 else c


def fp_neg(a):
    return P - a if a else 0


def fp_mul(a, b):
    return a * b % P


def fp_sqr(a):
    return a * a % P


def fp_inv(a):
    if a == 0:
        raise ZeroDivisionError("fp inverse of 0")
    return pow(a, P - 2, P)


def fp_pow(a, e):
    return pow(a, e, P)


def fp_is_square(a):
    """Euler criterion; 0 counts as square."""
    return a == 0 or pow(a, (P - 1) // 2, P) == 1


def fp_sqrt(a):
    """Square root in Fp (p = 3 mod 4).  Returns None if not a square."""
    if a == 0:
        return 0
    c = pow(a, (P + 1) // 4, P)
    return c if c * c % P == a else None


def fp_sgn0(a):
    return a & 1


# ---------------------------------------------------------------------------
# Fp2 = Fp[u] / (u^2 + 1)
# ---------------------------------------------------------------------------

FP2_ZERO = (0, 0)
FP2_ONE = (1, 0)
XI = (1, 1)  # the sextic non-residue 1 + u


def fp2(c0, c1=0):
    return (c0 % P, c1 % P)


def fp2_add(a, b):
    return (fp_add(a[0], b[0]), fp_add(a[1], b[1]))


def fp2_sub(a, b):
    return (fp_sub(a[0], b[0]), fp_sub(a[1], b[1]))


def fp2_neg(a):
    return (fp_neg(a[0]), fp_neg(a[1]))


def fp2_conj(a):
    return (a[0], fp_neg(a[1]))


def fp2_mul(a, b):
    a0, a1 = a
    b0, b1 = b
    t0 = a0 * b0
    t1 = a1 * b1
    # (a0+a1)(b0+b1) - t0 - t1 = a0*b1 + a1*b0
    return ((t0 - t1) % P, ((a0 + a1) * (b0 + b1) - t0 - t1) % P)


def fp2_sqr(a):
    a0, a1 = a
    # (a0 + a1 u)^2 = (a0+a1)(a0-a1) + 2 a0 a1 u
    return ((a0 + a1) * (a0 - a1) % P, 2 * a0 * a1 % P)


def fp2_mul_fp(a, s):
    return (a[0] * s % P, a[1] * s % P)


def fp2_mul_xi(a):
    """Multiply by xi = 1 + u:  (c0 - c1) + (c0 + c1) u."""
    a0, a1 = a
    return ((a0 - a1) % P, (a0 + a1) % P)


def fp2_inv(a):
    a0, a1 = a
    norm = (a0 * a0 + a1 * a1) % P
    ninv = fp_inv(norm)
    return (a0 * ninv % P, (P - a1) * ninv % P if a1 else 0)


def fp2_pow(a, e):
    result = FP2_ONE
    base = a
    while e > 0:
        if e & 1:
            result = fp2_mul(result, base)
        base = fp2_sqr(base)
        e >>= 1
    return result


def fp2_norm(a):
    """Norm map Fp2 -> Fp: a0^2 + a1^2."""
    return (a[0] * a[0] + a[1] * a[1]) % P


def fp2_is_square(a):
    """x in Fp2 is a square iff Norm(x) is a square in Fp."""
    return fp_is_square(fp2_norm(a))


def fp2_sqrt(a):
    """Square root in Fp2 via the complex method (p = 3 mod 4).

    Returns None when `a` is not a square.
    """
    if a == FP2_ZERO:
        return FP2_ZERO
    a0, a1 = a
    if a1 == 0:
        s = fp_sqrt(a0)
        if s is not None:
            return (s, 0)
        # a0 is a non-square in Fp, so sqrt is purely imaginary:
        # (t*u)^2 = -t^2 = a0  =>  t = sqrt(-a0)
        t = fp_sqrt(fp_neg(a0))
        if t is None:
            return None
        return (0, t)
    # alpha = norm(a) must be square in Fp
    alpha = fp_sqrt(fp2_norm(a))
    if alpha is None:
        return None
    # delta = (a0 + alpha)/2; if not square, use (a0 - alpha)/2
    inv2 = (P + 1) // 2
    delta = (a0 + alpha) * inv2 % P
    x0 = fp_sqrt(delta)
    if x0 is None:
        delta = (a0 - alpha) * inv2 % P
        x0 = fp_sqrt(delta)
        if x0 is None:
            return None
    x1 = a1 * inv2 % P * fp_inv(x0) % P
    cand = (x0, x1)
    return cand if fp2_sqr(cand) == a else None


def fp2_sgn0(a):
    """RFC 9380 sgn0 for m=2."""
    s0 = a[0] & 1
    z0 = a[0] == 0
    s1 = a[1] & 1
    return s0 | (z0 & s1)


# ---------------------------------------------------------------------------
# Fp6 = Fp2[v] / (v^3 - xi)
# ---------------------------------------------------------------------------

FP6_ZERO = (FP2_ZERO, FP2_ZERO, FP2_ZERO)
FP6_ONE = (FP2_ONE, FP2_ZERO, FP2_ZERO)


def fp6_add(a, b):
    return (fp2_add(a[0], b[0]), fp2_add(a[1], b[1]), fp2_add(a[2], b[2]))


def fp6_sub(a, b):
    return (fp2_sub(a[0], b[0]), fp2_sub(a[1], b[1]), fp2_sub(a[2], b[2]))


def fp6_neg(a):
    return (fp2_neg(a[0]), fp2_neg(a[1]), fp2_neg(a[2]))


def fp6_mul(a, b):
    a0, a1, a2 = a
    b0, b1, b2 = b
    t0 = fp2_mul(a0, b0)
    t1 = fp2_mul(a1, b1)
    t2 = fp2_mul(a2, b2)
    c0 = fp2_add(t0, fp2_mul_xi(fp2_sub(fp2_mul(fp2_add(a1, a2), fp2_add(b1, b2)), fp2_add(t1, t2))))
    c1 = fp2_add(fp2_sub(fp2_mul(fp2_add(a0, a1), fp2_add(b0, b1)), fp2_add(t0, t1)), fp2_mul_xi(t2))
    c2 = fp2_add(fp2_sub(fp2_mul(fp2_add(a0, a2), fp2_add(b0, b2)), fp2_add(t0, t2)), t1)
    return (c0, c1, c2)


def fp6_sqr(a):
    return fp6_mul(a, a)


def fp6_mul_by_v(a):
    """Multiply by v: (a0, a1, a2) -> (xi*a2, a0, a1)."""
    return (fp2_mul_xi(a[2]), a[0], a[1])


def fp6_mul_fp2(a, s):
    return (fp2_mul(a[0], s), fp2_mul(a[1], s), fp2_mul(a[2], s))


def fp6_inv(a):
    a0, a1, a2 = a
    t0 = fp2_sqr(a0)
    t1 = fp2_sqr(a1)
    t2 = fp2_sqr(a2)
    t3 = fp2_mul(a0, a1)
    t4 = fp2_mul(a0, a2)
    t5 = fp2_mul(a1, a2)
    c0 = fp2_sub(t0, fp2_mul_xi(t5))
    c1 = fp2_sub(fp2_mul_xi(t2), t3)
    c2 = fp2_sub(t1, t4)
    # det = a0*c0 + xi*(a2*c1 + a1*c2)
    det = fp2_add(fp2_mul(a0, c0), fp2_mul_xi(fp2_add(fp2_mul(a2, c1), fp2_mul(a1, c2))))
    det_inv = fp2_inv(det)
    return (fp2_mul(c0, det_inv), fp2_mul(c1, det_inv), fp2_mul(c2, det_inv))


# ---------------------------------------------------------------------------
# Fp12 = Fp6[w] / (w^2 - v)
# ---------------------------------------------------------------------------

FP12_ZERO = (FP6_ZERO, FP6_ZERO)
FP12_ONE = (FP6_ONE, FP6_ZERO)


def fp12_add(a, b):
    return (fp6_add(a[0], b[0]), fp6_add(a[1], b[1]))


def fp12_sub(a, b):
    return (fp6_sub(a[0], b[0]), fp6_sub(a[1], b[1]))


def fp12_neg(a):
    return (fp6_neg(a[0]), fp6_neg(a[1]))


def fp12_conj(a):
    """Conjugate = Frobenius^6: a0 - a1*w."""
    return (a[0], fp6_neg(a[1]))


def fp12_mul(a, b):
    a0, a1 = a
    b0, b1 = b
    t0 = fp6_mul(a0, b0)
    t1 = fp6_mul(a1, b1)
    c0 = fp6_add(t0, fp6_mul_by_v(t1))
    c1 = fp6_sub(fp6_sub(fp6_mul(fp6_add(a0, a1), fp6_add(b0, b1)), t0), t1)
    return (c0, c1)


def fp12_sqr(a):
    a0, a1 = a
    t = fp6_mul(a0, a1)
    c0 = fp6_sub(fp6_sub(fp6_mul(fp6_add(a0, a1), fp6_add(a0, fp6_mul_by_v(a1))), t), fp6_mul_by_v(t))
    c1 = fp6_add(t, t)
    return (c0, c1)


def fp12_inv(a):
    a0, a1 = a
    # 1/(a0 + a1 w) = (a0 - a1 w) / (a0^2 - v a1^2)
    det = fp6_sub(fp6_sqr(a0), fp6_mul_by_v(fp6_sqr(a1)))
    det_inv = fp6_inv(det)
    return (fp6_mul(a0, det_inv), fp6_neg(fp6_mul(a1, det_inv)))


def fp12_pow(a, e):
    if e < 0:
        return fp12_pow(fp12_conj(a), -e)  # valid only for unitary elements
    result = FP12_ONE
    base = a
    while e > 0:
        if e & 1:
            result = fp12_mul(result, base)
        base = fp12_sqr(base)
        e >>= 1
    return result


# ---------------------------------------------------------------------------
# Frobenius maps (coefficients computed at import, not hard-coded)
# ---------------------------------------------------------------------------

def _compute_frob_coeffs():
    """gamma_i = xi^(i*(p-1)/6) for i = 1..5, as Fp2 elements."""
    e = (P - 1) // 6
    g1 = fp2_pow(XI, e)
    gs = [FP2_ONE, g1]
    for _ in range(4):
        gs.append(fp2_mul(gs[-1], g1))
    return gs  # gs[i] = xi^(i(p-1)/6)


_FROB_GAMMA = _compute_frob_coeffs()


def fp2_frob(a):
    """a^p in Fp2 = conjugate (since p = 3 mod 4)."""
    return fp2_conj(a)


def fp6_frob(a):
    """(a0 + a1 v + a2 v^2)^p = a0^p + a1^p gamma2 v + a2^p gamma4 v^2."""
    return (
        fp2_conj(a[0]),
        fp2_mul(fp2_conj(a[1]), _FROB_GAMMA[2]),
        fp2_mul(fp2_conj(a[2]), _FROB_GAMMA[4]),
    )


def fp12_frob(a):
    """(b0 + b1 w)^p = b0^p + (b1^p * gamma1-spread) w."""
    a0, a1 = a
    b0 = fp6_frob(a0)
    b1 = fp6_frob(a1)
    # w^p = w * w^(p-1) = w * xi^((p-1)/6)
    b1 = fp6_mul_fp2(b1, _FROB_GAMMA[1])
    return (b0, b1)


def fp12_frob_n(a, n):
    for _ in range(n):
        a = fp12_frob(a)
    return a

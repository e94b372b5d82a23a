"""BLS and Schnorr signature schemes on BLS12-381 (host/golden path).

Counterparts of the reference's `key.AuthScheme` (BLS on G2,
`key/curve.go:39`) and `key.DKGAuthScheme` (Schnorr, `key/curve.go:43`).
Keys are G1 points (48 B compressed), BLS signatures are G2 points (96 B
compressed), matching drand's wire sizes.

The TPU path (drand_tpu.ops.bls via drand_tpu.verify) provides the batched
verify; this module is the single-item host implementation and its oracle.
"""

from __future__ import annotations

import hashlib
import secrets

from .bls12381 import curve as C
from .bls12381 import h2c
from .bls12381 import pairing as PR
from .bls12381.constants import R

# ---------------------------------------------------------------------------
# Keys
# ---------------------------------------------------------------------------

def keygen(seed: bytes | None = None) -> tuple[int, tuple]:
    """Generate (secret scalar, G1 public key).  Deterministic if seed given."""
    if seed is None:
        sk = secrets.randbelow(R - 1) + 1
    else:
        sk = int.from_bytes(hashlib.sha512(b"drand-tpu-keygen" + seed).digest(), "big") % R
        sk = sk or 1
    return sk, C.g1_mul(C.G1_GEN, sk)


def public_key(sk: int) -> tuple:
    return C.g1_mul(C.G1_GEN, sk)


# ---------------------------------------------------------------------------
# Plain BLS (sign on G2, verify with 2 pairings)
# ---------------------------------------------------------------------------

def bls_sign(sk: int, msg: bytes) -> bytes:
    """sigma = sk * H2(msg); returns 96-byte compressed G2 signature."""
    h = h2c.hash_to_g2(msg)
    return C.g2_to_bytes(C.g2_mul(h, sk))


def bls_verify(pub, msg: bytes, sig: bytes) -> bool:
    """Check e(g1, sigma) == e(pub, H2(msg)), i.e.
    e(-g1, sigma) * e(pub, H2(msg)) == 1.  pub is a G1 Jacobian point."""
    try:
        sigma = C.g2_from_bytes(sig)
    except ValueError:
        return False
    if not C.g2_in_subgroup(sigma):
        return False
    h = h2c.hash_to_g2(msg)
    return PR.pairing_check([(C.g1_neg(C.G1_GEN), sigma), (pub, h)])


# --- G1-signature variant (short sigs, pk on G2): scheme
# bls-unchained-g1-rfc9380 in later upstream drand (BASELINE.md config 4). ---

def keygen_g2(seed: bytes | None = None) -> tuple[int, tuple]:
    if seed is None:
        sk = secrets.randbelow(R - 1) + 1
    else:
        sk = int.from_bytes(hashlib.sha512(b"drand-tpu-keygen-g2" + seed).digest(), "big") % R
        sk = sk or 1
    return sk, C.g2_mul(C.G2_GEN, sk)


def bls_sign_g1(sk: int, msg: bytes) -> bytes:
    """sigma = sk * H1(msg); returns 48-byte compressed G1 signature."""
    h = h2c.hash_to_g1(msg)
    return C.g1_to_bytes(C.g1_mul(h, sk))


def bls_verify_g1(pub_g2, msg: bytes, sig: bytes) -> bool:
    """Check e(sigma, g2) == e(H1(msg), pub):  pub is a G2 Jacobian point."""
    try:
        sigma = C.g1_from_bytes(sig)
    except ValueError:
        return False
    if not C.g1_in_subgroup(sigma):
        return False
    h = h2c.hash_to_g1(msg)
    return PR.pairing_check([(C.g1_neg(sigma), C.G2_GEN), (h, pub_g2)])


# ---------------------------------------------------------------------------
# Schnorr (DKG packet authentication)
# ---------------------------------------------------------------------------

def _schnorr_challenge(r_bytes: bytes, pub_bytes: bytes, msg: bytes) -> int:
    return int.from_bytes(hashlib.sha512(b"drand-tpu-schnorr" + r_bytes + pub_bytes + msg).digest(), "big") % R


def schnorr_sign(sk: int, msg: bytes) -> bytes:
    """sig = R_compressed(48B) || s(32B big-endian); s = k + sk*h mod r."""
    k = secrets.randbelow(R - 1) + 1
    r_pt = C.g1_mul(C.G1_GEN, k)
    r_bytes = C.g1_to_bytes(r_pt)
    pub_bytes = C.g1_to_bytes(C.g1_mul(C.G1_GEN, sk))
    h = _schnorr_challenge(r_bytes, pub_bytes, msg)
    s = (k + sk * h) % R
    return r_bytes + s.to_bytes(32, "big")


def schnorr_verify(pub, msg: bytes, sig: bytes) -> bool:
    """Check s*G == R + h*pub."""
    if len(sig) != 80:
        return False
    try:
        r_pt = C.g1_from_bytes(sig[:48])
    except ValueError:
        return False
    s = int.from_bytes(sig[48:], "big")
    if s >= R:
        return False
    h = _schnorr_challenge(sig[:48], C.g1_to_bytes(pub), msg)
    lhs = C.g1_mul(C.G1_GEN, s)
    rhs = C.g1_add(r_pt, C.g1_mul(pub, h))
    return C.g1_eq(lhs, rhs)

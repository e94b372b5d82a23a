"""Batched big-integer modular arithmetic for TPU (JAX, int32 limbs).

This is the device-side counterpart of the reference's crypto dependency
chain (`key/curve.go:24` -> kilic/bls12-381 field arithmetic in x86-64
assembly): a Montgomery-form field engine designed for the TPU's 32-bit
integer vector lanes instead of 64-bit scalar registers.

Representation
--------------
A field element is `[..., 32]` int32: 32 limbs x 12 bits, little-endian
(limb 0 least significant), value = sum(limb[i] << (12*i)).  Canonical
elements have every limb in [0, 4096) and value in [0, modulus).  All
arithmetic is batched over the leading axes and is branchless, so it maps
onto `vmap`/`pjit` and compiles to static XLA graphs.

Why 12-bit limbs: schoolbook column sums accumulate at most 63 products of
two 12-bit limbs (63 * 4095^2 < 2^31), so every intermediate fits int32 —
the widest integer multiply-add the TPU VPU supports natively.

Montgomery domain: R = 2^384.  mont_mul(aR, bR) = abR mod m via SOS
(separated operand scanning) reduction; the m*modulus and lo*(-m^-1)
products multiply by *constants* and are expressed as Toeplitz
multiply-sums, which XLA can fuse aggressively (and which are the seam for
the Pallas/MXU fast path).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

LIMB_BITS = 12
N_LIMBS = 32
LIMB_MASK = (1 << LIMB_BITS) - 1
TOTAL_BITS = LIMB_BITS * N_LIMBS  # 384; R = 2^384

_ONE_VEC = np.zeros(N_LIMBS, np.int32)
_ONE_VEC[0] = 1


# ---------------------------------------------------------------------------
# Host-side limb packing
# ---------------------------------------------------------------------------

def int_to_limbs(x: int, n: int = N_LIMBS) -> np.ndarray:
    """Python int -> [n] int32 limb array (little-endian, 12-bit limbs)."""
    assert 0 <= x < (1 << (LIMB_BITS * n)), "value out of limb range"
    return np.array([(x >> (LIMB_BITS * i)) & LIMB_MASK for i in range(n)],
                    dtype=np.int32)


def limbs_to_int(limbs) -> int:
    out = 0
    for i, l in enumerate(np.asarray(limbs).tolist()):
        out += int(l) << (LIMB_BITS * i)
    return out


def ints_to_limbs(xs) -> np.ndarray:
    """List of python ints -> [len, 32] int32."""
    return np.stack([int_to_limbs(x) for x in xs])


def tail_segments(bits: str):
    """MSB-first bit string -> [(zero_run_len, has_set_bit)] segments.

    Shared by every static double-and-add ladder (Miller loop, final-exp
    x-chains, constant scalar multiplication): sparse constants like the
    BLS parameter |x| (5 set tail bits of 63) make a masked per-bit scan
    execute its full add/multiply path mostly as waste; `segmented_ladder`
    runs the add step on the set bits only, in either tracing mode."""
    segs, i, n = [], 0, len(bits)
    while i < n:
        j = i
        while j < n and bits[j] == "0":
            j += 1
        segs.append((j - i, j < n))
        i = j + 1
    return segs


def compact_graphs() -> bool:
    """Compile-lean mode: every ladder traces as ONE per-bit scan instead
    of the static segment unroll.  The graph shrinks ~10x (the full verify
    drops from ~550k to tens of thousands of HLO ops, and from 655 Pallas
    call sites to 152).  The scan's body doubles on every bit and adds
    under a `lax.cond` on the bit, so the work executed is the static
    mode's; what the static mode still has over it are the levers shut by
    `not compact_graphs()` (merged Miller kernels, addition chains).

    Read at TRACE time: the innermost `compact_scope()` decides, and
    outside any scope `DRAND_TPU_COMPACT=1` does."""
    scoped = _COMPACT.get()
    if scoped is not None:
        return scoped
    return bool(os.environ.get("DRAND_TPU_COMPACT"))


def miller_merged() -> bool:
    """Merged Miller-iteration kernel path (DRAND_TPU_MILLER_MERGED,
    default on): the Pallas executor fuses flat_sqr + the stacked
    doubling step + both line multiplies into one launch per iteration
    (pairing._miller_loop_pairs_merged).  Pallas-only — the XLA:CPU
    tier never reads it.  Read at TRACE time; like compact_graphs it is
    part of the AOT cache key (aot.cache_path), so A/B executables for
    warm_r9 never collide."""
    return os.environ.get("DRAND_TPU_MILLER_MERGED", "1") != "0"


def line_merge_enabled() -> bool:
    """Sparse-sparse line merge inside the merged Miller kernel
    (DRAND_TPU_LINE_MERGE, default on): multiply the two sparse lines
    into one denser element before touching f — one full-f multiply per
    iteration instead of two, at +36 sparse convs.  Trace-time flag,
    AOT-keyed; warm_r9 A/Bs it against the sequential multiplies."""
    return os.environ.get("DRAND_TPU_LINE_MERGE", "1") != "0"


def miller_path_tag() -> str:
    """Cache-key material for the Miller kernel-path flags (consumed by
    drand_tpu.aot.cache_path alongside the compact flag)."""
    return f"miller{int(miller_merged())}{int(line_merge_enabled())}"


import contextlib  # noqa: E402  (kept beside their sole user)
import contextvars  # noqa: E402

_COMPACT = contextvars.ContextVar("drand_tpu_compact", default=None)


@contextlib.contextmanager
def compact_scope(compact: bool = True):
    """Trace the enclosed graph(s) in compact mode (or, with False, in
    the static-unroll mode), then restore.

    The mode is an argument of whoever traces, carried by a context
    variable: it is local to the tracing thread, so concurrent traces in
    different modes cannot leak into each other.  Callers:
    `Verifier._run_fn(compact=...)`, the driver entry points
    (__graft_entry__) and tests.  The serialized-executable cache keys
    entries by this mode (aot.cache_path)."""
    token = _COMPACT.set(compact)
    try:
        yield
    finally:
        _COMPACT.reset(token)


def _repunit_plan(lengths, seeds):
    """Build plan for repunit powers r_l = a^(2^l - 1): recursive-halving
    steps (new, src, shift) meaning r_new = r_src^(2^shift) * r_shift.
    `seeds` are lengths available for free (r_1 = a; with an odd-power
    window table, r_2..r_5 are table entries a^3/a^7/a^15/a^31)."""
    have = set(seeds)
    steps = []

    def build_to(l):
        if l in have:
            return
        lo, hi = l // 2, l - l // 2
        build_to(hi)
        build_to(lo)
        steps.append((l, hi, lo))
        have.add(l)

    for l in sorted(lengths):
        build_to(l)
    return steps


@functools.lru_cache(maxsize=None)
def addchain_plan(e: int, w: int = 5, run_min: int = 99):
    """Compile a static exponent into an addition chain: sliding w-bit
    windows over an odd-power table (skipped zeros cost only squarings,
    and windows shrink to odd values — a Brauer chain), with maximal
    1-runs of length >= run_min lifted to repunit powers.  For the
    BLS12-381 sqrt/inv/QR exponents this measures 457-460 Montgomery ops
    vs 485-490 for the uniform 4-bit fixed window (~6% fewer; STATUS.md
    headroom 1c) — the planner is exact, so `pow_const` picks whichever
    costs less per exponent.

    Returns (ops, build, n_sqr, n_mul, used_odd):
      ops   — ("init_rep", l) / ("init_odd", v) / ("sqrmul_rep", k, l) /
              ("sqrmul_odd", k, v) / ("sqr", k), executed in order
              (sqrmul = k squarings then multiply by r_l / odd-table v);
      build — repunit steps (new, src, shift) executed first.
    The plan is validated by integer reconstruction before returning.
    """
    assert e >= 1 and w >= 2
    bits = bin(e)[2:]
    n = len(bits)
    ops = []
    i = 0
    pend = 0
    first = True
    used_odd = False
    rep_lens = set()
    while i < n:
        if bits[i] == "0":
            pend += 1
            i += 1
            continue
        j = i
        while j < n and bits[j] == "1":
            j += 1
        run = j - i
        if run >= run_min:
            rep_lens.add(run)
            if first:
                ops.append(("init_rep", run))
                first = False
            else:
                ops.append(("sqrmul_rep", pend + run, run))
            pend = 0
            i = j
        else:
            j2 = min(i + w, n)
            while bits[j2 - 1] == "0":
                j2 -= 1
            v = int(bits[i:j2], 2)
            used_odd = True
            if first:
                ops.append(("init_odd", v))
                first = False
            else:
                ops.append(("sqrmul_odd", pend + (j2 - i), v))
            pend = 0
            i = j2
    if pend:
        ops.append(("sqr", pend))
    seeds = set(range(1, w + 1)) if used_odd else {1}
    build = _repunit_plan(rep_lens, seeds)

    # validate structurally: replay the plan on integers
    reps = {l: (1 << l) - 1 for l in seeds}
    for new, src, shift in build:
        reps[new] = (reps[src] << shift) + reps[shift]
        assert reps[new] == (1 << new) - 1
    acc = 0
    for op in ops:
        if op[0] == "init_rep":
            acc = reps[op[1]]
        elif op[0] == "init_odd":
            acc = op[1]
        elif op[0] == "sqrmul_rep":
            acc = (acc << op[1]) + reps[op[2]]
        elif op[0] == "sqrmul_odd":
            acc = (acc << op[1]) + op[2]
        else:
            acc <<= op[1]
    assert acc == e, "addchain plan does not reproduce the exponent"

    n_sqr = sum(op[1] for op in ops if op[0] in
                ("sqrmul_rep", "sqrmul_odd", "sqr"))
    n_sqr += sum(shift for _, _, shift in build)
    n_mul = sum(1 for op in ops if op[0].startswith("sqrmul"))
    n_mul += len(build)
    if used_odd:
        n_sqr += 1                       # a^2 feeding the odd table
        n_mul += (1 << (w - 1)) - 1      # a^3, a^5, ..., a^(2^w - 1)
    return tuple(ops), tuple(build), n_sqr, n_mul, used_odd


def segmented_ladder(segments, state, dbl_fn, add_fn):
    """Shared driver for static double-and-add ladders over
    `tail_segments` output: scans each zero run with the double-only body
    and unrolls each set-bit step (double + add); in compact mode, one
    scan over all bits whose body doubles and then adds under a scalar
    `lax.cond`.  Either way `add_fn` runs on the set bits only.  `state`
    is any pytree; `dbl_fn(state) -> state`, `add_fn(state) -> state`
    (same structure and dtypes: it is a `cond` branch)."""
    if compact_graphs():
        bits = []
        for run, has_one in segments:
            bits.extend([0] * run)
            if has_one:
                bits.append(1)

        def body(st, bit):
            # `bit` is a scalar of the scanned constant, not a per-row
            # mask: the conditional stays a real branch inside the
            # `while`, so the add step runs on the set bits only
            return jax.lax.cond(bit != 0, add_fn, lambda s: s,
                                dbl_fn(st)), None

        state, _ = jax.lax.scan(body, state,
                                jnp.asarray(bits, dtype=jnp.int32))
        return state

    def dbl_body(st, _):
        return dbl_fn(st), None

    for run, has_one in segments:
        if run:
            state, _ = jax.lax.scan(dbl_body, state, None, length=run)
        if has_one:
            state = add_fn(dbl_fn(state))
    return state


# ---------------------------------------------------------------------------
# Limb kernels (modulus-independent)
# ---------------------------------------------------------------------------

def _shift_up(c: jnp.ndarray) -> jnp.ndarray:
    return jnp.concatenate([jnp.zeros_like(c[..., :1]), c[..., :-1]], axis=-1)


def _carry_cheap(z: jnp.ndarray, passes: int = 3) -> jnp.ndarray:
    """Value-preserving partial carry: after 3 passes every limb is <= 4097
    (column sums < 2^31 in), but long +1 ripple chains may remain un-flushed.
    Only valid where the consumer tolerates limbs slightly above 2^12 - 1
    (all products keep column sums < 2^31 with 4097-bounded limbs)."""
    for _ in range(passes):
        c = z >> LIMB_BITS
        z = (z & LIMB_MASK) + _shift_up(c)
    return z


def _carry(z: jnp.ndarray, passes: int = 3) -> jnp.ndarray:
    """EXACT carry normalization of non-negative limb sums into [0, 2^12)
    (mod 2^(12*width): the carry out of the top limb is dropped).

    Branchless log-depth normalization instead of a 32-step `lax.scan`
    ripple: a sequential scan compiles to a device loop whose per-step
    bookkeeping dwarfs the 1-limb payload, and it serializes what is
    otherwise pure vector code.  Three value-preserving cheap passes bound
    every limb by 4096 with pending carries in {0, 1} (the invariant the
    lookahead needs); the remaining +1 ripple chains (e.g. `x - x`, or the
    designed-zero low half of a Montgomery reduction) are resolved by
    Kogge-Stone carry-lookahead on (generate, propagate) bits —
    ceil(log2(width)) rounds of shift/AND/OR on full-width vectors, which
    XLA fuses into straight-line VPU code.
    (`passes` kept for signature compatibility; unused.)
    """
    del passes
    return _carry_overflow(z)[0]


def _carry_overflow(z: jnp.ndarray, cheap_passes: int = 3):
    """Exact carry normalization plus the dropped carry OUT of the top
    limb as a bool[...] — i.e. whether the true sum reached 2^(12*width).

    The overflow bit turns `a >= c` into "did a + (2^width - c) carry
    out", which the conditional-subtract paths use instead of a separate
    lexicographic compare.

    cheap_passes must leave every limb <= 4096 (pending carries in
    {0, 1}) — the invariant the Kogge-Stone lookahead needs.  The default
    3 covers any 2^31-bounded column sums (pass1 <= 4095 + 2^19,
    pass2 <= 4095 + 128, pass3 <= 4095 + 1).  Callers summing at most
    THREE 12-bit-limb operands (add/sub/cond-sub: limbs <= 3*4095, pass1
    carries <= 2 -> limbs <= 4097, pass2 -> <= 4096) may pass 2."""
    width = z.shape[-1]
    ov = jnp.zeros(z.shape[:-1], bool)
    for _ in range(cheap_passes):
        c = z >> LIMB_BITS
        ov = ov | (c[..., -1] > 0)
        z = (z & LIMB_MASK) + _shift_up(c)
    g = (z >> LIMB_BITS) > 0                      # generate: limb == 4096
    p = (z == LIMB_MASK)                          # propagate: limb == 4095

    def up(x, k):
        pad = jnp.zeros_like(x[..., :k])
        return jnp.concatenate([pad, x[..., :-k]], axis=-1)

    # Kogge-Stone: G_i = "carry out of limb i, given limbs <= i"
    step = 1
    while step < width:
        g = g | (p & up(g, step))
        p = p & up(p, step)
        step *= 2
    ov = ov | g[..., -1]
    return (z + up(g, 1).astype(jnp.int32)) & LIMB_MASK, ov


def _poly_mul_var(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook column sums of two [..., 32] limb vectors -> [..., 63].

    z[k] = sum_{i+j=k} a[i]*b[j]; columns are NOT carried yet (each fits
    int32 by the 12-bit limb bound).
    """
    k = jnp.arange(2 * N_LIMBS - 1)
    i = jnp.arange(N_LIMBS)
    idx = k[None, :] - i[:, None]                      # [32, 63]
    valid = (idx >= 0) & (idx < N_LIMBS)
    bg = jnp.where(valid, jnp.take(b, jnp.clip(idx, 0, N_LIMBS - 1), axis=-1), 0)
    return jnp.sum(a[..., :, None] * bg, axis=-2)


def _toeplitz_full(const_limbs: np.ndarray) -> np.ndarray:
    """[32, 63] matrix T with T[i, k] = const[k-i] (0 outside) so that
    (x[:, None] * T).sum(-2) == poly_mul(x, const)."""
    t = np.zeros((N_LIMBS, 2 * N_LIMBS - 1), dtype=np.int32)
    for i in range(N_LIMBS):
        t[i, i:i + N_LIMBS] = const_limbs
    return t


def _toeplitz_low(const_limbs: np.ndarray) -> np.ndarray:
    """[32, 32] lower-triangular Toeplitz: product truncated mod 2^384."""
    return _toeplitz_full(const_limbs)[:, :N_LIMBS]


def _mul_const(x: jnp.ndarray, toep: jnp.ndarray) -> jnp.ndarray:
    """Column sums of x (limbs) times a constant via its Toeplitz matrix."""
    return jnp.sum(x[..., :, None] * toep, axis=-2)


# ---------------------------------------------------------------------------
# Field context
# ---------------------------------------------------------------------------

class Field:
    """Montgomery-form modular arithmetic for one odd modulus < 2^381.

    Instantiated once per field (BLS12-381 base field Fp and scalar field
    Fr); all methods are jit-traceable and batched.
    """

    def __init__(self, modulus: int, name: str = "field"):
        assert modulus % 2 == 1 and modulus.bit_length() <= 381
        self.modulus = modulus
        self.name = name
        R = 1 << TOTAL_BITS
        self.R2_int = R * R % modulus
        self.R_int = R % modulus
        pprime = (-pow(modulus, -1, R)) % R

        self.MOD = int_to_limbs(modulus)
        self.MODP1 = int_to_limbs(modulus + 1)
        # 2^384 - k*modulus for the conditional-subtract trick
        self.NEG_MOD = {k: int_to_limbs(R - k * modulus)
                        for k in (1, 2, 4, 8) if k * modulus < R}
        self.K_MOD = {k: int_to_limbs(k * modulus)
                      for k in (1, 2, 4, 8) if k * modulus < R}
        self.PPRIME_TOEP = _toeplitz_low(int_to_limbs(pprime))
        self.MOD_TOEP = _toeplitz_full(self.MOD)

        self.zero = np.zeros(N_LIMBS, np.int32)
        self.one_mont = int_to_limbs(self.R_int)          # 1 in Montgomery form
        self.R2 = int_to_limbs(self.R2_int)
        self.R3 = int_to_limbs(R * R * R % modulus)
        self.Rinv_int = pow(R, -1, modulus)               # host decode constant

    # -- host conversions ---------------------------------------------------

    def to_mont_host(self, x: int) -> np.ndarray:
        return int_to_limbs(x * (1 << TOTAL_BITS) % self.modulus)

    def from_limbs_host(self, limbs, mont: bool = True) -> int:
        v = limbs_to_int(limbs)
        if mont:
            v = v * self.Rinv_int % self.modulus
        return v % self.modulus

    def encode(self, xs) -> np.ndarray:
        """List of ints -> [len, 32] Montgomery-form limbs."""
        return np.stack([self.to_mont_host(x % self.modulus) for x in xs])

    # -- comparisons --------------------------------------------------------

    def _lex_ge(self, a: jnp.ndarray, const: np.ndarray) -> jnp.ndarray:
        """a >= const for canonical limb vectors; returns bool[...]."""
        c = jnp.asarray(const)
        eq = (a == c)
        gt = (a > c)
        # MSB-first prefix of equality
        eqr = eq[..., ::-1]
        cp = jnp.cumprod(eqr.astype(jnp.int32), axis=-1).astype(bool)
        higher_eq = jnp.concatenate(
            [jnp.ones_like(cp[..., :1]), cp[..., :-1]], axis=-1)
        gtr = gt[..., ::-1]
        return jnp.any(gtr & higher_eq, axis=-1) | cp[..., -1]

    def eq(self, a, b):
        return jnp.all(a == b, axis=-1)

    def is_zero(self, a):
        return jnp.all(a == 0, axis=-1)

    # -- core ops -----------------------------------------------------------

    def add(self, a, b):
        """(a + b) mod m: the sum and its m-subtracted twin share ONE
        stacked carry chain; the twin's carry-out IS the a+b >= m test."""
        raw = a + b
        st = jnp.stack(jnp.broadcast_arrays(
            raw, raw + jnp.asarray(self.NEG_MOD[1])), 0)
        c, ov = _carry_overflow(st, 2)
        return jnp.where(ov[1][..., None], c[1], c[0])

    def _cond_sub_full(self, s):
        """Reduce canonical s < 2*modulus into [0, modulus).

        s >= m exactly when s + (2^384 - m) carries out of the top limb,
        so the subtraction's own carry chain doubles as the comparison —
        no separate lexicographic compare."""
        d, ge = _carry_overflow(s + jnp.asarray(self.NEG_MOD[1]), 2)
        return jnp.where(ge[..., None], d, s)

    def neg(self, b):
        """(-b) mod m for canonical b."""
        comp = (LIMB_MASK - b)
        s = _carry(jnp.asarray(self.MODP1) + comp, 4) & LIMB_MASK
        return jnp.where(self.is_zero(b)[..., None], jnp.zeros_like(b), s)

    def sub(self, a, b):
        """(a - b) mod m via the limb complement, one stacked carry chain:
        lane 0 carries a + (m+1) + ~b = (a - b + m) + 2^384 (canonical
        when a < b), lane 1 carries a + 1 + ~b = (a - b) + 2^384, whose
        carry-out is exactly the a >= b test picking the un-shifted
        difference.  No separate negation pass or compare, and b == 0
        needs no special case."""
        comp = a + (LIMB_MASK - b)
        st = jnp.stack(jnp.broadcast_arrays(
            comp + jnp.asarray(self.MODP1), comp + _ONE_VEC), 0)
        c, ov = _carry_overflow(st, 2)
        return jnp.where(ov[1][..., None], c[1], c[0])

    def mul_small(self, a, c: int):
        """a * c for a static tiny scalar 1 <= c <= 8."""
        assert 1 <= c <= 8
        s = _carry(a * c, 3)
        for k in (4, 2, 1):
            if k < c and k in self.K_MOD:
                s = self._cond_sub_k(s, k)
        return s

    def _cond_sub_k(self, s, k):
        d, ge = _carry_overflow(s + jnp.asarray(self.NEG_MOD[k]), 2)
        return jnp.where(ge[..., None], d, s)

    def mont_mul(self, a, b):
        """Montgomery product: (a * b * 2^-384) mod m, canonical in/out.

        Intermediates t and m use the cheap 3-pass carry (limbs bounded by
        4097, which keeps the next column sums < 2^31); only the final u
        needs the exact carry, because its low 384 bits are identically zero
        and residual +1 ripples there would corrupt the high half.  A
        slightly-overflowed m (value in [2^384, 2^384*(1+eps))) only shifts
        the result by one extra modulus, absorbed by the double cond-sub.
        """
        pf = self._pallas()
        if pf is not None:
            return pf.mont_mul(a, b)
        t = _carry_cheap(jnp.pad(_poly_mul_var(a, b), [(0, 0)] * (a.ndim - 1) + [(0, 1)]))
        return self.mont_reduce(t)

    def _pallas(self):
        """The fused TPU kernel backend, when running on a TPU (tests on
        the CPU backend keep the pure-XLA path)."""
        from drand_tpu.ops.pallas_field import pallas_field, use_pallas
        if not use_pallas():
            return None
        return pallas_field(self.modulus)

    def mont_reduce(self, t):
        """Montgomery-reduce a [..., 64] wide limb value: t * 2^-384 mod m.

        t limbs must be cheap-carried (each < 2^13-ish so the m*modulus
        column sums stay < 2^31); t's VALUE may be up to ~1.5*R*modulus
        (e.g. a sum of up to 12 canonical products), giving u < 2.5m which
        the double cond-sub still reduces to canonical."""
        pf = self._pallas()
        if pf is not None:
            return pf.mont_reduce(t)
        m = _carry_cheap(_mul_const(t[..., :N_LIMBS], jnp.asarray(self.PPRIME_TOEP)))
        u_cols = _mul_const(m, jnp.asarray(self.MOD_TOEP))
        u = jnp.pad(u_cols, [(0, 0)] * (t.ndim - 1) + [(0, 1)]) + t
        u = _carry(u, 3)
        r = u[..., N_LIMBS:]
        return self._cond_sub_upto2(r)

    def reduce_small_multiple(self, r, bound: int):
        """Reduce r < bound*modulus (exact-carried canonical limbs, value
        < 2^384) into [0, modulus) via binary conditional subtracts."""
        assert bound <= 16
        for k in (8, 4, 2, 1):
            if k < bound:
                r = self._cond_sub_k(r, k)
        return r

    def _cond_sub_upto2(self, r):
        """Reduce canonical r < 3*modulus into [0, modulus): r and its
        m- and 2m-subtracted twins share one stacked carry chain; the
        twins' carry-outs are the r >= m / r >= 2m tests."""
        st = jnp.stack(jnp.broadcast_arrays(
            r, r + jnp.asarray(self.NEG_MOD[1]),
            r + jnp.asarray(self.NEG_MOD[2])), 0)
        c, ov = _carry_overflow(st, 2)
        return jnp.where(ov[2][..., None], c[2],
                         jnp.where(ov[1][..., None], c[1], c[0]))

    def sqr(self, a):
        pf = self._pallas()
        if pf is not None:
            return pf.mont_sqr(a)
        return self.mont_mul(a, a)

    def pow_const(self, a, e: int):
        """a^e (Montgomery in/out) for a static exponent.

        4-bit fixed-window square-and-multiply as a `lax.scan` over the
        base-16 digits: each step is 4 squarings plus ONE multiply by a
        table entry picked with `dynamic_index_in_dim` (digit 0 multiplies
        by 1, which is exact in Montgomery form) — ~35% fewer multiplies
        than bitwise square-and-always-multiply and no per-bit selects,
        with the scan keeping the XLA graph a single small body.  The
        precomputed table a^0..a^15 is 16 broadcast copies of the batch
        (bounded VMEM: tower callers pass [..., 32] stacks)."""
        one = jnp.broadcast_to(jnp.asarray(self.one_mont),
                               a.shape).astype(jnp.int32)
        if e == 0:
            return one
        if e < 16:
            # tiny exponents: plain unrolled chain
            res = a
            for bit in bin(e)[3:]:
                res = self.sqr(res)
                if bit == "1":
                    res = self.mont_mul(res, a)
            return res
        if e >= (1 << 64) and not compact_graphs() \
                and self._pallas() is not None:
            # Fixed big exponents (the Fermat sqrt/inv/QR chains, ~28% of
            # device time): an exact-cost addition chain beats the
            # uniform 4-bit window when the planner says so (457-460 vs
            # 485-490 mont ops for the BLS12-381 exponents — STATUS.md
            # headroom 1c).  Auto-selected on the Pallas path only: every
            # chain step is one fused kernel there, while on XLA:CPU the
            # ~70 inlined step graphs would multiply the test suite's
            # compile bill for a path no deployment runs hot (the XLA
            # executor stays test-reachable via _pow_addchain directly).
            # Compact mode keeps the single-body scan.
            ops, build, n_sqr, n_mul, used_odd = addchain_plan(e)
            nd = len(f"{e:x}")
            if n_sqr + n_mul < 5 * (nd - 1) + 15:
                return self._pow_addchain(a, ops, build, used_odd)
        digits = np.array([int(c, 16) for c in f"{e:x}"], dtype=np.int32)
        pf = self._pallas()
        if pf is not None and not compact_graphs():
            # TileForm path: the table and the scan carry stay in the
            # kernel tile layout; each window step is ONE fused kernel
            # (res^16 * t, lazy inner squarings) with zero per-call
            # relayout.
            from drand_tpu.ops.pallas_field import TileForm
            a_t = pf.tile(a)
            tab = [pf.tile(one), a_t]
            for _ in range(14):
                tab.append(pf.mont_mul(tab[-1], a_t))
            tab_tiles = jnp.stack([t.tiles for t in tab], 0)
            shp, b = a_t.shape, a_t.b

            def body_t(res, digit):
                tt = TileForm(jax.lax.dynamic_index_in_dim(
                    tab_tiles, digit, 0, keepdims=False), shp, b)
                return pf.sqr4_mul(res, tt), None

            res = TileForm(jax.lax.dynamic_index_in_dim(
                tab_tiles, int(digits[0]), 0, keepdims=False), shp, b)
            res, _ = jax.lax.scan(body_t, res, jnp.asarray(digits[1:]))
            return pf.untile(res)
        if compact_graphs():
            # table via scan: 1 small body instead of 14 inlined multiply
            # graphs (the chains are the biggest repeated blob in the
            # compile-lean trace)
            def tb(acc, _):
                nxt = self.mont_mul(acc, a)
                return nxt, nxt
            _, tail = jax.lax.scan(tb, a, None, length=14)
            tab = jnp.concatenate([one[None], a[None], tail], 0)
        else:
            tab = [one, a]
            for _ in range(14):
                tab.append(self.mont_mul(tab[-1], a))
            tab = jnp.stack(tab, 0)                    # [16, ..., 32]

        def body(res, digit):
            t = jax.lax.dynamic_index_in_dim(tab, digit, 0, keepdims=False)
            if pf is not None:
                # one fused kernel per window step (res^16 * t) instead of
                # 5 launches with HBM round-trips between them
                return pf.sqr4_mul(res, t), None
            for _ in range(4):
                res = self.sqr(res)
            return self.mont_mul(res, t), None

        # seed with the leading digit: skips 4 squarings of 1
        res = jax.lax.dynamic_index_in_dim(tab, int(digits[0]), 0,
                                           keepdims=False)
        res, _ = jax.lax.scan(body, res, jnp.asarray(digits[1:]))
        return res

    def _sqr_n(self, x, k: int):
        """x^(2^k): short runs unroll, long runs scan one sqr body."""
        if k <= 3:
            for _ in range(k):
                x = self.sqr(x)
            return x
        out, _ = jax.lax.scan(lambda c, _: (self.sqr(c), None), x, None,
                              length=k)
        return out

    def _pow_addchain(self, a, ops, build, used_odd: bool):
        """Execute an `addchain_plan`.  On the Pallas path every
        sqrmul step is ONE fused kernel (PallasField.sqr_chain_mul: k
        lazy in-VMEM squarings + the canonical multiply — the
        addition-chain generalization of the fixed sqr4_mul window
        step); the XLA path scans a sqr body per run.  Outputs are
        canonical either way, so results are bit-identical across
        paths and to the windowed form."""
        pf = self._pallas()
        fused = pf is not None and not compact_graphs()
        if fused:
            a = pf.tile(a)

        def sqr_n(x, k):
            if k == 0:
                return x
            return pf.sqr_chain_mul(x, k) if fused else self._sqr_n(x, k)

        def sqrmul(x, k, t):
            if fused:
                return pf.sqr_chain_mul(x, k, t)
            return self.mont_mul(self._sqr_n(x, k), t)

        seed_lens = set()
        for _, src, shift in build:
            seed_lens.update(x for x in (src, shift) if 2 <= x <= 5)
        for op in ops:
            if op[0] in ("init_rep", "sqrmul_rep") and 2 <= op[-1] <= 5:
                seed_lens.add(op[-1])
        tab = {}
        if used_odd:
            need = max([op[2] for op in ops if op[0] == "sqrmul_odd"] +
                       [op[1] for op in ops if op[0] == "init_odd"] +
                       [(1 << l) - 1 for l in seed_lens] + [1])
            tab[1] = a
            a2 = pf.sqr_chain_mul(a, 1) if fused else self.sqr(a)
            v = 3
            while v <= need:
                tab[v] = pf.mont_mul(tab[v - 2], a2) if fused \
                    else self.mont_mul(tab[v - 2], a2)
                v += 2
        reps = {1: a}
        if used_odd:
            # with the odd table, r_2..r_5 are table entries (seeds)
            for l in seed_lens:
                reps[l] = tab[(1 << l) - 1]
        for new, src, shift in build:
            reps[new] = sqrmul(reps[src], shift, reps[shift])
        res = None
        for op in ops:
            if op[0] == "init_rep":
                res = reps[op[1]]
            elif op[0] == "init_odd":
                res = tab[op[1]]
            elif op[0] == "sqrmul_rep":
                res = sqrmul(res, op[1], reps[op[2]])
            elif op[0] == "sqrmul_odd":
                res = sqrmul(res, op[1], tab[op[2]])
            else:
                res = sqr_n(res, op[1])
        return pf.untile(res) if fused else res

    def inv(self, a):
        """a^-1 via Fermat (a in Montgomery form; returns Montgomery form).

        inv of 0 returns 0 (the RFC 9380 inv0 convention)."""
        return self.pow_const(a, self.modulus - 2)

    # -- stacked ops: the TPU-first batching seam ---------------------------
    #
    # One mont_mul on a [k, ..., 32] stack costs the same number of XLA ops
    # as on a single element — the limb kernels are shape-polymorphic — so
    # tower/curve formulas are phrased as stages of INDEPENDENT products
    # (and sums) executed in one call.  This is what keeps both the XLA
    # graph small and the VPU lanes full.

    @staticmethod
    def _common(arrs):
        shapes = [a.shape for a in arrs]
        target = jnp.broadcast_shapes(*shapes)
        return [jnp.broadcast_to(a, target).astype(jnp.int32) for a in arrs]

    def _stack2(self, pairs):
        """Broadcast every operand of every pair to one common shape, then
        stack lhs/rhs along a fresh leading axis."""
        flat = self._common([p[0] for p in pairs] + [p[1] for p in pairs])
        n = len(pairs)
        return jnp.stack(flat[:n], 0), jnp.stack(flat[n:], 0)

    def products(self, pairs):
        """[(a, b), ...] -> [a*b mod m, ...] via ONE stacked mont_mul."""
        if len(pairs) == 1:
            return [self.mont_mul(pairs[0][0], pairs[0][1])]
        out = self.mont_mul(*self._stack2(pairs))
        return [out[i] for i in range(len(pairs))]

    def sums(self, pairs):
        """[(a, b), ...] -> [a+b mod m, ...] via ONE stacked add."""
        if len(pairs) == 1:
            return [self.add(pairs[0][0], pairs[0][1])]
        out = self.add(*self._stack2(pairs))
        return [out[i] for i in range(len(pairs))]

    def diffs(self, pairs):
        """[(a, b), ...] -> [a-b mod m, ...] via ONE stacked sub."""
        if len(pairs) == 1:
            return [self.sub(pairs[0][0], pairs[0][1])]
        out = self.sub(*self._stack2(pairs))
        return [out[i] for i in range(len(pairs))]

    def negs(self, arrs):
        if len(arrs) == 1:
            return [self.neg(arrs[0])]
        out = self.neg(jnp.stack(self._common(arrs), 0))
        return [out[i] for i in range(len(arrs))]

    # -- dynamic-scalar helpers --------------------------------------------

    def select(self, mask, a, b):
        """mask ? a : b with mask[...] broadcast over the limb axis."""
        return jnp.where(mask[..., None], a, b)

    # -- Montgomery domain conversions (device) -----------------------------

    def to_mont(self, x):
        return self.mont_mul(x, jnp.asarray(self.R2))

    def from_mont(self, x):
        one = jnp.zeros_like(x).at[..., 0].set(1)
        return self.mont_mul(x, one)

    def reduce_wide(self, lo, hi):
        """(hi * 2^384 + lo) mod m, both canonical limb vectors, output
        Montgomery form NOT applied: returns plain residue in [0, m).

        Used to reduce 512-bit hash_to_field draws: mont_mul(lo, R2) = lo*R
        ... careful: we want the plain value mod m.  plain = from_mont(
        to_mont(plain)).  Here: value = hi*R + lo (since R = 2^384), so
        mont(value) = value*R = hi*R^2 + lo*R = mont_mul(hi, R3) + mont_mul(lo, R2).
        """
        m_hi = self.mont_mul(hi, jnp.asarray(self.R3))
        m_lo = self.mont_mul(lo, jnp.asarray(self.R2))
        return self.add(m_hi, m_lo)  # Montgomery form of (hi*2^384 + lo)


# The two BLS12-381 fields.
from drand_tpu.crypto.bls12381.constants import P as _P, R as _R  # noqa: E402

FP = Field(_P, "fp")
FR = Field(_R, "fr")

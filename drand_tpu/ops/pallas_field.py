"""Fused Pallas TPU kernels for the Montgomery limb engine.

The pure-XLA engine (ops/field.py) materializes every intermediate —
the [B, 32, 63] product tensor, carry passes, reduction products — in HBM,
and pays per-HLO-op overhead thousands of times per pairing.  These
kernels keep one batch tile's entire multiply -> carry -> Montgomery
reduction -> conditional subtract pipeline in VMEM/registers: one kernel
launch per stacked multiply instead of ~40 HLO ops.

Layout: a batch tile of 1024 elements is shaped [32 limbs, 8, 128] — each
limb row is exactly one VREG (8 sublanes x 128 lanes), so every unrolled
multiply-add below is a single full-width VPU instruction.

These kernels require a TPU; on the CPU backend (tests) ops/field.py
traces the pure-XLA path instead, selected by `use_pallas()`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

N_LIMBS = 32
LIMB_BITS = 12
MASK = (1 << LIMB_BITS) - 1
TILE = 1024                      # batch elements per grid step
_ROW = (8, 128)                  # one VREG
_jit = jax.jit                   # PallasField._launch; the eager simulator
                                 # (tests/pallas_sim.py) swaps in identity


# -- layout-conversion accounting -------------------------------------------
#
# Crossing the [..., limbs] <-> [nt, limbs, 8, 128] boundary is the cost the
# tile-residency work exists to remove (88 ms/batch of moveaxis+reshape in
# the round-3 trace).  Conversions happen at TRACE time, so these counters
# count crossings per traced program: snapshot around a trace (bench.py does)
# to see how many relayouts a dispatch pays.  The ONLY sanctioned conversion
# sites are TileForm.wrap/unwrap — tools/lint rule `tile-seam` flags direct
# `_to_tiles_impl`/`_from_tiles_impl` calls anywhere else, so the residency
# invariant cannot silently regress.

_LAYOUT_COUNTS = {"to_tiles": 0, "from_tiles": 0}


def layout_conversion_counts() -> dict:
    """Snapshot of trace-time layout-boundary crossings since reset."""
    return dict(_LAYOUT_COUNTS)


def reset_layout_conversions() -> None:
    for k in _LAYOUT_COUNTS:
        _LAYOUT_COUNTS[k] = 0


def _count_crossing(kind: str) -> None:
    _LAYOUT_COUNTS[kind] += 1
    try:  # metric export is best-effort: ops/ must not require metrics
        from drand_tpu import metrics as M
        M.LAYOUT_CONVERSIONS.labels(kind=kind).inc()
    except Exception:
        pass


# A Montgomery reduction is ~1.5 convolutions of VPU work, so how many a
# kernel body runs says what its recombination costs: one a coordinate
# that leaves the wide domain.  Counted where the bodies are traced, as
# the crossings above are: read it around a trace.

_MONT_REDUCTIONS = {"coords": 0}


def mont_reductions_traced() -> int:
    """Fp coordinates Montgomery-reduced by the kernel bodies traced so
    far (a stacked reduce counts its stack)."""
    return _MONT_REDUCTIONS["coords"]


@jax.tree_util.register_pytree_node_class
class TileForm:
    """A batched limb tensor ALREADY in the kernel tile layout
    [nt, limbs, 8, 128] plus its logical batch shape.

    Every PallasField wrapper historically re-laid-out its operands on
    both sides of the kernel call (moveaxis+reshape, ~88 ms per 16k-batch
    verify — 7.6% of device time in the round-3 trace).  Hot loops (the
    Fermat/x-power chains, the point ladders, the whole Miller iteration)
    instead thread TileForm values through consecutive kernel calls: the
    wrappers accept and return TileForm without converting, so the layout
    boundary is crossed once at pipeline entry/exit instead of per call.
    TileForm is a registered pytree, so it carries through
    `lax.scan`/`cond` unchanged.

    `wrap`/`unwrap` are the ONLY sanctioned layout-conversion sites (the
    tile-seam lint rule enforces this); both count into
    `layout_conversion_counts()` so bench.py can report crossings per
    dispatch."""

    __slots__ = ("tiles", "shape", "b")

    def __init__(self, tiles, shape, b):
        self.tiles = tiles
        self.shape = tuple(shape)
        self.b = b

    def tree_flatten(self):
        return (self.tiles,), (self.shape, self.b)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux[0], aux[1])

    @property
    def limbs(self):
        return self.tiles.shape[1]

    @classmethod
    def wrap(cls, x, limbs: int = N_LIMBS) -> "TileForm":
        """[..., limbs] array -> TileForm (no-op when already TileForm).
        The sanctioned entry crossing of the layout boundary."""
        if isinstance(x, cls):
            return x
        _count_crossing("to_tiles")
        tiles, shape, b = _to_tiles_impl(x.astype(jnp.int32), limbs)
        return cls(tiles, shape, b)

    def unwrap(self):
        """TileForm -> [..., limbs] array.  The sanctioned exit crossing
        of the layout boundary."""
        _count_crossing("from_tiles")
        return _from_tiles_impl(self.tiles, self.shape, self.b, self.limbs)


def tile_concat(tfs) -> TileForm:
    """Concatenate TileForms along the LIMB axis.  Layout-preserving —
    the (8, 128) batch tiling is untouched, so this is NOT a boundary
    crossing; it is how packed operands combine for a kernel call without
    relayout."""
    shape, b = tfs[0].shape, tfs[0].b
    for t in tfs[1:]:
        assert t.shape == shape and t.b == b, (t.shape, shape)
    return TileForm(jnp.concatenate([t.tiles for t in tfs], axis=1),
                    shape, b)


def tile_split(tf: TileForm, sizes) -> list:
    """Split a TileForm along the limb axis (inverse of tile_concat;
    layout-preserving, not a crossing)."""
    outs, off = [], 0
    for s in sizes:
        outs.append(TileForm(tf.tiles[:, off:off + s], tf.shape, tf.b))
        off += s
    assert off == tf.limbs, (off, tf.limbs)
    return outs


def tile_stack(tfs) -> TileForm:
    """Join K TileForms of one batch along the TILE axis, each keeping
    its own padding: run k of the result's tiles is tfs[k]'s, so one
    kernel launch walks the K batches as one grid and `flat_mul`'s
    `b_run` reads one of them back in place.  Layout-preserving, not a
    crossing; the logical shape is (K, padded rows)."""
    nt = tfs[0].tiles.shape[0]
    for t in tfs[1:]:
        assert t.tiles.shape == tfs[0].tiles.shape, (t.tiles.shape, nt)
    return TileForm(jnp.concatenate([t.tiles for t in tfs], axis=0),
                    (len(tfs), nt * TILE), len(tfs) * nt * TILE)


def _to_tiles_impl(x, limbs):
    """[..., limbs] -> ([Nt, limbs, 8, 128], batch, count).  Called ONLY
    by TileForm.wrap (tile-seam lint rule)."""
    shape = x.shape[:-1]
    b = int(np.prod(shape)) if shape else 1
    flat = x.reshape(b, limbs)
    pad = (-b) % TILE
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.zeros((pad, limbs), flat.dtype)], 0)
    nt = (b + pad) // TILE
    # [Nt, 8, 128, limbs] -> [Nt, limbs, 8, 128]
    tiles = jnp.moveaxis(flat.reshape(nt, _ROW[0], _ROW[1], limbs), -1, 1)
    return tiles, shape, b


def _from_tiles_impl(tiles, shape, b, limbs):
    """Inverse of _to_tiles_impl.  Called ONLY by TileForm.unwrap."""
    flat = jnp.moveaxis(tiles, 1, -1).reshape(-1, limbs)[:b]
    return flat.reshape(shape + (limbs,))


@functools.cache
def use_pallas() -> bool:
    """Is the backend a TPU?  Asked once per process, of the first device;
    a backend that cannot be brought up is an error here, not a reason to
    trace the pure-XLA graph."""
    return jax.devices()[0].platform == "tpu"


# ---------------------------------------------------------------------------
# In-kernel helpers (operate on lists of [8, 128] int32 rows)
# ---------------------------------------------------------------------------

# The row helpers below are what every kernel body is made of, tens of
# thousands of times per kernel, so they bind `lax` primitives directly:
# a `jnp` operator on a tracer goes through a jitted ufunc and costs
# several times as much to TRACE for the same equation (PR 22: tracing,
# not compiling, is where a verify program's build time goes).

_I32_MASK = np.int32(MASK)
_I32_BITS = np.int32(LIMB_BITS)
_mul, _add = jax.lax.mul, jax.lax.add
_and, _shr = jax.lax.bitwise_and, jax.lax.shift_right_arithmetic
_or, _eq, _gt = jax.lax.bitwise_or, jax.lax.eq, jax.lax.gt
_sub = jax.lax.sub


def _carry_cheap_rows(rows, passes=2):
    """Value-preserving partial carry over a row list (drops nothing as
    long as the caller allotted enough rows)."""
    for _ in range(passes):
        out = []
        carry = None
        for r in rows:
            lo = _and(r, _I32_MASK)
            if carry is not None:
                lo = _add(lo, carry)
            carry = _shr(r, _I32_BITS)
            out.append(lo)
        rows = out
        # final carry out of the top row must be zero by construction
    return rows


def _carry_exact_rows(rows):
    """Exact ripple carry: canonical [0, 2^12) rows, top overflow dropped
    (mod 2^(12*n))."""
    out = []
    carry = None
    for r in rows:
        t = r if carry is None else _add(r, carry)
        out.append(_and(t, _I32_MASK))
        carry = _shr(t, _I32_BITS)
    return out


def _ge_rows(a_rows, const_vec):
    """a >= const (canonical rows vs python-int limb list), branchless."""
    # lexicographic from most significant
    res = None
    for i in range(len(a_rows) - 1, -1, -1):
        c = np.int32(const_vec[i])
        eq = _eq(a_rows[i], c)
        gt = _gt(a_rows[i], c)
        if res is None:
            res = gt
            eq_all = eq
        else:
            res = _or(res, _and(eq_all, gt))
            eq_all = _and(eq_all, eq)
    return _or(res, eq_all)


def _conv_rows(a_rows, b_rows):
    """Schoolbook convolution: 63 column rows (un-carried, < 2^31)."""
    n = len(a_rows)
    cols = []
    for k in range(2 * n - 1):
        acc = None
        for i in range(max(0, k - n + 1), min(k, n - 1) + 1):
            p = _mul(a_rows[i], b_rows[k - i])
            acc = p if acc is None else _add(acc, p)
        cols.append(acc)
    return cols


def _sqr_conv_rows(a_rows):
    """Squaring convolution: n(n+1)/2 products instead of n^2.

    z[k] = 2 * sum_{i<j, i+j=k} a_i a_j + (k even ? a_{k/2}^2 : 0); the
    column VALUE equals the full conv's, so every downstream carry/reduce
    bound is unchanged, and the doubled partial sums stay < 2^30 (16
    off-diagonal 24-bit products, doubled)."""
    n = len(a_rows)
    cols = []
    for k in range(2 * n - 1):
        acc = None
        for i in range(max(0, k - n + 1), (k - 1) // 2 + 1):
            p = _mul(a_rows[i], a_rows[k - i])
            acc = p if acc is None else _add(acc, p)
        if acc is not None:
            acc = _add(acc, acc)
        if k % 2 == 0:
            d = _mul(a_rows[k // 2], a_rows[k // 2])
            acc = d if acc is None else _add(acc, d)
        cols.append(acc)
    return cols


def _mul_const_rows(x_rows, const_limbs, out_len):
    """x (rows) times a static constant (python ints), column sums."""
    n = len(x_rows)
    m = len(const_limbs)
    cols = []
    for k in range(out_len):
        acc = None
        for i in range(n):
            j = k - i
            if 0 <= j < m and const_limbs[j]:
                p = _mul(x_rows[i], np.int32(const_limbs[j]))
                acc = p if acc is None else _add(acc, p)
        cols.append(acc if acc is not None else None)
    return [c if c is not None else jnp.zeros(_ROW, jnp.int32) for c in cols]


def _fp2_block(ref, p, c):
    """Fp2 packed layout: limb rows of coordinate c of the p-th element."""
    base = (p * 2 + c) * N_LIMBS
    bb = ref[0, pl.ds(base, N_LIMBS)]
    return [bb[l] for l in range(N_LIMBS)]


def _select_rows(mask, a_rows, b_rows):
    return [jnp.where(mask, a, b) for a, b in zip(a_rows, b_rows)]


# ---------------------------------------------------------------------------
# Host-side static tables shared by the flat-Fp12 kernels and the merged
# Miller-iteration kernels (ONE builder per table so the merged kernel's
# multiply phases are the standalone kernels' phases by construction).
# ---------------------------------------------------------------------------

# Sparse-line flat layout: 3 Fp2 coefficients at w-powers {0, 2, 3}, i.e.
# flat slots {0,2,3,6,8,9} (pairing.LINE_IDX — asserted equal there).
LINE_IDX = (0, 2, 3, 6, 8, 9)


@functools.cache
def _flat_mul_tab(b_idx):
    """Compact product table for a 12-slot x b_idx flat multiply:
    (tab [4, max(K, n)] — rows 0 and 1, at column k, the start and the
     count of power k's run in the flat list of the n slot products;
     rows 2 and 3, at column t, the list's (i, jj): a_i times b row
     group jj, which holds power b_idx[jj] = k - i; i rising in a run —
     pairs ((k, n_products), ...), K)."""
    K = 11 + max(b_idx) + 1
    inv = {p: jj for jj, p in enumerate(b_idx)}
    runs = [[(i, inv[k - i]) for i in range(12) if k - i in inv]
            for k in range(K)]
    counts = [len(run) for run in runs]
    flat = [prod for run in runs for prod in run]
    tab = np.zeros((4, max(K, len(flat))), np.int32)
    tab[0, :K] = np.cumsum([0] + counts[:-1])
    tab[1, :K] = counts
    tab[2:, :len(flat)] = np.asarray(flat, np.int32).T
    return tab, tuple(enumerate(counts)), K


@functools.cache
def _flat_sqr_tab():
    """Compact slot-symmetric squaring table: (tab [4, 66] — rows 0 and
    1, at column k, the start and the count of power k's run in the flat
    list of the 66 pairs (i, k - i) with i < k - i; row 2 the count of
    its diagonal a_{k/2}^2, 1 for the 12 even k and 0 else; row 3, at
    column t, the list's i, rising in a run — and the per-conv product
    counts)."""
    K = 23
    runs = [list(range(max(0, k - 11), (k - 1) // 2 + 1)) for k in range(K)]
    counts = [len(run) for run in runs]
    flat = [i for run in runs for i in run]
    tab = np.zeros((4, len(flat)), np.int32)
    tab[0, :K] = np.cumsum([0] + counts[:-1])
    tab[1, :K] = counts
    tab[2, :K] = [k % 2 == 0 for k in range(K)]
    tab[3] = flat
    pairs = tuple((k, 2 * counts[k] + int(tab[2, k])) for k in range(K))
    return tab, pairs


@functools.cache
def _line_merge_tables():
    """Static tables for the sparse-sparse line product l1 * l2: both
    operands live on the 6 LINE_IDX slots, so the raw product spans
    w-powers 0..18 with at most 4 contributing (i, j) pairs per power —
    36 slot convolutions total, against 144 for a dense 12x12 multiply.

    Returns (pairs_by_k, scatter, counts): pairs_by_k[k] = ((i, j), ...)
    operand-group pairs landing on power k; scatter[k] = ((slot, coeff),
    ...) the signed minimal-polynomial recombination (w^12 = 2w^6 - 2
    iterated — validated against flat12._reduce_matrix below); counts
    feeds _flat_acc_offsets."""
    K = 2 * max(LINE_IDX) + 1              # 19
    pairs_by_k = [[] for _ in range(K)]
    for i, pi in enumerate(LINE_IDX):
        for j, pj in enumerate(LINE_IDX):
            pairs_by_k[pi + pj].append((i, j))
    scatter = []
    for k in range(K):
        if k < 12:
            scatter.append(((k, 1),))
        elif k < 18:
            scatter.append(((k - 6, 2), (k - 12, -2)))
        else:
            scatter.append(((k - 12, 2), (k - 18, -4)))
    # the scatter rows must BE the minimal-polynomial reduction matrix
    from drand_tpu.ops.flat12 import _reduce_matrix
    red = _reduce_matrix(K)
    for k in range(K):
        row = np.zeros(12, np.int64)
        for slot, coeff in scatter[k]:
            row[slot] += coeff
        assert (row == red[k]).all(), (k, row, red[k])
    counts = tuple((k, len(pairs_by_k[k])) for k in range(K))
    return (tuple(tuple(p) for p in pairs_by_k), tuple(scatter), counts)


# The Granger-Scott square's twelve outputs as linear forms of its 27
# un-reduced convolutions: the kernel's recombination and the bound
# builder (PallasField._cyclo_sqr_plan) both read THIS table, so what is
# asserted is what runs.  For an fp4 group (a, b) of tower cells with
# s = a + b, and for x = (x0, x1) in {a, b, s}: sq = x0^2, iq = x1^2,
# cr = 2 x0 x1, so x^2 = (sq - iq, cr) and
#   re = a^2 + xi b^2 = (a.sq - a.iq + b.sq - b.iq - b.cr,
#                        a.cr + b.sq - b.iq + b.cr)
#   im = s^2 - a^2 - b^2.
# An even slot is 3 re - 2 g, an odd one 3 t + 2 g with t = im, or
# xi im = (im_x - im_y, im_x + im_y) for slot 1; the flat encoding
# stores lo = x - y and hi = y, and the input's own lo and hi are g's.
# A row: (half, groups, slots, the input's coefficient, terms); every
# term is (coefficient, cell, convolution) and the whole sum is taken
# times three.
_CY_A, _CY_B, _CY_S = 0, 1, 2            # cell of the group: a, b, a + b
_CY_SQ, _CY_IQ, _CY_CR = 0, 1, 2         # x0^2, x1^2, 2 x0 x1
_CY_IM_X = ((1, _CY_S, _CY_SQ), (1, _CY_A, _CY_IQ), (1, _CY_B, _CY_IQ),
            (-1, _CY_S, _CY_IQ), (-1, _CY_A, _CY_SQ), (-1, _CY_B, _CY_SQ))
_CY_IM_Y = ((1, _CY_S, _CY_CR), (-1, _CY_A, _CY_CR), (-1, _CY_B, _CY_CR))


def _cy_scaled(terms, k):
    return tuple((k * c, cell, conv) for c, cell, conv in terms)


_CYCLO_SQR_OUTPUTS = (
    # slots 0, 2, 4 = 3 re - 2 g of groups A, B, C
    ("lo", (0, 1, 2), (0, 2, 4), -2,
     ((1, _CY_A, _CY_SQ), (-1, _CY_A, _CY_IQ), (-1, _CY_A, _CY_CR),
      (-2, _CY_B, _CY_CR))),
    ("hi", (0, 1, 2), (0, 2, 4), -2,
     ((1, _CY_A, _CY_CR), (1, _CY_B, _CY_SQ), (1, _CY_B, _CY_CR),
      (-1, _CY_B, _CY_IQ))),
    # slots 3, 5 = 3 im + 2 g of groups A, B
    ("lo", (0, 1), (3, 5), 2, _CY_IM_X + _cy_scaled(_CY_IM_Y, -1)),
    ("hi", (0, 1), (3, 5), 2, _CY_IM_Y),
    # slot 1 = 3 xi im + 2 g of group C
    ("lo", (2,), (1,), 2, _cy_scaled(_CY_IM_Y, -2)),
    ("hi", (2,), (1,), 2, _CY_IM_X + _CY_IM_Y),
)


def _carried_maxes(maxes, passes):
    """Row-wise upper bounds of `_carry_cheap_rows` over non-negative
    rows bounded by `maxes` (exact Python integers)."""
    for _ in range(passes):
        maxes = [min(m, MASK) + (maxes[i - 1] >> LIMB_BITS if i else 0)
                 for i, m in enumerate(maxes)]
    return maxes


# ---------------------------------------------------------------------------
# Kernel factory: mont_mul / mont_reduce for one modulus
# ---------------------------------------------------------------------------

class PallasField:
    """Pallas twin of ops.field.Field for one modulus."""

    def __init__(self, modulus: int):
        self.modulus = modulus
        R = 1 << (LIMB_BITS * N_LIMBS)
        pprime = (-pow(modulus, -1, R)) % R
        tolimbs = lambda v, n: [(v >> (LIMB_BITS * i)) & MASK
                                for i in range(n)]
        self.PPRIME = tolimbs(pprime, N_LIMBS)
        self.MOD = tolimbs(modulus, N_LIMBS)
        ks = tuple(k for k in (1, 2, 4, 8) if k * modulus < R)
        self.K = {k: tolimbs(k * modulus, N_LIMBS) for k in ks}
        self.NEG = {k: tolimbs(R - k * modulus, N_LIMBS) for k in ks}
        self.ONE_MONT = tolimbs(R % modulus, N_LIMBS)
        self._launchers = {}             # see _launch

    # -- the fused mont multiply -------------------------------------------

    def _mont_reduce_rows(self, t_rows, canonical=True, subs=(2, 1)):
        """t (64 cheap-carried rows) -> 32 rows of t*R^-1 mod m.

        canonical=True (the default) conditionally subtracts `subs` (value
        budget: t < (subs[0]*2 - 1)*R*m roughly; the standard (2, 1) chain
        reduces r < 3m, the extended (8, 4, 2, 1) chain r < 16m).
        canonical=False skips the conditional subtracts: the result rows
        are exact-carried (limbs in [0, 2^12)) with VALUE t/R + m-ish —
        bounded below 2.5m for any t < 2*R*m.  Lazy mode is valid
        whenever the consumer is another convolution (limb bounds hold
        regardless) and some later canonical reduce/cond-sub restores
        [0, m) — the Fermat/x-power chains run all intermediate squarings
        lazy and the final table multiply canonical."""
        _MONT_REDUCTIONS["coords"] += int(
            np.prod(t_rows[0].shape[:-len(_ROW)]))
        m_cols = _mul_const_rows(t_rows[:N_LIMBS], self.PPRIME, N_LIMBS)
        m_rows = _carry_cheap_rows(m_cols, 2)
        u_cols = _mul_const_rows(m_rows, self.MOD, 2 * N_LIMBS - 1)
        u = [u_cols[i] + t_rows[i] for i in range(2 * N_LIMBS - 1)]
        u.append(t_rows[2 * N_LIMBS - 1])
        u = _carry_exact_rows(_carry_cheap_rows(u, 2))
        r = u[N_LIMBS:]
        if not canonical:
            return r
        for k in subs:
            ge = _ge_rows(r, self.K[k])
            d = _carry_exact_rows([_add(r[i], np.int32(self.NEG[k][i]))
                                   for i in range(N_LIMBS)])
            r = _select_rows(ge, d, r)
        return r

    def _cond_sub_full_rows(self, s_rows):
        """Canonical s < 2m -> [0, m)."""
        ge = _ge_rows(s_rows, self.K[1])
        d = _carry_exact_rows([_add(s_rows[i], np.int32(self.NEG[1][i]))
                               for i in range(N_LIMBS)])
        return _select_rows(ge, d, s_rows)

    def _add_kernel(self, a_ref, b_ref, o_ref):
        s = _carry_exact_rows([a_ref[0, i] + b_ref[0, i]
                               for i in range(N_LIMBS)])
        r = self._cond_sub_full_rows(s)
        for i in range(N_LIMBS):
            o_ref[0, i] = r[i]

    def _sub_kernel(self, a_ref, b_ref, o_ref):
        # a - b = a + (m+1) + ~b, drop 2^384, then one cond-sub
        mp1 = [(self.modulus + 1 >> (LIMB_BITS * i)) & MASK
               for i in range(N_LIMBS)]
        mp1 = [((self.modulus + 1) >> (LIMB_BITS * i)) & MASK
               for i in range(N_LIMBS)]
        s = _carry_exact_rows([
            a_ref[0, i] + int(mp1[i]) + (MASK - b_ref[0, i])
            for i in range(N_LIMBS)])
        r = self._cond_sub_full_rows(s)
        for i in range(N_LIMBS):
            o_ref[0, i] = r[i]

    def _mont_mul_kernel(self, a_ref, b_ref, o_ref):
        a_rows = [a_ref[0, i] for i in range(N_LIMBS)]
        b_rows = [b_ref[0, i] for i in range(N_LIMBS)]
        t = _carry_cheap_rows(_conv_rows(a_rows, b_rows) +
                              [jnp.zeros(_ROW, jnp.int32)], 2)
        r = self._mont_reduce_rows(t)
        for i in range(N_LIMBS):
            o_ref[0, i] = r[i]

    def _mont_sqr_kernel(self, a_ref, o_ref):
        a_rows = [a_ref[0, i] for i in range(N_LIMBS)]
        t = _carry_cheap_rows(_sqr_conv_rows(a_rows) +
                              [jnp.zeros(_ROW, jnp.int32)], 2)
        r = self._mont_reduce_rows(t)
        for i in range(N_LIMBS):
            o_ref[0, i] = r[i]

    def _mont_reduce_kernel(self, t_ref, o_ref):
        t_rows = _carry_cheap_rows([t_ref[0, i]
                                    for i in range(2 * N_LIMBS)], 2)
        r = self._mont_reduce_rows(t_rows)
        for i in range(N_LIMBS):
            o_ref[0, i] = r[i]

    # -- in-kernel canonical Fp helpers (row lists in, canonical rows out) --
    #
    # These mirror ops.field.Field's add/sub/mul_small bounds exactly so the
    # fused curve/tower kernels below can keep every intermediate canonical
    # without leaving VMEM (profiling showed the XLA-level carry glue around
    # small adds/subs costing more than the Montgomery products themselves).

    def _add_rows(self, a_rows, b_rows):
        s = _carry_exact_rows([_add(a, b) for a, b in zip(a_rows, b_rows)])
        return self._cond_sub_full_rows(s)

    def _sub_rows(self, a_rows, b_rows):
        mp1 = [((self.modulus + 1) >> (LIMB_BITS * i)) & MASK
               for i in range(N_LIMBS)]
        s = _carry_exact_rows([
            _add(_add(a, np.int32(mp1[i])), jax.lax.sub(_I32_MASK, b))
            for i, (a, b) in enumerate(zip(a_rows, b_rows))])
        return self._cond_sub_full_rows(s)

    def _mul_small_rows(self, a_rows, c: int):
        assert 1 <= c <= 8
        s = _carry_exact_rows([_mul(r, np.int32(c)) for r in a_rows])
        for k in (4, 2, 1):
            if k < c:
                ge = _ge_rows(s, self.K[k])
                d = _carry_exact_rows([_add(s[i], np.int32(self.NEG[k][i]))
                                       for i in range(N_LIMBS)])
                s = _select_rows(ge, d, s)
        return s

    def _fp2_add_rows(self, a, b):
        return (self._add_rows(a[0], b[0]), self._add_rows(a[1], b[1]))

    def _fp2_sub_rows(self, a, b):
        return (self._sub_rows(a[0], b[0]), self._sub_rows(a[1], b[1]))

    def _neg_rows(self, a_rows):
        """(-a) mod m, canonical in/out (0 -> 0 via the cond-sub)."""
        zeros = [jnp.zeros_like(r) for r in a_rows]
        return self._sub_rows(zeros, a_rows)

    def _fp_mul_rows(self, a_rows, b_rows):
        """Canonical Fp rows -> canonical Montgomery product."""
        t = _carry_cheap_rows(_conv_rows(a_rows, b_rows) +
                              [jnp.zeros_like(a_rows[0])], 2)
        return self._mont_reduce_rows(t)

    def _fp_sqr_rows(self, a_rows):
        """Canonical Fp rows -> canonical Montgomery square."""
        t = _carry_cheap_rows(_sqr_conv_rows(a_rows) +
                              [jnp.zeros_like(a_rows[0])], 2)
        return self._mont_reduce_rows(t)

    def _fp2_mul_rows(self, x, y, off_limbs):
        """Canonical Fp2 rows product (same math/bounds as
        _fp2_products_kernel's body)."""
        x0, x1 = x
        y0, y1 = y
        z = jnp.zeros_like(x0[0])
        t00 = _carry_cheap_rows(_conv_rows(x0, y0) + [z], 2)
        t11 = _carry_cheap_rows(_conv_rows(x1, y1) + [z], 2)
        t01 = _carry_cheap_rows(_conv_rows(x0, y1) + [z], 2)
        t10 = _carry_cheap_rows(_conv_rows(x1, y0) + [z], 2)
        c0w = [t00[l] + (int(off_limbs[l]) - t11[l])
               for l in range(2 * N_LIMBS)]
        c1w = [t01[l] + t10[l] for l in range(2 * N_LIMBS)]
        r0 = self._mont_reduce_rows(_carry_cheap_rows(c0w, 1))
        r1 = self._mont_reduce_rows(_carry_cheap_rows(c1w, 1))
        return (r0, r1)

    def _fp2_sqr_rows(self, x, off_limbs, canonical=True):
        """Fp2 rows -> square (same math/bounds as _fp2_sqrs_kernel's
        body).  canonical=False runs both Montgomery reduces lazy (no
        conditional subtracts): with inputs of value < 2.5m the wide
        values stay below 2*c^2*m^2 + K*p^2 < 2*R*m, and the outputs stay
        below 2.5m — the stable operating band of the fp2 power chains
        (see fp2_sqr5_mul)."""
        x0, x1 = x
        z = jnp.zeros_like(x0[0])
        t00 = _carry_cheap_rows(_sqr_conv_rows(x0) + [z], 2)
        t11 = _carry_cheap_rows(_sqr_conv_rows(x1) + [z], 2)
        t01 = _conv_rows(x0, x1) + [z]
        t01 = _carry_cheap_rows([c + c for c in t01], 2)
        c0w = [t00[l] + (int(off_limbs[l]) - t11[l])
               for l in range(2 * N_LIMBS)]
        r0 = self._mont_reduce_rows(_carry_cheap_rows(c0w, 1), canonical)
        r1 = self._mont_reduce_rows(t01, canonical)
        return (r0, r1)

    # -- fused cyclotomic squaring (final-exp x-chains) ---------------------
    #
    # The x-power chains run flat_cyclo_sqr 63 times per chain; profiling
    # (round 3) showed its XLA form at ~85% carry/select glue around one
    # fused products call.  This kernel keeps the whole Granger-Scott
    # square in VMEM, and since ISSUE 42 its linear tail (the Fp4
    # recombination, the 3t +- 2g folds and the flat re-encoding) runs in
    # the WIDE domain, in front of the Montgomery reduction: the 27
    # convolutions of the nine Fp2 squares are summed into the 12 output
    # coordinates as 64-row int32 values (_CYCLO_SQR_OUTPUTS), the input
    # enters as g*R (its limbs at rows 32..63), and each output is
    # reduced once: 12 reductions a launch where reducing the squares
    # first took 18, and about 86 canonical add/sub passes gone.

    @functools.lru_cache(maxsize=None)
    def _cyclo_sqr_plan(self):
        """(offsets, subs) of `_cyclo_sqr_kernel`: a 64-limb K*p^2
        constant for each row of _CYCLO_SQR_OUTPUTS, sized to what that
        row subtracts, and the conditional-subtract chain of the one
        stacked reduce; `_cyclo_sqr_check` asserts every bound."""
        from drand_tpu.ops.towers import wide_neg_offset
        offs = []
        for out in _CYCLO_SQR_OUTPUTS:
            sub_value, _, sub_limbs, _ = self._cyclo_sqr_ranges(out)
            # wide_neg_offset's limbs are 4300 * scale below the top one;
            # 2 * scale << 756 above the subtracted value keeps its top
            # limb over the subtracted top limbs too
            scale = -(-max(sub_limbs[:-1]) // 4300)
            off, _ = wide_neg_offset(
                scale, min_value=sub_value + ((2 * scale) << 756))
            offs.append(tuple(int(v) for v in off))
        offs = tuple(offs)
        return offs, self._cyclo_sqr_check(offs)

    def _cyclo_sqr_ranges(self, out):
        """What one row of _CYCLO_SQR_OUTPUTS subtracts and adds, at most,
        over canonical inputs: (subtracted value, added value, subtracted
        limbs, added limbs), the limbs row by row of the 64."""
        m = self.modulus
        _half, _groups, _slots, g_coeff, terms = out
        products = [min(k, 2 * N_LIMBS - 2 - k) + 1
                    for k in range(2 * N_LIMBS - 1)]

        def conv(twice):
            # a convolution of canonical operands (cr: doubled), cheap-
            # carried in 64 rows: its value, and its rows under both
            value = twice * (m - 1) ** 2
            rows = _carried_maxes(
                [twice * n * MASK * MASK for n in products] + [0], 2)
            return value, [min(v, value >> (LIMB_BITS * l))
                           for l, v in enumerate(rows)]

        # every summand: (signed multiple, value, rows)
        parts = [(3 * coeff, *conv(2 if kind == _CY_CR else 1))
                 for coeff, _cell, kind in terms]
        # the input's coordinate times R: its limbs at rows 32..63
        parts.append((g_coeff, (m - 1) << (LIMB_BITS * N_LIMBS),
                      [0] * N_LIMBS + [min(MASK, (m - 1) >> (LIMB_BITS * l))
                                       for l in range(N_LIMBS)]))
        values, limbs = [0, 0], [[0] * (2 * N_LIMBS) for _ in range(2)]
        for k, value, rows in parts:
            side = int(k > 0)                    # 0: subtracted, 1: added
            values[side] += abs(k) * value
            limbs[side] = [a + abs(k) * v for a, v in zip(limbs[side], rows)]
        sub_value, add_value = values
        sub_limbs, add_limbs = limbs
        return sub_value, add_value, sub_limbs, add_limbs

    def _cyclo_sqr_check(self, offs):
        """Assert, on exact integers, that with `offs` every output of
        the wide recombination is a sound input of `_mont_reduce_rows`,
        and return the shortest conditional-subtract chain that brings
        all of them to [0, m).  A bound that fails fails the BUILD: an
        under-covered subtraction would wrap mod 2^768 at the reduce and
        surface as an off-by-one result (see towers.wide_neg_offset)."""
        m = self.modulus
        W = 2 * N_LIMBS
        R = 1 << (LIMB_BITS * N_LIMBS)
        assert len(offs) == len(_CYCLO_SQR_OUTPUTS)
        r_max = 0
        for out, off in zip(_CYCLO_SQR_OUTPUTS, offs):
            sub_value, add_value, sub_limbs, add_limbs = \
                self._cyclo_sqr_ranges(out)
            assert len(off) == W, out
            off_value = sum(int(v) << (LIMB_BITS * l)
                            for l, v in enumerate(off))
            # a multiple of the modulus: the residue is the formula's
            assert off_value % m == 0, out
            # never negative: in value, and limb by limb, so that every
            # row stays a non-negative int32 through the carries and
            # the reduction's m = t * p' is no negative number either
            assert off_value >= sub_value, (out, off_value, sub_value)
            assert all(o >= s for o, s in zip(off, sub_limbs)), (out, off)
            rows = [o + a for o, a in zip(off, add_limbs)]
            assert max(o + s + a for o, s, a in zip(
                off, sub_limbs, add_limbs)) < 1 << 31, out
            # one cheap pass brings the rows to what every caller of
            # _mont_reduce_rows hands it (limbs <= 4224)
            t_value = off_value + add_value
            t_rows = _carried_maxes(rows, 1)
            assert max(t_rows) <= 4224, (out, max(t_rows))
            # the reduction's own column sums, and u = t + m_val * m
            # inside the 64-limb window (rows non-negative: the top
            # row then holds the top of the value, nothing is dropped)
            m_cols = [sum(t_rows[i] * self.PPRIME[k - i]
                          for i in range(k + 1)) for k in range(N_LIMBS)]
            assert max(m_cols) < 1 << 31, out
            m_rows = _carried_maxes(m_cols, 2)
            u_cols = [sum(m_rows[i] * self.MOD[k - i]
                          for i in range(N_LIMBS) if 0 <= k - i < N_LIMBS)
                      + t_rows[k] for k in range(W)]
            assert max(u_cols) < 1 << 31, out
            m_value = sum(v << (LIMB_BITS * l) for l, v in enumerate(m_rows))
            assert t_value + m_value * m < 1 << (LIMB_BITS * W), out
            r_max = max(r_max, (t_value + m_value * m) // R)
        # the twelve reduce as ONE stack, so the worst of them decides
        # (with these offsets every one lies between 4m and 8m)
        for subs in ((1,), (2, 1), (4, 2, 1), (8, 4, 2, 1)):
            if r_max < 2 * subs[0] * m:
                assert all(k in self.K for k in subs), subs
                return subs
        raise AssertionError(("no chain reduces", r_max // m))

    def _cyclo_sqr_kernel(self, plan, a_ref, o_ref):
        """Every stage operates on STACKED rows ([k, 8, 128] per limb):
        the whole square is one traced conv/carry body per stage, not an
        unrolled per-cell program — ~6x fewer Mosaic instructions, same
        vector work.  Front: cell extraction, s = a + b a group, the 27
        convolutions of the nine Fp2 squares, cheap-carried and NOT
        reduced.  Tail: the 12 outputs of _CYCLO_SQR_OUTPUTS summed wide
        over the offsets of `plan`, one stacked Montgomery reduction,
        canonical rows out."""
        offs, subs = plan

        def stk(slots, base=0):
            return [jnp.stack([a_ref[0, (base + s) * N_LIMBS + l]
                               for s in slots], 0) for l in range(N_LIMBS)]

        lo6 = stk(range(6))
        hi6 = stk(range(6), base=6)
        xs6 = self._add_rows(lo6, hi6)                 # tower-cell x coords

        # tower cells (z0..z5) at flat slots (0,2,4)+(1,3,5); fp4 groups
        # A=(g0,g4), B=(g3,g2), C=(g1,g5).  Stack order: a-parts, b-parts.
        A_SLOT = (0, 1, 2)     # g0, g3, g1  at slots 0, 1, 2
        B_SLOT = (3, 4, 5)     # g4, g2, g5  at slots 3, 4, 5
        pick = lambda rows, idx: [jnp.stack([r[i] for i in idx], 0)
                                  for r in rows]
        ax = pick(xs6, A_SLOT); ay = pick(hi6, A_SLOT)
        bx = pick(xs6, B_SLOT); by = pick(hi6, B_SLOT)
        # s = a + b per group (three Fp2 adds, one stacked call per coord)
        sx = self._add_rows(ax, bx)
        sy = self._add_rows(ay, by)
        # nine squares in one stacked pass: [a(3), b(3), s(3)], every
        # operand canonical, so a convolution is below p^2
        x0s = [jnp.concatenate([a, b, s], 0) for a, b, s in zip(ax, bx, sx)]
        x1s = [jnp.concatenate([a, b, s], 0) for a, b, s in zip(ay, by, sy)]
        z = jnp.zeros_like(x0s[0])
        wide = {
            _CY_SQ: _carry_cheap_rows(_sqr_conv_rows(x0s) + [z], 2),
            _CY_IQ: _carry_cheap_rows(_sqr_conv_rows(x1s) + [z], 2),
            _CY_CR: _carry_cheap_rows(
                [_add(c, c) for c in _conv_rows(x0s, x1s) + [z]], 2),
        }

        # the recombination, row by row of the 64: each output kind a
        # stack over its groups, the kinds joined into one stack of 12
        t_rows = [[] for _ in range(2 * N_LIMBS)]
        for (half, groups, slots, g_coeff, terms), off in zip(
                _CYCLO_SQR_OUTPUTS, offs):
            g_in = stk(slots, base=0 if half == "lo" else 6)
            first, n = groups[0], len(groups)
            terms = sorted(terms, reverse=True)      # an added one first
            assert terms[0][0] > 0, terms
            for l in range(2 * N_LIMBS):
                acc = None
                for coeff, cell, conv in terms:
                    at = 3 * cell + first
                    x = wide[conv][l][at:at + n]
                    if abs(coeff) == 2:
                        x = _add(x, x)
                    if acc is None:
                        acc = x
                    else:
                        acc = _add(acc, x) if coeff > 0 else _sub(acc, x)
                acc = _add(_mul(acc, np.int32(3)), np.int32(off[l]))
                if l >= N_LIMBS:
                    acc = _add(acc, _mul(g_in[l - N_LIMBS],
                                         np.int32(g_coeff)))
                t_rows[l].append(acc)
        t = _carry_cheap_rows([jnp.concatenate(ks, 0) for ks in t_rows], 1)
        r = self._mont_reduce_rows(t, subs=subs)

        i = 0
        for half, _groups, slots, _g, _terms in _CYCLO_SQR_OUTPUTS:
            for s in slots:
                base = (s if half == "lo" else s + 6) * N_LIMBS
                for l in range(N_LIMBS):
                    o_ref[0, base + l] = r[l][i]
                i += 1

    def cyclo_sqr(self, a):
        """Fused Granger-Scott cyclotomic square of a flat Fp12 element
        ([..., 12, 32] canonical Montgomery limbs, or the packed
        TileForm — output kind follows the input)."""
        kernel = functools.partial(self._cyclo_sqr_kernel,
                                   self._cyclo_sqr_plan())
        if isinstance(a, TileForm):
            out = self._call(kernel, 12 * N_LIMBS, a.tiles)
            return TileForm(out, a.shape, a.b)
        shape = a.shape[:-2]
        tf = TileForm.wrap(a.reshape(shape + (12 * N_LIMBS,)), 12 * N_LIMBS)
        out = self._call(kernel, 12 * N_LIMBS, tf.tiles)
        return TileForm(out, tf.shape, tf.b).unwrap(
            ).reshape(shape + (12, N_LIMBS))

    # -- host wrappers ------------------------------------------------------

    def tile(self, x, limbs=N_LIMBS):
        """[..., limbs] array -> TileForm (no-op when already TileForm)."""
        return TileForm.wrap(x, limbs)

    def untile(self, x, limbs=None):
        """TileForm -> [..., limbs] array (no-op on plain arrays)."""
        if not isinstance(x, TileForm):
            return x
        return x.unwrap()

    def _tile_align(self, args, limbs):
        """Coerce operands to TileForm on one common logical shape (used
        by the TileForm fast paths of the binary wrappers)."""
        shape = None
        for a in args:
            if isinstance(a, TileForm):
                shape = a.shape
                break
        out = []
        for a in args:
            if isinstance(a, TileForm):
                assert a.shape == shape, (a.shape, shape)
                out.append(a)
            else:
                a = jnp.broadcast_to(a, shape + (limbs,))
                out.append(self.tile(a, limbs))
        return out

    def fp2_pack(self, a):
        """Fp2 tuple of [..., 32] coords -> packed TileForm (64 rows:
        c0 limbs then c1 limbs — the _fp2_block kernel layout)."""
        if isinstance(a, TileForm):
            return a
        shape = jnp.broadcast_shapes(a[0].shape, a[1].shape)
        c0 = jnp.broadcast_to(a[0], shape).astype(jnp.int32)
        c1 = jnp.broadcast_to(a[1], shape).astype(jnp.int32)
        return self.tile(jnp.concatenate([c0, c1], axis=-1), 2 * N_LIMBS)

    def fp2_unpack(self, tf):
        if not isinstance(tf, TileForm):
            return tf
        arr = self.untile(tf)
        return (arr[..., :N_LIMBS], arr[..., N_LIMBS:])

    def _call(self, kernel, limbs_out, *tiles, scratch=None):
        """One grid step a tile over operands of one tile count; a list
        `limbs_out` gives a kernel of as many outputs."""
        nt = tiles[0].shape[0]
        spec = lambda l: pl.BlockSpec((1, l, *_ROW), lambda i: (i, 0, 0, 0),
                                      memory_space=pltpu.VMEM)
        shape = lambda l: jax.ShapeDtypeStruct((nt, l, *_ROW), jnp.int32)
        many = isinstance(limbs_out, list)
        return self._launch(
            kernel, tiles,
            out_shape=[shape(l) for l in limbs_out] if many
            else shape(limbs_out),
            grid=(nt,),
            in_specs=[spec(t.shape[1]) for t in tiles],
            out_specs=[spec(l) for l in limbs_out] if many
            else spec(limbs_out),
            scratch_shapes=scratch or [],
        )

    def _launch(self, kernel, args, site=(), **call):
        """`pl.pallas_call(kernel, **call)(*args)` through ONE jitted
        wrapper per distinct kernel and operand shapes.

        pallas_call re-traces its kernel body at every call site, and the
        bodies here are fully unrolled limb arithmetic (7k jaxpr equations
        for mont_mul, 420k for a merged Miller iteration).  The verify
        program reaches 44 distinct kernels from 655 call sites, so
        site-by-site tracing is what made one program take hours to
        build; behind a memoised `jit` each body is traced and lowered
        once and every further site is a call of the same function.  XLA
        inlines the calls: the executable is unchanged.

        `kernel` is a bound method or a `functools.partial` of one over
        static (hashable) arguments; `call` is determined by the kernel
        and the operand shapes, so those two key the wrapper, with
        `site` for what they leave open (an index map's offset).  The
        method's name, less its `_kernel`, names the Pallas call: a
        device trace then says `.../miller/.../mont_mul/pallas_call`
        where the caller's `jax.named_scope` (ops.STAGES) gives the
        stage; the stage is no part of the key."""
        if isinstance(kernel, functools.partial):
            key = (kernel.func.__name__, kernel.args)
        else:
            key = (kernel.__name__, ())
        name = key[0].strip("_").removesuffix("_kernel")
        key += (tuple((a.shape, a.dtype.name) for a in args), site)
        fn = self._launchers.get(key)
        if fn is None:
            fn = self._launchers[key] = _jit(
                pl.pallas_call(kernel, name=name, **call))
        return fn(*args)

    def mont_mul(self, a, b):
        """Drop-in for Field.mont_mul (traceable; use inside jit).
        TileForm operands stay in tile layout end to end."""
        if isinstance(a, TileForm) or isinstance(b, TileForm):
            a, b = self._tile_align((a, b), N_LIMBS)
            out = self._call(self._mont_mul_kernel, N_LIMBS,
                             a.tiles, b.tiles)
            return TileForm(out, a.shape, a.b)
        shape = jnp.broadcast_shapes(a.shape, b.shape)
        at = TileForm.wrap(jnp.broadcast_to(a, shape))
        bt = TileForm.wrap(jnp.broadcast_to(b, shape))
        out = self._call(self._mont_mul_kernel, N_LIMBS, at.tiles, bt.tiles)
        return TileForm(out, at.shape, at.b).unwrap()

    def mont_sqr(self, a):
        """Specialized a*a (triangular conv: ~48% fewer kernel MACs)."""
        if isinstance(a, TileForm):
            out = self._call(self._mont_sqr_kernel, N_LIMBS, a.tiles)
            return TileForm(out, a.shape, a.b)
        at = TileForm.wrap(a)
        out = self._call(self._mont_sqr_kernel, N_LIMBS, at.tiles)
        return TileForm(out, at.shape, at.b).unwrap()

    def mont_reduce(self, t):
        """Drop-in for Field.mont_reduce ([..., 64] wide limbs in)."""
        tt = TileForm.wrap(t, 2 * N_LIMBS)
        out = self._call(self._mont_reduce_kernel, N_LIMBS, tt.tiles)
        return TileForm(out, tt.shape, tt.b).unwrap()

    def _binop(self, kernel, a, b):
        shape = jnp.broadcast_shapes(a.shape, b.shape)
        at = TileForm.wrap(jnp.broadcast_to(a, shape))
        bt = TileForm.wrap(jnp.broadcast_to(b, shape))
        out = self._call(kernel, N_LIMBS, at.tiles, bt.tiles)
        return TileForm(out, at.shape, at.b).unwrap()

    def add(self, a, b):
        return self._binop(self._add_kernel, a, b)

    def sub(self, a, b):
        return self._binop(self._sub_kernel, a, b)

    # -- fused flat-Fp12 multiply ------------------------------------------
    #
    # The XLA flat_mul materializes a [B, 12, J, 64] product tensor in HBM
    # (1.5 GB per instance at B=16k — it OOMs) and streams it back for the
    # reduction.  This kernel walks conv coefficients k one at a time: for
    # each k it accumulates the contributing (i, j) limb convolutions in
    # VMEM, Montgomery-reduces immediately, and only then recombines the
    # canonical coefficients — nothing wide ever leaves the chip.

    # -- wide recombination shared by the flat Fp12 kernels ----------------
    #
    # The round-3 kernels Montgomery-reduced every conv coefficient k
    # (21-23 reduces per multiply) and THEN recombined the canonical
    # coefficients onto the 12 basis slots.  A mont reduce costs ~1.5
    # conv-equivalents of VPU work, and the minimal-polynomial matrix
    # (w^12 = 2w^6 - 2 iterated) has at most 2 targets per k with small
    # +-1/2/4 coefficients — so recombining in the WIDE domain first and
    # reducing only the 12 slot accumulators removes 9-11 reduces per
    # multiply (~10-12% of the kernel).  Negative matrix entries fold
    # through per-slot offset constants (multiples of p^2 sized to keep
    # every slot's value non-negative); the slot values stay far below
    # the 64-limb window (static assert in _flat_acc_offsets).

    @functools.lru_cache(maxsize=None)
    def _flat_acc_offsets(self, K, max_pairs):
        """Per-slot 64-limb offset constants + exact static bound checks.

        Slot j gets the -2 edge from k = j+12 (when < K) and the -4 edge
        from k = j+18; conv_k holds at most `pairs_k` canonical
        slot-products, so the subtracted VALUE reaches
        coeff * pairs_k * m^2 — the offsets are sized per slot to cover
        exactly that (the round-4 warm-run corruption: fixed-scale
        offsets under-covered the subtracted convolution, the slot value
        went negative, and the mod-2^768 wrap surfaced as a +1 error
        after decode).  Every invariant is asserted on exact integers:
        non-negativity, the 64-limb window, the cond-sub range, and the
        int32 accumulation bound."""
        from drand_tpu.ops.towers import wide_neg_offset
        m = self.modulus
        pairs = dict(max_pairs)
        offs = []
        worst = 0
        worst_limb = 0
        for j in range(12):
            row = np.zeros(64, np.int64)
            val = 0
            sub_bound = 0
            if j < 6 and j + 12 < K:
                need = 2 * pairs.get(j + 12, 0) * m * m
                o2, v2 = wide_neg_offset(2, min_value=need + (need >> 3))
                row += o2.astype(np.int64)
                val += v2
                sub_bound += need
            if j < 5 and j + 18 < K:
                need = 4 * pairs.get(j + 18, 0) * m * m
                o4, v4 = wide_neg_offset(4, min_value=need + (need >> 3))
                row += o4.astype(np.int64)
                val += v4
                sub_bound += need
            # the slot value can never go negative
            assert val >= sub_bound, (j, val, sub_bound)
            # exact value bound: positive edges are +1*conv_j,
            # +2*conv_{j+6} (12 <= j+6 < 18), +2*conv_{j+12} (>= 18)
            bound = val + pairs.get(j, 0) * m * m
            if 12 <= j + 6 < min(K, 18):
                bound += 2 * pairs.get(j + 6, 0) * m * m
            if 18 <= j + 12 < K:
                bound += 2 * pairs.get(j + 12, 0) * m * m
            worst = max(worst, bound)
            worst_limb = max(worst_limb, int(row.max()))
            offs.append(tuple(int(v) for v in row))
        R = 1 << (LIMB_BITS * N_LIMBS)
        # u = t + m_val*M must fit the 64-limb window, and the reduced
        # r < 16m for the (8, 4, 2, 1) conditional-subtract chain
        assert worst + R * m < 1 << (2 * LIMB_BITS * N_LIMBS), worst
        assert worst // R + m < 16 * m, worst
        # int32 head-room in the scatter accumulation: offsets + up to
        # 5 coefficient-scaled conv limbs (each conv limb <= 12 * 4224,
        # doubled for the squaring layout)
        assert worst_limb + 5 * 4 * 2 * 12 * 4224 < (1 << 31) // 4
        return tuple(offs)

    @staticmethod
    def _acc_scratch():
        """The flat kernels' accumulator scratch, 64 wide rows a group:
        the 12 slot accumulators, the trash slot, and the sum of the
        power in hand (`_sum_run`; `line_merge` sums statically and
        leaves that last group unused)."""
        return pltpu.VMEM((14 * 2 * N_LIMBS, *_ROW), jnp.int32)

    def _acc_init(self, acc_ref, offs):
        for j in range(12):
            acc_ref[pl.ds(j * 2 * N_LIMBS, 2 * N_LIMBS)] = jnp.stack(
                [jnp.full(_ROW, int(v), jnp.int32) for v in offs[j]], 0)
        acc_ref[pl.ds(12 * 2 * N_LIMBS, 2 * N_LIMBS)] = jnp.zeros(
            (2 * N_LIMBS, *_ROW), jnp.int32)

    @staticmethod
    def _acc_scatter(acc_ref, k, wide):
        """Scatter conv coefficient k (wide rows) onto its 1-2 slot
        accumulators per the minimal-polynomial rows; slot 12 is a trash
        slot that absorbs the (non-existent) negative edge of k < 12 so
        the store pattern stays branch-free."""
        j1 = jnp.where(k < 12, k, jnp.where(k < 18, k - 6, k - 12))
        c1 = jnp.where(k < 12, 1, 2).astype(jnp.int32)
        j2 = jnp.where(k < 12, 12, jnp.where(k < 18, k - 12, k - 18))
        c2 = jnp.where(k < 18, 2, 4).astype(jnp.int32)
        s1 = pl.ds(j1 * (2 * N_LIMBS), 2 * N_LIMBS)
        acc_ref[s1] = acc_ref[s1] + c1 * wide
        s2 = pl.ds(j2 * (2 * N_LIMBS), 2 * N_LIMBS)
        acc_ref[s2] = acc_ref[s2] - c2 * wide

    def _acc_reduce_write(self, acc_ref, write):
        """Reduce the 12 slot accumulators to canonical Montgomery rows
        and hand each to `write(slot, rows)`."""
        for jp in range(12):
            rows = [acc_ref[jp * 2 * N_LIMBS + l]
                    for l in range(2 * N_LIMBS)]
            rows = _carry_cheap_rows(rows, 2)
            r = self._mont_reduce_rows(rows, subs=(8, 4, 2, 1))
            write(jp, r)

    def _acc_reduce_out(self, acc_ref, o_ref):
        def write(jp, r):
            for l in range(N_LIMBS):
                o_ref[0, jp * N_LIMBS + l] = r[l]

        self._acc_reduce_write(acc_ref, write)

    # -- shared multiply/square accumulation phases ------------------------
    #
    # The merged Miller-iteration kernel runs these same phase bodies
    # in-kernel (reading its staged operands through the `read_*`
    # callbacks), so the trio kernels and the merged kernel share one
    # implementation — bit-identity between the paths is by construction,
    # not by parallel maintenance.

    @staticmethod
    def _sum_run(acc_ref, start, count, conv_at):
        """One power's wide sum: the carried convolutions conv_at(t) of
        its run, t in [start, start + count), added in place in the
        scratch's last group.  The bounds are the run's own, read from
        SMEM, so every iteration is a product and no branch stands in
        the loop; and the loop carries its counter alone: a 64-row sum
        carried through it is the whole vector register file, spilled
        and filled around every product."""
        run = pl.ds(13 * 2 * N_LIMBS, 2 * N_LIMBS)
        acc_ref[run] = jnp.zeros((2 * N_LIMBS, *_ROW), jnp.int32)

        def t_body(t, _):
            acc_ref[run] = acc_ref[run] + conv_at(t)
            return 0

        jax.lax.fori_loop(start, start + count, t_body, 0)
        return acc_ref[run]

    def _mul_phase(self, acc_ref, tab_ref, K, read_a, read_b, offs):
        """Generic flat-multiply accumulation: for each conv coefficient
        k, sum the limb convolutions of its run of slot products a_i *
        b_jj (the _flat_mul_tab list) and scatter onto the slot
        accumulators.  The k and product loops are `fori_loop`s so the
        ~1.3k-instruction conv body is traced ONCE (a fully unrolled
        version is ~190k Mosaic instructions and stalls/ooms the
        compiler on full graphs)."""

        def conv_dyn(i, jj):
            aa = read_a(i)
            bb = read_b(jj)
            a_rows = [aa[l] for l in range(N_LIMBS)]
            b_rows = [bb[l] for l in range(N_LIMBS)]
            cols = _conv_rows(a_rows, b_rows) + [jnp.zeros(_ROW, jnp.int32)]
            return jnp.stack(_carry_cheap_rows(cols, 2), 0)

        self._acc_init(acc_ref, offs)

        def k_body(k, _):
            acc = self._sum_run(
                acc_ref, tab_ref[0, k], tab_ref[1, k],
                lambda t: conv_dyn(tab_ref[2, t], tab_ref[3, t]))
            self._acc_scatter(acc_ref, k, acc)
            return 0

        jax.lax.fori_loop(0, K, k_body, 0)

    def _sqr_phase(self, acc_ref, tab_ref, read_a, offs):
        """Slot-symmetric squaring accumulation (the _flat_sqr_tab
        list: a power's run of off-diagonal pairs summed and doubled
        once, then the triangular diagonal a_{k/2}^2 where the table
        counts one, scattered by itself: the scatter is linear, so the
        doubled sum passes through no branch for a diagonal it may not
        have)."""

        def conv_dyn(i, jj):
            aa = read_a(i)
            bb = read_a(jj)
            cols = _conv_rows([aa[l] for l in range(N_LIMBS)],
                              [bb[l] for l in range(N_LIMBS)])
            cols = cols + [jnp.zeros(_ROW, jnp.int32)]
            return jnp.stack(_carry_cheap_rows(cols, 2), 0)

        def sqr_dyn(i):
            aa = read_a(i)
            cols = _sqr_conv_rows([aa[l] for l in range(N_LIMBS)])
            cols = cols + [jnp.zeros(_ROW, jnp.int32)]
            return jnp.stack(_carry_cheap_rows(cols, 2), 0)

        self._acc_init(acc_ref, offs)

        def k_body(k, _):
            def pair(t):
                i = tab_ref[3, t]
                return conv_dyn(i, k - i)

            acc = self._sum_run(acc_ref, tab_ref[0, k], tab_ref[1, k], pair)
            self._acc_scatter(acc_ref, k, acc + acc)

            def d_body(_, c):
                self._acc_scatter(acc_ref, k, sqr_dyn(k // 2))
                return c

            jax.lax.fori_loop(0, tab_ref[2, k], d_body, 0)
            return 0

        jax.lax.fori_loop(0, 23, k_body, 0)

    def _flat_mul_kernel(self, b_idx, offs, tab_ref, a_ref, b_ref,
                         o_ref, acc_ref):
        """tab_ref (SMEM): the compact product table of _flat_mul_tab."""
        K = 11 + max(b_idx) + 1
        self._mul_phase(
            acc_ref, tab_ref, K,
            lambda i: a_ref[0, pl.ds(i * N_LIMBS, N_LIMBS)],
            lambda jj: b_ref[0, pl.ds(jj * N_LIMBS, N_LIMBS)], offs)
        self._acc_reduce_out(acc_ref, o_ref)

    # -- fused Fp2 product stack -------------------------------------------

    def _fp2_products_kernel(self, n, off_limbs, a_ref, b_ref, o_ref):
        def p_body(p, _):
            x0, x1 = _fp2_block(a_ref, p, 0), _fp2_block(a_ref, p, 1)
            y0, y1 = _fp2_block(b_ref, p, 0), _fp2_block(b_ref, p, 1)
            t00 = _carry_cheap_rows(_conv_rows(x0, y0) +
                                    [jnp.zeros(_ROW, jnp.int32)], 2)
            t11 = _carry_cheap_rows(_conv_rows(x1, y1) +
                                    [jnp.zeros(_ROW, jnp.int32)], 2)
            t01 = _carry_cheap_rows(_conv_rows(x0, y1) +
                                    [jnp.zeros(_ROW, jnp.int32)], 2)
            t10 = _carry_cheap_rows(_conv_rows(x1, y0) +
                                    [jnp.zeros(_ROW, jnp.int32)], 2)
            c0w = [t00[l] + (int(off_limbs[l]) - t11[l])
                   for l in range(2 * N_LIMBS)]
            c1w = [t01[l] + t10[l] for l in range(2 * N_LIMBS)]
            r0 = self._mont_reduce_rows(_carry_cheap_rows(c0w, 1))
            r1 = self._mont_reduce_rows(_carry_cheap_rows(c1w, 1))
            o_ref[0, pl.ds((p * 2) * N_LIMBS, N_LIMBS)] = jnp.stack(r0, 0)
            o_ref[0, pl.ds((p * 2 + 1) * N_LIMBS, N_LIMBS)] = \
                jnp.stack(r1, 0)
            return 0

        jax.lax.fori_loop(0, n, p_body, 0)

    def _fp2_sqrs_kernel(self, n, off_limbs, a_ref, o_ref):
        def p_body(p, _):
            x0, x1 = _fp2_block(a_ref, p, 0), _fp2_block(a_ref, p, 1)
            t00 = _carry_cheap_rows(_sqr_conv_rows(x0) +
                                    [jnp.zeros(_ROW, jnp.int32)], 2)
            t11 = _carry_cheap_rows(_sqr_conv_rows(x1) +
                                    [jnp.zeros(_ROW, jnp.int32)], 2)
            # cross term once, doubled (raw cols < 2^29, doubled < 2^30)
            t01 = _conv_rows(x0, x1) + [jnp.zeros(_ROW, jnp.int32)]
            t01 = _carry_cheap_rows([c + c for c in t01], 2)
            c0w = [t00[l] + (int(off_limbs[l]) - t11[l])
                   for l in range(2 * N_LIMBS)]
            r0 = self._mont_reduce_rows(_carry_cheap_rows(c0w, 1))
            r1 = self._mont_reduce_rows(t01)
            o_ref[0, pl.ds((p * 2) * N_LIMBS, N_LIMBS)] = jnp.stack(r0, 0)
            o_ref[0, pl.ds((p * 2 + 1) * N_LIMBS, N_LIMBS)] = \
                jnp.stack(r1, 0)
            return 0

        jax.lax.fori_loop(0, n, p_body, 0)

    def fp2_sqrs(self, items):
        """Fused Fp2 squares: ~49% fewer conv MACs than the products
        kernel on (x, x) pairs (two triangular convs + one doubled cross
        conv instead of four full convs).

        Packed TileForm items (the 64-row fp2_pack layout) stay packed
        end to end: operands combine via tile_concat (layout-preserving)
        and results split back — zero boundary crossings for operands
        already in tile form.  A mixed call coerces plain tuples through
        fp2_pack; output kind follows the input kind."""
        from drand_tpu.ops.towers import _WIDE_NEG_OFF
        n = len(items)
        kernel = functools.partial(
            self._fp2_sqrs_kernel, n,
            tuple(int(v) for v in _WIDE_NEG_OFF))
        if any(isinstance(x, TileForm) for x in items):
            packs = [self.fp2_pack(x) for x in items]
            at = tile_concat(packs)
            out = self._call(kernel, 2 * n * N_LIMBS, at.tiles)
            return tile_split(TileForm(out, at.shape, at.b),
                              [2 * N_LIMBS] * n)
        coords = []
        for x in items:
            coords.extend([x[0], x[1]])
        shape = jnp.broadcast_shapes(*(c.shape[:-1] for c in coords))
        coords = [jnp.broadcast_to(c, shape + (N_LIMBS,)) for c in coords]
        at = TileForm.wrap(jnp.concatenate(coords, axis=-1),
                           2 * n * N_LIMBS)
        out = self._call(kernel, 2 * n * N_LIMBS, at.tiles)
        flat = TileForm(out, at.shape, at.b).unwrap(
            ).reshape(shape + (n, 2, N_LIMBS))
        return [(flat[..., p, 0, :], flat[..., p, 1, :]) for p in range(n)]

    def fp2_products(self, pairs):
        """Fused twin of towers.fp2_products: [(x, y), ...] -> [x*y, ...]
        with x, y Fp2 tuples of [..., 32] arrays or packed TileForms
        (the latter stay packed end to end — see fp2_sqrs)."""
        from drand_tpu.ops.towers import _WIDE_NEG_OFF
        n = len(pairs)
        kernel = functools.partial(
            self._fp2_products_kernel, n,
            tuple(int(v) for v in _WIDE_NEG_OFF))
        if any(isinstance(c, TileForm) for pair in pairs for c in pair):
            xs = [self.fp2_pack(x) for x, _ in pairs]
            ys = [self.fp2_pack(y) for _, y in pairs]
            at = tile_concat(xs)
            bt = tile_concat(ys)
            out = self._call(kernel, 2 * n * N_LIMBS, at.tiles, bt.tiles)
            return tile_split(TileForm(out, at.shape, at.b),
                              [2 * N_LIMBS] * n)
        coords = []
        for x, y in pairs:
            coords.extend([x[0], x[1]])
        for x, y in pairs:
            coords.extend([y[0], y[1]])
        shape = jnp.broadcast_shapes(*(c.shape[:-1] for c in coords))
        coords = [jnp.broadcast_to(c, shape + (N_LIMBS,)) for c in coords]
        at = TileForm.wrap(jnp.concatenate(coords[:2 * n], axis=-1),
                           2 * n * N_LIMBS)
        bt = TileForm.wrap(jnp.concatenate(coords[2 * n:], axis=-1),
                           2 * n * N_LIMBS)
        out = self._call(kernel, 2 * n * N_LIMBS, at.tiles, bt.tiles)
        flat = TileForm(out, at.shape, at.b).unwrap(
            ).reshape(shape + (n, 2, N_LIMBS))
        return [(flat[..., p, 0, :], flat[..., p, 1, :]) for p in range(n)]

    # -- packed-Fp2 tile-layout glue (select / eq / masks) ------------------
    #
    # Selects, equality tests, and boolean masks are elementwise over the
    # (8, 128) batch tiling, so they operate on tile-layout tensors
    # directly: a mask lives as bool[nt, 8, 128] (the tile layout of a
    # [...]-shaped bool), and crossing back to [...] happens once at the
    # consumer's exit via mask_unwrap.  Padded lanes compare equal and
    # select arbitrarily — they are sliced away at unwrap.

    def fp2_eq_tiles(self, a: TileForm, b: TileForm):
        """Packed Fp2 equality -> bool[nt, 8, 128] mask in tile layout."""
        return jnp.all(a.tiles == b.tiles, axis=1)

    def fp2_select_tiles(self, mask, a: TileForm, b: TileForm) -> TileForm:
        """mask ? a : b for packed operands; mask is [nt, 8, 128]."""
        return TileForm(jnp.where(mask[:, None], a.tiles, b.tiles),
                        a.shape, a.b)

    def mask_wrap(self, m, shape):
        """bool[...] -> bool[nt, 8, 128] tile-layout mask (one entry
        crossing, via a 1-limb TileForm)."""
        arr = jnp.broadcast_to(m, shape).astype(jnp.int32)[..., None]
        return TileForm.wrap(arr, 1).tiles[:, 0] != 0

    def mask_unwrap(self, mask, shape, b):
        """bool[nt, 8, 128] tile-layout mask -> bool[...] (one exit
        crossing)."""
        tf = TileForm(mask.astype(jnp.int32)[:, None], shape, b)
        return tf.unwrap()[..., 0] != 0

    def flat_mul(self, a, b, b_idx, b_run=0):
        """Drop-in for flat12.flat_mul: a [..., 12, 32], b [..., J, 32]
        (or TileForm operands in the 12*32 / J*32 packed row layouts —
        the Miller accumulator path; output kind follows `a`).  Where b
        holds several batches of a's joined on the tile axis
        (`tile_stack`: the Miller step kernels' lines, a run a pair),
        `b_run` says which multiplies; the block index map reads it in
        place, no slice."""
        J = len(b_idx)
        K = 11 + max(b_idx) + 1
        a_tiled = isinstance(a, TileForm)
        if a_tiled or isinstance(b, TileForm):
            if not a_tiled:
                shape = b.shape           # b is necessarily TileForm here
                a = self.tile(jnp.broadcast_to(
                    a, shape + (12, N_LIMBS)).reshape(
                        shape + (12 * N_LIMBS,)), 12 * N_LIMBS)
            if not isinstance(b, TileForm):
                b = self.tile(jnp.broadcast_to(
                    b, a.shape + (J, N_LIMBS)).reshape(
                        a.shape + (J * N_LIMBS,)), J * N_LIMBS)
            at, bt, shape, n = a.tiles, b.tiles, a.shape, a.b
        else:
            shape = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
            a = jnp.broadcast_to(a, shape + (12, N_LIMBS))
            b = jnp.broadcast_to(b, shape + (J, N_LIMBS))
            atf = TileForm.wrap(a.reshape(shape + (12 * N_LIMBS,)),
                                12 * N_LIMBS)
            btf = TileForm.wrap(b.reshape(shape + (J * N_LIMBS,)),
                                J * N_LIMBS)
            at, bt, n = atf.tiles, btf.tiles, atf.b
        nt = at.shape[0]
        first = b_run * nt                # b's first tile of the run
        assert first + nt <= bt.shape[0], (b_run, nt, bt.shape)
        tab, pairs, K = _flat_mul_tab(tuple(b_idx))
        offs = self._flat_acc_offsets(K, pairs)
        kernel = functools.partial(
            self._flat_mul_kernel, tuple(b_idx), offs)
        spec = lambda l, first=0: pl.BlockSpec(
            (1, l, *_ROW), lambda i: (i + first, 0, 0, 0),
            memory_space=pltpu.VMEM)
        out = self._launch(
            kernel, (jnp.asarray(tab), at, bt), site=(first,),
            out_shape=jax.ShapeDtypeStruct((nt, 12 * N_LIMBS, *_ROW),
                                           jnp.int32),
            grid=(nt,),
            in_specs=[
                pl.BlockSpec(tab.shape, lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
                spec(12 * N_LIMBS), spec(J * N_LIMBS, first)],
            out_specs=spec(12 * N_LIMBS),
            scratch_shapes=[self._acc_scratch()],
        )
        if a_tiled:
            return TileForm(out, shape, n)
        return TileForm(out, shape, n).unwrap(
            ).reshape(shape + (12, N_LIMBS))

    def line_scaler(self, ps, active, shape, line_one):
        """Tile-resident `pairing._line_scaler`: the rows' P coordinates
        and masks cross into tile layout once, here; the returned
        function turns a step's table row [K, 6, 32] into the K masked
        sparse lines as TileForms in `flat_mul`'s packed layout.  The
        step's 4K Fp products are ONE launch of the `mont_mul` kernel,
        its operands joined along the tile (grid) axis; a table entry is
        the same for every row, so it is broadcast straight into tile
        layout, and no boundary is crossed inside the ladder."""
        K = len(ps)
        pt = [[self.tile(jnp.broadcast_to(c, shape + (N_LIMBS,)))
               for c in p] for p in ps]
        ref = pt[0][0]
        nt = ref.tiles.shape[0]
        rhs = jnp.concatenate([c.tiles for xp, yp in pt
                               for c in (xp, yp, xp, yp)], 0)
        masks = [m if m is None else self.mask_wrap(m, shape)[:, None]
                 for m in active]

        def lift(c):
            return jnp.broadcast_to(c.reshape(1, -1, 1, 1),
                                    (nt, c.size, *_ROW))
        one = lift(line_one)

        def lines(row):
            sc = self._call(
                self._mont_mul_kernel, N_LIMBS,
                jnp.concatenate([lift(row[k, s]) for k in range(K)
                                 for s in (1, 2, 4, 5)], 0), rhs)
            sc = sc.reshape(K, 4, nt, N_LIMBS, *_ROW)
            out = []
            for k, mask in enumerate(masks):
                b_lo, c_lo, b_y, c_y = sc[k]
                line = jnp.concatenate([lift(row[k, 0]), b_lo, c_lo,
                                        lift(row[k, 3]), b_y, c_y], 1)
                if mask is not None:
                    line = jnp.where(mask, line, one)
                out.append(TileForm(line, ref.shape, ref.b))
            return out
        return lines

    # -- fused Fermat-chain step: 4 squarings + one table multiply ---------
    #
    # pow_const's windowed scan body ran 5 kernel launches per step (4
    # mont_sqr + 1 mont_mul) with an HBM round-trip between each; the
    # Fermat chains (sqrt/inv in decompression, SSWU, affine conversion)
    # execute that body ~95 times per chain.

    def _sqr4_mul_kernel(self, r_ref, t_ref, o_ref):
        rows = [r_ref[0, l] for l in range(N_LIMBS)]
        z = jnp.zeros_like(rows[0])
        # The 4 inner squarings run LAZY (no conditional subtracts): with
        # canonical input, values stay in the < 1.4m band (c' = c^2*m/R + 1
        # converges), limbs stay exact-carried, and the final canonical
        # table multiply restores [0, m) — ~9% fewer VPU ops per chain
        # step for free.
        for _ in range(4):
            t = _carry_cheap_rows(_sqr_conv_rows(rows) + [z], 2)
            rows = self._mont_reduce_rows(t, canonical=False)
        t_rows = [t_ref[0, l] for l in range(N_LIMBS)]
        prod = _carry_cheap_rows(_conv_rows(rows, t_rows) + [z], 2)
        out = self._mont_reduce_rows(prod)
        for l in range(N_LIMBS):
            o_ref[0, l] = out[l]

    def sqr4_mul(self, res, t):
        """res^16 * t (Montgomery), the 4-bit-window exponentiation step."""
        if isinstance(res, TileForm) or isinstance(t, TileForm):
            res, t = self._tile_align((res, t), N_LIMBS)
            out = self._call(self._sqr4_mul_kernel, N_LIMBS,
                             res.tiles, t.tiles)
            return TileForm(out, res.shape, res.b)
        shape = jnp.broadcast_shapes(res.shape, t.shape)
        rt = TileForm.wrap(jnp.broadcast_to(res, shape))
        tt = TileForm.wrap(jnp.broadcast_to(t, shape))
        out = self._call(self._sqr4_mul_kernel, N_LIMBS, rt.tiles, tt.tiles)
        return TileForm(out, rt.shape, rt.b).unwrap()

    # -- fused addition-chain step: k squarings (+ optional multiply) ------
    #
    # The addition-chain exponentiation (field.addchain_plan, STATUS.md
    # headroom 1c) replaces pow_const's uniform 4-bit windows with
    # variable-length runs: each plan step is res^(2^k) or res^(2^k) * t.
    # This kernel runs the WHOLE step in VMEM — k lazy squarings (same
    # < 1.4m band as _sqr4_mul_kernel) and the canonical multiply — so a
    # chain step costs one launch like the window step it replaces.

    def _sqr_chain_mul_kernel(self, k, has_t, r_ref, *refs):
        o_ref = refs[-1]
        rows = [r_ref[0, l] for l in range(N_LIMBS)]
        z = jnp.zeros_like(rows[0])
        lazy = k if has_t else k - 1

        def one_sqr(rs, canonical):
            t = _carry_cheap_rows(_sqr_conv_rows(rs) + [z], 2)
            return self._mont_reduce_rows(t, canonical=canonical)

        if lazy > 8:
            # long zero-runs: loop in-kernel over a stacked carry instead
            # of unrolling (kernel size stays bounded)
            def body(_, st):
                rs = [st[l] for l in range(N_LIMBS)]
                return jnp.stack(one_sqr(rs, False))
            st = jax.lax.fori_loop(0, lazy, body, jnp.stack(rows))
            rows = [st[l] for l in range(N_LIMBS)]
        else:
            for _ in range(lazy):
                rows = one_sqr(rows, False)
        if has_t:
            t_rows = [refs[0][0, l] for l in range(N_LIMBS)]
            prod = _carry_cheap_rows(_conv_rows(rows, t_rows) + [z], 2)
            out = self._mont_reduce_rows(prod)
        else:
            out = one_sqr(rows, True)      # final squaring canonicalizes
        for l in range(N_LIMBS):
            o_ref[0, l] = out[l]

    def sqr_chain_mul(self, res, k: int, t=None):
        """res^(2^k) * t (canonical t multiply), or canonical res^(2^k)
        when t is None.  k >= 1 without t; k >= 0 with t."""
        if k == 0:
            assert t is not None
            return self.mont_mul(res, t)
        kernel = functools.partial(self._sqr_chain_mul_kernel, k,
                                   t is not None)
        if t is None:
            if isinstance(res, TileForm):
                out = self._call(kernel, N_LIMBS, res.tiles)
                return TileForm(out, res.shape, res.b)
            rt = TileForm.wrap(res)
            return TileForm(self._call(kernel, N_LIMBS, rt.tiles),
                            rt.shape, rt.b).unwrap()
        if isinstance(res, TileForm) or isinstance(t, TileForm):
            res, t = self._tile_align((res, t), N_LIMBS)
            out = self._call(kernel, N_LIMBS, res.tiles, t.tiles)
            return TileForm(out, res.shape, res.b)
        shape = jnp.broadcast_shapes(res.shape, t.shape)
        rt = TileForm.wrap(jnp.broadcast_to(res, shape))
        tt = TileForm.wrap(jnp.broadcast_to(t, shape))
        out = self._call(kernel, N_LIMBS, rt.tiles, tt.tiles)
        return TileForm(out, rt.shape, rt.b).unwrap()

    # -- fused Fp2 chain step: 5 lazy squarings + one canonical multiply --
    #
    # The direct Fp2 square roots (towers.fp2_pow_const: decompression
    # sqrt and the SSWU sqrt_ratio) scan this body ~152 times per ~758-bit
    # chain.  Values ride the lazy band (< 1.4m) through the squarings;
    # the table multiply's conditional subtracts restore canonical form
    # every step.

    def _fp2_sqr5_mul_kernel(self, off, r_ref, t_ref, o_ref):
        x = ([r_ref[0, l] for l in range(N_LIMBS)],
             [r_ref[0, N_LIMBS + l] for l in range(N_LIMBS)])
        for _ in range(5):
            x = self._fp2_sqr_rows(x, off, canonical=False)
        t = ([t_ref[0, l] for l in range(N_LIMBS)],
             [t_ref[0, N_LIMBS + l] for l in range(N_LIMBS)])
        out = self._fp2_mul_rows(x, t, off)
        for l in range(N_LIMBS):
            o_ref[0, l] = out[0][l]
            o_ref[0, N_LIMBS + l] = out[1][l]

    def _fp2_sqr_chain_mul_kernel(self, off, k, has_t, r_ref, *refs):
        o_ref = refs[-1]
        x = ([r_ref[0, l] for l in range(N_LIMBS)],
             [r_ref[0, N_LIMBS + l] for l in range(N_LIMBS)])
        lazy = k if has_t else k - 1
        if lazy > 8:
            def body(_, st):
                xx = ([st[l] for l in range(N_LIMBS)],
                      [st[N_LIMBS + l] for l in range(N_LIMBS)])
                out = self._fp2_sqr_rows(xx, off, canonical=False)
                return jnp.stack(list(out[0]) + list(out[1]))
            st = jax.lax.fori_loop(0, lazy, body,
                                   jnp.stack(list(x[0]) + list(x[1])))
            x = ([st[l] for l in range(N_LIMBS)],
                 [st[N_LIMBS + l] for l in range(N_LIMBS)])
        else:
            for _ in range(lazy):
                x = self._fp2_sqr_rows(x, off, canonical=False)
        if has_t:
            t = ([refs[0][0, l] for l in range(N_LIMBS)],
                 [refs[0][0, N_LIMBS + l] for l in range(N_LIMBS)])
            out = self._fp2_mul_rows(x, t, off)
        else:
            out = self._fp2_sqr_rows(x, off, canonical=True)
        for l in range(N_LIMBS):
            o_ref[0, l] = out[0][l]
            o_ref[0, N_LIMBS + l] = out[1][l]

    def fp2_sqr_chain_mul(self, res, k: int, t=None):
        """Fp2 addition-chain step: res^(2^k) * t, or canonical
        res^(2^k) when t is None — the variable-run generalization of
        fp2_sqr5_mul (same lazy band; the _WIDE_NEG_OFF_LAZY offsets are
        sized for the band's fixed point, so any k is safe)."""
        from drand_tpu.ops.towers import _WIDE_NEG_OFF_LAZY
        off = tuple(int(v) for v in _WIDE_NEG_OFF_LAZY)
        assert k >= 1, "k=0 steps never occur in addchain plans"
        kernel = functools.partial(self._fp2_sqr_chain_mul_kernel, off, k,
                                   t is not None)
        rt = self.fp2_pack(res)
        tiles = [rt.tiles]
        if t is not None:
            tt = self.fp2_pack(t)
            assert rt.shape == tt.shape, (rt.shape, tt.shape)
            tiles.append(tt.tiles)
        out = self._call(kernel, 2 * N_LIMBS, *tiles)
        tf = TileForm(out, rt.shape, rt.b)
        if isinstance(res, TileForm):
            return tf
        return self.fp2_unpack(tf)

    def fp2_sqr5_mul(self, res, t):
        """res^32 * t in Fp2 (packed 64-row layout / TileForm).  Uses the
        LAZY wide offset: the chain band's non-canonical values make the
        subtracted conv exceed the canonical offset's value (see
        towers._WIDE_NEG_OFF_LAZY)."""
        from drand_tpu.ops.towers import _WIDE_NEG_OFF_LAZY
        kernel = functools.partial(
            self._fp2_sqr5_mul_kernel,
            tuple(int(v) for v in _WIDE_NEG_OFF_LAZY))
        rt = self.fp2_pack(res)
        tt = self.fp2_pack(t)
        assert rt.shape == tt.shape, (rt.shape, tt.shape)
        out = self._call(kernel, 2 * N_LIMBS, rt.tiles, tt.tiles)
        tf = TileForm(out, rt.shape, rt.b)
        if isinstance(res, TileForm):
            return tf
        return self.fp2_unpack(tf)

    # -- fused Miller-loop step kernels ------------------------------------
    #
    # The Miller doubling/addition steps (pairing.py _dbl_step/_add_step)
    # are ~40% XLA carry/select glue around their product stacks; these
    # kernels run the complete step — products, small-scalar folds, line
    # coefficient scaling by P — in VMEM.  Formulas and bounds mirror the
    # XLA versions exactly (each product canonicalizes via mont reduce).

    def _read_coords(self, ref, n):
        return [[ref[0, c * N_LIMBS + l] for l in range(N_LIMBS)]
                for c in range(n)]

    def _write_coords(self, ref, coords):
        for c, rows in enumerate(coords):
            for l in range(N_LIMBS):
                ref[0, c * N_LIMBS + l] = rows[l]

    @staticmethod
    def _stack3(*items):
        """Row lists -> one stacked row list (fresh leading axis)."""
        return [jnp.stack(rs, 0) for rs in zip(*items)]

    @staticmethod
    def _unstk(rows, i):
        return [r[i] for r in rows]

    def _line_flat_rows(self, line):
        """`pairing.line_to_flat` on row lists: the Fp2 triple's six
        sparse flat slots, `lo = re - im` of a, b, c (one stacked
        subtraction), then the three `im`."""
        a_l, b_l, c_l = line
        los = self._sub_rows(self._stack3(a_l[0], b_l[0], c_l[0]),
                             self._stack3(a_l[1], b_l[1], c_l[1]))
        return [self._unstk(los, i) for i in range(3)] + \
            [a_l[1], b_l[1], c_l[1]]

    def _write_masked_line(self, lo_ref, mask, line):
        """The step's line as `flat_mul`'s sparse packed operand (slot s
        at limb rows [s*32, (s+1)*32)), the neutral line (1, 0, ..., 0)
        where `mask` is false."""
        for s, rows in enumerate(self._line_flat_rows(line)):
            for l in range(N_LIMBS):
                neutral = int(self.ONE_MONT[l]) if s == 0 else 0
                lo_ref[0, s * N_LIMBS + l] = jnp.where(
                    mask, rows[l], jnp.full(_ROW, neutral, jnp.int32))

    def _g2_dbl_line_kernel(self, off, t_ref, p_ref, m_ref, to_ref, lo_ref):
        c = self._read_coords(t_ref, 6)
        xp, yp = self._read_coords(p_ref, 2)
        T2, line = self._g2_dbl_line_rows(
            off, (c[0], c[1]), (c[2], c[3]), (c[4], c[5]), xp, yp)
        self._write_coords(to_ref, [r for coord in T2 for r in coord])
        self._write_masked_line(lo_ref, m_ref[0, 0] != 0, line)

    def _g2_dbl_line_rows(self, off, X, Y, Z, xp, yp):
        """The complete Miller doubling-step body on Fp2 row pairs —
        shared verbatim by the standalone kernel and the merged
        Miller-iteration kernel so both are bit-identical by
        construction.  Returns ((X2, Y2, Z2), (a, b, c))."""
        st = self._stack3
        un = self._unstk
        # XX, YY, ZZ in one stacked square; YZ separately
        sq = self._fp2_sqr_rows((st(X[0], Y[0], Z[0]),
                                 st(X[1], Y[1], Z[1])), off)
        XX = (un(sq[0], 0), un(sq[1], 0))
        YY = (un(sq[0], 1), un(sq[1], 1))
        ZZ = (un(sq[0], 2), un(sq[1], 2))
        YZ = self._fp2_mul_rows(Y, Z, off)
        xyy = self._fp2_add_rows(X, YY)
        E = (self._mul_small_rows(XX[0], 3), self._mul_small_rows(XX[1], 3))
        # X3c = XX*X, YZ3 = YZ*ZZ, XXZZ = XX*ZZ (stacked general products)
        mu = self._fp2_mul_rows(
            (st(XX[0], YZ[0], XX[0]), st(XX[1], YZ[1], XX[1])),
            (st(X[0], ZZ[0], ZZ[0]), st(X[1], ZZ[1], ZZ[1])), off)
        X3c = (un(mu[0], 0), un(mu[1], 0))
        YZ3 = (un(mu[0], 1), un(mu[1], 1))
        XXZZ = (un(mu[0], 2), un(mu[1], 2))
        # C = YY^2, S2 = xyy^2, F_ = E^2 (stacked squares)
        sq2 = self._fp2_sqr_rows((st(YY[0], xyy[0], E[0]),
                                  st(YY[1], xyy[1], E[1])), off)
        C = (un(sq2[0], 0), un(sq2[1], 0))
        S2 = (un(sq2[0], 1), un(sq2[1], 1))
        F_ = (un(sq2[0], 2), un(sq2[1], 2))
        a_l = self._fp2_sub_rows(
            (self._mul_small_rows(X3c[0], 3), self._mul_small_rows(X3c[1], 3)),
            (self._mul_small_rows(YY[0], 2), self._mul_small_rows(YY[1], 2)))
        nb3 = (self._neg_rows(self._mul_small_rows(XXZZ[0], 3)),
               self._neg_rows(self._mul_small_rows(XXZZ[1], 3)))
        cc2 = (self._add_rows(YZ3[0], YZ3[0]), self._add_rows(YZ3[1], YZ3[1]))
        # line b, c = coefficients scaled by P's Fp coordinates
        sc = self._fp_mul_rows(st(nb3[0], nb3[1], cc2[0], cc2[1]),
                               st(xp, xp, yp, yp))
        # dbl-2009-l
        D = self._fp2_sub_rows(S2, self._fp2_add_rows(XX, C))
        D = self._fp2_add_rows(D, D)
        X2 = self._fp2_sub_rows(F_, self._fp2_add_rows(D, D))
        Et = self._fp2_mul_rows(E, self._fp2_sub_rows(D, X2), off)
        Y2 = self._fp2_sub_rows(
            Et, (self._mul_small_rows(C[0], 8), self._mul_small_rows(C[1], 8)))
        Z2 = self._fp2_add_rows(YZ, YZ)
        return ((X2, Y2, Z2),
                (a_l, (un(sc, 0), un(sc, 1)), (un(sc, 2), un(sc, 3))))

    def _g2_add_line_kernel(self, off, t_ref, q_ref, p_ref, m_ref, to_ref,
                            lo_ref):
        c = self._read_coords(t_ref, 6)
        q = self._read_coords(q_ref, 4)
        xp, yp = self._read_coords(p_ref, 2)
        T3, line = self._g2_add_line_rows(
            off, (c[0], c[1]), (c[2], c[3]), (c[4], c[5]),
            (q[0], q[1]), (q[2], q[3]), xp, yp)
        mask = m_ref[0, 0] != 0
        # an inactive row keeps its T (the XLA path's fp2_select)
        self._write_coords(to_ref, [
            _select_rows(mask, new, old)
            for new, old in zip((r for coord in T3 for r in coord), c)])
        self._write_masked_line(lo_ref, mask, line)

    def _g2_add_line_rows(self, off, X, Y, Z, xq, yq, xp, yp):
        """Miller mixed-addition step body on Fp2 row pairs (shared by
        the standalone and merged kernels).  Returns
        ((X3, Y3, Z3), (a, b, c))."""
        st = self._stack3
        un = self._unstk
        ZZ = self._fp2_sqr_rows(Z, off)
        yqZ = self._fp2_mul_rows(yq, Z, off)
        # U2 = xq*ZZ, S2 = yqZ*ZZ
        m1 = self._fp2_mul_rows((st(xq[0], yqZ[0]), st(xq[1], yqZ[1])),
                                (st(ZZ[0], ZZ[0]), st(ZZ[1], ZZ[1])), off)
        U2 = (un(m1[0], 0), un(m1[1], 0))
        S2 = (un(m1[0], 1), un(m1[1], 1))
        H = self._fp2_sub_rows(U2, X)
        Sy = self._fp2_sub_rows(S2, Y)
        r = (self._mul_small_rows(Sy[0], 2), self._mul_small_rows(Sy[1], 2))
        ZH = self._fp2_add_rows(Z, H)
        # HH = H^2, rr = r^2, ZH2 = ZH^2 stacked; HZ = H*Z
        sq = self._fp2_sqr_rows((st(H[0], r[0], ZH[0]),
                                 st(H[1], r[1], ZH[1])), off)
        HH = (un(sq[0], 0), un(sq[1], 0))
        rr = (un(sq[0], 1), un(sq[1], 1))
        ZH2 = (un(sq[0], 2), un(sq[1], 2))
        HZ = self._fp2_mul_rows(H, Z, off)
        I = (self._mul_small_rows(HH[0], 4), self._mul_small_rows(HH[1], 4))
        HZ2 = (self._add_rows(HZ[0], HZ[0]), self._add_rows(HZ[1], HZ[1]))
        # J = H*I, V = X*I, rxq = r*xq, hzyq = HZ2*yq
        m2 = self._fp2_mul_rows(
            (st(H[0], X[0], r[0], HZ2[0]), st(H[1], X[1], r[1], HZ2[1])),
            (st(I[0], I[0], xq[0], yq[0]), st(I[1], I[1], xq[1], yq[1])), off)
        J = (un(m2[0], 0), un(m2[1], 0))
        V = (un(m2[0], 1), un(m2[1], 1))
        rxq = (un(m2[0], 2), un(m2[1], 2))
        hzyq = (un(m2[0], 3), un(m2[1], 3))
        X3 = self._fp2_sub_rows(
            self._fp2_sub_rows(rr, J),
            (self._mul_small_rows(V[0], 2), self._mul_small_rows(V[1], 2)))
        # rV = r*(V - X3), YJ = Y*J
        VX = self._fp2_sub_rows(V, X3)
        m3 = self._fp2_mul_rows((st(r[0], Y[0]), st(r[1], Y[1])),
                                (st(VX[0], J[0]), st(VX[1], J[1])), off)
        rV = (un(m3[0], 0), un(m3[1], 0))
        YJ = (un(m3[0], 1), un(m3[1], 1))
        Y3 = self._fp2_sub_rows(
            rV, (self._mul_small_rows(YJ[0], 2),
                 self._mul_small_rows(YJ[1], 2)))
        Z3 = self._fp2_sub_rows(ZH2, self._fp2_add_rows(ZZ, HH))
        a_l = self._fp2_sub_rows(rxq, hzyq)
        nr = (self._neg_rows(r[0]), self._neg_rows(r[1]))
        sc = self._fp_mul_rows(st(nr[0], nr[1], HZ2[0], HZ2[1]),
                               st(xp, xp, yp, yp))
        return ((X3, Y3, Z3),
                (a_l, (un(sc, 0), un(sc, 1)), (un(sc, 2), un(sc, 3))))

    def pack_coords(self, coords) -> TileForm:
        """List of [..., 32] coord arrays -> ONE packed TileForm (single
        entry crossing).  The packed-point/packed-line layout every fused
        curve/pairing kernel reads: coord c occupies limb rows
        [c*32, (c+1)*32)."""
        shape = jnp.broadcast_shapes(*(c.shape[:-1] for c in coords))
        coords = [jnp.broadcast_to(c, shape + (N_LIMBS,)).astype(jnp.int32)
                  for c in coords]
        return TileForm.wrap(jnp.concatenate(coords, axis=-1),
                             len(coords) * N_LIMBS)

    def unpack_coords(self, tf: TileForm, n: int):
        """Packed TileForm -> list of n [..., 32] coord arrays (single
        exit crossing)."""
        flat = tf.unwrap().reshape(tf.shape + (n, N_LIMBS))
        return [flat[..., i, :] for i in range(n)]

    def _coords_call(self, kernel, coords, n_out):
        """Broadcast a list of [..., 32] coords to one batch shape, pack
        along the limb axis, run the kernel, split n_out coords back."""
        at = self.pack_coords(coords)
        out = self._call(kernel, n_out * N_LIMBS, at.tiles)
        return self.unpack_coords(TileForm(out, at.shape, at.b), n_out)

    def _line_step(self, kernel, mask, T, *fixed):
        """One Miller step kernel on packed state: T (6 coords) and the
        ladder's fixed points as separate tile operands, `mask` the
        rows' `active` as int32 [tiles, 1, 8, 128]; two outputs, T' and
        the masked line in `flat_mul`'s sparse packed layout."""
        from drand_tpu.ops.towers import _WIDE_NEG_OFF
        t_out, line = self._call(
            functools.partial(kernel, tuple(int(v) for v in _WIDE_NEG_OFF)),
            [6 * N_LIMBS] * 2, T.tiles, *(x.tiles for x in fixed), mask)
        return TileForm(t_out, T.shape, T.b), TileForm(line, T.shape, T.b)

    def g2_dbl_line(self, T: TileForm, P: TileForm, mask):
        """Fused Miller doubling step on packed state: Jacobian T (Fp2,
        6 coords) and P (xp, yp) -> (T', line), `pairing._dbl_step` with
        `line_to_flat` and the neutral line of an inactive row done in
        the kernel."""
        return self._line_step(self._g2_dbl_line_kernel, mask, T, P)

    def g2_add_line(self, T: TileForm, Q: TileForm, P: TileForm, mask):
        """Fused Miller mixed-addition step on packed state
        (`pairing._add_step`; Q = (xq, yq), 4 coords): as `g2_dbl_line`,
        and an inactive row keeps its T."""
        return self._line_step(self._g2_add_line_kernel, mask, T, Q, P)

    # -- fused G2 Jacobian point kernels (ladder bodies) -------------------
    #
    # The cofactor-clearing and subgroup-check ladders scan point_double /
    # point_add bodies 63+ times per verify; these kernels run the full
    # formulas (including the branchless infinity/cancel case handling of
    # curve.point_add) in VMEM.

    def _rows_is_zero(self, rows):
        m = rows[0] == 0
        for r in rows[1:]:
            m = m & (r == 0)
        return m

    def _rows_eq(self, a_rows, b_rows):
        m = a_rows[0] == b_rows[0]
        for a, b in zip(a_rows[1:], b_rows[1:]):
            m = m & (a == b)
        return m

    def _const_rows(self, limbs, like):
        return [jnp.full_like(like, int(v)) for v in limbs]

    def _g2_dbl_rows(self, X, Y, Z, off):
        """dbl-2009-l body on Fp2 row pairs (mirrors curve.point_double)."""
        st = self._stack3
        un = self._unstk
        sq = self._fp2_sqr_rows((st(X[0], Y[0]), st(X[1], Y[1])), off)
        A = (un(sq[0], 0), un(sq[1], 0))          # X^2
        B = (un(sq[0], 1), un(sq[1], 1))          # Y^2
        YZ = self._fp2_mul_rows(Y, Z, off)
        xb = self._fp2_add_rows(X, B)
        sq2 = self._fp2_sqr_rows((st(B[0], xb[0]), st(B[1], xb[1])), off)
        C = (un(sq2[0], 0), un(sq2[1], 0))        # B^2
        S2 = (un(sq2[0], 1), un(sq2[1], 1))       # (X+B)^2
        E = (self._mul_small_rows(A[0], 3), self._mul_small_rows(A[1], 3))
        D = self._fp2_sub_rows(S2, self._fp2_add_rows(A, C))
        D = self._fp2_add_rows(D, D)
        F_ = self._fp2_sqr_rows(E, off)
        X3 = self._fp2_sub_rows(F_, self._fp2_add_rows(D, D))
        Et = self._fp2_mul_rows(E, self._fp2_sub_rows(D, X3), off)
        Y3 = self._fp2_sub_rows(
            Et, (self._mul_small_rows(C[0], 8), self._mul_small_rows(C[1], 8)))
        Z3 = self._fp2_add_rows(YZ, YZ)
        return X3, Y3, Z3

    def _g2_point_dbl_kernel(self, off, a_ref, o_ref):
        c = self._read_coords(a_ref, 6)
        X3, Y3, Z3 = self._g2_dbl_rows((c[0], c[1]), (c[2], c[3]),
                                       (c[4], c[5]), off)
        self._write_coords(o_ref, [X3[0], X3[1], Y3[0], Y3[1],
                                   Z3[0], Z3[1]])

    def _g2_point_add_kernel(self, off, with_double, a_ref, o_ref):
        c = self._read_coords(a_ref, 12)
        X1 = (c[0], c[1]); Y1 = (c[2], c[3]); Z1 = (c[4], c[5])
        X2 = (c[6], c[7]); Y2 = (c[8], c[9]); Z2 = (c[10], c[11])
        st = self._stack3
        un = self._unstk
        sq = self._fp2_sqr_rows((st(Z1[0], Z2[0]), st(Z1[1], Z2[1])), off)
        z1z1 = (un(sq[0], 0), un(sq[1], 0))
        z2z2 = (un(sq[0], 1), un(sq[1], 1))
        m1 = self._fp2_mul_rows(
            (st(Y1[0], Y2[0]), st(Y1[1], Y2[1])),
            (st(Z2[0], Z1[0]), st(Z2[1], Z1[1])), off)
        y1z2 = (un(m1[0], 0), un(m1[1], 0))
        y2z1 = (un(m1[0], 1), un(m1[1], 1))
        m2 = self._fp2_mul_rows(
            (st(X1[0], X2[0], y1z2[0], y2z1[0]),
             st(X1[1], X2[1], y1z2[1], y2z1[1])),
            (st(z2z2[0], z1z1[0], z2z2[0], z1z1[0]),
             st(z2z2[1], z1z1[1], z2z2[1], z1z1[1])), off)
        u1 = (un(m2[0], 0), un(m2[1], 0))
        u2 = (un(m2[0], 1), un(m2[1], 1))
        s1 = (un(m2[0], 2), un(m2[1], 2))
        s2 = (un(m2[0], 3), un(m2[1], 3))
        h = self._fp2_sub_rows(u2, u1)
        h2 = self._fp2_add_rows(h, h)
        rr = self._fp2_sub_rows(s2, s1)
        rr = self._fp2_add_rows(rr, rr)
        z12 = self._fp2_add_rows(Z1, Z2)
        sq2 = self._fp2_sqr_rows((st(h2[0], rr[0], z12[0]),
                                  st(h2[1], rr[1], z12[1])), off)
        i = (un(sq2[0], 0), un(sq2[1], 0))
        rr2 = (un(sq2[0], 1), un(sq2[1], 1))
        z12sq = (un(sq2[0], 2), un(sq2[1], 2))
        m3 = self._fp2_mul_rows((st(h[0], u1[0]), st(h[1], u1[1])),
                                (st(i[0], i[0]), st(i[1], i[1])), off)
        j = (un(m3[0], 0), un(m3[1], 0))
        v = (un(m3[0], 1), un(m3[1], 1))
        X3 = self._fp2_sub_rows(self._fp2_sub_rows(rr2, j),
                                self._fp2_add_rows(v, v))
        zz = self._fp2_sub_rows(z12sq, self._fp2_add_rows(z1z1, z2z2))
        vx = self._fp2_sub_rows(v, X3)
        m4 = self._fp2_mul_rows(
            (st(rr[0], s1[0], zz[0]), st(rr[1], s1[1], zz[1])),
            (st(vx[0], j[0], h[0]), st(vx[1], j[1], h[1])), off)
        y3t = (un(m4[0], 0), un(m4[1], 0))
        s1j = (un(m4[0], 1), un(m4[1], 1))
        Z3 = (un(m4[0], 2), un(m4[1], 2))
        Y3 = self._fp2_sub_rows(y3t, self._fp2_add_rows(s1j, s1j))
        out = [X3, Y3, Z3]

        inf1 = self._rows_is_zero(Z1[0]) & self._rows_is_zero(Z1[1])
        inf2 = self._rows_is_zero(Z2[0]) & self._rows_is_zero(Z2[1])
        eq_u = (self._rows_eq(u1[0], u2[0]) & self._rows_eq(u1[1], u2[1])
                & ~inf1 & ~inf2)
        eq_s = self._rows_eq(s1[0], s2[0]) & self._rows_eq(s1[1], s2[1])
        sel2 = lambda m, a, b: (_select_rows(m, a[0], b[0]),
                                _select_rows(m, a[1], b[1]))
        if with_double:
            dbl = self._g2_dbl_rows(X1, Y1, Z1, off)
            out = [sel2(eq_u & eq_s, d, o) for d, o in zip(dbl, out)]
        # P + (-P): infinity (X = Y = 1 in Montgomery form, Z = 0)
        one = self._const_rows(self.ONE_MONT, X3[0][0])
        zero = [jnp.zeros_like(X3[0][0])] * N_LIMBS
        inf_pt = [(one, zero), (one, zero), (zero, zero)]
        cancel = eq_u & ~eq_s
        out = [sel2(cancel, ip, o) for ip, o in zip(inf_pt, out)]
        p2 = [X2, Y2, Z2]
        p1 = [X1, Y1, Z1]
        out = [sel2(inf1, b, o) for b, o in zip(p2, out)]
        out = [sel2(inf2 & ~inf1, a, o) for a, o in zip(p1, out)]
        self._write_coords(o_ref, [out[0][0], out[0][1], out[1][0],
                                   out[1][1], out[2][0], out[2][1]])

    def g2_pack_point(self, pt) -> TileForm:
        """Fp2 Jacobian point tuple -> packed 6-coord TileForm (one entry
        crossing; no-op when already packed)."""
        if isinstance(pt, TileForm):
            return pt
        X, Y, Z = pt
        return self.pack_coords([X[0], X[1], Y[0], Y[1], Z[0], Z[1]])

    def g2_unpack_point(self, tf):
        """Inverse of g2_pack_point (no-op on point tuples)."""
        if not isinstance(tf, TileForm):
            return tf
        o = self.unpack_coords(tf, 6)
        return ((o[0], o[1]), (o[2], o[3]), (o[4], o[5]))

    def g2_point_dbl(self, pt):
        """Fused curve.point_double for Fp2 Jacobian points.  A packed
        TileForm point stays packed (the ladder-resident form: the
        cofactor/subgroup scans thread it with zero per-step relayout)."""
        from drand_tpu.ops.towers import _WIDE_NEG_OFF
        kernel = functools.partial(
            self._g2_point_dbl_kernel, tuple(int(v) for v in _WIDE_NEG_OFF))
        if isinstance(pt, TileForm):
            out = self._call(kernel, 6 * N_LIMBS, pt.tiles)
            return TileForm(out, pt.shape, pt.b)
        X, Y, Z = pt
        o = self._coords_call(
            kernel, [X[0], X[1], Y[0], Y[1], Z[0], Z[1]], 6)
        return ((o[0], o[1]), (o[2], o[3]), (o[4], o[5]))

    def g2_point_add(self, p1, p2, with_double: bool):
        """Fused curve.point_add for Fp2 Jacobian points (full branchless
        case handling).  Packed TileForm operands stay packed — the two
        points combine via tile_concat (layout-preserving)."""
        from drand_tpu.ops.towers import _WIDE_NEG_OFF
        kernel = functools.partial(
            self._g2_point_add_kernel, tuple(int(v) for v in _WIDE_NEG_OFF),
            with_double)
        if isinstance(p1, TileForm) or isinstance(p2, TileForm):
            a = self.g2_pack_point(p1)
            b = self.g2_pack_point(p2)
            at = tile_concat([a, b])
            out = self._call(kernel, 6 * N_LIMBS, at.tiles)
            return TileForm(out, at.shape, at.b)
        coords = []
        for p in (p1, p2):
            for cpt in p:
                coords.extend([cpt[0], cpt[1]])
        o = self._coords_call(kernel, coords, 6)
        return ((o[0], o[1]), (o[2], o[3]), (o[4], o[5]))

    # -- fused G1 Jacobian point kernels (ladder bodies) -------------------
    #
    # The G1 twins of the kernels above (ISSUE 46): the short-signature
    # program holds three 64-bit ladders over Fp (`g1_in_subgroup`'s two,
    # `hash_to_g1`'s cofactor clearing) whose generic step was four
    # `mont_mul` launches, each with its own relayout both ways, and
    # eleven XLA carry chains between them.  Same formulas, same
    # canonical coordinates between products, so the outputs are the XLA
    # forms' to the limb; 7 Montgomery reductions a doubling, 16 an
    # addition (23 with the doubling fall-back).

    def _g1_dbl_rows(self, X, Y, Z):
        """dbl-2009-l body on Fp rows (mirrors curve.point_double)."""
        st = self._stack3
        un = self._unstk
        sq = self._fp_sqr_rows(st(X, Y))
        A, B = un(sq, 0), un(sq, 1)               # X^2, Y^2
        YZ = self._fp_mul_rows(Y, Z)
        sq2 = self._fp_sqr_rows(st(B, self._add_rows(X, B)))
        C, S2 = un(sq2, 0), un(sq2, 1)            # B^2, (X+B)^2
        E = self._mul_small_rows(A, 3)
        D = self._sub_rows(S2, self._add_rows(A, C))
        D = self._add_rows(D, D)
        X3 = self._sub_rows(self._fp_sqr_rows(E), self._add_rows(D, D))
        Et = self._fp_mul_rows(E, self._sub_rows(D, X3))
        Y3 = self._sub_rows(Et, self._mul_small_rows(C, 8))
        Z3 = self._add_rows(YZ, YZ)
        return X3, Y3, Z3

    def _g1_point_dbl_kernel(self, a_ref, o_ref):
        self._write_coords(
            o_ref, self._g1_dbl_rows(*self._read_coords(a_ref, 3)))

    def _g1_point_add_kernel(self, with_double, a_ref, o_ref):
        X1, Y1, Z1, X2, Y2, Z2 = self._read_coords(a_ref, 6)
        st = self._stack3
        un = self._unstk
        sq = self._fp_sqr_rows(st(Z1, Z2))
        z1z1, z2z2 = un(sq, 0), un(sq, 1)
        m1 = self._fp_mul_rows(st(Y1, Y2), st(Z2, Z1))
        m2 = self._fp_mul_rows(st(X1, X2, un(m1, 0), un(m1, 1)),
                               st(z2z2, z1z1, z2z2, z1z1))
        u1, u2, s1, s2 = (un(m2, i) for i in range(4))
        h = self._sub_rows(u2, u1)
        rr = self._sub_rows(s2, s1)
        rr = self._add_rows(rr, rr)
        sq2 = self._fp_sqr_rows(st(self._add_rows(h, h), rr,
                                   self._add_rows(Z1, Z2)))
        i, rr2, z12sq = (un(sq2, k) for k in range(3))
        m3 = self._fp_mul_rows(st(h, u1), st(i, i))
        j, v = un(m3, 0), un(m3, 1)
        X3 = self._sub_rows(self._sub_rows(rr2, j), self._add_rows(v, v))
        zz = self._sub_rows(z12sq, self._add_rows(z1z1, z2z2))
        m4 = self._fp_mul_rows(st(rr, s1, zz),
                               st(self._sub_rows(v, X3), j, h))
        s1j = un(m4, 1)
        Y3 = self._sub_rows(un(m4, 0), self._add_rows(s1j, s1j))
        out = [X3, Y3, un(m4, 2)]

        inf1 = self._rows_is_zero(Z1)
        inf2 = self._rows_is_zero(Z2)
        eq_u = self._rows_eq(u1, u2) & ~inf1 & ~inf2
        eq_s = self._rows_eq(s1, s2)
        if with_double:
            dbl = self._g1_dbl_rows(X1, Y1, Z1)
            out = [_select_rows(eq_u & eq_s, d, o)
                   for d, o in zip(dbl, out)]
        # P + (-P): infinity (X = Y = 1 in Montgomery form, Z = 0)
        one = self._const_rows(self.ONE_MONT, X3[0])
        zero = [jnp.zeros_like(X3[0])] * N_LIMBS
        cancel = eq_u & ~eq_s
        out = [_select_rows(cancel, ip, o)
               for ip, o in zip((one, one, zero), out)]
        out = [_select_rows(inf1, b, o) for b, o in zip((X2, Y2, Z2), out)]
        out = [_select_rows(inf2 & ~inf1, a, o)
               for a, o in zip((X1, Y1, Z1), out)]
        self._write_coords(o_ref, out)

    def g1_pack_point(self, pt) -> TileForm:
        """Fp Jacobian point tuple -> packed 3-coord TileForm (one entry
        crossing; no-op when already packed)."""
        if isinstance(pt, TileForm):
            return pt
        return self.pack_coords(list(pt))

    def g1_unpack_point(self, tf):
        """Inverse of g1_pack_point (no-op on point tuples)."""
        if not isinstance(tf, TileForm):
            return tf
        return tuple(self.unpack_coords(tf, 3))

    def g1_point_dbl(self, pt):
        """Fused curve.point_double for Fp Jacobian points; a packed
        TileForm point stays packed, as in `g2_point_dbl`."""
        if isinstance(pt, TileForm):
            out = self._call(self._g1_point_dbl_kernel, 3 * N_LIMBS,
                             pt.tiles)
            return TileForm(out, pt.shape, pt.b)
        return tuple(self._coords_call(self._g1_point_dbl_kernel,
                                       list(pt), 3))

    def g1_point_add(self, p1, p2, with_double: bool):
        """Fused curve.point_add for Fp Jacobian points (full branchless
        case handling); packed operands stay packed, as in
        `g2_point_add`."""
        kernel = functools.partial(self._g1_point_add_kernel, with_double)
        if isinstance(p1, TileForm) or isinstance(p2, TileForm):
            at = tile_concat([self.g1_pack_point(p1),
                              self.g1_pack_point(p2)])
            out = self._call(kernel, 3 * N_LIMBS, at.tiles)
            return TileForm(out, at.shape, at.b)
        return tuple(self._coords_call(kernel, [*p1, *p2], 3))

    # -- fused flat-Fp12 SQUARE --------------------------------------------
    #
    # flat_mul(a, a) burns 144 generic slot convolutions; squaring is
    # symmetric in the slot pairs, so conv coefficient k needs only the
    # pairs i < k-i (doubled once) plus a triangular self-conv on the
    # diagonal — 66 general + 12 triangular convs, ~55% of the MACs.  The
    # Miller loop squares the accumulator every iteration (63x/verify).

    def _flat_sqr_kernel(self, offs, tab_ref, a_ref, o_ref, acc_ref):
        """tab_ref (SMEM): the compact pair table of _flat_sqr_tab."""
        self._sqr_phase(
            acc_ref, tab_ref,
            lambda i: a_ref[0, pl.ds(i * N_LIMBS, N_LIMBS)], offs)
        self._acc_reduce_out(acc_ref, o_ref)

    def flat_sqr(self, a):
        """Drop-in for flat12.flat_sqr: a [..., 12, 32] or a TileForm in
        the 12*32 packed row layout (output kind follows the input)."""
        K = 23
        a_tiled = isinstance(a, TileForm)
        if a_tiled:
            at, shape, n = a.tiles, a.shape, a.b
        else:
            shape = a.shape[:-2]
            atf = TileForm.wrap(a.reshape(shape + (12 * N_LIMBS,)),
                                12 * N_LIMBS)
            at, n = atf.tiles, atf.b
        nt = at.shape[0]
        # value bound per conv k: 2*pairs + diag slot-products
        tab, pairs = _flat_sqr_tab()
        offs = self._flat_acc_offsets(K, pairs)
        kernel = functools.partial(self._flat_sqr_kernel, offs)
        spec = lambda l: pl.BlockSpec((1, l, *_ROW), lambda i: (i, 0, 0, 0),
                                      memory_space=pltpu.VMEM)
        out = self._launch(
            kernel, (jnp.asarray(tab), at),
            out_shape=jax.ShapeDtypeStruct((nt, 12 * N_LIMBS, *_ROW),
                                           jnp.int32),
            grid=(nt,),
            in_specs=[
                pl.BlockSpec(tab.shape, lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
                spec(12 * N_LIMBS)],
            out_specs=spec(12 * N_LIMBS),
            scratch_shapes=[self._acc_scratch()],
        )
        if a_tiled:
            return TileForm(out, shape, n)
        return TileForm(out, shape, n).unwrap(
            ).reshape(shape + (12, N_LIMBS))

    # -- packed flat-Fp12 conjugation / Frobenius --------------------------
    #
    # flat_conj and flat_frob are the only final-exponentiation steps that
    # were XLA glue on plain arrays; packed twins keep the whole
    # final_exp tile-resident (the x-power chains and flat multiplies
    # already are).  Values are bit-identical to the XLA forms: _neg_rows
    # computes the same canonical (-a) mod m as FP.neg, and the Frobenius
    # constants are the same Montgomery limb tables.

    def _flat_conj_kernel(self, a_ref, o_ref):
        for s in range(12):
            rows = [a_ref[0, s * N_LIMBS + l] for l in range(N_LIMBS)]
            if s % 2:
                rows = self._neg_rows(rows)
            for l in range(N_LIMBS):
                o_ref[0, s * N_LIMBS + l] = rows[l]

    def flat_conj(self, a: TileForm) -> TileForm:
        """f^(p^6) on a packed flat element: negate the odd w-powers."""
        out = self._call(self._flat_conj_kernel, 12 * N_LIMBS, a.tiles)
        return TileForm(out, a.shape, a.b)

    def _flat_frob_kernel(self, consts, a_ref, o_ref):
        z = jnp.zeros(_ROW, jnp.int32)

        def cmul(rows, c):
            cols = _mul_const_rows(rows, c, 2 * N_LIMBS - 1) + [z]
            return self._mont_reduce_rows(_carry_cheap_rows(cols, 2))

        for s in range(6):
            lo = [a_ref[0, s * N_LIMBS + l] for l in range(N_LIMBS)]
            hi = [a_ref[0, (s + 6) * N_LIMBS + l] for l in range(N_LIMBS)]
            A, B, C, D = consts[s]
            out_lo = self._add_rows(cmul(lo, A), cmul(hi, B))
            out_hi = self._add_rows(cmul(lo, C), cmul(hi, D))
            for l in range(N_LIMBS):
                o_ref[0, s * N_LIMBS + l] = out_lo[l]
                o_ref[0, (s + 6) * N_LIMBS + l] = out_hi[l]

    def flat_frob(self, a: TileForm, n: int) -> TileForm:
        """a^(p^n) (n in 1..3) on a packed flat element: the block-
        diagonal per-slot-pair 2x2 constant multiply of flat12.flat_frob
        as one kernel (the constants are static, so each product is a
        Toeplitz constant multiply)."""
        from drand_tpu.ops.flat12 import _FROB
        A, B, C, D = (np.asarray(x) for x in _FROB[n])
        consts = tuple(
            (tuple(int(v) for v in A[s]), tuple(int(v) for v in B[s]),
             tuple(int(v) for v in C[s]), tuple(int(v) for v in D[s]))
            for s in range(6))
        kernel = functools.partial(self._flat_frob_kernel, consts)
        out = self._call(kernel, 12 * N_LIMBS, a.tiles)
        return TileForm(out, a.shape, a.b)

    # -- sparse-sparse line merge ------------------------------------------
    #
    # The Miller loop multiplies f by TWO sparse lines per iteration
    # (12x6 product stacks, 72 slot convs each).  Merging the lines first
    # costs 36 sparse convs and makes the second f multiply dense
    # (144 convs) — more raw conv MACs (180 vs 144), but ONE full walk of
    # the 12-slot accumulator pipeline instead of two: one scatter/carry/
    # reduce pass over f and one fewer 13x64-row accumulator cycle.
    # Round 4 argued the op-count against it in the launch-per-op
    # setting; inside the merged iteration kernel the trade is memory-
    # traffic-vs-MACs and only a device A/B settles it — warm_r9 measures
    # both (DRAND_TPU_LINE_MERGE), and both paths are bit-identical to
    # the sequential multiplies (field associativity + canonical
    # Montgomery uniqueness), pinned by the sim KATs.

    def _line_merge_phase(self, acc_ref, read1, read2, write, offs):
        """Statically-unrolled sparse line product: read1/read2 yield the
        6 flat groups of each line; canonical merged slots go to
        `write(slot, rows)` (all reads precede the first write)."""
        pairs_by_k, scatter, _ = _line_merge_tables()
        z = jnp.zeros(_ROW, jnp.int32)
        self._acc_init(acc_ref, offs)
        for k, kp in enumerate(pairs_by_k):
            acc = None
            for (i, j) in kp:
                aa = read1(i)
                bb = read2(j)
                cols = _conv_rows([aa[l] for l in range(N_LIMBS)],
                                  [bb[l] for l in range(N_LIMBS)]) + [z]
                c = jnp.stack(_carry_cheap_rows(cols, 2), 0)
                acc = c if acc is None else acc + c
            if acc is None:
                continue
            for slot, coeff in scatter[k]:
                s = pl.ds(slot * 2 * N_LIMBS, 2 * N_LIMBS)
                acc_ref[s] = acc_ref[s] + coeff * acc
        self._acc_reduce_write(acc_ref, write)

    def _line_merge_kernel(self, offs, a_ref, o_ref, acc_ref):
        def write(jp, r):
            for l in range(N_LIMBS):
                o_ref[0, jp * N_LIMBS + l] = r[l]

        self._line_merge_phase(
            acc_ref,
            lambda i: a_ref[0, pl.ds(i * N_LIMBS, N_LIMBS)],
            lambda j: a_ref[0, pl.ds((6 + j) * N_LIMBS, N_LIMBS)],
            write, offs)

    def line_merge(self, l1, l2):
        """Dense [..., 12, 32] product of two sparse flat lines
        ([..., 6, 32] in the LINE_IDX layout, or packed TileForms —
        output kind follows the inputs)."""
        _, _, counts = _line_merge_tables()
        offs = self._flat_acc_offsets(len(counts), counts)
        kernel = functools.partial(self._line_merge_kernel, offs)
        tiled = isinstance(l1, TileForm) or isinstance(l2, TileForm)
        if not tiled:
            shape = jnp.broadcast_shapes(l1.shape[:-2], l2.shape[:-2])
            l1 = TileForm.wrap(
                jnp.broadcast_to(l1, shape + (6, N_LIMBS)).reshape(
                    shape + (6 * N_LIMBS,)), 6 * N_LIMBS)
            l2 = TileForm.wrap(
                jnp.broadcast_to(l2, shape + (6, N_LIMBS)).reshape(
                    shape + (6 * N_LIMBS,)), 6 * N_LIMBS)
        at = tile_concat([l1, l2])
        out = self._call(
            kernel, 12 * N_LIMBS, at.tiles,
            scratch=[self._acc_scratch()])
        tf = TileForm(out, at.shape, at.b)
        if tiled:
            return tf
        return tf.unwrap().reshape(at.shape + (12, N_LIMBS))

    # -- merged Miller-iteration kernels -----------------------------------
    #
    # One Miller iteration used to cost a kernel trio + relayout per call:
    # flat_sqr(f), the stacked doubling-step kernel, and one 12x6 line
    # multiply per pair (4 launches, ~14 boundary crossings).  These
    # kernels run the COMPLETE iteration for the K=2 pairing check in ONE
    # launch — both pairs' curve steps (pair-stacked rows, the exact
    # _g2_dbl_line_rows/_g2_add_line_rows bodies), in-kernel flat line
    # encoding + neutral-line masking, f's squaring, and the line
    # multiplies (merged or sequential) — sharing f's loads and the
    # accumulator scratch across phases.  State (f, T) stays in TileForm
    # across the whole ladder: zero boundary crossings per iteration.
    #
    # VMEM: ins 898 rows + outs 768 + scratch 1216 = ~11.5 MB at the
    # 1024-element tile — the same envelope as flat_mul (whose in+out+
    # scratch is ~7.8 MB).  If a real-TPU Mosaic build overflows, set
    # DRAND_TPU_MILLER_MERGED=0 (trio path, unchanged performance
    # baseline) and record it in STATUS.md.

    def _write_flat(self, ref):
        def write(jp, r):
            for l in range(N_LIMBS):
                ref[0, jp * N_LIMBS + l] = r[l]

        return write

    def _write_pair_point(self, to_ref, T):
        """Pair-stacked point rows (leading axis 2) -> packed 12-group
        layout (pair p at groups [p*6, p*6+6))."""
        X, Y, Z = T
        coords = [X[0], X[1], Y[0], Y[1], Z[0], Z[1]]
        for p in range(2):
            for ci, rows in enumerate(coords):
                for l in range(N_LIMBS):
                    to_ref[0, (p * 6 + ci) * N_LIMBS + l] = rows[l][p]

    def _stage_masked_lines(self, lbuf_ref, m_ref, line):
        """Flat-encode the pair-stacked line triple (line_to_flat's exact
        layout: [a0-a1, b0-b1, c0-c1, a1, b1, c1]), select the neutral
        line (1, 0, ..., 0) where the pair is inactive, and stage line p
        at lbuf groups [p*6, p*6+6)."""
        groups = self._line_flat_rows(line)
        for p in range(2):
            mask = m_ref[0, p] != 0
            for gi, rows in enumerate(groups):
                for l in range(N_LIMBS):
                    neutral = int(self.ONE_MONT[l]) if gi == 0 else 0
                    lbuf_ref[(p * 6 + gi) * N_LIMBS + l] = jnp.where(
                        mask, rows[l][p],
                        jnp.full(_ROW, neutral, jnp.int32))

    def _mul_lines_into(self, a_src_ref, fo_ref, mul_tab_ref, K_mul,
                        line_merge, offs_mul, offs_merge, acc_ref,
                        lbuf_ref):
        """fo <- a_src * l1 * l2 with the lines staged in lbuf.  With
        line_merge the lines multiply into one dense element first (l12
        overwrites lbuf after all line reads); without it the two 12x6
        multiplies run sequentially through fo (exactly today's two
        fp12_mul_line calls)."""
        write_f = self._write_flat(fo_ref)
        read_a = lambda i: a_src_ref[0, pl.ds(i * N_LIMBS, N_LIMBS)]
        read_fo = lambda i: fo_ref[0, pl.ds(i * N_LIMBS, N_LIMBS)]
        if line_merge:
            def write_l(jp, r):
                lbuf_ref[pl.ds(jp * N_LIMBS, N_LIMBS)] = jnp.stack(r, 0)

            self._line_merge_phase(
                acc_ref,
                lambda i: lbuf_ref[pl.ds(i * N_LIMBS, N_LIMBS)],
                lambda j: lbuf_ref[pl.ds((6 + j) * N_LIMBS, N_LIMBS)],
                write_l, offs_merge)
            self._mul_phase(
                acc_ref, mul_tab_ref, K_mul, read_a,
                lambda jj: lbuf_ref[pl.ds(jj * N_LIMBS, N_LIMBS)],
                offs_mul)
            self._acc_reduce_write(acc_ref, write_f)
        else:
            self._mul_phase(
                acc_ref, mul_tab_ref, K_mul, read_a,
                lambda jj: lbuf_ref[pl.ds(jj * N_LIMBS, N_LIMBS)],
                offs_mul)
            self._acc_reduce_write(acc_ref, write_f)
            self._mul_phase(
                acc_ref, mul_tab_ref, K_mul, read_fo,
                lambda jj: lbuf_ref[pl.ds((6 + jj) * N_LIMBS, N_LIMBS)],
                offs_mul)
            self._acc_reduce_write(acc_ref, write_f)

    def _miller_dbl_iter_kernel(self, off, line_merge, offs_sqr, offs_mul,
                                offs_merge, K_mul, sqr_tab_ref,
                                mul_tab_ref, f_ref, t_ref, p_ref, m_ref,
                                fo_ref, to_ref, acc_ref, lbuf_ref):
        c = self._read_coords(t_ref, 12)
        pr = self._read_coords(p_ref, 4)
        pair2 = lambda r1, r2: [jnp.stack([a, b]) for a, b in zip(r1, r2)]
        X = (pair2(c[0], c[6]), pair2(c[1], c[7]))
        Y = (pair2(c[2], c[8]), pair2(c[3], c[9]))
        Z = (pair2(c[4], c[10]), pair2(c[5], c[11]))
        xp = pair2(pr[0], pr[2])
        yp = pair2(pr[1], pr[3])
        T2, line = self._g2_dbl_line_rows(off, X, Y, Z, xp, yp)
        self._write_pair_point(to_ref, T2)
        self._stage_masked_lines(lbuf_ref, m_ref, line)
        self._sqr_phase(acc_ref, sqr_tab_ref,
                        lambda i: f_ref[0, pl.ds(i * N_LIMBS, N_LIMBS)],
                        offs_sqr)
        self._acc_reduce_write(acc_ref, self._write_flat(fo_ref))
        self._mul_lines_into(fo_ref, fo_ref, mul_tab_ref, K_mul,
                             line_merge, offs_mul, offs_merge, acc_ref,
                             lbuf_ref)

    def _miller_add_iter_kernel(self, off, line_merge, offs_mul,
                                offs_merge, K_mul, mul_tab_ref, f_ref,
                                t_ref, q_ref, p_ref, m_ref, fo_ref,
                                to_ref, acc_ref, lbuf_ref):
        c = self._read_coords(t_ref, 12)
        qc = self._read_coords(q_ref, 8)
        pr = self._read_coords(p_ref, 4)
        pair2 = lambda r1, r2: [jnp.stack([a, b]) for a, b in zip(r1, r2)]
        X = (pair2(c[0], c[6]), pair2(c[1], c[7]))
        Y = (pair2(c[2], c[8]), pair2(c[3], c[9]))
        Z = (pair2(c[4], c[10]), pair2(c[5], c[11]))
        xq = (pair2(qc[0], qc[4]), pair2(qc[1], qc[5]))
        yq = (pair2(qc[2], qc[6]), pair2(qc[3], qc[7]))
        xp = pair2(pr[0], pr[2])
        yp = pair2(pr[1], pr[3])
        T3, line = self._g2_add_line_rows(off, X, Y, Z, xq, yq, xp, yp)
        # inactive pairs keep their old T (add_half's fp2_select)
        mask = jnp.stack([m_ref[0, 0], m_ref[0, 1]]) != 0     # [2, 8, 128]
        sel = lambda new, old: [jnp.where(mask, nr, orow)
                                for nr, orow in zip(new, old)]
        T3 = tuple((sel(nc[0], oc[0]), sel(nc[1], oc[1]))
                   for nc, oc in zip(T3, (X, Y, Z)))
        self._write_pair_point(to_ref, T3)
        self._stage_masked_lines(lbuf_ref, m_ref, line)
        self._mul_lines_into(f_ref, fo_ref, mul_tab_ref, K_mul,
                             line_merge, offs_mul, offs_merge, acc_ref,
                             lbuf_ref)

    def _miller_iter_tables(self, line_merge: bool):
        mul_tab, mul_pairs, K_mul = _flat_mul_tab(
            tuple(range(12)) if line_merge else LINE_IDX)
        offs_mul = self._flat_acc_offsets(K_mul, mul_pairs)
        offs_merge = None
        if line_merge:
            _, _, counts = _line_merge_tables()
            offs_merge = self._flat_acc_offsets(len(counts), counts)
        return mul_tab, K_mul, offs_mul, offs_merge

    def _miller_specs(self, nt):
        spec = lambda l: pl.BlockSpec((1, l, *_ROW),
                                      lambda i: (i, 0, 0, 0),
                                      memory_space=pltpu.VMEM)
        out_shape = [jax.ShapeDtypeStruct((nt, 12 * N_LIMBS, *_ROW),
                                          jnp.int32)] * 2
        scratch = [self._acc_scratch(),
                   pltpu.VMEM((12 * N_LIMBS, *_ROW), jnp.int32)]
        return spec, out_shape, scratch

    # Scoped VMEM of the two merged kernels, per grid step, at 4 KiB a
    # row (one int32 VREG): the blocks f, T, P, masks in and f', T' out
    # are (384+384+128+2) + (384+384) = 1666 rows, double-buffered 3332;
    # the add step's Q block adds 2*256; the scratch is 14*2*32 + 12*32 =
    # 1280 rows.  That is 18.02 MiB (dbl) and 20.02 MiB (add), and the
    # v5e compiler, which counts its own internal scratch too, asked for
    # 19.78 MiB and 23.29 MiB (PR 22, the scratch 64 rows smaller)
    # against its 16 MiB default and refused both.  48 MiB is twice what
    # it asked for and 3/8 of the 128 MiB of VMEM a v5e TensorCore has.
    _MILLER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=48 << 20)

    def miller_dbl_iter(self, f, T, P, masks, line_merge=True):
        """One merged Miller DOUBLING iteration for the 2-pair check:
        f' = f^2 * l1 * l2 plus both doubling steps, as ONE launch on
        TileForm state."""
        sqr_tab, sqr_pairs = _flat_sqr_tab()
        offs_sqr = self._flat_acc_offsets(23, sqr_pairs)
        mul_tab, K_mul, offs_mul, offs_merge = \
            self._miller_iter_tables(line_merge)
        from drand_tpu.ops.towers import _WIDE_NEG_OFF
        kernel = functools.partial(
            self._miller_dbl_iter_kernel,
            tuple(int(v) for v in _WIDE_NEG_OFF), line_merge, offs_sqr,
            offs_mul, offs_merge, K_mul)
        nt = f.tiles.shape[0]
        spec, out_shape, scratch = self._miller_specs(nt)
        f_out, t_out = self._launch(
            kernel,
            (jnp.asarray(sqr_tab), jnp.asarray(mul_tab), f.tiles, T.tiles,
             P.tiles, masks.tiles),
            out_shape=out_shape,
            grid=(nt,),
            in_specs=[
                pl.BlockSpec(sqr_tab.shape, lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(mul_tab.shape, lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
                spec(12 * N_LIMBS), spec(12 * N_LIMBS),
                spec(4 * N_LIMBS), spec(2)],
            out_specs=[spec(12 * N_LIMBS), spec(12 * N_LIMBS)],
            scratch_shapes=scratch,
            compiler_params=self._MILLER_PARAMS,
        )
        return (TileForm(f_out, f.shape, f.b),
                TileForm(t_out, T.shape, T.b))

    def miller_add_iter(self, f, T, Q, P, masks, line_merge=True):
        """One merged Miller ADDITION step for the 2-pair check:
        f' = f * l1 * l2 plus both mixed additions (mask-selected), as
        ONE launch on TileForm state."""
        mul_tab, K_mul, offs_mul, offs_merge = \
            self._miller_iter_tables(line_merge)
        from drand_tpu.ops.towers import _WIDE_NEG_OFF
        kernel = functools.partial(
            self._miller_add_iter_kernel,
            tuple(int(v) for v in _WIDE_NEG_OFF), line_merge, offs_mul,
            offs_merge, K_mul)
        nt = f.tiles.shape[0]
        spec, out_shape, scratch = self._miller_specs(nt)
        f_out, t_out = self._launch(
            kernel,
            (jnp.asarray(mul_tab), f.tiles, T.tiles, Q.tiles, P.tiles,
             masks.tiles),
            out_shape=out_shape,
            grid=(nt,),
            in_specs=[
                pl.BlockSpec(mul_tab.shape, lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
                spec(12 * N_LIMBS), spec(12 * N_LIMBS),
                spec(8 * N_LIMBS), spec(4 * N_LIMBS), spec(2)],
            out_specs=[spec(12 * N_LIMBS), spec(12 * N_LIMBS)],
            scratch_shapes=scratch,
            compiler_params=self._MILLER_PARAMS,
        )
        return (TileForm(f_out, f.shape, f.b),
                TileForm(t_out, T.shape, T.b))


_CACHE: dict[int, PallasField] = {}


def pallas_field(modulus: int) -> PallasField:
    if modulus not in _CACHE:
        _CACHE[modulus] = PallasField(modulus)
    return _CACHE[modulus]

"""The flat kernels' accumulation loops since ISSUE 45: one iteration a
real slot product.

`flat_mul` (by a dense element, by a sparse line, by a line read from
its run of tiles), `flat_sqr` and the merged Miller kernels, which run
the same two phases, are held to the XLA forms limb for limb (on the CPU
`use_pallas()` is false, so `flat12.flat_mul`/`flat_sqr` ARE the XLA
forms); the compact tables are held to the cell tables they replaced;
and the traced kernels hold no `cond` any more."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from drand_tpu.crypto.bls12381.constants import P
from drand_tpu.ops import flat12 as F
from drand_tpu.ops import pallas_field as PFm

DENSE = tuple(range(12))
TOP = [((P - 1) >> (12 * l)) & 0xFFF for l in range(32)]    # p - 1
VISITS = {"dense": 144, "sparse": 72, "square": 66 + 12}


@pytest.fixture()
def sim():
    from pallas_sim import sim_kernels
    with sim_kernels():
        yield


def _rows(seed, slots):
    """One tile of the simulator (8 rows) of `slots` canonical values in
    12-bit limbs: row 0 all p-1, row 1 all 0, rows 2 and 3 for the
    halves (the caller sets them), the rest random."""
    rng = random.Random(seed)
    out = np.zeros((8, slots, 32), np.int32)
    for r in range(4, 8):
        for s in range(slots):
            v = rng.randrange(P)
            out[r, s] = [(v >> (12 * l)) & 0xFFF for l in range(32)]
    out[0] = TOP
    return out


def _operands(b_slots):
    a, b = _rows(45, 12), _rows(4545, b_slots)
    a[2], b[2] = TOP, 0                 # all p-1 times all 0
    a[3], b[3] = 0, TOP                 # and the other half
    return jnp.asarray(a), jnp.asarray(b)


# -- the tables -------------------------------------------------------------

def _cell_table(kind):
    """The cells the loops walked before ISSUE 45, holes and all,
    written out from the definition: for a multiply tab[k][i] = the b
    row group that holds power k - i, or -1; for a square cols 0..5 the
    i of the pairs (i, k - i) with i < k - i, col 6 the diagonal's slot,
    -1 elsewhere."""
    if kind == "square":
        tab = []
        for k in range(23):
            row = [i for i in range(12) if i < k - i <= 11]
            tab.append(row + [-1] * (6 - len(row))
                       + [k // 2 if k % 2 == 0 else -1])
        return tab
    b_idx = PFm.LINE_IDX if kind == "sparse" else DENSE
    return [[b_idx.index(k - i) if k - i in b_idx else -1
             for i in range(12)] for k in range(11 + max(b_idx) + 1)]


@pytest.mark.parametrize("kind", list(VISITS))
def test_the_compact_tables_list_the_old_tables_products(kind):
    """Every non-negative cell of the old table is one product of the
    compact list and the other way round, power for power and in the
    cells' order: 144, 72, 66 + 12 products for 276, 252, 138 + 23
    cells."""
    cells = _cell_table(kind)
    K = len(cells)
    if kind == "square":
        tab, pairs = PFm._flat_sqr_tab()
        want = [[("pair", i, k - i) for i in row[:6] if i >= 0]
                + [("diag", d, d) for d in row[6:] if d >= 0]
                for k, row in enumerate(cells)]
        got = [[("pair", int(i), k - int(i))
                for i in tab[3, tab[0, k]:tab[0, k] + tab[1, k]]]
               + [("diag", k // 2, k // 2)] * int(tab[2, k])
               for k in range(K)]
        assert int(tab[1].sum()) == 66 and int(tab[2].sum()) == 12
        assert tab.shape == (4, 66)
        assert dict(pairs) == {k: 2 * int(tab[1, k]) + int(tab[2, k])
                               for k in range(K)}
        assert sum(map(len, cells)) == 138 + 23
    else:
        b_idx = PFm.LINE_IDX if kind == "sparse" else DENSE
        tab, pairs, k_tab = PFm._flat_mul_tab(b_idx)
        assert k_tab == K
        want = [[(i, jj) for i, jj in enumerate(row) if jj >= 0]
                for row in cells]
        got = [[(int(tab[2, t]), int(tab[3, t]))
                for t in range(tab[0, k], tab[0, k] + tab[1, k])]
               for k in range(K)]
        assert dict(pairs) == {k: len(w) for k, w in enumerate(want)}
        assert tab.shape == (4, VISITS[kind])
        assert sum(map(len, cells)) == {"dense": 276, "sparse": 252}[kind]
        # a product lands on the power its run is
        assert all(i + b_idx[jj] == k for k, run in enumerate(got)
                   for i, jj in run)
    assert got == want
    assert sum(map(len, got)) == VISITS[kind]
    # the runs tile the list: each starts where the one before ended
    assert [int(s) for s in tab[0, :K]] == \
        [sum(int(n) for n in tab[1, :k]) for k in range(K)]


# -- the kernels against the XLA forms ---------------------------------------

def _count_products(monkeypatch):
    calls = []
    conv, sqr_conv = PFm._conv_rows, PFm._sqr_conv_rows
    monkeypatch.setattr(PFm, "_conv_rows",
                        lambda a, b: calls.append("pair") or conv(a, b))
    monkeypatch.setattr(PFm, "_sqr_conv_rows",
                        lambda a: calls.append("diag") or sqr_conv(a))
    return calls


@pytest.mark.parametrize("kind", ["dense", "sparse", "sparse_b_run",
                                  "square"])
def test_a_flat_kernel_equals_the_xla_form_limb_for_limb(sim, monkeypatch,
                                                         kind):
    """Random canonical rows and rows of all 0, all p-1 and both halves,
    one tile; and the tile's loops make one visit a product (the
    simulator runs a `fori_loop` as Python: a visit is a call of the
    convolution)."""
    pf = PFm.pallas_field(P)
    if kind == "square":
        a, _ = _operands(12)
        want = np.asarray(F.flat_sqr(a))
        calls = _count_products(monkeypatch)
        got = pf.flat_sqr(a)
    elif kind == "dense":
        a, b = _operands(12)
        want = np.asarray(F.flat_mul(a, b, DENSE))
        calls = _count_products(monkeypatch)
        got = pf.flat_mul(a, b, DENSE)
    else:
        a, b = _operands(6)
        want = np.asarray(F.flat_mul(a, b, PFm.LINE_IDX))
        calls = _count_products(monkeypatch)
        if kind == "sparse":
            got = pf.flat_mul(a, b, PFm.LINE_IDX)
        else:           # the line is the second run of a joined operand
            other = pf.tile(jnp.asarray(_rows(7, 6)).reshape(8, 6 * 32),
                            6 * 32)
            joined = PFm.tile_stack(
                [other, pf.tile(b.reshape(8, 6 * 32), 6 * 32)])
            got = pf.flat_mul(pf.tile(a.reshape(8, 12 * 32), 12 * 32),
                              joined, PFm.LINE_IDX, b_run=1)
            got = got.unwrap().reshape(8, 12, 32)
    got = np.asarray(got)
    assert got.shape == want.shape and (got == want).all()
    assert want[4:].any() and not want[1].any()
    visits = VISITS[kind.removesuffix("_b_run")]
    assert len(calls) == visits
    assert calls.count("diag") == (12 if kind == "square" else 0)


@pytest.mark.parametrize("step,line_merge", [("dbl", False), ("add", True)],
                         ids=["dbl_square_and_two_sparse", "add_dense"])
def test_the_merged_kernels_phases_equal_the_xla_forms(sim, step,
                                                       line_merge):
    """The merged Miller kernels run `_sqr_phase` and `_mul_phase`
    in-kernel: the doubling iteration with its two sparse multiplies
    (78 + 2 x 72 products a tile), the addition step with the lines
    merged first and ONE dense multiply (144, after `line_merge`'s own
    36, which keeps its static sums), against the XLA composition, f'
    limb for limb and T' coordinate for coordinate."""
    import test_sim_kats as K
    pf = PFm.pallas_field(P)
    Tj, Q, Pc, f0, masks = K._miller_state()
    if step == "dbl":
        fr, Tsr = K._ref_dbl_iter(Tj, Pc, f0, masks)
    else:
        fr, Tsr = K._ref_add_iter(Tj, Q, Pc, f0, masks)
    ft, Tt, Qt, Pt, Mt = K._pack_miller(pf, Tj, Q, Pc, f0, masks)
    if step == "dbl":
        fo, To = pf.miller_dbl_iter(ft, Tt, Pt, Mt, line_merge=line_merge)
    else:
        fo, To = pf.miller_add_iter(ft, Tt, Qt, Pt, Mt,
                                    line_merge=line_merge)
    got = np.asarray(pf.untile(fo).reshape(f0.shape))
    assert (got == np.asarray(fr)).all()
    K._assert_point_pack(pf, To, Tsr)


# -- no branch is left in the loops -------------------------------------------

def _primitives(jaxpr, into, loops):
    """Count the primitives of a jaxpr and all it holds; `loops` gets
    the counts of every `while`'s own body beside."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        into[name] = into.get(name, 0) + 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, into, loops)
        if name == "while":
            loops.append(_primitives(eqn.params["body_jaxpr"].jaxpr, {}, []))
    return into


@pytest.mark.parametrize("kind", list(VISITS))
def test_no_cond_is_left_in_the_traced_loops(kind):
    """The kernel as the chip's compiler gets it (the real `pallas_call`,
    traced and not lowered): its accumulation is loops alone, the
    products' with bounds read from SMEM (a `while`), and the conv body
    is traced once a kind of product."""
    pf = PFm.PallasField(P)
    tf = lambda tiles: PFm.TileForm(tiles, (PFm.TILE,), PFm.TILE)
    tiles = lambda slots: jnp.zeros((1, slots * 32, *PFm._ROW), jnp.int32)
    if kind == "square":
        fn, args = (lambda a: pf.flat_sqr(tf(a)).tiles), (tiles(12),)
    else:
        b_idx = PFm.LINE_IDX if kind == "sparse" else DENSE
        fn = lambda a, b: pf.flat_mul(tf(a), tf(b), b_idx).tiles
        args = (tiles(12), tiles(len(b_idx)))
    loops = []
    seen = _primitives(
        jax.make_jaxpr(fn)(*args).jaxpr, {}, loops)
    assert seen.get("pallas_call") == 1
    assert seen.get("cond", 0) == 0
    # the loops with bounds read from SMEM: the products', whose body is
    # ONE 32 x 32 limb convolution, and for a square the diagonal's 0 or
    # 1 beside it, ONE triangular convolution (a few more multiplies
    # each: the rows' offsets and the scatter's two coefficients)
    muls = sorted(body.get("mul", 0) for body in loops)
    want = [528, 1024] if kind == "square" else [1024]
    assert len(muls) == len(want)
    assert all(w <= m < w + 8 for m, w in zip(muls, want)), muls
    assert all(body.get("cond", 0) == 0 for body in loops)

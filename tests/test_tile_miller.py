"""The G2-signature programs' Miller loop keeps its curve state in the
kernels' tile layout (ISSUE 39): on the Pallas path `miller_loop_pairs`
packs every pair's T = (xq, yq, 1) and P once before the ladder, carries
T as one TileForm with the pairs joined on the tile axis, and a step is
kernels only.  The f that leaves the loop is the XLA path's limb for
limb, and the crossings into tile layout are the entry's, whatever the
ladder's length.

The kernels run through the eager simulator (`pallas_sim`) on batches of
3 rows in tiles of 8; the crossings are counted on traces of the real
`pallas_call`s, which run nothing.
"""

import random
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from drand_tpu.crypto.bls12381 import curve as GC
from drand_tpu.crypto.bls12381.constants import P, R
from drand_tpu.ops import flat12 as F
from drand_tpu.ops import pairing as DP
from drand_tpu.ops import pallas_field as PFm
from drand_tpu.ops.field import compact_scope
from test_ops_pairing import affine_g1_dev, affine_g2_dev

rng = random.Random(0x39)
B = 3
ONE_STEP = [(0, True)]          # one doubling and one addition

# rows in which pair k is active; None: the pair has no mask
MASKS = {
    "off": [None, None],
    "one": [[True, False, True], None],
    "both": [[True, False, True], [True, True, False]],
}


@pytest.fixture(scope="module")
def points():
    """Two pairs of B rows each: G1 points, and another G2 point a row."""
    return [(affine_g1_dev([GC.g1_mul(GC.G1_GEN, rng.randrange(1, R))
                            for _ in range(B)]),
             affine_g2_dev([GC.g2_mul(GC.G2_GEN, rng.randrange(1, R))
                            for _ in range(B)])) for _ in range(2)]


def _active(case, k_pairs):
    return [m if m is None else jnp.asarray(m)
            for m in MASKS[case][:k_pairs]]


def _same_f_and_entry_crossings(pairs, active, keep_tiled):
    """The loop on the compact Pallas path under the simulator against
    the XLA path, with what it crossed on the way."""
    want = np.asarray(DP.miller_loop_pairs(pairs, active))
    with mock.patch.object(PFm, "use_pallas", return_value=True):
        from pallas_sim import sim_kernels
        with sim_kernels(), jax.disable_jit(), compact_scope(True):
            before = PFm.layout_conversion_counts()
            f = DP.miller_loop_pairs(pairs, active, _keep_tiled=keep_tiled)
            after = PFm.layout_conversion_counts()
            assert isinstance(f, PFm.TileForm) == keep_tiled
            got = np.asarray(F.flat_untile(f))
    assert got.shape == (B, 12, 32) and got.dtype == np.int32
    assert (got == want).all()
    # f, every pair's coordinates in one pack, a mask a pair
    assert after["to_tiles"] - before["to_tiles"] == len(pairs) + 2
    assert after["from_tiles"] - before["from_tiles"] == int(not keep_tiled)
    return got


@pytest.mark.parametrize("k_pairs,case,keep_tiled,ladder", [
    (1, "off", False, "one_step"),
    (1, "one", True, "one_step"),
    (2, "one", False, "one_step"),
    (2, "both", True, "one_step"),
    pytest.param(2, "off", True, "whole", marks=pytest.mark.slow),
])
def test_the_tiled_loop_is_the_xla_loop_limb_for_limb(points, monkeypatch,
                                                      k_pairs, case,
                                                      keep_tiled, ladder):
    if ladder == "one_step":
        monkeypatch.setattr(DP, "_X_SEGMENTS", ONE_STEP)
    active = _active(case, k_pairs)
    got = _same_f_and_entry_crossings(points[:k_pairs], active, keep_tiled)
    if case == "both" and k_pairs == 2:
        # row 0 has both pairs live: no neutral element
        assert not (got[0] == np.asarray(F.FLAT_ONE)).all()
    if case == "one" and k_pairs == 1:
        # row 1's one pair is inactive: the empty product, conjugated
        assert (got[1] == np.asarray(F.FLAT_ONE)).all()


def _traced_crossings(points, segments, keep_tiled, compact):
    """What a trace of the loop on the Pallas path adds to the layout
    counters (the real `pallas_call`s, traced and never run)."""
    active = _active("both", 2)

    def run(pairs):
        f = DP.miller_loop_pairs(pairs, active, _keep_tiled=keep_tiled)
        return f.tiles if keep_tiled else f

    # the static unroll of two pairs would take the merged kernels
    with mock.patch.object(PFm, "use_pallas", return_value=True), \
            mock.patch.object(DP, "_X_SEGMENTS", segments), \
            mock.patch.dict("os.environ", DRAND_TPU_MILLER_MERGED="0"), \
            compact_scope(compact):
        before = PFm.layout_conversion_counts()
        jax.make_jaxpr(run)(points)
        after = PFm.layout_conversion_counts()
    return {k: after[k] - before[k] for k in after}


@pytest.mark.parametrize("keep_tiled", [True, False])
def test_the_crossings_are_the_entrys_whatever_the_ladders_length(
        points, keep_tiled):
    """Into tile layout: f, one pack of both pairs' sixteen coordinates,
    two masks; out of it nothing, or f where the caller wants limbs.
    The same for a ladder of one step, of two, and of all 68."""
    want = {"to_tiles": 4, "from_tiles": int(not keep_tiled)}
    for compact, segments in [(True, ONE_STEP), (True, DP._X_SEGMENTS),
                              (False, ONE_STEP),
                              (False, [(0, True), (0, True)])]:
        assert _traced_crossings(points, segments, keep_tiled,
                                 compact) == want, (compact, segments)


@pytest.mark.parametrize("check,want", [
    ("pairs", {"to_tiles": 24, "from_tiles": 11}),
    ("fixed_q", {"to_tiles": 25, "from_tiles": 11}),
])
def test_a_whole_check_crosses_as_it_did_before_issue_42(points, check, want):
    """`cyclo_sqr` recombines in the wide domain since ISSUE 42: its
    callers, the TileForm threading and the crossings of a whole pairing
    check do not change.  The counts are the parent commit's (8e90d58),
    read around the same traces: Miller loop and x-power chains of one
    step, the final exponentiation whole (`flat_inv`'s tower evaluation
    is its counted interior exception), the verdict's mask out."""
    from drand_tpu.crypto.bls12381 import curve as GCv
    if check == "pairs":
        fn, args = DP.pairing_check_pairs, (points,)
    else:
        qs = [GCv.g2_affine(q) for q in
              (GCv.G2_GEN, GCv.g2_mul(GCv.G2_GEN, 7))]
        fn = DP.pairing_check_fixed_q
        args = ([p for p, _ in points], jnp.asarray(DP.fixed_q_table(qs)))
    with mock.patch.object(PFm, "use_pallas", return_value=True), \
            mock.patch.object(DP, "_X_SEGMENTS", ONE_STEP), \
            mock.patch.dict("os.environ", DRAND_TPU_MILLER_MERGED="0"), \
            compact_scope(True):
        before = PFm.layout_conversion_counts()
        jax.make_jaxpr(fn)(*args)
        after = PFm.layout_conversion_counts()
    assert {k: after[k] - before[k] for k in after} == want


def test_a_line_is_read_from_its_pairs_run_of_tiles(points):
    """`flat_mul`'s `b_run` under the simulator: with two batches joined
    on the tile axis it multiplies by the one asked for, in place."""
    from pallas_sim import sim_kernels

    def fps(n):
        return jnp.asarray(DP.FP.encode(
            [rng.randrange(P) for _ in range(B * n)])).reshape(B, n, 32)

    lines, a = [fps(6), fps(6)], fps(12)
    want = [np.asarray(F.flat_mul(a, ln, DP.LINE_IDX)) for ln in lines]
    assert not (want[0] == want[1]).all()
    with mock.patch.object(PFm, "use_pallas", return_value=True), \
            sim_kernels(), jax.disable_jit():
        pf = DP.FP._pallas()
        at = F.flat_tile(a)
        joined = PFm.tile_stack(
            [pf.tile(ln.reshape(B, 6 * 32), 6 * 32) for ln in lines])
        assert joined.tiles.shape[0] == 2 * at.tiles.shape[0]
        assert joined.unwrap().shape == (2, PFm.TILE, 6 * 32)
        got = [np.asarray(F.flat_untile(
            pf.flat_mul(at, joined, DP.LINE_IDX, b_run=k))) for k in range(2)]
    for k in range(2):
        assert (got[k] == want[k]).all(), k

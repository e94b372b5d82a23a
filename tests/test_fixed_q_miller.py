"""The G1-signature program's Miller loop takes its lines from a table
built once a key (ISSUE 37): `pairing.miller_loop_fixed_q` equals
`pairing.miller_loop_pairs` limb for limb, the host's table is the
per-row steps' triple before its scaling by P, and `Verifier` on the
short-signature scheme judges as the host does, with the table its
program's third, run-time argument.

The CPU tier (pure-XLA graph, compact ladders, batches of 3 to 8); one
case drives the Pallas wrapper (`PallasField.line_scaler`) through the
eager simulator on a ladder of two steps.
"""

import hashlib
import json
import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import drand_tpu.verify as V
from benchmark import harness as H
from drand_tpu import tracing
from drand_tpu.crypto import sign as S
from drand_tpu.crypto.bls12381 import curve as GC
from drand_tpu.crypto.bls12381 import fp as GF
from drand_tpu.crypto.bls12381.constants import P, R
from drand_tpu.ops import bls as BLS
from drand_tpu.ops import flat12 as F
from drand_tpu.ops import pairing as DP
from drand_tpu.ops import pallas_field as PFm
from drand_tpu.ops import towers as T
from drand_tpu.ops.field import FP, compact_scope
from test_ops_pairing import affine_g1_dev     # golden G1 points -> limbs

rng = random.Random(0x37)
B = 3
N = 8               # rows of the verify program


@pytest.fixture(scope="module")
def pairs():
    """Two fixed G2 points (the generator and a key), their table, and B
    rows of G1 points a pair, with the Qs broadcast for the per-row loop."""
    qs = [GC.g2_affine(q) for q in
          (GC.G2_GEN, GC.g2_mul(GC.G2_GEN, rng.randrange(1, R)))]
    ps = [affine_g1_dev([GC.g1_mul(GC.G1_GEN, rng.randrange(1, R))
                   for _ in range(B)]) for _ in qs]
    q_dev = [tuple(T.fp2_broadcast(T.fp2_const(c), (B,)) for c in q)
             for q in qs]
    return qs, ps, q_dev, jnp.asarray(DP.fixed_q_table(qs))


MASKS = {
    "masks_off": None,
    "masks_on": [[True, False, True], [True, True, False]],
}


@pytest.mark.parametrize("case", sorted(MASKS))
def test_the_fixed_q_loop_is_the_per_row_loop_limb_for_limb(pairs, case):
    _qs, ps, q_dev, table = pairs
    active = MASKS[case] and [jnp.asarray(m) for m in MASKS[case]]
    with compact_scope(True):
        per_row = jax.jit(lambda ps, qs: DP.miller_loop_pairs(
            list(zip(ps, qs)), active))(ps, q_dev)
        fixed = jax.jit(lambda ps, t: DP.miller_loop_fixed_q(
            ps, t, active))(ps, table)
    assert fixed.shape == (B, 12, 32) and fixed.dtype == jnp.int32
    assert (np.asarray(fixed) == np.asarray(per_row)).all()
    if active:
        # a row with both pairs live differs from the neutral element
        assert not (np.asarray(fixed[0]) == np.asarray(F.FLAT_ONE)).all()


def _steps_at_batch_1(q, p_dev):
    """{table row: (a, b, c) device line} of `_dbl_step`/`_add_step` run
    at batch 1 on Q, for the first two doublings, the first add (row 1:
    the ladder's first bit is set) and the last row."""
    xp, yp = (c[:1] for c in p_dev)
    Q = tuple(T.fp2_broadcast(T.fp2_const(c), (1,)) for c in q)
    Tj = (*Q, T.fp2_broadcast(T.FP2_ONE, (1,)))
    bits = DP._X_BITS[1:]
    first_add = bits.index("1") + 1
    want = {0, first_add, first_add + 1, DP.LINE_STEPS - 1}
    lines, row = {}, 0
    dbl, add = jax.jit(DP._dbl_step), jax.jit(DP._add_step)
    for bit in bits:
        Tj, line = dbl(Tj, xp, yp)
        lines[row] = line
        row += 1
        if bit == "1":
            Tj, line = add(Tj, Q, xp, yp)
            lines[row] = line
            row += 1
    assert row == DP.LINE_STEPS and bits[-1] == "0"
    return {r: lines[r] for r in want}, first_add


@pytest.mark.parametrize("which", ["generator", "key"])
def test_the_hosts_table_is_the_steps_triple_before_p(pairs, which):
    """Row r of the table, scaled by P on the host with Python integers,
    is the line `_dbl_step`/`_add_step` give on the device at that step:
    same denominator-cleared formulas, same slots."""
    qs, ps, _q_dev, table = pairs
    k = ["generator", "key"].index(which)
    xp, yp = (FP.from_limbs_host(np.asarray(c[0])) for c in ps[k])
    lines, first_add = _steps_at_batch_1(qs[k], ps[k])
    assert first_add == 1 and sorted(lines) == [0, 1, 2, 67]
    for r, line in lines.items():
        got = [FP.from_limbs_host(np.asarray(table[r, k, s]))
               for s in range(6)]
        scaled = [got[0], got[1] * xp % P, got[2] * yp % P,
                  got[3], got[4] * xp % P, got[5] * yp % P]
        flat = np.asarray(DP.line_to_flat(line))[0]
        assert scaled == [FP.from_limbs_host(flat[s]) for s in range(6)], r


def test_the_tables_shape_and_its_first_row():
    q = GC.g2_affine(GC.G2_GEN)
    table = DP.fixed_q_table([q])
    assert table.shape == (DP.LINE_STEPS, 1, 6, 32) == (68, 1, 6, 32)
    assert table.dtype == np.int32
    # the first doubling at Z = 1: a = 3x^3 - 2y^2, nb3 = -3x^2, cc2 = 2y
    x, y = q
    a = GF.fp2_sub(GF.fp2_mul_fp(GF.fp2_mul(GF.fp2_sqr(x), x), 3),
                   GF.fp2_mul_fp(GF.fp2_sqr(y), 2))
    nb3 = GF.fp2_neg(GF.fp2_mul_fp(GF.fp2_sqr(x), 3))
    cc2 = GF.fp2_mul_fp(y, 2)
    want = [(c[0] - c[1]) % P for c in (a, nb3, cc2)] + \
        [c[1] for c in (a, nb3, cc2)]
    assert [FP.from_limbs_host(table[0, 0, s]) for s in range(6)] == want


def test_the_pallas_wrapper_crosses_the_layout_once_and_agrees(pairs,
                                                               monkeypatch):
    """`PallasField.line_scaler` under the eager simulator, on a ladder
    cut to one doubling and one addition: the same f as the XLA path, and
    every crossing into tile layout (f, four coordinates, two masks) made
    before the ladder, none inside it."""
    from unittest import mock

    from pallas_sim import sim_kernels
    _qs, ps, _q_dev, table = pairs
    active = [jnp.asarray(m) for m in MASKS["masks_on"]]
    monkeypatch.setattr(DP, "_X_SEGMENTS", [(0, True)])
    want = np.asarray(DP.miller_loop_fixed_q(ps, table, active))
    with sim_kernels(), jax.disable_jit(), compact_scope(True), \
            mock.patch.object(PFm, "use_pallas", return_value=True):
        before = dict(PFm.layout_conversion_counts())
        f = DP.miller_loop_fixed_q(ps, table, active, _keep_tiled=True)
        after = PFm.layout_conversion_counts()
        assert isinstance(f, PFm.TileForm)
        got = np.asarray(F.flat_untile(f))
    assert after["to_tiles"] - before["to_tiles"] == 7
    assert after["from_tiles"] == before["from_tiles"]
    assert (got == want).all()


# -- through `Verifier` on the short-signature scheme -------------------------

def _off_subgroup_sig() -> bytes:
    """A point of E(Fp) outside the r-torsion, compressed."""
    x = 5
    while True:
        y = GF.fp_sqrt((x * x * x + 4) % P)
        if y is not None and not GC.g1_in_subgroup((x, y, 1)):
            return GC.g1_to_bytes((x, y, 1))
        x += 1


@pytest.fixture(scope="module")
def quicknet():
    """The benchmark's quicknet key, its first N fixture rounds with four
    of them spoiled, the verdicts of the 8-row G1 program under the key's
    table, and the same executable's under another key's."""
    with open(os.path.join(H.BENCH_DIR, "configs", "quicknet-g1.json")) as f:
        config = json.load(f)
    sigs = np.ascontiguousarray(np.load(os.path.join(
        H.BENCH_DIR, "fixtures", config["fixture"]["file"]))[:N])
    pk = GC.g2_from_bytes(bytes.fromhex(config["public_key_hex"]))
    rows = {"good": 0, "flipped_bit": 2, "infinity": 4, "off_subgroup": 5}
    sigs[rows["flipped_bit"], 17] ^= np.uint8(1 << 3)
    sigs[rows["infinity"]] = np.frombuffer(GC.g1_to_bytes(GC.G1_INF), np.uint8)
    sigs[rows["off_subgroup"]] = np.frombuffer(_off_subgroup_sig(), np.uint8)
    v = V.Verifier(pk, V.SHAPE_UNCHAINED_G1)
    rounds = np.arange(1, N + 1, dtype=np.uint64)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(V, "_BUCKETS", (N,))
        tracing.RECORDER.clear()
        with compact_scope(True):
            rec = v.build(N)
        rec["span"] = next(s.to_dict()["attrs"]
                           for s in tracing.RECORDER.spans()
                           if s.name == "verifier.build")
        served = v.verify_batch(rounds, sigs)
    other = BLS.const_g2_lines(S.keygen_g2(b"another chain")[1])
    under_other = np.asarray(v._kernels[N](
        jnp.asarray(v.messages(rounds, None)), jnp.asarray(sigs), other))
    host = [S.bls_verify_g1(pk, hashlib.sha256(
        int(r).to_bytes(8, "big")).digest(), bytes(s))
        for r, s in zip(rounds, sigs)]
    return rec, rows, served, under_other, host


@pytest.mark.parametrize("case", ["good", "flipped_bit", "infinity",
                                  "off_subgroup"])
def test_the_g1_program_judges_a_row_as_the_host_does(quicknet, case):
    _rec, rows, served, _other, host = quicknet
    row = rows[case]
    assert bool(served[row]) == host[row] == (case == "good")
    # the rows left alone are sound, whatever stands beside them
    assert [bool(served[i]) for i in range(N)] == host
    assert sum(host) == N - 3


def test_another_keys_table_rejects_this_keys_rounds(quicknet):
    """The table is a run-time argument of one executable: the same
    program under a second key's lines accepts none of the first key's
    rounds."""
    _rec, _rows, served, under_other, _host = quicknet
    assert served.sum() == N - 3 and not under_other.any()


def test_the_build_says_which_loop_the_program_holds(quicknet):
    rec = quicknet[0]
    assert rec["program"].startswith("verify-g1sig-un-")
    assert "anykey" in rec["program"]
    for said in (rec, rec["span"]):
        assert (said["miller_lines"], said["line_steps"]) == ("table", 68)

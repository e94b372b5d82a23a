"""Compact-graph mode (DRAND_TPU_COMPACT): the one-scan ladder, whose body
doubles on every bit and adds under a conditional on the set ones, must
compute exactly what the static segmented ladder computes.

The driver's dryrun/compile-check trace with this flag set (graph-size
bound), so a divergence here would make the dryrun validate a different
program than the one the bench measures.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp

from drand_tpu.crypto.bls12381 import curve as GC
from drand_tpu.ops import curve as DC
from drand_tpu.ops import towers as T
from drand_tpu.ops.field import (compact_graphs, compact_scope,
                                 segmented_ladder, tail_segments)

_X_ABS = 0xd201000000010000


def test_flag_off_by_default():
    assert not compact_graphs()


def _ladder(k: int, add=lambda s: s + 1):
    """Integer double-and-add over (x2, +1), from a start state of ones:
    computes the scalar itself (ladder logic only, no field ops — fast
    to compile)."""
    segments = tail_segments(bin(k)[3:])
    return lambda s: segmented_ladder(segments, s, lambda a: a * 2, add)


def _run(k: int, add=lambda s: s + 1) -> float:
    return float(np.asarray(_ladder(k, add)(jnp.ones((1,))))[0])


@pytest.mark.parametrize(
    "k", (_X_ABS, 0b1011, 1 << 20, (1 << 20) + 1, 0x1FF), ids=hex)
def test_segmented_ladder_dense_parity(k):
    """Both modes must agree for sparse and dense scalars."""
    with compact_scope(False):
        static = _run(k)
    with compact_scope():
        dense = _run(k)
    # the modes must agree bit-for-bit, and small scalars (inside float
    # mantissa range) must equal k exactly
    assert static == dense, (k, static, dense)
    if k < (1 << 24):
        assert static == float(k), (k, static)


@pytest.mark.parametrize("k", (_X_ABS, 1 << 20, 0x1FF), ids=hex)
def test_compact_ladder_runs_add_on_set_bits_only(k):
    """The mechanism: one scan whose body holds a real conditional on the
    scanned bit, so `add_fn` is EXECUTED once a set tail bit (5 of 63 for
    |x|), not computed on every bit and selected away."""
    from jax.experimental import io_callback

    executed = []

    def counted_add(s):
        io_callback(lambda: executed.append(1), None, ordered=True)
        return s + 1

    with compact_scope():
        _run(k, counted_add)
        jax.effects_barrier()
        jaxpr = jax.make_jaxpr(_ladder(k))(jnp.ones((1,)))
    assert len(executed) == bin(k)[3:].count("1")
    (scan,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    body = [e.primitive.name for e in scan.params["jaxpr"].jaxpr.eqns]
    assert "cond" in body and "select_n" not in body, body


def test_compact_ladder_under_shard_map():
    """`ShardedVerifier` runs the same body under `shard_map` over the
    round axis: the bit is a replicated scalar, every shard takes the
    same branch, and the rows come back what the static ladder gives."""
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:4]), ("rounds",))
    rows = jnp.arange(8, dtype=jnp.float32) + 1
    with compact_scope():
        got = jax.jit(jax.shard_map(
            _ladder(_X_ABS), mesh=mesh, in_specs=P("rounds"),
            out_specs=P("rounds")))(rows)
    with compact_scope(False):
        want = _ladder(_X_ABS)(rows)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_point_mul_const_compact_matches_golden(monkeypatch):
    """G1 scalar mul by the (sparse) BLS parameter through the compact
    ladder lands on the golden model's point."""
    monkeypatch.setenv("DRAND_TPU_COMPACT", "1")
    x_abs = 0xd201000000010000
    # batch of 2 points: generator and 2*generator
    g = GC.G1_GEN
    g2 = GC.g1_double(g)
    pts = [g, g2]
    xs = T.fp_encode([GC.g1_affine(p)[0] for p in pts])
    ys = T.fp_encode([GC.g1_affine(p)[1] for p in pts])
    import jax.numpy as jnp
    one = jnp.broadcast_to(T.FP_ONE, xs.shape).astype(jnp.int32)
    dev = DC.point_mul_const((xs, ys, one), x_abs, DC.FpOps)
    (ax, ay), inf = DC.point_to_affine(dev, DC.FpOps)
    for i, p in enumerate(pts):
        want = GC.g1_affine(GC.g1_mul(p, x_abs))
        got = (T.fp_decode(ax, i), T.fp_decode(ay, i))
        assert got == want, f"point {i}"

"""Eager-mode simulator for the drand_tpu Pallas kernels (test helper).

`pallas_call(interpret=True)` wraps the kernel in a jit whose XLA:CPU
compile takes tens of minutes for the big fused kernels on this 1-core
host.  This shim executes the kernel body EAGERLY under
`jax.disable_jit()` with numpy-backed refs: `lax.fori_loop`/`cond` run
as python control flow, jnp int32 arithmetic matches XLA semantics
bit-for-bit, and a full fused-kernel KAT takes seconds.

Supports exactly the pallas feature subset the kernels use: 1-D grids,
VMEM/SMEM BlockSpecs whose index_map returns block indices, `pl.ds`
dynamic slices (with concrete starts, as under disable_jit), and VMEM
scratch shapes.  Cross-checked against the real interpreter by the
`test_sim_matches_interpreter` KAT in test_pallas_field.py.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _to_slice(e):
    # pl.ds(start, size) objects expose .start and .size
    if hasattr(e, "start") and hasattr(e, "size") and not isinstance(e, slice):
        start = int(e.start)
        return slice(start, start + int(e.size))
    if isinstance(e, jnp.ndarray) or isinstance(e, np.ndarray):
        return int(e)
    return e


class _Ref:
    def __init__(self, arr: np.ndarray):
        self.arr = arr

    def _conv(self, idx):
        if not isinstance(idx, tuple):
            idx = (idx,)
        return tuple(_to_slice(e) for e in idx)

    def __getitem__(self, idx):
        return jnp.asarray(self.arr[self._conv(idx)])

    def __setitem__(self, idx, val):
        self.arr[self._conv(idx)] = np.asarray(val)


def _block_view(arr, spec, step):
    if spec is None or spec.block_shape is None:
        return _Ref(arr)
    bs = tuple(spec.block_shape)
    idx = spec.index_map(step)
    sl = tuple(slice(i * b, (i + 1) * b) for i, b in zip(idx, bs))
    return _Ref(arr[sl])


def sim_pallas_call(kernel, out_shape, grid=None, in_specs=None,
                    out_specs=None, scratch_shapes=None, **kw):
    """Drop-in replacement for pl.pallas_call in tests."""
    assert grid is not None and len(grid) == 1, "1-D grids only"

    def run(*args):
        outs = out_shape if isinstance(out_shape, (list, tuple)) else [out_shape]
        out_arrs = [np.zeros(o.shape, np.dtype(o.dtype)) for o in outs]
        arrs = [np.asarray(a) for a in args]
        scratch = [np.zeros(tuple(s.shape), np.dtype(s.dtype))
                   for s in (scratch_shapes or [])]
        with jax.disable_jit(), contextlib.ExitStack():
            for step in range(grid[0]):
                in_refs = [_block_view(a, s, step)
                           for a, s in zip(arrs, in_specs)]
                o_specs = (out_specs if isinstance(out_specs, (list, tuple))
                           else [out_specs])
                out_refs = [_block_view(a, s, step)
                            for a, s in zip(out_arrs, o_specs)]
                kernel(*in_refs, *out_refs, *[_Ref(s) for s in scratch])
        res = [jnp.asarray(a) for a in out_arrs]
        return res[0] if not isinstance(out_shape, (list, tuple)) else res

    return run


@contextlib.contextmanager
def sim_kernels(tile=8, row=(1, 8)):
    """Route drand_tpu.ops.pallas_field kernels through the simulator
    with a tiny tile (mirrors the interp fixture's shape overrides)."""
    from drand_tpu.ops import pallas_field as PFm
    orig_call, orig_tile, orig_row = PFm.pl.pallas_call, PFm.TILE, PFm._ROW
    orig_jit = PFm._jit
    PFm.pl.pallas_call = sim_pallas_call
    PFm._jit = lambda fn: fn        # the simulator runs eagerly
    PFm.TILE, PFm._ROW = tile, row
    PFm._CACHE.clear()
    try:
        yield
    finally:
        PFm.pl.pallas_call = orig_call
        PFm._jit = orig_jit
        PFm.TILE, PFm._ROW = orig_tile, orig_row
        PFm._CACHE.clear()


def assert_line_steps_match_xla(pf, Tj, Q, xp, yp, active):
    """`pf.g2_dbl_line`/`pf.g2_add_line` on packed state against the XLA
    `_dbl_step`/`_add_step` (the oracle: on the CPU `use_pallas()` is
    False): T' coordinate for coordinate, the line as `line_to_flat`
    lays it out, and where `active` is false the neutral line and, on an
    addition, the old T."""
    from drand_tpu.ops import pairing as DP
    from drand_tpu.ops import pallas_field as PFm
    on = np.asarray(active)
    Tt = pf.g2_pack_point(Tj)
    Pt = pf.pack_coords([xp, yp])
    Qt = pf.pack_coords([Q[0][0], Q[0][1], Q[1][0], Q[1][1]])
    mask = PFm.TileForm.wrap(jnp.asarray(on)[:, None], 1).tiles
    before = PFm.layout_conversion_counts()
    steps = {"dbl": pf.g2_dbl_line(Tt, Pt, mask),
             "add": pf.g2_add_line(Tt, Qt, Pt, mask)}
    assert PFm.layout_conversion_counts() == before    # kernels only
    want = {"dbl": DP._dbl_step(Tj, xp, yp),
            "add": DP._add_step(Tj, Q, xp, yp)}
    for name, (t_new, line) in steps.items():
        t_want, line_want = want[name]
        if name == "add":
            t_want = jax.tree_util.tree_map(
                lambda new, old: jnp.where(on[:, None], new, old),
                t_want, Tj)
        for got, ref in zip(jax.tree_util.tree_leaves(
                pf.g2_unpack_point(t_new)),
                jax.tree_util.tree_leaves(t_want)):
            assert (np.asarray(got) == np.asarray(ref)).all(), name
        flat = np.where(on[:, None, None],
                        np.asarray(DP.line_to_flat(line_want)),
                        np.asarray(DP._LINE_ONE_FLAT))
        got = np.asarray(line.unwrap()).reshape(flat.shape)
        assert (got == flat).all(), name

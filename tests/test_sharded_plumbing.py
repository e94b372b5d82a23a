"""ShardedVerifier mesh plumbing on the 8-virtual-device CPU mesh.

These run in the DEFAULT suite: they exercise the sharding, padding, and
mesh-factorization logic with a stub kernel (no pairing compile), so
plumbing regressions (e.g. a broken pad helper) fail fast.  The crypto
parity of the same paths runs under --runslow in test_parallel.py.
"""

import numpy as np
import pytest

import drand_tpu.verify as V
from drand_tpu.crypto.bls12381 import curve as GC
from drand_tpu.parallel.sharded import ShardedVerifier, _pad2


def test_pad2_edge_pads_leading_axes():
    a = np.arange(2 * 3 * 4, dtype=np.uint8).reshape(2, 3, 4)
    p = _pad2(a, 4, 4)
    assert p.shape == (4, 4, 4)
    assert (p[2] == p[1]).all() and (p[3] == p[1]).all()
    assert (p[:, 3] == p[:, 2]).all()
    assert (p[:2, :3] == a).all()


class _StubVerifier(V.Verifier):
    """`Verifier` with a stand-in body for the sharding layer: a row is
    valid iff its signature's first byte is even.  `ShardedVerifier`
    builds its program over the mesh from this verifier's exported form
    (`Verifier.build(n, mesh=...)`): it must NOT reuse
    `Verifier._kernel`'s single-device Compiled, which cannot accept
    NamedSharding inputs."""

    def __init__(self):
        super().__init__(GC.G1_GEN, V.SHAPE_UNCHAINED)

    def _aot_name(self, n):
        return f"stub-verify-b{n}"

    def _run_fn(self, compact=None):
        def run(msgs, sigs, pk):
            return (sigs[..., 0] % 2) == 0
        return run


@pytest.fixture(autouse=True)
def cache(tmp_path, monkeypatch):
    """A stand-in body's exported form stays out of the checkout's
    cache."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_sharded_verify_batch_async_pipelines():
    """Two dispatches can be in flight before either resolves, and each
    resolver returns its own batch's (unpadded) verdicts."""
    sv = ShardedVerifier(_StubVerifier())
    n = 20
    rounds = np.arange(1, n + 1, dtype=np.uint64)
    sigs_a = np.zeros((n, 96), dtype=np.uint8)
    sigs_a[3, 0] = 1
    sigs_b = np.zeros((n, 96), dtype=np.uint8)
    sigs_b[7, 0] = 1
    pa = sv.verify_batch_async(rounds, sigs_a)
    pb = sv.verify_batch_async(rounds, sigs_b)
    ok_b = pb()          # resolve out of dispatch order
    ok_a = pa()
    assert ok_a.shape == (n,) and ok_b.shape == (n,)
    assert not ok_a[3] and ok_a.sum() == n - 1
    assert not ok_b[7] and ok_b.sum() == n - 1


def test_sharded_verify_batch_plumbing():
    import jax
    assert len(jax.devices()) == 8
    sv = ShardedVerifier(_StubVerifier())
    n = 20   # not a multiple of 8: exercises the pad path
    rounds = np.arange(1, n + 1, dtype=np.uint64)
    sigs = np.zeros((n, 96), dtype=np.uint8)
    sigs[5, 0] = 1   # odd first byte -> invalid
    ok = sv.verify_batch(rounds, sigs)
    assert ok.shape == (n,)
    assert not ok[5] and ok.sum() == n - 1


def test_rows_charged_is_what_a_dispatch_is_padded_to():
    """The answer the catch-up cuts its segments by (ISSUE 30) is the
    size the sharded dispatch compiles and pads to: every device's
    slice in the verifier's own bucket."""
    from drand_tpu.verify import _bucket
    sv = ShardedVerifier(_StubVerifier())
    n = 20
    assert sv.rows_charged(n) == 8 * _bucket(3)
    sv.verify_batch(np.arange(1, n + 1, dtype=np.uint64),
                    np.zeros((n, 96), dtype=np.uint8))
    assert list(sv._kernels) == [sv.rows_charged(n)]
    assert sv.rows_charged(8 * _bucket(3) + 1) == 2 * sv.rows_charged(n)


def test_sharded_kernel_inputs_actually_sharded():
    """The compiled sharded kernel receives mesh-sharded inputs (not
    arrays silently de-sharded back to one device)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sv = ShardedVerifier(_StubVerifier())
    n = 16
    rounds = np.arange(1, n + 1, dtype=np.uint64)
    sigs = np.zeros((n, 96), dtype=np.uint8)
    ok = sv.verify_batch(rounds, sigs)
    assert ok.shape == (n,)
    # the jit was built with explicit mesh shardings (batch padded to
    # devices x bucket granularity = 64): run it on mesh-sharded inputs
    # and confirm the OUTPUT comes back sharded over the round axis —
    # a de-sharded kernel would place everything on one device
    import jax.numpy as jnp
    (m, kern), = sv._kernels.items()
    shard = NamedSharding(sv.mesh, P("rounds", None))
    msgs = jax.device_put(jnp.zeros((m, 8), jnp.uint8), shard)
    sgs = jax.device_put(jnp.zeros((m, 96), jnp.uint8), shard)
    repl = NamedSharding(sv.mesh, P())
    out = kern(msgs, sgs, jax.device_put(sv.verifier._pk, repl))
    assert out.sharding.is_equivalent_to(
        NamedSharding(sv.mesh, P("rounds")), out.ndim)


def test_sharded_partials_mesh_factorization():
    """The 2-D mesh factors (rounds, signers) correctly for several
    shapes, including ones that need padding on both axes."""
    import jax
    from unittest import mock

    sv = ShardedVerifier(_StubVerifier())
    shapes_seen = []

    def fake_kernel(commits, dst, shape, shardings, msg_len=32):
        import jax.numpy as jnp

        def run(m, s, i, dev_commits):
            shapes_seen.append((shape, m.shape))
            return (i % 2) == 0
        if shardings is None:
            return jax.jit(run)
        sh3, sh2 = shardings
        repl = jax.sharding.NamedSharding(sh2.mesh,
                                          jax.sharding.PartitionSpec())
        csh = (repl,)
        return jax.jit(run, in_shardings=(sh3, sh3, sh2, csh),
                       out_shardings=sh2)

    with mock.patch.object(ShardedVerifier, "_partials_kernel",
                           side_effect=fake_kernel), \
         mock.patch.object(ShardedVerifier, "_dev_commits",
                           side_effect=lambda c: (np.zeros(32, np.int32),)):
        for (R, S) in [(2, 4), (3, 3), (1, 16), (5, 2)]:
            msgs = np.zeros((R, S, 32), dtype=np.uint8)
            sigs = np.zeros((R, S, 96), dtype=np.uint8)
            idxs = np.arange(R * S, dtype=np.int32).reshape(R, S)
            ok = sv.verify_partials(msgs, sigs, idxs, ["commits"], b"DST")
            assert ok.shape == (R, S)
            assert (ok == ((idxs % 2) == 0)).all(), (R, S)


def test_sharded_partials_shared_mesh_factorization():
    """verify_partials_shared (ISSUE 7): rounds-major digests + signer
    table on the 2-D mesh — shapes, padding, and unpadding with a stub
    kernel (crypto parity is --runslow in test_parallel.py)."""
    import jax
    from unittest import mock

    sv = ShardedVerifier(_StubVerifier())

    def fake_kernel(n, dst, shape, shardings, msg_len=32):
        import jax.numpy as jnp

        def run(rm, s, i, tx, ty, tinf):
            # verdict depends on BOTH the per-round digest (broadcast
            # across signers) and the per-partial index, so a transposed
            # or mis-padded wiring fails loudly
            return ((i % 2) == 0) & (rm[:, :1] % 2 == 0)
        if shardings is None:
            return jax.jit(run)
        shm, sh3, sh2, repl = shardings
        return jax.jit(run, in_shardings=(shm, sh3, sh2, repl, repl, repl),
                       out_shardings=sh2)

    table = (np.zeros((16, 32), np.int32), np.zeros((16, 32), np.int32),
             np.zeros(16, bool))
    with mock.patch.object(ShardedVerifier, "_shared_kernel",
                           side_effect=fake_kernel):
        for (R, S) in [(2, 4), (3, 3), (1, 16), (5, 2), (7, 16)]:
            rmsgs = np.zeros((R, 32), dtype=np.uint8)
            rmsgs[:, 0] = np.arange(R) % 2          # odd rounds invalid
            sigs = np.zeros((R, S, 96), dtype=np.uint8)
            idxs = np.arange(R * S, dtype=np.int32).reshape(R, S) % 16
            ok = sv.verify_partials_shared(rmsgs, sigs, idxs, table, b"DST")
            assert ok.shape == (R, S), (R, S)
            want = ((idxs % 2) == 0) & ((np.arange(R) % 2) == 0)[:, None]
            assert (ok == want).all(), (R, S)


def test_shared_partials_artifact_names_stable():
    n1 = ShardedVerifier.shared_partials_name(1024, 16, 16, b"DST")
    n2 = ShardedVerifier.shared_partials_name(1024, 16, 16, b"DST")
    assert n1 == n2 and "1024x16" in n1 and "n16" in n1
    assert ShardedVerifier.shared_partials_name(
        1024, 16, 16, b"OTHER") != n1

"""Mesh-sharded batched verification.

`ShardedVerifier` wraps `drand_tpu.verify.Verifier` with a 1-D device
mesh over the round axis: inputs are placed shard-by-shard, every device
verifies its slice of the chain segment, and the boolean results gather
back.  On a multi-chip host this is the throughput path for catch-up
sync and the check-chain audit; on one chip it degrades to the plain
verifier.

The signer dimension of t-of-n partial verification shards the same way
(`verify_partials`): rounds x signers lays out on a 2-D mesh so both the
catch-up and the aggregation workloads scale with chips.
"""

from __future__ import annotations

import numpy as np


def _pad2(arr: np.ndarray, rp: int, sp: int) -> np.ndarray:
    """Edge-pad the two leading (rounds, signers) axes up to (rp, sp)."""
    r, s = arr.shape[:2]
    widths = [(0, rp - r), (0, sp - s)] + [(0, 0)] * (arr.ndim - 2)
    return np.pad(arr, widths, mode="edge")


class ShardedVerifier:
    def __init__(self, verifier, devices=None, axis: str = "rounds"):
        import jax
        from jax.sharding import Mesh

        self.verifier = verifier
        devs = list(devices if devices is not None else jax.devices())
        self.n_dev = len(devs)
        self.axis = axis
        self.mesh = Mesh(np.array(devs), (axis,))
        self._kernels = {}         # rows over the mesh -> compiled program
        self._pk_placed = None     # `verifier._pk`, on every device

    def _named(self, *spec):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P(*spec))

    def over(self, call, structs):
        """(`call` as one jitted program over the mesh, the structs it
        takes): `call` is one device's whole verify program at `structs`
        (`Verifier.build` hands its exported form's), and every device
        runs it on its slice of the round axis.

        `shard_map`, not sharding propagation: rounds are independent,
        and the TPU's compiler refuses to partition a Pallas kernel by
        itself ("Mosaic kernels cannot be automatically partitioned").
        The shardings are explicit on both sides, so an input that came
        de-sharded would be refused, not silently gathered."""
        import jax
        from jax.sharding import PartitionSpec as P

        msgs, sigs, pk = structs
        on_each = jax.tree_util.tree_map
        body = jax.shard_map(
            call, mesh=self.mesh,
            in_specs=(P(self.axis, None), P(self.axis, None),
                      on_each(lambda _: P(), pk)),
            out_specs=P(self.axis), check_vma=False)
        rows = self._named(self.axis, None)
        program = jax.jit(
            body, out_shardings=self._named(self.axis),
            in_shardings=(rows, rows, on_each(lambda _: self._named(), pk)))

        def wide(s):
            return jax.ShapeDtypeStruct(
                (s.shape[0] * self.n_dev, *s.shape[1:]), s.dtype)
        return program, (wide(msgs), wide(sigs), pk)

    def build(self, n: int) -> dict:
        """The program of `n` rows a DEVICE, installed for `n` times the
        mesh; `Verifier.build`'s algorithm, record (with `devices`) and
        spans.  The exported form is the one-device program's own: a
        process on a host of four loads the file a one-chip process
        wrote, or writes the one it will load, and traces nothing
        twice."""
        if self.n_dev == 1:
            return self.verifier.build(n)
        return self.verifier.build(n, mesh=self)

    def _kernel(self, m: int):
        if m not in self._kernels:
            self.build(m // self.n_dev)
        return self._kernels[m]

    def rows_charged(self, n: int) -> int:
        """`Verifier.rows_charged` on this mesh: every device's equal
        slice is padded into the verifier's program (its own answer; a
        verifier that gives none is charged the slice)."""
        per_dev = -(-n // self.n_dev)
        charged = getattr(self.verifier, "rows_charged", None)
        return (charged(per_dev) if charged else per_dev) * self.n_dev

    def verify_batch_async(self, rounds, sigs, prev_sigs=None):
        """Dispatch a sharded batch verify without blocking; returns a
        zero-arg callable yielding bool[B] (same contract as
        Verifier.verify_batch_async, so the sync manager's pipeline
        overlaps transfer with compute on multi-device hosts too).

        Pads the batch to the mesh times the verifier's program
        (`rows_charged`), so every device holds an equal slice; padded
        rows are copies of the last and their verdicts are dropped."""
        import time

        import jax

        from drand_tpu import tracing
        from drand_tpu.profiling import DISPATCH, record_dispatch
        from drand_tpu.verify import pad_rows

        rounds = np.asarray(rounds, dtype=np.uint64)
        n = rounds.shape[0]
        if n == 0 or self.n_dev == 1:
            return self.verifier.verify_batch_async(rounds, sigs, prev_sigs)
        v = self.verifier
        beacon_id = getattr(v, "beacon_id", "")
        # `verify.dispatch` as on one device (`Verifier.verify_batch_async`)
        # with `bucket` the rows charged over the whole mesh; its child
        # `verify.shard_put` is the placement of every device's slice,
        # straight from the host's rows (no stop on the first device)
        with tracing.span("verify.dispatch", beacon_id=beacon_id, n=n,
                          devices=self.n_dev) as sp:
            m = self.rows_charged(n)
            msgs, sigs = pad_rows(v.messages(rounds, prev_sigs), sigs, m)
            msgs = np.ascontiguousarray(msgs, dtype=np.uint8)
            sigs = np.ascontiguousarray(sigs, dtype=np.uint8)
            t0 = time.perf_counter()
            kernel = self._kernel(m)
            t1 = time.perf_counter()
            with tracing.span("verify.shard_put", devices=self.n_dev,
                              bytes=msgs.nbytes + sigs.nbytes):
                if self._pk_placed is None:
                    # a replicated runtime argument, placed once
                    self._pk_placed = jax.device_put(v._pk, self._named())
                rows = self._named(self.axis, None)
                placed = jax.device_put((msgs, sigs), rows)
            flight, in_flight, behind_other = DISPATCH.enqueue(v)
            ok = kernel(*placed, self._pk_placed)
            dispatch_s = time.perf_counter() - t1
            sp.set(bucket=m, pad_rows=m - n, per_dev=m // self.n_dev,
                   prepare_s=t0 - sp.start_mono, enqueue_s=dispatch_s,
                   msg_bytes=msgs.shape[1],
                   h2d_bytes=msgs.nbytes + sigs.nbytes, dispatches=1,
                   in_flight=in_flight, behind_other=behind_other)
        done = [False]

        def resolve():
            t1 = time.perf_counter()
            jax.block_until_ready(ok)
            # `verify.gather` begins with every shard's verdicts ready:
            # it is the copies to the host and their joining in round
            # order, and holds no wait for the device
            t2 = time.perf_counter()
            out = np.asarray(ok)[:n]
            if not done[0]:
                done[0] = True
                t3 = time.perf_counter()
                DISPATCH.resolved(flight)
                resolved = tracing.record_span("verify.resolve", t1, t3,
                                               beacon_id=beacon_id, n=n,
                                               bucket=m)
                tracing.record_span("verify.gather", t2, t3, parent=resolved,
                                    devices=self.n_dev)
                record_dispatch("sharded", n, m, dispatch_s + (t3 - t1),
                                devices=self.n_dev, per_dev=m // self.n_dev)
            return out
        return resolve

    def verify_batch(self, rounds, sigs, prev_sigs=None):
        """Same contract as Verifier.verify_batch, sharded over rounds."""
        return self.verify_batch_async(rounds, sigs, prev_sigs)()

    # -- t-of-n partial verification on a 2-D rounds x signers mesh ----------

    def verify_partials(self, msgs, sigs, indices, commits, dst):
        """Batched tbls partial verification sharded on a 2-D mesh.

        msgs [R, S, L] uint8 digests, sigs [R, S, 96] uint8 (index prefix
        stripped), indices [R, S] int32, commits = golden G1 commitment
        points (the group's public polynomial), dst = G2 hash suite DST.
        Returns bool [R, S].

        The device mesh factors as (rounds, signers): the signer axis gets
        the largest factor of n_dev that fits S, rounds take the rest —
        both catch-up audits (R large) and live aggregation (S large)
        shard fully.
        """
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        msgs = np.asarray(msgs, dtype=np.uint8)
        sigs = np.asarray(sigs, dtype=np.uint8)
        indices = np.asarray(indices, dtype=np.int32)
        R, S = indices.shape
        if self.n_dev == 1:
            return np.asarray(self._partials_kernel(
                commits, dst, (R, S), None, msgs.shape[2])(
                jnp.asarray(msgs), jnp.asarray(sigs), jnp.asarray(indices),
                self._dev_commits(commits)))[:R, :S]
        ds = next(d for d in range(min(self.n_dev, S), 0, -1)
                  if self.n_dev % d == 0)
        dr = self.n_dev // ds
        Rp = -(-R // dr) * dr
        Sp = -(-S // ds) * ds
        if (Rp, Sp) != (R, S):
            msgs = _pad2(msgs, Rp, Sp)
            sigs = _pad2(sigs, Rp, Sp)
            indices = _pad2(indices, Rp, Sp)
        devs = np.array(jax.devices()[:self.n_dev]).reshape(dr, ds)
        mesh = Mesh(devs, ("rounds", "signers"))
        sh3 = NamedSharding(mesh, P("rounds", "signers", None))
        sh2 = NamedSharding(mesh, P("rounds", "signers"))
        kern = self._partials_kernel(commits, dst, (Rp, Sp), (sh3, sh2),
                                     msgs.shape[2])
        repl = NamedSharding(mesh, P())
        dev_commits = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, repl), self._dev_commits(commits))
        ok = kern(jax.device_put(jnp.asarray(msgs), sh3),
                  jax.device_put(jnp.asarray(sigs), sh3),
                  jax.device_put(jnp.asarray(indices), sh2),
                  dev_commits)
        return np.asarray(ok)[:R, :S]

    def verify_partials_shared(self, round_msgs, sigs, indices, table, dst):
        """Rounds-major tabled partial verification on the 2-D mesh: one
        digest per round hashes ONCE (sharded on the rounds axis) and
        broadcasts across the signer axis in-kernel; signer public keys
        gather from the precomputed per-signer table instead of riding
        the Horner eval in-batch.

        round_msgs [R, L] uint8 (one digest per round), sigs [R, S, 96],
        indices [R, S] int32, table = (tx, ty, tinf) signer-key arrays
        (drand_tpu/beacon/signer_table.py), dst = G2 hash suite DST.
        Returns bool [R, S] — bit-identical verdicts to verify_partials
        on the equivalent per-partial batch.
        """
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        round_msgs = np.asarray(round_msgs, dtype=np.uint8)
        sigs = np.asarray(sigs, dtype=np.uint8)
        indices = np.asarray(indices, dtype=np.int32)
        R, S = indices.shape
        tx, ty, tinf = (np.asarray(a) for a in table)
        if self.n_dev == 1:
            kern = self._shared_kernel(tx.shape[0], dst, (R, S), None,
                                       round_msgs.shape[1])
            return np.asarray(kern(
                jnp.asarray(round_msgs), jnp.asarray(sigs),
                jnp.asarray(indices), jnp.asarray(tx), jnp.asarray(ty),
                jnp.asarray(tinf)))[:R, :S]
        ds = next(d for d in range(min(self.n_dev, S), 0, -1)
                  if self.n_dev % d == 0)
        dr = self.n_dev // ds
        Rp = -(-R // dr) * dr
        Sp = -(-S // ds) * ds
        if (Rp, Sp) != (R, S):
            sigs = _pad2(sigs, Rp, Sp)
            indices = _pad2(indices, Rp, Sp)
            if Rp != R:
                round_msgs = np.pad(round_msgs, [(0, Rp - R), (0, 0)],
                                    mode="edge")
        devs = np.array(jax.devices()[:self.n_dev]).reshape(dr, ds)
        mesh = Mesh(devs, ("rounds", "signers"))
        shm = NamedSharding(mesh, P("rounds", None))
        sh3 = NamedSharding(mesh, P("rounds", "signers", None))
        sh2 = NamedSharding(mesh, P("rounds", "signers"))
        repl = NamedSharding(mesh, P())
        kern = self._shared_kernel(tx.shape[0], dst, (Rp, Sp),
                                   (shm, sh3, sh2, repl),
                                   round_msgs.shape[1])
        ok = kern(jax.device_put(jnp.asarray(round_msgs), shm),
                  jax.device_put(jnp.asarray(sigs), sh3),
                  jax.device_put(jnp.asarray(indices), sh2),
                  jax.device_put(jnp.asarray(tx), repl),
                  jax.device_put(jnp.asarray(ty), repl),
                  jax.device_put(jnp.asarray(tinf), repl))
        return np.asarray(ok)[:R, :S]

    @staticmethod
    def shared_partials_name(Rp: int, Sp: int, n: int, dst: bytes,
                             msg_len: int = 32) -> str:
        """AOT cache name for a sharded SHARED-HASH tabled partials
        executable at the padded (Rp, Sp) shape (n = table size)."""
        import hashlib as _hl
        dst_h = _hl.sha256(dst).hexdigest()[:8]
        return (f"sharded-partials-shared-{Rp}x{Sp}-n{n}-{dst_h}"
                f"-m{msg_len}")

    def _shared_kernel(self, n: int, dst, shape, shardings,
                       msg_len: int = 32):
        """Shared-hash tabled partial-verify kernel.  The signer-key
        table is a RUNTIME argument (one executable serves every group
        and epoch — same design as the runtime commitments of
        _partials_kernel), so the cache key is shapes only."""
        import jax

        from drand_tpu.ops import bls as BLS

        key = ("shared", n, dst, shape, shardings is not None, msg_len)
        cache = getattr(self, "_pkernels", None)
        if cache is None:
            cache = self._pkernels = {}
        if key not in cache:
            def run(rm, s, i, tx, ty, tinf):
                return BLS.verify_partial_g2_sigs_shared(
                    rm, s, i, (tx, ty, tinf), dst)

            if shardings is None:
                cache[key] = jax.jit(run)
            else:
                import jax.numpy as jnp

                from drand_tpu import aot
                shm, sh3, sh2, repl = shardings
                R, S = shape
                name = self.shared_partials_name(R, S, n, dst, msg_len)
                fn = aot.load(name)
                if fn is None:
                    fn = jax.jit(
                        run,
                        in_shardings=(shm, sh3, sh2, repl, repl, repl),
                        out_shardings=sh2,
                    ).lower(
                        jax.ShapeDtypeStruct((R, msg_len), jnp.uint8),
                        jax.ShapeDtypeStruct((R, S, 96), jnp.uint8),
                        jax.ShapeDtypeStruct((R, S), jnp.int32),
                        jax.ShapeDtypeStruct((n, 32), jnp.int32),
                        jax.ShapeDtypeStruct((n, 32), jnp.int32),
                        jax.ShapeDtypeStruct((n,), jnp.bool_)).compile()
                    try:
                        aot.save(name, fn)
                    except Exception as e:
                        import sys
                        print(f"drand_tpu.aot: sharded shared-partials "
                              f"save failed ({type(e).__name__}: {e}); "
                              "continuing without persistence",
                              file=sys.stderr)
                cache[key] = fn
        return cache[key]

    @staticmethod
    def partials_name(Rp: int, Sp: int, t: int, dst: bytes,
                      msg_len: int = 32) -> str:
        """AOT cache name for a sharded partials executable at the PADDED
        shape (Rp, Sp).  Single source of truth — the warm-persistence
        gate in __graft_entry__ queries this instead of duplicating the
        formula (ADVICE r4)."""
        import hashlib as _hl
        dst_h = _hl.sha256(dst).hexdigest()[:8]
        return f"sharded-partials-{Rp}x{Sp}-t{t}-{dst_h}-m{msg_len}"

    @classmethod
    def partials_artifact_name(cls, n_dev: int, R: int, S: int, t: int,
                               dst: bytes, msg_len: int = 32) -> str:
        """Name for the executable `verify_partials` on an n_dev-device
        host would build for a logical (R, S) batch — applies the same
        mesh factorization + padding as verify_partials."""
        ds = next(d for d in range(min(n_dev, S), 0, -1) if n_dev % d == 0)
        dr = n_dev // ds
        return cls.partials_name(-(-R // dr) * dr, -(-S // ds) * ds,
                                 t, dst, msg_len)

    def _dev_commits(self, commits):
        """Golden commitment points -> device affine pytree (cached by
        wire bytes; conversion is host bignum math)."""
        from drand_tpu.crypto.bls12381 import curve as GC
        from drand_tpu.ops import bls as BLS
        key = tuple(GC.g1_to_bytes(c) for c in commits)
        cache = getattr(self, "_pcommits", None)
        if cache is None:
            cache = self._pcommits = {}
        if key not in cache:
            cache[key] = tuple(BLS._const_g1_affine(c) for c in commits)
        return cache[key]

    def _partials_kernel(self, commits, dst, shape, shardings,
                         msg_len: int = 32):
        """Partial-verify kernel: commitments are RUNTIME arguments (one
        executable serves every group — same design as the runtime public
        key), so the cache key is shapes + threshold only and the
        mesh-sharded form persists through the AOT cache."""
        import jax

        from drand_tpu.ops import bls as BLS

        key = ("partials", len(commits), dst, shape,
               shardings is not None, msg_len)
        cache = getattr(self, "_pkernels", None)
        if cache is None:
            cache = self._pkernels = {}
        if key not in cache:
            def run(m, s, i, dev_commits):
                return BLS.verify_partial_g2_sigs(m, s, i,
                                                  list(dev_commits), dst)

            dev_commits = self._dev_commits(commits)
            cstruct = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                dev_commits)
            if shardings is None:
                cache[key] = jax.jit(run)
            else:
                from drand_tpu import aot
                sh3, sh2 = shardings
                repl = jax.sharding.NamedSharding(
                    sh2.mesh, jax.sharding.PartitionSpec())
                csh = jax.tree_util.tree_map(lambda _: repl, dev_commits)
                R, S = shape
                name = self.partials_name(R, S, len(commits), dst, msg_len)
                fn = aot.load(name)
                if fn is None:
                    import jax.numpy as jnp
                    fn = jax.jit(
                        run, in_shardings=(sh3, sh3, sh2, csh),
                        out_shardings=sh2,
                    ).lower(
                        jax.ShapeDtypeStruct((R, S, msg_len), jnp.uint8),
                        jax.ShapeDtypeStruct((R, S, 96), jnp.uint8),
                        jax.ShapeDtypeStruct((R, S), jnp.int32),
                        cstruct).compile()
                    try:
                        aot.save(name, fn)
                    except Exception as e:
                        import sys
                        print(f"drand_tpu.aot: sharded partials save "
                              f"failed ({type(e).__name__}: {e}); "
                              "continuing without persistence",
                              file=sys.stderr)
                cache[key] = fn
        return cache[key]

    def _verify_single_host(self, round_, sig, prev_sig):
        return self.verifier._verify_single_host(round_, sig, prev_sig)

    def verify_chain_segment(self, start_round: int, sigs, anchor_prev_sig):
        """Same anchor/recursion semantics as the single-device verifier —
        reused directly so the irregular-anchor handling lives once; only
        verify_batch (sharded here) differs."""
        from drand_tpu.verify import Verifier
        return Verifier.verify_chain_segment(
            self, start_round, np.asarray(sigs), anchor_prev_sig)

    def verify_chain_segment_async(self, start_round: int, sigs,
                                   anchor_prev_sig):
        from drand_tpu.verify import Verifier
        return Verifier.verify_chain_segment_async(
            self, start_round, np.asarray(sigs), anchor_prev_sig)

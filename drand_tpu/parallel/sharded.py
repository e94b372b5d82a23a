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

    def _shard(self, arr):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(arr, NamedSharding(self.mesh, P(self.axis)))

    def _run_fn(self):
        """The verifier's pure (msgs, sigs, pk) -> bool[B] body
        (`Verifier._run_fn`; stubs provide the same hook)."""
        return self.verifier._run_fn()

    def _sharded_kernel(self, m: int):
        """The verify body compiled with explicit mesh in/out shardings.

        Verifier._kernel's executables are lowered from sharding-less
        single-device ShapeDtypeStructs: a `Compiled` does not
        re-specialize, so calling one with NamedSharding multi-device
        inputs either fails or silently de-shards the throughput path.
        The multi-device path therefore compiles its own kernels, keyed
        by batch size (mesh/axis are fixed per ShardedVerifier).  On the
        CPU tier they persist through the same serialized-executable
        cache as the single-device path (the mesh shape is part of the
        cache name; aot's env tag already pins platform + device count);
        on the TPU JAX's persistent cache is the only one."""
        cache = getattr(self, "_skernels", None)
        if cache is None:
            cache = self._skernels = {}
        if m not in cache:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            from drand_tpu import aot
            from drand_tpu.ops.pallas_field import use_pallas

            cpu_tier = not use_pallas()
            name = (f"sharded-{self.axis}{self.n_dev}-"
                    f"{self.verifier._aot_name(m)}")
            fn = aot.load(name) if cpu_tier else None
            if fn is None:
                shard_in = NamedSharding(self.mesh, P(self.axis, None))
                out_sh = NamedSharding(self.mesh, P(self.axis))
                repl = NamedSharding(self.mesh, P())
                pk_sh = jax.tree_util.tree_map(lambda _: repl,
                                               self.verifier._pk)
                # shard_map, not sharding propagation: every device runs
                # the whole body on its slice of the round axis (rounds
                # are independent), and the TPU's compiler refuses to
                # partition a Pallas kernel by itself ("Mosaic kernels
                # cannot be automatically partitioned")
                body = jax.shard_map(
                    self._run_fn(), mesh=self.mesh,
                    in_specs=(P(self.axis, None), P(self.axis, None),
                              jax.tree_util.tree_map(lambda _: P(),
                                                     self.verifier._pk)),
                    out_specs=P(self.axis), check_vma=False)
                fn = jax.jit(
                    body,
                    in_shardings=(shard_in, shard_in, pk_sh),
                    out_shardings=out_sh,
                ).lower(
                    jax.ShapeDtypeStruct((m, self.verifier._msg_len()),
                                         "uint8"),
                    jax.ShapeDtypeStruct((m, self.verifier.shape.sig_len),
                                         "uint8"),
                    self.verifier._pk_struct()).compile()
                if cpu_tier:
                    try:
                        aot.save(name, fn)
                    except Exception as e:
                        import sys
                        print(f"drand_tpu.aot: sharded kernel save failed "
                              f"({type(e).__name__}: {e}); continuing "
                              "without persistence", file=sys.stderr)
            cache[m] = fn
        return cache[m]

    def rows_charged(self, n: int) -> int:
        """`Verifier.rows_charged` on this mesh: every device's equal
        slice is padded into the verifier's program."""
        from drand_tpu.verify import _bucket
        return _bucket(-(-n // self.n_dev)) * self.n_dev

    def verify_batch_async(self, rounds, sigs, prev_sigs=None):
        """Dispatch a sharded batch verify without blocking; returns a
        zero-arg callable yielding bool[B] (same contract as
        Verifier.verify_batch_async, so the sync manager's one-in-flight
        pipeline overlaps transfer with compute on multi-device hosts
        too).

        Pads the batch to a multiple of the mesh size so every device
        holds an equal slice (the kernel is branchless — padded lanes
        just redo the last element's work)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        rounds = np.asarray(rounds, dtype=np.uint64)
        n = rounds.shape[0]
        if n == 0 or self.n_dev == 1:
            return self.verifier.verify_batch_async(rounds, sigs, prev_sigs)
        v = self.verifier
        msgs = v.messages(rounds, prev_sigs)
        # pad to devices * bucket granularity
        m = self.rows_charged(n)
        per_dev = m // self.n_dev
        if m != n:
            pad = m - n
            msgs = np.concatenate([msgs, np.repeat(msgs[-1:], pad, 0)])
            sigs = np.concatenate([sigs, np.repeat(sigs[-1:], pad, 0)])
        kern = self._sharded_kernel(m)
        # pk is a replicated runtime argument (verify.py batch-3 design);
        # only the round axis shards
        repl = NamedSharding(self.mesh, P())
        pk = jax.tree_util.tree_map(lambda a: jax.device_put(a, repl),
                                    v._pk)
        import time as _time
        t0 = _time.perf_counter()
        ok = kern(self._shard(jnp.asarray(msgs, jnp.uint8)),
                  self._shard(jnp.asarray(sigs, jnp.uint8)),
                  pk)
        dispatch_s = _time.perf_counter() - t0
        done = [False]

        def resolve():
            t1 = _time.perf_counter()
            out = np.asarray(ok)[:n]
            if not done[0]:
                done[0] = True
                from drand_tpu.profiling import record_dispatch
                record_dispatch("sharded", n, m,
                                dispatch_s + (_time.perf_counter() - t1),
                                devices=self.n_dev, per_dev=per_dev)
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

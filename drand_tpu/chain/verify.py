"""Scheme-aware beacon verification: single and batched.

Counterpart of `chain/verify.go` — the single choke point all beacon
verification flows through — except the primitive here is batched:
`ChainVerifier.verify_batch` checks B beacons in one device call
(the reference loops `VerifyBeacon` per round: `sync_manager.go:397-399`).
"""

from __future__ import annotations

import hashlib
import struct

import numpy as np

import os

from drand_tpu import log as dlog
from drand_tpu.chain.beacon import Beacon
from drand_tpu.chain.scheme import Scheme
from drand_tpu.verify import Verifier

# batches at or below this size verify on the host (latency path)
_HOST_VERIFY_MAX = int(os.environ.get("DRAND_TPU_HOST_VERIFY_MAX", "32"))

_NATIVE_WARNED = False


def _warn_native_unavailable(reason: str) -> None:
    """One-time loud warning: without the native C++ tier every live-path
    verify falls back to the ~175 ms pure-python golden model, and
    host-side small batches cost seconds instead of milliseconds."""
    global _NATIVE_WARNED
    if _NATIVE_WARNED:
        return
    _NATIVE_WARNED = True
    dlog.get("chain").warning(
        "native C++ verification tier unavailable (%s); the live path is "
        "falling back to the pure-python golden model (~175 ms/verify vs "
        "~6 ms native). Install g++ and delete any stale build under "
        "drand_tpu/native/ to restore the fast path.", reason)


class ChainVerifier:
    """Verifier bound to one (scheme, distributed public key).

    `beacon_id` only labels tracing spans / stage histograms — chain
    verification itself is beacon-id-agnostic."""

    def __init__(self, scheme: Scheme, public_key_bytes: bytes,
                 beacon_id: str = ""):
        from drand_tpu.crypto.bls12381 import curve as GC
        self.scheme = scheme
        self.beacon_id = beacon_id
        self.public_key_bytes = public_key_bytes
        if scheme.shape.sig_on_g1:
            self._pk_point = GC.g2_from_bytes(public_key_bytes)
        else:
            self._pk_point = GC.g1_from_bytes(public_key_bytes)
        self._lazy_verifier = None

    @property
    def _verifier(self) -> Verifier:
        """The batched device verifier, built on first batched use — the
        live round loop never pays an XLA compile.  On a multi-device host
        the batch shards over a 1-D round-axis mesh (ShardedVerifier), so
        catch-up sync and check-chain scale with chips (SURVEY.md §5.8)."""
        if self._lazy_verifier is None:
            import jax
            v = Verifier(self._pk_point, self.scheme.shape,
                         single_host=self._verify_single,
                         beacon_id=self.beacon_id)
            if len(jax.devices()) > 1:
                from drand_tpu.parallel import ShardedVerifier
                v = ShardedVerifier(v)
            self._lazy_verifier = v
        return self._lazy_verifier

    # -- digest (host scalar path; device batches build their own) ----------

    def digest_message(self, round_: int, prev_sig: bytes) -> bytes:
        """sha256(prev_sig || be64(round)) or sha256(be64(round)) when the
        scheme decouples the previous signature (`chain/verify.go:24-32`)."""
        h = hashlib.sha256()
        if not self.scheme.decouple_prev_sig:
            h.update(prev_sig)
        h.update(struct.pack(">Q", round_))
        return h.digest()

    # -- verification -------------------------------------------------------

    def verify_beacon(self, beacon: Beacon) -> bool:
        """Single-beacon check — the latency path of the dual backend.

        Live round production verifies ONE recovered signature every
        period; routing that through the batched device kernel would pay
        an XLA compile and a device round-trip for a batch of one, so the
        scalar path stays on the host: the native C++ tier
        (drand_tpu/native, ~30x the golden model) when the toolchain
        built it, the golden model otherwise.  Catch-up/sync uses
        `verify_beacons`/`verify_chain_segment` (throughput path, device).
        """
        from drand_tpu import tracing
        with tracing.span("verify.beacon", beacon_id=self.beacon_id,
                          round_=beacon.round):
            return self._verify_single(beacon.round, beacon.signature,
                                       beacon.previous_sig)[0]

    def _verify_single(self, round_: int, signature: bytes,
                       previous_sig: bytes) -> tuple[bool, str]:
        """One round on the host: (verdict, the tier that gave it).  Also
        the batched verifier's check of a shape-irregular row (round 1
        over the 32-byte genesis seed)."""
        msg = self.digest_message(round_, previous_sig)
        native_ok = False
        try:
            from drand_tpu import native
            native_ok = native.available()
        except Exception as e:
            _warn_native_unavailable(f"import failed: {type(e).__name__}: {e}")
        if native_ok:
            try:
                check = native.verify_g1 if self.scheme.shape.sig_on_g1 \
                    else native.verify_g2
                return bool(check(self.public_key_bytes, msg, signature,
                                  self.scheme.shape.dst)), "native"
            except Exception:
                # a per-call failure is NOT tier unavailability: log it
                # (with traceback) and fall back for this beacon only
                dlog.get("chain").exception(
                    "native verify raised; falling back to the golden "
                    "model for this beacon")
        else:
            _warn_native_unavailable("native.available() returned False "
                                     "(g++ build failed or missing)")
        from drand_tpu.crypto import sign as S
        check = S.bls_verify_g1 if self.scheme.shape.sig_on_g1 \
            else S.bls_verify
        try:
            return bool(check(self._pk_point, msg, signature)), "golden"
        except Exception:
            return False, "golden"

    def rows_charged(self, n: int) -> int:
        """Rows of verification a batch of `n` costs on the tier that
        will take it: `n` itself on the host tier (a small batch before
        the device verifier exists), else the device verifier's program
        (`Verifier.rows_charged`).  The catch-up cuts its segments by
        it."""
        if n <= _HOST_VERIFY_MAX and self._lazy_verifier is None:
            return n
        return self._verifier.rows_charged(n)

    def verify_beacons_async(self, beacons: list[Beacon]):
        """Dispatch a batch verify without blocking; returns a zero-arg
        callable that blocks and yields bool[B].

        Beacons whose previous signature has an irregular length (round 1
        links to the 32-byte genesis seed) take the host scalar path
        eagerly; the uniform rest dispatches to the device asynchronously
        (both the single-device Verifier and the multi-device
        ShardedVerifier implement verify_batch_async).

        EAGER-HOST EXCEPTION to the non-blocking contract: batches at or
        below _HOST_VERIFY_MAX (before the device kernel exists) and the
        irregular elements above verify synchronously AT DISPATCH TIME —
        up to ~175 ms each on the golden-model fallback.  Callers on an
        event loop (the sync manager's flush) tolerate this because it
        only happens for tiny batches or the one genesis-linked round;
        a large mixed batch dispatches its regular majority async."""
        if not beacons:
            return lambda: np.zeros(0, dtype=bool)
        if len(beacons) <= _HOST_VERIFY_MAX and self._lazy_verifier is None:
            # small batches (live gaps, short syncs) stay on the host UNTIL
            # the device kernel exists: the one-time XLA compile only pays
            # off when real catch-up segments amortize it — but once
            # compiled, the device call beats 32 sequential host pairings
            out = np.array([self.verify_beacon(b) for b in beacons])
            return lambda: out
        sig_len = self.scheme.sig_len
        if not self.scheme.decouple_prev_sig:
            irregular = [i for i, b in enumerate(beacons)
                         if len(b.previous_sig) != sig_len]
            if irregular:
                regular = [i for i in range(len(beacons))
                           if i not in set(irregular)]
                pending = self.verify_beacons_async(
                    [beacons[i] for i in regular]) if regular else None
                out = np.zeros(len(beacons), dtype=bool)
                for i in irregular:
                    out[i] = self.verify_beacon(beacons[i])

                def resolve():
                    if pending is not None:
                        out[np.asarray(regular)] = pending()
                    return out

                return resolve
        rounds = np.array([b.round for b in beacons], dtype=np.uint64)
        sigs = np.stack([np.frombuffer(b.signature, dtype=np.uint8)
                         for b in beacons])
        prev = None
        if not self.scheme.decouple_prev_sig:
            prev = np.stack([np.frombuffer(b.previous_sig, dtype=np.uint8)
                             for b in beacons])
        # the span covers dispatch THROUGH resolve: it is closed on the
        # resolving thread, so it writes nothing into a profiler capture
        # (`tracing.clock_mark` ties its `start_mono` to one instead)
        from drand_tpu import tracing
        sp = tracing.begin_span(
            "verify.batch", beacon_id=self.beacon_id,
            round_=int(beacons[-1].round), batch=len(beacons))
        try:
            pending = self._verifier.verify_batch_async(rounds, sigs, prev)
        except Exception:
            sp.end("error")
            raise

        def resolve():
            try:
                out = pending()
            except Exception:
                sp.end("error")
                raise
            sp.end()
            return out

        return resolve

    def verify_beacons(self, beacons: list[Beacon]) -> np.ndarray:
        """Batch of arbitrary (round, prev_sig, sig) triples -> bool[B]."""
        return self.verify_beacons_async(beacons)()

    def verify_chain_segment_async(self, beacons: list[Beacon],
                                   anchor_prev_sig: bytes):
        """Dispatch a contiguous-segment verify without blocking; the
        linkage (prev_sig chain) checks on the host at dispatch time, the
        signature batch resolves via the returned callable.  Lets a
        streaming consumer (sync manager) overlap segment k+1's transfer
        with segment k's device compute."""
        if not beacons:
            return lambda: np.zeros(0, dtype=bool)
        from drand_tpu import tracing
        sp = tracing.begin_span(
            "verify.segment", beacon_id=self.beacon_id,
            round_=int(beacons[-1].round),
            first_round=int(beacons[0].round), batch=len(beacons))
        ok_link = np.ones(len(beacons), dtype=bool)
        if not self.scheme.decouple_prev_sig:
            want_prev = anchor_prev_sig
            for i, b in enumerate(beacons):
                ok_link[i] = (b.previous_sig == want_prev)
                want_prev = b.signature
        # signature validity is per-beacon regardless of round spacing;
        # contiguity only matters for the linkage checked above
        try:
            pending = self.verify_beacons_async(beacons)
        except Exception:
            sp.end("error")
            raise

        def resolve():
            try:
                out = pending() & ok_link
            except Exception:
                sp.end("error")
                raise
            sp.end()
            return out

        return resolve

    def verify_chain_segment(self, beacons: list[Beacon],
                             anchor_prev_sig: bytes) -> np.ndarray:
        """Contiguous rounds: checks linkage (prev_sig chain) host-side and
        signatures device-side in one call.  Returns per-beacon validity."""
        return self.verify_chain_segment_async(beacons, anchor_prev_sig)()

    def verify_packed_segment_async(self, packed, anchor_prev_sig: bytes):
        """Packed (columnar) form of verify_chain_segment_async: `packed`
        is a chain.segment.PackedBeacons whose signatures never left their
        (B, sig_len) wire matrix — no per-round Beacon objects, no
        per-round linkage loop.  Linkage for chained schemes is
        STRUCTURAL: prev row i := sig row i-1 with the caller's own
        anchor at row 0, so the batch verifies exactly the chain the
        consumer believes in (a server's advisory first_prev is never
        trusted).  Returns a zero-arg resolver yielding bool[B]."""
        if not len(packed):
            return lambda: np.zeros(0, dtype=bool)
        if len(packed) <= _HOST_VERIFY_MAX and self._lazy_verifier is None:
            # same small-batch economics as verify_beacons_async: don't
            # build the device kernel for a short tail
            return self.verify_chain_segment_async(
                packed.beacons(anchor_sig=anchor_prev_sig), anchor_prev_sig)
        from drand_tpu import tracing
        sp = tracing.begin_span(
            "verify.segment", beacon_id=self.beacon_id,
            round_=int(packed.end_round),
            first_round=int(packed.start_round), batch=len(packed))
        try:
            # the SCHEME decides the message layout, not the wire flag: a
            # chunk mislabeled unchained still verifies against the
            # anchor-constructed prev column (and fails if it should)
            if self.scheme.decouple_prev_sig:
                pending = self._verifier.verify_batch_async(
                    packed.rounds(), packed.sigs, None)
            else:
                anchor = np.frombuffer(anchor_prev_sig, dtype=np.uint8)
                pending = self._verifier.verify_chain_segment_async(
                    packed.start_round, packed.sigs, anchor)
        except Exception:
            sp.end("error")
            raise

        def resolve():
            try:
                out = pending()
            except Exception:
                sp.end("error")
                raise
            sp.end()
            return out

        return resolve

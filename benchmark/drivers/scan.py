"""Closed loop: the start-up integrity scan over a node's own store,
again and again.  A store as the daemon builds it, filled with rounds
1..N during set-up; an operation is `scan_store(store.insecure, verifier)`,
what `startup_recovery` runs at a daemon's start before it serves.  No
wire and no commit: the verify program is the largest stage.
"""

from __future__ import annotations

import os
import time

from benchmark import harness as H


class Driver:
    def __init__(self, ctx: H.Ctx):
        self.ctx = ctx
        self.backlog = len(ctx.sigs)
        self._stores: list = []
        self.store = None

    def segment_starts(self) -> list[int]:
        """scan_store flushes every `bucket_rounds` good rows."""
        return list(range(1, self.backlog + 1,
                          self.ctx.config["bucket_rounds"]))

    def _filled(self, sigs, prevs, label: str, damaged: bool = False):
        """A node's store holding these rows.  Damaged rows go in beneath
        the store's decorators, as damage on a disk does (under a chained
        scheme `SchemeStore` refuses a row that does not link)."""
        store = H.new_node_store(
            os.path.join(self.ctx.workdir, f"{label}.db"), self.ctx.group)
        self._stores.append(store)
        H.fill_store(store.insecure if damaged and prevs is not None
                     else store, H.beacons_of(sigs, prevs))
        return store

    async def setup(self) -> None:
        self.store = self._filled(self.ctx.sigs, self.ctx.prevs, "node")

    async def warmup(self) -> None:
        n = min(self.ctx.traffic["warmup_rounds"], self.backlog)
        store = self._filled(self.ctx.sigs[:n], self.ctx.prevs
                             and self.ctx.prevs[:n], "warmup")
        rec = await self._scan(store)
        self._drop(store)
        if not rec["ok"] or rec["report"]["tip_round"] != n:
            raise H.BenchFailure(f"the warm-up scan found {rec['report']}")

    def _drop(self, store) -> None:
        self._stores.remove(store)
        store.close()
        os.remove(store.insecure.path)

    async def _scan(self, store) -> dict:
        from drand_tpu.chain.recovery import scan_store
        ctx = self.ctx
        first_span = len(ctx.spans.rows)
        t0 = time.perf_counter()
        report = await scan_store(H.SpanStore(store.insecure, ctx.spans),
                                  ctx.verifier)
        wall = time.perf_counter() - t0
        rounds = report.scanned - 1          # the genesis row is no round
        return {"ok": report.ok and report.verify_checked
                and report.verified_tip == report.tip_round,
                "rounds": rounds, "wall_s": wall,
                "report": report.to_dict(),
                "spans": ctx.spans.totals(first_span)}

    async def operate(self) -> dict:
        rec = await self._scan(self.store)
        rec["ok"] = rec["ok"] and rec["rounds"] == self.backlog
        return rec

    def end_to_end(self, records: list[dict], elapsed: float) -> dict:
        good = [r for r in records if r["ok"]]
        return {"scan_rate": sum(r["rounds"] for r in good) / elapsed}

    async def check_window(self, records: list[dict]) -> dict:
        """The scanned store still holds the chain, byte for byte (the
        scan reads; it may not write)."""
        rounds, sigs, prevs = H.stored_rows(
            self.store.insecure, self.backlog, self.ctx.sigs.shape[1])
        whole = len(rounds) == self.backlog
        return {"window.store_missing_rounds": int(not whole),
                "window.stored_rows_differing":
                    H.rows_differing(sigs, prevs, self.ctx.sigs,
                                     self.ctx.prevs)
                    if whole else self.backlog - len(rounds)}

    async def check_faulted(self, draw: dict) -> dict:
        """A scan of the chain with the faults planted reports what the
        plain reference finds there, and nothing else: under `bad_sigs`
        only rounds whose signature is false over their own fields, under
        `unlinked` only rounds whose stored `previous_sig` is not the
        stored signature before them, and every damaged, false or
        unlinked round under one of the two (the scan does not verify a
        row it has filed as unlinked: `chain/recovery.py`).  Under an
        unchained scheme nothing is unlinked, and this is: exactly the
        planted rounds as bad signatures."""
        ctx = self.ctx
        planted = {f[0] for f in draw["faults"]}
        bad, bad_prevs = H.plant(ctx.sigs, draw["faults"], ctx.prevs)
        invalid, unlinked = H.reference_findings(ctx.config, bad, bad_prevs,
                                                 planted)
        store = self._filled(bad, bad_prevs, "faulted", damaged=True)
        try:
            rec = await self._scan(store)
        finally:
            self._drop(store)
        rep = rec["report"]
        found, found_unlinked = set(rep["bad_sigs"]), set(rep["unlinked"])
        H.emit(faulted_pass={"planted": sorted(planted),
                             "invalid": sorted(invalid),
                             "unlinked": sorted(unlinked),
                             "bad_sigs": rep["bad_sigs"][:16],
                             "found_unlinked": rep["unlinked"][:16],
                             "verified_tip": rep["verified_tip"],
                             "wall_s": rec["wall_s"]})
        out = {
            "faulted.bad_sigs_missed":
                len((planted | invalid | unlinked) - found - found_unlinked),
            "faulted.bad_sigs_spurious": len(found - invalid)}
        if ctx.prevs is not None:
            out["faulted.unlinked_spurious"] = len(found_unlinked - unlinked)
        out.update({
            "faulted.verified_tip_off_by":
                abs(rep["verified_tip"] - (min(planted) - 1)),
            "faulted.other_findings": len(rep["corrupt"])
                + len(rep["missing"]) + len(found_unlinked - unlinked)})
        return out

    async def close(self) -> None:
        for store in list(self._stores):
            self._stores.remove(store)
            store.close()

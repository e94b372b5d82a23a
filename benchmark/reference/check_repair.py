"""A plain model of `drand util check`: given the rows a store holds,
which rounds a correct check files under which list, which replacements
a peer serves verify, and the store that results.  Pure Python over
dictionaries; it imports nothing of the program (the verdicts come from
`benchmark/reference/sign.py`, through `Judge`).

A store is `{round: (signature, previous_sig)}`; a round whose row does
not decode maps to None, a round that is not stored is not a key.  Round
0, where stored, is the genesis row: its signature is the genesis seed
and it is an anchor, never judged.

The check, row by row in round order:
  missing    every range of rounds between two stored rows;
  corrupt    a row that does not decode;
  unlinked   (chained schemes) the row before is stored and decodes, and
             this row's `previous_sig` is not that row's signature,
             whatever else is wrong with either;
  bad_sigs   a row that is none of these and whose signature is false
             over its own `previous_sig` and round.
A row filed as unlinked is not verified.

The repair: the rounds to mend are those of the four lists, in
contiguous runs.  A run's replacements are the served SIGNATURES alone,
each verified over the signature the consumer holds before it: the
stored signature of the row before the run (the genesis seed before
round 1), inside a run the replacement before.  A replacement is written
(with that `previous_sig`) if it verified true; a run's last only if the
stored row after the run, where there is one with a `previous_sig`,
names its signature.  Where the peer has no signature for a round, or
nothing is stored before a run, the run's rest is left.  Every other row
stays as it was.
"""

from __future__ import annotations

import hashlib
import struct


class Judge:
    """Verdicts of the plain reference under one key.  A pairing in pure
    Python takes a sixth of a second, so a row that equals the chain's
    own (`truth`: {round: (signature, previous_sig)}, true when it was
    made) is true without one."""

    def __init__(self, public_key: bytes, sig_on_g1: bool, chained: bool,
                 truth: dict | None = None):
        from benchmark.reference import sign as S
        from benchmark.reference.bls12381 import curve as C
        self._pk = C.g2_from_bytes(public_key) if sig_on_g1 \
            else C.g1_from_bytes(public_key)
        self._check = S.bls_verify_g1 if sig_on_g1 else S.bls_verify
        self.chained = chained
        self.truth = truth or {}
        self.pairings = 0

    def __call__(self, round_: int, sig: bytes, prev: bytes) -> bool:
        if not self.chained:
            prev = b""
        if self.truth.get(round_) == (sig, prev):
            return True
        self.pairings += 1
        msg = hashlib.sha256(prev + struct.pack(">Q", round_)).digest()
        try:
            return bool(self._check(self._pk, msg, sig))
        except Exception:
            return False


def runs(rounds) -> list[tuple[int, int]]:
    """(first, last) of the contiguous runs of `rounds`."""
    out: list[tuple[int, int]] = []
    for r in sorted(set(rounds)):
        if out and r == out[-1][1] + 1:
            out[-1] = (out[-1][0], r)
        else:
            out.append((r, r))
    return out


def check(rows: dict, valid, chained: bool, up_to: int | None = None) -> dict:
    """What a correct check files of `rows` (those at or below `up_to`,
    where one is given): the four lists, `scanned` (rows examined, the
    genesis row among them) and `tip_round`."""
    stored = sorted(r for r in rows if up_to is None or r <= up_to)
    found = {"corrupt": [], "missing": [], "unlinked": [], "bad_sigs": [],
             "scanned": len(stored), "tip_round": stored[-1] if stored else -1}
    for before, r in zip([None] + stored[:-1], stored):
        if before is not None and r > before + 1:
            found["missing"].append((before + 1, r - 1))
        if rows[r] is None:
            found["corrupt"].append(r)
            continue
        sig, prev = rows[r]
        if r == 0:
            continue
        if chained and prev and before == r - 1 and rows[before] is not None \
                and prev != rows[before][0]:
            found["unlinked"].append(r)
        elif not valid(r, sig, prev):
            found["bad_sigs"].append(r)
    return found


def to_mend(found: dict) -> list[int]:
    """The rounds a repair fetches, ascending."""
    rounds = set(found["corrupt"]) | set(found["unlinked"]) \
        | set(found["bad_sigs"])
    for first, last in found["missing"]:
        rounds.update(range(first, last + 1))
    return sorted(rounds)


def repair(rows: dict, mend, served: dict, valid, chained: bool,
           genesis_seed: bytes | None = None):
    """(rows after the repair, fixed, unfixed) where one peer serves
    `served` ({round: signature}) for the rounds `mend`."""
    out = dict(rows)
    fixed = []
    for first, last in runs(mend):
        before = rows.get(first - 1)
        prev = before[0] if before else None
        if prev is None and first == 1:
            prev = genesis_seed
        written = []
        for r in range(first, last + 1):
            sig = served.get(r)
            if sig is None or (chained and prev is None):
                break
            if valid(r, sig, prev or b""):
                written.append((r, sig, prev if chained else b""))
            prev = sig
        else:
            after = rows.get(last + 1)
            if chained and after and after[1] and after[1] != prev:
                written = [w for w in written if w[0] != last]
        for r, sig, prev_r in written:
            out[r] = (sig, prev_r)
            fixed.append(r)
    return out, fixed, sorted(set(mend) - set(fixed))

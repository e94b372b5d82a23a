"""The output check over many seeds, and the control that it has to
fail, in ONE process that builds the verify program once (by hand, on the
chip; not part of a benchmark run):

    python benchmark/check_seeds.py --workload <cell> --seconds <s> \
        --seeds 1,2,3,... --control-seeds 7,8,9

The traffic does not depend on the seed (the chain is a fixture); the
seed draws the sampled rounds and the planted faults.  So one short
window is driven, and each seed then makes its own faulted pass and its
own verdict comparison against that window; the first seed's check also
compares the window's stores.  The control drives the same window and
checks with the stub verifier (says yes to everything) in the program's
place: every one of its seeds has to come out not correct.

Last line: {"seeds_correct": n, "seeds": N, "control_not_correct": m,
"controls": M, "ok": bool}; exit 0 only if ok.
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import harness as H  # noqa: E402
from benchmark.run import Run  # noqa: E402


async def _many(run: Run, seconds: float, seeds: list[int], label: str):
    verdicts = []
    try:
        await run.prepare()
        await run.window(seconds, False)
        for seed in seeds:
            correct = await run.check(seed)
            verdicts.append(correct)
            H.emit(seed_check={
                "which": label, "seed": seed, "correct": correct,
                "not_held": [c["name"] for c in run.checks if not c["ok"]]})
        run.observe()
    finally:
        await run.close()
    return verdicts


async def go(args) -> dict:
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    sound = await _many(
        Run(args.workload, rehearse="host" if args.rehearse else None),
        args.seconds, seeds, "program")
    broken = await _many(
        Run(args.workload, rehearse="stub" if args.rehearse else None,
            verifier="stub"),
        args.seconds, controls, "control: stub verifier")
    return {"seeds_correct": sum(sound), "seeds": len(sound),
            "control_not_correct": sum(1 for c in broken if not c),
            "controls": len(broken),
            "ok": all(sound) and not any(broken)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    out = asyncio.run(go(args))
    H.emit(**out)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

"""Runs of one cell, each a process of its own, one after another, and
the spread of every metric over them (by hand, on the chip; the driver
makes its own runs):

    python benchmark/measure.py --workload <cell> --seconds <s> \
        --seeds 11,12,13,14,15,16 --sets 2 [--trace-seed 99] --out DIR

This process never touches JAX: a chip belongs to one process at a time.
A spread is the distance between the first and the third quartile
(`statistics.quantiles(values, n=4)`) as a share of the median.  Every
result line goes to `DIR/<cell>.jsonl`, the runs' earlier lines to
`DIR/<cell>.log`.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values: list[float]) -> float:
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def one_run(workload: str, seed: int, seconds: float, trace: int,
            log) -> dict:
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
           workload, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    t0 = time.perf_counter()
    proc = subprocess.run(cmd, capture_output=True, text=True)
    wall = time.perf_counter() - t0
    log.write(f"## {' '.join(cmd[1:])} -> rc {proc.returncode}, "
              f"{wall:.1f} s\n{proc.stdout}\n{proc.stderr[-4000:]}\n")
    log.flush()
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    last = json.loads(lines[-1]) if lines else {}
    last.update(seed=seed, trace=trace, rc=proc.returncode, process_s=wall)
    return last


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace-seed", type=int, default=None)
    ap.add_argument("--out", default="chiprun_out")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    os.makedirs(args.out, exist_ok=True)
    base = os.path.join(args.out, args.workload)
    sets, all_ok = [], True
    with open(base + ".jsonl", "a") as results, \
            open(base + ".log", "a") as log:
        plan = [(k, s, 0) for k in range(args.sets) for s in seeds]
        if args.trace_seed is not None:
            plan.append((-1, args.trace_seed, 1))
        for k, seed, trace in plan:
            res = one_run(args.workload, seed, args.seconds, trace, log)
            res["set"] = k
            results.write(json.dumps(res) + "\n")
            results.flush()
            all_ok &= res["rc"] == 0 and bool(res.get("correct"))
            print(json.dumps({"set": k, "seed": seed, "trace": trace,
                              "rc": res["rc"],
                              "correct": res.get("correct"),
                              "process_s": round(res["process_s"], 1),
                              "metrics": {n: m["value"] for n, m in
                                          res.get("metrics", {}).items()},
                              "reason": res.get("reason")}), flush=True)
            if trace:
                print(json.dumps({"breakdown": res.get("breakdown"),
                                  "device": res.get("device")}), flush=True)
            else:
                while len(sets) <= k:
                    sets.append([])
                sets[k].append(res)
    summary = {}
    for k, runs in enumerate(sets):
        names = sorted({n for r in runs for n in r.get("metrics", {})})
        for n in names:
            vals = [r["metrics"][n]["value"] for r in runs
                    if n in r.get("metrics", {})]
            if len(vals) >= 2:
                summary.setdefault(n, []).append(
                    {"set": k, "n": len(vals),
                     "median": statistics.median(vals),
                     "spread": spread(vals), "min": min(vals),
                     "max": max(vals)})
    print(json.dumps({"workload": args.workload, "summary": summary,
                      "all_correct": all_ok}), flush=True)
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())

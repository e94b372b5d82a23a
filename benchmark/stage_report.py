"""A traced run of one cell, for a builder who asks which stage of the
verify program a change shortens:

    python benchmark/stage_report.py --workload <cell> --seed <n> --seconds <s>

It is `run.py --trace 1` under the name that the records and the verify
skill use.  Since PR 28 `run.py` keeps the profiler's trace until
`Run.close`, so `readers/device_scopes.py` reads it there as here: the
`program.<stage>_s.*` metrics are in the result line, and the
`device_scopes` line before it holds the dearest Pallas kernels by name
and stage.  The driver does not run this file.
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--rehearse", choices=("stub", "host"), default=None)
    ap.add_argument("--bench-file", default=None)
    args = ap.parse_args(argv)
    return R.main(["--workload", args.workload, "--seed", args.seed,
                   "--seconds", args.seconds, "--trace", "1"]
                  + (["--rehearse", args.rehearse] if args.rehearse else [])
                  + (["--bench-file", args.bench_file]
                     if args.bench_file else []))


if __name__ == "__main__":
    sys.exit(main())

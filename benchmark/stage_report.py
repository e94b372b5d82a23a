"""A traced run of one cell that also reports the device's seconds by
stage of the verify program:

    python benchmark/stage_report.py --workload <cell> --seed <n> --seconds <s>

It is `run.py --trace 1` with two differences.  `run.py` deletes the
profiler's trace before it asks a reader for a metric
(`Run._reduce_trace`), so `readers/device_scopes.py` finds nothing there;
here the trace is reduced by stage first.  And the per-layer entries that
wait on that (`pending_per_layer.json`: they move into `BENCHMARK.json`
once `run.py` keeps the trace until `Run.close`) are reported with the
accepted ones.  The driver does not run this file; a builder does, to
say which stage a program change shortens.
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import harness as H  # noqa: E402
from benchmark import run as R  # noqa: E402
from benchmark.readers import device_scopes  # noqa: E402


class Run(R.Run):
    def __init__(self, workload: str, **kw):
        super().__init__(workload, **kw)
        self.bench["per_layer"] += H.load_json("pending_per_layer.json")

    def _reduce_trace(self) -> None:
        device_scopes.scopes_of(self)      # kept for the readers' asking
        super()._reduce_trace()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--rehearse", choices=("stub", "host"), default=None)
    args = ap.parse_args(argv)
    R.Run = Run             # `run.py`'s own phases and lines, of this class
    return R.main(["--workload", args.workload, "--seed", args.seed,
                   "--seconds", args.seconds, "--trace", "1"]
                  + (["--rehearse", args.rehearse] if args.rehearse else []))


if __name__ == "__main__":
    sys.exit(main())

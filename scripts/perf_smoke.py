"""Perf observability smoke: deterministic bench -> unified artifact
-> perfgate PASS -> perfgate FAIL on an injected 2x regression.

The stage proves the whole observability pipeline with zero timing
noise:

  1. a synthetic dispatch workload through a DispatchRecorder ring
     (known n/bucket mix -> exact fill ratio and padding count),
  2. a synthetic two-node round collated by journey.collate() (fixed
     wall stamps -> exact hop offsets, monotonic by construction),
  3. the four derived numbers emitted as schema-valid BenchRecords,
  4. `python -m tools.perf.gate` over that artifact against the
     COMMITTED baselines (must exit 0 — the values are constants), and
  5. the same gate against a fixture baseline with every budget halved
     (an injected 2x regression on the lower-is-better metrics) which
     MUST exit 1 — the stage that proves the gate can actually fail.

Jax-free and sub-second; wired as a scripts/check.sh stage.

Usage:  python scripts/perf_smoke.py [--emit-baselines PATH]
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from drand_tpu.profiling import dispatch, journey  # noqa: E402
from tools.perf import migrate, schema  # noqa: E402

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _synthetic_dispatch() -> dict:
    """Known dispatch mix -> exact seam summary (no singleton: the
    smoke must not pollute the process-global flight recorder)."""
    ring = dispatch.DispatchRecorder(maxlen=16)
    ring.record("verify", n=10, bucket=16, host_wall_s=0.004)
    ring.record("verify", n=16, bucket=16, host_wall_s=0.004)
    ring.record("partials", n=6, bucket=8, host_wall_s=0.002)
    summary = ring.seam_summary()
    v = summary["verify"]
    assert v["dispatches"] == 2 and v["rounds"] == 26, summary
    assert v["padding_rounds"] == 6, summary
    assert v["avg_fill_ratio"] == 0.8125, summary  # 26 / (26 + 6)
    assert len(ring) == 3
    return v


def _synthetic_journey() -> dict:
    """Fixed-wall two-node round -> exact, monotonic hop offsets."""
    spans = [
        {"name": "round.tick", "start": 1000.00, "duration_s": 0.0,
         "beacon_id": "smoke", "round": 7, "node": "a"},
        {"name": "partial.broadcast", "start": 1000.01, "duration_s": 0.04,
         "beacon_id": "smoke", "round": 7, "node": "a"},
        {"name": "partial.verify", "start": 1000.10, "duration_s": 0.10,
         "beacon_id": "smoke", "round": 7, "node": "a"},
        {"name": "partial.verify", "start": 1000.15, "duration_s": 0.25,
         "beacon_id": "smoke", "round": 7, "node": "b"},
        {"name": "partial.aggregate", "start": 1000.45, "duration_s": 0.15,
         "beacon_id": "smoke", "round": 7, "node": "b"},
        {"name": "store.commit", "start": 1000.70, "duration_s": 0.15,
         "beacon_id": "smoke", "round": 7, "node": "b"},
    ]
    merged = journey.collate(spans, beacon_id="smoke", round_=7)
    assert sorted(merged["nodes"]) == ["a", "b"], merged["nodes"]
    hops = merged["journey"]["hops"]
    offsets = [hops[h]["offset_s"] for h in journey.HOPS if h in hops]
    assert offsets == sorted(offsets), f"non-monotonic journey: {hops}"
    assert hops["commit"]["offset_s"] == 0.85, hops
    assert len(hops) == 6, hops  # every hop but serve
    return hops


def _records(fill: dict, hops: dict) -> list:
    ts = schema.stamp()
    mk = lambda metric, value, unit, direction: schema.make_record(  # noqa: E731
        bench="perf_smoke", metric=metric, value=value, unit=unit,
        direction=direction, timestamp=ts, config={"synthetic": True},
        device="cpu", writer="scripts/perf_smoke.py")
    return [
        mk("dispatch avg fill ratio (synthetic)",
           fill["avg_fill_ratio"], "ratio", "higher"),
        mk("dispatch padding rounds (synthetic)",
           float(fill["padding_rounds"]), "rounds", "lower"),
        mk("journey commit offset (synthetic)",
           hops["commit"]["offset_s"], "s", "lower"),
        mk("journey hops collated (synthetic)",
           float(len(hops)), "hops", "higher"),
    ]


def _gate(artifact: str, baseline: str, history: str) -> int:
    proc = subprocess.run(
        [sys.executable, "-m", "tools.perf.gate", "--baseline", baseline,
         "--history", history, artifact],
        cwd=REPO, capture_output=True, text=True)
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr)
    return proc.returncode


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--emit-baselines",
                    help="write seeded baseline entries for the smoke's "
                         "metrics (bootstrap helper) and exit")
    args = ap.parse_args(argv)

    fill = _synthetic_dispatch()
    hops = _synthetic_journey()
    records = _records(fill, hops)
    bad = [e for rec in records for e in schema.validate(rec)]
    assert not bad, f"schema-invalid smoke records: {bad}"

    if args.emit_baselines:
        with open(args.emit_baselines, "w") as fh:
            json.dump(migrate.seed_baselines(records, tolerance=0.25), fh,
                      indent=1, sort_keys=True)
        print(f"perf_smoke: baselines -> {args.emit_baselines}")
        return 0

    with tempfile.TemporaryDirectory() as tmp:
        artifact = os.path.join(tmp, "perf_smoke.json")
        with open(artifact, "w") as fh:
            json.dump(records, fh, indent=1)

        # leg 1: committed baselines must pass (the values are constants)
        committed = os.path.join(REPO, "tools", "perf", "baselines.json")
        rc = _gate(artifact, committed, os.path.join(tmp, "hist.jsonl"))
        assert rc == 0, f"gate FAILED against committed baselines (rc={rc})"

        # leg 2: inject a 2x regression — halve every lower-is-better
        # budget so our constant values overshoot by +100% — and the
        # gate MUST exit nonzero
        rigged = {schema.metric_key(r): {
            "value": r["value"] / 2 if r["direction"] == "lower"
            else r["value"] * 2,
            "direction": r["direction"], "tolerance": 0.25,
            "unit": r["unit"],
        } for r in records}
        fixture = os.path.join(tmp, "rigged_baselines.json")
        with open(fixture, "w") as fh:
            json.dump(rigged, fh)
        rc = _gate(artifact, fixture, os.path.join(tmp, "hist.jsonl"))
        assert rc == 1, f"gate MISSED an injected 2x regression (rc={rc})"

    print("perf_smoke: OK  dispatch fill=0.8125 padding=6  "
          "journey commit=+0.85s (6 hops, monotonic)  "
          "gate PASS on baseline, FAIL on injected 2x regression")
    return 0


if __name__ == "__main__":
    sys.exit(main())

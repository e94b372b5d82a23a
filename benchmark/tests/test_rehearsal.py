"""The harness end to end on the CPU (`--rehearse`): both drivers with
both configurations, the crossings from data alone, the control that
`correct` has to fail, and the refusal off the TPU."""

import asyncio
import copy
import json
import os
import subprocess
import sys

import pytest

from benchmark import harness as H
from benchmark.harness import ROOT

RUN = [sys.executable, os.path.join(ROOT, "benchmark", "run.py")]


def _bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _crossed(tmp_path) -> tuple[str, list[str]]:
    """A BENCHMARK.json that also holds every crossing of configuration
    and traffic mix: entries only, no file under benchmark/ is new."""
    bench = _bench()
    have = {(w["config"], w["traffic"]) for w in bench["workloads"]}
    traffics = {w["traffic"] for w in bench["workloads"]}
    by_traffic = {}
    for w in bench["workloads"]:
        by_traffic.setdefault(w["traffic"], w["name"])
    added = []
    for c in bench["configs"]:
        for t in sorted(traffics):
            if (c["name"], t) in have:
                continue
            name = f"{t}.{c['name']}"
            bench["workloads"].append({"name": name, "config": c["name"],
                                       "traffic": t, "chips": 1,
                                       "why": "crossing, rehearsed"})
            for m in bench["end_to_end"] + bench["per_layer"]:
                if by_traffic[t] in m.get("workloads", []):
                    m["workloads"].append(name)
            added.append(name)
    path = tmp_path / "BENCHMARK.crossed.json"
    path.write_text(json.dumps(bench))
    return str(path), added


def _run(*args, env=None):
    proc = subprocess.run(RUN + list(args), capture_output=True, text=True,
                          cwd=ROOT, timeout=600,
                          env=dict(os.environ, **(env or {})))
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    return proc, [json.loads(ln) for ln in lines]


def _cells():
    return [w["name"] for w in _bench()["workloads"]]


@pytest.mark.parametrize("cell", _cells())
def test_every_cell_rehearses_correct_on_the_host_tier(cell):
    proc, lines = _run("--workload", cell, "--seed", str(2**31 + 99),
                       "--seconds", "6", "--trace", "0",
                       "--rehearse", "host")
    last = lines[-1]
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    assert "setup_s" in last["metrics"] and len(last["metrics"]) >= 2
    run = [ln for ln in lines if "run" in ln][-1]["run"]
    assert run["compilations inside the window"] == 0
    assert "compile_cache" in run and "versions" in run
    assert "consumer_store_filesystem" in run


@pytest.mark.parametrize("cell", _cells())
def test_the_stub_verifier_comes_out_not_correct(cell):
    """The control: a verifier that checks less."""
    proc, lines = _run("--workload", cell, "--seed", "7", "--seconds", "1",
                       "--trace", "0", "--rehearse", "stub")
    assert proc.returncode == 1
    assert lines[-1]["correct"] is False
    compared = [ln for ln in lines if "compared" in ln][-1]["compared"]
    assert any(not c["ok"] and c["name"].startswith("faulted.")
               for c in compared)


CHAINED = os.path.join(ROOT, "benchmark", "tests", "chained",
                       "BENCHMARK.json")


def _chained_cells():
    with open(CHAINED) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", _chained_cells())
def test_a_chained_configuration_rehearses_from_data_alone(cell):
    """`pedersen-bls-chained` on 1,024 rounds, a configuration file and
    entries of a BENCHMARK.json of its own: correct on the host tier (the
    seed plants damage in both fields), not correct on the stub."""
    seed = str(2**31 + 105)
    proc, lines = _run("--workload", cell, "--seed", seed, "--seconds", "1",
                       "--trace", "0", "--rehearse", "host",
                       "--bench-file", CHAINED)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
    draw = [ln for ln in lines if "check_draw" in ln][-1]["check_draw"]
    fields = set().union(*H.damaged_fields(
        [tuple(f) for f in draw["faults"]], 96, True).values())
    assert fields == {"signature", "previous_sig"}
    proc, lines = _run("--workload", cell, "--seed", seed, "--seconds", "1",
                       "--trace", "0", "--rehearse", "stub",
                       "--bench-file", CHAINED)
    assert proc.returncode == 1 and lines[-1]["correct"] is False
    assert any(c["name"].startswith("verdicts.")
               for c in lines[-1]["not_held"])


def test_the_crossings_run_from_data_alone(tmp_path):
    path, added = _crossed(tmp_path)
    assert len(added) == 2
    for cell in added:
        proc, lines = _run("--workload", cell, "--seed", "11", "--seconds",
                           "6", "--trace", "1", "--rehearse", "host",
                           "--bench-file", path)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert lines[-1]["correct"] is True
        assert lines[-1]["metrics"], "per-layer metrics of the traced run"


def test_off_the_tpu_a_run_prints_no_result():
    proc, lines = _run("--workload", _cells()[0], "--seed", "1",
                       "--seconds", "1", "--trace", "0",
                       env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 2
    assert not any("correct" in ln for ln in lines)
    assert "not 'tpu'" in proc.stderr


def test_a_store_that_drops_a_round_comes_out_not_correct(monkeypatch):
    """The timed path broken underneath: every commit loses its last
    round.  Drives a whole run in this process, past the look for a chip
    (the rehearsal's host tier gives true verdicts)."""
    from benchmark.run import Run

    whole = H.SpanStore.put_many

    def lossy(self, beacons):
        whole(self, list(beacons)[:-1])

    async def go():
        run = Run("catchup-deep.unchained-g2", rehearse="host")
        try:
            await run.prepare()
            monkeypatch.setattr(H.SpanStore, "put_many", lossy)
            await run.window(1.0, False)
            return (await run.check(5), copy.deepcopy(run.checks),
                    run.result(False, True))
        finally:
            await run.close()

    correct, checks, result = asyncio.run(go())
    assert correct is False
    assert result["failed"] == result["attempted"] >= 1
    assert any(not c["ok"] and c["name"].startswith("window.")
               for c in checks)

"""Subprocess demo orchestrator.

Counterpart of the reference's `demo/lib/orchestrator.go` +
`demo/node/node_subprocess.go`: runs REAL daemons as subprocesses driven
through the real CLI, walks the full lifecycle — keygen, DKG, genesis,
beacon checks over HTTP, node kill/restart with catch-up — and fails loudly
at the first broken invariant.  Usable as a library (integration tests) or
a script:

    python -m demo.orchestrator --nodes 3 --threshold 2 --period 3

A CPU recipe: it starts one daemon process per node and each imports JAX,
while an accelerator belongs to one process at a time.  `_child_env` pins
every child to `JAX_PLATFORMS=cpu`, whatever the parent's environment
says.  The chip path is `python chip_smoke.py` (one process, both nodes).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.lru_cache(maxsize=None)
def _cli_knows(repo: str, flag: str) -> bool:
    """True when the CLI source at `repo` DEFINES `flag` — a static
    capability probe for mixed-revision nets (running `--help` per node
    would cost a JAX import each).  Cached: a checkout's source is fixed
    for the run.

    Anchors on the argument-definition form (`"--flag"` as a quoted
    string literal, the shape argparse add_argument calls use), not a
    bare substring: a revision that merely *mentions* the flag in a
    comment, help text, or error message must not be handed an unknown
    flag and crash at startup (ADVICE r5 #1)."""
    try:
        with open(os.path.join(repo, "drand_tpu", "cli", "main.py")) as f:
            src = f.read()
        return f'"{flag}"' in src or f"'{flag}'" in src
    except OSError:
        return False


def _child_env(repo: str, **extra) -> dict:
    """Environment of every process the demo starts: CPU backend (see the
    module doc string) and the repo's one compile-cache directory."""
    from drand_tpu import aot
    return dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu",
                JAX_COMPILATION_CACHE_DIR=aot.persistent_cache_dir(),
                **extra)


class Node:
    def __init__(self, index: int, base: str, control: int, private: int,
                 public: int | None, repo: str = REPO,
                 certs_dir: str | None = None):
        self.index = index
        self.folder = os.path.join(base, f"node{index}")
        self.control = control
        self.private_addr = f"127.0.0.1:{private}"
        self.public_port = public
        self.proc: subprocess.Popen | None = None
        # per-node code revision (mixed-version regression harness: the
        # reference runs master-vs-candidate networks,
        # demo/regression/main.go:29-60)
        self.repo = repo
        # TLS mode: shared trust folder of every node's self-signed cert;
        # this node's own pair lives in its folder (written by setup)
        self.certs_dir = certs_dir

    def cli(self, *args, timeout=120, check=True) -> str:
        env = _child_env(self.repo,
                         DRAND_SHARE_SECRET="demo-orchestrator-secret")
        cmd = [sys.executable, "-m", "drand_tpu.cli", *args]
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=timeout, env=env, cwd=self.repo)
        if check and r.returncode != 0:
            raise RuntimeError(
                f"node{self.index} cli {args} failed: {r.stderr[-800:]}")
        return r.stdout

    def start(self):
        env = _child_env(self.repo)
        args = [sys.executable, "-m", "drand_tpu.cli", "start",
                "--folder", self.folder, "--control", str(self.control),
                "--private-listen", self.private_addr]
        if self.certs_dir:
            args += ["--tls-cert", os.path.join(self.folder, "tls.crt"),
                     "--tls-key", os.path.join(self.folder, "tls.key"),
                     "--certs-dir", self.certs_dir]
        else:
            # --insecure (not its newer --tls-disable alias): mixed-revision
            # nets drive older checkouts whose CLI predates the alias
            args.append("--insecure")
        if self.repo == REPO:
            # only CLIs of the current revision are guaranteed to know the
            # flag (mixed-revision nets run older checkouts; get private
            # falls back to another group member for non-serving nodes)
            args.append("--private-rand")
        if self.public_port:
            args += ["--public-listen", f"127.0.0.1:{self.public_port}"]
        with open(os.path.join(self.folder, "node.log"), "w") as logf:
            self.proc = subprocess.Popen(
                args, stdout=logf, stderr=subprocess.STDOUT, env=env,
                cwd=self.repo)

    def stop(self, hard: bool = False):
        if self.proc is None:
            return
        if hard:
            self.proc.kill()
            try:
                self.proc.wait(5)
            except subprocess.TimeoutExpired:
                pass  # teardown stays best-effort
        else:
            try:
                self.cli("stop", "--control", str(self.control), check=False)
            except Exception:
                pass
            try:
                self.proc.wait(5)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        self.proc = None


class Orchestrator:
    def __init__(self, n: int, thr: int, period: int, base_port: int = 21000,
                 repos: list | None = None, tls: bool = False):
        """repos: optional per-node repo checkouts (mixed-version nets);
        defaults to this repo for every node.  tls=True runs the whole
        network on self-signed TLS (the operator flow the reference's
        --tls-cert/--certs-dir flags serve)."""
        self.base = tempfile.mkdtemp(prefix="drand-demo-")
        self.period = period
        self.thr = thr
        self.tls = tls
        if tls and repos and any(r != REPO for r in repos):
            # older checkouts' CLIs predate --certs-dir/--tls-disable and
            # default to plaintext — a mixed TLS net would silently mix
            # transports (or fail argparse); refuse instead
            raise ValueError("tls=True is not supported for "
                             "mixed-revision networks")
        certs_dir = os.path.join(self.base, "certs") if tls else None
        self.nodes = [
            Node(i, self.base, base_port + i,
                 base_port + 100 + i,
                 base_port + 200 + i if i == 0 else None,
                 repo=(repos[i] if repos and i < len(repos) else REPO),
                 certs_dir=certs_dir)
            for i in range(n)]
        for nd in self.nodes:
            os.makedirs(nd.folder, exist_ok=True)
        if tls:
            os.makedirs(certs_dir, exist_ok=True)
            from drand_tpu.net.certs import generate_self_signed
            for nd in self.nodes:
                cert = os.path.join(nd.folder, "tls.crt")
                generate_self_signed("127.0.0.1", cert,
                                     os.path.join(nd.folder, "tls.key"))
                shutil.copy(cert, os.path.join(certs_dir,
                                               f"node{nd.index}.crt"))

    def log(self, msg):
        print(f"[demo] {msg}", flush=True)

    def setup(self):
        self.log(f"starting {len(self.nodes)} daemons")
        for nd in self.nodes:
            nd.start()
        time.sleep(8)
        for nd in self.nodes:
            keygen = ["generate-keypair", "--folder", nd.folder,
                      nd.private_addr]
            if self.tls:
                keygen.append("--tls")   # mark the identity TLS so peers
                # dial it with secure channels (key.Identity.TLS)
            nd.cli(*keygen)
            nd.cli("load", "--control", str(nd.control))

    def run_dkg(self):
        self.log("running DKG")
        leader = self.nodes[0]
        procs = []

        def _env(nd):
            return _child_env(
                nd.repo, DRAND_SHARE_SECRET="demo-orchestrator-secret")

        def _share_flags(nd):
            # non-TLS nets must say so (share's leader_tls defaults on,
            # matching start's TLS-by-default posture) — but only CLIs
            # that KNOW the flag can take it; checkouts predating it
            # default to plaintext and would choke on the unknown flag.
            # Probe the node revision's CLI source instead of assuming
            # worktree == old (a worktree of a post-TLS revision has the
            # flag and NEEDS it — the revision-path test broke the first
            # mixed-revision run after TLS-by-default landed).
            if not self.tls and _cli_knows(nd.repo, "--tls-disable"):
                return ["--tls-disable"]
            return []

        lead = subprocess.Popen(
            [sys.executable, "-m", "drand_tpu.cli", "share",
             "--control", str(leader.control), "--leader",
             "--nodes", str(len(self.nodes)),
             "--threshold", str(self.thr),
             "--period", str(self.period), "--timeout", "5",
             *_share_flags(leader)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=_env(leader),
            cwd=leader.repo, text=True)
        time.sleep(4)
        for nd in self.nodes[1:]:
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "drand_tpu.cli", "share",
                 "--control", str(nd.control),
                 "--connect", leader.private_addr, "--timeout", "5",
                 *_share_flags(nd)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=_env(nd),
                cwd=nd.repo, text=True))
        out, err = lead.communicate(timeout=180)
        if lead.returncode != 0:
            raise RuntimeError(f"leader share failed: {err[-800:]}")
        for p in procs:
            p.communicate(timeout=60)
        self.log("DKG complete")
        return out

    def chain_hash(self) -> str:
        out = self.nodes[0].cli("get", "chain-info", "--control",
                                str(self.nodes[0].control))
        return json.loads(out)["hash"]

    def fetch(self, round_: int | str):
        port = self.nodes[0].public_port
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/public/{round_}", timeout=10) as r:
            return json.loads(r.read())

    def wait_round(self, target: int, timeout: float = 120):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                latest = self.fetch("latest")
                if latest["round"] >= target:
                    return latest
            except Exception:
                pass
            time.sleep(self.period / 2)
        raise RuntimeError(f"round {target} not reached in {timeout}s")

    def check_beacons(self, up_to: int):
        """Every round serves consistently over HTTP (orchestrator.go
        beacon checks)."""
        seen = {}
        for r in range(1, up_to + 1):
            b = self.fetch(r)
            assert b["round"] == r, b
            seen[r] = b["signature"]
        self.log(f"checked {up_to} rounds over HTTP")
        return seen

    def private_rand_check(self):
        """ECIES private randomness end-to-end: group file -> get private
        -> decrypted 32-byte blob (reference `drand get private`,
        core/drand_beacon_public.go:135-160)."""
        nd = self.nodes[0]
        group_toml = nd.cli("show", "group", "--control", str(nd.control))
        path = os.path.join(self.base, "group.toml")
        with open(path, "w") as f:
            f.write(group_toml)
        get_args = ["get", "private", "--group", path]
        if self.tls:
            get_args += ["--certs-dir", self.nodes[0].certs_dir]
        out = nd.cli(*get_args)
        rand = json.loads(out)["randomness"]
        assert len(bytes.fromhex(rand)) == 32, out
        self.log("private randomness served and decrypted")

    def kill_restart_check(self):
        """Kill the last node, let the network run, restart, require
        catch-up (orchestrator.go:530-577)."""
        victim = self.nodes[-1]
        self.log(f"killing node{victim.index}")
        victim.stop(hard=True)
        latest = self.fetch("latest")["round"]
        self.wait_round(latest + 2)
        self.log("network progressed without the victim; restarting it")
        victim.start()       # start auto-loads persisted beacons
        time.sleep(8)
        head = self.fetch("latest")["round"]
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            out = victim.cli("util", "status", "--control",
                             str(victim.control), check=False)
            try:
                if json.loads(out)["chain"]["last_round"] >= head:
                    self.log("victim caught up")
                    return
            except Exception:
                pass
            time.sleep(self.period)
        raise RuntimeError("victim failed to catch up")

    def teardown(self):
        for nd in self.nodes:
            nd.stop()
        shutil.rmtree(self.base, ignore_errors=True)

    def run_all(self):
        try:
            self.setup()
            self.run_dkg()
            self.log(f"chain hash {self.chain_hash()}")
            self.wait_round(3)
            self.check_beacons(3)
            self.private_rand_check()
            self.kill_restart_check()
            self.log("ALL DEMO CHECKS PASSED")
        finally:
            self.teardown()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=3)
    ap.add_argument("--threshold", type=int, default=2)
    ap.add_argument("--period", type=int, default=3)
    ap.add_argument("--tls", action="store_true",
                    help="run the network on self-signed TLS")
    args = ap.parse_args()
    Orchestrator(args.nodes, args.threshold, args.period,
                 tls=args.tls).run_all()


if __name__ == "__main__":
    main()

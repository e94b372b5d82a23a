"""Production-traffic load harness for the public serving surface.

Simulates thousands of concurrent clients against a drand node or relay
(the CDN-header path): `/public/latest` fetches, fixed-round fetches,
and long-poll watch streams — the three request shapes real consumers
make — and reports the numbers that matter at scale: p50/p99/p999
latency tails, error rates, goodput, and how much the server SHED
(503 + ``Retry-After``, the admission stage's overload contract).

    python -m tools.bench_serve --url http://127.0.0.1:8080 \
        --clients 2000 --duration 10 --json BENCH_serve.json

Two stop conditions:

  - ``--duration S``: classic closed-loop wall-clock run;
  - ``--requests N``: each client issues exactly N requests — the
    deterministic scaled-down form the tier-1 suite and the serve-smoke
    stage use (completion does not depend on machine speed).

Shed handling closes the loop with the server: a 503's ``Retry-After``
hint pauses THAT virtual client for the hinted interval (capped) before
it retries — exactly what a well-behaved edge does — so the recover
half of shed→recover is part of every run.  Pacing sleeps ride the
injectable clock seam (`clock`), so a fake-clock test can drive the
retry schedule deterministically.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys

# "cached" (ISSUE 14) is a conditional GET of /public/latest carrying
# the last ETag this driver saw (`If-None-Match` → 304 on a fresh
# cache) — a polling edge's steady state.  It is appended LAST with a
# default weight of 0 so the hash→op mapping of every pre-existing
# (seed, mix) schedule is unchanged (--requests determinism).
OPS = ("latest", "round", "watch", "cached")
DEFAULT_MIX = {"latest": 0.6, "round": 0.3, "watch": 0.1}
RETRY_AFTER_CAP_S = 5.0       # never idle a virtual client longer


def percentile(sorted_vals: list, q: float) -> float:
    """Nearest-rank percentile over an ascending list (0 when empty)."""
    if not sorted_vals:
        return 0.0
    idx = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[idx]


class _RealClock:
    """Default clock seam: loop-monotonic time + real sleeps (matches
    drand_tpu.beacon.clock.Clock's surface used here)."""

    def now(self) -> float:
        return asyncio.get_running_loop().time()

    async def sleep(self, seconds: float) -> None:
        await asyncio.sleep(seconds)


class ServeStats:
    """Latency/outcome accumulator, per op and overall."""

    def __init__(self):
        self.lat_s: dict[str, list[float]] = {op: [] for op in OPS}
        self.ok: dict[str, int] = {op: 0 for op in OPS}
        self.shed: dict[str, int] = {op: 0 for op in OPS}
        self.errors: dict[str, int] = {op: 0 for op in OPS}
        self.statuses: dict[int, int] = {}
        self.retry_after_seen = 0       # sheds that carried the header
        self.watch_rounds = 0           # distinct rounds watch streams saw
        self.conditional = 0            # requests sent with If-None-Match
        self.n304 = 0                   # 304 Not Modified answers
        self.cache_events: dict[str, int] = {}   # X-Drand-Cache counts

    def note(self, op: str, status: int, elapsed_s: float,
             retry_after: bool = False) -> None:
        self.statuses[status] = self.statuses.get(status, 0) + 1
        if status in (200, 304):
            # 304 is a SUCCESSFUL conditional answer (the revalidation
            # the serve cache's ETag exists for), not an error
            self.ok[op] += 1
            self.lat_s[op].append(elapsed_s)
            if status == 304:
                self.n304 += 1
        elif status in (429, 503):
            self.shed[op] += 1
            if retry_after:
                self.retry_after_seen += 1
        else:
            self.errors[op] += 1

    def note_error(self, op: str) -> None:
        self.errors[op] += 1

    # -- report -------------------------------------------------------------

    def _tails_ms(self, vals: list[float]) -> dict:
        s = sorted(vals)
        return {"p50": round(percentile(s, 0.50) * 1e3, 3),
                "p99": round(percentile(s, 0.99) * 1e3, 3),
                "p999": round(percentile(s, 0.999) * 1e3, 3),
                "max": round((s[-1] if s else 0.0) * 1e3, 3),
                "n": len(s)}

    def report(self, clients: int, elapsed_s: float, target: str) -> dict:
        all_lat = [v for op in OPS for v in self.lat_s[op]]
        ok = sum(self.ok.values())
        shed = sum(self.shed.values())
        errors = sum(self.errors.values())
        total = ok + shed + errors
        tails = self._tails_ms(all_lat)
        return {
            # BENCH_*.json-shaped headline (bench.py parsed form)
            "metric": "public-serve p99 latency under concurrent load",
            "value": tails["p99"],
            "unit": "ms",
            "config": f"clients={clients} mix=latest/round/watch/cached",
            "target": target,
            "clients": clients,
            "elapsed_s": round(elapsed_s, 3),
            "requests": total,
            "ok": ok,
            "shed": shed,
            "shed_with_retry_after": self.retry_after_seen,
            "errors": errors,
            "error_rate": round(errors / total, 6) if total else 0.0,
            "goodput_rps": round(ok / elapsed_s, 1) if elapsed_s else 0.0,
            "latency_ms": tails,
            "per_op": {op: {"ok": self.ok[op], "shed": self.shed[op],
                            "errors": self.errors[op],
                            "latency_ms": self._tails_ms(self.lat_s[op])}
                       for op in OPS},
            "statuses": {str(k): v
                         for k, v in sorted(self.statuses.items())},
            "watch_rounds": self.watch_rounds,
            # encode-once fast lane visibility (ISSUE 14): how much of
            # the run revalidated (304) and which serve lane answered
            # (the server's X-Drand-Cache header)
            "cache": self._cache_block(),
        }

    def _cache_block(self) -> dict:
        served = dict(sorted(self.cache_events.items()))
        lane_total = sum(served.values())
        hits = served.get("hit", 0)
        return {
            "conditional_requests": self.conditional,
            "not_modified": self.n304,
            "ratio_304": (round(self.n304 / self.conditional, 4)
                          if self.conditional else 0.0),
            "served_by_lane": served,
            "hit_ratio": (round(hits / lane_total, 4)
                          if lane_total else 0.0),
        }


class LoadDriver:
    """N virtual clients against one base URL, mixed op shapes.

    Usable in-process (tests, scripts/serve_smoke.py) or via the CLI.
    The op sequence per client is a pure hash of (seed, client, i) —
    runs are reproducible, not dependent on a shared RNG stream."""

    def __init__(self, base_url: str, clients: int = 100,
                 duration_s: float | None = 5.0,
                 requests_per_client: int | None = None,
                 mix: dict | None = None, seed: int = 0,
                 honor_retry_after: bool = True,
                 request_timeout_s: float = 30.0,
                 clock=None):
        self.base_url = base_url.rstrip("/")
        self.clients = clients
        self.duration_s = duration_s
        self.requests_per_client = requests_per_client
        self.mix = dict(mix or DEFAULT_MIX)
        self.seed = seed
        self.honor_retry_after = honor_retry_after
        self.request_timeout_s = request_timeout_s
        self.clock = clock or _RealClock()
        self.stats = ServeStats()
        self._head_round = 0
        self._latest_etag: str | None = None    # for the `cached` op
        if duration_s is None and requests_per_client is None:
            raise ValueError("need duration_s or requests_per_client")

    # -- deterministic op schedule ------------------------------------------

    def _op_for(self, client: int, i: int) -> str:
        import hashlib
        h = hashlib.sha256(f"{self.seed}|{client}|{i}".encode()).digest()
        frac = int.from_bytes(h[:8], "big") / 2 ** 64
        acc = 0.0
        for op in OPS:
            acc += self.mix.get(op, 0.0)
            if frac < acc:
                return op
        return "latest"

    def _round_for(self, client: int, i: int) -> int:
        import hashlib
        if self._head_round <= 1:
            return 1
        h = hashlib.sha256(f"r|{self.seed}|{client}|{i}".encode()).digest()
        return 1 + int.from_bytes(h[:8], "big") % self._head_round

    # -- one virtual client --------------------------------------------------

    async def _request(self, session, op: str, client: int, i: int) -> None:
        import aiohttp
        headers = {}
        if op == "round":
            url = f"{self.base_url}/public/{self._round_for(client, i)}"
        else:
            # watch = repeated long-poll against latest: the server holds
            # the GET until the next beacon lands (http/server.py);
            # cached = a polling edge's conditional GET revalidating the
            # last ETag it saw (If-None-Match -> 304 on a fresh cache)
            url = f"{self.base_url}/public/latest"
            if op == "cached" and self._latest_etag:
                headers["If-None-Match"] = self._latest_etag
                self.stats.conditional += 1
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        try:
            async with session.get(
                    url, headers=headers, timeout=aiohttp.ClientTimeout(
                        total=self.request_timeout_s)) as resp:
                body = await resp.read()
                elapsed = loop.time() - t0
                retry_after = "Retry-After" in resp.headers
                lane = resp.headers.get("X-Drand-Cache")
                if lane:
                    self.stats.cache_events[lane] = \
                        self.stats.cache_events.get(lane, 0) + 1
                if resp.status == 200 and op != "round":
                    etag = resp.headers.get("ETag")
                    if etag:
                        self._latest_etag = etag
                self.stats.note(op, resp.status, elapsed, retry_after)
                if op == "watch" and resp.status == 200:
                    try:
                        r = json.loads(body).get("round", 0)
                        if r > self._head_round:
                            self._head_round = r
                            self.stats.watch_rounds += 1
                    except Exception:
                        pass
                if resp.status in (429, 503) and self.honor_retry_after:
                    hint = resp.headers.get("Retry-After", "1")
                    try:
                        pause = min(float(hint), RETRY_AFTER_CAP_S)
                    except ValueError:
                        pause = 1.0
                    await self.clock.sleep(pause)
        except asyncio.CancelledError:
            raise
        except Exception:
            self.stats.note_error(op)

    async def _client_loop(self, session, client: int,
                           stop_at: float | None) -> None:
        i = 0
        while True:
            if self.requests_per_client is not None \
                    and i >= self.requests_per_client:
                return
            if stop_at is not None and self.clock.now() >= stop_at:
                return
            await self._request(session, self._op_for(client, i), client, i)
            i += 1

    # -- the run -------------------------------------------------------------

    async def run(self) -> dict:
        import aiohttp
        loop = asyncio.get_running_loop()
        conn = aiohttp.TCPConnector(limit=0)        # we ARE the load
        async with aiohttp.ClientSession(connector=conn) as session:
            # learn the head once so fixed-round fetches hit real rounds
            try:
                async with session.get(
                        f"{self.base_url}/public/latest",
                        timeout=aiohttp.ClientTimeout(total=10)) as resp:
                    if resp.status == 200:
                        self._head_round = json.loads(
                            await resp.read()).get("round", 0)
            except Exception:
                pass
            stop_at = None
            if self.duration_s is not None:
                stop_at = self.clock.now() + self.duration_s
            t0 = loop.time()
            tasks = [asyncio.create_task(
                self._client_loop(session, c, stop_at))
                for c in range(self.clients)]
            await asyncio.gather(*tasks)
            elapsed = loop.time() - t0
        return self.stats.report(self.clients, elapsed, self.base_url)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="bench_serve",
        description="async load harness for /public/latest, fixed-round, "
                    "and long-poll watch traffic")
    p.add_argument("--url", default="http://127.0.0.1:8080",
                   help="node or relay base URL")
    p.add_argument("--clients", type=int, default=100)
    p.add_argument("--duration", type=float, default=None,
                   help="seconds of closed-loop load (default 5 unless "
                        "--requests is given)")
    p.add_argument("--requests", type=int, default=None,
                   help="requests per client (deterministic stop)")
    p.add_argument("--mix", default=None,
                   help="op mix, e.g. latest:0.5,round:0.3,watch:0.1,"
                        "cached:0.1")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--json", dest="json_out", default=None,
                   help="write the full report to this path ('-' = stdout)")
    p.add_argument("--p99-bound-ms", type=float, default=None,
                   help="exit 1 when overall p99 exceeds this bound")
    p.add_argument("--no-retry-after", action="store_true",
                   help="do not pause shed clients for the server's hint")
    args = p.parse_args(argv)

    mix = None
    if args.mix:
        mix = {}
        for part in args.mix.split(","):
            op, _, w = part.partition(":")
            if op.strip() not in OPS:
                p.error(f"unknown op {op!r} in --mix (known: {OPS})")
            mix[op.strip()] = float(w or 0)
    duration = args.duration
    if duration is None and args.requests is None:
        duration = 5.0

    driver = LoadDriver(args.url, clients=args.clients, duration_s=duration,
                        requests_per_client=args.requests, mix=mix,
                        seed=args.seed,
                        honor_retry_after=not args.no_retry_after)
    report = asyncio.run(driver.run())

    tails = report["latency_ms"]
    print(f"serve bench: {report['requests']} requests from "
          f"{report['clients']} clients in {report['elapsed_s']}s "
          f"against {report['target']}")
    print(f"  goodput:   {report['goodput_rps']} ok/s "
          f"(ok {report['ok']}, shed {report['shed']}, "
          f"errors {report['errors']})")
    print(f"  latency:   p50 {tails['p50']}ms  p99 {tails['p99']}ms  "
          f"p999 {tails['p999']}ms  max {tails['max']}ms")
    for op, d in report["per_op"].items():
        t = d["latency_ms"]
        print(f"  {op:7s} ok {d['ok']:6d}  shed {d['shed']:5d}  "
              f"err {d['errors']:4d}  p50 {t['p50']}ms  p99 {t['p99']}ms")
    cb = report["cache"]
    if cb["conditional_requests"] or cb["served_by_lane"]:
        print(f"  cache:     304s {cb['not_modified']}/"
              f"{cb['conditional_requests']} conditional "
              f"(ratio {cb['ratio_304']}), lanes {cb['served_by_lane']}, "
              f"hit ratio {cb['hit_ratio']}")
    if args.json_out == "-":
        print(json.dumps(report, indent=2))
    elif args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(report, f, indent=2)
        print(f"  report written to {args.json_out}")
    if args.p99_bound_ms is not None and tails["p99"] > args.p99_bound_ms:
        print(f"FAIL: p99 {tails['p99']}ms exceeds bound "
              f"{args.p99_bound_ms}ms", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

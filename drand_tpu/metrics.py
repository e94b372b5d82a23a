"""Metrics and observability.

Counterpart of `metrics/metrics.go`: beacon gauges (discrepancy latency,
last round, group size/threshold, `:80-91`), DKG/reshare state-machine
gauges (`:20-40`), and an HTTP exposition endpoint.  The reference's four
separate registries collapse into per-metric label dimensions
(beacon_id), which Prometheus handles natively.
"""

from __future__ import annotations


from aiohttp import web
from prometheus_client import (CollectorRegistry, Counter, Gauge, Histogram,
                               generate_latest)

from drand_tpu import log as dlog
log = dlog.get("metrics")

REGISTRY = CollectorRegistry()

# Naming conventions (enforced by tests/test_hygiene.py):
#   - every collector is `drand_`-prefixed
#   - histograms are native-seconds and end in `_seconds`
#   - point-in-time latency/duration gauges end in `_ms`

# beacon metrics (metrics.go:80-91)
BEACON_DISCREPANCY_LATENCY = Gauge(
    "drand_beacon_discrepancy_latency_ms",
    "Difference between a beacon's creation and expected round time (ms)",
    ["beacon_id"], registry=REGISTRY)
LAST_BEACON_ROUND = Gauge(
    "drand_last_beacon_round", "Last locally stored beacon round",
    ["beacon_id"], registry=REGISTRY)
GROUP_SIZE = Gauge("drand_group_size", "Number of group members",
                   ["beacon_id"], registry=REGISTRY)
GROUP_THRESHOLD = Gauge("drand_group_threshold", "Group threshold",
                        ["beacon_id"], registry=REGISTRY)
# DKG state machine (metrics.go:20-40): 0=not started, 1=waiting, 2=in
# progress, 3=done, 4=failed
DKG_STATE = Gauge("drand_dkg_state", "DKG state machine",
                  ["beacon_id"], registry=REGISTRY)
RESHARE_STATE = Gauge("drand_reshare_state", "Reshare state machine",
                      ["beacon_id"], registry=REGISTRY)
# ceremony phase observability (ISSUE 20): the fast-sync phaser closes
# every deal/response/justification phase with a typed outcome —
# duration distribution plus complete-vs-timeout counts per phase.
# Buckets bracket the sub-second in-process ceremonies through the
# multi-minute n=128 phase timeouts.
DKG_PHASE_SECONDS = Histogram(
    "drand_dkg_phase_seconds",
    "Wall duration of one DKG/reshare ceremony phase "
    "(deal/response/justification), from phase open to its typed close",
    ["beacon_id", "phase"], registry=REGISTRY,
    buckets=(.05, .1, .25, .5, 1.0, 2.5, 5.0, 15.0, 60.0, 300.0,
             900.0))
DKG_PHASE_OUTCOMES = Counter(
    "drand_dkg_phase_outcomes_total",
    "Typed ceremony phase closes per phase (complete = every awaited "
    "bundle arrived; timeout = the phaser advanced on the deadline "
    "with bundles missing)",
    ["beacon_id", "phase", "outcome"], registry=REGISTRY)
# verification throughput (TPU path)
VERIFIED_BEACONS = Counter(
    "drand_verified_beacons_total",
    "Beacons verified through the batched device path",
    ["beacon_id"], registry=REGISTRY)
PARTIALS_RECEIVED = Counter(
    "drand_partials_received_total", "Partial signatures accepted",
    ["beacon_id"], registry=REGISTRY)
SYNC_ROUNDS_COMMITTED = Counter(
    "drand_sync_rounds_committed_total",
    "Rounds committed via batched catch-up segments (put_many) — the "
    "latency gauge emits one sample per SEGMENT on this path, so rate "
    "consumers should count rounds here",
    ["beacon_id"], registry=REGISTRY)
# batched sync wire (ISSUE 13): rounds RECEIVED per wire shape ("chunk"
# = packed SyncChunk messages, "single" = per-beacon BeaconPackets — the
# reference-compat fallback), vs rounds COMMITTED above; a chunk-capable
# client talking to a reference peer shows up as wire="single" here.
SYNC_ROUNDS = Counter(
    "drand_sync_rounds_total",
    "Rounds received on the catch-up sync wire, by wire shape",
    ["beacon_id", "wire"], registry=REGISTRY)
SYNC_SEGMENT_SECONDS = Histogram(
    "drand_sync_segment_seconds",
    "Host seconds per catch-up pipeline stage per segment "
    "(fetch/pack/verify/commit)",
    ["stage"], registry=REGISTRY,
    buckets=(.001, .0025, .005, .01, .025, .05, .1, .25, .5,
             1.0, 2.5, 5.0, 15.0, 60.0))
# client-side instrumentation (reference client/metric.go +
# client/http/http.go:146-177 instrumented transports): per-source
# request counters/latency and the watch's actual-vs-expected lag
CLIENT_REQUESTS = Counter(
    "drand_client_requests_total",
    "Client SDK requests by source, operation, and outcome",
    ["source", "op", "outcome"], registry=REGISTRY)
CLIENT_REQUEST_LATENCY = Gauge(
    "drand_client_request_latency_ms",
    "Latest client SDK request latency per source and operation (ms)",
    ["source", "op"], registry=REGISTRY)
CLIENT_WATCH_LATENCY = Gauge(
    "drand_client_watch_latency_ms",
    "Delay between a watched round's expected time and its arrival (ms)",
    ["source"], registry=REGISTRY)
# per-stage round-lifecycle latency distributions, fed by every ended
# tracing.Span (drand_tpu/tracing.py).  Buckets span the sub-ms host
# stages (store commit, partial verify) through multi-second deep-sync
# segment verifies.
STAGE_DURATION = Histogram(
    "drand_stage_duration_seconds",
    "Duration of one traced round-lifecycle stage",
    ["stage", "beacon_id"], registry=REGISTRY,
    buckets=(.0005, .001, .0025, .005, .01, .025, .05, .1, .25, .5,
             1.0, 2.5, 5.0, 10.0, 30.0, 60.0))
# health / SLO surface (drand_tpu/health): the judgments layer over the
# raw gauges above — how far behind the clock is this node, how late do
# rounds land, which peers answer pings (reference metrics/metrics.go
# GroupConnectivity + the /health handler's expected-vs-actual check).
BEACON_LAG_ROUNDS = Gauge(
    "drand_beacon_lag_rounds",
    "Rounds the stored chain tip lags the clock-expected round",
    ["beacon_id"], registry=REGISTRY)
ROUND_LATENESS = Histogram(
    "drand_round_lateness_seconds",
    "How late each committed round landed relative to its scheduled time",
    ["beacon_id"], registry=REGISTRY,
    buckets=(.05, .1, .25, .5, 1.0, 2.0, 4.0, 8.0, 15.0, 30.0,
             60.0, 120.0))
GROUP_CONNECTIVITY = Gauge(
    "drand_group_connectivity",
    "1 when the peer answered the last health ping, else 0",
    ["peer"], registry=REGISTRY)
PEER_PARTIAL_LAG = Gauge(
    "drand_peer_partial_lag_rounds",
    "Rounds since a valid partial signature was last seen from a peer",
    ["beacon_id", "peer"], registry=REGISTRY)
SLO_ATTAINMENT = Gauge(
    "drand_slo_attainment_ratio",
    "Fraction of windowed rounds published within the SLO threshold",
    ["beacon_id", "window"], registry=REGISTRY)
SLO_BURN_RATE = Gauge(
    "drand_slo_error_budget_burn",
    "Error-budget burn rate over the window (1.0 = spending the budget "
    "exactly as fast as the SLO allows)",
    ["beacon_id", "window"], registry=REGISTRY)
SCRAPE_ERRORS = Counter(
    "drand_metrics_scrape_errors_total",
    "Gauge-refresh failures swallowed during /metrics exposition",
    ["beacon_id"], registry=REGISTRY)
CHAOS_INJECTED = Counter(
    "drand_chaos_injected_total",
    "Faults injected by an armed chaos schedule (drand_tpu/chaos)",
    ["site", "kind"], registry=REGISTRY)
# resilience layer (drand_tpu/resilience): retries, breakers, hedges,
# and server-side deadline shedding — the policies every remote-call
# site now routes through
RETRY_ATTEMPTS = Counter(
    "drand_retry_attempts_total",
    "Retry-policy attempt outcomes per call site "
    "(success/retry/exhausted/fatal/deadline/breaker_open)",
    ["site", "outcome"], registry=REGISTRY)
BREAKER_STATE = Gauge(
    "drand_breaker_state",
    "Per-peer circuit breaker state: 0=closed, 1=open, 2=half-open",
    ["peer"], registry=REGISTRY)
HEDGE_REQUESTS = Counter(
    "drand_hedge_requests_total",
    "Hedged-request launches and wins per call site "
    "(primary/hedged/win)",
    ["site", "outcome"], registry=REGISTRY)
DEADLINE_SHED = Counter(
    "drand_deadline_shed_total",
    "RPCs shed server-side because the caller's deadline budget had "
    "already expired on arrival",
    ["rpc"], registry=REGISTRY)
# serving surface (drand_tpu/resilience/admission.py): the overload-
# protection stage in front of the public HTTP API and the relay
# frontend — inflight per priority class, sheds (503 + Retry-After),
# and the end-to-end handler latency distribution the load harness
# (tools/bench_serve.py) asserts over
SERVE_INFLIGHT = Gauge(
    "drand_serve_inflight",
    "Requests currently inside an admission-guarded handler, per "
    "priority class",
    ["cls"], registry=REGISTRY)
SERVE_SHED = Counter(
    "drand_serve_shed_total",
    "Requests shed by the admission stage (503 + Retry-After) per "
    "route, priority class, and reason (queue_full/queue_timeout)",
    ["route", "cls", "reason"], registry=REGISTRY)
SERVE_LATENCY = Histogram(
    "drand_serve_latency_seconds",
    "Admission-to-response latency of public-surface handlers",
    ["route", "cls"], registry=REGISTRY,
    buckets=(.001, .0025, .005, .01, .025, .05, .1, .25, .5,
             1.0, 2.5, 5.0, 10.0, 30.0))
# encode-once serve fast lane (drand_tpu/http/response_cache.py,
# ISSUE 14): whether each public response came from the pre-encoded
# memory body (hit — includes requests coalesced behind an in-flight
# cold load), required the one stampede-guarded store read (miss), or
# skipped the cache entirely (bypass: DRAND_TPU_SERVE_CACHE=0 or a
# process without a cache) — plus the store reads the fast lane exists
# to eliminate, which the serve smoke asserts stay at ZERO for the hot
# latest path under burst
SERVE_CACHE = Counter(
    "drand_serve_cache_total",
    "Serve fast-lane outcomes per route: hit (pre-encoded memory body), "
    "miss (one stampede-guarded store read), bypass (cache disabled or "
    "absent)",
    ["route", "event"], registry=REGISTRY)
SERVE_STORE_READS = Counter(
    "drand_serve_store_reads_total",
    "Store reads performed by public serve handlers — the cost the "
    "encode-once fast lane eliminates (0 per request on the hot latest "
    "path at steady state)",
    ["route"], registry=REGISTRY)
# aggregation hot loop (beacon/crypto_backend + beacon/signer_table):
# the live-wiring visibility the partials bench trajectory is tracked
# against — batch sizes reaching the device path and the signer-key
# table's group epoch (a reshare MUST bump it; a frozen epoch across a
# group transition means stale key material on the verify path)
AGGREGATE_BATCH_SIZE = Gauge(
    "drand_aggregate_batch_size",
    "Partials per backend verify call (the aggregation path's batching "
    "efficiency — 1 means the micro-batcher is not coalescing)",
    registry=REGISTRY)
SIGNER_TABLE_EPOCH = Gauge(
    "drand_signer_table_epoch",
    "Group epoch of the precomputed signer-key table (bumps on "
    "reshare/group transition; stale = wrong-key verification risk)",
    registry=REGISTRY)
LAYOUT_CONVERSIONS = Counter(
    "drand_layout_conversions_total",
    "Trace-time crossings of the device tile-layout boundary "
    "(TileForm.wrap/unwrap in ops/pallas_field.py).  The tile-residency "
    "invariant (ISSUE 9) keeps hot dispatches at entry+exit only; a "
    "growing per-trace count means per-call relayout churn regressed",
    ["kind"], registry=REGISTRY)
QUEUE_DROPPED = Counter(
    "drand_queue_dropped_total",
    "Items dropped because a bounded internal queue was full — visible "
    "shed instead of silent backlog growth (queue = partial_verify / "
    "sync_requests / watch_fanout / dkg_fanout)",
    ["queue"], registry=REGISTRY)
AOT_COMPILE_SECONDS = Gauge(
    "drand_aot_compile_seconds",
    "Seconds the last XLA compile of this AOT cache entry took "
    "(the cost a warm cache entry avoids)",
    ["name"], registry=REGISTRY)
AOT_LOAD_SECONDS = Gauge(
    "drand_aot_load_seconds",
    "Seconds the last deserialize-and-load of this AOT cache entry "
    "took (must stay far under the <60 s fresh-process bar)",
    ["name"], registry=REGISTRY)
AOT_CACHE = Counter(
    "drand_aot_cache_total",
    "AOT executable-cache events per entry name "
    "(hit/miss/compile/stale/load_error)",
    ["name", "event"], registry=REGISTRY)
# native (C++) host-verify tier (drand_tpu/native, ISSUE 12): the
# single-verify latency axis the rebuilt Montgomery arithmetic targets —
# per-scheme distributions from every wrapped verify call, plus the
# availability gauge the golden-model fallback routing is visible
# through.  Buckets bracket the warm ≤3/≤5 ms targets and the ~175 ms
# golden fallback.
NATIVE_VERIFY = Histogram(
    "drand_native_verify_seconds",
    "Latency of one native-tier BLS verification, by scheme "
    "(g2/g1/partial)",
    ["scheme"], registry=REGISTRY,
    buckets=(.0005, .001, .002, .003, .005, .0075, .01, .025, .05,
             .1, .25))
NATIVE_AVAILABLE = Gauge(
    "drand_native_available",
    "1 when the native C++ BLS tier built and loaded, else 0",
    registry=REGISTRY)
# crash-safe chain storage (drand_tpu/chain/recovery.py, ISSUE 15): the
# startup integrity scan's verdict per beacon and the forensic-quarantine
# volume — the pair the chaos crash-recover / torn-write-heal scenarios
# counter-assert (a clean kill -9 must leave integrity=1 and move ZERO
# rows; injected corruption must move exactly the damaged suffix)
STORE_INTEGRITY = Gauge(
    "drand_store_integrity",
    "Last startup integrity-scan verdict for this beacon's chain store "
    "(1 = clean, 0 = damage found and repair engaged)",
    ["beacon_id"], registry=REGISTRY)
STORE_QUARANTINED = Counter(
    "drand_store_quarantined_total",
    "Rows moved from the live chain to the quarantine sidecar table "
    "(damaged rows + rolled-back suffixes; forensics, never deleted)",
    registry=REGISTRY)
# object sync tier (drand_tpu/objectsync, ISSUE 18): sealed-segment
# publishing progress and how far the published tip trails the chain —
# a stalled publisher (backend down, damaged local row) shows up as a
# growing lag long before any client notices a stale manifest
OBJECTSYNC_PUBLISHED = Counter(
    "drand_objectsync_published_total",
    "Sealed segment objects published to the object-store backend",
    ["beacon_id"], registry=REGISTRY)
OBJECTSYNC_LAG = Gauge(
    "drand_objectsync_lag_rounds",
    "Committed rounds not yet covered by a published segment object",
    ["beacon_id"], registry=REGISTRY)
# dispatch flight recorder (drand_tpu/profiling/dispatch.py, ISSUE 17):
# every batched seam pads work up to a bucket — these are the axes a
# chronically under-filled device shows up on.  Ratio gauges end in
# `_ratio` (unitless 0..1), same contract as the SLO attainment gauge.
DISPATCH_SECONDS = Histogram(
    "drand_dispatch_seconds",
    "Host wall seconds around one batched dispatch (dispatch plus "
    "blocking resolve; not device time), by seam and padded bucket size",
    ["seam", "bucket"], registry=REGISTRY,
    buckets=(.0005, .001, .0025, .005, .01, .025, .05, .1, .25, .5,
             1.0, 2.5, 5.0, 15.0))
DISPATCH_FILL_RATIO = Gauge(
    "drand_dispatch_fill_ratio",
    "Requested-n over chosen-bucket of the LAST dispatch per seam "
    "(1.0 = no padding waste; chronically low = wrong bucket table)",
    ["seam"], registry=REGISTRY)
DISPATCH_PADDING = Counter(
    "drand_dispatch_padding_rounds_total",
    "Padding rounds dispatched to fill buckets — device work spent "
    "verifying repeated filler rows, by seam",
    ["seam"], registry=REGISTRY)
# round-journey timelines (drand_tpu/profiling/journey.py, ISSUE 17):
# per-hop seconds-since-tick of each round's life, collated from the
# tracing spans (tick -> broadcast -> partials -> aggregate -> commit ->
# first served byte)
JOURNEY_SECONDS = Histogram(
    "drand_round_journey_seconds",
    "Seconds from a round's tick to the completion of each journey hop",
    ["hop"], registry=REGISTRY,
    buckets=(.005, .01, .025, .05, .1, .25, .5, 1.0, 2.5, 5.0,
             15.0, 60.0))
# fleet observatory (drand_tpu/observatory, ISSUE 19): the group-wide
# signer-health plane.  Participation/margin come from the ledger fed by
# the Handler accept seam + the aggregator's recovery hook; the fleet_*
# families come from the cross-node consistency prober.
SIGNER_PARTICIPATION = Gauge(
    "drand_signer_participation_ratio",
    "Fraction of the rolling finalized-round window this signer "
    "contributed a partial to (on-time or late)",
    ["beacon_id", "signer"], registry=REGISTRY)
THRESHOLD_MARGIN = Gauge(
    "drand_threshold_margin",
    "Distinct contributors minus threshold for the newest finalized "
    "round — 0 means one more silent signer halts the chain",
    ["beacon_id"], registry=REGISTRY)
TIME_TO_THRESHOLD = Histogram(
    "drand_time_to_threshold_seconds",
    "Seconds from a round's scheduled time to its threshold recovery",
    ["beacon_id"], registry=REGISTRY,
    buckets=(.05, .1, .25, .5, 1.0, 2.5, 5.0, 15.0, 60.0, 300.0))
FLEET_TIP_SKEW = Gauge(
    "drand_fleet_tip_skew_rounds",
    "Sampled peer chain tip minus local tip (negative = peer behind)",
    ["beacon_id", "peer"], registry=REGISTRY)
FLEET_FORK_DETECTED = Counter(
    "drand_fleet_fork_detected_total",
    "Fork/equivocation detections: a peer served a different signature "
    "for a round this node committed (one count per peer+round)",
    registry=REGISTRY)


def observe_beacon(beacon_id: str, round_: int,
                   latency_ms: float | None = None) -> None:
    LAST_BEACON_ROUND.labels(beacon_id).set(round_)
    if latency_ms is not None:
        BEACON_DISCREPANCY_LATENCY.labels(beacon_id).set(latency_ms)
        # same sample, as a distribution: the point-in-time gauge answers
        # "how late is it NOW", the histogram answers "how late are
        # rounds usually" (the SLO tracker's raw material)
        ROUND_LATENESS.labels(beacon_id).observe(max(latency_ms, 0.0) / 1000.0)


def observe_group(beacon_id: str, size: int, threshold: int) -> None:
    GROUP_SIZE.labels(beacon_id).set(size)
    GROUP_THRESHOLD.labels(beacon_id).set(threshold)


def exposition(daemon) -> bytes:
    """Refresh gauges from live processes, return Prometheus text format."""
    for bid, bp in daemon.processes.items():
        try:
            st = bp.status()
            if not st["is_empty"]:
                LAST_BEACON_ROUND.labels(bid).set(st["last_round"])
            if bp.group is not None:
                observe_group(bid, bp.group.size, bp.group.threshold)
        except Exception as exc:
            # a scrape must still answer with whatever refreshed, but
            # never silently: count it so a flapping process shows up on
            # the dashboard that is hiding it
            SCRAPE_ERRORS.labels(bid).inc()
            log.debug("gauge refresh failed for beacon %s: %s", bid, exc)
    return generate_latest(REGISTRY)


class MetricsRPC:
    """MetricsService gRPC impl on the private gateway: lets any group
    member scrape this node through the authenticated node-to-node channel
    (reference: metrics federation via httpgrpc tunnel,
    net/client_grpc.go:336-371, handler registration at
    core/drand_daemon.go:263-272)."""

    def __init__(self, daemon):
        self.daemon = daemon

    async def Metrics(self, request, context):
        from drand_tpu.protogen import drand_pb2
        return drand_pb2.MetricsResponse(payload=exposition(self.daemon))


# bound on one peer scrape through the gRPC metrics channel: shared by
# the /peers/{addr}/metrics proxy and the /debug/fleet fan-out — a hung
# peer must cost a timeout, never a wedged handler
PEER_SCRAPE_TIMEOUT_S = 10.0


class MetricsServer:
    """Exposition endpoint + pprof-style debug routes on the metrics port
    (metrics.Start + metrics/pprof, reference core/drand_daemon.go:271).
    `/peers/{addr}/metrics` proxies a group member's exposition over the
    node-to-node gRPC channel (the reference's GroupHandler)."""

    def __init__(self, daemon, port: int, host: str = "127.0.0.1"):
        self.daemon = daemon
        self.host = host
        self.port = port  # owner: server start (rebound once to the bound port)
        self.app = web.Application()
        self.app.add_routes([
            web.get("/metrics", self.handle_metrics),
            web.get("/peers/{addr}/metrics", self.handle_peer_metrics),
            web.get("/debug/gc", self.handle_gc),
            web.get("/debug/tasks", self.handle_tasks),
            web.get("/debug/jax-profile", self.handle_jax_profile),
            web.get("/debug/dispatch", self.handle_dispatch),
            web.get("/debug/journey", self.handle_journey),
            web.get("/debug/spans", self.handle_spans),
            web.get("/debug/spans/{trace_id}", self.handle_trace),
            web.get("/debug/logs", self.handle_logs),
            web.get("/debug/slo", self.handle_slo),
            web.get("/debug/health", self.handle_health_snapshot),
            web.get("/debug/resilience", self.handle_resilience),
            web.get("/debug/serve", self.handle_serve),
            web.get("/debug/sync", self.handle_sync),
            web.get("/debug/dkg", self.handle_dkg),
            web.get("/debug/objectsync", self.handle_objectsync),
            web.get("/debug/participation", self.handle_participation),
            web.get("/debug/consistency", self.handle_consistency),
            web.get("/debug/fleet", self.handle_fleet),
            web.get("/debug/store", self.handle_store),
            web.get("/debug/chaos", self.handle_chaos),
            web.post("/debug/chaos/arm", self.handle_chaos_arm),
            web.post("/debug/chaos/disarm", self.handle_chaos_disarm),
        ])
        self._runner: web.AppRunner | None = None

    async def start(self):
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        for s in self._runner.sites:
            self.port = s._server.sockets[0].getsockname()[1]
            break
        log.info("metrics on %s:%d", self.host, self.port)

    async def stop(self):
        if self._runner is not None:
            await self._runner.cleanup()

    async def handle_metrics(self, request):
        return web.Response(body=exposition(self.daemon),
                            content_type="text/plain")

    async def handle_peer_metrics(self, request):
        """Scrape a group member through the private gRPC channel.  The
        peer must be a member of one of this daemon's groups (same
        restriction as the reference's GroupHandler).  The scrape is
        deadline-bounded: a hung peer costs the caller a 504, never a
        stuck handler holding an admission slot."""
        import asyncio
        addr = request.match_info["addr"]
        try:
            payload = await asyncio.wait_for(
                self.daemon.fetch_peer_metrics(addr), PEER_SCRAPE_TIMEOUT_S)
        except KeyError:
            return web.Response(status=404, text="unknown peer")
        except asyncio.TimeoutError:
            return web.Response(status=504, text="peer scrape timed out")
        except Exception as exc:
            return web.Response(status=502, text=f"peer scrape failed: {exc}")
        return web.Response(body=payload, content_type="text/plain")

    async def handle_gc(self, request):
        import gc
        return web.json_response({"collected": gc.collect()})

    async def handle_jax_profile(self, request):
        """On-demand JAX profiler capture (the reference's pprof-on-metrics
        pattern, metrics/pprof/pprof.go; ours records an XLA device trace
        instead of Go stacks)."""
        import asyncio
        seconds = min(float(request.query.get("seconds", "2")), 30.0)
        # output path is server-generated: the reference pprof pattern
        # never takes a filesystem path from the request
        out = f"/tmp/drand_tpu_trace_{int(self._now())}"
        from drand_tpu import profiling
        try:
            await asyncio.to_thread(profiling.capture, out, seconds)
        except Exception as exc:
            return web.Response(status=500, text=f"profile failed: {exc}")
        # full manifest, not just the path: the operator pulling a trace
        # wants to know whether the capture actually wrote device data
        # (an empty dir means the profiler found nothing to record)
        man = profiling.manifest(out)
        man["seconds"] = seconds
        try:
            import jax
            man["device_platform"] = jax.default_backend()
        except Exception:
            man["device_platform"] = None
        return web.json_response(man)

    @staticmethod
    def _now():
        import time
        # wall-clock stamp in the trace dir name, so operators can match
        # a capture to their incident timeline
        return time.time()  # lint: disable=no-wall-clock

    async def handle_tasks(self, request):
        import asyncio
        tasks = [str(t.get_coro()) for t in asyncio.all_tasks()]
        return web.json_response({"count": len(tasks), "tasks": tasks[:100],
                                  "truncated": len(tasks) > 100})

    # -- perf-observability routes (drand_tpu/profiling) ------------------

    async def handle_dispatch(self, request):
        """Dispatch flight recorder snapshot: per-seam fill/padding/
        amortized-cost totals plus the recent per-dispatch ring
        (drand_tpu/profiling/dispatch.py)."""
        from drand_tpu.profiling import dispatch
        try:
            limit = int(request.query.get("limit", "50"))
        except ValueError:
            return web.Response(status=400, text="limit must be an integer")
        if not (1 <= limit <= 500):
            return web.Response(status=400, text="limit must be 1..500")
        return web.json_response(dispatch.DISPATCH.snapshot(limit=limit))

    async def handle_journey(self, request):
        """Round-journey snapshot: recent per-round hop timelines plus
        rolling p50/p99/p999 per hop (drand_tpu/profiling/journey.py)."""
        from drand_tpu.profiling import journey
        try:
            limit = int(request.query.get("limit", "20"))
        except ValueError:
            return web.Response(status=400, text="limit must be an integer")
        if not (1 <= limit <= 200):
            return web.Response(status=400, text="limit must be 1..200")
        return web.json_response(journey.JOURNEY.snapshot(limit=limit))

    # -- span routes (drand_tpu/tracing.py ring buffer) ------------------

    async def handle_spans(self, request):
        """Newest-first trace summaries with bounded pagination."""
        from drand_tpu import tracing
        try:
            limit = int(request.query.get("limit", "50"))
            offset = int(request.query.get("offset", "0"))
        except ValueError:
            return web.Response(status=400,
                                text="limit/offset must be integers")
        if not (1 <= limit <= 500) or offset < 0:
            return web.Response(
                status=400, text="limit must be 1..500, offset >= 0")
        return web.json_response(tracing.RECORDER.traces(limit, offset))

    async def handle_trace(self, request):
        from drand_tpu import tracing
        trace_id = request.match_info["trace_id"]
        spans = tracing.RECORDER.trace(trace_id)
        if not spans:
            return web.Response(status=404,
                                text=f"no spans for trace {trace_id}")
        return web.json_response({
            "trace_id": trace_id,
            "spans": [s.to_dict() for s in spans]})

    # -- health / SLO / log-pivot routes (drand_tpu/health, drand_tpu/log) --

    async def handle_logs(self, request):
        """Recent structured log records from the in-process ring
        (drand_tpu/log.py).  `?trace_id=<hex>` pivots one trace between
        `/debug/spans/{trace_id}` and its log lines; `?level=` filters
        by minimum level, `?limit=` bounds the page (1..1000)."""
        from drand_tpu import log as dlog
        try:
            limit = int(request.query.get("limit", "200"))
        except ValueError:
            return web.Response(status=400, text="limit must be an integer")
        if not (1 <= limit <= 1000):
            return web.Response(status=400, text="limit must be 1..1000")
        return web.json_response(dlog.RING.entries(
            trace_id=request.query.get("trace_id"),
            level=request.query.get("level"), limit=limit))

    async def handle_slo(self, request):
        """Rolling-window SLO attainment and error-budget burn per
        beacon (health/slo.py), fed by the daemon's watchdog."""
        health = getattr(self.daemon, "health", None)
        if health is None:
            return web.Response(status=404,
                                text="health watchdog not running")
        return web.json_response(health.slo_snapshot())

    async def handle_health_snapshot(self, request):
        """The watchdog's full operator view: per-beacon verdicts,
        stall flags, peer connectivity, SLO windows."""
        health = getattr(self.daemon, "health", None)
        if health is None:
            return web.Response(status=404,
                                text="health watchdog not running")
        return web.json_response(health.snapshot())

    async def handle_resilience(self, request):
        """The resilience hub's operator view: per-peer breaker states
        plus the tail of the retry/breaker decision log
        (drand_tpu/resilience)."""
        hub = getattr(self.daemon, "resilience", None)
        if hub is None:
            return web.Response(status=404,
                                text="resilience hub not wired")
        return web.json_response(hub.snapshot())

    async def handle_serve(self, request):
        """The public HTTP server's admission-stage snapshot: per-class
        inflight/waiting/shed counters (drand_tpu/resilience/admission)."""
        http = getattr(self.daemon, "http_server", None)
        adm = getattr(http, "admission", None)
        if adm is None:
            return web.Response(status=404,
                                text="public HTTP server not running")
        return web.json_response(adm.snapshot())

    async def handle_sync(self, request):
        """Catch-up sync operator view (ISSUE 13): per-beacon pipeline
        snapshot — current peer, adaptive chunk target, pipeline depth,
        backlog estimate, cumulative per-stage host seconds."""
        processes = getattr(self.daemon, "processes", None)
        if not processes:
            return web.Response(status=404, text="no beacon processes")
        out = {}
        for beacon_id, bp in processes.items():
            sm = getattr(bp, "sync_manager", None)
            if sm is not None:
                out[beacon_id] = sm.snapshot()
        return web.json_response(out)

    async def handle_dkg(self, request):
        """Ceremony operator view (ISSUE 20): per-beacon CeremonyStatus
        (live phases + post-mortem of the last ceremony) plus, while a
        ceremony runs, the echo-broadcast board's queue/drop snapshot
        (core/dkg_runner.CeremonyStatus, core/broadcast.EchoBroadcast)."""
        processes = getattr(self.daemon, "processes", None)
        if not processes:
            return web.Response(status=404, text="no beacon processes")
        out = {}
        for beacon_id, bp in processes.items():
            st = getattr(bp, "dkg_status", None)
            entry = {"status": st.to_dict() if st is not None else None}
            board = getattr(bp, "dkg_board", None)
            if board is not None:
                entry["board"] = board.snapshot()
            out[beacon_id] = entry
        return web.json_response(out)

    async def handle_objectsync(self, request):
        """Object-sync publisher operator view (ISSUE 18): per-beacon
        publisher snapshot — backend, published tip vs store tip, lag,
        last error (drand_tpu/objectsync/publisher.py)."""
        processes = getattr(self.daemon, "processes", None)
        if not processes:
            return web.Response(status=404, text="no beacon processes")
        out = {}
        for beacon_id, bp in processes.items():
            pub = getattr(bp, "object_publisher", None)
            if pub is not None:
                out[beacon_id] = pub.snapshot()
        return web.json_response(out)

    # -- fleet observatory routes (drand_tpu/observatory, ISSUE 19) --------

    async def handle_participation(self, request):
        """Signer participation ledger operator view: per-beacon rolling
        contributor bitmaps, threshold margins, time-to-threshold, and
        per-signer participation rates
        (drand_tpu/observatory/participation.py)."""
        processes = getattr(self.daemon, "processes", None)
        if not processes:
            return web.Response(status=404, text="no beacon processes")
        try:
            limit = int(request.query.get("limit", "32"))
        except ValueError:
            return web.Response(status=400, text="limit must be an integer")
        if not (1 <= limit <= 512):
            return web.Response(status=400, text="limit must be 1..512")
        out = {}
        for beacon_id, bp in processes.items():
            ledger = getattr(getattr(bp, "handler", None), "ledger", None)
            if ledger is not None:
                out[beacon_id] = ledger.snapshot(limit=limit)
        return web.json_response(out)

    async def handle_consistency(self, request):
        """Cross-node consistency prober operator view: per-peer tip
        skew, stale flags, and the typed fork-report ring
        (drand_tpu/observatory/consistency.py)."""
        prober = getattr(self.daemon, "consistency", None)
        if prober is None:
            return web.Response(status=404,
                                text="consistency prober not running")
        return web.json_response(prober.snapshot())

    async def handle_fleet(self, request):
        """Group-wide metric federation: every peer's exposition scraped
        through the gRPC metrics channel and folded into one typed
        FleetSnapshot (drand_tpu/observatory/fleet.py)."""
        from drand_tpu.observatory import fleet
        processes = getattr(self.daemon, "processes", None)
        if not processes:
            return web.Response(status=404, text="no beacon processes")
        snap = await fleet.collect_fleet(self.daemon,
                                         timeout_s=PEER_SCRAPE_TIMEOUT_S)
        return web.json_response(snap.to_dict())

    async def handle_store(self, request):
        """Chain-store durability operator view (ISSUE 15): per-beacon
        db path, tip, quarantine volume, and the last startup
        integrity-scan report (drand_tpu/chain/recovery.py)."""
        import asyncio
        processes = getattr(self.daemon, "processes", None)
        if not processes:
            return web.Response(status=404, text="no beacon processes")
        out = {}
        for beacon_id, bp in processes.items():
            entry = {"db_path": bp.db_path(), "tip": -1, "rows": 0,
                     "quarantined": 0, "integrity_report": None}
            base = getattr(bp._store, "insecure", None) \
                if bp._store is not None else None
            if base is not None:
                def snap(b=base):
                    try:
                        tip = b.last().round
                    except Exception:
                        tip = -1
                    return tip, len(b), len(b.quarantined())
                try:
                    entry["tip"], entry["rows"], entry["quarantined"] = \
                        await asyncio.to_thread(snap)
                except Exception:
                    pass
            rep = getattr(bp, "integrity_report", None)
            if rep is not None:
                entry["integrity_report"] = rep.to_dict()
            out[beacon_id] = entry
        return web.json_response(out)

    # -- chaos control routes (drand_tpu/chaos/failpoints.py) -------------
    # The metrics server binds 127.0.0.1 by default: these are the
    # localhost control seam for arming/inspecting fault injection on a
    # live (test) daemon — the reference's gofail HTTP endpoint analog.

    async def handle_chaos(self, request):
        from drand_tpu.chaos import failpoints as chaos
        sched = chaos.active()
        out = {"armed": sched is not None,
               "sites": dict(chaos.SITES)}
        if sched is not None:
            out["schedule"] = sched.to_spec()
            out["injections"] = sched.injection_log()[-200:]
        return web.json_response(out)

    async def handle_chaos_arm(self, request):
        from drand_tpu.chaos import failpoints as chaos
        try:
            spec = await request.json()
            chaos.arm(chaos.Schedule.from_spec(spec))
        except Exception as exc:
            return web.Response(status=400, text=f"bad chaos spec: {exc}")
        log.warning("chaos fault injection ARMED via /debug/chaos/arm")
        return web.json_response({"armed": True,
                                  "rules": len(chaos.active().rules)})

    async def handle_chaos_disarm(self, request):
        from drand_tpu.chaos import failpoints as chaos
        chaos.disarm()
        return web.json_response({"armed": False})

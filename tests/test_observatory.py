"""Fleet observatory units (ISSUE 19, drand_tpu/observatory/).

The live-group halves of the feature — ledger wiring through the
Handler accept seam, the consistency prober's fork detection, margin
movement under signer loss — are exercised by the chaos scenarios
(tests/test_chaos_scenarios.py: fork-detect, signer-loss) and the
observatory smoke (scripts/observatory_smoke.py).  These tests pin the
pure logic: ledger accounting and windowing, the exposition parser,
fleet snapshot folding, the table renderer, and the new debug routes
on stub daemons.
"""

import asyncio

from drand_tpu.observatory.fleet import (FleetSnapshot, NodeView,
                                         collect_fleet, parse_exposition,
                                         render_table)
from drand_tpu.observatory.participation import ParticipationLedger


def _recover(led, round_, indices, elapsed=0.5):
    led.note_recovery(round_, indices, len(indices), elapsed)


def test_ledger_margins_healthy_group():
    """n=3 t=2, everyone on time: at-recovery margin is 0 (recovery
    triggers exactly at threshold) but the FINAL margin — sealed when
    the next round recovers — counts all three contributors."""
    led = ParticipationLedger(group_size=3, threshold=2)
    for r in (1, 2, 3):
        for i in (0, 1, 2):
            led.note_partial(i, r)
        _recover(led, r, (0, 1))
    # rounds 1 and 2 are sealed (round 3 is still open to late arrivals)
    assert led.rounds_recovered == 3
    assert led.last_final_margin == 1          # 3 contributors - t
    rec = led._records[1]
    assert rec.margin_at_recovery == 0
    assert rec.final_margin == 1
    assert led._records[3].final_margin is None
    assert all(led.rate(i) == 1.0 for i in range(3))
    assert led.missing_signers() == []


def test_ledger_signer_loss_and_late_arrival():
    led = ParticipationLedger(group_size=3, threshold=2)
    # signer 2 healthy for one round, then silent
    for i in (0, 1, 2):
        led.note_partial(i, 1)
    _recover(led, 1, (0, 1))
    for r in (2, 3, 4, 5):
        led.note_partial(0, r)
        led.note_partial(1, r)
        _recover(led, r, (0, 1))
    assert led.last_final_margin == 0          # 2 contributors - t
    assert led.rate(2) == 0.25                 # 1 of 4 sealed rounds
    assert led.miss_streak(2) == 3
    assert led.missing_signers() == [2]
    assert led.newest[2] == 1
    # a late partial for the still-unsealed round 5 counts toward its
    # final margin once round 6 seals it
    assert not led.is_counted(2, 5)
    led.note_late(2, 5)
    assert led.is_counted(2, 5)
    assert led.late_partials == 1
    led.note_partial(0, 6)
    led.note_partial(1, 6)
    _recover(led, 6, (0, 1))
    assert led._records[5].final_margin == 1   # late arrival counted
    assert led.miss_streak(2) == 0             # reset by round 5's seal
    assert led.missing_signers() == []


def test_ledger_counts_a_partial_verified_while_its_round_recovered():
    """The Handler checks a partial's signature off the loop; the round
    can recover from the other signers meanwhile, and `note_partial` then
    comes after `note_recovery`.  The signer is alive: it is on the
    round's books as late, and the sealed margin counts it."""
    led = ParticipationLedger(group_size=3, threshold=2)
    led.note_partial(0, 1)
    led.note_partial(1, 1)
    _recover(led, 1, (0, 1))
    led.note_partial(2, 1)                     # after the recovery
    assert led.is_counted(2, 1)
    assert led.late_partials == 1 and 1 not in led._open
    led.note_partial(0, 2)
    led.note_partial(1, 2)
    _recover(led, 2, (0, 1))
    assert led._records[1].final_margin == 1
    assert led.last_final_margin == 1 and led.miss_streak(2) == 0
    led.note_partial(2, 1)                     # sealed: nothing moves
    assert led._records[1].final_margin == 1 and led.newest[2] == 1


def test_ledger_window_and_open_round_bounds():
    led = ParticipationLedger(group_size=2, threshold=2, window=4)
    for r in range(1, 11):
        led.note_partial(0, r)
        led.note_partial(1, r)
        _recover(led, r, (0, 1))
    assert len(led._final) == 4                # rolling window holds
    assert led.rate(0) == 1.0
    # open observations for never-recovered rounds stay bounded
    from drand_tpu.observatory import participation as P
    for r in range(100, 100 + 2 * P.MAX_OPEN_ROUNDS):
        led.note_partial(0, r)
    assert len(led._open) <= P.MAX_OPEN_ROUNDS
    snap = led.snapshot(limit=3)
    assert snap["finalized"] == 4
    assert len(snap["rounds"]) == 3
    assert set(snap["signers"]) == {"0", "1"}


def test_parse_exposition_labels_and_noise():
    text = "\n".join([
        "# HELP drand_last_beacon_round tip",
        "# TYPE drand_last_beacon_round gauge",
        'drand_last_beacon_round{beacon_id="default"} 42.0',
        'drand_breaker_state{peer="10.0.0.1:80"} 1.0',
        'drand_breaker_state{peer="10.0.0.2:80"} 0.0',
        'drand_weird{a="x,y",b="esc\\"q"} 7',
        "drand_serve_inflight 3.0",
        "not a metric line at all",
    ])
    fams = parse_exposition(text)
    assert fams["drand_last_beacon_round"] == [({"beacon_id": "default"},
                                                42.0)]
    assert len(fams["drand_breaker_state"]) == 2
    labels, v = fams["drand_weird"][0]
    assert labels == {"a": "x,y", "b": 'esc"q'} and v == 7.0
    assert fams["drand_serve_inflight"] == [({}, 3.0)]
    assert "not" not in fams


def test_node_view_from_exposition():
    text = "\n".join([
        'drand_last_beacon_round{beacon_id="default"} 17',
        'drand_last_beacon_round{beacon_id="alt"} 9',
        "drand_beacon_lag_rounds 0.5",
        'drand_breaker_state{peer="a:1"} 0',
        'drand_breaker_state{peer="b:2"} 1',
        "drand_serve_shed_total 4",
        'drand_signer_participation_ratio{beacon_id="default",signer="0"} 1.0',
        'drand_signer_participation_ratio{beacon_id="default",signer="1"} 0.5',
        'drand_threshold_margin{beacon_id="default"} 1',
        'drand_fleet_tip_skew_rounds{beacon_id="default",peer="b:2"} -3',
        "drand_fleet_fork_detected_total 2",
    ])
    view = NodeView.from_exposition("n0:1", text, is_self=True)
    assert view.ok and view.is_self
    assert view.tip == 17 and view.beacons == {"default": 17, "alt": 9}
    assert view.breakers_open == 1
    assert view.serve_shed == 4
    assert view.participation == {"0": 1.0, "1": 0.5}
    assert view.threshold_margin == 1
    assert view.tip_skew == {"b:2": -3.0}
    assert view.forks_detected == 2
    d = view.to_dict()
    assert d["address"] == "n0:1" and d["tip"] == 17


def test_render_table_covers_all_nodes():
    snap = FleetSnapshot(
        nodes=[
            NodeView.from_exposition(
                "n0:1", 'drand_last_beacon_round{beacon_id="default"} 5\n'
                'drand_threshold_margin{beacon_id="default"} 1',
                is_self=True),
            NodeView(address="n1:2", ok=False, error="scrape timeout"),
        ],
        groups={"default": {"size": 2, "threshold": 2}})
    out = render_table(snap.to_dict())
    assert "n0:1 *" in out
    assert "DOWN (scrape timeout)" in out
    assert "group default: n=2 t=2" in out
    assert "reachable 1/2" in out


class _Node:
    def __init__(self, address):
        self.address = address
        self.tls = False


class _Group:
    def __init__(self, nodes, threshold):
        self.nodes = nodes
        self.size = len(nodes)
        self.threshold = threshold


class _Keypair:
    class public:  # noqa: N801 — attribute stand-in
        address = "self:1"


class _BP:
    def __init__(self, group):
        self.group = group
        self.keypair = _Keypair()

    def status(self):
        return {"is_empty": True}


class _FleetStub:
    """Daemon surface collect_fleet needs: processes with a group, and
    the peer-metrics proxy seam."""

    def __init__(self, payloads):
        nodes = [_Node("self:1")] + [_Node(a) for a in payloads]
        self.processes = {"default": _BP(_Group(nodes, 2))}
        self._payloads = payloads

    async def fetch_peer_metrics(self, addr):
        payload = self._payloads[addr]
        if isinstance(payload, Exception):
            raise payload
        if payload is None:
            await asyncio.sleep(3600)          # hanging peer
        return payload


def test_collect_fleet_folds_peers_and_bounds_failures():
    async def main():
        payloads = {
            "peer-ok:1":
                b'drand_last_beacon_round{beacon_id="default"} 12',
            "peer-dead:2": RuntimeError("connection refused"),
            "peer-hang:3": None,
        }
        snap = await collect_fleet(_FleetStub(payloads), timeout_s=0.2)
        by_addr = {n.address: n for n in snap.nodes}
        assert by_addr["self:1"].is_self and by_addr["self:1"].ok
        assert by_addr["peer-ok:1"].ok and by_addr["peer-ok:1"].tip == 12
        assert not by_addr["peer-dead:2"].ok
        assert "connection refused" in by_addr["peer-dead:2"].error
        assert not by_addr["peer-hang:3"].ok
        assert by_addr["peer-hang:3"].error == "scrape timeout"
        assert snap.reachable == 2 and len(snap.nodes) == 4
        assert snap.max_tip == 12
        d = snap.to_dict()
        assert d["total"] == 4 and d["groups"]["default"]["size"] == 4

    asyncio.run(main())


def test_observatory_debug_routes_on_stub_daemon():
    """/debug/participation (snapshot + limit validation),
    /debug/consistency 404 without a prober, /debug/fleet 404 without
    processes — no live group needed."""
    import aiohttp

    from drand_tpu.metrics import MetricsServer

    class _Handler:
        def __init__(self):
            self.ledger = ParticipationLedger(group_size=2, threshold=2,
                                              beacon_id="default")

    class _RouteBP:
        group = None

        def __init__(self):
            self.handler = _Handler()

        def status(self):
            return {"is_empty": True}

    class _RouteDaemon:
        def __init__(self, processes=None):
            self.processes = processes or {}

        async def fetch_peer_metrics(self, addr):
            raise KeyError(addr)

    async def main():
        bp = _RouteBP()
        led = bp.handler.ledger
        for r in (1, 2):
            led.note_partial(0, r)
            led.note_partial(1, r)
            _recover(led, r, (0, 1))
        ms = MetricsServer(_RouteDaemon({"default": bp}), 0)
        await ms.start()
        try:
            base = f"http://127.0.0.1:{ms.port}"
            async with aiohttp.ClientSession() as http:
                async with http.get(f"{base}/debug/participation") as resp:
                    assert resp.status == 200
                    body = await resp.json()
                    assert body["default"]["rounds_recovered"] == 2
                    assert body["default"]["last_final_margin"] == 0
                async with http.get(
                        f"{base}/debug/participation?limit=0") as resp:
                    assert resp.status == 400
                async with http.get(
                        f"{base}/debug/participation?limit=x") as resp:
                    assert resp.status == 400
                # no prober attached to the stub -> 404
                async with http.get(f"{base}/debug/consistency") as resp:
                    assert resp.status == 404
        finally:
            await ms.stop()

        # no processes at all: participation AND fleet both 404
        ms2 = MetricsServer(_RouteDaemon(), 0)
        await ms2.start()
        try:
            base = f"http://127.0.0.1:{ms2.port}"
            async with aiohttp.ClientSession() as http:
                for route in ("/debug/participation", "/debug/fleet"):
                    async with http.get(base + route) as resp:
                        assert resp.status == 404
        finally:
            await ms2.stop()

    asyncio.run(main())

"""Per-peer async gRPC clients with connection caching.

Counterpart of `net/client_grpc.go:29-49,286-334` (per-peer cached
grpc.ClientConn, 1-minute default call timeout) and the streaming clients
for SyncChain / PublicRandStream (`:220-258`, `:106-147`).  Also the
transport implementation behind the beacon Handler's `BeaconNetwork`
interface (drand_tpu/beacon/node.py).
"""

from __future__ import annotations

import asyncio

import grpc
import grpc.aio

from drand_tpu import log as dlog
from drand_tpu.beacon.chain import PartialPacket
from drand_tpu.beacon.node import BeaconNetwork
from drand_tpu.chain.beacon import Beacon
from drand_tpu.net.gateway import DEFAULT_TIMEOUT_S
from drand_tpu.net.rpc import ServiceStub
from drand_tpu.protogen import common_pb2, drand_pb2

log = dlog.get("net")


def make_metadata(beacon_id: str = "default",
                  chain_hash: bytes = b"") -> common_pb2.Metadata:
    from drand_tpu import tracing
    from drand_tpu.common import VERSION
    md = common_pb2.Metadata(
        node_version=common_pb2.NodeVersion(
            major=VERSION.major, minor=VERSION.minor, patch=VERSION.patch),
        beaconID=beacon_id, chain_hash=chain_hash)
    # trace-context propagation: every outgoing RPC carries the calling
    # task's active span, so the peer's spans parent to ours
    tracing.inject(md)
    return md


class PeerClients:
    """Cached channels/stubs keyed by peer address
    (net/client_grpc.go:286-334)."""

    def __init__(self, tls_ca: str | None = None,
                 trust_pem: bytes | None = None,
                 timeout_s: float = DEFAULT_TIMEOUT_S):
        """tls_ca: path to a root PEM; trust_pem: in-memory PEM pool (a
        net.certs.CertManager.pool_pem())."""
        self._channels: dict[tuple[str, bool], grpc.aio.Channel] = {}
        self._tls_ca = tls_ca
        self._trust_pem = trust_pem
        self.timeout_s = timeout_s

    def channel(self, address: str, tls: bool = False) -> grpc.aio.Channel:
        key = (address, tls)
        if key not in self._channels:
            if tls:
                pem = self._trust_pem
                if pem is None and self._tls_ca:
                    with open(self._tls_ca, "rb") as f:
                        pem = f.read()
                creds = grpc.ssl_channel_credentials(pem)
                self._channels[key] = grpc.aio.secure_channel(address, creds)
            else:
                self._channels[key] = grpc.aio.insecure_channel(address)
        return self._channels[key]

    def protocol(self, address: str, tls: bool = False) -> ServiceStub:
        return ServiceStub(self.channel(address, tls), "Protocol")

    def public(self, address: str, tls: bool = False) -> ServiceStub:
        return ServiceStub(self.channel(address, tls), "Public")

    def metrics(self, address: str, tls: bool = False) -> ServiceStub:
        return ServiceStub(self.channel(address, tls), "MetricsService")

    async def close(self):
        for ch in self._channels.values():
            await ch.close()
        self._channels.clear()


class GrpcBeaconNetwork(BeaconNetwork):
    """Protocol-service transport for the beacon Handler: partial fan-out,
    chain sync streams, peer status.  Every unary send routes through the
    resilience hub (drand_tpu/resilience): seeded-backoff retries inside
    a deadline budget, gated by the target peer's circuit breaker."""

    # this node's own protocol address (set by BeaconProcess once the
    # keypair loads): the `src` half of chaos failpoint contexts, so
    # seeded partitions can target (src, dst) pairs
    local_addr: str = ""

    def __init__(self, peers: PeerClients, beacon_id: str = "default",
                 resilience=None):
        from drand_tpu.resilience import Resilience
        self.peers = peers
        self.beacon_id = beacon_id
        self.resilience = resilience or Resilience()

    async def send_partial(self, node, packet: PartialPacket,
                           deadline=None) -> None:
        from drand_tpu import tracing
        from drand_tpu.chaos import failpoints as chaos
        from drand_tpu.resilience import Deadline, deadline as dl_mod
        res = self.resilience
        # default budget = the legacy flat timeout; the Handler passes a
        # round-derived Deadline (period/2) on the hot path
        dl = deadline or Deadline.after(res.clock, self.peers.timeout_s)
        stub = self.peers.protocol(node.address, getattr(node, "tls", False))
        breaker = res.breakers.get(node.address)
        with tracing.span("partial.send", beacon_id=packet.beacon_id,
                          round_=packet.round, peer=node.address):
            async def attempt(_n):
                # the failpoint sits INSIDE the retried attempt so chaos
                # drop/delay rules exercise the retry path; `times`-capped
                # rules let a later attempt through (the recovery proof)
                await chaos.failpoint("net.send_partial", src=self.local_addr,
                                      dst=node.address, round=packet.round)
                req = drand_pb2.PartialBeaconPacket(
                    round=packet.round,
                    previous_sig=packet.previous_signature,
                    partial_sig=packet.partial_sig,
                    metadata=make_metadata(packet.beacon_id))
                dl_mod.stamp(req.metadata, dl)
                await stub.PartialBeacon(
                    req, timeout=dl.timeout(cap=self.peers.timeout_s))

            await res.retry.call("net.send_partial", attempt,
                                 peer=node.address, key=f"r{packet.round}",
                                 deadline=dl, breaker=breaker)

    async def sync_chain(self, node, from_round: int):
        """The peer's stream as Beacons or PackedBeacons.  Two readings
        a message go as counters to the span current in the consumer's
        context (`sync.catchup`, whose fetch loop files them on its
        `sync.fetch` span a segment): `recv_s`, the stream's read, which
        is the serving node and the transport, and `decode_s`, from
        there to the `yield`; with them the message's `bytes`."""
        import os as _os
        import time

        from drand_tpu import tracing
        from drand_tpu.chain.segment import WIRE_CHUNK_DEFAULT, PackedBeacons
        from drand_tpu.chaos import failpoints as chaos
        from drand_tpu.core import convert
        stub = self.peers.protocol(node.address, getattr(node, "tls", False))
        # advertise chunk capability (ISSUE 13): reference servers ignore
        # the unknown field and keep streaming per-beacon — the consumer
        # handles both shapes below.  0 disables chunking (the bench A/B
        # control and an escape hatch).
        wire_chunk = int(_os.environ.get("DRAND_TPU_SYNC_WIRE_CHUNK",
                                         str(WIRE_CHUNK_DEFAULT)))
        req = drand_pb2.SyncRequest(from_round=from_round,
                                    chunk_size=max(0, wire_chunk),
                                    metadata=make_metadata(self.beacon_id))
        call = stub.SyncChain(req)
        stream = call.__aiter__()
        try:
            while True:
                t0 = time.perf_counter()
                try:
                    pkt = await stream.__anext__()
                except StopAsyncIteration:
                    return
                t1 = time.perf_counter()
                item = convert.packet_to_item(pkt)
                packed = isinstance(item, PackedBeacons)
                # drop = the stream is cut mid-flight (the consumer's peer
                # loop falls back); delay = a slow stream.  src is the
                # SERVING peer: chaos ctx follows message direction.  One
                # site visit per wire MESSAGE — for a chunk that is one
                # visit per 512 rounds, the protocol-level win made visible
                # to chaos rules.  The ctx round is the chunk's START (the
                # cut position): the stream start is pinned by the request's
                # from_round, while the chunk END rides the serving peer's
                # tip — a value that races the rest of the scenario and
                # would make seeded injection logs unreplayable.
                await chaos.failpoint(
                    "net.sync_recv", src=node.address, dst=self.local_addr,
                    round=item.start_round if packed else item.round)
                try:
                    from drand_tpu import metrics as M
                    M.SYNC_ROUNDS.labels(
                        self.beacon_id,
                        "chunk" if packed else "single").inc(
                            len(item) if packed else 1)
                except Exception:
                    pass
                tracing.count(recv_s=t1 - t0,
                              decode_s=time.perf_counter() - t1,
                              bytes=pkt.ByteSize())
                yield item
        finally:
            # a consumer that stops reading (a bounded catch-up at its
            # `up_to`) closes this generator, which by itself tells the
            # peer nothing: it would send until flow control stops it
            # and hold the stream open for good.  No-op once the stream
            # has ended.
            call.cancel()

    async def status(self, node) -> dict:
        from drand_tpu.chaos import failpoints as chaos
        stub = self.peers.protocol(node.address, getattr(node, "tls", False))
        # the health watchdog's connectivity probe rides this RPC: the
        # chaos seam makes a partition visible to it (drop = peer down).
        # Deliberately NOT breaker-gated — this IS the probe path; the
        # watchdog records its outcome into the breaker registry
        # (health/watchdog.py), covering timeouts this frame can't see.
        await chaos.failpoint("net.ping", src=self.local_addr,
                              dst=node.address)
        resp = await stub.Status(
            drand_pb2.StatusRequest(metadata=make_metadata(self.beacon_id)),
            timeout=self.peers.timeout_s)
        return {
            "beacon": {"is_running": resp.beacon.is_running,
                       "is_serving": resp.beacon.is_serving},
            "chain_store": {"last_round": resp.chain_store.last_round,
                            "length": resp.chain_store.length,
                            "is_empty": resp.chain_store.is_empty},
        }

    async def get_identity(self, address: str, tls: bool = False):
        stub = self.peers.protocol(address, tls)
        return await stub.GetIdentity(
            drand_pb2.IdentityRequest(metadata=make_metadata(self.beacon_id)),
            timeout=self.peers.timeout_s)


class ControlClient:
    """Localhost control-plane client used by the CLI
    (net/control.go:55-426)."""

    def __init__(self, port: int, host: str = "127.0.0.1",
                 timeout_s: float = DEFAULT_TIMEOUT_S):
        self._channel = grpc.aio.insecure_channel(f"{host}:{port}")
        self.stub = ServiceStub(self._channel, "Control")
        self.timeout_s = timeout_s

    async def ping(self, beacon_id: str = "default"):
        await self.stub.PingPong(
            drand_pb2.Ping(metadata=make_metadata(beacon_id)),
            timeout=self.timeout_s)

    async def close(self):
        await self._channel.close()

"""Observer-mode chain following (StartFollowChain).

Counterpart of `core/drand_beacon_control.go:1055-1165`: fetch + verify the
chain info from the given peers (hash check against metadata when
provided), build a store for the beacon id, and drive the sync manager
against those peers, streaming progress back to the CLI.
"""

from __future__ import annotations

import asyncio

from drand_tpu import log as dlog
from drand_tpu.beacon.sync_manager import SyncManager, SyncRequest
from drand_tpu.chain.scheme import scheme_by_id
from drand_tpu.chain.store import new_chain_store
from drand_tpu.chain.verify import ChainVerifier
from drand_tpu.core import convert
from drand_tpu.key.group import Node
from drand_tpu.net.client import GrpcBeaconNetwork, make_metadata
from drand_tpu.protogen import drand_pb2

log = dlog.get("core")


async def chain_info_from_peers(peers, addresses, tls, beacon_id,
                                expected_hash: bytes | None = None):
    """Query peers for chain info until one answers with a matching hash
    (core/drand_beacon_control.go:1259-1287)."""
    last_exc = None
    for addr in addresses:
        try:
            stub = peers.public(addr, tls)
            pkt = await stub.ChainInfo(
                drand_pb2.ChainInfoRequest(metadata=make_metadata(beacon_id)),
                timeout=10.0)
            info = convert.info_from_proto(pkt)
            if expected_hash and info.hash() != expected_hash:
                raise ValueError(
                    f"chain info hash mismatch from {addr}")
            return info
        except Exception as exc:
            last_exc = exc
    raise RuntimeError(f"no peer returned usable chain info: {last_exc}")


async def follow_chain(daemon, request):
    """Async generator of (current, target) progress pairs."""
    md = request.metadata
    beacon_id = md.beaconID or "default"
    expected = md.chain_hash or None
    addresses = list(request.nodes)
    if not addresses:
        raise RuntimeError("StartFollowChain needs at least one peer")

    info = await chain_info_from_peers(daemon.peers, addresses,
                                       request.is_tls, beacon_id, expected)

    # observer store under multibeacon/<id>/db, like a real process
    bp = daemon.processes.get(beacon_id) or daemon.instantiate(beacon_id)
    import os
    folder = os.path.join(daemon.config.multibeacon_folder, beacon_id, "db")
    os.makedirs(folder, mode=0o700, exist_ok=True)

    class _FollowGroup:
        period = info.period
        genesis_time = info.genesis_time
        scheme_id = info.scheme_id
        threshold = 0

    store = new_chain_store(os.path.join(folder, "drand.db"), _FollowGroup,
                            clock=daemon.config.clock.now,
                            beacon_id=beacon_id)
    verifier = ChainVerifier(scheme_by_id(info.scheme_id), info.public_key,
                             beacon_id=beacon_id)
    nodes = [Node(key=b"", address=a, tls=request.is_tls, index=i)
             for i, a in enumerate(addresses)]
    network = GrpcBeaconNetwork(daemon.peers, beacon_id,
                                resilience=daemon.resilience)
    sm = SyncManager(store, _FollowGroup, verifier, network, nodes,
                     daemon.config.clock,
                     insecure_store=getattr(store, "insecure", None),
                     resilience=daemon.resilience, beacon_id=beacon_id)

    from drand_tpu.chain.time import current_round
    target = request.up_to or current_round(
        daemon.config.clock.now(), info.period, info.genesis_time)

    q: asyncio.Queue = asyncio.Queue(maxsize=64)
    sm.on_progress = lambda cur, tgt: q.put_nowait((cur, target))
    # begin/end (not `with`): the span brackets an async generator's
    # whole life, which ends in the finally below, not a lexical scope
    from drand_tpu import tracing
    sp = tracing.begin_span("sync.follow", beacon_id=beacon_id,
                            target=int(target), peers=len(addresses))
    try:
        # seed genesis so the append chain has an anchor
        from drand_tpu.chain.beacon import genesis_beacon
        from drand_tpu.chain.store import BeaconNotFound
        try:
            store.last()
        except BeaconNotFound:
            store.put(genesis_beacon(info.genesis_seed))
        yield 0, target
        task = asyncio.ensure_future(
            sm.sync(SyncRequest(from_round=1, up_to=request.up_to)))
        while not task.done():
            try:
                yield await asyncio.wait_for(q.get(), 0.5)
            except asyncio.TimeoutError:
                continue
        while not q.empty():
            yield q.get_nowait()
        # a bounded follow is true only with the store at `up_to`, false
        # only once every peer was tried; an unbounded one is true where
        # any peer's stream committed anything
        ok = task.result()
        last = store.last()
        yield last.round, target
        if not ok and last.round < target:
            sp.set(stalled_at=last.round)
            raise RuntimeError(
                f"follow stalled at round {last.round}/{target}")
    except BaseException:
        sp.status = "error"
        raise
    finally:
        sp.end()
        store.close()

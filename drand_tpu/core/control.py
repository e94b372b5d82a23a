"""Control service: the localhost operator plane behind the CLI.

Counterpart of `core/drand_beacon_control.go` routed through the daemon
demux (`core/drand_daemon_control.go:19-45`): DKG/reshare initiation,
share/key/group queries, follow/check chain streams, DB backup, shutdown.
"""

from __future__ import annotations

import asyncio

import grpc

from drand_tpu import log as dlog
from drand_tpu.core import convert
from drand_tpu.core.services import _Demux, _meta_beacon_id
from drand_tpu.net.client import make_metadata
from drand_tpu.protogen import drand_pb2

log = dlog.get("core")

# `StartCheckChain` says a check's counts in its trailing metadata, as
# `drand-check-<name>` (`CheckResult.counts`: rows scanned, rounds
# flagged, mended and left)
CHECK_COUNT_PREFIX = "drand-check-"


class ControlService(_Demux):
    async def PingPong(self, request, context):
        return drand_pb2.Pong(metadata=make_metadata())

    async def ListSchemes(self, request, context):
        from drand_tpu.chain.scheme import list_schemes
        return drand_pb2.ListSchemesResponse(ids=list_schemes(),
                                             metadata=make_metadata())

    async def ListBeaconIDs(self, request, context):
        return drand_pb2.ListBeaconIDsResponse(
            ids=sorted(self.daemon.processes.keys()),
            metadata=make_metadata())

    async def Status(self, request, context):
        bp = await self._process(request, context)
        st = bp.status()
        resp = drand_pb2.StatusResponse()
        resp.beacon.is_running = st["is_running"]
        resp.beacon.is_serving = st["is_running"]
        resp.chain_store.is_empty = st["is_empty"]
        resp.chain_store.last_round = st["last_round"]
        resp.chain_store.length = st["length"]
        return resp

    async def Share(self, request, context):
        bp = await self._process(request, context)
        if bp.share is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "no share")
        return drand_pb2.ShareResponse(
            index=bp.share.share_index(),
            share=bp.share.public().key_bytes(),
            metadata=make_metadata(bp.beacon_id))

    async def PublicKey(self, request, context):
        bp = await self._process(request, context)
        if bp.keypair is None:
            bp.load_keypair()
        return drand_pb2.PublicKeyResponse(
            pubKey=bp.keypair.public.key,
            metadata=make_metadata(bp.beacon_id))

    async def PrivateKey(self, request, context):
        bp = await self._process(request, context)
        if bp.keypair is None:
            bp.load_keypair()
        return drand_pb2.PrivateKeyResponse(
            priKey=bp.keypair.secret.to_bytes(32, "big"),
            metadata=make_metadata(bp.beacon_id))

    async def ChainInfo(self, request, context):
        bp = await self._process(request, context)
        return convert.info_to_proto(bp.chain_info())

    async def GroupFile(self, request, context):
        bp = await self._process(request, context)
        if bp.group is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "no group")
        return convert.group_to_proto(bp.group)

    async def InitDKG(self, request, context):
        bp = await self._process(request, context)
        from drand_tpu.core.dkg_runner import run_init_dkg
        try:
            group = await run_init_dkg(self.daemon, bp, request)
        except Exception as exc:
            log.exception("InitDKG failed")
            if context is None:
                raise
            await context.abort(grpc.StatusCode.INTERNAL, f"dkg failed: {exc}")
        return convert.group_to_proto(group)

    async def InitReshare(self, request, context):
        bp = await self._process(request, context)
        from drand_tpu.core.dkg_runner import run_init_reshare
        try:
            group = await run_init_reshare(self.daemon, bp, request)
        except Exception as exc:
            log.exception("InitReshare failed")
            if context is None:
                raise
            await context.abort(grpc.StatusCode.INTERNAL, f"reshare failed: {exc}")
        return convert.group_to_proto(group)

    async def LoadBeacon(self, request, context):
        bid = _meta_beacon_id(request)
        bp = self.daemon.processes.get(bid) or self.daemon.instantiate(bid)
        if bp._started:
            # already serving (daemon start auto-loads from disk) —
            # re-building the engine under a live handler would wedge it
            return drand_pb2.LoadBeaconResponse(metadata=make_metadata(bid))
        if bp.load():
            self.daemon.register_chain_hash(bp)
            await bp.start(catchup=True)
        return drand_pb2.LoadBeaconResponse(metadata=make_metadata(bid))

    async def StartFollowChain(self, request, context):
        """Observer-mode sync from a list of peers
        (core/drand_beacon_control.go:1055-1165)."""
        from drand_tpu.core.follow import follow_chain
        async for current, target in follow_chain(self.daemon, request):
            yield drand_pb2.SyncProgress(current=current, target=target)

    async def StartCheckChain(self, request, context):
        """Validate + repair the local chain
        (core/drand_beacon_control.go:1168-1257): all of it is
        `SyncManager.check_chain`.  The scan's progress streams as it is
        made; the counts ride the trailing metadata (`CHECK_COUNT_PREFIX`), and
        a check that leaves rounds unmended ends DATA_LOSS, naming how
        many."""
        bp = await self._process(request, context)
        if bp.sync_manager is None:
            await context.abort(grpc.StatusCode.FAILED_PRECONDITION,
                          "beacon not loaded")
        progress: asyncio.Queue = asyncio.Queue()
        check = asyncio.ensure_future(bp.sync_manager.check_chain(
            request.up_to or None,
            on_progress=lambda current, target:
                progress.put_nowait((current, target))))
        check.add_done_callback(lambda _: progress.put_nowait(None))
        try:
            while (step := await progress.get()) is not None:
                yield drand_pb2.SyncProgress(current=step[0], target=step[1])
            result = await check
        finally:
            check.cancel()
        counts = tuple((CHECK_COUNT_PREFIX + name, str(n))
                       for name, n in result.counts().items())
        if result.unfixed:
            await context.abort(
                grpc.StatusCode.DATA_LOSS,
                f"{len(result.unfixed)} rounds left unmended (first "
                f"{result.unfixed[:8]})", trailing_metadata=counts)
        context.set_trailing_metadata(counts)

    async def BackupDatabase(self, request, context):
        bp = await self._process(request, context)
        if bp._store is None:
            await context.abort(grpc.StatusCode.FAILED_PRECONDITION,
                          "beacon not loaded")
        bp._store.save_to(request.output_file)
        return drand_pb2.BackupDBResponse(metadata=make_metadata())

    async def RemoteStatus(self, request, context):
        resp = drand_pb2.RemoteStatusResponse()
        bid = _meta_beacon_id(request)
        for addr in request.addresses:
            try:
                stub = self.daemon.peers.protocol(addr.address, addr.tls)
                st = await stub.Status(
                    drand_pb2.StatusRequest(metadata=make_metadata(bid)),
                    timeout=5.0)
                resp.statuses[addr.address].CopyFrom(st)
            except Exception:
                resp.statuses[addr.address].CopyFrom(
                    drand_pb2.StatusResponse())
        return resp

    async def Shutdown(self, request, context):
        async def _stop():
            await asyncio.sleep(0.2)
            await self.daemon.stop()
        asyncio.get_running_loop().create_task(_stop())
        return drand_pb2.ShutdownResponse(metadata=make_metadata())

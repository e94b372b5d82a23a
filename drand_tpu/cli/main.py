"""CLI entry point and command implementations.

Counterpart of `cmd/drand-cli/cli.go` (flags/commands, :62-530) and
`control.go` (command impls over `net.ControlClient`, :101-833).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

from drand_tpu.net.client import ControlClient, make_metadata
from drand_tpu.protogen import drand_pb2

DEFAULT_FOLDER = os.path.expanduser("~/.drand")
DEFAULT_CONTROL = 8888


def _base_flags(p: argparse.ArgumentParser):
    p.add_argument("--folder", default=DEFAULT_FOLDER,
                   help="drand state folder")
    p.add_argument("--control", type=int, default=DEFAULT_CONTROL,
                   help="control port")
    p.add_argument("--id", default="default", dest="beacon_id",
                   help="beacon id")


def _secret(args) -> bytes:
    """DKG secret: --secret-file or DRAND_SHARE_SECRET
    (cmd/drand-cli/control.go:44-62)."""
    if getattr(args, "secret_file", None):
        with open(args.secret_file, "rb") as f:
            return f.read().strip()
    env = os.environ.get("DRAND_SHARE_SECRET", "")
    if not env:
        raise SystemExit(
            "missing DKG secret: pass --secret-file or set "
            "DRAND_SHARE_SECRET")
    return env.encode()


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="drand-tpu",
                                description=__doc__.splitlines()[0])
    sub = p.add_subparsers(dest="command", required=True)

    sp = sub.add_parser("start", help="run the daemon")
    _base_flags(sp)
    sp.add_argument("--private-listen", default="0.0.0.0:4444")
    sp.add_argument("--public-listen", default="")
    sp.add_argument("--metrics", type=int, default=0)
    # TLS is the default transport posture (cmd/drand-cli/cli.go:62-119):
    # an operator must either supply a cert/key pair or EXPLICITLY opt out
    # with --tls-disable (--insecure is the historical alias).  cmd_start
    # enforces the either/or.
    sp.add_argument("--tls-cert", help="PEM certificate for the private "
                    "gRPC listener")
    sp.add_argument("--tls-key", help="PEM key for --tls-cert")
    sp.add_argument("--tls-disable", "--insecure", dest="tls_disable",
                    action="store_true", default=False,
                    help="run without TLS (tests, local nets)")
    sp.add_argument("--certs-dir", default="",
                    help="folder of trusted peer certificate PEMs "
                    "(self-signed group deployments); system roots are "
                    "used when empty")
    sp.add_argument("--private-rand", action="store_true", default=False,
                    help="serve ECIES private randomness (opt-in)")

    sp = sub.add_parser("stop", help="stop the daemon")
    _base_flags(sp)

    sp = sub.add_parser("generate-keypair",
                        help="create the longterm keypair")
    _base_flags(sp)
    sp.add_argument("address", help="public address host:port")
    sp.add_argument("--tls", action="store_true")
    sp.add_argument("--source", default="",
                    help="executable whose stdout seeds the keypair, "
                    "XOR-mixed with the OS CSPRNG")
    sp.add_argument("--user-source-only", action="store_true", default=False)

    sp = sub.add_parser("share", help="run DKG / reshare")
    _base_flags(sp)
    sp.add_argument("--leader", action="store_true")
    sp.add_argument("--connect", default="", help="leader address")
    sp.add_argument("--tls-disable", "--insecure", dest="tls_disable",
                    action="store_true", default=False,
                    help="dial the leader without TLS (must match the "
                    "network's transport posture)")
    sp.add_argument("--nodes", type=int, default=0)
    sp.add_argument("--threshold", type=int, default=0)
    sp.add_argument("--period", type=int, default=30)
    sp.add_argument("--catchup-period", type=int, default=0)
    sp.add_argument("--scheme", default="pedersen-bls-chained")
    sp.add_argument("--timeout", type=int, default=10)
    sp.add_argument("--secret-file")
    sp.add_argument("--source", default="",
                    help="executable whose stdout supplies DKG entropy, "
                    "XOR-mixed with the OS CSPRNG "
                    "(cmd/drand-cli/cli.go sourceFlag)")
    sp.add_argument("--user-source-only", action="store_true", default=False,
                    help="use ONLY --source entropy (no CSPRNG mixing)")
    sp.add_argument("--transition", action="store_true",
                    help="reshare from the existing group")
    sp.add_argument("--from", dest="old_group_path", default="",
                    help="previous group TOML (joining a reshare)")

    sp = sub.add_parser("load", help="load a beacon from disk")
    _base_flags(sp)

    sp = sub.add_parser("sync", help="follow/sync a chain from peers")
    _base_flags(sp)
    sp.add_argument("--sync-nodes", required=True,
                    help="comma-separated peer addresses")
    sp.add_argument("--up-to", type=int, default=0)
    sp.add_argument("--follow", action="store_true")
    sp.add_argument("--chain-hash", default="")

    sp = sub.add_parser("get", help="fetch randomness / chain info")
    _base_flags(sp)
    sp.add_argument("what", choices=["public", "private", "chain-info"])
    sp.add_argument("round", nargs="?", type=int, default=0)
    sp.add_argument("--url", action="append", default=[],
                    help="HTTP API endpoints")
    sp.add_argument("--watch", action="store_true", default=False,
                    help="get public: stream rounds as they land "
                    "(failover via the optimizing client stack); each "
                    "emitted round logs with its per-round trace id")
    sp.add_argument("--chain-hash", default="")
    sp.add_argument("--group", default="",
                    help="group TOML (get private: node picked from it)")
    sp.add_argument("--certs-dir", default="",
                    help="trusted peer certificate PEMs for TLS group "
                    "members (self-signed deployments)")

    sp = sub.add_parser("show", help="print local state")
    _base_flags(sp)
    sp.add_argument("what", choices=["share", "group", "chain-info",
                                     "public", "private"])

    sp = sub.add_parser("util", help="operator utilities")
    _base_flags(sp)
    sp.add_argument("what", choices=["status", "ping", "list-schemes",
                                     "list-ids", "check", "backup",
                                     "self-sign", "reset", "del-beacon",
                                     "remote-status", "migrate", "health",
                                     "fsck", "journey", "fleet"])
    sp.add_argument("target", nargs="?", default="",
                    help="util health: the node's public HTTP address "
                    "(host:port or URL) to probe; util fsck: the chain "
                    "db path to scan; util journey: the round number "
                    "to reconstruct; util fleet: any group member's "
                    "metrics address (host:port) to pull /debug/fleet "
                    "from")
    sp.add_argument("--nodes", default="",
                    help="util journey: comma-separated metrics "
                    "addresses (host:port) to pull /debug/spans from")
    sp.add_argument("--repair", action="store_true",
                    help="util fsck: quarantine damaged rows and roll "
                    "the tip back to the verified prefix (forensic "
                    "sidecar, nothing deleted)")
    sp.add_argument("--json", action="store_true", dest="json_out",
                    help="util fsck: machine-readable report on stdout")
    sp.add_argument("--up-to", type=int, default=0,
                    help="util check: rows above this round are neither "
                    "read nor judged (0 = the whole stored chain)")

    sp = sub.add_parser("relay", help="run an HTTP relay over upstreams")
    sp.add_argument("--url", action="append", required=True,
                    help="upstream HTTP API endpoints")
    sp.add_argument("--chain-hash", required=True)
    sp.add_argument("--listen", default="0.0.0.0:8080")

    sp = sub.add_parser("relay-pubsub",
                        help="run a push-distribution relay node")
    sp.add_argument("--url", action="append", default=[],
                    help="upstream HTTP API endpoints (optional when "
                    "--bootstrap is given: a pure mesh node learns "
                    "rounds from its peers)")
    sp.add_argument("--chain-hash", required=True)
    sp.add_argument("--listen", default="0.0.0.0:4454")
    sp.add_argument("--bootstrap", default="",
                    help="comma-separated gossip peers; enables the "
                    "self-assembling mesh (peer exchange + degree-D "
                    "subscriptions) instead of a standalone relay")
    sp.add_argument("--degree", type=int, default=3,
                    help="gossip mesh degree (subscriptions kept live)")
    sp.add_argument("--advertise", default="",
                    help="address peers should dial back (defaults to "
                    "the bound listen address)")

    sp = sub.add_parser("lint", help="run the project linter "
                        "(tools/lint: async/clock/jit/secret hygiene)")
    sp.add_argument("paths", nargs="*",
                    help="files/dirs relative to the repo root "
                    "(default: drand_tpu demo tools)")
    sp.add_argument("--format", choices=["text", "json"], default="text",
                    dest="lint_format")
    sp.add_argument("--rule", action="append", default=None,
                    metavar="NAME", dest="lint_rules",
                    help="run only this rule (repeatable; --list-rules "
                    "shows names)")
    sp.add_argument("--no-baseline", action="store_true",
                    help="report every finding, baselined or not")
    sp.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline keeping surviving "
                    "justifications")
    sp.add_argument("--list-rules", action="store_true")

    sp = sub.add_parser("chaos", help="deterministic fault injection: "
                        "list failpoint sites/scenarios, run or replay "
                        "seeded multi-node chaos scenarios")
    sp.add_argument("action", choices=["list", "run", "replay"])
    sp.add_argument("scenario", nargs="?", default="",
                    help="scenario name (chaos list shows them)")
    sp.add_argument("--seed", type=int, default=1,
                    help="schedule seed: same seed, same injections — "
                    "replay a failing run by its seed")
    sp.add_argument("--nodes", type=int, default=3)
    sp.add_argument("--threshold", type=int, default=0,
                    help="0 = majority (n//2 + 1)")
    sp.add_argument("--scheme", default="pedersen-bls-unchained")
    sp.add_argument("--json", action="store_true", dest="chaos_json",
                    help="machine-readable report")
    sp.add_argument("--sanitize", action="store_true",
                    help="arm the runtime asyncio sanitizer across the "
                    "fault window (loop-blocking callbacks, unlocked / "
                    "cross-task mutations); also via "
                    "DRAND_TPU_ASYNC_SANITIZE=1")

    sp = sub.add_parser("relay-s3", help="relay rounds into an object "
                        "store (cmd/relay-s3/main.go)")
    sp.add_argument("--url", action="append", required=True,
                    help="upstream HTTP API endpoints")
    sp.add_argument("--chain-hash", required=True)
    sp.add_argument("--bucket", required=True,
                    help="S3 bucket name, or a filesystem path when "
                    "boto3 is unavailable / --fs is set")
    sp.add_argument("--prefix", default="public",
                    help="object key prefix (default: public)")
    sp.add_argument("--fs", action="store_true",
                    help="force the filesystem backend (treat --bucket "
                    "as a directory)")

    sp = sub.add_parser("objectsync",
                        help="content-addressed segment objects over dumb "
                        "object storage (supersedes relay-s3's per-round "
                        "JSON; drand_tpu/objectsync/)")
    sp.add_argument("action", choices=["publish", "sync", "status"])
    sp.add_argument("--dir", default="",
                    help="filesystem object-store root (tests, rsync-to-"
                    "bucket deployments)")
    sp.add_argument("--url", default="",
                    help="HTTP object-store base URL (S3-compatible "
                    "endpoint or any static server / CDN)")
    sp.add_argument("--db", default="",
                    help="chain store sqlite path (publish: source; "
                    "sync: destination)")
    sp.add_argument("--chain-hash", default="",
                    help="hex chain hash pinned into objects / verified "
                    "against the manifest")
    sp.add_argument("--scheme", default="",
                    help="scheme id (default: pedersen-bls-chained)")
    sp.add_argument("--public-key", default="",
                    help="hex group public key (sync: BLS verification)")
    sp.add_argument("--segment-rounds", type=int, default=0,
                    help="rounds per segment object (default 16384; an "
                    "existing manifest's value always wins)")
    sp.add_argument("--up-to", type=int, default=0,
                    help="sync: stop after this round (0 = whole chain)")
    sp.add_argument("--genesis-seed", default="",
                    help="sync: hex genesis seed to anchor an EMPTY "
                    "store (round-0 row); existing stores ignore it")
    return p


# ---------------------------------------------------------------------------
# Command implementations
# ---------------------------------------------------------------------------

async def cmd_start(args):
    from drand_tpu import log as dlog
    dlog.configure(level=os.environ.get("DRAND_LOG_LEVEL", "info"),
                   json_output=bool(os.environ.get("DRAND_LOG_JSON")))
    from drand_tpu.core import Config, DrandDaemon
    if not args.tls_disable and not (args.tls_cert and args.tls_key):
        raise SystemExit(
            "TLS is the default: provide --tls-cert and --tls-key, or "
            "explicitly opt out with --tls-disable "
            "(cmd/drand-cli/cli.go:62-119 enforces the same either/or)")
    cfg = Config(folder=args.folder, private_listen=args.private_listen,
                 public_listen=args.public_listen,
                 control_port=args.control, tls_cert=args.tls_cert,
                 tls_key=args.tls_key, insecure=args.tls_disable,
                 trusted_certs=[args.certs_dir] if args.certs_dir else [],
                 metrics_port=args.metrics,
                 enable_private_rand=args.private_rand)
    daemon = DrandDaemon(cfg)
    await daemon.start()
    loaded = await daemon.load_beacons_from_disk()
    print(f"daemon running: private={daemon.private_addr()} "
          f"control={cfg.control_port} beacons={loaded}")
    try:
        while daemon.control_listener is not None:
            await asyncio.sleep(1)
    except (KeyboardInterrupt, asyncio.CancelledError):
        await daemon.stop()


async def cmd_stop(args):
    cc = ControlClient(args.control)
    await cc.stub.Shutdown(drand_pb2.ShutdownRequest(
        metadata=make_metadata(args.beacon_id)), timeout=10)
    print("daemon stopping")
    await cc.close()


async def cmd_generate_keypair(args):
    from drand_tpu.key.keys import Pair
    from drand_tpu.key.store import FileStore
    ks = FileStore(args.folder, args.beacon_id)
    seed = None
    if args.source:
        from drand_tpu import entropy as ent
        seed = ent.get_random(ent.ScriptReader(args.source), 32,
                              args.user_source_only)
    pair = Pair.generate(args.address, tls=args.tls, seed=seed)
    ks.save_key_pair(pair)
    print(json.dumps({"address": args.address,
                      "public_key": pair.public.key.hex(),
                      "folder": args.folder, "beacon": args.beacon_id}))


async def cmd_share(args):
    if (args.transition or args.old_group_path) and args.source:
        # The reshare wire packet carries no EntropyInfo (ours and the
        # reference's, protobuf/drand/control.proto InitResharePacket):
        # resharing polynomials anchor on the existing share, and the
        # reference CLI silently drops --source here — reject loudly
        # (and before any channel is opened) instead of letting the
        # operator believe their entropy was used.
        raise SystemExit(
            "--source only applies to a fresh DKG (share without "
            "--transition/--from): resharing re-deals the existing "
            "secret and takes no user entropy")
    cc = ControlClient(args.control, timeout_s=600.0)
    secret = _secret(args)
    info = drand_pb2.SetupInfoPacket(
        leader=args.leader, leader_address=args.connect,
        nodes=args.nodes, threshold=args.threshold,
        timeout=args.timeout, secret=secret,
        leader_tls=not args.tls_disable)
    if args.transition or args.old_group_path:
        req = drand_pb2.InitResharePacket(
            info=info, catchup_period=args.catchup_period,
            metadata=make_metadata(args.beacon_id))
        if args.old_group_path:
            req.old.path = args.old_group_path
        group = await cc.stub.InitReshare(req, timeout=600)
    else:
        req = drand_pb2.InitDKGPacket(
            info=info, beacon_period=args.period,
            catchup_period=args.catchup_period, schemeID=args.scheme,
            metadata=make_metadata(args.beacon_id))
        if args.source:
            req.entropy.script = args.source
            req.entropy.userOnly = args.user_source_only
        group = await cc.stub.InitDKG(req, timeout=600)
    from drand_tpu.core import convert
    g = convert.group_from_proto(group)
    print(g.to_toml())
    await cc.close()


async def cmd_load(args):
    cc = ControlClient(args.control)
    await cc.stub.LoadBeacon(drand_pb2.LoadBeaconRequest(
        metadata=make_metadata(args.beacon_id)), timeout=30)
    print(f"beacon {args.beacon_id} loaded")
    await cc.close()


async def cmd_sync(args):
    cc = ControlClient(args.control, timeout_s=0)
    req = drand_pb2.StartSyncRequest(
        nodes=args.sync_nodes.split(","), up_to=args.up_to,
        metadata=make_metadata(
            args.beacon_id,
            bytes.fromhex(args.chain_hash) if args.chain_hash else b""))
    rpc = cc.stub.StartFollowChain if args.follow \
        else cc.stub.StartCheckChain
    async for progress in rpc(req):
        print(f"\rsync {progress.current}/{progress.target}",
              end="", flush=True)
    print()
    await cc.close()


async def cmd_get(args):
    if args.what == "public":
        if not args.url:
            raise SystemExit("get public needs at least one --url")
        from drand_tpu.client import new_client
        chain_hash = bytes.fromhex(args.chain_hash) \
            if args.chain_hash else None
        cli = new_client(urls=args.url, chain_hash=chain_hash,
                         insecure=chain_hash is None,
                         speed_test_interval=0)
        try:
            if args.watch:
                await _watch_public(cli, args.beacon_id)
                return
            d = await cli.get(args.round)
            print(json.dumps({"round": d.round,
                              "randomness": d.randomness.hex(),
                              "signature": d.signature.hex()}))
        finally:
            await cli.close()
    elif args.what == "private":
        # ECIES round trip against a node from the group file
        # (reference: `drand get private group.toml`,
        # cmd/drand-cli/control.go private randomness path +
        # core/drand_beacon_public.go:135-160).
        if not args.group:
            raise SystemExit("get private needs --group <group.toml>")
        import random

        from drand_tpu.crypto import ecies
        from drand_tpu.crypto.bls12381 import curve as GC
        from drand_tpu.key.group import Group
        from drand_tpu.net.client import PeerClients
        import pathlib
        group = Group.from_toml(
            await asyncio.to_thread(pathlib.Path(args.group).read_text))
        if not group.nodes:
            raise SystemExit("group file has no nodes")
        # Shuffled first-success: private randomness is per-node opt-in,
        # so fall through members that refuse (the reference client's
        # peer-iteration discipline).
        candidates = list(group.nodes)
        random.shuffle(candidates)
        pool = None
        if getattr(args, "certs_dir", ""):
            from drand_tpu.net.certs import CertManager
            cm = CertManager()
            cm.add_folder(args.certs_dir)
            pool = cm.pool_pem() or None
        peers = PeerClients(trust_pem=pool)
        errors = []
        try:
            for node in candidates:
                req_bytes, esk = ecies.encode_request(None)
                try:
                    stub = peers.public(node.address, node.tls)
                    resp = await stub.PrivateRand(
                        drand_pb2.PrivateRandRequest(
                            request=req_bytes,
                            metadata=make_metadata(args.beacon_id)),
                        timeout=10)
                    rand = ecies.decrypt_reply(
                        esk, GC.g1_from_bytes(node.key), resp.response)
                    print(json.dumps({"node": node.address,
                                      "randomness": rand.hex()}))
                    return
                except Exception as exc:
                    errors.append(f"{node.address}: {exc}")
            raise SystemExit("no node served private randomness:\n  " +
                             "\n  ".join(errors))
        finally:
            await peers.close()
    else:  # chain-info
        cc = ControlClient(args.control)
        pkt = await cc.stub.ChainInfo(drand_pb2.ChainInfoRequest(
            metadata=make_metadata(args.beacon_id)), timeout=10)
        from drand_tpu.core import convert
        print(convert.info_from_proto(pkt).to_json().decode())
        await cc.close()


async def _watch_public(cli, beacon_id: str) -> None:
    """`get public --watch`: stream rounds through the client stack's
    failover watch (client/optimizing.py watchState — source demotion +
    resubscribe on stream death).  Each emitted round prints AND logs
    with its deterministic per-round trace id, so an operator can pivot
    from a watched round straight into `/debug/spans/{trace_id}` and
    `/debug/logs?trace_id=...` on any group member."""
    from drand_tpu import log as dlog
    from drand_tpu import tracing
    wlog = dlog.get("cli", "watch")
    async for d in cli.watch():
        tid = tracing.round_trace_id(beacon_id, d.round)
        wlog.info("watch round %d", d.round,
                  extra={"trace_id": tid, "span_id": None})
        print(json.dumps({"round": d.round,
                          "randomness": d.randomness.hex(),
                          "signature": d.signature.hex(),
                          "trace_id": tid}), flush=True)


async def cmd_show(args):
    cc = ControlClient(args.control)
    md = make_metadata(args.beacon_id)
    if args.what == "share":
        r = await cc.stub.Share(drand_pb2.ShareRequest(metadata=md),
                                timeout=10)
        print(json.dumps({"index": r.index, "public": r.share.hex()}))
    elif args.what == "group":
        r = await cc.stub.GroupFile(drand_pb2.GroupRequest(metadata=md),
                                    timeout=10)
        from drand_tpu.core import convert
        print(convert.group_from_proto(r).to_toml())
    elif args.what == "chain-info":
        r = await cc.stub.ChainInfo(drand_pb2.ChainInfoRequest(metadata=md),
                                    timeout=10)
        from drand_tpu.core import convert
        print(convert.info_from_proto(r).to_json().decode())
    elif args.what == "public":
        r = await cc.stub.PublicKey(drand_pb2.PublicKeyRequest(metadata=md),
                                    timeout=10)
        print(r.pubKey.hex())
    elif args.what == "private":
        r = await cc.stub.PrivateKey(drand_pb2.PrivateKeyRequest(metadata=md),
                                     timeout=10)
        print(r.priKey.hex())
    await cc.close()


async def cmd_relay(args):
    from drand_tpu.client import new_client
    from drand_tpu.relay import HTTPRelay
    upstream = new_client(urls=args.url,
                          chain_hash=bytes.fromhex(args.chain_hash))
    relay = HTTPRelay(upstream, args.listen)
    await relay.start()
    print(f"HTTP relay serving on :{relay.port}")
    while True:
        await asyncio.sleep(3600)


async def cmd_relay_pubsub(args):
    from drand_tpu.client import new_client
    from drand_tpu.relay import GossipRelayNode, PubSubRelayNode
    chain_hash = bytes.fromhex(args.chain_hash)
    if not args.url and not args.bootstrap:
        raise SystemExit("pass --url (upstream) and/or --bootstrap (mesh)")
    upstream = None
    if args.url:
        upstream = new_client(urls=args.url, chain_hash=chain_hash,
                              auto_watch=True)
    if args.bootstrap:
        peers = [p.strip() for p in args.bootstrap.split(",") if p.strip()]
        from drand_tpu.relay.gossip import is_wildcard_listen
        if is_wildcard_listen(args.listen) and not args.advertise:
            raise SystemExit(
                "--listen binds a wildcard address: peers would learn an "
                "undialable 0.0.0.0 — pass --advertise <host:port>")
        if upstream is not None:
            info = await upstream.info()
        else:
            info = await _fetch_mesh_chain_info(peers, chain_hash)
        node = GossipRelayNode(upstream, args.listen, info,
                               bootstrap=peers, degree=args.degree,
                               advertise=args.advertise or None)
        kind = "gossip relay"
    else:
        node = PubSubRelayNode(upstream, args.listen)
        kind = "pubsub relay"
    await node.start()
    print(f"{kind} serving on {node.address}")
    while True:
        await asyncio.sleep(3600)


async def _fetch_mesh_chain_info(peers: list[str], chain_hash: bytes):
    """A pure mesh node pins its root of trust by fetching chain info
    from a bootstrap peer — GrpcClient.info() already does the fetch,
    conversion, and pinned-hash validation."""
    from drand_tpu.client.grpc import GrpcClient
    last_exc = None
    for addr in peers:
        c = GrpcClient(addr, chain_hash=chain_hash)
        try:
            return await c.info()
        except Exception as exc:
            last_exc = exc
        finally:
            await c.close()
    raise SystemExit(f"no bootstrap peer served chain info: {last_exc}")


async def cmd_relay_s3(args):
    """Object-store relay (cmd/relay-s3/main.go:40-50): boto3 bucket when
    importable, filesystem backend otherwise (or with --fs)."""
    from drand_tpu.client import new_client
    from drand_tpu.relay.s3 import FileStoreBackend, S3Relay
    backend = None
    if not args.fs:
        try:
            import boto3  # not in this image; real deployments have it
            backend = boto3.resource("s3").Bucket(args.bucket)
            backend = _Boto3Backend(backend)
        except ImportError:
            print("boto3 not installed; using filesystem backend at "
                  f"{args.bucket}", file=sys.stderr)
    if backend is None:
        backend = FileStoreBackend(args.bucket)
    upstream = new_client(urls=args.url,
                          chain_hash=bytes.fromhex(args.chain_hash))
    relay = S3Relay(upstream, backend, prefix=args.prefix)
    await relay.start()
    print(f"s3 relay uploading to {args.bucket}/{args.prefix}")
    while True:
        await asyncio.sleep(3600)


def _objectsync_backend(args):
    from drand_tpu.objectsync import FilesystemBackend, HTTPBackend
    if bool(args.dir) == bool(args.url):
        raise SystemExit("objectsync needs exactly one of --dir / --url")
    return FilesystemBackend(args.dir) if args.dir else HTTPBackend(args.url)


async def cmd_objectsync(args):
    """Objectsync tier (drand_tpu/objectsync/; supersedes relay-s3's
    per-round JSON uploads): one-shot publish of sealed segments from a
    local chain db, verify-then-commit sync of a local db from published
    objects, or backend status."""
    from drand_tpu import objectsync as osync
    backend = _objectsync_backend(args)
    try:
        if args.action == "status":
            try:
                m = osync.Manifest.from_json(
                    await backend.get(osync.MANIFEST_NAME))
            except osync.ObjectNotFound:
                print(json.dumps({"backend": backend.describe(),
                                  "manifest": None}))
                return
            print(json.dumps({
                "backend": backend.describe(),
                "chain_hash": m.chain_hash,
                "scheme": m.scheme_id,
                "segment_rounds": m.segment_rounds,
                "segments": len(m.segments),
                "tip": m.tip,
            }, indent=1))
            return

        if not args.db or not args.chain_hash:
            raise SystemExit(
                f"objectsync {args.action} needs --db and --chain-hash")
        from drand_tpu.chain.scheme import scheme_by_id
        from drand_tpu.chain.store import (AppendStore, SchemeStore,
                                           SqliteStore)
        scheme = scheme_by_id(args.scheme or None)
        chain_hash = bytes.fromhex(args.chain_hash)

        if args.action == "publish":
            store = SqliteStore(args.db)
            try:
                pub = osync.ObjectPublisher(
                    store, backend, chain_hash=chain_hash,
                    scheme_id=scheme.id,
                    segment_rounds=(args.segment_rounds
                                    or osync.DEFAULT_SEGMENT_ROUNDS))
                await pub.load_manifest()
                published = await pub.publish_sealed()
                snap = pub.snapshot()
                snap["published_now"] = published
                print(json.dumps(snap, indent=1))
                if pub.last_error:
                    raise SystemExit(1)
            finally:
                store.close()
            return

        # sync: verify every fetched segment against the LOCAL anchor
        # before committing — the object store is fully untrusted
        if not args.public_key:
            raise SystemExit("objectsync sync needs --public-key")
        from drand_tpu.chain.beacon import Beacon
        from drand_tpu.chain.store import BeaconNotFound
        from drand_tpu.chain.verify import ChainVerifier
        from drand_tpu.resilience import Resilience
        base = SqliteStore(args.db)
        store = SchemeStore(AppendStore(base), scheme.decouple_prev_sig)
        try:
            try:
                store.last()
            except BeaconNotFound:
                if not args.genesis_seed:
                    raise SystemExit(
                        "empty store: pass --genesis-seed to anchor "
                        "round 0")
                store.put(Beacon(round=0,
                                 signature=bytes.fromhex(
                                     args.genesis_seed)))
            verifier = ChainVerifier(scheme,
                                     bytes.fromhex(args.public_key))
            client = osync.ObjectSyncClient(
                backend, store, verifier, chain_hash=chain_hash,
                resilience=Resilience())
            result = await client.sync(up_to=args.up_to)
            out = result.to_dict()
            out["stats"] = dict(client.stats)
            print(json.dumps(out, indent=1))
            if not result.ok:
                raise SystemExit(1)
        finally:
            base.close()
    finally:
        await backend.close()


async def cmd_chaos(args):
    """Chaos subcommand: list sites/scenarios, run/replay a seeded
    scenario through the in-process multi-node harness."""
    from drand_tpu.chaos import failpoints
    if args.action == "list":
        from drand_tpu.chaos import runner as _r   # jax path; list needs
        print("failpoint sites:")
        for site, doc in sorted(failpoints.SITES.items()):
            print(f"  {site:18s} {doc}")
        print("\nscenarios (drand-tpu chaos run <name> --seed S):")
        for name, spec in sorted(_r.SCENARIOS.items()):
            tag = " [slow]" if spec.slow else ""
            print(f"  {name:22s}{tag} {spec.doc}")
        print(f"  {'mesh-churn':22s} seeded kill/restart waves + one-way "
              "partition over an N-node gossip relay mesh "
              "(--nodes, default 24; drand_tpu/chaos/mesh.py)")
        return
    if not args.scenario:
        raise SystemExit("chaos run/replay needs a scenario name "
                         "(see `drand-tpu chaos list`)")
    from drand_tpu.chaos import runner
    if args.scenario != "mesh-churn" \
            and args.scenario not in runner.SCENARIOS:
        raise SystemExit(f"unknown scenario {args.scenario!r} "
                         f"(known: {sorted(runner.SCENARIOS) + ['mesh-churn']})")
    from drand_tpu.chaos.invariants import InvariantViolation
    try:
        if args.scenario == "mesh-churn":
            from drand_tpu.chaos import mesh
            # --nodes keeps its protocol-harness default of 3; the mesh
            # floor is where churn gets interesting
            report = await mesh.run_mesh_scenario(
                args.seed, nodes=args.nodes if args.nodes > 3 else 24)
        else:
            report = await runner.run_scenario(
                args.scenario, args.seed, nodes=args.nodes,
                threshold=args.threshold or None, scheme=args.scheme,
                sanitize=True if args.sanitize else None)
    except (InvariantViolation, AssertionError) as exc:
        print(f"FAIL seed={args.seed} scenario={args.scenario}: {exc}",
              file=sys.stderr)
        print(f"replay with: drand-tpu chaos replay {args.scenario} "
              f"--seed {args.seed}", file=sys.stderr)
        raise SystemExit(1)
    if args.chaos_json:
        print(json.dumps(report.to_dict(), indent=2))
        if getattr(report, "sanitized", False) and report.sanitizer_reports:
            raise SystemExit(1)
        return
    print(f"scenario {report.scenario} seed={report.seed} "
          f"nodes={report.nodes} thr={report.threshold}: OK")
    print(f"  final rounds:  {report.final_rounds}")
    print(f"  invariants:    {', '.join(report.invariants_passed)}")
    print(f"  injections:    {len(report.injections)} "
          f"({len(report.summary)} distinct)")
    print(f"  decisions:     {len(report.decisions)} retry/breaker "
          f"({len(report.decision_summary)} distinct)")
    if getattr(report, "sanitized", False):   # mesh reports lack it
        print(f"  sanitizer:     armed, "
              f"{len(report.sanitizer_reports)} report(s)")
        for r in report.sanitizer_reports:
            print(f"    [{r['kind']}] {r['what']} — {r['detail']}")
        if report.sanitizer_reports:
            # a sanitized run is a race gate: reports are failures
            # (exit-coded so check.sh and CI treat them like a
            # violated invariant), with the full stacks on stderr
            for r in report.sanitizer_reports:
                print(f"[{r['kind']}] {r['what']} — {r['detail']}\n"
                      f"{r['stack']}", file=sys.stderr)
            raise SystemExit(1)
    if args.action == "replay":
        # the replay view: the full deterministic injection log, then
        # the resilience layer's retry/breaker decision log
        for entry in report.injections:
            print("  " + json.dumps(entry, sort_keys=True))
        for entry in report.decisions:
            print("  " + json.dumps(entry, sort_keys=True))


class _Boto3Backend:
    """Adapt a boto3 Bucket to the put(key, body) backend protocol."""

    def __init__(self, bucket):
        self.bucket = bucket

    def put(self, key: str, body: bytes) -> None:
        self.bucket.put_object(Key=key, Body=body,
                               ContentType="application/json")


async def cmd_util(args):
    md = make_metadata(args.beacon_id)
    if args.what == "fsck":
        # Offline integrity check against a chain db file — no daemon,
        # no control port, no jax: the structural scan (codec decode,
        # round contiguity, prev-sig linkage) from
        # drand_tpu/chain/recovery.py, working on mixed JSON/binary
        # stores.  Exit 0 on a clean chain, 1 when damage was found
        # (fsck convention: non-zero means something needed attention,
        # repaired or not).
        if not args.target:
            raise SystemExit("util fsck needs a chain db path: "
                             "drand-tpu util fsck <store.db> "
                             "[--repair] [--json]")
        if not os.path.exists(args.target):
            raise SystemExit(f"no such db: {args.target}")
        from drand_tpu.chain.recovery import repair_store, scan_store
        from drand_tpu.chain.store import SqliteStore
        store = SqliteStore(args.target)
        try:
            report = await scan_store(store, None,
                                      beacon_id=args.beacon_id)
            summary = None
            if args.repair and not report.ok:
                summary = repair_store(store, report)
            if args.json_out:
                out = report.to_dict()
                out["repair"] = summary
                print(json.dumps(out))
            else:
                d = report.to_dict()
                print(f"scanned {report.scanned} rows "
                      f"(rounds {report.first_round}..{report.tip_round}) "
                      f"in {report.elapsed_s:.3f}s")
                for k in ("corrupt", "missing", "unlinked", "bad_sigs"):
                    if d[k]:
                        print(f"  {k}: {d[k]}")
                if report.ok:
                    print("chain OK")
                elif summary is not None:
                    print(f"repaired: quarantined "
                          f"{summary['quarantined']} damaged + "
                          f"{summary['truncated']} rolled-back rows; "
                          f"tip now {summary['verified_tip']} "
                          f"(re-sync the suffix from peers)")
                else:
                    print(f"DAMAGE FOUND (verified prefix ends at "
                          f"{report.verified_tip}); run with --repair "
                          f"to quarantine and roll back")
        finally:
            store.close()
        raise SystemExit(0 if report.ok else 1)
    if args.what == "health":
        # operator liveness probe against the node's public HTTP API
        # (the reference's curl-/health runbook step as a subcommand):
        # exit 0 on 200/caught-up, 1 on 503/behind or unreachable.
        if not args.target:
            raise SystemExit("util health needs the node's public HTTP "
                             "address: drand-tpu util health <host:port>")
        base = args.target if args.target.startswith("http") \
            else f"http://{args.target}"
        import aiohttp
        try:
            async with aiohttp.ClientSession() as s:
                async with s.get(f"{base.rstrip('/')}/health",
                                 timeout=aiohttp.ClientTimeout(
                                     total=10)) as r:
                    body = await r.json()
                    print(json.dumps({"status": r.status, **body}))
                    if r.status != 200:
                        raise SystemExit(1)
        except aiohttp.ClientError as exc:
            raise SystemExit(f"health probe failed: {exc}")
        return
    if args.what == "fleet":
        # group-wide observatory view: any member's metrics port serves
        # /debug/fleet (its own exposition + every group peer's, scraped
        # over the node-to-node metrics RPC), rendered as one table.
        # Stays jax-free: the render consumes the JSON shape only.
        if not args.target:
            raise SystemExit("util fleet needs a group member's metrics "
                             "address: drand-tpu util fleet <host:port>")
        base = args.target if args.target.startswith("http") \
            else f"http://{args.target}"
        import aiohttp
        try:
            async with aiohttp.ClientSession() as s:
                async with s.get(f"{base.rstrip('/')}/debug/fleet",
                                 timeout=aiohttp.ClientTimeout(
                                     total=30)) as r:
                    if r.status != 200:
                        raise SystemExit(
                            f"/debug/fleet returned {r.status}: "
                            f"{await r.text()}")
                    snap = await r.json()
        except aiohttp.ClientError as exc:
            raise SystemExit(f"fleet probe failed: {exc}")
        if args.json_out:
            print(json.dumps(snap, indent=1))
        else:
            from drand_tpu.observatory.fleet import render_table
            print(render_table(snap))
        unreachable = [n["address"] for n in snap.get("nodes", [])
                       if not n.get("ok")]
        raise SystemExit(1 if unreachable else 0)
    if args.what == "journey":
        # reconstruct one round's cross-node journey: pull the round's
        # trace spans from every peer's metrics port and merge them into
        # a single wall-ordered timeline + canonical hop record (the
        # offline twin of each node's live /debug/journey view).
        if not args.target:
            raise SystemExit("util journey needs a round number: "
                             "drand-tpu util journey <round> "
                             "--nodes host:port[,host:port...]")
        try:
            round_ = int(args.target)
        except ValueError:
            raise SystemExit(f"not a round number: {args.target!r}")
        nodes = [n.strip() for n in args.nodes.split(",") if n.strip()]
        if not nodes:
            raise SystemExit("util journey needs --nodes: comma-"
                             "separated metrics addresses (host:port) "
                             "to pull /debug/spans from")
        from drand_tpu import tracing
        from drand_tpu.profiling import journey as journey_mod
        trace_id = tracing.round_trace_id(args.beacon_id, round_)
        import aiohttp
        spans, errors = [], {}
        async with aiohttp.ClientSession() as s:
            for node in nodes:
                base = node if node.startswith("http") \
                    else f"http://{node}"
                url = f"{base.rstrip('/')}/debug/spans/{trace_id}"
                try:
                    async with s.get(url, timeout=aiohttp.ClientTimeout(
                            total=10)) as r:
                        if r.status == 404:
                            errors[node] = "no spans for this round"
                            continue
                        body = await r.json()
                        for d in body.get("spans", []):
                            d.setdefault("node", node)
                            spans.append(d)
                except (aiohttp.ClientError, asyncio.TimeoutError) as exc:
                    errors[node] = str(exc) or type(exc).__name__
        merged = journey_mod.collate(spans, beacon_id=args.beacon_id,
                                     round_=round_)
        merged = {"round": round_, "trace_id": trace_id, **merged}
        if errors:
            merged["errors"] = errors
        print(json.dumps(merged, indent=1))
        if not spans:
            raise SystemExit(1)
        return
    if args.what == "migrate":
        from drand_tpu.core.migration import migrate_old_folder_structure
        moved = migrate_old_folder_structure(args.folder)
        print("migrated" if moved else "nothing to migrate")
        return
    if args.what == "self-sign":
        from drand_tpu.key.store import FileStore
        ks = FileStore(args.folder, args.beacon_id)
        pair = ks.load_key_pair()
        pair.self_sign()
        ks.save_key_pair(pair)
        print("keypair re-signed")
        return
    if args.what == "reset":
        import shutil
        target = os.path.join(args.folder, "multibeacon", args.beacon_id,
                              "db")
        if os.path.isdir(target):
            shutil.rmtree(target)
        print(f"chain data for {args.beacon_id} removed")
        return
    if args.what == "del-beacon":
        import shutil
        target = os.path.join(args.folder, "multibeacon", args.beacon_id)
        if os.path.isdir(target):
            shutil.rmtree(target)
        print(f"beacon {args.beacon_id} removed")
        return

    cc = ControlClient(args.control)
    if args.what == "ping":
        await cc.ping(args.beacon_id)
        print("pong")
    elif args.what == "status":
        r = await cc.stub.Status(drand_pb2.StatusRequest(metadata=md),
                                 timeout=10)
        print(json.dumps({
            "beacon": {"running": r.beacon.is_running},
            "chain": {"last_round": r.chain_store.last_round,
                      "length": r.chain_store.length,
                      "empty": r.chain_store.is_empty}}))
    elif args.what == "list-schemes":
        r = await cc.stub.ListSchemes(
            drand_pb2.ListSchemesRequest(metadata=md), timeout=10)
        print("\n".join(r.ids))
    elif args.what == "list-ids":
        r = await cc.stub.ListBeaconIDs(
            drand_pb2.ListBeaconIDsRequest(metadata=md), timeout=10)
        print("\n".join(r.ids))
    elif args.what == "check":
        # scan the stored chain on the daemon's verifier and mend in
        # place what it flags (`SyncManager.check_chain`)
        import grpc
        from drand_tpu.core.control import CHECK_COUNT_PREFIX
        call = cc.stub.StartCheckChain(drand_pb2.StartSyncRequest(
            up_to=args.up_to, metadata=md))
        left = ""
        try:
            async for p in call:
                print(f"\rcheck {p.current}/{p.target}", end="", flush=True)
        except grpc.aio.AioRpcError as exc:
            if exc.code() != grpc.StatusCode.DATA_LOSS:
                raise
            left = exc.details()
        print()
        print(" / ".join(
            f"{key[len(CHECK_COUNT_PREFIX):]} {value}"
            for key, value in await call.trailing_metadata() or ()
            if key.startswith(CHECK_COUNT_PREFIX)))
        if left:
            await cc.close()
            raise SystemExit(f"check: {left}")
    elif args.what == "backup":
        if not args.target:
            raise SystemExit("util backup needs an output path")
        await cc.stub.BackupDatabase(drand_pb2.BackupDBRequest(
            output_file=args.target, metadata=md), timeout=120)
        print(f"backup written to {args.target}")
    elif args.what == "remote-status":
        req = drand_pb2.RemoteStatusRequest(metadata=md)
        for a in (args.target or "").split(","):
            if a:
                req.addresses.append(drand_pb2.Address(address=a))
        r = await cc.stub.RemoteStatus(req, timeout=30)
        out = {a: {"last_round": s.chain_store.last_round}
               for a, s in r.statuses.items()}
        print(json.dumps(out))
    await cc.close()


def cmd_lint(args) -> int:
    """Run the project linter (tools/lint).  Synchronous and jax-free:
    the gate must be cheap enough to run on every edit.  Resolves the
    repo root from this file so `drand-tpu lint` works from anywhere
    inside a checkout."""
    import pathlib
    root = pathlib.Path(__file__).resolve().parents[2]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    try:
        from tools.lint.__main__ import run as lint_run
    except ImportError:
        print("error: tools/lint not importable — `drand-tpu lint` needs "
              "a repo checkout", file=sys.stderr)
        return 2
    argv = list(args.paths) + ["--format", args.lint_format]
    for name in args.lint_rules or []:
        argv += ["--rule", name]
    if args.no_baseline:
        argv.append("--no-baseline")
    if args.update_baseline:
        argv.append("--update-baseline")
    if args.list_rules:
        argv.append("--list-rules")
    return lint_run(argv)


_COMMANDS = {
    "start": cmd_start, "stop": cmd_stop,
    "generate-keypair": cmd_generate_keypair, "share": cmd_share,
    "load": cmd_load, "sync": cmd_sync, "get": cmd_get,
    "show": cmd_show, "util": cmd_util,
    "relay": cmd_relay, "relay-pubsub": cmd_relay_pubsub,
    "relay-s3": cmd_relay_s3, "objectsync": cmd_objectsync,
    "chaos": cmd_chaos,
}


def _ensure_jax_backend() -> None:
    """Bring the JAX backend up before any command uses it, and fail
    there if it cannot be.  An operator without an accelerator starts
    the daemon with `JAX_PLATFORMS=cpu`; a daemon started for a chip it
    cannot reach must not carry on silently on the CPU."""
    import jax
    jax.devices()


# commands that touch the JAX device path (daemon verification, client
# verification, chain sync); everything else skips the multi-second import
_NEEDS_JAX = {"start", "get", "sync", "share", "relay", "relay-pubsub",
              "relay-s3", "chaos", "objectsync"}


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.command == "lint":     # sync, jax-free
        return cmd_lint(args)
    if args.command == "chaos":
        # the scenario nets sync only dozens of rounds: pin the small
        # verify bucket the default test suite already warms, instead of
        # paying a fresh multi-minute XLA compile for the 512 bucket
        os.environ.setdefault("DRAND_TPU_BUCKETS", "64")
    if args.command in _NEEDS_JAX:
        _ensure_jax_backend()
    try:
        asyncio.run(_COMMANDS[args.command](args))
        return 0
    except KeyboardInterrupt:
        return 130
    except SystemExit as e:
        raise
    except Exception as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())

"""`weed-tpu` multi-command CLI (ref: weed/command/command.go:10-31).

Commands: master, volume, server (combined), filer, s3, blob, shell,
benchmark, upload, download, export, fix, compact, scaffold, version.
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys


def _add_master_flags(p: argparse.ArgumentParser) -> None:
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=9333)
    p.add_argument("-volumeSizeLimitMB", type=int, default=30_000)
    p.add_argument("-defaultReplication", default="000")
    p.add_argument("-garbageThreshold", type=float, default=0.3)
    p.add_argument(
        "-peers",
        default="",
        help="comma-separated list of all master addresses (incl. self) "
        "for a multi-master raft cluster (ref weed master -peers)",
    )
    p.add_argument(
        "-jwtSigningKey",
        default="",
        help="HS256 key: the master issues fid-scoped upload JWTs and the "
        "volume servers verify them (ref security/jwt.go)",
    )
    p.add_argument(
        "-sequencerFile",
        default="",
        help="persist the file-id sequencer to this path (the durable "
        "role of the reference's etcd sequencer); '' = in-memory",
    )
    p.add_argument(
        "-raftStateFile",
        default="",
        help="persist raft term/vote/max-volume-id to this path so a "
        "restarted master cannot double-vote in its term; '' = in-memory",
    )
    p.add_argument(
        "-tierConfig",
        default="",
        help="JSON file configuring storage.backend tiers; the master "
        "snapshots backends registered at start and pushes them to "
        "volume servers via heartbeat responses (ref backend.go:77-95)",
    )


def _add_volume_flags(p: argparse.ArgumentParser) -> None:
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=8080)
    p.add_argument("-dir", default="./data", help="comma-separated data dirs")
    p.add_argument("-max", default="7", help="comma-separated max volume counts")
    p.add_argument("-mserver", default="127.0.0.1:9333")
    p.add_argument("-dataCenter", default="")
    p.add_argument("-rack", default="")
    p.add_argument("-publicUrl", default="")
    p.add_argument(
        "-storageBackend",
        default=os.environ.get("SEAWEEDFS_TPU_BACKEND", "adaptive"),
        choices=["adaptive", "cpu", "tpu", "numpy"],
        help="erasure-coding compute backend ('adaptive' measures the device "
        "round trip once and serves whichever of tpu/cpu is faster here)",
    )
    p.add_argument(
        "-batchLookup",
        default="off",
        choices=["off", "auto", "host", "device", "arena"],
        help="micro-batch concurrent read index probes through one "
        "vectorized bulk lookup (device IndexSnapshot when attached); "
        "'arena' answers each wakeup as ONE ragged dispatch over the "
        "HBM-resident column arena, falling back to host when cold",
    )
    p.add_argument(
        "-tierConfig",
        default="",
        help="JSON file configuring storage.backend tiers"
        " (ref backend.go LoadConfiguration)",
    )
    p.add_argument(
        "-index",
        default="memory",
        choices=["memory", "leveldb", "sorted", "lsm"],
        help="needle map kind (ref NeedleMapKind, weed/storage/needle_map.go:14;"
        " lsm = memory-bounded out-of-core map with O(tail) snapshot mount)",
    )
    p.add_argument(
        "-jwtSigningKey",
        default="",
        help="HS256 key gating uploads (ref security/jwt.go; usually set "
        "via [security] in -config)",
    )
    p.add_argument(
        "-cpuprofile", default="", help="cpu profile output file (pstats)"
    )
    p.add_argument(
        "-memprofile", default="", help="memory profile output file"
    )
    p.add_argument(
        "-pprof",
        action="store_true",
        help="force /debug/pprof HTTP handlers on (default: served unless SEAWEEDFS_TPU_PPROF=0)",
    )
    p.add_argument(
        "-whiteList",
        default="",
        help="comma-separated IPs/CIDRs allowed to write (ref guard.go); "
        "empty = everyone",
    )


def _apply_config_defaults(
    p: argparse.ArgumentParser,
    argv: list[str],
    sections: list[str],
    renames: dict | None = None,
):
    """-config support (ref weed/util/config.go:19-51): load a scaffold-
    emitted TOML (explicit path, or a name searched in ., ~/.seaweedfs-tpu,
    /etc/seaweedfs-tpu), apply its sections as flag defaults (explicit CLI
    flags still win), honor WEED_SECTION_KEY env overrides, and install
    [security]/[grpc] side effects (JWT key, mTLS)."""
    p.add_argument(
        "-config",
        default="",
        help="TOML config file (or name searched in ., ~/.seaweedfs-tpu, "
        "/etc/seaweedfs-tpu); CLI flags override file values",
    )
    pre = argparse.ArgumentParser(add_help=False)
    pre.add_argument("-config", default="")
    known, _ = pre.parse_known_args(argv)
    if not known.config:
        return None
    from ..util.config import load_configuration

    cfg = load_configuration(known.config, required=True)
    dests = {a.dest for a in p._actions}
    defaults = {}
    for section in sections:
        for k, v in cfg.section(section).items():
            if k in dests:
                defaults[k] = v
    # cross-section key remaps (e.g. the combined server command maps
    # [volume] port to its -volumePort flag)
    for dotted, dest in (renames or {}).items():
        v = cfg.get(dotted)
        if v is not None and dest in dests:
            defaults[dest] = v
    # [storage] backend -> -storageBackend (the tpu switch)
    backend = cfg.get("storage.backend")
    if backend and "storageBackend" in dests:
        defaults["storageBackend"] = backend
    # argparse applies type= only to string defaults; a numeric TOML value
    # for a string-typed flag (e.g. `max = 7`) must become a string or the
    # consumer's .split() crashes
    actions_by_dest = {a.dest: a for a in p._actions}
    for k, v in list(defaults.items()):
        a = actions_by_dest.get(k)
        if a is not None and isinstance(a.default, str) and not isinstance(v, str):
            defaults[k] = str(v)
    p.set_defaults(**defaults)

    # [grpc] ca/cert/key -> process-wide mTLS (ref weed/security/tls.go)
    grpc_sec = cfg.section("grpc")
    if grpc_sec.get("ca") and grpc_sec.get("cert") and grpc_sec.get("key"):
        from ..pb.rpc import TlsConfig, configure_tls

        configure_tls(
            TlsConfig.from_files(
                grpc_sec["ca"], grpc_sec["cert"], grpc_sec["key"]
            )
        )
    return cfg


def _build_volume_server(args, port_offset: int = 0):
    from ..server.volume import VolumeServer
    from ..util.metrics import mark_startup

    mark_startup("imports")
    _load_tier_config(getattr(args, "tierConfig", ""))
    dirs = args.dir.split(",")
    maxes = [int(m) for m in args.max.split(",")]
    if len(maxes) == 1:
        maxes = maxes * len(dirs)
    return VolumeServer(
        master=[x for x in args.mserver.split(",") if x],
        directories=dirs,
        host=args.ip,
        port=args.port + port_offset,
        public_url=args.publicUrl,
        max_volume_counts=maxes,
        needle_map_kind=getattr(args, "index", "memory"),
        data_center=args.dataCenter,
        rack=args.rack,
        codec_backend=args.storageBackend,
        jwt_signing_key=getattr(args, "jwtSigningKey", ""),
        pprof=getattr(args, "pprof", False),
        white_list=tuple(
            x for x in getattr(args, "whiteList", "").split(",") if x
        ),
        batch_lookup=getattr(args, "batchLookup", "off"),
        **_pulse_kwargs(),
    )


async def _run_forever(*servers) -> None:
    for s in servers:
        await s.start()
    stop = asyncio.Event()
    try:
        await stop.wait()
    finally:
        for s in servers:
            await s.stop()


def _serve(*servers) -> None:
    """Run servers until the process is told to end, on ONE loop whose
    selector reads the clock (serving_core.LoopClock: where the loop's
    second goes, on /metrics)."""
    from ..server.serving_core import new_event_loop

    asyncio.run(_run_forever(*servers), loop_factory=new_event_loop)


def _pulse_kwargs() -> dict:
    """SEAWEEDFS_TPU_PULSE_SECONDS -> pulse_seconds for master/volume.
    The heartbeat cadence is an in-process constructor knob the bench
    legs tune (0.2s clusters converge in tier-1 budgets); subprocess
    clusters (ops/proc_cluster.py) reach it only through the child's
    environment, so the CLI honors the env var instead of growing a
    flag every spawner must thread through."""
    v = os.environ.get("SEAWEEDFS_TPU_PULSE_SECONDS", "").strip()
    if not v:
        return {}
    return {"pulse_seconds": float(v)}


def _load_tier_config(path: str) -> None:
    if not path:
        return
    import json

    from ..storage.tier_backend import load_from_config

    with open(path) as f:
        load_from_config(json.load(f))


def _maintenance_kwargs(cfg) -> dict:
    """[master.maintenance] scripts / sleep_minutes + [master.filer] default
    (ref scaffold.go master template)."""
    if cfg is None:
        return {}
    return {
        "maintenance_scripts": cfg.get("master.maintenance.scripts", "") or "",
        "maintenance_sleep_minutes": float(
            cfg.get("master.maintenance.sleep_minutes", 17)
        ),
        "maintenance_filer": cfg.get("master.filer.default", "") or "",
    }


def cmd_master(argv: list[str]) -> int:
    p = argparse.ArgumentParser(prog="weed-tpu master")
    _add_master_flags(p)
    cfg = _apply_config_defaults(p, argv, ["master"])
    args = p.parse_args(argv)
    from ..server.master import MasterServer

    _load_tier_config(getattr(args, "tierConfig", ""))
    ms = MasterServer(
        host=args.ip,
        port=args.port,
        volume_size_limit_mb=args.volumeSizeLimitMB,
        default_replication=args.defaultReplication,
        garbage_threshold=args.garbageThreshold,
        peers=[x for x in args.peers.split(",") if x] or None,
        jwt_signing_key=args.jwtSigningKey,
        sequencer_file=args.sequencerFile,
        raft_state_file=args.raftStateFile,
        **_maintenance_kwargs(cfg),
        **_pulse_kwargs(),
    )
    print(f"master listening on {args.ip}:{args.port}")
    _serve(ms)
    return 0


def cmd_volume(argv: list[str]) -> int:
    p = argparse.ArgumentParser(prog="weed-tpu volume")
    _add_volume_flags(p)
    _apply_config_defaults(p, argv, ["volume", "security"])
    args = p.parse_args(argv)
    vs = _build_volume_server(args)
    print(f"volume server listening on {args.ip}:{args.port}")
    from ..util.profiling import Profiler

    with Profiler(args.cpuprofile, args.memprofile):
        _serve(vs)
    return 0


def cmd_server(argv: list[str]) -> int:
    """Combined master + volume server (ref command/server.go)."""
    p = argparse.ArgumentParser(prog="weed-tpu server")
    _add_master_flags(p)
    p.add_argument("-dir", default="./data")
    p.add_argument("-max", default="7")
    p.add_argument("-volumePort", type=int, default=8080)
    p.add_argument("-dataCenter", default="")
    p.add_argument("-rack", default="")
    p.add_argument(
        "-storageBackend",
        default=os.environ.get("SEAWEEDFS_TPU_BACKEND", "adaptive"),
        choices=["adaptive", "cpu", "tpu", "numpy"],
        help="EC codec route: 'adaptive' measures the device round trip once "
        "and serves whichever of tpu/cpu is actually faster here",
    )
    p.add_argument(
        "-batchLookup",
        default="off",
        choices=["off", "auto", "host", "device", "arena"],
        help="micro-batch concurrent read index probes through one "
        "vectorized bulk lookup (device IndexSnapshot when attached); "
        "'arena' answers each wakeup as ONE ragged dispatch over the "
        "HBM-resident column arena, falling back to host when cold",
    )
    # -tierConfig comes from _add_master_flags (shared with cmd_master)
    p.add_argument(
        "-index", default="memory",
        choices=["memory", "leveldb", "sorted", "lsm"],
    )
    p.add_argument("-cpuprofile", default="", help="cpu profile output file")
    p.add_argument("-memprofile", default="", help="memory profile output file")
    p.add_argument(
        "-pprof",
        action="store_true",
        help="force /debug/pprof handlers on for the volume server (default: SEAWEEDFS_TPU_PPROF env gate)",
    )
    p.add_argument(
        "-whiteList",
        default="",
        help="comma-separated IPs/CIDRs allowed to write (ref guard.go)",
    )
    p.add_argument("-filer", action="store_true", help="also run a filer")
    p.add_argument("-filerPort", type=int, default=8888)
    p.add_argument("-s3", action="store_true", help="also run an S3 gateway (implies -filer)")
    p.add_argument("-s3Port", type=int, default=8333)
    p.add_argument("-s3Config", default="", help="IAM identities JSON for the S3 gateway")
    cfg = _apply_config_defaults(
        p,
        argv,
        ["master", "server", "security"],
        renames={
            "volume.port": "volumePort",
            "volume.dir": "dir",
            "volume.max": "max",
            "volume.dataCenter": "dataCenter",
            "volume.rack": "rack",
            "volume.index": "index",
            "volume.whiteList": "whiteList",
        },
    )
    args = p.parse_args(argv)
    from ..server.master import MasterServer
    from ..server.volume import VolumeServer
    from ..util.metrics import mark_startup

    mark_startup("imports")
    if args.tierConfig:
        import json

        from ..storage.tier_backend import load_from_config

        with open(args.tierConfig) as f:
            load_from_config(json.load(f))

    peers = [x for x in args.peers.split(",") if x] or None
    ms = MasterServer(
        host=args.ip,
        port=args.port,
        volume_size_limit_mb=args.volumeSizeLimitMB,
        default_replication=args.defaultReplication,
        peers=peers,
        jwt_signing_key=args.jwtSigningKey,
        sequencer_file=args.sequencerFile,
        raft_state_file=args.raftStateFile,
        **_maintenance_kwargs(cfg),
    )
    vs = VolumeServer(
        master=peers or f"{args.ip}:{args.port}",
        directories=args.dir.split(","),
        host=args.ip,
        port=args.volumePort,
        max_volume_counts=[int(m) for m in args.max.split(",")],
        data_center=args.dataCenter,
        rack=args.rack,
        codec_backend=args.storageBackend,
        needle_map_kind=args.index,
        jwt_signing_key=args.jwtSigningKey,
        pprof=args.pprof,
        white_list=tuple(x for x in args.whiteList.split(",") if x),
        batch_lookup=getattr(args, "batchLookup", "off"),
    )
    servers = [ms, vs]
    desc = (
        f"server: master on {args.ip}:{args.port}, volume on "
        f"{args.ip}:{args.volumePort}"
    )
    if args.filer or args.s3:
        from ..server.filer import FilerServer

        fs = FilerServer(
            master=f"{args.ip}:{args.port}",
            host=args.ip,
            port=args.filerPort,
            jwt_signing_key=args.jwtSigningKey,
        )
        servers.append(fs)
        desc += f", filer on {args.ip}:{args.filerPort}"
        if args.s3:
            from ..s3.server import S3Server

            iam = None
            if args.s3Config:
                from ..s3.auth import IdentityAccessManagement

                iam = IdentityAccessManagement.from_file(args.s3Config)
            servers.append(S3Server(fs, host=args.ip, port=args.s3Port, iam=iam))
            desc += f", s3 on {args.ip}:{args.s3Port}"
    print(desc)
    from ..util.profiling import Profiler

    with Profiler(args.cpuprofile, args.memprofile):
        _serve(*servers)
    return 0


def cmd_filer(argv: list[str]) -> int:
    p = argparse.ArgumentParser(prog="weed-tpu filer")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=8888)
    p.add_argument("-master", default="127.0.0.1:9333")
    p.add_argument(
        "-store",
        default="",
        help="metadata store: '' = memory, *.flog = append-log, "
        "*.lsm = LSM segments+WAL, anything else = sqlite",
    )
    p.add_argument("-maxMB", type=int, default=4, help="chunk size in MB")
    p.add_argument(
        "-shards",
        type=int,
        default=0,
        help="partition the store into N directory-prefix shards "
        "(crash-safe shard map + heat-driven rebalance; -store then "
        "names a directory — sqlite sub-stores, or LSM when it ends "
        "in .lsm)",
    )
    p.add_argument(
        "-metaLog",
        default="",
        help="directory for the durable segmented meta-log change "
        "feed (resumable per-subscriber cursors); '' = in-memory ring",
    )
    p.add_argument("-collection", default="")
    p.add_argument("-replication", default="")
    p.add_argument("-jwtSigningKey", default="")
    p.add_argument(
        "-peers",
        default="",
        help="comma-separated peer filers (host:port) whose metadata "
        "streams this filer follows and aggregates (ref -peers, "
        "weed/filer2/meta_aggregator.go)",
    )
    p.add_argument(
        "-encryptVolumeData",
        action="store_true",
        help="encrypt chunk content before it reaches volume servers "
        "(AES-256-GCM, per-chunk keys in entry metadata; ref filer "
        "-encryptVolumeData)",
    )
    p.add_argument(
        "-notifySink",
        default="",
        choices=["", "none", "log", "memory", "broker", "webhook", "s3"],
        help="publish filer mutation events (ref notification.toml): "
        "webhook POSTs JSON to -notifyUrl; s3 writes signed event objects "
        "to -notifyEndpoint/-notifyBucket; broker publishes to -notifyBroker",
    )
    p.add_argument("-notifyUrl", default="", help="webhook sink target URL")
    p.add_argument("-notifyBroker", default="", help="broker sink host:port")
    p.add_argument("-notifyTopic", default="filer")
    p.add_argument("-notifyEndpoint", default="", help="s3 sink host:port")
    p.add_argument("-notifyBucket", default="")
    p.add_argument("-notifyAccessKey", default="")
    p.add_argument("-notifySecretKey", default="")
    p.add_argument(
        "-dataCenter",
        default="",
        help="this filer's data center label: reads prefer same-DC "
        "replicas, geo-shipped chunks land on same-DC volumes",
    )
    p.add_argument(
        "-geoSource",
        default="",
        help="PRIMARY cluster filer (host:port) to geo-replicate FROM: "
        "this filer becomes the second site, tailing the primary's "
        "meta-log under an exactly-resuming durable cursor",
    )
    p.add_argument(
        "-geoState",
        default="",
        help="durable geo cursor file (default: <-store>.geo.json)",
    )
    p.add_argument(
        "-fleetMap",
        default="",
        help="shared FLEETMAP file of a shard-range filer fleet: this "
        "filer serves the directory-prefix range the map assigns it and "
        "forwards/redirects everything else to the owning member",
    )
    p.add_argument(
        "-fleetSelf",
        default="",
        help="this member's address as listed in -fleetMap "
        "(default: <-ip>:<-port>)",
    )
    p.add_argument(
        "-followSource",
        default="",
        help="PRIMARY filer (host:port) to follow as a read-only "
        "meta-log-fed replica: serves eventually-consistent GET/LIST "
        "with a disclosed staleness bound, redirects writes",
    )
    _apply_config_defaults(p, argv, ["filer", "security", "notification"])
    args = p.parse_args(argv)
    from ..notification import Notifier, build_sink
    from ..server.filer import FilerServer

    sink = build_sink(
        args.notifySink,
        url=args.notifyUrl,
        broker=args.notifyBroker,
        topic=args.notifyTopic,
        endpoint=args.notifyEndpoint,
        bucket=args.notifyBucket,
        access_key=args.notifyAccessKey,
        secret_key=args.notifySecretKey,
    )
    fs = FilerServer(
        master=args.master,
        host=args.ip,
        port=args.port,
        store_path=args.store,
        notifier=Notifier([sink]) if sink is not None else None,
        chunk_size=args.maxMB * 1024 * 1024,
        collection=args.collection,
        replication=args.replication,
        jwt_signing_key=args.jwtSigningKey,
        peers=tuple(
            x.strip() for x in args.peers.split(",") if x.strip()
        ),
        cipher=args.encryptVolumeData,
        shards=args.shards,
        meta_log_path=args.metaLog,
        data_center=args.dataCenter,
        geo_source=args.geoSource,
        geo_state_path=args.geoState,
        fleet_map_path=args.fleetMap,
        fleet_self=args.fleetSelf,
        follow_source=args.followSource,
    )
    print(f"filer listening on {args.ip}:{args.port}")
    _serve(fs)
    return 0


def cmd_s3(argv: list[str]) -> int:
    p = argparse.ArgumentParser(prog="weed-tpu s3")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=8333)
    p.add_argument("-master", default="127.0.0.1:9333")
    p.add_argument("-filerPort", type=int, default=8888)
    p.add_argument(
        "-store",
        default="",
        help="metadata store: '' = memory, *.flog = append-log, "
        "*.lsm = LSM segments+WAL, anything else = sqlite",
    )
    p.add_argument(
        "-config",
        default="",
        help="IAM identities JSON (ref s3api auth_credentials.go); "
        "empty = anonymous",
    )
    args = p.parse_args(argv)
    from ..s3.server import S3Server
    from ..server.filer import FilerServer

    iam = None
    if args.config:
        from ..s3.auth import IdentityAccessManagement

        iam = IdentityAccessManagement.from_file(args.config)
    fs = FilerServer(
        master=args.master, host=args.ip, port=args.filerPort, store_path=args.store
    )
    s3 = S3Server(fs, host=args.ip, port=args.port, iam=iam)
    print(f"s3 gateway on {args.ip}:{args.port} (filer on :{args.filerPort})")
    _serve(fs, s3)
    return 0


def cmd_blob(argv: list[str]) -> int:
    """In-tree blob server (server/blob.py): the cold tier's stand-in
    object store as a standalone process, so multi-process clusters
    (ops/proc_cluster.py) get a remote tier that is subject to the same
    process-level chaos as every other role."""
    p = argparse.ArgumentParser(prog="weed-tpu blob")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=8334)
    p.add_argument("-dir", default="./blob", help="blob storage directory")
    args = p.parse_args(argv)
    from ..server.blob import BlobServer

    bs = BlobServer(args.dir, args.port, host=args.ip)
    print(f"blob server listening on {args.ip}:{args.port}")
    _serve(bs)
    return 0


def cmd_webdav(argv: list[str]) -> int:
    p = argparse.ArgumentParser(prog="weed-tpu webdav")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=7333)
    p.add_argument("-master", default="127.0.0.1:9333")
    p.add_argument("-filerPort", type=int, default=8888)
    args = p.parse_args(argv)
    from ..server.filer import FilerServer
    from ..server.webdav import WebDavServer

    fs = FilerServer(master=args.master, host=args.ip, port=args.filerPort)
    dav = WebDavServer(fs, host=args.ip, port=args.port)
    print(f"webdav on {args.ip}:{args.port} (filer on :{args.filerPort})")
    _serve(fs, dav)
    return 0


def cmd_msg_broker(argv: list[str]) -> int:
    p = argparse.ArgumentParser(prog="weed-tpu msgBroker")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=17777)
    p.add_argument(
        "-filer",
        default="",
        help="filer host:port journaling topic partitions (durable restart)",
    )
    args = p.parse_args(argv)
    from ..messaging import MessageBroker

    broker = MessageBroker(host=args.ip, port=args.port, filer=args.filer)
    print(f"message broker gRPC on {args.ip}:{args.port + 10000}")
    _serve(broker)
    return 0


def cmd_backup(argv: list[str]) -> int:
    """Incremental pull of a remote volume into a local directory
    (ref command/backup.go)."""
    p = argparse.ArgumentParser(prog="weed-tpu backup")
    p.add_argument("-master", default="127.0.0.1:9333")
    p.add_argument("-dir", default=".")
    p.add_argument("-volumeId", type=int, required=True)
    p.add_argument("-collection", default="")
    args = p.parse_args(argv)

    async def go() -> None:
        from ..client.operation import lookup
        from ..pb import grpc_address
        from ..pb.rpc import Stub, close_all_channels
        from ..storage.volume import Volume
        from ..storage.volume_backup import apply_incremental

        locs = await lookup(args.master, args.volumeId, args.collection)
        if not locs:
            raise SystemExit(f"volume {args.volumeId} not found")
        v = Volume(args.dir, args.collection, args.volumeId)
        since = v.last_append_at_ns
        stub = Stub(grpc_address(locs[0]), "volume")
        buf = bytearray()
        async for msg in stub.server_stream(
            "VolumeIncrementalCopy",
            {"volume_id": args.volumeId, "since_ns": since},
        ):
            if msg.get("error"):
                raise SystemExit(msg["error"])
            buf.extend(msg.get("file_content", b""))
        applied = apply_incremental(v, bytes(buf))
        print(f"volume {args.volumeId}: applied {applied} records since {since}")
        v.close()
        await close_all_channels()

    asyncio.run(go())
    return 0


def cmd_shell(argv: list[str]) -> int:
    p = argparse.ArgumentParser(prog="weed-tpu shell")
    p.add_argument("-master", default="127.0.0.1:9333")
    p.add_argument("commands", nargs="*", help="semicolon-separated one-shot commands")
    args = p.parse_args(argv)

    from ..shell import CommandEnv, run_command

    async def repl() -> None:
        env = CommandEnv(args.master)
        try:
            if args.commands:
                for line in " ".join(args.commands).split(";"):
                    out = await run_command(env, line)
                    if out:
                        print(out)
                return
            print("seaweedfs-tpu shell; `help` lists commands, ctrl-d exits")
            loop = asyncio.get_event_loop()
            while True:
                try:
                    line = await loop.run_in_executor(None, input, "> ")
                except EOFError:
                    break
                try:
                    out = await run_command(env, line)
                except Exception as e:
                    # one failing command must not kill the REPL
                    out = f"error: {e}"
                if out:
                    print(out)
        finally:
            await env.release_lock()
            from ..pb.rpc import close_all_channels

            await close_all_channels()

    asyncio.run(repl())
    return 0


def cmd_benchmark(argv: list[str]) -> int:
    p = argparse.ArgumentParser(prog="weed-tpu benchmark")
    p.add_argument("-master", default="127.0.0.1:9333")
    p.add_argument("-n", type=int, default=1024 * 1024)
    p.add_argument("-size", type=int, default=1024)
    p.add_argument("-c", type=int, default=16)
    p.add_argument("-collection", default="")
    p.add_argument("-write", action="store_true", default=True)
    p.add_argument("-skipRead", action="store_true")
    p.add_argument(
        "-assignBatch", type=int, default=1,
        help="lease file ids in count=N assign batches (amortizes the "
        "per-write master round-trip; keep 1 against JWT-secured "
        "clusters — upload tokens cover the base fid only)",
    )
    p.add_argument(
        "-cpuprofile", default="", help="cpu profile output file (pstats)"
    )
    p.add_argument("-memprofile", default="", help="memory profile output file")
    args = p.parse_args(argv)
    from .benchmark import run_benchmark
    from ..util.profiling import Profiler

    with Profiler(args.cpuprofile, args.memprofile):
        out = asyncio.run(
            run_benchmark(
                args.master,
                num_files=args.n,
                file_size=args.size,
                concurrency=args.c,
                collection=args.collection,
                do_read=not args.skipRead,
                assign_batch=args.assignBatch,
            )
        )
    print(out)
    return 0


def cmd_upload(argv: list[str]) -> int:
    p = argparse.ArgumentParser(prog="weed-tpu upload")
    p.add_argument("-master", default="127.0.0.1:9333")
    p.add_argument("-collection", default="")
    p.add_argument("-replication", default="")
    p.add_argument("-ttl", default="")
    p.add_argument(
        "-maxMB",
        type=int,
        default=0,
        help="split larger files into chunks + manifest (0 = never split)",
    )
    p.add_argument("files", nargs="+")
    args = p.parse_args(argv)

    async def go() -> None:
        import aiohttp

        from ..client.operation import submit_file

        from ..util.http_timeouts import client_timeout

        async with aiohttp.ClientSession(
            timeout=client_timeout()
        ) as session:
            for path in args.files:
                with open(path, "rb") as f:
                    data = f.read()
                fid, result = await submit_file(
                    session,
                    args.master,
                    data,
                    filename=os.path.basename(path),
                    collection=args.collection,
                    replication=args.replication,
                    ttl=args.ttl,
                    chunk_size=args.maxMB * 1024 * 1024,
                )
                print(f"{path} -> fid {fid} ({result.get('size')} bytes)")

    asyncio.run(go())
    return 0


def cmd_download(argv: list[str]) -> int:
    p = argparse.ArgumentParser(prog="weed-tpu download")
    p.add_argument("-master", default="127.0.0.1:9333")
    p.add_argument("-dir", default=".")
    p.add_argument("fids", nargs="+")
    args = p.parse_args(argv)

    async def go() -> None:
        import aiohttp

        from ..client.operation import lookup, read_url

        from ..util.http_timeouts import client_timeout

        async with aiohttp.ClientSession(
            timeout=client_timeout()
        ) as session:
            for fid in args.fids:
                vid = int(fid.split(",")[0])
                locs = await lookup(args.master, vid)
                if not locs:
                    print(f"{fid}: volume not found", file=sys.stderr)
                    continue
                data = await read_url(session, f"http://{locs[0]}/{fid}")
                out = os.path.join(args.dir, fid.replace(",", "_"))
                with open(out, "wb") as f:
                    f.write(data)
                print(f"{fid} -> {out} ({len(data)} bytes)")

    asyncio.run(go())
    return 0


def cmd_export(argv: list[str]) -> int:
    """List/extract needles from a volume .dat (ref command/export.go)."""
    p = argparse.ArgumentParser(prog="weed-tpu export")
    p.add_argument("-dir", required=True)
    p.add_argument("-volumeId", type=int, required=True)
    p.add_argument("-collection", default="")
    p.add_argument("-o", default="", help="output directory (default: list only)")
    args = p.parse_args(argv)

    from ..storage.volume import Volume

    v = Volume(args.dir, args.collection, args.volumeId, create=False)

    def visit(n, offset, body) -> None:
        print(
            f"key={n.id:x} cookie={n.cookie:x} size={n.size} "
            f"name={n.name.decode(errors='replace')!r} offset={offset}"
        )
        if args.o and n.data:
            name = n.name.decode(errors="replace") or f"{n.id:x}"
            with open(os.path.join(args.o, name), "wb") as f:
                f.write(n.data)

    if args.o:
        os.makedirs(args.o, exist_ok=True)
    v.scan(visit)
    v.close()
    return 0


def cmd_fix(argv: list[str]) -> int:
    """Rebuild the .idx from the .dat (ref command/fix.go)."""
    p = argparse.ArgumentParser(prog="weed-tpu fix")
    p.add_argument("-dir", required=True)
    p.add_argument("-volumeId", type=int, required=True)
    p.add_argument("-collection", default="")
    args = p.parse_args(argv)
    return _fix(args)


def _fix(args) -> int:
    from ..storage.backend import DiskFile
    from ..storage.needle_map import MemDb
    from ..storage.super_block import read_super_block
    from ..storage.volume import scan_volume_file, volume_base_name
    from ..types import to_offset_units

    base = volume_base_name(args.dir, args.collection, args.volumeId)
    dat = DiskFile(base + ".dat", create=False, read_only=True)
    sb = read_super_block(dat)
    nm = MemDb()

    def visit(n, offset, body) -> None:
        if n.size > 0:
            nm.set(n.id, to_offset_units(offset), n.size)
        else:
            nm.delete(n.id)

    scan_volume_file(dat, sb, visit, read_body=False)
    nm.save_to_idx(base + ".idx")
    # the .idx was rewritten wholesale (key-sorted): a persisted lsm
    # needle-map snapshot folding the old log must not survive
    from ..storage.needle_map.lsm_map import invalidate_snapshot

    invalidate_snapshot(base)
    dat.close()
    print(f"rebuilt {base}.idx with {len(nm)} entries")
    return 0


def cmd_compact(argv: list[str]) -> int:
    """Offline vacuum (ref command/compact.go)."""
    p = argparse.ArgumentParser(prog="weed-tpu compact")
    p.add_argument("-dir", required=True)
    p.add_argument("-volumeId", type=int, required=True)
    p.add_argument("-collection", default="")
    args = p.parse_args(argv)

    from ..storage.vacuum import commit_compact, compact2
    from ..storage.volume import Volume

    v = Volume(args.dir, args.collection, args.volumeId, create=False)
    compact2(v)
    v2 = commit_compact(v)
    print(f"compacted volume {args.volumeId}: {v2.data_file_size()} bytes")
    v2.close()
    return 0


SCAFFOLD_TEMPLATES = {
    # keys match the CLI flag names so -config can apply them as flag
    # defaults directly (ref: weed/command/scaffold.go emits per-subsystem
    # templates consumed by util.LoadConfiguration)
    "config": """# seaweedfs-tpu configuration (TOML); load with -config config
# (searched in ., ~/.seaweedfs-tpu, /etc/seaweedfs-tpu). Every value can be
# overridden from the environment as WEED_<SECTION>_<KEY>, e.g.
# WEED_MASTER_PORT=9444.
[master]
ip = "127.0.0.1"
port = 9333
volumeSizeLimitMB = 30000
defaultReplication = "000"
# peers = "host1:9333,host2:9333,host3:9333"

[volume]
port = 8080
dir = "./data"
max = "7"
mserver = "127.0.0.1:9333"
index = "memory"          # memory | leveldb | sorted | lsm

[server]
volumePort = 8080
filerPort = 8888

[storage]
backend = "tpu"           # route erasure coding through the TPU kernels

# periodically run admin-shell scripts on the leader master
# (ref weed scaffold master template)
[master.maintenance]
scripts = '''
ec.encode -fullPercent=95 -quietFor=1h
ec.rebuild
ec.balance
volume.balance -force
'''
sleep_minutes = 17

[master.filer]
default = "localhost:8888"  # used when maintenance scripts need fs.* commands
""",
    "security": """# seaweedfs-tpu security configuration (TOML)
# (ref: weed scaffold -config=security; weed/security/tls.go)
[security]
jwtSigningKey = ""        # non-empty gates uploads behind fid-scoped JWTs

[grpc]
# PEM files enabling mutual TLS on every gRPC surface when all three are set
ca = ""
cert = ""
key = ""
""",
}


def cmd_scaffold(argv: list[str]) -> int:
    """Emit config templates (ref command/scaffold.go:37-45):
    scaffold [-config config|security] [-output dir]."""
    p = argparse.ArgumentParser(prog="weed-tpu scaffold")
    p.add_argument(
        "-config",
        default="config",
        choices=sorted(SCAFFOLD_TEMPLATES),
        help="which template to generate",
    )
    p.add_argument(
        "-output",
        default="",
        help="directory to write <name>.toml into ('' = print to stdout)",
    )
    args = p.parse_args(argv)
    text = SCAFFOLD_TEMPLATES[args.config]
    if args.output:
        path = os.path.join(args.output, args.config + ".toml")
        with open(path, "w") as f:
            f.write(text)
        print(f"wrote {path}")
    else:
        print(text)
    return 0


def cmd_mount(argv: list[str]) -> int:
    """Mount the filer as a FUSE filesystem (ref command/mount.go,
    weed/filesys/wfs.go:55-61).

    Speaks the FUSE kernel protocol natively over /dev/fuse
    (mount.fuse_lowlevel — the same no-libfuse approach as the reference's
    bazil.org/fuse), serving the kernel-agnostic WFS layer. Requires a
    fuse-capable host (/dev/fuse + either CAP_SYS_ADMIN or fusermount).
    """
    p = argparse.ArgumentParser(prog="weed-tpu mount")
    p.add_argument("-filer", default="localhost:8888")
    p.add_argument("-dir", required=True, help="mount point")
    p.add_argument("-cacheDir", default="", help="local chunk cache dir")
    p.add_argument("-cacheSizeMB", type=int, default=128)
    p.add_argument("-collection", default="")
    p.add_argument("-replication", default="")
    p.add_argument("-chunkSizeLimitMB", type=int, default=4)
    p.add_argument(
        "-cipher",
        action="store_true",
        help="encrypt uploaded chunk content client-side (AES-256-GCM, "
        "per-chunk keys in entry metadata; ref mount -cipher)",
    )
    args = p.parse_args(argv)
    if not os.path.exists("/dev/fuse"):
        print("no /dev/fuse on this host — cannot mount", file=sys.stderr)
        return 2
    if not os.path.isdir(args.dir):
        print(f"mount point {args.dir} is not a directory", file=sys.stderr)
        return 2

    async def run() -> None:
        from ..mount import WFS
        from ..mount.fuse_adapter import mount_and_serve

        wfs = WFS(
            args.filer,
            chunk_size=args.chunkSizeLimitMB * 1024 * 1024,
            cache_dir=args.cacheDir or None,
            cache_size_mb=args.cacheSizeMB,
            collection=args.collection,
            replication=args.replication,
            cipher=args.cipher,
        )
        await wfs.start()
        conn = await mount_and_serve(wfs, args.dir)
        print(f"mounted {args.filer} at {args.dir}")
        try:
            await conn.serve()
        finally:
            conn.unmount()
            await wfs.stop()

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass
    return 0


def cmd_filer_copy(argv: list[str]) -> int:
    """Bulk-copy local files/directories into the filer namespace
    (ref command/filer_copy.go): chunks are assigned and uploaded straight
    to volume servers, then one CreateEntry per file lands the metadata —
    bytes never round-trip through the filer process."""
    p = argparse.ArgumentParser(
        prog="weed-tpu filer.copy",
        usage="weed-tpu filer.copy [options] file_or_dir... dest_filer_path",
    )
    p.add_argument("-filer", default="localhost:8888")
    p.add_argument("-collection", default="")
    p.add_argument("-replication", default="")
    p.add_argument("-ttl", default="")
    p.add_argument("-maxMB", type=int, default=4, help="chunk size in MB")
    p.add_argument("-concurrency", type=int, default=8)
    p.add_argument(
        "-include", default="",
        help="fnmatch pattern; only matching basenames copy (ref -include)",
    )
    p.add_argument("paths", nargs="+", help="sources... then /dest/dir/")
    args = p.parse_args(argv)
    if args.maxMB < 1:
        # unlike `upload -maxMB 0` (never split), a zero chunk size here
        # would read nothing — reject instead of silently copying empties
        print("-maxMB must be >= 1", file=sys.stderr)
        return 2
    if len(args.paths) < 2:
        print("need at least one source and a destination path", file=sys.stderr)
        return 2
    sources, dest = args.paths[:-1], args.paths[-1]
    if not dest.startswith("/"):
        print(f"destination {dest!r} must be an absolute filer path",
              file=sys.stderr)
        return 2

    import fnmatch
    import mimetypes
    import time as _time

    chunk_size = args.maxMB * 1024 * 1024

    missing_sources = []

    def walk():
        """(local_path, filer_path) pairs."""
        for src in sources:
            if os.path.isdir(src):
                root = os.path.abspath(src)
                base = os.path.basename(root.rstrip("/"))
                for dirpath, _dirs, files in os.walk(root):
                    rel = os.path.relpath(dirpath, root)
                    for fn in sorted(files):
                        if args.include and not fnmatch.fnmatch(
                            fn, args.include
                        ):
                            continue
                        sub = fn if rel == "." else f"{rel}/{fn}"
                        yield (
                            os.path.join(dirpath, fn),
                            f"{dest.rstrip('/')}/{base}/{sub}",
                        )
            elif os.path.isfile(src):
                if args.include and not fnmatch.fnmatch(
                    os.path.basename(src), args.include
                ):
                    continue
                yield src, f"{dest.rstrip('/')}/{os.path.basename(src)}"
            else:
                missing_sources.append(src)
                print(f"cannot copy {src!r}: not a file or directory",
                      file=sys.stderr)

    async def run() -> int:
        import aiohttp

        from ..client.operation import upload_data
        from ..filer.entry import Attr, Entry, FileChunk
        from ..pb import grpc_address
        from ..pb.rpc import Stub, new_channel

        # private channel: this command runs its own short-lived event
        # loop, so the process-global channel cache must not be touched
        # (rpc.Stub docstring) — close exactly what we opened
        channel = new_channel(grpc_address(args.filer))
        stub = Stub(grpc_address(args.filer), "filer", channel=channel)
        from ..util.http_timeouts import client_timeout

        session = aiohttp.ClientSession(timeout=client_timeout())
        sem = asyncio.Semaphore(args.concurrency)
        stats = {"files": 0, "bytes": 0, "failed": 0}
        ttl_seconds = 0
        if args.ttl:
            # parse ONCE, and fail before any chunk is uploaded
            from ..storage.ttl import TTL

            ttl_seconds = TTL.read(args.ttl).minutes * 60

        # the filer's cipher setting governs DIRECT volume uploads too:
        # with -encryptVolumeData, plaintext chunks from this command would
        # break the "volume servers only see ciphertext" guarantee, so the
        # cipher flag is read once up front and every chunk is encrypted
        # client-side with its own key carried in chunk metadata (ref
        # filer_copy.go:114,180; upload_content.go:135-150)
        try:
            conf = await stub.call("GetFilerConfiguration", {})
            cipher = bool(conf.get("cipher"))
        except Exception as e:
            # fail CLOSED: assuming no cipher on an RPC blip would upload
            # plaintext to a cluster whose guarantee is "volume servers
            # only see ciphertext"
            print(
                f"GetFilerConfiguration failed ({e}); refusing to copy "
                "without knowing the filer's cipher setting",
                file=sys.stderr,
            )
            await session.close()
            await channel.close()
            return 1

        async def upload_chunk(data: bytes) -> FileChunk:
            resp = await stub.call(
                "AssignVolume",
                {
                    "count": 1,
                    "collection": args.collection,
                    "replication": args.replication,
                    "ttl": args.ttl,
                },
            )
            if resp.get("error"):
                raise RuntimeError(resp["error"])
            key = b""
            payload = data
            if cipher:
                from ..util.cipher import encrypt, gen_cipher_key

                key = gen_cipher_key()
                payload = encrypt(data, key)
            # shared chunk-upload helper: multipart, JWT, the ttl query the
            # volume server stamps the needle TTL from, error-body checks
            result = await upload_data(
                session, resp["url"], resp["file_id"], payload,
                ttl=args.ttl, jwt=resp.get("auth", ""),
            )
            return FileChunk(
                fid=resp["file_id"], offset=0, size=len(data),
                mtime_ns=_time.time_ns(),
                etag=result.get("eTag", ""),
                cipher_key=key,
            )

        async def copy_one(local: str, remote: str) -> None:
            async with sem:
                try:
                    st = await asyncio.to_thread(os.stat, local)
                    chunks = []
                    with open(local, "rb") as f:
                        offset = 0
                        while True:
                            # file IO off the loop: a slow disk must not
                            # stall the other in-flight uploads
                            data = await asyncio.to_thread(
                                f.read, chunk_size
                            )
                            if not data:
                                break  # empty file -> chunkless entry
                            c = await upload_chunk(data)
                            c.offset = offset
                            chunks.append(c)
                            offset += len(data)
                    mime = mimetypes.guess_type(local)[0] or ""
                    entry = Entry(
                        full_path=remote,
                        attr=Attr(
                            mtime=st.st_mtime,
                            crtime=st.st_mtime,
                            mode=st.st_mode & 0o7777,
                            mime=mime,
                            collection=args.collection,
                            replication=args.replication,
                            ttl_seconds=ttl_seconds,
                        ),
                        chunks=chunks,
                    )
                    resp = await stub.call(
                        "CreateEntry", {"entry": entry.to_dict()}
                    )
                    if resp.get("error"):
                        raise RuntimeError(resp["error"])
                    stats["files"] += 1
                    stats["bytes"] += st.st_size
                except Exception as e:
                    stats["failed"] += 1
                    print(f"copy {local} -> {remote}: {e}", file=sys.stderr)

        await asyncio.gather(*(copy_one(l, r) for l, r in walk()))
        await session.close()
        await channel.close()
        stats["failed"] += len(missing_sources)
        print(
            f"copied {stats['files']} files, {stats['bytes']:,} bytes"
            + (f", {stats['failed']} FAILED" if stats["failed"] else "")
        )
        return 1 if stats["failed"] else 0

    return asyncio.run(run())


def cmd_filer_replicate(argv: list[str]) -> int:
    """Continuously replicate one filer's changes into another cluster
    (ref command/filer_replication.go): subscribes to the source filer's
    SubscribeMetadata stream and applies each event to a filer-HTTP or
    V4-signed S3 sink."""
    p = argparse.ArgumentParser(prog="weed-tpu filer.replicate")
    p.add_argument("-filer", default="localhost:8888", help="source filer")
    p.add_argument("-pathPrefix", default="/")
    p.add_argument("-targetFiler", default="", help="destination filer host:port")
    p.add_argument("-targetS3", default="", help="destination S3 endpoint host:port")
    p.add_argument("-s3Bucket", default="")
    p.add_argument("-s3AccessKey", default="")
    p.add_argument("-s3SecretKey", default="")
    p.add_argument("-s3Region", default="us-east-1")
    p.add_argument(
        "-timeAgoSeconds",
        type=float,
        default=0,
        help="replay events starting this many seconds ago (0 = from now)",
    )
    args = p.parse_args(argv)
    if not args.targetFiler and not args.targetS3:
        p.error("need -targetFiler or -targetS3")

    async def run() -> None:
        import time as _time

        from ..pb import grpc_address
        from ..pb.rpc import Stub
        from ..replication import FilerHttpSink, S3Sink

        if args.targetS3:
            sink = S3Sink(
                source_filer=args.filer,
                endpoint=args.targetS3,
                bucket=args.s3Bucket,
                access_key=args.s3AccessKey,
                secret_key=args.s3SecretKey,
                region=args.s3Region,
            )
        else:
            sink = FilerHttpSink(args.filer, args.targetFiler)
        since_ns = (
            int((_time.time() - args.timeAgoSeconds) * 1e9)
            if args.timeAgoSeconds
            else -1
        )
        try:
            # reconnect forever: a filer restart must not kill the daemon
            # (ref filer_replication.go's indefinite retry loop)
            while True:
                stub = Stub(grpc_address(args.filer), "filer")
                try:
                    async for msg in stub.server_stream(
                        "SubscribeMetadata",
                        {
                            "client_name": "filer.replicate",
                            "path_prefix": args.pathPrefix,
                            "since_ns": since_ns,
                        },
                    ):
                        notif = msg.get("event_notification") or {}
                        event_type = notif.get("event_type", "")
                        new, old = notif.get("new_entry"), notif.get("old_entry")
                        target = new or old
                        if target:
                            path = target["full_path"]
                            entry = new
                            if event_type == "rename" and old and new:
                                entry = dict(new)
                                entry["_old_path"] = old["full_path"]
                            # retry until the sink accepts the event; only
                            # then advance the resume point — a transient
                            # target outage must not drop events (ref
                            # filer_replication.go's retry loop)
                            while True:
                                try:
                                    await sink.apply(event_type, path, entry)
                                    print(
                                        f"replicated {event_type} {path}",
                                        flush=True,
                                    )
                                    break
                                except Exception as e:
                                    print(
                                        f"replicate {event_type} {path}"
                                        f" failed ({e}); retrying",
                                        flush=True,
                                    )
                                    await asyncio.sleep(1.0)
                        if msg.get("ts_ns"):
                            since_ns = int(msg["ts_ns"])
                except Exception as e:
                    print(f"subscribe lost ({e}); reconnecting", flush=True)
                await asyncio.sleep(1.0)
        finally:
            await sink.close()

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass
    return 0


def cmd_watch(argv: list[str]) -> int:
    """Follow recent metadata changes on a filer (ref command/watch.go)."""
    p = argparse.ArgumentParser(prog="weed-tpu watch")
    p.add_argument("-filer", default="localhost:8888")
    p.add_argument("-pathPrefix", default="/")
    p.add_argument(
        "-timeAgoSeconds",
        type=float,
        default=0,
        help="replay events starting this many seconds ago",
    )
    args = p.parse_args(argv)

    async def run() -> None:
        import json
        import time as _time

        from ..pb import grpc_address
        from ..pb.rpc import Stub

        # -1 = "from now" on the server clock (immune to client skew)
        since_ns = (
            int((_time.time() - args.timeAgoSeconds) * 1e9)
            if args.timeAgoSeconds
            else -1
        )
        stub = Stub(grpc_address(args.filer), "filer")
        async for msg in stub.server_stream(
            "SubscribeMetadata",
            {
                "client_name": "watch",
                "path_prefix": args.pathPrefix,
                "since_ns": since_ns,
            },
        ):
            print(f"events: {json.dumps(msg)}", flush=True)

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass
    return 0


def cmd_version(argv: list[str]) -> int:
    from .. import __version__

    print(f"seaweedfs-tpu {__version__}")
    return 0


COMMANDS = {
    "master": cmd_master,
    "volume": cmd_volume,
    "server": cmd_server,
    "filer": cmd_filer,
    "s3": cmd_s3,
    "blob": cmd_blob,
    "webdav": cmd_webdav,
    "msgBroker": cmd_msg_broker,
    "shell": cmd_shell,
    "benchmark": cmd_benchmark,
    "upload": cmd_upload,
    "download": cmd_download,
    "backup": cmd_backup,
    "export": cmd_export,
    "fix": cmd_fix,
    "compact": cmd_compact,
    "scaffold": cmd_scaffold,
    "mount": cmd_mount,
    "watch": cmd_watch,
    "filer.copy": cmd_filer_copy,
    "filer.replicate": cmd_filer_replicate,
    "version": cmd_version,
}


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help", "help"):
        print("usage: weed-tpu <command> [options]\ncommands: " + " ".join(sorted(COMMANDS)))
        return 0
    cmd = COMMANDS.get(argv[0])
    if cmd is None:
        print(f"unknown command {argv[0]!r}", file=sys.stderr)
        return 1
    return cmd(argv[1:])


if __name__ == "__main__":
    sys.exit(main())

"""Shell command registry (ref: weed/shell/commands.go + command files).

Each command: async fn(env, argv) -> output string.
"""

from __future__ import annotations

import asyncio
import re
import time
from collections import defaultdict

from ..storage.erasure_coding import DATA_SHARDS_COUNT, TOTAL_SHARDS_COUNT
from ..storage.erasure_coding.ec_volume import ShardBits
from .command_env import CommandEnv
from .ec_common import (
    EcNode,
    ShardMove,
    execute_shard_move,
    nodes_from_topology,
    plan_balanced_spread,
    plan_dedupe,
    plan_rack_balance,
    select_volumes_for_ec_encode,
)

COMMANDS: dict[str, callable] = {}


def command(name: str):
    def deco(fn):
        COMMANDS[name] = fn
        return fn

    return deco


def _parse_flags(argv: list[str]) -> dict[str, str]:
    flags = {}
    i = 0
    while i < len(argv):
        arg = argv[i]
        if arg.startswith("-"):
            key = arg.lstrip("-")
            if "=" in key:
                key, _, val = key.partition("=")
                flags[key] = val
            elif i + 1 < len(argv) and not argv[i + 1].startswith("-"):
                flags[key] = argv[i + 1]
                i += 1
            else:
                flags[key] = "true"
        i += 1
    return flags


_GO_DURATION_UNITS = {
    "ns": 1e-9, "us": 1e-6, "\u00b5s": 1e-6, "ms": 1e-3,
    "s": 1.0, "m": 60.0, "h": 3600.0,
}
_GO_DURATION_PART = re.compile(r"(\d+\.?\d*|\.\d+)(ns|us|\u00b5s|ms|s|m|h)")


def parse_go_duration(text: str) -> float:
    """Seconds of a Go duration flag (time.ParseDuration): `1h`, `30m`,
    `45s`, `1h30m`, `1.5h`, `0`. ValueError on anything else."""
    body = text.strip().lstrip("+")
    if body == "0":
        return 0.0
    pos, total = 0, 0.0
    while pos < len(body):
        part = _GO_DURATION_PART.match(body, pos)
        if part is None:
            break
        total += float(part.group(1)) * _GO_DURATION_UNITS[part.group(2)]
        pos = part.end()
    if pos == 0 or pos != len(body):
        raise ValueError(f"bad duration {text!r}; want e.g. 1h, 30m, 45s")
    return total


async def run_command(env: CommandEnv, line: str) -> str:
    parts = line.strip().split()
    if not parts:
        return ""
    name, argv = parts[0], parts[1:]
    fn = COMMANDS.get(name)
    if fn is None:
        return f"unknown command: {name} (try `help`)"
    return await fn(env, argv)


# ---------------- basic ----------------
@command("help")
async def cmd_help(env, argv) -> str:
    """help [command]: the commands, or one command's own description."""
    if argv:
        fn = COMMANDS.get(argv[0])
        if fn is None:
            return f"unknown command: {argv[0]} (try `help`)"
        import inspect

        return f"{argv[0]}: " + inspect.cleandoc(fn.__doc__ or "no description")
    return "commands:\n  " + "\n  ".join(sorted(COMMANDS))


@command("lock")
async def cmd_lock(env, argv) -> str:
    await env.acquire_lock()
    return "locked"


@command("unlock")
async def cmd_unlock(env, argv) -> str:
    await env.release_lock()
    return "unlocked"


@command("volume.list")
async def cmd_volume_list(env, argv) -> str:
    nodes = await env.collect_data_nodes()
    lines = []
    for dn in nodes:
        lines.append(
            f"node {dn['url']} dc:{dn['data_center']} rack:{dn['rack']} "
            f"volumes:{len(dn.get('volumes', []))} free:{dn.get('free_space', 0)}"
        )
        for v in dn.get("volumes", []):
            lines.append(
                f"  volume id:{v['id']} size:{v.get('size', 0)} "
                f"collection:{v.get('collection', '')!r} "
                f"file_count:{v.get('file_count', 0)} "
                f"deleted:{v.get('delete_count', 0)} "
                f"read_only:{v.get('read_only', False)}"
            )
        for m in dn.get("ec_shards", []):
            bits = ShardBits(int(m["ec_index_bits"]))
            lines.append(f"  ec volume id:{m['id']} shards:{bits.shard_ids()}")
    return "\n".join(lines) or "no volume servers"


@command("collection.list")
async def cmd_collection_list(env, argv) -> str:
    resp = await env.master_stub.call("CollectionList", {})
    names = [c["name"] or "(default)" for c in resp.get("collections", [])]
    return "\n".join(names) or "no collections"


@command("collection.delete")
async def cmd_collection_delete(env, argv) -> str:
    env.confirm_is_locked()
    flags = _parse_flags(argv)
    name = flags.get("collection", argv[0] if argv else "")
    await env.master_stub.call("CollectionDelete", {"name": name})
    return f"deleted collection {name!r}"


# ---------------- volume management ----------------
@command("volume.mark.readonly")
async def cmd_volume_mark_readonly(env, argv) -> str:
    env.confirm_is_locked()
    flags = _parse_flags(argv)
    vid = int(flags["volumeId"])
    for dn in await env.collect_data_nodes():
        if any(int(v["id"]) == vid for v in dn.get("volumes", [])):
            await env.volume_stub(dn["url"]).call(
                "VolumeMarkReadonly", {"volume_id": vid}
            )
    return f"volume {vid} marked readonly"


@command("volume.delete")
async def cmd_volume_delete(env, argv) -> str:
    env.confirm_is_locked()
    flags = _parse_flags(argv)
    vid = int(flags["volumeId"])
    node = flags.get("node", "")
    for dn in await env.collect_data_nodes():
        if node and dn["url"] != node:
            continue
        if any(int(v["id"]) == vid for v in dn.get("volumes", [])):
            await env.volume_stub(dn["url"]).call("VolumeDelete", {"volume_id": vid})
    return f"volume {vid} deleted"


@command("volume.unmount")
async def cmd_volume_unmount(env, argv) -> str:
    env.confirm_is_locked()
    flags = _parse_flags(argv)
    vid = int(flags["volumeId"])
    node = flags["node"]
    await env.volume_stub(node).call("VolumeUnmount", {"volume_id": vid})
    return f"volume {vid} unmounted from {node}"


@command("volume.mount")
async def cmd_volume_mount(env, argv) -> str:
    env.confirm_is_locked()
    flags = _parse_flags(argv)
    vid = int(flags["volumeId"])
    node = flags["node"]
    await env.volume_stub(node).call("VolumeMount", {"volume_id": vid})
    return f"volume {vid} mounted on {node}"


async def move_volume(
    env, vid: int, collection: str, source: str, target: str, timeout: float = 600
) -> str:
    """Copy a volume to the target node, then delete the source copy;
    returns '' on success (ref command_volume_move.go). Shared by
    volume.move and volume.balance."""
    r = await env.volume_stub(target).call(
        "VolumeCopy",
        {"volume_id": vid, "collection": collection, "source_data_node": source},
        timeout=timeout,
    )
    if r.get("error"):
        return r["error"]
    await env.volume_stub(source).call("VolumeDelete", {"volume_id": vid})
    return ""


@command("volume.move")
async def cmd_volume_move(env, argv) -> str:
    """Copy a volume to a target node, then delete the source copy
    (ref command_volume_move.go)."""
    env.confirm_is_locked()
    flags = _parse_flags(argv)
    vid = int(flags["volumeId"])
    source, target = flags["source"], flags["target"]
    err = await move_volume(env, vid, flags.get("collection", ""), source, target)
    if err:
        return f"move failed: {err}"
    return f"volume {vid} moved {source} -> {target}"


@command("volume.copy")
async def cmd_volume_copy(env, argv) -> str:
    """volume.copy <source host:port> <target host:port> <volume id> —
    copy a volume between volume servers (ref command_volume_copy.go;
    usually unmount it first)."""
    env.confirm_is_locked()
    from .operator_commands import _fs_args

    flags, args = _fs_args(argv, value_flags=("collection",))
    if len(args) != 3:
        return (
            "usage: volume.copy <source host:port> <target host:port> "
            "<volume id>"
        )
    source, target, vid_s = args
    try:
        vid = int(vid_s)
    except ValueError:
        return f"wrong volume id format {vid_s!r}"
    if source == target:
        return "source and target volume servers are the same!"
    r = await env.volume_stub(target).call(
        "VolumeCopy",
        {
            "volume_id": vid,
            "collection": flags.get("collection", ""),
            "source_data_node": source,
        },
        timeout=3600,
    )
    if r.get("error"):
        return f"copy failed: {r['error']}"
    return f"volume {vid} copied {source} -> {target}"


@command("volume.configure.replication")
async def cmd_volume_configure_replication(env, argv) -> str:
    """Change a volume's replica placement in place
    (ref command_volume_configure_replication.go): every server holding
    the volume rewrites its super block; heartbeats propagate the change."""
    env.confirm_is_locked()
    flags = _parse_flags(argv)
    vid = int(flags["volumeId"])
    replication = flags.get("replication", "")
    from ..storage.super_block import ReplicaPlacement

    try:
        rp = ReplicaPlacement.parse(replication)
        rp.to_byte()  # force the representability check up front
    except ValueError as e:
        return f"replication format: {e}"
    holders = []
    for dn in await env.collect_data_nodes():
        for v in dn.get("volumes", []):
            if int(v["id"]) == vid and int(
                v.get("replica_placement", 0)
            ) != rp.to_byte():
                holders.append(dn["url"])
    if not holders:
        return "no volume needs change"
    # keep going through every holder even after a failure: stopping at the
    # first error would leave replicas with silently divergent placements
    # and no pointer to which servers still carry the old one
    ok, failed = [], []
    for url in holders:
        try:
            r = await env.volume_stub(url).call(
                "VolumeConfigure",
                {"volume_id": vid, "replication": replication},
            )
            err = r.get("error")
        except Exception as e:
            err = str(e)
        if err:
            failed.append((url, err))
        else:
            ok.append(url)
    if failed:
        lines = [
            f"volume {vid}: replication -> {rp} on {len(ok)}/{len(holders)} "
            "server(s)"
        ]
        lines += [f"  FAILED {url}: {err}" for url, err in failed]
        lines.append(
            "  placement now DIVERGES across replicas; re-run "
            "volume.configure.replication after fixing the failed servers: "
            + ", ".join(url for url, _ in failed)
        )
        return "\n".join(lines)
    return (
        f"volume {vid}: replication -> {rp} on {len(holders)} server(s)"
    )


@command("volume.tier.upload")
async def cmd_volume_tier_upload(env, argv) -> str:
    """Move a volume's .dat to a remote tier
    (ref command_volume_tier_upload.go): volume.tier.upload
    -volumeId N -dest s3.default [-collection c] [-keepLocalDatFile]."""
    env.confirm_is_locked()
    flags = _parse_flags(argv)
    vid = int(flags["volumeId"])
    dest = flags.get("dest", "")
    collection = flags.get("collection", "")
    out = []
    for dn in await env.collect_data_nodes():
        if any(int(v["id"]) == vid for v in dn.get("volumes", [])):
            async for msg in env.volume_stub(dn["url"]).server_stream(
                "VolumeTierMoveDatToRemote",
                {
                    "volume_id": vid,
                    "collection": collection,
                    "destination_backend_name": dest,
                    "keep_local_dat_file": "keepLocalDatFile" in flags,
                },
                timeout=600,
            ):
                if msg.get("error"):
                    return f"tier upload failed: {msg['error']}"
                if msg.get("key"):
                    out.append(
                        f"volume {vid} tiered to {dest} as {msg['key']}"
                        f" ({msg.get('size', 0)} bytes)"
                    )
    return "\n".join(out) or f"volume {vid} not found"


@command("volume.tier.download")
async def cmd_volume_tier_download(env, argv) -> str:
    """Bring a tiered volume's .dat back to local disk
    (ref command_volume_tier_download.go): volume.tier.download -volumeId N."""
    env.confirm_is_locked()
    flags = _parse_flags(argv)
    vid = int(flags["volumeId"])
    out = []
    for dn in await env.collect_data_nodes():
        if any(int(v["id"]) == vid for v in dn.get("volumes", [])):
            async for msg in env.volume_stub(dn["url"]).server_stream(
                "VolumeTierMoveDatFromRemote", {"volume_id": vid}, timeout=600
            ):
                if msg.get("error"):
                    return f"tier download failed: {msg['error']}"
                if msg.get("size"):
                    out.append(f"volume {vid} downloaded ({msg['size']} bytes)")
    return "\n".join(out) or f"volume {vid} not found"


@command("volume.vacuum")
async def cmd_volume_vacuum(env, argv) -> str:
    """Vacuum plane: `volume.vacuum [-garbageThreshold=0.3]` forces a
    cluster sweep; `-status` shows the master's highest-garbage-first
    queue and recent outcomes; `-run` forces one scheduler scan+dispatch
    round off heartbeat garbage ratios (see docs/perf.md "Vacuum plane")."""
    flags = _parse_flags(argv)
    if "status" in flags or "run" in flags:
        req: dict = {}
        if "run" in flags:
            req["run"] = True
            if "garbageThreshold" in flags:
                req["garbage_threshold"] = float(flags["garbageThreshold"])
        r = await env.master_stub.call("VacuumStatus", req, timeout=3600)
        if r.get("error"):
            return f"vacuum status failed: {r['error']}"
        lines = [
            f"auto_vacuum: {'on' if r.get('auto_vacuum') else 'off'} "
            f"(threshold {r.get('garbage_threshold')}) · "
            f"queue depth: {r.get('queue_depth', 0)}"
        ]
        from ..topology.vacuum_plan import priority_to_ratio

        for t in r.get("queue", []):
            lines.append(
                f"  queued volume {t['volume_id']} (garbage ~"
                f"{priority_to_ratio(int(t['priority'])):.2f}, "
                f"attempts {t['attempts']})"
            )
        for t in r.get("recent", []):
            if t.get("error"):
                outcome = f"ERROR: {t['error']}"
            elif t.get("skipped"):
                outcome = f"skipped ({t['skipped']})"
            else:
                outcome = "compacted"
            lines.append(f"  recent volume {t['volume_id']}: {outcome}")
        if "ran" in r:
            ran = r["ran"]
            lines.append(
                f"ran one round: dispatched {len(ran.get('dispatched', []))},"
                f" queue depth now {ran.get('queue_depth', 0)}"
            )
        return "\n".join(lines)
    threshold = float(flags.get("garbageThreshold", 0.3))
    import aiohttp

    from ..util.http_timeouts import client_timeout

    async with aiohttp.ClientSession(timeout=client_timeout()) as session:
        async with session.get(
            f"http://{env.master}/vol/vacuum?garbageThreshold={threshold}"
        ) as resp:
            data = await resp.json()
    return f"vacuum: {data}"


@command("volume.lifecycle")
async def cmd_volume_lifecycle(env, argv) -> str:
    """Lifecycle plane: `volume.lifecycle -status` shows the master's
    heat thresholds, conversion queue and recent outcomes; `-run` forces
    one scheduler scan+dispatch round off heartbeat heat (`-all` waives
    the cold/full planner gates — the dispatcher's authoritative re-check
    still applies). See docs/perf.md "Lifecycle plane"."""
    flags = _parse_flags(argv)
    req: dict = {}
    if "run" in flags:
        req["run"] = True
        if "all" in flags:
            req["include_all"] = True
        if "maxDispatch" in flags:
            req["max_dispatch"] = int(flags["maxDispatch"])
    r = await env.master_stub.call("LifecycleStatus", req, timeout=3600)
    if r.get("error"):
        return f"lifecycle status failed: {r['error']}"
    th = r.get("thresholds", {})
    cold_backend = r.get("cold_backend") or "off"
    lines = [
        f"auto_lifecycle: {'on' if r.get('auto_lifecycle') else 'off'} "
        f"(cold<= {th.get('cold_read_heat')}r/{th.get('cold_write_heat')}w, "
        f"hot>= {th.get('hot_read_heat')}, "
        f"full>= {th.get('full_fraction')}x limit) · "
        f"cold tier: {cold_backend} "
        f"(offload<= {th.get('offload_read_heat')}, "
        f"recall>= {th.get('recall_read_heat')}) · "
        f"queue depth: {r.get('queue_depth', 0)}"
    ]
    _DIRECTIONS = {
        "lifecycle_ec": "auto-EC",
        "lifecycle_inflate": "re-inflate",
        "lifecycle_offload": "offload",
        "lifecycle_recall": "recall",
    }
    for t in r.get("queue", []):
        direction = _DIRECTIONS.get(t["kind"], t["kind"])
        lines.append(
            f"  queued volume {t['volume_id']} ({direction}, "
            f"attempts {t['attempts']})"
        )
    for t in r.get("recent", []):
        if t.get("error"):
            outcome = f"ERROR: {t['error']}"
        elif t.get("skipped"):
            outcome = f"skipped ({t['skipped']})"
        elif t.get("converted") == "ec":
            outcome = f"erasure-coded (spread {t.get('spread')})"
        elif t.get("offloaded") is not None:
            outcome = (
                f"offloaded to {t.get('backend')} ({t.get('bytes', 0)} B)"
            )
        elif t.get("recalled") is not None:
            walls = t.get("recall_s") or {}
            slowest = max(walls.values(), default=0.0)
            outcome = (
                f"recalled ({t.get('bytes', 0)} B, slowest holder "
                f"{slowest:.3f}s)"
            )
        else:
            outcome = f"re-inflated on {t.get('target')}"
        lines.append(f"  recent volume {t['volume_id']}: {outcome}")
    if "ran" in r:
        ran = r["ran"]
        lines.append(
            f"ran one round: dispatched {len(ran.get('dispatched', []))},"
            f" queue depth now {ran.get('queue_depth', 0)}"
        )
    return "\n".join(lines)


@command("volume.tier.sweep")
async def cmd_volume_tier_sweep(env, argv) -> str:
    """Remote-orphan sweep (ISSUE 15 satellite): the master collects
    every key the live volume servers' tier manifests still name,
    lists the cold backend, and deletes aged objects nothing names —
    bytes leaked by crashes between manifest uncommit and remote
    delete, never data. `-backend name` overrides the configured cold
    backend; `-grace seconds` (default 3600) protects young objects
    that may be in-flight offloads (0 also sweeps undatable ones);
    `-expect N` refuses the sweep unless at least N volume servers are
    connected (a down holder's manifests cannot be consulted). Keys of
    volumes still registered in the topology are never deleted."""
    flags = _parse_flags(argv)
    req: dict = {}
    if "backend" in flags:
        req["backend"] = flags["backend"]
    if "grace" in flags:
        req["grace_s"] = float(flags["grace"])
    if "expect" in flags:
        req["expected_holders"] = int(flags["expect"])
    r = await env.master_stub.call("TierOrphanSweep", req, timeout=3600)
    if r.get("error"):
        return f"tier sweep failed: {r['error']}"
    if r.get("skipped"):
        return f"tier sweep skipped: {r['skipped']}"
    return (
        f"backend {r.get('backend')}: listed {r.get('listed', 0)}, "
        f"referenced {r.get('referenced', 0)} across "
        f"{r.get('holders', 0)} holders, swept "
        f"{r.get('orphans_swept', 0)} orphans, "
        f"{r.get('skipped_young', 0)} young + "
        f"{r.get('skipped_registered', 0)} registered-volume objects "
        "left alone"
    )


@command("volume.fix.replication")
async def cmd_volume_fix_replication(env, argv) -> str:
    """Re-replicate under-replicated volumes (ref
    command_volume_fix_replication.go)."""
    env.confirm_is_locked()
    nodes = await env.collect_data_nodes()
    fixes = plan_replication_fixes(nodes)
    done = []
    for vid, source, target, collection in fixes:
        r = await env.volume_stub(target).call(
            "VolumeCopy",
            {"volume_id": vid, "collection": collection,
             "source_data_node": source},
            timeout=600,
        )
        if not r.get("error"):
            done.append(f"volume {vid}: copied {source} -> {target}")
    return "\n".join(done) or "no under-replicated volumes"


def plan_replication_fixes(
    nodes: list[dict],
) -> list[tuple[int, str, str, str]]:
    """Pure planner: -> [(vid, source_url, target_url, collection)]."""
    locations = defaultdict(list)
    info_by_vid = {}
    for dn in nodes:
        for v in dn.get("volumes", []):
            locations[int(v["id"])].append(dn["url"])
            info_by_vid[int(v["id"])] = v
    fixes = []
    for vid, urls in locations.items():
        info = info_by_vid[vid]
        from ..storage.super_block import ReplicaPlacement

        rp = ReplicaPlacement.from_byte(int(info.get("replica_placement", 0)))
        want = rp.copy_count()
        if len(urls) >= want:
            continue
        candidates = [
            dn["url"]
            for dn in nodes
            if dn["url"] not in urls and int(dn.get("free_space", 0)) > 0
        ]
        for target in candidates[: want - len(urls)]:
            fixes.append((vid, urls[0], target, info.get("collection", "")))
    return fixes


# ---------------- EC suite ----------------
async def _collect_ec_nodes(env) -> list[EcNode]:
    return nodes_from_topology(await env.collect_data_nodes())


async def _ec_geometry(env, vid: int, collection: str, holders) -> tuple[int, int]:
    """(data_shards, parity_shards) of an EC volume, asked from a shard
    holder's .vif (VolumeEcShardsInfo); falls back to the standard 10.4."""
    for url in holders:
        try:
            r = await env.volume_stub(url).call(
                "VolumeEcShardsInfo", {"volume_id": vid, "collection": collection}
            )
            if not r.get("error"):
                return (
                    int(r.get("data_shards") or DATA_SHARDS_COUNT),
                    int(
                        r.get("parity_shards")
                        or TOTAL_SHARDS_COUNT - DATA_SHARDS_COUNT
                    ),
                )
        except Exception:
            continue
    return DATA_SHARDS_COUNT, TOTAL_SHARDS_COUNT - DATA_SHARDS_COUNT


@command("ec.encode")
async def cmd_ec_encode(env, argv) -> str:
    """Erasure-code volumes and spread shards
    (ref command_ec_encode.go:55-264).

    ec.encode -volumeId <id[,id...]> [-collection <name>] [-shards k.m]
    ec.encode [-collection <name>] [-fullPercent=95] [-quietFor=1h] [-shards k.m]

    Without -volumeId the master's topology selects, as upstream's
    collectVolumeIdsForEcEncode does: the volumes of -collection (default:
    the empty one) whose size is over -fullPercent of the master's volume
    size limit AND that were last modified more than -quietFor ago (a Go
    duration: 1h, 30m, 45s, 0s; default 1h). The master's maintenance
    script runs `ec.encode -fullPercent=95 -quietFor=1h`. Volumes that share
    a node are converted by one VolumeEcShardsGenerateBatch call.

    -shards k.m selects an alternate RS geometry (e.g. 6.3, 12.4); the
    default is the reference's 10.4 (ec_encoder.go:17-23).
    """
    env.confirm_is_locked()
    flags = _parse_flags(argv)
    collection = flags.get("collection", "")
    data_shards = parity_shards = 0
    if "shards" in flags:
        try:
            k, _, m = flags["shards"].partition(".")
            data_shards, parity_shards = int(k), int(m)
        except ValueError:
            data_shards = parity_shards = 0
        if data_shards < 1 or parity_shards < 1:
            return f"bad -shards {flags['shards']!r}; want e.g. 10.4 or 6.3"
    vids: list[int] = []
    nodes = await env.collect_data_nodes()
    if "volumeId" in flags:
        # comma-separated ids allowed: co-located ones encode as one batch
        vids = [int(x) for x in str(flags["volumeId"]).split(",") if x]
    else:
        try:
            full_pct = float(flags.get("fullPercent", 95))
            quiet_s = parse_go_duration(flags.get("quietFor", "1h"))
        except ValueError as e:
            return f"ec.encode: {e}"
        resp = await env.master_stub.call("VolumeList", {})
        vids = select_volumes_for_ec_encode(
            [v for dn in nodes for v in dn.get("volumes", [])],
            collection,
            int(resp.get("volume_size_limit_mb", 30000)),
            full_pct, quiet_s, time.time(),
        )
    results = []
    # volumes co-located on one node are converted by ONE call
    # (VolumeEcShardsGenerateBatch -> write_ec_files_multi: each through
    # the one encode pipeline, a device codec's one after another, a host
    # codec's at once) instead of one RPC each
    by_source: dict = {}
    for vid in vids:
        source = None
        for dn in nodes:
            if any(int(v["id"]) == vid for v in dn.get("volumes", [])):
                source = dn["url"]
                break
        by_source.setdefault(source, []).append(vid)
    for source, group in by_source.items():
        if source is None:
            results.extend(f"volume {v}: not found" for v in group)
        elif len(group) == 1:
            results.append(
                await _do_ec_encode(
                    env, group[0], collection, data_shards, parity_shards,
                    source=source,
                )
            )
        else:
            sstub = env.volume_stub(source)
            for v in group:
                await sstub.call("VolumeMarkReadonly", {"volume_id": v})
            gen_req = {"volume_ids": group, "collection": collection}
            if data_shards:
                gen_req["data_shards"] = data_shards
                gen_req["parity_shards"] = parity_shards
            r = await sstub.call(
                "VolumeEcShardsGenerateBatch", gen_req, timeout=3600
            )
            errs = (
                {str(v): r["error"] for v in group}
                if r.get("error")
                else r.get("errors", {})
            )
            for v in group:
                if str(v) in errs:
                    results.append(
                        f"volume {v}: generate failed: {errs[str(v)]}"
                    )
                else:
                    results.append(
                        await _ec_spread(
                            env, v, collection, data_shards,
                            parity_shards, source,
                        )
                    )
    return "\n".join(results) or "no volumes to encode"


async def _do_ec_encode(
    env,
    vid: int,
    collection: str,
    data_shards: int = 0,
    parity_shards: int = 0,
    source: str = "",
) -> str:
    if not source:
        nodes = await env.collect_data_nodes()
        for dn in nodes:
            if any(int(v["id"]) == vid for v in dn.get("volumes", [])):
                source = dn["url"]
                break
        if not source:
            return f"volume {vid}: not found"
    sstub = env.volume_stub(source)
    await sstub.call("VolumeMarkReadonly", {"volume_id": vid})
    gen_req = {"volume_id": vid, "collection": collection}
    if data_shards:
        gen_req["data_shards"] = data_shards
        gen_req["parity_shards"] = parity_shards
    r = await sstub.call("VolumeEcShardsGenerate", gen_req, timeout=3600)
    if r.get("error"):
        return f"volume {vid}: generate failed: {r['error']}"
    return await _ec_spread(
        env, vid, collection, data_shards, parity_shards, source
    )


async def _ec_spread(
    env,
    vid: int,
    collection: str,
    data_shards: int,
    parity_shards: int,
    source: str,
) -> str:
    """Spread freshly-generated shards, mount them, drop the source volume
    (the tail of ref command_ec_encode.go:110-135)."""
    sstub = env.volume_stub(source)
    total = (data_shards + parity_shards) or TOTAL_SHARDS_COUNT
    ec_nodes = await _collect_ec_nodes(env)
    assignment = plan_balanced_spread(
        ec_nodes, vid, list(range(total)), source
    )
    for target, shard_ids in assignment.items():
        tstub = env.volume_stub(target)
        if target != source:
            r = await tstub.call(
                "VolumeEcShardsCopy",
                {
                    "volume_id": vid,
                    "collection": collection,
                    "shard_ids": shard_ids,
                    "copy_ecx_file": True,
                    "source_data_node": source,
                },
                timeout=3600,
            )
            if r.get("error"):
                return f"volume {vid}: copy to {target} failed: {r['error']}"
        r = await tstub.call(
            "VolumeEcShardsMount",
            {"volume_id": vid, "collection": collection, "shard_ids": shard_ids},
        )
        if r.get("error"):
            return f"volume {vid}: mount on {target} failed: {r['error']}"

    # drop the source volume + its non-assigned shard files. Delete WHILE
    # mounted (keep_ec_files spares the .vif/.heat the EC volume needs):
    # the old unmount-then-delete sequence no-op'd the delete, leaving a
    # stale .dat a later mount scan could resurrect as a writable twin
    await sstub.call(
        "VolumeDelete", {"volume_id": vid, "keep_ec_files": True}
    )
    own = assignment.get(source, [])
    await sstub.call(
        "VolumeEcShardsDelete",
        {
            "volume_id": vid,
            "collection": collection,
            "shard_ids": [i for i in range(total) if i not in own],
        },
    )
    spread = {t: s for t, s in assignment.items()}
    return f"volume {vid}: encoded, spread {spread}"


@command("ec.decode")
async def cmd_ec_decode(env, argv) -> str:
    """Collect all data shards to one node and convert back to a volume
    (ref command_ec_decode.go:75-148)."""
    env.confirm_is_locked()
    flags = _parse_flags(argv)
    vid = int(flags["volumeId"])
    collection = flags.get("collection", "")
    ec_nodes = [n for n in await _collect_ec_nodes(env) if vid in n.shards]
    if not ec_nodes:
        return f"ec volume {vid} not found"
    k, m = await _ec_geometry(
        env, vid, collection, [n.url for n in ec_nodes]
    )
    target = max(ec_nodes, key=lambda n: n.shards[vid].count())
    have = set(target.shards[vid].shard_ids())
    tstub = env.volume_stub(target.url)
    for n in ec_nodes:
        if n.url == target.url:
            continue
        missing_here = [
            s for s in n.shards[vid].shard_ids() if s not in have
        ]
        if not missing_here:
            continue
        r = await tstub.call(
            "VolumeEcShardsCopy",
            {
                "volume_id": vid,
                "collection": collection,
                "shard_ids": missing_here,
                "copy_ecx_file": False,
                "source_data_node": n.url,
            },
            timeout=3600,
        )
        if r.get("error"):
            return f"copy shards {missing_here} from {n.url}: {r['error']}"
        have.update(missing_here)
    if len([s for s in have if s < k]) < k:
        # rebuild missing data shards locally from parity
        r = await tstub.call(
            "VolumeEcShardsRebuild",
            {"volume_id": vid, "collection": collection},
            timeout=3600,
        )
        if r.get("error"):
            return f"rebuild for decode failed: {r['error']}"
    r = await tstub.call(
        "VolumeEcShardsToVolume",
        {"volume_id": vid, "collection": collection},
        timeout=3600,
    )
    if r.get("error"):
        return f"decode failed: {r['error']}"
    # unmount ec shards everywhere, mount the volume
    for n in ec_nodes:
        nstub = env.volume_stub(n.url)
        await nstub.call(
            "VolumeEcShardsUnmount",
            {"volume_id": vid, "shard_ids": n.shards[vid].shard_ids()},
        )
        await nstub.call(
            "VolumeEcShardsDelete",
            {"volume_id": vid, "collection": collection,
             "shard_ids": list(range(k + m))},
        )
    await tstub.call("VolumeMount", {"volume_id": vid})
    return f"ec volume {vid} decoded back to a normal volume on {target.url}"


@command("ec.rebuild")
async def cmd_ec_rebuild(env, argv) -> str:
    """Rebuild missing shards of damaged EC volumes
    (ref command_ec_rebuild.go:97-244).

    Survivor pulls happen per volume as in the reference, but the rebuild
    RPCs are grouped per rebuilder node into VolumeEcShardsRebuildBatch so
    a fleet-wide repair (every volume that lost the same node's shards)
    decodes through shared wide batches server-side instead of one RPC and
    one dispatch per volume (our extension; per-volume fallback kept for
    servers without the batch RPC)."""
    env.confirm_is_locked()
    flags = _parse_flags(argv)
    collection = flags.get("collection", "")
    ec_nodes = await _collect_ec_nodes(env)
    by_vid: dict[int, ShardBits] = defaultdict(ShardBits)
    for n in ec_nodes:
        for vid, bits in n.shards.items():
            by_vid[vid] = by_vid[vid].plus(bits)
    results = []
    plans = []  # (vid, rebuilder, local bits after pulls)
    for vid, bits in sorted(by_vid.items()):
        holders = [n.url for n in ec_nodes if vid in n.shards]
        k, m = await _ec_geometry(env, vid, collection, holders)
        missing = [i for i in range(k + m) if not bits.has(i)]
        if not missing:
            continue
        if bits.count() < k:
            results.append(f"volume {vid}: unrepairable ({bits.count()} shards)")
            continue
        rebuilder = max(ec_nodes, key=lambda n: n.free_slots)
        rstub = env.volume_stub(rebuilder.url)
        local = rebuilder.shards.get(vid, ShardBits())
        # pull every survivor shard the rebuilder lacks; a copy failure
        # skips THIS volume only — the other damaged volumes still rebuild
        copy_error = None
        for n in ec_nodes:
            if n.url == rebuilder.url:
                continue
            pull = [
                s
                for s in n.shards.get(vid, ShardBits()).shard_ids()
                if not local.has(s)
            ]
            if not pull:
                continue
            r = await rstub.call(
                "VolumeEcShardsCopy",
                {
                    "volume_id": vid,
                    "collection": collection,
                    "shard_ids": pull,
                    "copy_ecx_file": True,
                    "source_data_node": n.url,
                },
                timeout=3600,
            )
            if r.get("error"):
                copy_error = r["error"]
                break
            for s in pull:
                local = local.add(s)
        if copy_error is not None:
            results.append(f"volume {vid}: copy for rebuild: {copy_error}")
            continue
        plans.append((vid, rebuilder, local))

    # one batched rebuild RPC per rebuilder node
    by_rebuilder: dict[str, list] = defaultdict(list)
    for plan in plans:
        by_rebuilder[plan[1].url].append(plan)
    for url, group in by_rebuilder.items():
        rstub = env.volume_stub(url)
        vids = [vid for vid, _n, _l in group]
        per_vid: dict[int, dict] = {}
        try:
            r = await rstub.call(
                "VolumeEcShardsRebuildBatch",
                {"volume_ids": vids, "collection": collection},
                timeout=3600,
            )
        except Exception as e:  # older server without the batch RPC
            r = {"error": str(e)}
        if r.get("error"):
            # per-volume fallback
            for vid, _n, _l in group:
                per_vid[vid] = await rstub.call(
                    "VolumeEcShardsRebuild",
                    {"volume_id": vid, "collection": collection},
                    timeout=3600,
                )
        else:
            for vid in vids:
                res = r.get("results", {}).get(str(vid))
                err = r.get("errors", {}).get(str(vid))
                per_vid[vid] = res if res is not None else {
                    "error": err or "missing batch result"
                }
        for vid, rebuilder, local in group:
            rr = per_vid[vid]
            if rr.get("error"):
                results.append(f"volume {vid}: rebuild failed: {rr['error']}")
                continue
            rebuilt = rr.get("rebuilt_shard_ids", [])
            await rstub.call(
                "VolumeEcShardsMount",
                {"volume_id": vid, "collection": collection,
                 "shard_ids": rebuilt},
            )
            # drop the extra survivor copies the rebuilder pulled
            extra = [
                s for s in local.shard_ids()
                if s not in rebuilt
                and not rebuilder.shards.get(vid, ShardBits()).has(s)
            ]
            if extra:
                await rstub.call(
                    "VolumeEcShardsDelete",
                    {"volume_id": vid, "collection": collection,
                     "shard_ids": extra},
                )
            results.append(
                f"volume {vid}: rebuilt shards {rebuilt} on {rebuilder.url}"
            )
    return "\n".join(results) or "no damaged ec volumes"


@command("volume.scrub")
async def cmd_volume_scrub(env, argv) -> str:
    """Force a scrub pass: volume.scrub [-volumeId N] [-node host:port].
    Every targeted server re-verifies needle CRCs, index extents and EC
    parity (rate-shaped by its SEAWEEDFS_TPU_SCRUB_MBPS), applies the
    quarantine policy, and reports findings (our extension; see
    docs/robustness.md "Anti-entropy plane")."""
    flags = _parse_flags(argv)
    vid = int(flags.get("volumeId", 0) or 0)
    node = flags.get("node", "")
    lines = []
    for dn in await env.collect_data_nodes():
        if node and dn["url"] != node:
            continue
        if vid and not (
            any(int(v["id"]) == vid for v in dn.get("volumes", []))
            or any(int(m["id"]) == vid for m in dn.get("ec_shards", []))
        ):
            continue
        try:
            r = await env.volume_stub(dn["url"]).call(
                "VolumeScrub",
                {"volume_id": vid, "include_ec": True},
                timeout=3600,
            )
        except Exception as e:
            lines.append(f"{dn['url']}: scrub failed: {e}")
            continue
        if r.get("error"):
            lines.append(f"{dn['url']}: scrub failed: {r['error']}")
            continue
        for vr in r.get("volumes", []):
            lines.append(
                f"{dn['url']} volume {vr['volume_id']}: "
                f"{vr['scanned']} records / {vr['bytes']} bytes verified, "
                f"{len(vr['corruptions'])} corruption(s)"
                + ("" if vr.get("completed", True) else " (partial pass)")
            )
            for key, kind, detail in vr["corruptions"]:
                lines.append(f"  CORRUPT key {int(key):#x}: {kind} ({detail})")
        for er in r.get("ec_volumes", []):
            if er.get("skipped"):
                lines.append(
                    f"{dn['url']} ec volume {er['volume_id']}: "
                    f"skipped ({er['skipped']})"
                )
                continue
            lines.append(
                f"{dn['url']} ec volume {er['volume_id']}: "
                f"{er['bytes']} bytes parity-verified, "
                f"corrupt shards {er['corrupt_shards']}"
            )
        for q in r.get("quarantined", []):
            what = (
                f"shard {q['shard_id']}" if "shard_id" in q else "volume"
            )
            lines.append(
                f"{dn['url']}: QUARANTINED {what} of volume "
                f"{q['volume_id']} (repair scheduler will pick it up)"
            )
    return "\n".join(lines) or "nothing to scrub"


@command("ec.repair.status")
async def cmd_ec_repair_status(env, argv) -> str:
    """Repair-plane status: ec.repair.status [-run]. Shows the master's
    prioritized repair queue (fewest-survivors-first), silent nodes, and
    recent dispatch outcomes; -run forces one scan+dispatch round."""
    flags = _parse_flags(argv)
    req = {"run": True} if "run" in flags else {}
    r = await env.master_stub.call("RepairStatus", req, timeout=3600)
    if r.get("error"):
        return f"repair status failed: {r['error']}"
    lines = [
        f"auto_repair: {'on' if r.get('auto_repair') else 'off'} "
        f"(grace {r.get('grace_seconds')}s) · "
        f"queue depth: {r.get('queue_depth', 0)} · "
        f"live nodes: {len(r.get('live_nodes', []))}"
    ]
    if r.get("silent_nodes"):
        lines.append("silent nodes: " + ", ".join(r["silent_nodes"]))
    for t in r.get("queue", []):
        lines.append(
            f"  queued {t['kind']} volume {t['volume_id']} "
            f"(survivors {t['survivors']}, attempts {t['attempts']})"
        )
    for t in r.get("recent", []):
        outcome = (
            f"ERROR: {t['error']}" if t.get("error") else "repaired"
        )
        lines.append(f"  recent {t['kind']} volume {t['volume_id']}: {outcome}")
    if "ran" in r:
        ran = r["ran"]
        lines.append(
            f"ran one round: dispatched {len(ran.get('dispatched', []))}, "
            f"queue depth now {ran.get('queue_depth', 0)}"
        )
    return "\n".join(lines)


@command("geo.status")
async def cmd_geo_status(env, argv) -> str:
    """Geo-plane status: geo.status [-run] [-filer host:port].

    Master side: DC/rack placement-policy violations (replica spread +
    EC failure domains) and the queued placement repair moves; -run
    forces one anti-entropy scan first. Filer side (-filer, or the
    env's sticky filer): the second-site replication tail — cursor,
    lag p99, applied/skipped/retried counters, full-resync flag."""
    flags = _parse_flags(argv)
    req = {"run": True} if "run" in flags else {}
    r = await env.master_stub.call("PlacementStatus", req, timeout=3600)
    if r.get("error"):
        return f"placement status failed: {r['error']}"
    by_dc: dict[str, int] = defaultdict(int)
    for n in r.get("nodes", []):
        by_dc[n.get("dc", "")] += 1
    lines = [
        "placement: "
        + (
            ", ".join(
                f"{dc or '(unlabeled)'}: {cnt} node(s)"
                for dc, cnt in sorted(by_dc.items())
            )
            or "no live nodes"
        )
    ]
    viols = r.get("violations", [])
    lines.append(f"policy violations: {len(viols)}")
    for v in viols:
        what = (
            f"volume {v['volume_id']} replication {v.get('replication')}"
            if v["kind"] == "replica_spread"
            else f"ec volume {v['volume_id']} domain {v.get('domain')} "
            f"holds {v.get('shards_in_domain')} shards "
            f"(parity {v.get('parity_shards')})"
        )
        lines.append(f"  {v['kind']}: {what} -> {v.get('repair', 'n/a')}")
    moves = r.get("queued_moves", [])
    if moves:
        lines.append(f"queued placement moves: {len(moves)}")
        for t in moves:
            lines.append(
                f"  {t['kind']} volume {t['volume_id']} -> {t['target']}"
                f" (attempts {t['attempts']})"
            )
    filer = flags.get("filer", "") or env.filer
    if filer:
        from ..pb import grpc_address
        from ..pb.rpc import Stub

        try:
            g = await Stub(grpc_address(filer), "filer").call(
                "GeoStatus", {}, timeout=10.0
            )
        except Exception as e:
            lines.append(f"filer {filer}: GeoStatus failed: {e}")
            return "\n".join(lines)
        if not g.get("configured"):
            lines.append(
                f"filer {filer}: geo replication not configured"
                + (
                    f" (dc {g['data_center']})"
                    if g.get("data_center")
                    else ""
                )
            )
        else:
            lines.append(
                f"filer {filer} (dc {g.get('data_center') or '?'}) <- "
                f"{g.get('source')}: "
                + ("connected" if g.get("connected") else "DISCONNECTED")
            )
            lines.append(
                f"  cursor {g.get('cursor_ns')} · lag p99 "
                f"{g.get('lag_p99_seconds')}s (last "
                f"{g.get('last_lag_seconds')}s) · applied "
                f"{g.get('applied')} · skipped {g.get('skipped')} · "
                f"retried {g.get('retried')}"
            )
            if g.get("resync_required"):
                lines.append(
                    "  FULL RESYNC REQUIRED: cursor behind primary "
                    f"retention (trimmed through "
                    f"{g.get('trimmed_through')})"
                )
    return "\n".join(lines)


@command("geo.resync")
async def cmd_geo_resync(env, argv) -> str:
    """Re-seed a second-site filer from its primary: geo.resync
    [-filer host:port]. Clears a geo.status 'FULL RESYNC REQUIRED'
    halt by walking the primary namespace through the idempotent
    stamped-upsert path (unchanged entries skip without re-shipping
    bytes), pruning peer entries the primary no longer has, and
    resuming the tail from a pre-walk watermark. Safe to re-run."""
    flags = _parse_flags(argv)
    filer = flags.get("filer", "") or env.filer
    if not filer:
        return "geo.resync needs -filer host:port (or a sticky filer)"
    from ..pb import grpc_address
    from ..pb.rpc import Stub

    r = await Stub(grpc_address(filer), "filer").call(
        "GeoResync", {}, timeout=3600
    )
    if r.get("error"):
        return f"geo.resync on {filer} failed: {r['error']}"
    return (
        f"filer {filer} resynced from {r.get('source')}: "
        f"{r.get('upserted')} upserted · {r.get('skipped')} unchanged · "
        f"{r.get('pruned')} pruned · cursor {r.get('cursor_ns')} · "
        f"{r.get('wall_s')}s"
    )


@command("meta.fleet.status")
async def cmd_meta_fleet_status(env, argv) -> str:
    """Metadata fleet status: meta.fleet.status [-filer host:port].
    Shows the queried member's FLEETMAP view (epoch, every member's
    directory range, pending move/cleanup), its write-gate coalescing
    stats + store write rounds, and — when the member is a follower —
    the tail cursor and disclosed staleness bound."""
    flags = _parse_flags(argv)
    filer = flags.get("filer", "") or env.filer
    if not filer:
        return "meta.fleet.status needs -filer host:port (or a sticky filer)"
    from ..pb import grpc_address
    from ..pb.rpc import Stub

    r = await Stub(grpc_address(filer), "filer").call(
        "FleetStatus", {}, timeout=10.0
    )
    if r.get("error"):
        return f"meta.fleet.status on {filer} failed: {r['error']}"
    lines = [f"filer {r.get('address', filer)}:"]
    fleet = r.get("fleet")
    if not r.get("configured"):
        lines.append("  fleet: not a fleet member")
    elif fleet:
        m = fleet.get("map", {})
        lines.append(
            f"  fleet epoch {fleet.get('epoch')} · "
            f"{fleet.get('members')} member(s) · self range "
            f"[{fleet['range'][0] or '-inf'}, {fleet['range'][1] or '+inf'})"
        )
        bounds = m.get("bounds", [])
        for i, addr in enumerate(m.get("addresses", [])):
            lo = bounds[i - 1] if i > 0 else ""
            hi = bounds[i] if i < len(bounds) else ""
            marker = " (self)" if addr == fleet.get("self") else ""
            lines.append(
                f"    {addr}: [{lo or '-inf'}, {hi or '+inf'}){marker}"
            )
        if m.get("pending_move"):
            pm = m["pending_move"]
            lines.append(
                f"  PENDING MOVE [{pm['lo']}, {pm['hi']}) "
                f"{pm['src']} -> {pm['dst']}"
            )
        if m.get("pending_cleanup"):
            pc = m["pending_cleanup"]
            lines.append(
                f"  pending cleanup [{pc['lo']}, {pc['hi']}) on {pc['src']}"
            )
        c = fleet.get("counters", {})
        lines.append(
            f"  forwarded {c.get('forwarded')} · ingested "
            f"{c.get('ingested')} · moves {c.get('moves_committed')} ok / "
            f"{c.get('moves_failed')} failed · fence waits "
            f"{c.get('fence_waits')}"
        )
    wg = r.get("write_gate")
    if wg:
        lines.append(
            f"  write gate: {wg.get('writes')} writes in "
            f"{wg.get('batches')} round(s) · coalesced "
            f"{wg.get('coalesced')} · largest batch "
            f"{wg.get('largest_batch')} · item retries "
            f"{wg.get('item_retries')}"
        )
    if "write_rounds" in r:
        lines.append(f"  store write rounds: {r['write_rounds']}")
    fo = r.get("follower")
    if fo:
        lines.append(
            f"  follower of {fo.get('source')}: "
            + ("connected" if fo.get("connected") else "DISCONNECTED")
            + f" · cursor {fo.get('cursor_ns')} · staleness bound "
            f"{fo.get('staleness_bound_s')}s · applied {fo.get('applied')}"
            f" · redirects {fo.get('redirects')}"
        )
        if fo.get("resync_required"):
            lines.append(
                "  RESYNC REQUIRED: cursor behind primary retention "
                f"(trimmed through {fo.get('trimmed_through')})"
            )
    return "\n".join(lines)


@command("ec.balance")
async def cmd_ec_balance(env, argv) -> str:
    """Dedupe + rack-aware rebalancing of EC shards
    (ref command_ec_balance.go:29-95)."""
    env.confirm_is_locked()
    flags = _parse_flags(argv)
    collection = flags.get("collection", "")
    ec_nodes = await _collect_ec_nodes(env)
    vids = sorted({vid for n in ec_nodes for vid in n.shards})
    log = []
    for vid in vids:
        for shard_id, url in plan_dedupe(ec_nodes, vid):
            stub = env.volume_stub(url)
            await stub.call(
                "VolumeEcShardsUnmount", {"volume_id": vid, "shard_ids": [shard_id]}
            )
            await stub.call(
                "VolumeEcShardsDelete",
                {"volume_id": vid, "collection": collection,
                 "shard_ids": [shard_id]},
            )
            log.append(f"volume {vid}: dropped duplicate shard {shard_id} on {url}")
        for move in plan_rack_balance(ec_nodes, vid):
            await execute_shard_move(env, move, collection)
            log.append(
                f"volume {vid}: moved shard {move.shard_id} "
                f"{move.source} -> {move.target}"
            )
    return "\n".join(log) or "balanced: no moves needed"


# ---------------- distributed tracing (ISSUE 8) ----------------
async def _trace_endpoints(env, flags) -> list[str]:
    """Servers whose /debug/traces to consult: the master plus every
    registered volume server, plus any -servers=a:p,b:p extras (filer /
    S3 gateways, which the master does not track)."""
    urls = [env.master]
    try:
        for dn in await env.collect_data_nodes():
            if dn.get("url"):
                urls.append(dn["url"])
    except Exception:
        pass
    extra = flags.get("servers", "")
    if extra:
        urls.extend(u for u in extra.split(",") if u)
    if env.filer:
        urls.append(env.filer)
    # de-dup, keep order
    seen: set = set()
    return [u for u in urls if not (u in seen or seen.add(u))]


async def _fetch_debug_traces(url: str, query: str = ""):
    import aiohttp

    timeout = aiohttp.ClientTimeout(total=10)
    async with aiohttp.ClientSession(timeout=timeout) as s:
        async with s.get(f"http://{url}/debug/traces{query}") as resp:
            if resp.status != 200:
                raise IOError(f"{url}: status {resp.status}")
            return await resp.text()


@command("trace.status")
async def cmd_trace_status(env, argv) -> str:
    """Per-server flight-recorder state: sampling rate, ring occupancy,
    admission/promotion counters. -servers=host:port,... adds filer/S3
    gateways the master does not know about."""
    import json as _json

    flags = _parse_flags(argv)
    lines = []
    for url in await _trace_endpoints(env, flags):
        try:
            st = _json.loads(await _fetch_debug_traces(url, "?status=1"))
        except Exception as e:
            lines.append(f"{url}: unreachable ({e})")
            continue
        thr = st.get("slow_threshold_ms")
        lines.append(
            f"{url} [{st.get('server', '?')}]: sample={st.get('sample')} "
            f"ring={st.get('spans_in_ring')}/{st.get('capacity')} "
            f"admitted={st.get('admitted')} "
            f"promoted(slow/flag/fault)={st.get('promoted_slow')}/"
            f"{st.get('promoted_flagged')}/{st.get('promoted_fault')} "
            f"p99_gate={'%.2fms' % thr if thr is not None else 'warming'}"
        )
    return "\n".join(lines) or "no servers"


@command("trace.dump")
async def cmd_trace_dump(env, argv) -> str:
    """Merge every server's flight-recorder ring by trace id and print
    span trees. Flags: -trace=<32-hex id> (one trace), -limit=N (newest
    N traces, default 5), -servers=host:port,... (extra filer/S3
    endpoints). In-process clusters share one ring; spans are de-duped
    by (trace, span) id."""
    import json as _json

    flags = _parse_flags(argv)
    want = flags.get("trace", "")
    limit = int(flags.get("limit", "5") or 5)
    spans: dict[tuple, dict] = {}
    errors = []
    for url in await _trace_endpoints(env, flags):
        try:
            body = await _fetch_debug_traces(url)
        except Exception as e:
            errors.append(f"# {url}: unreachable ({e})")
            continue
        for line in body.splitlines():
            if not line:
                continue
            try:
                s = _json.loads(line)
            except ValueError:
                continue
            spans.setdefault((s.get("trace"), s.get("span")), s)

    by_trace: dict[str, list] = defaultdict(list)
    for (tid, _sid), s in spans.items():
        by_trace[tid].append(s)
    if want:
        by_trace = {tid: v for tid, v in by_trace.items() if tid == want}
    # newest traces first (by earliest span start within the trace)
    ordered = sorted(
        by_trace.items(),
        key=lambda kv: min(s.get("start", 0) for s in kv[1]),
        reverse=True,
    )[:limit]

    out = list(errors)
    for tid, tspans in ordered:
        tspans.sort(key=lambda s: s.get("start", 0))
        out.append(f"trace {tid} ({len(tspans)} spans)")
        by_span = {s["span"]: s for s in tspans}

        def depth(s) -> int:
            d, seen = 0, set()
            p = s.get("parent")
            while p and p in by_span and p not in seen:
                seen.add(p)
                d += 1
                p = by_span[p].get("parent")
            return d

        for s in tspans:
            tags = s.get("tags", {})
            extras = "".join(
                f" {k}={v}" for k, v in tags.items() if k not in ("path",)
            )
            flagstr = (
                " !" + ",".join(s["flags"]) if s.get("flags") else ""
            )
            links = (
                f" links={len(s['links'])}" if s.get("links") else ""
            )
            out.append(
                f"  {'  ' * depth(s)}{s.get('name')} "
                f"{s.get('dur_us', 0):.0f}us"
                f"{extras}{links}{flagstr}"
                + (f" err={s['err']}" if s.get("err") else "")
            )
    return "\n".join(out) or "no traces recorded"


async def _fetch_debug_json(url: str, path: str) -> dict:
    import json as _json

    import aiohttp

    timeout = aiohttp.ClientTimeout(total=10)
    async with aiohttp.ClientSession(timeout=timeout) as s:
        async with s.get(f"http://{url}{path}") as resp:
            if resp.status != 200:
                raise IOError(f"{url}: status {resp.status}")
            return _json.loads(await resp.text())


@command("overload.status")
async def cmd_overload_status(env, argv) -> str:
    """The overload control plane's live state, cluster-wide: each
    server's admission gate (adaptive concurrency limit, baseline,
    inflight/queued, admitted/shed totals, pressure), open circuit
    breakers, and the shared retry-budget fill. -servers=host:port,...
    adds filer/S3 gateways the master does not know about. In-process
    clusters share one process: each gate carries a per-process unique
    `gate` id (server NAMES repeat — three in-process volume servers
    are all "volume"), so the merge de-dupes repeated reports of one
    gate without collapsing distinct same-named gates. ``-tenants``
    adds each gate's per-tenant rows (ISSUE 12): weight, admitted/shed/
    queued, quota bucket fill, and the bounded metric label the tenant
    currently maps to (top-K by heat or 'other')."""
    flags = _parse_flags(argv)
    show_tenants = flags.get("tenants") == "true"
    lines = []
    seen_gates: set = set()
    open_breakers: dict[str, dict] = {}
    budget = None
    for url in await _trace_endpoints(env, flags):
        try:
            st = await _fetch_debug_json(url, "/debug/overload")
        except Exception as e:
            lines.append(f"{url}: unreachable ({e})")
            continue
        if not st.get("admission_enabled", True):
            lines.append(f"{url}: admission disabled (SEAWEEDFS_TPU_ADMIT=0)")
        host = (st.get("addr") or url).rsplit(":", 1)[0]
        for g in st.get("gates", []):
            # gates are per-PROCESS (an in-process cluster reports the
            # same list via every port it listens on): (host, pid,
            # gate-id) identifies one — never the server NAME (three
            # in-process volume servers are all "volume" and would
            # collapse) and never counter values (same-shape servers
            # across processes would collapse)
            key = (host, st.get("pid"), g.get("gate"), g.get("server"))
            if key in seen_gates:
                continue  # same in-process gate seen via another server
            seen_gates.add(key)
            budgets = g.get("queue_budget_ms") or []
            lines.append(
                f"{g.get('server', '?')}[{url}]: limit={g.get('limit')} "
                f"(baseline={g.get('baseline_ms')}ms "
                f"+{g.get('limit_increases', 0)}/-{g.get('limit_decreases', 0)}) "
                f"inflight={g.get('inflight')} queued={g.get('queued')} "
                f"admitted={g.get('admitted_total')} shed={g.get('shed_total')} "
                f"budget_ms={budgets} pressure={g.get('pressure')}"
            )
            if show_tenants:
                for name, t in sorted(
                    (g.get("tenants") or {}).items()
                ):
                    quota = t.get("quota")
                    qs = (
                        f" quota[qps={quota.get('qps')} "
                        f"bps={quota.get('byte_ps')} "
                        f"req_tokens={quota.get('request_tokens')} "
                        f"byte_tokens={quota.get('byte_tokens')}]"
                        if quota
                        else ""
                    )
                    lines.append(
                        f"  tenant {name}: weight={t.get('weight')} "
                        f"admitted={t.get('admitted')} "
                        f"shed={t.get('shed')} queued={t.get('queued')} "
                        f"label={t.get('label')}{qs}"
                    )
        for peer, b in (st.get("breakers") or {}).items():
            if b.get("state") != "closed" or b.get("opens"):
                open_breakers[peer] = b
        if budget is None:
            budget = st.get("retry_budget")
    for peer, b in sorted(open_breakers.items()):
        lines.append(
            f"breaker {peer}: {b.get('state')} (opened {b.get('opens')}x)"
        )
    if budget is not None:
        lines.append(
            f"retry budget: {budget.get('tokens')}/{budget.get('max_tokens')} "
            f"tokens (refill ratio {budget.get('ratio')})"
        )
    return "\n".join(lines) or "no servers"

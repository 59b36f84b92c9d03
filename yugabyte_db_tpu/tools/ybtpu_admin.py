"""yb-admin-style cluster admin CLI.

Reference: src/yb/tools/yb-admin_cli.cc — snapshot/restore, tablet moves,
compactions, tserver listing. Usage:

    python -m yugabyte_db_tpu.tools.ybtpu_admin --master HOST:PORT <cmd> ...

Commands: list_tables, list_tservers, list_tablets TABLE,
create_snapshot TABLE, restore_snapshot SNAPSHOT_ID NEW_TABLE,
create_snapshot_schedule TABLE INTERVAL_S KEEP,
list_snapshot_schedules TABLE,
restore_snapshot_schedule SCHEDULE_ID AT_UNIX_TS NEW_TABLE,
setup_xcluster SOURCE_HOST:PORT TABLE, drop_xcluster TABLE,
list_xcluster,
split_tablet TABLET_ID, move_replica TABLET_ID FROM TO, balance_tick,
blacklist TS_UUID, compact_table TABLE, flush_table TABLE,
create_tablespace NAME ZONE:MIN[,ZONE:MIN...] [PREF[,PREF...]],
set_placement_info ZONE:MIN[,...] [PREF[,...]], list_tablespaces,
drop_tablespace NAME
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys

from ..client import YBClient
from ..docdb.wire import read_request_to_wire

#: deadline of the flush/compact RPCs (compact_table, flush_table)
MAINTENANCE_RPC_TIMEOUT_S = 3600.0


# minimum positional args per command (commands absent here take 0)
_MIN_ARGS = {
    "list_tablets": 1, "create_snapshot": 1, "restore_snapshot": 2,
    "create_snapshot_schedule": 3, "restore_snapshot_schedule": 3,
    "split_tablet": 1, "move_replica": 3, "blacklist": 1,
    "setup_xcluster": 2, "drop_xcluster": 1,
    "compact_table": 1, "flush_table": 1,
    "create_tablespace": 2, "set_placement_info": 1,
    "drop_tablespace": 1,
}


async def run_command(args) -> int:
    host, port = args.master.rsplit(":", 1)
    client = YBClient((host, int(port)))
    m = client.messenger
    maddr = client.master_addr
    cmd = args.command
    a = args.args
    if len(a) < _MIN_ARGS.get(cmd, 0):
        print(f"error: {cmd} takes at least {_MIN_ARGS[cmd]} argument(s) "
              f"(see module docstring)", file=sys.stderr)
        return 1
    if cmd == "list_tables":
        print(json.dumps(await client.list_tables(), indent=1))
    elif cmd == "list_tservers":
        r = await m.call(maddr, "master", "list_tservers", {})
        print(json.dumps(r, indent=1))
    elif cmd == "list_tablets":
        ct = await client._table(a[0])
        for l in ct.locations:
            print(l.tablet_id, l.partition, "leader:", l.leader,
                  "replicas:", [u for u, _ in l.replicas])
    elif cmd == "create_snapshot":
        r = await m.call(maddr, "master", "create_snapshot",
                         {"table": a[0]}, timeout=120.0)
        print(json.dumps(r))
    elif cmd == "restore_snapshot":
        r = await m.call(maddr, "master", "restore_snapshot",
                         {"snapshot_id": a[0], "new_name": a[1]},
                         timeout=120.0)
        print(json.dumps(r))
    elif cmd == "create_snapshot_schedule":
        r = await m.call(maddr, "master", "create_snapshot_schedule",
                         {"table": a[0], "interval_s": float(a[1]),
                          "keep": int(a[2])}, timeout=120.0)
        print(json.dumps(r))
    elif cmd == "list_snapshot_schedules":
        r = await m.call(maddr, "master", "list_snapshot_schedules",
                         {"table": a[0]} if a else {}, timeout=120.0)
        print(json.dumps(r, indent=1))
    elif cmd == "restore_snapshot_schedule":
        r = await m.call(maddr, "master", "restore_snapshot_schedule",
                         {"schedule_id": a[0], "at": float(a[1]),
                          "new_name": a[2]}, timeout=120.0)
        print(json.dumps(r))
    elif cmd == "setup_xcluster":
        if ":" not in a[0] or not a[0].rsplit(":", 1)[1].isdigit():
            print(f"error: setup_xcluster needs SOURCE_HOST:PORT, "
                  f"got {a[0]!r}", file=sys.stderr)
            return 1
        shost, sport = a[0].rsplit(":", 1)
        r = await m.call(maddr, "master", "setup_xcluster_replication",
                         {"source_master": [shost, int(sport)],
                          "table": a[1]}, timeout=120.0)
        print(json.dumps(r))
    elif cmd == "drop_xcluster":
        r = await m.call(maddr, "master", "drop_xcluster_replication",
                         {"table": a[0]}, timeout=120.0)
        print(json.dumps(r))
    elif cmd == "list_xcluster":
        r = await m.call(maddr, "master", "list_xcluster_replication",
                         {}, timeout=120.0)
        print(json.dumps(r, indent=1))
    elif cmd == "split_tablet":
        r = await m.call(maddr, "master", "split_tablet",
                         {"tablet_id": a[0]}, timeout=120.0)
        print(json.dumps(r))
    elif cmd == "move_replica":
        r = await m.call(maddr, "master", "move_replica",
                         {"tablet_id": a[0], "from": a[1], "to": a[2]},
                         timeout=120.0)
        print(json.dumps(r))
    elif cmd == "balance_tick":
        r = await m.call(maddr, "master", "balance_tick", {}, timeout=120.0)
        print(json.dumps(r))
    elif cmd == "blacklist":
        r = await m.call(maddr, "master", "blacklist", {"ts_uuid": a[0]})
        print(json.dumps(r))
    elif cmd in ("create_tablespace", "set_placement_info"):
        # args: [NAME] ZONE:MIN[,ZONE:MIN...] [PREF_ZONE[,PREF_ZONE...]]
        pos = 0 if cmd == "set_placement_info" else 1
        placement = [{"zone": z, "min_replicas": int(n)}
                     for z, n in (b.split(":") for b in
                                  a[pos].split(",") if b)]
        pref = a[pos + 1].split(",") if len(a) > pos + 1 else []
        payload = {"placement": placement, "preferred_zones": pref}
        if cmd == "create_tablespace":
            payload["name"] = a[0]
        r = await m.call(maddr, "master", cmd, payload, timeout=30.0)
        print(json.dumps(r))
    elif cmd == "list_tablespaces":
        r = await m.call(maddr, "master", "list_tablespaces", {},
                         timeout=30.0)
        print(json.dumps(r, indent=1))
    elif cmd == "drop_tablespace":
        r = await m.call(maddr, "master", "drop_tablespace",
                         {"name": a[0]}, timeout=30.0)
        print(json.dumps(r))
    elif cmd in ("compact_table", "flush_table"):
        method = "compact" if cmd == "compact_table" else "flush"
        ct = await client._table(a[0])
        for l in ct.locations:
            # a compaction of a real-size tablet outlasts the default
            # 10 s RPC deadline, and a retry would start a second one
            r = await client._call_leader(ct, l.tablet_id, method,
                                          {"tablet_id": l.tablet_id},
                                          timeout=MAINTENANCE_RPC_TIMEOUT_S)
            print(l.tablet_id, r)
    else:
        print(f"unknown command {cmd}", file=sys.stderr)
        return 1
    await m.shutdown()
    return 0


def main(argv=None):
    p = argparse.ArgumentParser(prog="ybtpu-admin")
    p.add_argument("--master", required=True, help="master host:port")
    p.add_argument("command")
    p.add_argument("args", nargs="*")
    args = p.parse_args(argv)
    from ..rpc.messenger import RpcError
    try:
        return asyncio.run(run_command(args))
    except RpcError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    except BrokenPipeError:
        return 0   # output piped into a closed reader (e.g. | head)


if __name__ == "__main__":
    sys.exit(main())

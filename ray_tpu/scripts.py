"""Operator CLI: ``ray-tpu start|status|list|submit|logs|serve|memory|
timeline|microbenchmark``.

Reference analogue: `python/ray/scripts/scripts.py`. Three ways to reach
a runtime:

- ``--address host:port`` attaches to a LIVE session's control-plane RPC
  (``ray-tpu start`` serves it; status/list/logs --follow work remotely).
- ``--snapshot path`` reads a persisted control-plane snapshot from a
  possibly-dead runtime.
- neither: commands run against a fresh in-process runtime (``submit``
  supervises the entrypoint as a job; ``serve run`` deploys and blocks;
  ``start`` boots the long-lived session: snapshots, metrics, RPC, log
  publishing).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Any, Dict, List


def _print_rows(rows: List[Dict[str, Any]], columns: List[str]) -> None:
    if not rows:
        print("(none)")
        return
    widths = {c: max(len(c), *(len(str(r.get(c, ""))) for r in rows)) for c in columns}
    print("  ".join(c.upper().ljust(widths[c]) for c in columns))
    for r in rows:
        print("  ".join(str(r.get(c, "")).ljust(widths[c]) for c in columns))


def _sum_resources(nodes) -> Dict[str, float]:
    acc: Dict[str, float] = {}
    for n in nodes:
        for k, v in n.resources_total.items():
            acc[k] = acc.get(k, 0.0) + v
    return acc


def _remote_cp(address: str):
    from ray_tpu.core.rpc import RemoteControlPlane

    return RemoteControlPlane(address)


def cmd_status(args) -> int:
    if args.address:
        cp = _remote_cp(args.address)
        nodes = cp.alive_nodes()
        actors = cp.list_actors()
        jobs = cp.list_jobs()
        print(json.dumps({
            "address": args.address,
            "nodes_alive": len(nodes),
            "actors": len(actors),
            "jobs": len(jobs),
            "cluster_resources": _sum_resources(nodes),
        }, indent=2, default=str))
        cp.close()
        return 0
    if args.snapshot:
        from ray_tpu.core import persistence

        snap = persistence.load_snapshot(args.snapshot)
        age = time.time() - snap.get("time", 0)
        print(f"snapshot: {args.snapshot} (written {age:.0f}s ago)")
        print(f"  kv entries:    {len(snap.get('kv', {}))}")
        print(f"  jobs:          {len(snap.get('jobs', {}))}")
        print(f"  named actors:  {sorted(snap.get('named_actors', {}))}")
        print(f"  nodes:         {len(snap.get('nodes', []))}")
        print(f"  objects:       {len(snap.get('objects', []))}")
        return 0
    import ray_tpu
    from ray_tpu.util import state

    ray_tpu.init()
    s = state.summary()
    print(json.dumps(s, indent=2, default=str))
    return 0


def cmd_list(args) -> int:
    if args.address:
        cp = _remote_cp(args.address)
        if args.what == "nodes":
            rows = [{"node_id": n.node_id.hex()[:16], "state": n.state.value,
                     "resources": n.resources_total} for n in cp.all_nodes()]
            _print_rows(rows, ["node_id", "state", "resources"])
        elif args.what == "actors":
            rows = [{"actor_id": a.actor_id.hex()[:16], "name": a.name,
                     "class": a.class_name, "state": a.state.value}
                    for a in cp.list_actors()]
            _print_rows(rows, ["actor_id", "name", "class", "state"])
        elif args.what == "jobs":
            rows = [{"job_id": j.hex()[:16], **{k: v for k, v in m.items()
                     if isinstance(v, (str, int, float))}}
                    for j, m in cp.list_jobs().items()]
            _print_rows(rows, ["job_id", "state"])
        else:
            print("objects are node-local; not served over the control plane")
        cp.close()
        return 0
    if args.snapshot:
        from ray_tpu.core import persistence

        snap = persistence.load_snapshot(args.snapshot)
        if args.what == "jobs":
            rows = [{"job_id": j, **m} for j, m in snap.get("jobs", {}).items()]
            _print_rows(rows, ["job_id", "state", "death_cause"])
        elif args.what == "actors":
            rows = [
                {"name": n, "class": e.get("class_name", "")}
                for n, e in snap.get("named_actors", {}).items()
            ]
            _print_rows(rows, ["name", "class"])
        elif args.what == "nodes":
            _print_rows(snap.get("nodes", []), ["node_id", "state", "resources"])
        else:
            print("\n".join(snap.get("objects", [])) or "(none)")
        return 0
    import ray_tpu
    from ray_tpu.util import state

    ray_tpu.init()
    fn = {
        "nodes": state.list_nodes,
        "actors": state.list_actors,
        "jobs": state.list_jobs,
        "objects": state.list_objects,
    }[args.what]
    rows = fn(limit=args.limit)
    cols = list(rows[0].keys()) if rows else []
    _print_rows(rows, cols)
    return 0


def cmd_submit(args) -> int:
    import ray_tpu
    from ray_tpu.job_submission import JobSubmissionClient

    import shlex

    ray_tpu.init()
    client = JobSubmissionClient()
    entrypoint = shlex.join(args.entrypoint)  # preserve argv quoting
    job_id = client.submit_job(entrypoint=entrypoint)
    print(f"job {job_id} submitted: {entrypoint}", file=sys.stderr)
    status = client.wait_until_finish(job_id, timeout_s=args.timeout)
    logs = client.get_job_logs(job_id)
    if logs:
        sys.stdout.write(logs)
    print(f"job {job_id}: {status}", file=sys.stderr)
    return 0 if status == "SUCCEEDED" else 1


def cmd_start(args) -> int:
    import ray_tpu
    from ray_tpu.util import state

    if getattr(args, "address", None):
        # worker mode: join the head and serve dispatched tasks until the
        # head stops us (or dies)
        system_config = (
            {"node_host": args.node_host} if args.node_host else None
        )
        worker = ray_tpu.init(
            address=args.address, num_cpus=args.num_cpus,
            num_tpus=args.num_tpus, system_config=system_config,
        )
        print(f"joined {args.address} as node {worker.node_id.hex()[:8]} "
              f"({worker.info.resources_total})")
        try:
            worker.wait()
        except KeyboardInterrupt:
            print("shutting down worker")
            worker.shutdown()
        return 0

    system_config: Dict[str, Any] = {"control_plane_rpc_port": args.rpc_port}
    if args.snapshot:
        system_config["control_plane_snapshot_path"] = args.snapshot
    rt = ray_tpu.init(
        system_config=system_config,
        resume_from=args.resume_from,
    )
    port = state.start_metrics_server(port=args.metrics_port)
    print(f"ray-tpu session up: metrics http://127.0.0.1:{port}/metrics")
    from ray_tpu.core.log_monitor import LogMonitor

    # publish session logs to the control-plane pubsub so remote shells can
    # `ray-tpu logs --follow --address …`; silent locally (sink drops)
    LogMonitor(sink=lambda record: None,
               pubsub=rt.control_plane.pubsub).start()
    cp_server = getattr(rt, "_cp_server", None)
    if cp_server is not None:
        print(f"  control-plane RPC: {cp_server.address} "
              f"(attach: ray-tpu status --address {cp_server.address})")
    res = rt.control_plane.alive_nodes()
    for n in res:
        print(f"  node {n.node_id.hex()[:8]}: {n.resources_total}")
    if args.serve_app:
        module, _, attr = args.serve_app.partition(":")
        import importlib

        from ray_tpu import serve

        app = getattr(importlib.import_module(module), attr or "app")
        serve.run(app)
        print(f"  serve app '{args.serve_app}' at port {serve.http_port()}")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        print("shutting down")
    return 0


def cmd_logs(args) -> int:
    """Session log access: list files, tail one, or follow the live stream
    of an attached session (reference: `ray logs` + the log monitor's
    driver echo)."""
    from ray_tpu.core.log_monitor import (
        LOG_CHANNEL,
        list_log_files,
        tail_log_file,
    )

    if args.follow:
        import threading

        if not args.address:
            print("logs --follow needs --address (a live session's RPC)",
                  file=sys.stderr)
            return 2
        client = _remote_cp(args.address)
        done = threading.Event()

        def on_record(record):
            pid = f" pid={record['pid']}" if record.get("pid") else ""
            print(f"({record['file']}{pid}) {record['line']}", flush=True)

        client.subscribe(LOG_CHANNEL, on_record)
        print(f"following logs from {args.address} (ctrl-c to stop)",
              file=sys.stderr)
        try:
            while not done.wait(1.0):
                pass
        except KeyboardInterrupt:
            pass
        finally:
            client.close()
        return 0

    if args.file:
        try:
            for line in tail_log_file(args.file, n=args.lines,
                                      directory=args.log_dir):
                print(line)
        except OSError as e:
            print(f"cannot read {args.file}: {e}", file=sys.stderr)
            return 1
        return 0

    files = list_log_files(args.log_dir)
    if not files:
        print("no session logs found (is a session running on this host?)")
        return 0
    _print_rows(files, ["file", "bytes", "mtime"])
    return 0


def cmd_memory(args) -> int:
    """Object-plane introspection (reference: `ray memory`), federated
    over the cluster ledger: every live object with size / location set /
    refcount / pin reason / age, top-N by size. `--group-by reason|node`
    aggregates instead; `--leaks` runs the leak sweep and prints what it
    flagged. `--snapshot` still lists object ids from a persisted
    control-plane snapshot of a dead runtime."""
    if args.snapshot:
        from ray_tpu.core import persistence

        snap = persistence.load_snapshot(args.snapshot)
        oids = snap.get("objects", [])
        print("\n".join(oids) or "(none)")
        print(f"\ntotal: {len(oids)} objects (snapshot)")
        return 0
    import ray_tpu
    from ray_tpu.core import object_ledger

    rt = ray_tpu.init()
    report = object_ledger.sweep(rt, force=True)
    body = object_ledger.collect_objects(rt, limit=max(args.limit, 10_000))
    rows = body["objects"]

    if args.leaks:
        leak_rows = [{
            "kind": l.get("kind", ""),
            "object_id": l.get("object_id", "")[:16],
            "node_id": l.get("node_id", ""),
            "size_bytes": l.get("size_bytes", 0),
            "age_s": l.get("age_s", 0.0),
            "detail": l.get("detail", ""),
        } for l in report.get("leaks", [])]
        _print_rows(leak_rows, ["kind", "object_id", "node_id",
                                "size_bytes", "age_s", "detail"])
        counts = report.get("counts", {})
        print(f"\nleaks: {sum(counts.values())} "
              + " ".join(f"{k}={v}" for k, v in sorted(counts.items())))
        return 0

    if args.group_by:
        key = {"reason": "pin_reason", "node": "node_id"}[args.group_by]
        groups: Dict[str, Dict[str, Any]] = {}
        for r in rows:
            g = groups.setdefault(str(r.get(key, "") or "(none)"),
                                  {args.group_by: str(r.get(key, "") or "(none)"),
                                   "objects": 0, "bytes": 0})
            g["objects"] += 1
            g["bytes"] += int(r.get("size_bytes", 0) or 0)
        grows = sorted(groups.values(), key=lambda g: g["bytes"], reverse=True)
        _print_rows(grows, [args.group_by, "objects", "bytes"])
    else:
        view = [{
            "object_id": r.get("object_id", "")[:16],
            "size_bytes": r.get("size_bytes", 0),
            "node_id": r.get("node_id", ""),
            "store": r.get("store", ""),
            "locations": ",".join(r.get("locations", [])) or "-",
            "refcount": r.get("refcount", 0),
            "pin_reason": r.get("pin_reason", "") or "-",
            "age_s": round(float(r.get("age_s", 0.0)), 1),
            "creator_task": r.get("creator_task", "") or "-",
        } for r in rows[:args.limit]]
        _print_rows(view, ["object_id", "size_bytes", "node_id", "store",
                           "locations", "refcount", "pin_reason", "age_s",
                           "creator_task"])
    counts = report.get("counts", {})
    print(f"\ntotal: {body['total_objects']} objects, "
          f"{body['total_bytes']} bytes across "
          f"{len(body['nodes'])} node store(s); "
          f"leaks flagged: {sum(counts.values())} (--leaks for detail)")
    return 0


def cmd_serve_run(args) -> int:
    """Run serve apps in the foreground from a YAML/JSON config or an
    import path (reference: `serve run` / `serve deploy` config shape)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.schema import ServeConfigSchema, apply

    ray_tpu.init()
    target = args.config_or_import_path
    if target.endswith((".yaml", ".yml", ".json")):
        config = ServeConfigSchema.load(target)
        if args.http_port:
            config.http_port = args.http_port
        status = apply(config)
    else:
        import importlib

        module, _, attr = target.partition(":")
        app = getattr(importlib.import_module(module), attr or "app")
        serve.run(app, http_port=args.http_port)
        status = serve.status()
    if getattr(args, "grpc_port", None) is not None:
        port = serve.start_grpc(port=args.grpc_port)
        print(f"gRPC ingress on 127.0.0.1:{port}", file=sys.stderr)
    print(json.dumps(status, indent=2, default=str))
    print(f"serving on http://127.0.0.1:{serve.http_port()} (ctrl-c to stop)",
          file=sys.stderr)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        serve.shutdown()
    return 0


def cmd_microbenchmark(args) -> int:
    from ray_tpu.microbenchmark import run_all

    run_all()
    return 0


def cmd_timeline(args) -> int:
    if args.events_dir:
        # merge per-session dumps (written on runtime shutdown when
        # system_config event_log_dir is set) into one Perfetto trace
        import glob
        import os

        events: List[Dict[str, Any]] = []
        files = sorted(glob.glob(os.path.join(args.events_dir, "timeline_*.json")))
        for f in files:
            try:
                events.extend(json.load(open(f)).get("traceEvents", []))
            except Exception as e:
                print(f"skipping {f}: {e}", file=sys.stderr)
        with open(args.out, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
        print(f"merged {len(events)} events from {len(files)} session(s) "
              f"into {args.out} (open in Perfetto)")
        return 0
    import ray_tpu

    n = ray_tpu.timeline(args.out)
    if n == 0:
        print(
            "no events in this process. Task events live in the runtime "
            "process; set system_config={'event_log_dir': DIR} there (dumped "
            "on shutdown) and run: ray-tpu timeline --events-dir DIR",
            file=sys.stderr,
        )
        return 1
    print(f"wrote {n} events to {args.out} (open in Perfetto)")
    return 0


def cmd_health(args) -> int:
    import ray_tpu

    ray_tpu.status(address=args.address or "")
    return 0


def cmd_profile(args) -> int:
    """Stack-dump / CPU-profile any process in the cluster (profiling
    plane, util/profiler.py). `--address` reads a running head's
    dashboard over HTTP; without it the in-process runtime is used."""
    node = args.node or ""
    if node in ("head", "local", "-"):
        node = ""
    pid = int(args.pid or 0)
    duration = args.duration
    if args.address:
        from urllib.request import urlopen

        url = args.address if "://" in args.address else f"http://{args.address}"
        path = f"{url.rstrip('/')}/api/v0/profile/{node or 'head'}"
        if pid:
            path += f"/{pid}"
        q = [f"kind={args.kind}"]
        if duration is not None:
            q.append(f"duration={duration}")
        if args.hz is not None:
            q.append(f"hz={args.hz}")
        path += "?" + "&".join(q)
        with urlopen(path, timeout=(duration or 5.0) + 30.0) as r:
            out = json.loads(r.read().decode())
    else:
        import time as _time

        from . import api
        from .core import core_worker
        from .core.cross_host import HeadService

        api._auto_init()
        svc = HeadService(core_worker.get_runtime())
        if args.kind == "jax":
            out = svc.profile_start(node=node, pid=pid,
                                    duration_s=duration or 5.0, kind="jax")
        elif args.kind == "cpu":
            svc.profile_start(node=node, pid=pid, duration_s=duration or 2.0,
                              hz=args.hz, kind="cpu")
            _time.sleep(min(duration or 2.0, 60.0))
            out = svc.profile_fetch(node=node, pid=pid, kind="cpu")
        else:
            out = svc.profile_fetch(node=node, pid=pid, kind=args.kind)
    if isinstance(out.get("text"), str):
        print(out["text"])
    elif isinstance(out.get("collapsed"), dict):
        for stack, count in sorted(out["collapsed"].items(),
                                   key=lambda kv: (-kv[1], kv[0])):
            print(f"{stack} {count}")
    elif isinstance(out.get("collapsed"), str):
        print(out["collapsed"])
    else:
        print(json.dumps(out, indent=2, default=str))
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="ray-tpu", description=__doc__.split("\n")[0])
    sub = p.add_subparsers(dest="cmd", required=True)

    ps = sub.add_parser("status", help="runtime or snapshot summary")
    ps.add_argument("--snapshot", help="read a control-plane snapshot file")
    ps.add_argument("--address", help="attach to a live runtime's control-plane "
                    "RPC (system_config control_plane_rpc_port)")
    ps.set_defaults(fn=cmd_status)

    pl = sub.add_parser("list", help="list nodes/actors/jobs/objects")
    pl.add_argument("what", choices=["nodes", "actors", "jobs", "objects"])
    pl.add_argument("--snapshot", help="read a control-plane snapshot file")
    pl.add_argument("--address", help="attach to a live runtime's control-plane RPC")
    pl.add_argument("--limit", type=int, default=100)
    pl.set_defaults(fn=cmd_list)

    pj = sub.add_parser("submit", help="run an entrypoint as a supervised job")
    pj.add_argument("--timeout", type=float, default=3600.0)
    pj.add_argument("entrypoint", nargs=argparse.REMAINDER,
                    help="command to run, e.g.: -- python train.py")
    pj.set_defaults(fn=cmd_submit)

    pst = sub.add_parser("start", help="long-lived session (metrics + snapshots)")
    pst.add_argument("--snapshot", help="control-plane snapshot path to write")
    pst.add_argument("--resume-from", help="snapshot to restore at boot")
    pst.add_argument("--metrics-port", type=int, default=0)
    pst.add_argument("--rpc-port", type=int, default=0,
                     help="control-plane RPC port (0 = ephemeral)")
    pst.add_argument("--serve-app", help="module:attr of a serve Application")
    pst.add_argument("--address", help="join an existing head as a WORKER "
                     "host (head's control-plane RPC host:port)")
    pst.add_argument("--num-cpus", type=float, default=None,
                     help="CPU resource to advertise (worker join)")
    pst.add_argument("--num-tpus", type=float, default=None,
                     help="TPU resource to advertise (worker join)")
    pst.add_argument("--node-host", default=None,
                     help="this host's cluster-reachable address (worker "
                     "join serves dispatch/transfer on it; default "
                     "RAY_TPU_NODE_HOST or 127.0.0.1)")
    pst.set_defaults(fn=cmd_start)

    ph = sub.add_parser("health", help="health plane: alerts, SLO digests, "
                        "node liveness (renders /api/v0/health)")
    ph.add_argument("--address", default="",
                    help="dashboard host:port of a running head (default: "
                    "in-process health plane)")
    ph.set_defaults(fn=cmd_health)

    ppf = sub.add_parser("profile", help="profiling plane: stack-dump or "
                         "CPU-profile any worker (util/profiler.py)")
    ppf.add_argument("node", nargs="?", default="",
                     help="node id hex prefix ('' / 'head' = the head node)")
    ppf.add_argument("pid", nargs="?", type=int, default=0,
                     help="target pid (0 = the node's agent process; "
                     "--kind pids lists what a node can profile)")
    ppf.add_argument("--kind", choices=["stack", "cpu", "jax", "pids"],
                     default="stack")
    ppf.add_argument("--duration", type=float, default=None,
                     help="sampling window seconds (cpu/jax kinds)")
    ppf.add_argument("--hz", type=float, default=None,
                     help="cpu sampling rate (default config profiler_sample_hz)")
    ppf.add_argument("--address", default="",
                     help="dashboard host:port of a running head (default: "
                     "in-process runtime)")
    ppf.set_defaults(fn=cmd_profile)

    pmem = sub.add_parser("memory", help="object ledger: sizes, locations, "
                          "refcounts, pin reasons, leaks")
    pmem.add_argument("--limit", type=int, default=100,
                      help="top-N objects by size")
    pmem.add_argument("--group-by", choices=["reason", "node"], default=None,
                      help="aggregate objects/bytes by pin reason or node")
    pmem.add_argument("--leaks", action="store_true",
                      help="run the leak sweep and print flagged objects")
    pmem.add_argument("--snapshot", help="read a control-plane snapshot file")
    pmem.set_defaults(fn=cmd_memory)

    plog = sub.add_parser("logs", help="list/tail/follow session logs")
    plog.add_argument("file", nargs="?", help="log file name to tail")
    plog.add_argument("-n", "--lines", type=int, default=100)
    plog.add_argument("--log-dir", help="session log dir "
                      "(default: /tmp/ray_tpu/session_latest/logs)")
    plog.add_argument("--follow", action="store_true",
                      help="stream live lines over RPC (needs --address)")
    plog.add_argument("--address", help="live session control-plane RPC address")
    plog.set_defaults(fn=cmd_logs)

    pt = sub.add_parser("timeline", help="export the task timeline (chrome trace)")
    pt.add_argument("out", nargs="?", default="timeline.json")
    pt.add_argument("--events-dir",
                    help="merge session dumps written via event_log_dir")
    pt.set_defaults(fn=cmd_timeline)

    pm = sub.add_parser("microbenchmark",
                        help="core task/actor/object-plane throughput canaries")
    pm.set_defaults(fn=cmd_microbenchmark)

    psv = sub.add_parser("serve", help="serve apps from a config or import path")
    psv_sub = psv.add_subparsers(dest="serve_cmd", required=True)
    psr = psv_sub.add_parser("run", help="deploy + serve in the foreground")
    psr.add_argument("config_or_import_path",
                     help="a serve YAML/JSON config, or module:attr")
    psr.add_argument("--http-port", type=int, default=0)
    psr.add_argument("--grpc-port", type=int, default=None,
                     help="also serve the gRPC ingress (0 = ephemeral)")
    psr.set_defaults(fn=cmd_serve_run)

    args = p.parse_args(argv)
    if hasattr(args, "entrypoint"):
        # strip a leading "--" separator
        if args.entrypoint and args.entrypoint[0] == "--":
            args.entrypoint = args.entrypoint[1:]
        if not args.entrypoint:
            p.error("submit: entrypoint required (e.g.: ray-tpu submit -- python train.py)")
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())

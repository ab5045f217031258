"""``agentainer`` CLI — verb parity with the reference's cobra tree.

Reference commands (cmd/agentainer/main.go:266-282): server, deploy, start,
stop, restart, pause, resume, remove, logs, list, invoke, requests, health,
metrics, backup {create,list,restore,delete,export}, audit. All lifecycle verbs are
thin HTTP clients against the management API with a bearer token
(makeAPIRequest parity, main.go:577-613); ``server`` runs the daemon.

Usage:  python -m agentainer_tpu.cli <command> [...]   (or the `agentainer`
console script once installed).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import requests as http

from .config import load_config


def _base(args) -> str:
    return args.server.rstrip("/")


def _headers(args) -> dict:
    return {"Authorization": f"Bearer {args.token}"}


def _call(args, method: str, path: str, body: dict | None = None) -> dict:
    url = _base(args) + path
    resp = http.request(method, url, json=body, headers=_headers(args), timeout=60)
    try:
        doc = resp.json()
    except ValueError:
        print(f"error: non-JSON response ({resp.status_code})", file=sys.stderr)
        sys.exit(1)
    if not doc.get("success", False):
        print(f"error: {doc.get('message', resp.status_code)}", file=sys.stderr)
        sys.exit(1)
    return doc


def _print(data) -> None:
    print(json.dumps(data, indent=2, default=str))


def _pairs(flag: str, pairs: list[str]):
    for pair in pairs or []:
        key, sep, val = pair.partition("=")
        if not sep or not key:
            raise SystemExit(f"{flag} expects KEY=VALUE, got {pair!r}")
        yield key, val


def _parse_env(pairs: list[str]) -> dict[str, str]:
    return dict(_pairs("--env", pairs))


def _parse_options(pairs: list[str]) -> dict[str, object]:
    """``--option KEY=VALUE`` pairs as keys of the model spec's ``options``:
    a value that reads as a JSON scalar is one (true, 4, 0.5, null), any
    other is the string as typed."""
    options: dict[str, object] = {}
    for key, val in _pairs("--option", pairs):
        try:
            parsed = json.loads(val)
        except ValueError:
            parsed = val
        options[key] = val if isinstance(parsed, (dict, list)) else parsed
    return options


# -- commands -------------------------------------------------------------
def cmd_server(args) -> None:
    import asyncio

    from .daemon import build_services, run_daemon

    cfg = load_config(args.config)
    if args.port:
        cfg.server.port = args.port
    services = build_services(config=cfg)
    try:
        asyncio.run(run_daemon(services))
    except KeyboardInterrupt:
        pass


def cmd_deploy(args) -> None:
    if args.file:
        from .manager.deployconfig import fan_out, load_deployment

        config = load_deployment(args.file)
        for spec in config.agents:
            for name, s in fan_out(spec):
                doc = _call(
                    args,
                    "POST",
                    "/agents",
                    {
                        "name": name,
                        "model": s.model.to_dict(),
                        "env": s.env,
                        "resources": s.resources.to_dict(),
                        "auto_restart": s.auto_restart,
                        "health_check": s.health_check.to_dict() if s.health_check else None,
                        "replicas": s.engine_replicas,
                    },
                )
                agent = doc["data"]
                print(f"deployed {name}: {agent['id']}")
                if args.start:
                    _call(args, "POST", f"/agents/{agent['id']}/start")
                    print(f"started {agent['id']}")
        return
    model: object = args.model
    if getattr(args, "model_dir", ""):
        # deploy-from-directory (builder.go:98-218 analogue): validate +
        # register the checkpoint dir as a dedup-named artifact with build
        # progress, then deploy an llm agent serving it
        doc = _call(
            args,
            "POST",
            "/artifacts",
            {"path": args.model_dir, "name": args.name or ""},
        )
        art = doc["data"]
        for line in art.get("build_log", []):
            print(f"  {line}")
        print(f"built artifact {art['name']!r}")
        model = {"engine": "llm", "artifact": art["name"]}
    options = _parse_options(args.option)
    if options:
        # options live in the dict form of the model spec
        if isinstance(model, str):
            engine, _, config = model.partition(":")
            model = {"engine": engine or "echo", "config": config}
        model.setdefault("options", {}).update(options)
    body = {
        "name": args.name,
        "model": model,
        "env": _parse_env(args.env),
        "resources": {"chips": args.chips, "hbm_bytes": args.hbm_bytes},
        "auto_restart": args.auto_restart,
    }
    if getattr(args, "replicas", 0):
        body["replicas"] = args.replicas
    if args.health_endpoint:
        body["health_check"] = {
            "endpoint": args.health_endpoint,
            "interval_s": args.health_interval,
            "timeout_s": args.health_timeout,
            "retries": args.health_retries,
        }
    doc = _call(args, "POST", "/agents", body)
    agent = doc["data"]
    print(f"deployed {agent['name']}: {agent['id']}")
    if args.start:
        _call(args, "POST", f"/agents/{agent['id']}/start")
        print(f"started {agent['id']}")


def _lifecycle(op: str):
    def cmd(args) -> None:
        doc = _call(args, "POST", f"/agents/{args.agent_id}/{op}")
        agent = doc["data"]
        print(f"{op}: {agent['id']} is {agent['status']}")

    return cmd


def cmd_remove(args) -> None:
    _call(args, "DELETE", f"/agents/{args.agent_id}")
    print(f"removed {args.agent_id}")


def cmd_list(args) -> None:
    doc = _call(args, "GET", "/agents")
    rows = doc["data"]
    if args.json:
        _print(rows)
        return
    fmt = "{:<28} {:<16} {:<9} {:<12} {}"
    print(fmt.format("ID", "NAME", "STATUS", "MODEL", "CHIPS"))
    for a in rows:
        chips = (a.get("placement") or {}).get("chips", [])
        model = a["model"]["engine"] + (f":{a['model']['config']}" if a["model"]["config"] else "")
        print(fmt.format(a["id"], a["name"][:16], a["status"], model[:12], chips))


def cmd_logs(args) -> None:
    if getattr(args, "follow", False):
        # stream until interrupted (docker logs -f parity)
        url = _base(args) + f"/agents/{args.agent_id}/logs?tail={args.tail}&follow=1"
        with http.get(url, headers=_headers(args), stream=True, timeout=None) as resp:
            if resp.status_code != 200:
                print(f"error: {resp.status_code} {resp.text[:200]}", file=sys.stderr)
                sys.exit(1)
            try:
                # bounded chunk size (None buffers until EOF, which a follow
                # stream never reaches); decode_unicode handles multibyte
                # UTF-8 straddling chunk boundaries
                for chunk in resp.iter_content(chunk_size=1024, decode_unicode=True):
                    sys.stdout.write(
                        chunk if isinstance(chunk, str) else chunk.decode("utf-8", "replace")
                    )
                    sys.stdout.flush()
            except KeyboardInterrupt:
                pass
        return
    doc = _call(args, "GET", f"/agents/{args.agent_id}/logs?tail={args.tail}")
    for line in doc["data"]["logs"]:
        print(line)


def cmd_invoke(args) -> None:
    """POST through the proxy (reference `invoke`, main.go parity)."""
    url = f"{_base(args)}/agent/{args.agent_id}{args.path}"
    body = args.data.encode() if args.data else None
    resp = http.request(args.method, url, data=body, timeout=120)
    print(f"HTTP {resp.status_code}")
    print(resp.text)


def cmd_requests(args) -> None:
    import time as _time

    doc = _call(args, "GET", f"/agents/{args.agent_id}/requests?status={args.status}")
    data = doc["data"]
    print(f"stats: {data['stats']}")
    for r in data["requests"]:
        line = f"  {r['id']}  {r['method']} {r['path']}  {r['status']}  retries={r['retry_count']}"
        if r.get("deadline_at"):
            remaining = r["deadline_at"] - _time.time()
            line += f"  deadline={'+' if remaining > 0 else ''}{remaining:.1f}s"
        if r.get("error"):
            line += f"  error={r['error']}"
        print(line)


def cmd_requeue(args) -> None:
    """Put a dead-lettered (failed/expired) request back on the pending
    queue with retries reset — operator recovery after a transient outage."""
    doc = _call(
        args, "POST", f"/agents/{args.agent_id}/requests/{args.request_id}/requeue"
    )
    r = doc["data"]
    print(f"requeued {r['id']} ({r['method']} {r['path']}); replay kicked")


def cmd_health(args) -> None:
    if args.agent_id:
        _print(_call(args, "GET", f"/agents/{args.agent_id}/health")["data"])
    else:
        _print(_call(args, "GET", "/health")["data"])


def cmd_metrics(args) -> None:
    if args.agent_id:
        path = f"/agents/{args.agent_id}/metrics"
        if args.history:
            path += "/history"
        _print(_call(args, "GET", path)["data"])
    else:
        _print(_call(args, "GET", "/metrics")["data"])


def cmd_models(args) -> None:
    doc = _call(args, "GET", "/artifacts")
    rows = doc["data"]
    if not rows:
        print("no artifacts registered (deploy --model-dir ./checkpoint to add one)")
        return
    for a in rows:
        params = f"{a['n_params'] / 1e6:.1f}M" if a.get("n_params") else "?"
        print(f"{a['name']:24s} {a['layout']:6s} {params:>10s}  {a['path']}")


def cmd_slice(args) -> None:
    _print(_call(args, "GET", "/slice")["data"])


def cmd_backup(args) -> None:
    if args.backup_cmd == "create":
        doc = _call(args, "POST", "/backups", {"name": args.name, "description": args.description})
        print(f"created {doc['data']['id']} ({doc['data']['agents']} agents)")
    elif args.backup_cmd == "list":
        _print(_call(args, "GET", "/backups")["data"])
    elif args.backup_cmd == "restore":
        doc = _call(args, "POST", f"/backups/{args.backup_id}/restore")
        print(f"restored {len(doc['data'])} agents")
    elif args.backup_cmd == "delete":
        _call(args, "DELETE", f"/backups/{args.backup_id}")
        print(f"deleted {args.backup_id}")
    elif args.backup_cmd == "export":
        # the server streams the tar.gz; the archive lands on THIS machine
        url = _base(args) + f"/backups/{args.backup_id}/export"
        # stream: archives carry checkpoints/KV snapshots and can be large
        resp = http.request("POST", url, headers=_headers(args), timeout=120, stream=True)
        if resp.status_code != 200 or resp.headers.get("Content-Type", "").startswith(
            "application/json"
        ):
            try:
                msg = resp.json().get("message", resp.status_code)
            except ValueError:
                msg = resp.status_code
            print(f"error: {msg}", file=sys.stderr)
            sys.exit(1)
        out = args.output or f"{args.backup_id}.tar.gz"
        with open(out, "wb") as f:
            for chunk in resp.iter_content(1 << 20):
                f.write(chunk)
        print(f"exported to {out}")


def cmd_faults(args) -> None:
    """Inspect/arm/disarm the daemon's fault-injection plane (failpoints).

    Examples:
        agentainer faults                       # list active failpoints
        agentainer faults --arm "store.get:error=ConnectionError,count=5"
        agentainer faults --disarm store.get
        agentainer faults --clear               # disarm everything
    """
    body = {}
    if getattr(args, "clear", False):
        body["disarm_all"] = True
    if args.disarm:
        body["disarm"] = args.disarm
    if args.arm:
        body["arm"] = ";".join(args.arm)
    if body:
        doc = _call(args, "POST", "/internal/faults", body)
        data = doc["data"]
        for name in data["armed"]:
            print(f"armed {name}")
        for name in data["disarmed"]:
            print(f"disarmed {name}")
        active = data["active"]
    else:
        active = _call(args, "GET", "/internal/faults")["data"]["active"]
    if not active:
        print("no failpoints armed")
        return
    fmt = "{:<28} {:<20} {:>9} {:>6} {:>7} {:>7} {:>10}"
    print(fmt.format("NAME", "ERROR", "DELAY_MS", "P", "COUNT", "FIRED", "EVALUATED"))
    for fp in active:
        print(
            fmt.format(
                fp["name"],
                fp["error"],
                fp["delay_ms"],
                fp["probability"],
                fp["count"],
                fp["fired"],
                fp["evaluated"],
            )
        )


def cmd_audit(args) -> None:
    path = f"/audit?limit={args.limit}"
    if args.action:
        path += f"&action={args.action}"
    for e in _call(args, "GET", path)["data"]:
        print(f"{e['ts']:.0f}  {e['user']:<12} {e['action']:<16} {e['resource']:<32} {e['result']}")


def cmd_atlogs(args) -> None:
    if getattr(args, "follow", False):
        # stream JSON-lines from the logs:stream channel (TailLogs parity)
        url = _base(args) + f"/logs?follow=1&limit={args.limit}"
        if args.component:
            url += f"&component={args.component}"
        with http.request("GET", url, headers=_headers(args), stream=True, timeout=None) as resp:
            for raw in resp.iter_lines():
                if not raw:
                    continue
                try:
                    e = json.loads(raw)
                    print(f"{e['ts']:.0f}  {e['level']:<5} {e['component']:<12} {e['message']}", flush=True)
                except (ValueError, KeyError):
                    print(raw.decode(errors="replace"), flush=True)
        return
    path = f"/logs?limit={args.limit}"
    if args.component:
        path += f"&component={args.component}"
    for e in _call(args, "GET", path)["data"]:
        print(f"{e['ts']:.0f}  {e['level']:<5} {e['component']:<12} {e['message']}")


def build_parser() -> argparse.ArgumentParser:
    cfg = load_config()
    p = argparse.ArgumentParser(prog="agentainer", description=__doc__)
    p.add_argument(
        "--server",
        default=os.environ.get("ATPU_SERVER_URL", f"http://127.0.0.1:{cfg.server.port}"),
        help="management API base URL",
    )
    p.add_argument("--token", default=cfg.auth_token, help="bearer token")
    sub = p.add_subparsers(dest="cmd", required=True)

    s = sub.add_parser("server", help="run the control-plane daemon")
    s.add_argument("--config", default=None)
    s.add_argument("--port", type=int, default=None)
    s.set_defaults(fn=cmd_server)

    s = sub.add_parser("deploy", help="deploy an agent (or -f deployment.yaml)")
    s.add_argument("--name")
    s.add_argument("--model", default="echo", help='engine[:config], e.g. "llm:llama3-8b"')
    s.add_argument(
        "--model-dir",
        default="",
        help="deploy from a local checkpoint directory (HF config.json + "
        "safetensors, or an orbax save): validates, registers a dedup-named "
        "artifact, and serves it with the llm engine",
    )
    s.add_argument("--env", action="append", default=[], metavar="KEY=VALUE")
    s.add_argument(
        "--replicas",
        type=int,
        default=0,
        help="engine replicas for this agent (fleet: health-aware routing, "
        "mid-decode failover, token-identical session resume on a "
        "survivor); 0 = the daemon's fleet.replicas default",
    )
    s.add_argument("--chips", type=int, default=1)
    s.add_argument("--hbm-bytes", type=int, default=8 * 1024**3)
    s.add_argument("--auto-restart", action="store_true")
    s.add_argument(
        "--option",
        action="append",
        default=[],
        metavar="KEY=VALUE",
        help="set a key of the model's options for this agent's engine, as "
        "options: does in a deployment YAML (repeatable; the value is read "
        "as a JSON scalar where it is one, e.g. --option paged_kv=true "
        "--option spec_gamma_max=4)",
    )
    s.add_argument("--health-endpoint", default="")
    s.add_argument("--health-interval", type=float, default=30.0)
    s.add_argument("--health-timeout", type=float, default=5.0)
    s.add_argument("--health-retries", type=int, default=3)
    s.add_argument("--start", action="store_true", help="start right after deploy")
    s.add_argument("-f", "--file", help="AgentDeployment YAML")
    s.set_defaults(fn=cmd_deploy)

    for op in ("start", "stop", "restart", "pause", "resume"):
        s = sub.add_parser(op, help=f"{op} an agent")
        s.add_argument("agent_id")
        s.set_defaults(fn=_lifecycle(op))

    s = sub.add_parser("remove", help="remove an agent and all its state")
    s.add_argument("agent_id")
    s.set_defaults(fn=cmd_remove)

    s = sub.add_parser("list", help="list agents")
    s.add_argument("--json", action="store_true")
    s.set_defaults(fn=cmd_list)

    s = sub.add_parser("logs", help="engine logs")
    s.add_argument("agent_id")
    s.add_argument("--tail", type=int, default=100)
    s.add_argument("-f", "--follow", action="store_true", help="stream new lines")
    s.set_defaults(fn=cmd_logs)

    s = sub.add_parser("invoke", help="send a request through the proxy")
    s.add_argument("agent_id")
    s.add_argument("path", help="e.g. /chat")
    s.add_argument("--method", default="POST")
    s.add_argument("--data", default="")
    s.set_defaults(fn=cmd_invoke)

    s = sub.add_parser("requests", help="journaled requests for an agent")
    s.add_argument("agent_id")
    s.add_argument(
        "--status",
        default="pending",
        help="pending|processing|completed|failed|expired",
    )
    s.set_defaults(fn=cmd_requests)

    s = sub.add_parser(
        "requeue",
        help="reset a dead-lettered (failed/expired) request back onto pending",
    )
    s.add_argument("agent_id")
    s.add_argument("request_id")
    s.set_defaults(fn=cmd_requeue)

    s = sub.add_parser("health", help="server or agent health")
    s.add_argument("agent_id", nargs="?", default="")
    s.set_defaults(fn=cmd_health)

    s = sub.add_parser("metrics", help="metrics (all agents or one)")
    s.add_argument("agent_id", nargs="?", default="")
    s.add_argument("--history", action="store_true")
    s.set_defaults(fn=cmd_metrics)

    s = sub.add_parser("models", help="registered model artifacts")
    s.set_defaults(fn=cmd_models)

    s = sub.add_parser("slice", help="chip topology + placements")
    s.set_defaults(fn=cmd_slice)

    s = sub.add_parser("backup", help="backup management")
    bs = s.add_subparsers(dest="backup_cmd", required=True)
    b = bs.add_parser("create")
    b.add_argument("--name", default="")
    b.add_argument("--description", default="")
    for name in ("restore", "delete"):
        b = bs.add_parser(name)
        b.add_argument("backup_id")
    b = bs.add_parser("export")
    b.add_argument("backup_id")
    b.add_argument("-o", "--output", default="")
    bs.add_parser("list")
    s.set_defaults(fn=cmd_backup)

    s = sub.add_parser(
        "faults",
        help="fault-injection plane: list/arm/disarm failpoints on the daemon",
    )
    s.add_argument(
        "--arm",
        action="append",
        default=[],
        metavar="SPEC",
        help='failpoint spec, e.g. "store.get:error=ConnectionError,'
        'probability=0.5,seed=7,count=10" (repeatable)',
    )
    s.add_argument(
        "--disarm", action="append", default=[], metavar="NAME", help="disarm one failpoint"
    )
    s.add_argument("--clear", action="store_true", help="disarm every failpoint")
    s.set_defaults(fn=cmd_faults)

    s = sub.add_parser("audit", help="audit trail")
    s.add_argument("--limit", type=int, default=50)
    s.add_argument("--action", default="")
    s.set_defaults(fn=cmd_audit)

    s = sub.add_parser("logs-server", help="control-plane structured logs")
    s.add_argument("--limit", type=int, default=50)
    s.add_argument("--component", default="")
    s.add_argument("-f", "--follow", action="store_true", help="stream live entries")
    s.set_defaults(fn=cmd_atlogs)

    return p


def main(argv: list[str] | None = None) -> None:
    args = build_parser().parse_args(argv)
    try:
        args.fn(args)
    except BrokenPipeError:
        # stdout piped into head/less that exited: not an error
        try:
            sys.stdout.close()
        except Exception:
            pass
        sys.exit(0)


if __name__ == "__main__":
    main()

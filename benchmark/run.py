#!/usr/bin/env python3
"""One run of one cell of the benchmark, in a fresh process.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Starts the daemon as a child, deploys the cell's configuration as
``llm:<config>`` over REST, waits for the model, checks correctness, offers
the cell's traffic to ``/agent/{id}/chat`` (proxy -> journal -> engine ->
device), measures for ``--seconds`` and prints, as the last line of standard
output, one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` when traced). Every earlier line is a JSON
object worth keeping (set-up phases, generator lateness, probes). A phase that
fails is a non-zero exit and no result line, with the daemon's and the
engines' log tails on standard error.

This process never imports JAX. What runs on the device runs in children: the
engine hosts (of the daemon) and ``harness/numerics_child.py`` before them.
Everything belonging to one cell is data found by name from
``BENCHMARK.json``: ``configs/<config>.json`` (naming its block's
``families/<family>.py``), ``traffic/<traffic>.json`` (naming
``generators/<generator>.py``) and ``layer_metrics/<metric>.py``.

The builder's own extras, never used by the driver: ``--rehearse`` (tiny
widths on the CPU; prints no device metric a chip run could be taken for) and
``--keep DIR`` (copy the trace and the numerics child's small trace there).
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import math
import os
import shutil
import subprocess
import sys
import time

T_PROCESS_START = time.monotonic()

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness import loadgen, stats  # noqa: E402
from harness.family import family_of  # noqa: E402
from harness.system import ONE_CHIP_ENV, Agent, Daemon, PhaseFailure, check, device_of  # noqa: E402

LOAD_BUDGET_S = 1100.0  # a first run compiles every step program
TRACE_S = 5.0
WARM_SEED_SALT = 7_919_000  # the warm-up's sessions come from another seed
PROBE = "You are an agent on a TPU. The control plane journals every request. Say what you do next."


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell's entry with its configuration and traffic, found by name."""
    bench = load_json(REPO, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    traffic = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    if "base" in traffic:  # a population's own file over the mix it offers
        traffic = {**load_json(BENCH, "traffic", traffic["base"] + ".json"), **traffic}

    def mine(m: dict) -> bool:
        return "workloads" not in m or name in m["workloads"]

    return {
        "name": name,
        "chips": int(cell["chips"]),
        "config_path": os.path.join(REPO, entry["file"]),
        "config": load_json(REPO, entry["file"]),
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def run_child(args: list[str], env: dict, what: str, timeout: float) -> dict:
    """A child that prints one JSON line last; waited for, so that it is gone
    (and the chip free) before anything else starts."""
    proc = subprocess.run(
        [sys.executable, "-m", *args], env=env, cwd=REPO, capture_output=True, text=True, timeout=timeout
    )
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.startswith("{")]
    check(bool(lines), f"{what} printed no report (rc {proc.returncode}): {proc.stderr[-1500:]}")
    doc = json.loads(lines[-1])
    doc["rc"] = proc.returncode
    return doc


def measure(agent: Agent, traffic: dict, seed: int, seconds: float, trace: bool) -> dict:
    """One window (warm-up flowing into it) and everything read around it."""
    gen = importlib.import_module("generators." + traffic["generator"])
    warm = gen.sessions(traffic, seed + WARM_SEED_SALT, seed, "w")
    main = gen.sessions(traffic, seed, seed, "m")
    snap: dict = {}

    def traced() -> None:
        # the engine's counters right before and right after the trace: the
        # roofline readers take the work per launch from their difference,
        # in the same (slowed) regime as the trace, and the launches and
        # their device time from the trace itself
        snap["trace_before"] = agent.engine_docs()
        snap["profile"] = agent.profile(TRACE_S)
        snap["trace_after"] = agent.engine_docs()

    hooks = [
        (0.0, lambda: snap.__setitem__("before", agent.engine_docs())),
        (seconds, lambda: snap.__setitem__("after", agent.engine_docs())),
    ]
    if trace:
        hooks.append((max(0.5, seconds / 2 - TRACE_S / 2), traced))
    rec = loadgen.run(agent.chat_url, traffic, warm, main, seconds, hooks)
    check("before" in snap and "after" in snap, "the engines' counters were not read around the window")
    return {"rec": rec, **snap}


def done_in_window(rec: loadgen.Recorder, seconds: float) -> list[dict]:
    return [r for r in rec.records if r["ok"] and 0.0 <= r["done_s"] < seconds]


def end_to_end(rec: loadgen.Recorder, seconds: float) -> dict:
    """Requests served per second of the window, warm-up requests that ended
    in it included (``stats.served_in_window``). Requests and not tokens: a
    request's tokens differ forty-fold in the chat mix, so the tokens a
    window completes swing with which requests its edges caught."""
    served = stats.served_in_window([(r["due_s"], r["done_s"]) for r in rec.records if r["ok"]], seconds)
    return {"req_per_s": served / seconds}


def lateness(rec: loadgen.Recorder) -> dict:
    late = [1000.0 * (r["sent_s"] - r["due_s"]) for r in rec.window()]
    return {"gen_late_p50_ms": stats.percentile(late, 50.0), "gen_late_p95_ms": stats.percentile(late, 95.0),
            "gen_late_max_ms": max(late) if late else None}


def summary_line(m: dict, seconds: float) -> dict:
    rec = m["rec"]
    window = rec.window()
    done_in = done_in_window(rec, seconds)
    return {
        "attempted": len(window),
        "failed": sum(not r["ok"] for r in window),
        "offered_rps": len(window) / seconds,
        "completed_in_window": len(done_in),
        "cut_by_drain": rec.cut_by_drain,
        **end_to_end(rec, seconds),
        # not judged: the median latency (per-layer ``req_p50_ms``), and the
        # tokens of the replies that landed in the window, as the generator
        # sent and asked for them (the engine's own count is printed beside)
        "req_p50_ms": stats.percentile([r["latency_ms"] if r["ok"] else math.inf for r in window], 50.0),
        "total_tok_s": sum(r["want_prompt_tokens"] + r["want_completion_tokens"] for r in done_in) / seconds,
        "sent_prompt_tokens": sum(r["want_prompt_tokens"] for r in done_in),
        "engine_prompt_tokens": sum(r["prompt_tokens"] or 0 for r in done_in),
        **lateness(rec),
        # a stall of the whole engine shows as a cluster of slow requests
        "slowest": [[round(r["due_s"], 2), None if math.isinf(r["latency_ms"]) else round(r["latency_ms"])]
                    for r in sorted(window, key=lambda r: -r["latency_ms"])[:5]],
        "errors": [r.get("error") for r in window if not r["ok"]][:3],
    }


def reduce_trace(profile: dict, env: dict, keep: str | None) -> dict:
    files = sorted(glob.glob(os.path.join(profile["trace_dir"], "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)
    check(bool(files), f"the engine wrote no trace under {profile['trace_dir']}")
    if keep and os.path.getsize(files[-1]) < 40 << 20:
        os.makedirs(keep, exist_ok=True)
        shutil.copy(files[-1], os.path.join(keep, "serving.xplane.pb"))
    doc = run_child(["benchmark.harness.trace_reduce", files[-1]], {**env, "JAX_PLATFORMS": "cpu"}, "trace_reduce", 600)
    check(doc["rc"] == 0, f"trace_reduce failed: {doc}")
    doc["trace_bytes"] = os.path.getsize(files[-1])
    return doc


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--keep", default="")
    args = ap.parse_args()

    cell = load_cell(args.workload)
    seconds = float(args.seconds if args.seconds is not None else load_json(REPO, "BENCHMARK.json")["run_seconds"])
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(p for p in (REPO, env.get("PYTHONPATH", "")) if p)
    config, config_path = cell["config"], cell["config_path"]
    daemon = None
    try:
        if args.rehearse:
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            # the same cell at widths a CPU can serve (control flow only)
            config = {**config, **family_of(config).REHEARSAL_WIDTHS}
            os.makedirs(os.path.join(REPO, ".chipwork"), exist_ok=True)
            config_path = os.path.join(REPO, ".chipwork", f"rehearsal-{config['name']}.json")
            with open(config_path, "w") as f:
                json.dump(config, f)
        cell["config"] = config

        # (a) numerics: the program's forward against the plain reference,
        # on the device, before an engine takes it
        t0 = time.monotonic()
        child = ["benchmark.harness.numerics_child", config_path, str(args.seed)]
        child += ["--rehearse"] if args.rehearse else []
        child += ["--trace", os.path.join(args.keep, "numerics_trace")] if args.keep else []
        numerics = run_child(child, env if args.rehearse else {**env, **ONE_CHIP_ENV}, "numerics child", 900)
        emit("numerics", seconds=time.monotonic() - t0, **numerics)
        check(numerics["rc"] == 0 and numerics.get("ok") is True, f"numerics check failed: {numerics}")

        daemon = Daemon(env, chips=cell["chips"], config_path=config_path)
        emit("daemon", **{k: daemon.health.get(k) for k in ("slice", "slice_chips", "data_plane")})
        agent = Agent(daemon, config)
        t0 = time.monotonic()
        agent.start()
        docs = agent.wait_loaded(LOAD_BUDGET_S)
        device = device_of(docs, cell["chips"], args.rehearse)
        emit(
            "engine_ready",
            seconds=time.monotonic() - t0,
            device=device,
            engines=[
                {k: m.get(k) for k in ("replica", "chips", "visible_chips", "engine_load_s", "warmup_skipped",
                                        "compile_cache", "attention", "param_hbm_bytes", "kv_arena_bytes",
                                        "max_batch", "max_seq", "moe_routed")}
                for m in docs
            ],
        )
        tokens_before = agent.generate_greedy(PROBE, 16)

        m = measure(agent, cell["traffic"], args.seed, seconds, bool(args.trace))
        rec = m["rec"]
        setup_s = rec.origin_monotonic - T_PROCESS_START
        window = rec.window()
        emit("window", seconds=seconds, **summary_line(m, seconds))

        after = agent.engine_docs()
        device = device_of(after, cell["chips"], args.rehearse)
        journal = agent.journal()
        tokens_after = agent.generate_greedy(PROBE, 16)
        n = min(len(tokens_before), len(tokens_after))
        emit(
            "served_path",
            journal=journal,
            greedy_probe_agreement=sum(a == b for a, b in zip(tokens_before, tokens_after)) / n if n else None,
            compile_cache=[x.get("compile_cache") for x in after],
            worker_errors=[x.get("worker_errors") for x in after],
        )
        failed = sum(not r["ok"] for r in window)
        correct = (
            numerics.get("ok") is True
            and failed == 0
            and len(window) > 0
            and rec.cut_by_drain == 0
            # every acknowledged request was journaled and completed
            and not any(journal.get(k) for k in ("failed", "pending", "expired"))
        )

        cell.update(device=device, seconds=seconds)
        values: dict = {}
        result: dict = {"correct": bool(correct), "attempted": len(window), "failed": failed}
        if args.trace:
            trace = reduce_trace(m["profile"], env, args.keep or None)
            trace.update(counters_before=m["trace_before"], counters_after=m["trace_after"])
            check(args.rehearse or trace["busy_s"] > 0, f"no operation ran on the device in the traced window: {trace['planes']}")
            emit("trace", **{k: trace[k] for k in ("window_s", "busy_s", "host_span_s", "device_planes", "modules", "trace_bytes")})
            for metric in cell["per_layer"]:
                reader = importlib.import_module("layer_metrics." + metric["name"])
                v = reader.read(m["before"], m["after"], window, trace, cell)
                if v is not None:
                    values[metric["name"]] = {"value": v, "unit": metric["unit"]}
            device = {**device, "busy_s": trace["busy_s"], "window_s": trace["window_s"]}
            result["breakdown"] = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}
        else:
            e2e = {**end_to_end(rec, seconds), "setup_s": setup_s}
            for metric in cell["end_to_end"]:
                v = e2e.get(metric["name"])
                if v is not None and not math.isinf(v):
                    values[metric["name"]] = {"value": v, "unit": metric["unit"]}
        if args.rehearse:
            device = {**device, "rehearsal": True}
        result.update(metrics=values, device=device)
        daemon.close()
        daemon = None
        emit("done", seconds=time.monotonic() - T_PROCESS_START, setup_s=setup_s)
        print(json.dumps(result), flush=True)
        return 0
    except (PhaseFailure, subprocess.TimeoutExpired) as e:
        print(f"benchmark run failed: {e}", file=sys.stderr)
        if daemon is not None:
            print(daemon.diagnosis(), file=sys.stderr)
        return 1
    finally:
        if daemon is not None:
            daemon.close()


if __name__ == "__main__":
    sys.exit(main())

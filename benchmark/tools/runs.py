"""Runs of cells one after another in one chip call, each with the stall
sampler beside it, and one JSON line a run in ``--out``. The builder's
tool for the proof sets and the sweep of a rate (PERF.md); not part of a
benchmark run, and it never touches JAX (each run is a child that gets the
chip to itself).

    chiprun --timeout 3000 -- python3 benchmark/tools/runs.py \\
        --out chiprun_out/chat.jsonl serve-chat:4900000101 serve-chat:4900000102:1

    chiprun --timeout 3000 -- python3 benchmark/tools/runs.py \\
        --out chiprun_out/sweep.jsonl --sweep sessions_per_s=0.64,1.3,1.9 \\
        --doubles ttft_p90_ms serve-chat:4900000001 serve-chat:4900000002

A run is ``<cell>:<seed>[:<trace>]``. ``--sweep KEY=a,b,c`` makes every
run once a value, values in order, and with ``--doubles METRIC`` stops
after the value at which the metric's mean over the runs is twice the
value before's or more (the knee is the value before). ``--set KEY=JSON``
goes to every run as it stands. ``--log-compiles`` runs with
``JAX_LOG_COMPILES=1`` and keeps the programs JAX says it compiles behind
the run's ``bench window`` line: what the warm-up did not meet. A line holds the run's result object, its
``bench ...`` lines by name (the last of each), its ``bench stalls``
object and the sampler's record with the episodes that lie in the run's
window or its drain."""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FACT = re.compile(r"(\w+)=(\S+)")


def _number(text: str):
    try:
        return json.loads(text)
    except ValueError:
        return text


def parse(stdout: str) -> dict:
    """The ``bench <what>: k=v ...`` lines (the last of each kind), the
    ``bench stalls`` object and the result line of a run's output."""
    said, stalls, result = {}, None, None
    for line in stdout.splitlines():
        if line.startswith("bench stalls: "):
            stalls = json.loads(line[len("bench stalls: "):])
        elif line.startswith("bench "):
            what, _, rest = line[len("bench "):].partition(": ")
            said[what.replace(" ", "_")] = {
                k: _number(v) for k, v in FACT.findall(rest)}
        elif line.startswith("{"):
            try:
                result = json.loads(line)
            except ValueError:
                pass
    return {"said": said, "stalls": stalls, "result": result}


def in_window(record: dict, stalls: dict | None) -> dict:
    """The sampler's record cut to what lies between the window's start
    and its end (a stall in the drain delays tokens of measured requests
    too, so the episodes run on 30 s past the window)."""
    if not record or not stalls:
        return record
    t0, t1 = stalls["window"]
    keep = dict(record)
    keep["episodes"] = [e for e in record["episodes"]
                        if e["start"] + e["seconds"] >= t0
                        and e["start"] <= t1 + 30.0]
    keep["sampler_held"] = [x for x in record.get("sampler_held", [])
                            if t0 <= x[0] <= t1 + 30.0]
    return keep


def one(cell: str, seed: int, trace: int, seconds: float, sets: list,
        sample: bool, tag: str, least: float = 0.08,
        log_compiles: bool = False) -> dict:
    command = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
               "--workload", cell, "--seed", str(seed),
               "--seconds", str(seconds), "--trace", str(trace)]
    for item in sets:
        command += ["--set", item]
    sampled = os.path.join(ROOT, ".bench_out", f"sampler-{tag}.json")
    if sample:
        command = [sys.executable,
                   os.path.join(ROOT, "benchmark", "tools",
                                "stall_sampler.py"),
                   "--out", sampled, "--least", str(least), "--"] + command
    t = time.monotonic()
    env = dict(os.environ, PYTHONUNBUFFERED="1")
    if log_compiles:
        env["JAX_LOG_COMPILES"] = "1"
    done = subprocess.run(
        command, cwd=ROOT, stdout=subprocess.PIPE, text=True, env=env,
        stderr=subprocess.STDOUT if log_compiles else subprocess.PIPE)
    line = {"cell": cell, "seed": seed, "trace": trace, "set": sets,
            "rc": done.returncode, "wall_s": time.monotonic() - t}
    line.update(parse(done.stdout))
    if log_compiles:
        behind = done.stdout.partition("\nbench window:")[2]
        line["compiled_in_window"] = [
            x.partition("Compiling ")[2][:300]
            for x in behind.splitlines() if "Compiling " in x]
    if done.returncode != 0 or line["result"] is None:
        line["stdout_end"] = done.stdout[-3000:]
        line["stderr_end"] = (done.stderr or "")[-3000:]
    if sample and os.path.exists(sampled):
        with open(sampled) as f:
            line["sampler"] = in_window(json.load(f), line["stalls"])
        os.remove(sampled)
    return line


def brief(line: dict) -> str:
    """What of a run is worth a line of the call's output."""
    said, res = line["said"], line["result"] or {}
    notes = said.get("open_loop") or said.get("backlog") or {}
    counts = said.get("counts", {})
    metrics = {k: v["value"] for k, v in res.get("metrics", {}).items()}
    stalls = line["stalls"] or {}
    sampler = line.get("sampler") or {}
    return json.dumps({
        "cell": line["cell"], "seed": line["seed"], "trace": line["trace"],
        "set": line["set"], "rc": line["rc"],
        "correct": res.get("correct"), "failed": res.get("failed"),
        "attempted": res.get("attempted"), "metrics": metrics,
        "compiles_in_window": counts.get("compiles_in_window"),
        "notes": {k: notes[k] for k in (
            "measured", "tpot_qualifying", "prefilled_sessions",
            "slots_mean", "slots_max", "queue_wait_p50_ms", "ttft_p50_ms",
            "tpot_p50_ms", "late_p99_ms", "drain_s", "tokens_per_s",
            "tokens") if k in notes},
        "setup": said.get("setup", {}).get("total_s"),
        "cache": said.get("compile_cache"),
        "holdups": stalls.get("holdups"), "gc": stalls.get("gc_counts"),
        "collections": stalls.get("collections"),
        "compiled_in_window": line.get("compiled_in_window"),
        "episodes": [[e["kind"], round(e["seconds"], 3),
                      round(e["main_run_s"], 3), round(e["main_wait_s"], 3),
                      round(e["others_run_s"], 3), e.get("machine_steal_s"),
                      round(e["sampler_gap_max_s"], 3)]
                     for e in sampler.get("episodes", [])][:12],
        "sampler_held": [[round(x[1], 3), x[2]]
                         for x in sampler.get("sampler_held", [])][:12],
        "wall_s": round(line["wall_s"], 1)})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--sweep", default=None, metavar="KEY=a,b,c")
    ap.add_argument("--doubles", default=None, metavar="METRIC")
    ap.add_argument("--no-sampler", action="store_true")
    ap.add_argument("--log-compiles", action="store_true")
    ap.add_argument("--least", type=float, default=0.08,
                    help="the shortest episode the sampler keeps, seconds")
    ap.add_argument("runs", nargs="+", metavar="CELL:SEED[:TRACE]")
    args = ap.parse_args(argv)
    runs = []
    for spec in args.runs:
        cell, seed, *trace = spec.split(":")
        runs.append((cell, int(seed), int(trace[0]) if trace else 0))
    values = [None]
    if args.sweep:
        key, _, listed = args.sweep.partition("=")
        values = listed.split(",")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    before, n, rc = None, 0, 0
    with open(args.out, "a") as out:
        for value in values:
            sets = list(args.set) + ([f"{key}={value}"] if args.sweep else [])
            read = []
            for cell, seed, trace in runs:
                n += 1
                line = one(cell, seed, trace, args.seconds, sets,
                           not args.no_sampler, f"{os.getpid()}-{n}",
                           args.least, args.log_compiles)
                rc = rc or line["rc"]
                out.write(json.dumps(line) + "\n")
                out.flush()
                print("run " + brief(line), flush=True)
                metric = ((line["result"] or {}).get("metrics", {})
                          .get(args.doubles or "", {}).get("value"))
                if metric is not None:
                    read.append(metric)
            if args.doubles and read:
                mean = sum(read) / len(read)
                if before is not None and mean >= 2.0 * before:
                    print(f"sweep: {args.doubles} {before:.4f} -> {mean:.4f} "
                          f"at {sets[-1]}: doubled, stopping", flush=True)
                    break
                before = mean
    return rc


if __name__ == "__main__":
    sys.exit(main())

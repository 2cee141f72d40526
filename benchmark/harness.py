"""The benchmark's harness: one command, one process, driven by data.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` names the cell; the harness finds by name alone
``benchmark/configs/<config>.json`` (the sizes, the model family under
``family``, and the program's settings under ``system``),
``benchmark/traffic/<traffic>.json`` (which names a generator under
``benchmark/generators/`` and a runner under ``benchmark/runners/``) and,
for a traced run, one reader ``benchmark/layer_metrics/<metric>.py`` per
per-layer metric of the cell. Whatever knows a model's block is found by
the configuration's ``family`` and by nothing else:
``benchmark/families/<family>.py`` holds the adapter to the program, the
plain reference that decides ``correct`` and the operation and byte
counts the readers divide by (``benchmark/families/__init__.py``); no
other file names a family, a weight or a width of a block, and programs
are told apart in a trace by their names alone.

So what a later PR adds is new files and new entries, and no file that is
there changes:

- a model family: ``benchmark/families/<family>.py``;
- a configuration: ``benchmark/configs/<config>.json`` and its entry
  under ``configs``;
- a traffic mix: ``benchmark/traffic/<traffic>.json``;
- a per-layer metric: ``benchmark/layer_metrics/<metric>.py`` and its
  entry under ``per_layer``, with the cells that report it under
  ``workloads``;
- a cell: its entry under ``workloads``, and the one thing that is not a
  new entry: the cell's name appended to the ``workloads`` list of each
  end-to-end metric it reports, and of each per-layer metric there
  already that it reports. Nothing else of those entries moves.

The last line of standard output is the result object and nothing else;
what else is worth reading is printed on earlier lines. Off a TPU, or with
fewer chips than the cell asks for, the run exits non-zero with no result
line (``--rehearse`` runs the same code on whatever JAX finds, for the CPU
tests, and prints counts only: never a result line, never a device metric).
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join(ROOT, ".bench_out")      # traces; inside the checkout
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


WATCHDOG_S = 1150.0


def _give_up():
    sys.stderr.write(f"benchmark: no result after {WATCHDOG_S:.0f} s; "
                     "giving up\n")
    sys.stderr.flush()
    os._exit(3)


def say(what: str, **facts):
    print(f"bench {what}: " + " ".join(
        f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
        for k, v in facts.items()), flush=True)


def load_cell(workload: str, root: str = ROOT) -> tuple:
    """(benchmark, cell, config, traffic) for a cell, by name alone."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json (has: {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return bench, cell, config, traffic


def cell_metrics(bench: dict, cell: str, group: str) -> list:
    """The metrics of ``group`` that this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(name: str, root: str = ROOT):
    """A per-layer metric's reader, found by the metric's name: a file
    of that name, else of the name before its last suffix (one reader
    serves ``decode_roofline.chat`` and ``decode_roofline.doc``)."""
    folder = os.path.join(root, "benchmark", "layer_metrics")
    path = os.path.join(folder, name + ".py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(folder, name.rsplit(".", 1)[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Phases:
    """Set-up, by phase, on the host's clock."""

    def __init__(self, t_start: float):
        self.t_start = t_start
        self.t_last = t_start
        self.rows: list = []

    def mark(self, name: str):
        now = time.perf_counter()
        self.rows.append((name, now - self.t_last))
        self.t_last = now

    def report(self, setup_s: float):
        say("setup", total_s=setup_s, **{n.replace(" ", "_") + "_s": s
                                         for n, s in self.rows})


class CompileCounter:
    """Counts programs compiled or read from the compile cache (either
    means a shape was not warmed) between ``open`` and ``close``."""

    def __init__(self):
        self.count = 0
        self.cache_hits = self.cache_misses = 0   # of the whole run
        self._open = False
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_cache)

    def _on(self, event, duration, **_):
        if self._open and event == COMPILE_EVENT:
            self.count += 1

    def _on_cache(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def open(self):
        self._open = True

    def close(self):
        self._open = False


class Tracer:
    """The profiler around a slice of the window. Start and stop run on a
    thread of their own: stopping serialises the trace, which takes
    seconds, and the generator must not stall for it."""

    def __init__(self, enabled: bool, workload: str, seconds: float):
        self.enabled = enabled
        self.dir = os.path.join(OUT_DIR, "trace", workload)
        self.seconds = seconds
        self.keep = False
        self._thread = None

    def _run(self, delay: float):
        import jax

        time.sleep(max(0.0, delay))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        time.sleep(self.seconds)
        jax.profiler.stop_trace()

    def start_in(self, delay: float):
        if not self.enabled:
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        self._thread = threading.Thread(target=self._run, args=(delay,),
                                        name="bench-tracer")
        self._thread.start()

    def finish(self):
        """Wait for the trace and reduce it; None with tracing off."""
        if self._thread is None:
            return None
        self._thread.join()
        from benchmark.trace import Trace, find_xplane

        path = find_xplane(self.dir)
        if path is None:
            return None
        trace = Trace.from_file(path)
        if not self.keep:
            shutil.rmtree(self.dir, ignore_errors=True)
        return trace


def span(name: str):
    """A benchmark span in the profiler's own trace, so that an idle gap
    of the device names what the host was doing."""
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


class Context:
    """What a runner gets."""

    def __init__(self, args, bench, cell, config, traffic, t_start):
        self.args, self.bench, self.cell = args, bench, cell
        self.config, self.traffic = config, traffic
        self.phases = Phases(t_start)
        self.t_start = t_start
        self.device = None          # set once JAX has been asked
        self.compiles = CompileCounter()
        trace_s = min(float(traffic.get("trace_s", 6)), float(args.seconds))
        self.tracer = Tracer(bool(args.trace), cell["name"], trace_s)
        self.tracer.keep = args.keep_trace
        self.setup_s = None

    def window_opens(self):
        """Called by the runner at the instant the measured window starts."""
        self.setup_s = time.perf_counter() - self.t_start
        self.compiles.open()
        say("window", opens_after_s=self.setup_s)


def device_info(chips: int, rehearse: bool) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if rehearse:
        return info
    if info["platform"] != "tpu":
        raise SystemExit(f"benchmark: JAX found platform "
                         f"{info['platform']!r}, not a TPU; no result")
    if info["count"] < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX "
                         f"found {info['count']}; no result")
    return info


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on whatever JAX finds; counts only, no result")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the .xplane.pb under .bench_out/ (debugging)")
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=JSON", help="override a traffic parameter, "
                    "or with system.KEY one of the configuration's system "
                    "settings, for a sweep (never used by the driver)")
    args = ap.parse_args(argv)
    bench, cell, config, traffic = load_cell(args.workload)
    for item in args.set:
        key, _, value = item.partition("=")
        if key.startswith("system."):
            config["system"][key[len("system."):]] = json.loads(value)
        else:
            traffic[key] = json.loads(value)

    from ray_tpu._private.accelerator import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    ctx = Context(args, bench, cell, config, traffic, t_start)
    device = device_info(cell["chips"], args.rehearse)
    ctx.device = device
    ctx.phases.mark("backend init")
    say("start", workload=cell["name"], config=cell["config"],
        traffic=cell["traffic"], seed=args.seed, seconds=args.seconds,
        trace=args.trace, compile_cache=cache_dir, **device)

    # a run that hangs would hold the chip until the driver's limit: the
    # first run of a cell may take 1200 s (it compiles), so give up at 1150
    watchdog = threading.Timer(WATCHDOG_S, _give_up)
    watchdog.daemon = True
    watchdog.start()
    runner = importlib.import_module("benchmark.runners." + traffic["runner"])
    run = runner.run(ctx)          # a RunRecord
    trace = ctx.tracer.finish()
    if trace is not None and not trace.devices:
        trace = None        # no chip in the trace: no device metric
    run.trace = trace
    run.setup_s = ctx.setup_s
    run.end_to_end["setup_s"] = ctx.setup_s
    run.counters["compiles_in_window"] = ctx.compiles.count
    ctx.phases.report(run.setup_s)
    say("compile_cache", hits=ctx.compiles.cache_hits,
        misses=ctx.compiles.cache_misses)

    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(bench, cell["name"], group):
        if group == "end_to_end":
            value = run.end_to_end.get(m["name"])
        else:
            value = load_reader(m["name"])(run)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    say("counts", attempted=run.attempted, failed=run.failed,
        correct=run.correct, compiles_in_window=ctx.compiles.count,
        **run.notes)
    if args.rehearse:
        # counts only: a CPU run never carries a time, a rate or a share
        counted = {k: v for k, v in metrics.items()
                   if next(m for m in bench[group] if m["name"] == k)
                   ["source"] == "program_counter"}
        print("rehearsal " + json.dumps(
            {"correct": run.correct, "attempted": run.attempted,
             "failed": run.failed, "metrics": counted, "device": device}))
        return 0
    device = dict(device, memory_peak_bytes=int(run.memory_peak_bytes))
    result = {"correct": bool(run.correct), "attempted": int(run.attempted),
              "failed": int(run.failed), "metrics": metrics,
              "device": device}
    if args.trace:
        if trace is None or trace.busy_s() <= 0.0:
            raise SystemExit("benchmark: the traced run holds no device "
                             "operation; no result")
        for name, runs, seconds in trace.programs():
            say("program", name=name, runs=runs, seconds=seconds)
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.extent_s
        result["breakdown"] = {"device_ops": trace.top_ops(10),
                               "idle_gaps": trace.idle_gaps(10)}
    watchdog.cancel()
    print(json.dumps(result))
    return 0


class RunRecord:
    """What a runner hands back: the end-to-end values, and the counters
    and trace the per-layer readers take their numbers from."""

    def __init__(self, ctx):
        self.config, self.traffic = ctx.config, ctx.traffic
        self.device = ctx.device
        self.setup_s = None
        self.end_to_end: dict = {}
        self.counters: dict = {}
        self.notes: dict = {}
        self.attempted = 0
        self.failed = 0
        self.correct = False
        self.memory_peak_bytes = 0
        self.trace = None

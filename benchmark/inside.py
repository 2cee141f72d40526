"""Arithmetic of the per-layer metrics that measure the serving layers and
the kernels from inside (PR 24): device programs and kernels found BY NAME
in the reduced trace (``jit_paged_decode_c<chunk>_w<pages>``,
``jit_paged_prefill_w<pages>``, ``jit__step``, ``flash_fwd`` /
``flash_dq`` / ``flash_dkv``), and the engine loop's own spans
(``program_spans``). The name is the only way a program is told from
another: nothing here counts layers or loop passes, so a model with more
than one layer loop reads like any other.
Pure functions of a ``Trace`` or a list of span records, so the CPU tests
run them on synthetic ones. Each returns None where there is nothing to
read, or fewer than ``MIN_SAMPLES`` samples: a median of three is not one.

``readers.py`` holds the readers of the client's counters and of the chip
as a whole, and ``decode_roofline``, which divides a family's bytes by
the step time read here."""

from __future__ import annotations

import re
from collections import Counter

from benchmark import stats
from benchmark.trace import KERNEL_TARGET

MIN_SAMPLES = 5
DECODE = re.compile(r"^jit_paged_decode_c(\d+)_w\d+\(")
PREFILL = re.compile(r"^jit_paged_prefill_w\d+\(")
TRAIN_STEP = re.compile(r"^jit__step\(")
WAITS = ("engine.wait_device", "engine.wait_arrivals")
STAGES = ("queue_wait", "device_wait", "prefill", "pipeline_stall", "ship")


# -- the device trace, by name ------------------------------------------

def decode_program_step_ms(trace):
    """Device time of whole runs of the decode programs over the steps
    they ran: a run of ``..._c<k>_...`` is k steps."""
    if trace is None or not trace.devices:
        return None
    seconds = steps = 0.0
    for name in {n for n, _, _ in trace.devices[0]["modules"]}:
        m = DECODE.match(name)
        if m:
            t, runs = trace.module_time(lambda n: n == name, whole=True)
            seconds, steps = seconds + t, steps + runs * int(m.group(1))
    return seconds / steps * 1e3 if steps >= MIN_SAMPLES else None


def prefill_program_share(trace):
    """The prefill programs' share of the device's busy time."""
    if trace is None or trace.busy_s() <= 0:
        return None
    seconds, runs = trace.module_time(PREFILL.match)
    if runs < MIN_SAMPLES:
        return None
    return 100.0 * seconds / trace.busy_s()


def prefill_program_runs_ms(trace) -> list:
    """Durations of the prefill programs' runs on the device's clock, in
    the order they ran (a trace's edges cut decode chunks, not these)."""
    if trace is None or not trace.devices:
        return []
    mods = sorted(trace.devices[0]["modules"], key=lambda x: x[1])
    return [(e - s) * 1e3 for n, s, e in mods if PREFILL.match(n)]


def kernel_share(trace, kernels: tuple):
    """Share of the train step programs' time in the Pallas kernels whose
    HLO instruction is named after one of ``kernels`` (a kernel's
    ``name`` becomes its instruction's: ``%flash_fwd.6 = ...``)."""
    if trace is None or not trace.devices:
        return None
    step, _ = trace.module_time(TRAIN_STEP.match)

    def mine(op: str) -> bool:
        return KERNEL_TARGET in op and op.lstrip("%").startswith(kernels)

    calls = sum(1 for n, _, _ in trace.devices[0]["ops"] if mine(n))
    if not step or calls < MIN_SAMPLES:
        return None
    return 100.0 * trace.op_time(mine) / step


# -- the engine loop's spans --------------------------------------------

def _named(spans, name: str) -> list:
    return [s for s in spans or () if s["name"] == name]


def engine_host_share(spans):
    """Over the ``engine.iteration`` spans: the time not inside a child
    that waits (for the device, for arrivals), over their total. A child
    whose iteration was dropped from the ring is skipped."""
    iterations = {s["span_id"]: s for s in _named(spans, "engine.iteration")}
    total = sum(s["duration"] for s in iterations.values())
    if len(iterations) < MIN_SAMPLES or total <= 0:
        return None
    waiting = sum(s["duration"] for s in spans if s["name"] in WAITS
                  and s.get("parent_id") in iterations)
    return 100.0 * (total - waiting) / total


def _prefill_runs(spans) -> list:
    return [s for s in _named(spans, "device.run")
            if s.get("attrs", {}).get("kind") == "prefill"]


def prefill_device_wait_p50_ms(spans):
    """Median, over the prefill dispatches, of the time between the
    dispatch and the device's starting on it (the watcher's stamps)."""
    runs = _prefill_runs(spans)
    if len(runs) < MIN_SAMPLES:
        return None
    return stats.percentile([s["attrs"]["wait_s"] for s in runs], 50) * 1e3


def prefill_runs_ms(spans) -> list:
    """The prefill programs' runs, start to done, by the watcher's stamps
    on the host's clock, in stream order."""
    runs = sorted(_prefill_runs(spans), key=lambda s: s["attrs"]["seq"])
    return [s["duration"] * 1e3 for s in runs]


def prefill_run_p50_ms(spans):
    runs = prefill_runs_ms(spans)
    return stats.percentile(runs, 50) if len(runs) >= MIN_SAMPLES else None


def stamp_check(spans, trace) -> dict:
    """The watcher's stamps against the device's clock: both medians of
    the prefill runs and, where the spans and the trace hold the same
    number of them (they are then the same runs, in order), the median
    and the largest difference run by run."""
    host, device = prefill_runs_ms(spans), prefill_program_runs_ms(trace)
    out = {"host_runs": len(host), "device_runs": len(device)}
    if host and device:
        out.update(host_stamps_p50_ms=stats.percentile(host, 50),
                   device_clock_p50_ms=stats.percentile(device, 50))
    if host and len(host) == len(device):
        diffs = [h - d for h, d in zip(host, device)]
        out.update(run_by_run_diff_p50_ms=stats.percentile(diffs, 50),
                   run_by_run_diff_max_ms=max(diffs, key=abs))
    return out


def decode_active_share(spans):
    """Live slots per decode dispatch, over the slots there are."""
    shares = [s["attrs"]["live"] / s["attrs"]["slots"]
              for s in _named(spans, "engine.dispatch_decode")]
    if len(shares) < MIN_SAMPLES:
        return None
    return 100.0 * sum(shares) / len(shares)


def stage_medians_ms(spans) -> dict:
    """{stage: median ms} over the requests whose ``engine.request`` span
    and all five stage children are in ``spans``, with the median of
    their times to first token under ``ttft`` and their number under
    ``requests``."""
    requests = {s["span_id"]: s for s in _named(spans, "engine.request")}
    stages: dict = {}
    for s in spans or ():
        if s.get("parent_id") in requests:
            stages.setdefault(s["parent_id"], {})[s["name"]] = s["duration"]
    whole = [rid for rid, st in stages.items()
             if all("engine." + name in st for name in STAGES)]
    if not whole:
        return {"requests": 0}
    out = {name: stats.percentile(
        [stages[rid]["engine." + name] for rid in whole], 50) * 1e3
        for name in STAGES}
    out["ttft"] = stats.percentile(
        [requests[rid]["duration"] for rid in whole], 50) * 1e3
    out["requests"] = len(whole)
    return out


def phase_shares(spans) -> dict:
    """{phase: % of the iterations' total time}, the loop's time busy by
    phase: each direct child of an ``engine.iteration`` under its name
    (``engine.admit`` holds its prefill dispatches), and ``self`` for
    what no child covers."""
    iterations = {s["span_id"] for s in _named(spans, "engine.iteration")}
    total = sum(s["duration"] for s in _named(spans, "engine.iteration"))
    if total <= 0:
        return {}
    seconds = Counter()
    for s in spans:
        if s.get("parent_id") in iterations:
            seconds[s["name"].split(".", 1)[1]] += s["duration"]
    seconds["self"] = total - sum(seconds.values())
    return {name: 100.0 * t / total for name, t in sorted(seconds.items())}


def summary(spans) -> dict:
    """What a run's spans hold, for one line of the run's output."""
    counts = Counter(s["name"] for s in spans or ())
    out = {"iterations": counts["engine.iteration"],
           "prefill_dispatches": counts["engine.dispatch_prefill"],
           "decode_dispatches": counts["engine.dispatch_decode"],
           "device_runs": counts["device.run"]}
    out.update({f"share_{k}": v for k, v in phase_shares(spans).items()})
    out.update({f"p50_{k}_ms" if k != "requests" else k: v
                for k, v in stage_medians_ms(spans).items()})
    return out

"""Drives the paged engine on an open loop from a schedule fixed before
the window: every request is submitted when it is due, whether or not
earlier ones have finished, and timed from its due time. The schedule
starts ``ramp_s`` before the window and keeps offering load after its end
until every request that was due inside it has finished (or ``cap_s`` has
passed: what is unfinished then has failed). Only requests due inside the
window are measured."""

from __future__ import annotations

import importlib
import math
import time

from benchmark import serving, stats, systems
from benchmark.harness import RunRecord, say, span


def run(ctx) -> RunRecord:
    rec = RunRecord(ctx)
    config, traffic = ctx.config, ctx.traffic
    system, seconds = config["system"], float(ctx.args.seconds)
    gen = importlib.import_module(
        "benchmark.generators." + traffic["generator"])
    sched = gen.build(traffic, config, system, ctx.args.seed, seconds)
    reqs = sched["requests"]
    ctx.phases.mark("schedule")
    eng, facts = serving.prepare_engine(
        ctx, [(r.prompt, r.max_new_tokens) for r in reqs]
        + [(p, 1) for p in sched["prefill"]], traffic["prefill_limits"])

    # sessions already under way hold their last prompt in the prefix cache
    serving.fill_prefix_cache(eng, sched["prefill"],
                              traffic["prefill_limits"]["max_group"])
    ctx.phases.mark("prefix cache fill")

    serving.settle_collector()
    ctx.phases.mark("collector settled")

    client = serving.Client(eng, config["vocab_size"])
    ramp = sched["ramp_s"]
    t_sched = time.perf_counter()
    t0, t1 = t_sched + ramp, t_sched + ramp + seconds
    cap = t1 + sched["cap_s"]
    for r in reqs:
        r.due += t_sched            # on the run's clock from here on
    measured = stats.measured(reqs, t0, t1)
    ctx.tracer.start_in(ramp + 0.5)
    nxt, opened, closed, c0, c1, late = 0, False, False, None, None, []
    watch = serving.StallWatch()
    while True:
        now = time.perf_counter()
        if not opened and now >= t0:
            opened, c0 = True, systems.engine_counters(eng)
            ctx.window_opens()
        if not closed and now >= t1:
            closed, c1 = True, systems.engine_counters(eng)
            ctx.compiles.close()
        while nxt < len(reqs) and reqs[nxt].due <= now:
            req = reqs[nxt]
            nxt += 1
            client.submit(req, time.perf_counter())
            if t0 <= req.due < t1:
                late.append(req.submit_t - req.due)
        with span("read"):
            client.read()
        if ((closed and all(r.done for r in measured)) or now >= cap
                or eng.error is not None):
            break
        wake = serving.POLL_S
        if nxt < len(reqs):
            wake = min(wake, max(0.0, reqs[nxt].due - time.perf_counter()))
        slept = watch.took(now, 0.0, "work")
        with span("sleep"):
            time.sleep(wake)
        watch.took(slept, wake, "sleep")
    drain_s = time.perf_counter() - t1
    watch.report(t0, t1)
    error = serving.stop_engine(eng)
    for r in measured:
        r.failed = r.failed or not r.done    # unfinished at the cap

    ttft = [stats.ttft_ms(r) for r in measured]
    tpot = [x for x in map(stats.tpot_ms, measured) if x is not None]
    failed = sum(r.failed for r in measured)
    rec.attempted, rec.failed = len(measured), failed
    rec.correct = (error is None and failed == 0 and bool(measured)
                   and facts["token_gap"] <= facts["tol"]
                   and client.valid_tokens(measured))
    if ttft:
        rec.end_to_end["ttft_p90_ms"] = stats.percentile(ttft, 90)
    if tpot:
        rec.end_to_end["tpot_p90_ms"] = stats.percentile(tpot, 90)
    queue_wait = [r.handle.breakdown["queue_wait_s"] for r in measured
                  if r.handle is not None and r.handle.breakdown]
    rec.counters.update(serving.window_counters(
        client, c0 or c1, c1 or systems.engine_counters(eng), t0, t1,
        system))
    rec.counters.update(
        generator_late_s=late, queue_wait_s=queue_wait,
        ttft_ms=[x for x in ttft if math.isfinite(x)],
        tpot_ms=[x for x in tpot if math.isfinite(x)],
        tokens_per_s=stats.tokens_in_window(client.all, t0, t1) / seconds,
        peak_hbm_bytes=facts["peak_bytes"])
    rec.memory_peak_bytes = rec.counters["peak_hbm_bytes"]
    rec.notes.update(
        scheduled=len(reqs), measured=len(measured),
        tpot_qualifying=len(tpot), prefilled_sessions=len(sched["prefill"]),
        drain_s=drain_s,
        ttft_p50_ms=stats.percentile(ttft, 50) if ttft else None,
        tpot_p50_ms=stats.percentile(tpot, 50) if tpot else None,
        tokens_per_s=rec.counters["tokens_per_s"],
        ttft_p90_ms=rec.end_to_end.get("ttft_p90_ms"),
        tpot_p90_ms=rec.end_to_end.get("tpot_p90_ms"),
        queue_wait_p50_ms=(stats.percentile(queue_wait, 50) * 1e3
                           if queue_wait else None),
        queue_wait_p99_ms=(stats.percentile(queue_wait, 99) * 1e3
                           if queue_wait else None),
        queue_wait_max_ms=max(queue_wait) * 1e3 if queue_wait else None,
        slots_max=max(rec.counters["occupancy_samples"], default=0),
        slots_mean=(sum(rec.counters["occupancy_samples"])
                    / max(1, len(rec.counters["occupancy_samples"]))),
        late_p99_ms=stats.percentile(late, 99) * 1e3 if late else None,
        engine_error=repr(error))
    say("open_loop", **rec.notes)
    return rec

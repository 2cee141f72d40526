"""Drives the paged engine from a backlog that never runs dry: an offline
batch. The benchmark holds the backlog and keeps ``max_waiting`` requests
handed to the engine and not yet dispatched, topping them up every couple
of milliseconds, so every freed slot is refilled at once. Judged on the output tokens that reach the
client inside the window, over its length."""

from __future__ import annotations

import importlib
import time
import weakref

from benchmark import serving, stats, systems
from benchmark.harness import RunRecord, say, span


class _Feeder:
    """Keeps ``max_waiting`` requests handed to the engine and not yet
    dispatched, and reads every token as it arrives."""

    def __init__(self, client, backlog, max_waiting: int):
        self.client, self.backlog = client, backlog
        self.max_waiting = max_waiting
        self.undispatched: list = []

    def run_until(self, t_end: float):
        client = self.client
        while client.eng.error is None:
            now = time.perf_counter()
            if now >= t_end:
                break
            # what the engine has been handed and has not yet dispatched:
            # its admission takes everything waiting into ONE prefill
            # program, and four 2048-token prompts in one program do not
            # fit the chip (PERF.md, PR 23)
            self.undispatched = [
                r for r in self.undispatched
                if r.handle.dispatch_t is None and not r.done]
            for req in self.backlog.take(
                    self.max_waiting - len(self.undispatched)):
                client.submit(req, now)
                self.undispatched.append(req)
            with span("read"):
                client.read()
            with span("sleep"):
                time.sleep(serving.POLL_S)


def run(ctx) -> RunRecord:
    rec = RunRecord(ctx)
    config, traffic = ctx.config, ctx.traffic
    system, seconds = config["system"], float(ctx.args.seconds)
    gen = importlib.import_module(
        "benchmark.generators." + traffic["generator"])
    sched = gen.build(traffic, config, system, ctx.args.seed, seconds)
    backlog = sched["backlog"]
    # one cycle holds every length the grid has (prompts only: a request
    # kept here would keep its engine, and so the page pool, alive)
    shapes = [(r.prompt, r.max_new_tokens) for r in backlog.first_cycle()]
    ctx.phases.mark("schedule")
    for again in (False, True):
        eng, facts = serving.prepare_engine(ctx, shapes,
                                            traffic["prefill_limits"])
        client = serving.Client(eng, config["vocab_size"])
        feeder = _Feeder(client, backlog, traffic["max_waiting"])
        missed = ctx.compiles.cache_misses
        t0 = time.perf_counter() + sched["ramp_s"]
        feeder.run_until(t0)
        ctx.phases.mark("ramp" + (" again" if again else ""))
        # the ramp fills every slot, and only then does the engine take
        # its short decode chunk: where that program was COMPILED in the
        # ramp, the loop's chunk-period estimate is off for a minute
        # (serving.prepare_engine), so the engine is made anew, once
        if ctx.compiles.cache_misses == missed or again:
            break
        error = serving.stop_engine(eng)
        if error is not None:
            break
        gone, eng, client, feeder = weakref.ref(eng), None, None, None
        serving.wait_gone(gone)
    t1 = t0 + seconds
    ctx.tracer.start_in(0.5)
    c0 = systems.engine_counters(eng)
    ctx.window_opens()
    feeder.run_until(t1)
    c1 = systems.engine_counters(eng)
    ctx.compiles.close()
    error = serving.stop_engine(eng)

    finished = [r for r in client.all if r.done and r.token_times
                and t0 <= r.token_times[-1] < t1]
    failed = [r for r in finished if r.failed]
    tokens = stats.tokens_in_window(client.all, t0, t1)
    rec.attempted, rec.failed = len(finished), len(failed)
    if error is not None:
        rec.failed = rec.attempted = max(1, len(client.all))
    rec.correct = (error is None and not failed and tokens > 0
                   and facts["token_gap"] <= facts["tol"]
                   and client.valid_tokens(client.all))
    rec.end_to_end["serve_tokens_per_s"] = tokens / seconds
    rec.counters.update(serving.window_counters(
        client, c0 or c1, c1, t0, t1, system))
    rec.counters["tokens_per_s"] = tokens / seconds
    rec.counters["peak_hbm_bytes"] = facts["peak_bytes"]
    rec.memory_peak_bytes = rec.counters["peak_hbm_bytes"]
    rec.notes.update(tokens=tokens, submitted=len(client.all),
                     finished_in_window=len(finished),
                     engine_error=repr(error))
    say("backlog", **rec.notes)
    return rec

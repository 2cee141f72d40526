"""Benchmark: train + serve + core-op throughput in one artifact.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "detail"}.
The headline metric is Llama train tokens/sec/chip; the detail block
carries the serve (req/s + p50 TTFT) and core-op (tasks/s, actor calls/s,
put/get) numbers so every round's artifact records all three surfaces
(the BASELINE metric names train AND serve; the envelope names core ops).

The reference publishes no absolute numbers (BASELINE.md: envelope only),
so vs_baseline is measured against a hardware-grounded target: 40% MFU of
the chip's peak bf16 throughput.

Env knobs:
    BENCH_MODE=all|train|serve|core  (default all)
    BENCH_PRESET=small|base   (default base; small for CPU smoke runs)
    BENCH_STEPS=N             (timed steps, default 10)
    BENCH_REQUESTS=N          (serve mode: requests, default 16)

``core`` is the microbenchmark suite analog
(``python/ray/_private/ray_perf.py:93``): task/actor/put/get op
throughput on the cluster runtime. ``envelope`` is the bounded
scalability probe (``release/benchmarks/README.md`` analog): queued-task
drain rate, actor-creation rate through the fork-server worker pool,
and steady-state calls/s across the created actors — sized by
``RAY_TPU_BENCH_ENVELOPE_TASKS`` / ``RAY_TPU_BENCH_ENVELOPE_ACTORS``
(defaults 100k tasks / 500 actors).
"""

from __future__ import annotations

import json
import os
import sys
import time


def bench_train(preset: str | None = None) -> dict:
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import create_mesh
    from ray_tpu.train.trainer import JaxTrainer, TrainConfig

    preset = preset or os.environ.get("BENCH_PRESET", "base")
    steps = int(os.environ.get("BENCH_STEPS", "10"))
    remat = os.environ.get("BENCH_REMAT")          # override: none|dots|full
    batch_override = os.environ.get("BENCH_BATCH")
    fused = os.environ.get("BENCH_FUSED")          # "1" forces fused CE loss

    n_dev = len(jax.devices())
    platform = jax.devices()[0].platform

    if preset == "small":
        model_cfg = llama.llama_tiny()
        if remat:
            from dataclasses import replace as _replace

            model_cfg = _replace(model_cfg, remat=remat)
        batch, seq = 8, 128
    elif preset == "longctx":
        # long-context demonstration: the 0.5B model at 16k tokens per
        # sequence — Pallas flash attention (fwd+bwd, O(seq) memory) is
        # what makes the quadratic-attention memory wall a non-issue
        model_cfg = llama.LlamaConfig(
            vocab_size=32768, d_model=1536, n_layers=12, n_heads=12,
            n_kv_heads=4, head_dim=128, d_ff=6144,
            # "dots_attn": save matmul outputs AND the flash-attention
            # residuals so the backward never re-runs the O(s^2)
            # attention forward — at 16k this was the round-3 MFU gap
            # (28.6% under remat="full"; 35.0%/55.4% incl-attn with this)
            remat=remat or "dots_attn",
        )
        # one sequence per chip (the batch dim shards over fsdp when
        # multi-chip, so it must be divisible by the device count)
        batch, seq = max(n_dev, 1), 16384
    elif preset == "large":
        # ~1.0B params: the largest honest single-chip config — full
        # rematerialization trades recompute FLOPs for HBM so params +
        # Adam moments (~12 GB f32) and activations fit a 16 GB chip
        model_cfg = llama.LlamaConfig(
            vocab_size=32768, d_model=2048, n_layers=16, n_heads=16,
            n_kv_heads=4, head_dim=128, d_ff=7168,
            # dots_attn fits at b=4 and lifts MFU 0.541 -> 0.595 over
            # "full" (no matmul or flash-fwd recompute in the backward)
            remat=remat or "dots_attn",
        )
        batch, seq = 4, 2048
    else:
        # ~0.5B-param Llama-style model: fits one v5e chip with Adam state.
        model_cfg = llama.LlamaConfig(
            vocab_size=32768, d_model=1536, n_layers=12, n_heads=12,
            n_kv_heads=4, head_dim=128, d_ff=6144,
            # "dots_attn" (save matmul outputs + flash residuals)
            # measured 0.600 MFU vs 0.586 for "dots" at this size on
            # v5e; "none" OOMs with Adam state, batch 16 OOMs
            remat=remat or "dots_attn",
        )
        batch, seq = 8, 2048
    if batch_override:
        batch = int(batch_override)

    # Multi-chip: shard params/optimizer on an fsdp axis; single chip: dp.
    axis = "fsdp" if n_dev > 1 else "dp"
    trainer = JaxTrainer(
        model_cfg,
        TrainConfig(
            mesh_axes={axis: n_dev}, strategy="fsdp" if n_dev > 1 else "dp",
            warmup_steps=10, total_steps=1000,
            fused_loss=bool(fused and fused != "0"),
        ),
        mesh=create_mesh({axis: n_dev}),
    )

    key = jax.random.key(0)
    state = trainer.init_state(key)
    n_params = llama.num_params(state.params)

    def batch_fn(i):
        return jax.random.randint(
            jax.random.key(i), (batch, seq + 1), 0, model_cfg.vocab_size,
            dtype=jnp.int32,
        )

    # warmup (compile). Dispatch is asynchronous: each timed region ends
    # by fetching the loss to the host, which waits for the device just
    # as block_until_ready does.
    t0 = time.perf_counter()
    state, metrics = trainer.train_step(state, batch_fn(0))
    float(metrics["loss"])
    compile_s = time.perf_counter() - t0
    state, metrics = trainer.train_step(state, batch_fn(1))
    float(metrics["loss"])

    t0 = time.perf_counter()
    for i in range(steps):
        state, metrics = trainer.train_step(state, batch_fn(i + 2))
    float(metrics["loss"])
    elapsed = time.perf_counter() - t0

    tokens_per_step = batch * seq
    tokens_per_sec = tokens_per_step * steps / elapsed
    per_chip = tokens_per_sec / n_dev

    # The reference publishes no absolute numbers (BASELINE.json
    # published: {}), so vs_baseline is reported against a hardware-
    # grounded target: 40% MFU of the chip's peak bf16 throughput
    # (1.0 == hitting that target).
    from ray_tpu.train.telemetry import detect_peak_flops

    # None off TPU, where no MFU is reported; an unknown TPU raises
    peak_flops = detect_peak_flops()
    peak = peak_flops / 1e12 if peak_flops else None
    achieved_tflops = 6 * n_params * per_chip / 1e12
    # causal attention FLOPs per token (ignored by the 6N rule; the
    # dominant term at long context): 6 * L * seq * d_attn for fwd+bwd
    # at average causal span seq/2
    attn_flops = 6 * model_cfg.n_layers * seq * \
        (model_cfg.n_heads * model_cfg.head_dim)
    tflops_incl_attn = (6 * n_params + attn_flops) * per_chip / 1e12
    vs_baseline = round(achieved_tflops / (0.4 * peak), 4) \
        if peak else None

    result = {
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": vs_baseline,
        "detail": {
            "platform": platform,
            "n_devices": n_dev,
            "params": n_params,
            "batch": batch,
            "seq": seq,
            "steps": steps,
            "step_time_s": round(elapsed / steps, 4),
            "compile_s": round(compile_s, 1),
            "final_loss": round(float(metrics["loss"]), 4),
            "model_flops_per_token": 6 * n_params,
            "tflops_per_sec_per_chip": round(
                6 * n_params * per_chip / 1e12, 2
            ),
            "mfu": round(achieved_tflops / peak, 4) if peak else None,
            "attn_flops_per_token": attn_flops,
            "mfu_incl_attn": (round(tflops_incl_attn / peak, 4)
                              if peak else None),
        },
    }
    return result


def bench_train_telemetry() -> dict:
    """Train leg WITH the telemetry plane on: per-step wall-clock
    decomposition (data_wait / compute / collective_sync / checkpoint,
    compile split out on the first step), per-rank MFU from the declared
    FLOPs-per-step, and goodput buckets via util.state.train_goodput.

    Two invariants are asserted here (and fenced in ci/perf_gate.py):
    the decomposition sums to the observed step wall on EVERY step, and
    the per-step telemetry cost — measured with the amortized-delta
    method (min-of-k probe of the stamping path, hot minus cold, like
    the metrics/tracing overhead gates) — stays under 1% of the
    measured step wall."""
    import glob as _glob
    import tempfile

    import jax

    import ray_tpu
    from ray_tpu import train as rtrain
    from ray_tpu.train import session as _session
    from ray_tpu.util import state as _state

    steps = int(os.environ.get("BENCH_STEPS", "8"))
    world_size = int(os.environ.get("BENCH_TRAIN_WORKERS", "2"))
    platform = jax.devices()[0].platform
    # MFU needs a peak FLOP/s: the device's own on TPU (an unknown TPU
    # raises). Off TPU there is none, so a nominal 1 TFLOP/s is DECLARED
    # to exercise the mechanism, and the artifact says so
    # (peak_flops_is_nominal): that number is not a utilization.
    from ray_tpu.train.telemetry import detect_peak_flops

    peak = detect_peak_flops() or 1e12
    storage = tempfile.mkdtemp(prefix="bench_train_telemetry_")
    run_name = "bench-telemetry"

    def loop(config):
        import json as _json

        import jax
        import jax.numpy as jnp

        from ray_tpu.models import llama
        from ray_tpu.parallel.mesh import create_mesh
        from ray_tpu.train.trainer import JaxTrainer, TrainConfig

        model_cfg = llama.llama_tiny()
        trainer = JaxTrainer(
            model_cfg, TrainConfig(mesh_axes={"dp": 1}, strategy="dp",
                                   warmup_steps=2, total_steps=1000),
            mesh=create_mesh({"dp": 1}))
        state = trainer.init_state(jax.random.key(0))
        batch, seq = 4, 128
        n_params = llama.num_params(state.params)
        _session.set_flops_per_step(6.0 * n_params * batch * seq,
                                    peak_flops=config["peak_flops"])

        def batch_fn(i):
            return jax.random.randint(
                jax.random.key(i), (batch, seq + 1), 0,
                model_cfg.vocab_size, dtype=jnp.int32)

        ctx = rtrain.get_context()
        for i in range(config["steps"]):
            with _session.timeit("data_wait"):
                tokens = batch_fn(i)
            state, metrics = trainer.train_step(state, tokens)
            loss = float(metrics["loss"])   # sync -> residual = compute
            if i == config["steps"] // 2:
                with _session.timeit("checkpoint"):
                    jax.block_until_ready(state.params)
                    with open(os.path.join(
                            ctx.trial_dir,
                            f"ckpt_rank{ctx.rank}.bin"), "wb") as f:
                        f.write(b"\0" * 4096)
                        f.flush()
                        os.fsync(f.fileno())
            _session.report({"loss": loss})
        tel = _session.telemetry()
        with open(os.path.join(ctx.trial_dir,
                               f"telemetry_rank{ctx.rank}.json"),
                  "w") as f:
            _json.dump({"rank": ctx.rank, "history": tel.history,
                        "goodput": tel.goodput}, f)

    # the local runtime: the ranks are threads of this process, the one
    # process here that touches the device
    ray_tpu.init(num_cpus=8, num_tpus=0)
    trainer = rtrain.DataParallelTrainer(
        loop,
        train_loop_config={"steps": steps, "peak_flops": peak},
        scaling_config=rtrain.ScalingConfig(num_workers=world_size),
        run_config=rtrain.RunConfig(name=run_name, storage_path=storage))
    t0 = time.perf_counter()
    result = trainer.fit()
    fit_s = time.perf_counter() - t0
    if result.error:
        raise RuntimeError(f"telemetry train leg failed: {result.error}")

    # per-rank stamps written by the ranks themselves; the sum check is
    # asserted on EVERY step of EVERY rank
    ranks = []
    for path in sorted(_glob.glob(
            os.path.join(storage, "**", "telemetry_rank*.json"),
            recursive=True)):
        with open(path) as f:
            ranks.append(json.load(f))
    assert len(ranks) == world_size, f"expected {world_size} rank files"
    max_residual = 0.0
    stage_totals: dict = {}
    mfus = []
    wall_total = 0.0
    steady_total = steady_n = 0
    n_steps = 0
    for r in ranks:
        for stamp in r["history"]:
            diff = abs(sum(stamp["stages"].values()) - stamp["wall_s"])
            assert diff < 1e-6, \
                f"decomposition != wall on step {stamp['step']}: {diff}"
            max_residual = max(max_residual, diff)
            for stage, dt in stamp["stages"].items():
                stage_totals[stage] = stage_totals.get(stage, 0.0) + dt
            wall_total += stamp["wall_s"]
            n_steps += 1
            if "compile" not in stamp["stages"]:
                steady_total += stamp["wall_s"]
                steady_n += 1
            if stamp["mfu"] is not None:
                mfus.append(stamp["mfu"])
    # overhead is fenced against the STEADY-state step wall (first
    # steps carry compile — dividing by them would flatter the ratio)
    step_wall_s = (steady_total / steady_n if steady_n
                   else wall_total / max(n_steps, 1))
    goodput = _state.train_goodput(run_name)
    stragglers = _state.train_stragglers(run_name)

    # amortized-delta overhead probe: the full stamping path (bucket
    # close + residual split + metric emission + annex/watchdog) hot,
    # minus the disabled-path guard cold, over min-of-k large loops —
    # divided by the MEASURED per-step wall above. Never a diff of two
    # noisy end-to-end rates.
    from ray_tpu.train.telemetry import StepTelemetry

    probe_tel = StepTelemetry("bench-probe", 0, flops_per_step=1e9,
                              peak_flops=peak, history_cap=8)

    def _probe_cost(fn, iters: int = 5000, k: int = 5) -> float:
        best = float("inf")
        for _ in range(k):
            t0 = time.perf_counter()
            for _ in range(iters):
                fn()
            best = min(best, (time.perf_counter() - t0) / iters)
        return best

    hot = _probe_cost(lambda: probe_tel.on_report({}))
    noop = _session.telemetry   # the off-path: one accessor + None test
    cold = _probe_cost(lambda: noop() is None)
    overhead_ratio = max(hot - cold, 0.0) / step_wall_s
    assert overhead_ratio < 0.01, \
        f"telemetry overhead {overhead_ratio:.4f} >= 1% of step wall"

    ray_tpu.shutdown()
    gp_round = {k: round(v, 4) for k, v in goodput["buckets"].items()}
    return {
        "metric": "train_telemetry_goodput_fraction",
        "value": round(goodput["goodput_fraction"] or 0.0, 4),
        "unit": "fraction",
        "vs_baseline": None,
        "detail": {
            "platform": platform,
            "world_size": world_size,
            "steps": steps,
            "fit_s": round(fit_s, 2),
            "step_time_s": round(step_wall_s, 4),
            "decomposition_s": {k: round(v, 4)
                                for k, v in sorted(stage_totals.items())},
            "decomposition_max_residual_s": max_residual,
            "steps_sample": ranks[0]["history"][:3],
            "mfu": round(sum(mfus) / len(mfus), 4) if mfus else None,
            "peak_flops_declared": peak,
            "peak_flops_is_nominal": platform != "tpu",
            "goodput": gp_round,
            "goodput_fraction": round(
                goodput["goodput_fraction"] or 0.0, 4),
            "stragglers": stragglers["stragglers"],
            "max_step_skew": stragglers["skew_steps"],
            "telemetry_overhead": {
                "probe_hot_us": round(hot * 1e6, 2),
                "probe_cold_us": round(cold * 1e6, 3),
                "per_step_ms": round(step_wall_s * 1e3, 2),
                "ratio": round(overhead_ratio, 5),
            },
        },
    }


def bench_serve() -> dict:
    """Continuous-batching decode throughput + TTFT on the paged-KV LLM
    engine: a burst phase (comparable with earlier rounds) and a
    SUSTAINED closed-loop phase (concurrency 16, a new request the
    moment one finishes)."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama
    from ray_tpu.serve.paged_llm import PagedLLMEngine

    preset = os.environ.get("BENCH_PRESET", "base")
    n_requests = int(os.environ.get("BENCH_REQUESTS", "16"))
    platform = jax.devices()[0].platform

    if preset == "small":
        model_cfg = llama.llama_tiny()
        max_batch, max_len, prompt_len, new_tokens = 4, 256, 32, 32
        concurrency, sustained_total = 4, 8
    else:
        model_cfg = llama.LlamaConfig(
            vocab_size=32768, d_model=1536, n_layers=12, n_heads=12,
            n_kv_heads=4, head_dim=128, d_ff=6144, remat="none",
        )
        # 4 spare slots over the offered concurrency: admission never
        # waits for a retirement (the free-slot drain path runs)
        max_batch, max_len, prompt_len, new_tokens = 20, 2048, 128, 128
        concurrency, sustained_total = 16, 64

    # the fixed per-dispatch sync cost of the device — the TTFT floor
    # no engine scheduling can beat (recorded so the numbers stay
    # interpretable from one machine to the next)
    _f = jax.jit(lambda x: x + 1)
    _x = jnp.zeros((4,))
    np.asarray(_f(_x))
    _t = time.perf_counter()
    for _ in range(5):
        np.asarray(_f(_x))
    sync_rtt_ms = (time.perf_counter() - _t) / 5 * 1e3

    params = llama.init_params(model_cfg, jax.random.key(0))
    n_params = llama.num_params(params)
    # decode_chunk 16 was the latency/throughput knee measured in
    # round 5 on a chip whose every dispatch cost ~95 ms; it has not
    # been measured on a locally attached chip (ROADMAP Queue 1 3(c)).
    eng = PagedLLMEngine(params=params, cfg=model_cfg,
                         kv_dtype=os.environ.get("BENCH_KV_DTYPE", "bf16"),
                         max_batch=max_batch, max_len=max_len,
                         decode_chunk=int(os.environ.get(
                             "BENCH_DECODE_CHUNK",
                             "16" if preset != "small" else "8")))
    # deterministic warmup BEFORE the loop starts: every prefill group
    # size + decode programs at every pages bucket compile now, so no
    # JIT lands inside a measured window
    eng.warmup(prompt_len)
    eng.start()
    rng = np.random.default_rng(0)
    w = eng.submit(rng.integers(1, model_cfg.vocab_size, prompt_len),
                   max_new_tokens=4)
    list(w.tokens())

    # -- burst phase (round-comparable) --
    t0 = time.perf_counter()
    reqs = [
        eng.submit(rng.integers(1, model_cfg.vocab_size, prompt_len),
                   max_new_tokens=new_tokens)
        for _ in range(n_requests)
    ]
    done = [list(r.tokens()) for r in reqs]
    elapsed = time.perf_counter() - t0

    generated = sum(len(d) for d in done)
    tokens_per_sec = generated / elapsed
    ttfts = [r.ttft for r in reqs if r.ttft is not None]

    # -- sustained phase: closed loop at fixed concurrency --
    done_counts: list = []
    sus_ttfts: list = []
    lock = threading.Lock()
    remaining = [sustained_total - concurrency]
    # monotonic: Request.submit_t uses time.monotonic — mixing clocks
    # breaks the steady-state filter on platforms where their epochs
    # differ
    t0 = time.monotonic()

    def consume(req):
        toks = list(req.tokens())
        with lock:
            done_counts.append(len(toks))
            if req.ttft is not None:
                # breakdown: the MEASURED per-request TTFT decomposition
                # (queue wait / prefill / pipeline stall / first-token
                # ship) stamped by the engine; stages sum to the TTFT
                sus_ttfts.append((req.submit_t - t0, req.ttft,
                                  req.breakdown))
            go = remaining[0] > 0
            if go:
                remaining[0] -= 1
        if go:
            nxt = eng.submit(
                rng.integers(1, model_cfg.vocab_size, prompt_len),
                max_new_tokens=new_tokens)
            threading.Thread(target=consume, args=(nxt,),
                             daemon=True).start()

    for _ in range(concurrency):
        r = eng.submit(rng.integers(1, model_cfg.vocab_size, prompt_len),
                       max_new_tokens=new_tokens)
        threading.Thread(target=consume, args=(r,), daemon=True).start()
    while True:
        with lock:
            if len(done_counts) >= sustained_total:
                break
        time.sleep(0.05)
    sus_elapsed = time.monotonic() - t0
    sus_tps = sum(done_counts) / sus_elapsed
    steady_rows = [r for r in sus_ttfts if r[0] > 0.5] or sus_ttfts
    steady = [t for _, t, _ in steady_rows]
    # measured TTFT decomposition over the steady requests: per-stage
    # means, plus the sum-vs-observed check that proves the stages
    # account for the whole latency (not a model — stamped timestamps)
    steady_bds = [bd for _, _, bd in steady_rows if bd is not None]
    ttft_breakdown = None
    if steady_bds:
        ttft_breakdown = {
            k: round(float(np.mean([bd[k] for bd in steady_bds])), 4)
            for k in ("queue_wait_s", "device_wait_s", "prefill_s",
                      "pipeline_stall_s", "ship_s")}
        ttft_breakdown["sum_s"] = round(
            sum(ttft_breakdown.values()), 4)
        ttft_breakdown["mean_observed_ttft_s"] = round(
            float(np.mean([t for _, t, bd in steady_rows
                           if bd is not None])), 4)
        # queue wait as a share of the whole TTFT: the continuous-
        # admission acceptance number (ci/perf_gate.py fences it)
        if ttft_breakdown["sum_s"] > 0:
            ttft_breakdown["queue_wait_share"] = round(
                ttft_breakdown["queue_wait_s"] / ttft_breakdown["sum_s"],
                4)

    # -- prefix-cache phase: shared system prompt + unique tails --
    # (the chat/agent-serving shape; random-prompt phases above never
    # hit the cache). One prime request registers the shared pages;
    # a warm burst compiles the suffix-bucket programs; the measured
    # burst then shows cached-prefix TTFT.
    sys_len, tail_len, pre_n = 4 * prompt_len, 32, 8
    sys_prompt = rng.integers(1, model_cfg.vocab_size, sys_len)

    def _prefix_burst(n, new_tokens):
        reqs = [eng.submit(
            np.concatenate([sys_prompt,
                            rng.integers(1, model_cfg.vocab_size,
                                         tail_len)]),
            max_new_tokens=new_tokens) for _ in range(n)]
        for r in reqs:
            list(r.tokens())
        return reqs

    _prefix_burst(1, 4)          # prime: registers the prefix pages
    _prefix_burst(pre_n, 4)      # warm: compiles suffix-bucket programs
    hit0 = eng.stats()["prefix_cache"]["hit_pages"]
    pre_reqs = _prefix_burst(pre_n, 16)
    pre_ttfts = [r.ttft for r in pre_reqs if r.ttft is not None]
    pages = eng.stats()
    eng.stop()

    # end-to-end engine throughput: the window covers prefill + queueing +
    # decode for the whole request set (what a serving client experiences)
    result = {
        "metric": "llama_serve_engine_tokens_per_sec",
        # headline = SUSTAINED throughput (the serving-steady-state
        # number; the burst figure is round-comparable detail)
        "value": round(sus_tps, 1),
        "unit": "tokens/s",
        "vs_baseline": None,  # reference publishes no serving numbers
        "detail": {
            "platform": platform,
            "params": n_params,
            "kv_layout": "paged",
            "requests": n_requests,
            "prompt_len": prompt_len,
            "new_tokens": new_tokens,
            "max_batch": max_batch,
            "burst_tokens_per_sec": round(tokens_per_sec, 1),
            "mean_ttft_s": round(float(np.mean(ttfts)), 4) if ttfts else None,
            "p50_ttft_s": round(float(np.median(ttfts)), 4) if ttfts else None,
            "requests_per_sec": round(n_requests / elapsed, 2),
            "sustained": {
                "concurrency": concurrency,
                "requests": sustained_total,
                "tokens_per_sec": round(sus_tps, 1),
                "p50_ttft_s": round(float(np.median(steady)), 4),
                "p95_ttft_s": round(float(np.percentile(steady, 95)), 4),
                "ttft_breakdown": ttft_breakdown,
            },
            # fixed per-dispatch sync latency of the device — the
            # floor under every TTFT above (a prefill pays ~2 of these)
            "dispatch_sync_rtt_ms": round(sync_rtt_ms, 1),
            "prefix_cache": {
                "system_prompt_len": sys_len,
                "tail_len": tail_len,
                "requests": pre_n,
                "p50_ttft_s": round(float(np.median(pre_ttfts)), 4)
                if pre_ttfts else None,
                "hit_pages": (pages.get("prefix_cache") or {}).get(
                    "hit_pages", 0) - hit0,
            },
            "kv_pages": {
                "total": pages.get("kv_pages_total"),
                "bytes": pages.get("kv_pages_bytes"),
                "dense_equiv_bytes": pages.get("kv_dense_equiv_bytes"),
            },
        },
    }
    return result


def bench_serve_scaleout() -> dict:
    """Multi-replica serve leg: cluster tokens/s and per-replica TTFT
    decomposition at 1/2/4 replicas under REPEAT-PREFIX traffic, routed
    through the prefix-affinity DeploymentHandle (serve/prefix_router.py
    digests pushed over the metrics plane from real worker processes).

    The scaling mechanism on a 1-cpu host is redundant-prefill
    ELIMINATION, not extra compute: 8 session prefixes of 24 pages each
    (192 pages working set) round-robin against a 128-page per-replica
    pool, so one replica LRU-thrashes and re-prefills ~768 tokens per
    request, while 2+ replicas with affinity routing each keep their
    session subset cached and prefill only the 32-token tail. Efficiency
    at 2x = cluster tokens/s ratio vs the single-replica leg."""
    import threading

    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.utils.config import reset_config

    # digests must reach the router fast enough to settle affinity
    # within a couple of rounds (default 2s push would dominate a leg)
    os.environ.setdefault("RAY_TPU_METRICS_PUSH_INTERVAL_S", "0.25")
    reset_config()

    PAGE, PREFIX, TAIL, NEW = 32, 768, 32, 8
    MAX_LEN, MAX_BATCH, POOL = 1024, 4, 128
    SESSIONS, CONC = 8, 4
    SETTLE_ROUNDS = int(os.environ.get("BENCH_SCALEOUT_SETTLE", "3"))
    MEASURE_ROUNDS = int(os.environ.get("BENCH_SCALEOUT_ROUNDS", "4"))
    REPLICA_LEGS = (1, 2, 4)

    from ray_tpu.models import llama
    vocab = llama.llama_tiny().vocab_size

    c = Cluster()
    c.add_node(num_cpus=max(REPLICA_LEGS) + 1)
    ray_tpu.init(address=c.gcs_address)

    rng = np.random.default_rng(0)
    session_prefixes = [rng.integers(1, vocab, PREFIX)
                        for _ in range(SESSIONS)]

    @serve.deployment(max_concurrent_queries=8)
    class ScaleLLM:
        def __init__(self):
            import jax
            from ray_tpu.models import llama as _llama
            from ray_tpu.serve.paged_llm import PagedLLMEngine

            cfg = _llama.llama_tiny()
            params = _llama.init_params(cfg, jax.random.key(0))
            self.eng = PagedLLMEngine(
                params=params, cfg=cfg, max_batch=MAX_BATCH,
                max_len=MAX_LEN, page_size=PAGE, num_pages=POOL,
                decode_chunk=8)
            # cold-miss prefill + decode buckets, then the suffix
            # programs prefix-cache hits dispatch — no XLA compile may
            # land inside a measured round
            self.eng.warmup(PREFIX + TAIL)
            self.eng.warmup_prefix(PREFIX, TAIL)
            self.eng.start()

        def __call__(self, tokens, max_new):
            import numpy as _np

            w = self.eng.submit(_np.asarray(tokens, _np.int32),
                                max_new_tokens=max_new)
            toks = list(w.tokens())
            st = self.eng.stats()     # also force-publishes the digest
            pc = st["prefix_cache"]
            return {"n": len(toks), "ttft": w.ttft,
                    "breakdown": w.breakdown,
                    "tag": self.eng.replica_tag,
                    "hit_pages": pc["hit_pages"],
                    "miss_pages": pc["miss_pages"]}

    def _run_leg(n_replicas: int) -> dict:
        name = f"scale{n_replicas}"
        handle = serve.run(
            ScaleLLM.options(name=name, num_replicas=n_replicas).bind(),
            name=name)

        def _call(req_tokens):
            toks = [int(t) for t in req_tokens]
            return ray_tpu.get(
                handle.remote(toks, NEW, _prefix_tokens=toks),
                timeout=600)

        def _run_rounds(rounds: int):
            seq = [np.concatenate([session_prefixes[s],
                                   rng.integers(1, vocab, TAIL)])
                   for _ in range(rounds) for s in range(SESSIONS)]
            out: list = []
            lock = threading.Lock()
            idx = [0]

            def worker():
                while True:
                    with lock:
                        i = idx[0]
                        if i >= len(seq):
                            return
                        idx[0] += 1
                    r = _call(seq[i])
                    with lock:
                        out.append(r)

            ths = [threading.Thread(target=worker) for _ in range(CONC)]
            t0 = time.perf_counter()
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            return out, time.perf_counter() - t0

        # settle: absorbs replica construction, prime misses, and the
        # digest-driven session->replica migration; hit/miss counters at
        # the end of settle are the measured rounds' baselines
        settle, _ = _run_rounds(SETTLE_ROUNDS)
        base: dict = {}
        for r in settle:
            b = base.setdefault(r["tag"], {"hit": 0, "miss": 0})
            b["hit"] = max(b["hit"], r["hit_pages"])
            b["miss"] = max(b["miss"], r["miss_pages"])

        measured, elapsed = _run_rounds(MEASURE_ROUNDS)
        tokens = sum(r["n"] for r in measured)
        ttfts = [r["ttft"] for r in measured if r["ttft"] is not None]
        per_tag: dict = {}
        for r in measured:
            d = per_tag.setdefault(r["tag"], {
                "requests": 0, "ttfts": [], "bds": [],
                "hit": 0, "miss": 0})
            d["requests"] += 1
            if r["ttft"] is not None:
                d["ttfts"].append(r["ttft"])
            if r["breakdown"]:
                d["bds"].append(r["breakdown"])
            d["hit"] = max(d["hit"], r["hit_pages"])
            d["miss"] = max(d["miss"], r["miss_pages"])
        per_replica = {}
        for tag, d in sorted(per_tag.items()):
            b = base.get(tag, {"hit": 0, "miss": 0})
            bd = None
            if d["bds"]:
                bd = {k: round(float(np.mean([x[k] for x in d["bds"]])), 4)
                      for k in ("queue_wait_s", "device_wait_s",
                                "prefill_s", "pipeline_stall_s", "ship_s")}
            per_replica[tag] = {
                "requests": d["requests"],
                "p50_ttft_s": (round(float(np.median(d["ttfts"])), 4)
                               if d["ttfts"] else None),
                "ttft_breakdown": bd,
                "prefix_hit_pages": d["hit"] - b["hit"],
                "prefix_miss_pages": d["miss"] - b["miss"],
            }
        leg = {
            "replicas": n_replicas,
            "requests": len(measured),
            "elapsed_s": round(elapsed, 3),
            "cluster_tokens_per_sec": round(tokens / elapsed, 1),
            "p50_ttft_s": (round(float(np.median(ttfts)), 4)
                           if ttfts else None),
            "per_replica": per_replica,
        }
        serve.delete(name)
        return leg

    legs = {str(n): _run_leg(n) for n in REPLICA_LEGS}
    tps1 = legs["1"]["cluster_tokens_per_sec"]
    eff2 = round(legs["2"]["cluster_tokens_per_sec"] / tps1, 3)
    eff4 = round(legs["4"]["cluster_tokens_per_sec"] / tps1, 3)
    serve.shutdown()
    ray_tpu.shutdown()
    c.shutdown()
    return {
        "metric": "serve_scaleout_efficiency_2x",
        "value": eff2,
        "unit": "x",
        "vs_baseline": None,  # reference publishes no serving numbers
        "detail": {
            "traffic": {
                "sessions": SESSIONS, "prefix_len": PREFIX,
                "tail_len": TAIL, "new_tokens": NEW,
                "page_size": PAGE, "pool_pages": POOL,
                "working_set_pages": SESSIONS * (PREFIX // PAGE),
                "concurrency": CONC,
                "measured_requests": MEASURE_ROUNDS * SESSIONS,
            },
            "prefix_affinity_routing": True,
            "efficiency_2x": eff2,
            "efficiency_4x": eff4,
            "legs": legs,
        },
    }


def bench_data() -> dict:
    """Data-plane leg: map_batches throughput (GiB/s) and PUSH-BASED
    shuffle rows/s on an external-process cluster, every round's rate
    recorded so spread is visible in the artifact. Per-stage bytes are
    priced through the memory plane's accounting — each stage's output
    block oids valued via the GCS memory_table (the same size table
    ``memory_summary`` reconciles against) — not driver-side guesses."""
    import numpy as np

    import ray_tpu
    from ray_tpu import data as rdata
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.data.context import DataContext
    from ray_tpu.runtime import core as _core

    rows = int(os.environ.get("BENCH_DATA_ROWS", "400000"))
    blocks = int(os.environ.get("BENCH_DATA_BLOCKS", "16"))
    rounds = int(os.environ.get("BENCH_DATA_ROUNDS", "3"))
    c = Cluster(external_gcs=True)
    c.add_node(num_cpus=4, external=True)
    ray_tpu.init(address=c.gcs_address)
    rt = _core.get_runtime()

    def priced_bytes(bundles) -> int:
        """Value a stage's output blocks through the GCS size table,
        falling back to bundle metadata for blocks the object directory
        never saw (driver-local memstore blocks)."""
        oids = [r.id.hex() for b in bundles for r in b.refs]
        table = rt._gcs.call("memory_table", oids=oids)["objects"]
        total = 0
        for b in bundles:
            sz = sum(table.get(r.id.hex(), {}).get("size", 0)
                     for r in b.refs)
            total += sz if sz else b.size_bytes
        return total

    detail: dict = {"rows": rows, "blocks": blocks, "rounds": rounds}

    # -- map_batches stage --
    map_gibs: list = []
    map_bytes = 0
    for _ in range(rounds):
        ds = rdata.range(rows, num_blocks=blocks).map_batches(
            lambda b: {"id": b["id"],
                       "val": np.sqrt(b["id"].astype(np.float64))})
        t0 = time.perf_counter()
        bundles = list(ds.iter_bundles())
        wall = time.perf_counter() - t0
        got = sum(b.num_rows for b in bundles)
        assert got == rows, f"map leg lost rows: {got} != {rows}"
        map_bytes = priced_bytes(bundles)
        map_gibs.append(round(map_bytes / wall / (1 << 30), 4))
    detail["map_batches_gib_per_sec"] = max(map_gibs)
    detail["map_batches_rounds_gib_per_sec"] = map_gibs
    detail["map_output_bytes"] = map_bytes

    # -- push-based shuffle stage --
    DataContext.get_current().use_push_based_shuffle = True
    try:
        shuf_rates: list = []
        shuf_bytes = 0
        shuf_wall = 0.0
        for i in range(rounds):
            ds = rdata.range(rows, num_blocks=blocks).random_shuffle(
                seed=i)
            t0 = time.perf_counter()
            bundles = list(ds.iter_bundles())
            shuf_wall = time.perf_counter() - t0
            got = sum(b.num_rows for b in bundles)
            assert got == rows, f"shuffle lost rows: {got} != {rows}"
            shuf_bytes = priced_bytes(bundles)
            shuf_rates.append(round(rows / shuf_wall, 1))
    finally:
        DataContext.get_current().use_push_based_shuffle = False
    detail["push_shuffle_rows_per_sec"] = max(shuf_rates)
    detail["push_shuffle_rounds_rows_per_sec"] = shuf_rates
    detail["push_shuffle_spread"] = round(
        (max(shuf_rates) - min(shuf_rates)) / max(shuf_rates), 4)
    detail["per_stage_bytes_per_sec"] = {
        "map_batches": round(max(map_gibs) * (1 << 30), 1),
        "push_shuffle": round(shuf_bytes / shuf_wall, 1),
    }
    detail["push_shuffle_output_bytes"] = shuf_bytes

    ray_tpu.shutdown()
    c.shutdown()
    return {
        "metric": "data_push_shuffle_rows_per_sec",
        "value": detail["push_shuffle_rows_per_sec"],
        "unit": "rows/s",
        "vs_baseline": None,  # reference publishes no data-plane rates
        "detail": detail,
    }


def bench_core() -> dict:
    """Core-op microbenchmarks (reference: ``ray_perf.py`` — tasks/sec,
    actor calls/sec, put/get throughput on a real multi-process cluster)."""
    import numpy as np

    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    # own knob: BENCH_STEPS tunes the train loop; reusing it here would
    # shrink the op count (noisy rates) whenever train steps are reduced
    n = int(os.environ.get("BENCH_CORE_OPS", "2000"))
    # external GCS + raylet: both run as their own OS processes (exactly
    # like the reference's gcs_server + raylet) — their RPC handling
    # must not share the driver's GIL, which is the hot resource in a
    # submit microbenchmark
    c = Cluster(external_gcs=True)
    c.add_node(num_cpus=4, external=True)
    ray_tpu.init(address=c.gcs_address)
    results = {}

    rounds_detail: dict[str, list] = {}

    def best_of(fn, rounds: int = 5, name: str | None = None) -> float:
        """Steady-state rate: best of N rounds (ray_perf-style repeat).
        Five rounds, not two: this box has ONE cpu, and host scheduling
        noise swings a single round of the pure-Python RPC ops by ±35%
        between identical runs — the max over five draws is what a
        quiet machine reproducibly measures. EVERY round's rate is
        recorded in the artifact (``rounds`` detail) so noise vs real
        regression is visible in the artifact itself."""
        best = 0.0
        seen = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            fn()
            rate = n / (time.perf_counter() - t0)
            seen.append(round(rate, 1))
            best = max(best, rate)
        if name:
            rounds_detail[name] = seen
        return round(best, 1)

    @ray_tpu.remote
    def nop():
        return None

    # warm the worker pool
    ray_tpu.get([nop.remote() for _ in range(8)])
    results["tasks_per_sec"] = best_of(
        lambda: ray_tpu.get([nop.remote() for _ in range(n)]),
        name="tasks_per_sec")

    @ray_tpu.remote
    class A:
        def m(self):
            return None

    a = A.remote()
    ray_tpu.get(a.m.remote())
    results["actor_calls_per_sec"] = best_of(
        lambda: ray_tpu.get([a.m.remote() for _ in range(n)]),
        name="actor_calls_per_sec")

    # tracing hot-path fence input (round 9): the amortized-delta
    # methodology from round 4's probe gates — time the per-call tracing
    # probe (wire_context with tracing ON minus OFF, min-of-k over a
    # large loop) and divide by the measured per-op cost, instead of
    # diffing two noisy end-to-end rates. ci/perf_gate.py holds the
    # ratio under an ABSOLUTE 3% ceiling (a cross-round relative fence
    # is meaningless for a ratio that sits near zero). A traced steady
    # actor round rides along as the loose end-to-end tripwire.
    from ray_tpu.util import tracing as _tracing

    def _probe_cost(iters: int = 200_000, k: int = 5) -> float:
        best = float("inf")
        for _ in range(k):
            t0 = time.perf_counter()
            for _ in range(iters):
                _tracing.wire_context()
            best = min(best, (time.perf_counter() - t0) / iters)
        return best

    cold = _probe_cost()                   # tracing disabled
    _tracing.enable_tracing()
    try:
        with _tracing.span("bench-overhead"):
            hot = _probe_cost()            # enabled + ambient context
            t0 = time.perf_counter()
            ray_tpu.get([a.m.remote() for _ in range(n)])
            traced_rate = round(n / (time.perf_counter() - t0), 1)
    finally:
        _tracing.disable_tracing()
    per_op_s = 1.0 / results["actor_calls_per_sec"]
    results["tracing_overhead"] = {
        "probe_delta_ns": round((hot - cold) * 1e9, 1),
        "per_op_us": round(per_op_s * 1e6, 1),
        "ratio": round(max(hot - cold, 0.0) / per_op_s, 5),
        "traced_actor_calls_per_sec": traced_rate,
    }

    # log-plane capture fence: amortized per-LINE delta — the stamped
    # tee emit (time.time + contextvar reads + %-format + os.write)
    # minus a plain unstamped os.write of the same text — over the
    # per-op cost. Ship/store/echo all run off-process (raylet monitor,
    # GCS), so the emit IS the whole hot-path tax a printing task pays;
    # ci/perf_gate.py holds the ratio under an absolute 3% ceiling.
    import shutil as _sh
    import tempfile as _tf

    from ray_tpu.runtime import log_plane as _log_plane

    _ldir = _tf.mkdtemp(prefix="raytpu-bench-logs-")
    cap = _log_plane.LogCapture("bench", _ldir, max_bytes=256 << 20)
    line = "bench log line with a bit of payload 0123456789"
    raw_fd = os.open(os.path.join(_ldir, "raw.txt"),
                     os.O_WRONLY | os.O_CREAT | os.O_APPEND)
    raw_data = (line + "\n").encode()

    def _line_cost(fn, iters: int = 100_000, k: int = 5) -> float:
        best = float("inf")
        for _ in range(k):
            t0 = time.perf_counter()
            for _ in range(iters):
                fn()
            best = min(best, (time.perf_counter() - t0) / iters)
        return best

    hot_line = _line_cost(lambda: cap.emit("o", line))
    cold_line = _line_cost(lambda: os.write(raw_fd, raw_data))
    os.close(raw_fd)
    cap.close()
    _sh.rmtree(_ldir, ignore_errors=True)
    results["log_overhead"] = {
        "emit_ns": round(hot_line * 1e9, 1),
        "plain_write_ns": round(cold_line * 1e9, 1),
        "delta_ns": round((hot_line - cold_line) * 1e9, 1),
        "per_op_us": round(per_op_s * 1e6, 1),
        "ratio": round(max(hot_line - cold_line, 0.0) / per_op_s, 5),
    }

    small = b"x" * 1024
    put_refs: list = []

    def do_puts():
        put_refs.clear()
        put_refs.extend(ray_tpu.put(small) for _ in range(n))

    results["puts_1kb_per_sec"] = best_of(do_puts, name="puts_1kb_per_sec")
    results["gets_1kb_per_sec"] = best_of(lambda: ray_tpu.get(put_refs),
                                          name="gets_1kb_per_sec")

    # memory-plane accounting fence: the per-put ownership tax —
    # creation-callsite capture + owned-table insert (the whole
    # addition driver.put pays for runtime/refcount.py accounting) —
    # amortized min-of-k, minus the disabled-path guard, divided by the
    # measured per-put cost above. ci/perf_gate.py holds the ratio
    # under an ABSOLUTE 3% ceiling (same methodology as the tracing and
    # log fences: never a diff of two noisy end-to-end rates).
    from ray_tpu.runtime import refcount as _refcount

    _rc = _refcount.RefCounter()
    _oids = ["%032x" % i for i in range(8192)]

    # SHORT rounds, many reps, interleaved: the probe runs inside a
    # live runtime whose flusher threads steal the GIL every few tens
    # of ms — a 100k-iter round always eats a wakeup, a 20k-iter round
    # lets the min dodge them; interleaving samples hot and cold under
    # the same box conditions
    def _mem_round(fn, iters: int = 20_000) -> float:
        t0 = time.perf_counter()
        for i in range(iters):
            fn(i)
        return (time.perf_counter() - t0) / iters

    _hot_fn = lambda i: _rc.note_owned_here(_oids[i & 8191], 1024)
    _cold_fn = lambda i: _refcount.is_active()
    _mem_round(_hot_fn)
    _mem_round(_cold_fn)  # warm both paths
    hot_mem = cold_mem = float("inf")
    for _ in range(15):
        hot_mem = min(hot_mem, _mem_round(_hot_fn))
        cold_mem = min(cold_mem, _mem_round(_cold_fn))
    per_put_s = 1.0 / results["puts_1kb_per_sec"]
    results["memory_accounting_overhead"] = {
        "probe_hot_ns": round(hot_mem * 1e9, 1),
        "probe_cold_ns": round(cold_mem * 1e9, 1),
        "per_put_us": round(per_put_s * 1e6, 1),
        "ratio": round(max(hot_mem - cold_mem, 0.0) / per_put_s, 5),
    }

    big = np.zeros(32 << 18, dtype=np.float64)  # 64 MiB
    t0 = time.perf_counter()
    bref = ray_tpu.put(big)
    put_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = ray_tpu.get(bref)
    get_s = time.perf_counter() - t0
    assert out.nbytes == big.nbytes
    results["put_gbps"] = round(big.nbytes / put_s / 1e9, 2)
    results["get_gbps"] = round(big.nbytes / get_s / 1e9, 2)
    results["rounds"] = rounds_detail

    ray_tpu.shutdown()
    c.shutdown()
    return {
        "metric": "core_tasks_per_sec",
        "value": results["tasks_per_sec"],
        "unit": "tasks/s",
        "vs_baseline": None,  # reference's numbers are external (nightly)
        "detail": results,
    }


def bench_envelope() -> dict:
    """Bounded scalability-envelope probe: how far the cluster runtime
    stretches in ONE artifact-visible leg (the full nightly tier runs
    10x+ these axes; this keeps a driver-captured record every round).

    Three axes on an external-process GCS + raylet:
      * drain rate of ``bench_envelope_tasks`` queued no-op tasks
        (submitted in windows so the host never holds every ref),
      * creation rate of ``bench_envelope_actors`` trivial actors —
        the fork-server worker pool (``runtime/prestart.py``) is what
        moves this axis: each actor is an ``os.fork()`` of the warm
        zygote template, not a cold interpreter boot,
      * steady-state actor calls/s round-robined over all of them.
    """
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.utils.config import get_config

    cfg = get_config()
    n_tasks = cfg.bench_envelope_tasks
    n_actors = cfg.bench_envelope_actors
    c = Cluster(external_gcs=True)
    c.add_node(num_cpus=4, external=True)
    ray_tpu.init(address=c.gcs_address)
    detail: dict = {"tasks": n_tasks, "actors": n_actors}

    @ray_tpu.remote
    def nop(i):
        return i

    # warm the pool + zygote template so the probe measures the runtime,
    # not first-boot imports
    ray_tpu.get([nop.remote(i) for i in range(8)])

    window = min(25_000, n_tasks)
    t0 = time.perf_counter()
    done = 0
    while done < n_tasks:
        take = min(window, n_tasks - done)
        out = ray_tpu.get([nop.remote(done + i) for i in range(take)])
        assert out[0] == done and out[-1] == done + take - 1
        done += take
    detail["envelope_tasks_per_sec"] = round(
        n_tasks / (time.perf_counter() - t0), 1)

    @ray_tpu.remote(num_cpus=0)
    class A:
        def __init__(self, i):
            self.i = i

        def who(self):
            return self.i

    # creation clock stops when every actor has ANSWERED a call (alive
    # and schedulable, not merely submitted); per-phase decomposition
    # (register / place / ready / resolve) comes from the driver's
    # registration coalescer + the GCS actor-plane counters
    from ray_tpu.runtime import core as _core
    from ray_tpu.runtime.rpc import RpcClient

    rt = _core.get_runtime()
    gcs_probe = RpcClient(tuple(c.gcs_address), label="driver")
    gcs_probe.call("actor_plane_stats", reset=True)
    polls_before = getattr(rt, "_actor_get_polls", 0)
    t0 = time.perf_counter()
    actors = [A.remote(i) for i in range(n_actors)]
    submit_s = time.perf_counter() - t0
    if hasattr(rt, "_reg_drain"):
        for a in actors:   # registration acks (cheap: set lookups)
            rt._reg_drain(a._actor_id.hex())
    register_s = time.perf_counter() - t0
    got = ray_tpu.get([a.who.remote() for a in actors])
    create_s = time.perf_counter() - t0
    assert got == list(range(n_actors))
    plane = gcs_probe.call("actor_plane_stats")
    gcs_probe.close()
    detail["actors_created_per_sec"] = round(n_actors / create_s, 1)
    detail["actor_create_elapsed_s"] = round(create_s, 1)
    detail["creation_phases"] = {
        "submit_s": round(submit_s, 3),
        "register_s": round(register_s, 3),
        "place_mean_ms": round(1e3 * plane["place_s"]
                               / max(plane["placed"], 1), 2),
        "ready_mean_ms": round(1e3 * plane["ready_s"]
                               / max(plane["ready"], 1), 2),
        "resolve_and_first_call_s": round(create_s - register_s, 3),
        "register_batches": plane["register_batches"],
        "register_batch_max": plane["register_batch_max"],
        "host_batches": plane["host_batches"],
        "host_batch_max": plane["host_batch_max"],
        "ready_batches": plane["ready_batches"],
    }

    # steady state: every live actor answers again, round-robin; the
    # location-resolve rate rides the warm pushed table (zero polls).
    # bench_profile_enabled samples the DRIVER's threads across exactly
    # this window (the submit/await path is driver-side — the axis that
    # dipped when the actor count grew) and writes the collapsed-stack
    # artifact any flamegraph renderer consumes.
    profiler = None
    if cfg.bench_profile_enabled:
        import threading as _threading

        from ray_tpu.util.profiling import sample_profile

        prof_out: list = []
        prof_stop = _threading.Event()
        profiler = _threading.Thread(
            target=lambda: prof_out.append(
                sample_profile(duration_s=600.0, hz=200, stop=prof_stop)),
            daemon=True, name="bench-profiler")
        profiler.start()
    calls = 4 * n_actors
    t0 = time.perf_counter()
    refs = [actors[i % n_actors].who.remote() for i in range(calls)]
    ray_tpu.get(refs)
    steady_s = time.perf_counter() - t0
    detail["steady_actor_calls_per_sec"] = round(calls / steady_s, 1)
    if profiler is not None:
        prof_stop.set()
        profiler.join(timeout=10)
        if prof_out:
            prof = prof_out[0]
            path = os.environ.get("BENCH_PROFILE_OUT",
                                  "PROFILE_envelope.folded")
            with open(path, "w") as f:
                f.write(prof["folded"] + "\n")
            detail["profile"] = {
                "artifact": path,
                "samples": prof["samples"],
                "duration_s": prof["duration_s"],
                # top frames inline so the artifact JSON alone shows
                # where the steady-call window went
                "top_stacks": prof["folded"].splitlines()[:5],
            }
    t0 = time.perf_counter()
    for a in actors:
        rt._actor_location(a._actor_id.hex())
    detail["actor_resolves_per_sec"] = round(
        n_actors / max(time.perf_counter() - t0, 1e-9), 1)
    detail["resolve_fallback_polls"] = (
        getattr(rt, "_actor_get_polls", 0) - polls_before)

    for a in actors:
        ray_tpu.kill(a)
    ray_tpu.shutdown()
    c.shutdown()
    return {
        "metric": "envelope_actors_created_per_sec",
        "value": detail["actors_created_per_sec"],
        "unit": "actors/s",
        "vs_baseline": None,  # reference envelope publishes no rates
        "detail": detail,
    }


def bench_chaos_soak() -> dict:
    """Seeded crash/partition soak with conservation invariants
    (ray_tpu/chaos_soak.py). Knobs: CHAOS_SOAK_DURATION (seconds per
    seed, default 300), CHAOS_SOAK_SEEDS (comma list, default "0"),
    CHAOS_SOAK_OUT (report path, default CHAOS_r10.json next to this
    file). The gate metric is the violation count — the MTTR means ride
    in detail for the perf-gate ceilings."""
    from ray_tpu.chaos_soak import run_soak_matrix

    duration = float(os.environ.get("CHAOS_SOAK_DURATION", "300"))
    seeds = [int(s) for s in
             os.environ.get("CHAOS_SOAK_SEEDS", "0").split(",")
             if s.strip()]
    out = os.environ.get(
        "CHAOS_SOAK_OUT",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "CHAOS_r10.json"))
    report = run_soak_matrix(
        duration, seeds, out_path=out,
        log=lambda *a: print(*a, file=sys.stderr))
    detail = {"seeds": report["seeds"],
              "chaos_soak_invariant_violations":
                  report["chaos_soak_invariant_violations"]}
    for key in ("chaos_mttr_replica_mean_s", "chaos_mttr_raylet_mean_s"):
        if key in report:
            detail[key] = report[key]
    if isinstance(report.get("probe_overhead"), dict):
        detail["probe_overhead"] = report["probe_overhead"]
    return {
        "metric": "chaos_soak_invariant_violations",
        "value": report["chaos_soak_invariant_violations"],
        "unit": "violations",
        "vs_baseline": None,
        "detail": detail,
    }


def _bench_subprocess(mode: str, timeout: float = 900.0) -> dict:
    """Run one bench mode in a FRESH interpreter (parity with a
    standalone ``BENCH_MODE=<mode>`` run; ray_perf runs standalone too).
    bench_all runs every such leg BEFORE its in-parent legs, while the
    parent is still off JAX: a parent that has initialised a backend
    holds the chip, and on a small host its dispatch threads would steal
    timeslices from the child's cluster."""
    import signal
    import subprocess

    env = dict(os.environ)
    env["BENCH_MODE"] = mode
    # own process group: a timeout kill must take the child's external
    # raylet/GCS processes down with it, not orphan them on the host
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        start_new_session=True)
    try:
        stdout, stderr = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait()
        raise RuntimeError(f"{mode} bench subprocess timed out") from None
    if proc.returncode != 0 or not stdout.strip():
        raise RuntimeError(
            f"{mode} bench subprocess failed (rc={proc.returncode}): "
            f"{(stderr or '')[-2000:]}")
    return json.loads(stdout.strip().splitlines()[-1])


def bench_core_subprocess() -> dict:
    return _bench_subprocess("core")


def bench_all() -> dict:
    """Train headline + serve/core sub-benchmarks folded into detail. A
    leg that fails is recorded as an error string, so the artifact keeps
    what did complete, and makes the run exit non-zero.

    Order is the device rule: every leg that is a child process runs
    first, while this parent is off JAX (it then neither holds the chip
    nor, on a small host, steals timeslices from the child's cluster
    with its dispatch threads); then the legs that use the chip, all in
    this one process."""
    subs = [("core", bench_core_subprocess),
            ("data", lambda: _bench_subprocess("data", 1800.0)),
            ("envelope", lambda: _bench_subprocess("envelope", 1800.0)),
            # multi-replica scale-out leg: builds a worker-process cluster
            ("serve_scaleout",
             lambda: _bench_subprocess("serve_scaleout", 1800.0))]
    if os.environ.get("BENCH_PRESET", "base") != "small":
        # the ~1B entry is a real-chip measurement; a CPU smoke run
        # (BENCH_PRESET=small) must not train a 1B model on host
        subs += [("train_large", lambda: bench_train("large")),
                 ("train_longctx", lambda: bench_train("longctx"))]
    subs.append(("serve", bench_serve))
    pre: dict = {}
    for name, fn in subs:
        try:
            sub = fn()
            pre[name] = {
                "metric": sub["metric"], "value": sub["value"],
                "unit": sub["unit"], **sub["detail"]}
        except Exception as e:  # noqa: BLE001
            pre[name] = {"error": f"{type(e).__name__}: {e}"}
    try:
        result = bench_train()
    except Exception as e:  # noqa: BLE001 — a late headline failure
        # (e.g. chip preemption) must not discard the completed sub
        # results: degrade to an artifact that carries them + the error
        result = {"metric": "llama_train_tokens_per_sec_per_chip",
                  "value": 0.0, "unit": "tokens/s/chip",
                  "vs_baseline": None,
                  "detail": {"error": f"{type(e).__name__}: {e}"}}
    result["detail"].update(pre)
    return result


def failed_legs(result: dict) -> list:
    """Names of the legs of a bench result that recorded an error."""
    detail = result.get("detail", {})
    legs = [k for k, v in detail.items()
            if isinstance(v, dict) and "error" in v]
    return legs + (["train"] if "error" in detail else [])


if __name__ == "__main__":
    mode = os.environ.get("BENCH_MODE", "all")
    fn = {"serve": bench_serve, "core": bench_core,
          "data": bench_data,
          "envelope": bench_envelope,
          "serve_scaleout": bench_serve_scaleout,
          "chaos_soak": bench_chaos_soak,
          "train": bench_train,
          "train_telemetry": bench_train_telemetry}.get(mode, bench_all)
    from ray_tpu._private.accelerator import enable_compile_cache

    enable_compile_cache()   # before the first compile, children included
    result = fn()
    print(json.dumps(result))
    failed = failed_legs(result)
    if failed:
        print(f"bench legs failed: {failed}", file=sys.stderr)
    sys.exit(1 if failed else 0)

"""Drives ``JaxTrainer`` through a pre-training job: set-up (state on the
device from the seed, the step compiled, the first step's loss held to the
plain reference's), then steps on fresh seeded batches for the window."""

from __future__ import annotations

import importlib
import math
import time

from benchmark import reference, systems
from benchmark.harness import RunRecord, say, span

# The trainer's bf16 loss against the float32 reference's on the same
# weights and batch, at the first step and again at the second (on the
# weights the first step's update left). The tolerance is a small multiple
# of the gaps the chip runs printed (PERF.md, PR 23). Targets are random
# tokens, so the loss answers a logit error only at second order: this
# holds the forward, the loss and the state the update leaves to the
# reference, and does not prove a gradient right (PERF.md, open questions).
LOSS_TOL = 0.002


def run(ctx) -> RunRecord:
    import jax

    rec = RunRecord(ctx)
    config, traffic = ctx.config, ctx.traffic
    gen = importlib.import_module(
        "benchmark.generators." + traffic["generator"])
    job = gen.build(traffic, config, config["system"], ctx.args.seed,
                    ctx.args.seconds)
    ctx.phases.mark("job")
    import ray_tpu.train.trainer  # noqa: F401 - timed apart from its use
    ctx.phases.mark("program import")
    trainer = systems.make_trainer(config)
    n_chips = trainer.mesh.devices.size
    key = jax.block_until_ready(systems.seed_key(ctx.args.seed))
    ctx.phases.mark("trainer and mesh")
    state = jax.block_until_ready(trainer.init_state(key))
    ctx.phases.mark("state init")

    data_key = jax.random.fold_in(key, 1)
    probe = jax.eval_shape(job["make_batch"], data_key, 0)
    make_batch = jax.jit(job["make_batch"],
                         out_shardings=trainer._batch_shardings(probe))
    batch0 = jax.block_until_ready(make_batch(data_key, 0))
    ctx.phases.mark("first batch")

    family = systems.family(config)     # its reference and its counts
    ref_loss = reference.loss(family.logits, config, state.params, batch0,
                              rows_per_call=n_chips)
    ctx.phases.mark("reference loss")

    # the step, compiled (or read from the cache) ahead of the first call,
    # for what the compiler says of its temporaries
    step_fn = trainer.compile_step(state, batch0)
    compiled = step_fn.lower(state, batch0).compile()
    temp_bytes = systems.program_bytes(compiled)
    del compiled
    ctx.phases.mark("step compile")

    state, metrics = trainer.train_step(state, batch0)
    loss0 = float(metrics["loss"])
    batch1 = make_batch(data_key, 1)
    ref_loss1 = reference.loss(family.logits, config, state.params, batch1,
                               rows_per_call=n_chips)
    state, metrics = trainer.train_step(state, batch1)
    loss1 = float(metrics["loss"])              # the steady-state path once
    ctx.phases.mark("first steps")
    live = systems.live_bytes()
    peak_bytes = max(live, temp_bytes)

    tokens_per_step = job["batch"] * job["seq_len"]
    ctx.tracer.start_in(0.5)
    ctx.window_opens()
    t0 = time.perf_counter()
    deadline = t0 + ctx.args.seconds
    step, done, losses, prev = 2, 0, [], None
    while True:
        with span("make_batch"):
            batch = make_batch(data_key, step)
        with span("step_call"):
            state, metrics = trainer.train_step(state, batch)
        step += 1
        if prev is not None:
            with span("wait"):
                losses.append(float(prev["loss"]))   # waits for that step
            done += 1
        prev = metrics
        if time.perf_counter() >= deadline:
            break
    with span("wait"):
        losses.append(float(prev["loss"]))
    done += 1
    elapsed = time.perf_counter() - t0
    ctx.compiles.close()

    finite = all(math.isfinite(x) for x in losses) and math.isfinite(loss0)
    gap = max(abs(loss0 - ref_loss), abs(loss1 - ref_loss1))
    rec.correct = finite and gap <= LOSS_TOL
    rec.attempted, rec.failed = done, 0 if finite else done
    rate = done * tokens_per_step / elapsed / n_chips
    rec.end_to_end["train_tokens_per_s_per_chip"] = rate
    rec.counters.update(
        tokens_per_s_per_chip=rate, tokens_per_step=tokens_per_step,
        batch=job["batch"], seq_len=job["seq_len"], chips=n_chips,
        steps=done, step_program="jit__step")
    rec.memory_peak_bytes = max(
        peak_bytes,
        max(d.memory_stats()["peak_bytes_in_use"] for d in jax.devices())
        if ctx.device["platform"] == "tpu" else 0)
    rec.counters["peak_hbm_bytes"] = peak_bytes
    rec.notes.update(steps=done, elapsed_s=elapsed, loss0=loss0,
                     reference_loss=ref_loss, loss1=loss1,
                     reference_loss1=ref_loss1, loss_gap=gap, tol=LOSS_TOL,
                     last_loss=losses[-1], live_gb=live / 1e9,
                     temp_gb=temp_bytes / 1e9,
                     flops_per_token=family.train_flops_per_token(
                         config, job["seq_len"]))
    say("train", **rec.notes)
    return rec

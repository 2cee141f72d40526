#!/usr/bin/env python3
"""The quickest proof that ray_tpu's main path still starts on the chip.

    python chip_smoke.py             # one chip: device, kernels, train, serve
    python chip_smoke.py --chips 4   # one 2x2 host: the fsdp-4 train step only

Each phase is a child process, one after another, so each owns the chip
alone and gives it back when it exits; this parent never imports JAX.
Phases drive the entry points a user calls (``JaxTrainer``, ``serve.run``
of an ``LLMDeployment`` on a ``Cluster``) at the published widths of
Llama-3-8B with the depth cut to fit, weights and data made from
``--seed``, and check what comes out against the repo's plain
references. There is no CPU fallback and no interpret mode: off a TPU
the first phase fails. A failed phase ends the run with a non-zero exit
code at once. On success the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Everything printed before it (``smoke <phase>: ...``) is smoke output:
seconds and counts that show the run was warm or cold, not benchmark
numbers. The compile cache is where ``JAX_COMPILATION_CACHE_DIR`` says,
else ``<checkout>/.jax_cache``; the children and the replica's worker
share it.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import signal
import statistics
import subprocess
import sys
import threading
import time

PHASES = {1: ("device", "kernels", "train", "serve"), 4: ("fsdp4",)}
PHASE_TIMEOUT_S = 900
RESULT_MARK = "SMOKE_RESULT "

# Tolerances, all for bf16 compute against an fp32-accumulating reference.
KERNEL_TOL = 3e-2       # |flash - reference| <= tol * max(1, max|reference|)
SERVE_LOGIT_TOL = 0.1   # reference logit of the engine's token vs the max
FSDP4_LOSS_TOL = 0.02   # sharded vs single-device step-0 loss


def say(phase: str, **facts):
    print(f"smoke {phase}: " + " ".join(
        f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
        for k, v in facts.items()), flush=True)


def check(ok: bool, what: str):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _require_tpu(platform: str):
    check(platform == "tpu", f"platform is {platform!r}, not 'tpu'")


def _require_kernel(hlo_text: str, what: str):
    check("tpu_custom_call" in hlo_text,
          f"{what} does not contain the Pallas flash kernel")


def _devices(want: int) -> dict:
    import jax

    devs = jax.devices()
    _require_tpu(devs[0].platform)
    check(len(devs) == want,
          f"found {len(devs)} chips, this run is for --chips {want}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# the configurations: Llama-3-8B's published widths, depth cut to fit
# ---------------------------------------------------------------------------

def _llama3_8b(n_layers: int, **kw):
    from ray_tpu.models import llama

    full = llama.llama3_8b()
    say("config", model="llama3_8b", d_model=full.d_model,
        heads=f"{full.n_heads}/{full.n_kv_heads}x{full.head_dim}",
        d_ff=full.d_ff, vocab=full.vocab_size,
        reduced=f"n_layers {full.n_layers}->{n_layers}")
    return dataclasses.replace(full, n_layers=n_layers, **kw)


def train_config():
    return _llama3_8b(2, remat="dots_attn"), 2, 2048    # cfg, batch, seq


def serve_config():
    return _llama3_8b(8)


def fsdp4_config():
    return _llama3_8b(4, remat="dots_attn"), 4, 2048


KERNEL_SHAPE = dict(batch=2, seq=2048, heads=32, kv_heads=8, head_dim=128)
TRAIN_STEPS = 6
# 5.6 GB of weights + 3.8 GB of pages, and the prefill program's
# temporaries (the pool itself stays in place: it is not among them)
SERVE = dict(num_pages=896, max_batch=8, max_len=2048, new_tokens=32,
             prompts=(64, 64, 300, 640, 640, 1024), shared_prefix=512)


# ---------------------------------------------------------------------------
# phases (each runs in its own process)
# ---------------------------------------------------------------------------

def phase_device(args) -> dict:
    return _devices(1)


def _flash(q, k, v):
    from ray_tpu.ops.flash_attention import flash_attention

    return flash_attention(q, k, v, causal=True)   # interpret=False


def phase_kernels(args) -> dict:
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import reference_attention

    device = _devices(1)
    reference = functools.partial(reference_attention, causal=True)
    s = KERNEL_SHAPE
    keys = jax.random.split(jax.random.key(args.seed), 4)

    def operand(key, heads):
        return jax.random.normal(
            key, (s["batch"], s["seq"], heads, s["head_dim"]),
            jnp.float32).astype(jnp.bfloat16)

    q, k, v = (operand(keys[0], s["heads"]), operand(keys[1], s["kv_heads"]),
               operand(keys[2], s["kv_heads"]))
    cot = operand(keys[3], s["heads"]).astype(jnp.float32)

    def close(name, got, want):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(got - want)))
        bound = KERNEL_TOL * max(1.0, float(jnp.max(jnp.abs(want))))
        check(bool(jnp.isfinite(got).all()), f"flash {name} is not finite")
        check(err <= bound, f"flash {name} differs from the reference by "
              f"{err:.4f} > {bound:.4f}")
        return err

    def loss(attn, q, k, v, cot):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) * cot)

    # the cotangent is an argument: closed over, its 67 MB would be a
    # constant in every executable, and in the compile cache
    facts = {}
    for name, fn, ref, args in [
            ("fwd", _flash, reference, (q, k, v)),
            ("grad",
             jax.grad(functools.partial(loss, _flash), argnums=(0, 1, 2)),
             jax.grad(functools.partial(loss, reference),
                      argnums=(0, 1, 2)), (q, k, v, cot))]:
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        facts[f"{name}_compile_s"] = time.perf_counter() - t0
        _require_kernel(compiled.as_text(), f"flash {name}")
        got, want = compiled(*args), jax.jit(ref)(*args)
        errs = [close(f"{name}[{i}]", g, w) for i, (g, w) in enumerate(
            zip(jax.tree.leaves(got), jax.tree.leaves(want)))]
        facts[f"{name}_max_err"] = max(errs)
    say("kernels", shape="x".join(str(x) for x in s.values()),
        tol=KERNEL_TOL, **facts)
    return device


def _peak_gb() -> float:
    import jax

    return max(d.memory_stats()["peak_bytes_in_use"]
               for d in jax.devices()) / 1e9


def _seeded_batch(seed: int, batch: int, seq: int, vocab: int):
    import jax
    import jax.numpy as jnp

    return jax.random.randint(jax.random.key(seed + 1), (batch, seq + 1),
                              0, vocab, dtype=jnp.int32)


def _compiled_step_text(trainer, state, batch) -> tuple:
    """Compile the trainer's own step ahead of time, to read the program
    the chip will run. The train_step that follows finds it in the
    persistent cache."""
    import jax

    t0 = time.perf_counter()
    compiled = trainer.compile_step(state, batch).lower(
        state, jax.device_put(batch, trainer._batch_shardings(batch))
    ).compile()
    return compiled.as_text(), time.perf_counter() - t0


def _run_steps(trainer, state, batch, steps: int) -> tuple:
    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, metrics = trainer.train_step(state, batch)
        losses.append(float(metrics["loss"]))    # waits for the device
        times.append(time.perf_counter() - t0)
    check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    return state, losses, times


def _check_first_loss(loss0: float, vocab: int):
    # fan-in init gives logits of unit variance, and the cross entropy of
    # V such logits against a random target is ln V + 1/2
    want = math.log(vocab) + 0.5
    check(abs(loss0 - want) < 0.25,
          f"step-0 loss {loss0:.4f} is not within 0.25 of ln V + 1/2 = "
          f"{want:.4f}")


def phase_train(args) -> dict:
    import jax

    from ray_tpu.models import llama
    from ray_tpu.train.trainer import JaxTrainer, TrainConfig

    device = _devices(1)
    cfg, batch_size, seq = train_config()
    # README "Training on a mesh", on one chip. One warm-up step, else
    # six steps of the default 100-step ramp move bf16 weights by less
    # than their spacing
    trainer = JaxTrainer(cfg, TrainConfig(
        mesh_axes={"dp": 1}, strategy="dp", fused_loss=True,
        warmup_steps=1))
    t0 = time.perf_counter()
    state = jax.block_until_ready(
        trainer.init_state(jax.random.key(args.seed)))
    init_s = time.perf_counter() - t0
    batch = _seeded_batch(args.seed, batch_size, seq, cfg.vocab_size)
    text, compile_s = _compiled_step_text(trainer, state, batch)
    _require_kernel(text, "the compiled train step")
    state, losses, times = _run_steps(trainer, state, batch, TRAIN_STEPS)
    _check_first_loss(losses[0], cfg.vocab_size)
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")
    say("train", params=llama.num_params(state.params),
        batch=f"{batch_size}x{seq}", init_s=init_s, compile_s=compile_s,
        first_step_s=times[0], step_s=statistics.median(times[1:]),
        kernel_calls=text.count("tpu_custom_call"),
        losses="/".join(f"{x:.4f}" for x in losses),
        peak_gb=_peak_gb())
    return device


def phase_fsdp4(args) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import llama
    from ray_tpu.train.trainer import JaxTrainer, TrainConfig

    device = _devices(4)
    cfg, batch_size, seq = fsdp4_config()
    trainer = JaxTrainer(cfg, TrainConfig(
        mesh_axes={"fsdp": 4}, strategy="fsdp", fused_loss=True,
        warmup_steps=1))
    state = jax.block_until_ready(
        trainer.init_state(jax.random.key(args.seed)))
    batch = _seeded_batch(args.seed, batch_size, seq, cfg.vocab_size)

    # code that never ran on more than one chip may put everything on
    # device 0: each chip must hold about a quarter of the state
    total = sum(x.nbytes for x in jax.tree.leaves(state))
    held = {d.id: 0 for d in jax.devices()}
    for leaf in jax.tree.leaves(state):
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    shares = {d: b / total for d, b in held.items()}
    check(all(0.24 <= s <= 0.27 for s in shares.values()),
          f"state is not split in quarters over the chips: {shares}")

    # what it is compared with: the plain forward and loss, on one chip,
    # on the same parameters (gathered in bf16) and the same batch; a row
    # at a time, the fp32 logits of four rows being 4.2 GB
    one_chip = SingleDeviceSharding(jax.devices()[0])
    params1 = jax.device_put(state.params, one_chip)
    rows = jax.device_put(batch, one_chip)

    @jax.jit
    def plain_loss(params, row):
        logits = llama.forward(cfg, params, row[None, :-1],
                               attn_impl="reference")
        return llama.cross_entropy_loss(logits, row[None, 1:])

    ref_loss = float(jnp.mean(jnp.stack(
        [plain_loss(params1, rows[i]) for i in range(batch_size)])))
    del params1

    text, compile_s = _compiled_step_text(trainer, state, batch)
    _require_kernel(text, "the compiled fsdp-4 train step")
    collectives = {op: text.count(f" {op}(") + text.count(f" {op}-start(")
                   for op in ("all-gather", "reduce-scatter", "all-reduce",
                              "all-to-all", "collective-permute")}
    check(collectives["all-gather"] > 0,
          f"no all-gather of the sharded parameters: {collectives}")
    # the gradients' reduce-scatter: the TPU compiler may emit it under
    # that name, as all-reduce + slice, or as a ring of collective-permutes
    # fused with the matmuls
    check(collectives["reduce-scatter"] + collectives["all-reduce"]
          + collectives["collective-permute"] > 0,
          f"no collective reduces the gradients: {collectives}")

    state, losses, times = _run_steps(trainer, state, batch, 3)
    _check_first_loss(losses[0], cfg.vocab_size)
    check(abs(losses[0] - ref_loss) <= FSDP4_LOSS_TOL,
          f"sharded step-0 loss {losses[0]:.4f} and single-device "
          f"reference {ref_loss:.4f} differ by more than {FSDP4_LOSS_TOL}")
    say("fsdp4", params=llama.num_params(state.params),
        batch=f"{batch_size}x{seq}",
        shares="/".join(f"{s:.3f}" for s in shares.values()),
        sharded_loss=losses[0], single_device_loss=ref_loss,
        loss_diff=abs(losses[0] - ref_loss), tol=FSDP4_LOSS_TOL,
        compile_s=compile_s, first_step_s=times[0],
        step_s=times[-1], kernel_calls=text.count("tpu_custom_call"),
        collectives=json.dumps(collectives).replace(" ", ""),
        losses="/".join(f"{x:.4f}" for x in losses),
        peak_gb=_peak_gb())
    return device


def build_serve_model(cfg, seed: int):
    """The deployment's model_builder: bf16 weights initialised on the
    replica's device from the seed."""
    import jax

    from ray_tpu.models import llama

    params = jax.jit(functools.partial(llama.init_params, cfg))(
        jax.random.key(seed))
    return cfg, jax.block_until_ready(params)


def _smoke_deployment():
    """LLMDeployment plus the two questions the smoke asks of the process
    that owns the chip. A class of its own only for those; requests go
    through LLMDeployment.__call__ untouched."""
    from ray_tpu.serve.llm import LLMDeployment

    class SmokeLLM(LLMDeployment):
        def device_info(self) -> dict:
            import jax

            dev = jax.devices()[0]
            return {"pid": os.getpid(), "platform": dev.platform,
                    "kind": dev.device_kind, "count": len(jax.devices()),
                    "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
                    "memory": dev.memory_stats()}

        def reference_gap(self, prompt, tokens) -> float:
            """Teacher-force the engine's tokens through the plain
            forward: the largest amount by which the reference's logit
            of an engine token falls short of the reference's maximum."""
            import jax
            import jax.numpy as jnp
            import numpy as np

            from ray_tpu.models import llama

            eng = self._engine
            seq = jnp.asarray(np.concatenate(
                [prompt, tokens[:-1]]).astype(np.int32))[None]
            logits = jax.jit(functools.partial(
                llama.forward, eng.cfg, attn_impl="reference"))(
                    eng.params, seq)[0, len(prompt) - 1:]
            chosen = jnp.take_along_axis(
                logits, jnp.asarray(tokens, jnp.int32)[:, None], axis=1)
            return float(jnp.max(logits.max(axis=1) - chosen[:, 0]))

    return SmokeLLM


def phase_serve(args) -> dict:
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.runtime.prestart import jax_backends_initialized

    cfg = serve_config()
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in SERVE["prompts"]]
    # the two 640s share their first 512 tokens
    a, b = [i for i, n in enumerate(SERVE["prompts"]) if n == 640]
    prompts[b][:SERVE["shared_prefix"]] = prompts[a][:SERVE["shared_prefix"]]
    new = SERVE["new_tokens"]

    t0 = time.perf_counter()
    cluster = Cluster()
    cluster.add_node(num_cpus=4, num_tpus=1)
    ray_tpu.init(address=cluster.gcs_address)
    try:
        handle = serve.run(serve.deployment(
            _smoke_deployment(), resources_per_replica={"TPU": 1}).bind(
                model_builder=functools.partial(
                    build_serve_model, cfg, args.seed),
                max_batch=SERVE["max_batch"], max_len=SERVE["max_len"],
                num_pages=SERVE["num_pages"]))

        def ask(method, *a):
            return ray_tpu.get(
                handle.options(method_name=method).remote(*a), timeout=600)

        info = ask("device_info")
        ready_s = time.perf_counter() - t0
        _require_tpu(info["platform"])
        check(info["pid"] != os.getpid() and info["count"] == 1,
              f"the replica does not own one chip in its own process: {info}")
        device = {k: info[k] for k in ("platform", "kind", "count")}

        # all but the second 640 at once: pages register at prefill
        # dispatch, so that one waits until the first has finished
        t0 = time.perf_counter()
        first = [i for i in range(len(prompts)) if i != b]
        refs = [handle.remote(prompts[i], max_new_tokens=new) for i in first]
        outs = dict(zip(first, ray_tpu.get(refs, timeout=900)))
        batch_s = time.perf_counter() - t0
        hits0 = ask("stats")["prefix_cache"]["hit_pages"]
        t0 = time.perf_counter()
        outs[b] = ray_tpu.get(handle.remote(prompts[b], max_new_tokens=new),
                              timeout=600)
        shared_s = time.perf_counter() - t0
        stats = ask("stats")
        hits = stats["prefix_cache"]["hit_pages"] - hits0

        for i, out in outs.items():
            check(len(out) == new
                  and all(0 <= t < cfg.vocab_size for t in out),
                  f"request {i} ({len(prompts[i])} tokens in) returned "
                  f"{len(out)} tokens, not {new} valid ones")
        check(hits > 0, "the shared-prefix request hit no cached page")
        gaps = [ask("reference_gap", prompts[i], outs[i])
                for i, n in enumerate(SERVE["prompts"]) if n == 64]
        check(max(gaps) <= SERVE_LOGIT_TOL,
              f"engine tokens fall short of the reference's best logit by "
              f"{gaps}, more than {SERVE_LOGIT_TOL}")
        memory = ask("device_info")["memory"]
        say("serve", replica_pid=info["pid"],
            visible_chips=info["visible_chips"], replica_ready_s=ready_s,
            requests=len(outs), tokens=sum(len(o) for o in outs.values()),
            first_five_s=batch_s, shared_prefix_s=shared_s,
            prefix_hit_pages=hits, kv_pages=stats["kv_pages_total"],
            kv_pool_gb=stats["kv_pages_bytes"] / 1e9,
            reference_gap=max(gaps), tol=SERVE_LOGIT_TOL,
            peak_gb=memory["peak_bytes_in_use"] / 1e9,
            limit_gb=memory["bytes_limit"] / 1e9)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
        cluster.shutdown()
    # this process is the driver, and the GCS and the raylet are its
    # threads: none of them may have touched the device
    check(not jax_backends_initialized(),
          "the driver process initialised a JAX backend")
    return device


# ---------------------------------------------------------------------------
# parent: one child per phase, never JAX
# ---------------------------------------------------------------------------

def run_phase(phase: str, args) -> dict:
    """Run one phase as a child in its own process group, pass its lines
    through, and return what it reported. Whatever the phase started dies
    with it."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", phase,
         "--chips", str(args.chips), "--seed", str(args.seed)],
        stdout=subprocess.PIPE, text=True, start_new_session=True)
    result = None
    started = time.monotonic()
    watchdog = threading.Timer(PHASE_TIMEOUT_S, os.killpg,
                               (proc.pid, signal.SIGKILL))
    watchdog.start()
    try:
        for line in proc.stdout:
            if line.startswith(RESULT_MARK):
                result = json.loads(line[len(RESULT_MARK):])
            else:
                print(line, end="", flush=True)
        code = proc.wait()
    finally:
        watchdog.cancel()
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    check(code == 0 and result is not None,
          f"phase {phase} exited with code {code} (killed at "
          f"{PHASE_TIMEOUT_S}s if -9)")
    say(phase, ok=True, wall_s=time.monotonic() - started)
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=sorted(PHASES), default=1,
                    help="4: only the fsdp-4 train step and its reference")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", choices=sum(PHASES.values(), ()),
                    help=argparse.SUPPRESS)     # a child's own phase
    args = ap.parse_args()
    if args.phase:
        device = globals()[f"phase_{args.phase}"](args)
        print(RESULT_MARK + json.dumps(device), flush=True)
        return 0
    from ray_tpu._private.accelerator import enable_compile_cache

    say("start", chips=args.chips, seed=args.seed,
        compile_cache=enable_compile_cache())
    check("jax" not in sys.modules, "the parent imported JAX")
    devices = [run_phase(phase, args) for phase in PHASES[args.chips]]
    check(all(d == devices[0] for d in devices),
          f"phases saw different devices: {devices}")
    print(json.dumps({"ok": True, "device": devices[0]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

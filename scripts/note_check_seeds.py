"""``serve-note-gen``'s reference check alone, seed after seed, on the chip.

The check is the harness's own: ``benchmark/serving.py:prepare_engine``
with no traffic to warm beside the check's two prompts (weights from the
seed, an engine, the check's programs warmed, the two prompts answered
and held to the family's plain float32 reference), one JSON line a seed
with the ``token_gap`` it says (``scripts/check_departures.py`` reads one
seed's tokens against a reference blind to a mechanism). ``--latent
NAME=INT`` sets a constant of ``ray_tpu/ops/latent_attention.py``
before any program is traced, for the same sums in another order (the
witnesses of PERF.md's findings of PR 58):

    python scripts/note_check_seeds.py --seeds 5200000803,5300002505
    ... --latent PREFILL_KERNEL_SCORES_BYTES=4611686018427387904  # plain
    ... --latent _PREFILL_CHUNK=1024         # the kernel, chunks twice as long
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import weakref

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, serving  # noqa: E402
from ray_tpu.ops import latent_attention  # noqa: E402


def check(cell, seed):
    args = argparse.Namespace(seed=seed, seconds=0.0, trace=0,
                              keep_trace=False)
    ctx = harness.Context(args, *cell, time.perf_counter())
    eng, facts = serving.prepare_engine(
        ctx, [], ctx.traffic["prefill_limits"])
    line = {"seed": seed, "token_gap": facts["token_gap"],
            "correct": facts["token_gap"] <= facts["tol"],
            "not_the_references": facts["tokens_not_the_references"],
            "latent_prefill_kernel_dispatches": eng.stats().get(
                "latent_prefill_kernel_dispatches")}
    error = serving.stop_engine(eng)
    if error is not None:
        raise RuntimeError("engine loop failed") from error
    # its page pool has to go before the next seed's is made
    gone, eng = weakref.ref(eng), None
    serving.wait_gone(gone)
    gc.collect()
    return line


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True,
                    help="whole numbers, comma-separated")
    ap.add_argument("--latent", action="append", default=[],
                    metavar="NAME=INT")
    ap.add_argument("--workload", default="serve-note-gen")
    ap.add_argument("--root", default=harness.ROOT,
                    help="the checkout whose BENCHMARK.json names the cell "
                    "(the CPU tests' toy copy rehearses this)")
    ns = ap.parse_args()
    for item in ns.latent:
        name, _, value = item.partition("=")
        assert hasattr(latent_attention, name), name
        setattr(latent_attention, name, int(value))
    cell = harness.load_cell(ns.workload, ns.root)
    # the compile cache as ``harness.main`` sets it: ``prepare_engine``
    # reads its misses to tell a warm-up that compiled
    from ray_tpu._private.accelerator import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    for seed in ns.seeds.split(","):
        line = check(cell, int(seed))
        line["latent"] = ns.latent
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()

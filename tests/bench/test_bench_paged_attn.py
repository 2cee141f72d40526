"""The reader of ``paged_attn_roofline`` (PR 33; in every cell whose decode
program holds the kernel since PR 49, where ``paged_attn_kernel_share``, PR
30's reading of the same kernel's time, went) on synthetic traces: it finds
the paged decode-attention kernel by its instruction's name inside the
decode programs' runs, and reports nothing for a program without it."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_pins  # noqa: E402
import bench_toy  # noqa: E402

from benchmark import flops, harness, inside, systems  # noqa: E402
from benchmark.trace import Trace  # noqa: E402

DECODE = "jit_paged_decode_c8_w16(1234)"
PREFILL = "jit_paged_prefill_w16(77)"
KERNEL = ("%paged_decode_attn.8 = bf16[32,32,128]{2,1,0} custom-call(s32[1] "
          "%layer, s32[512] %table, s32[32] %count, s32[33] %next, "
          "bf16[32,32,128] %q, bf16[12,544,128,8,128] %k, "
          'bf16[12,544,128,8,128] %v), custom_call_target="tpu_custom_call"')
# another kernel, the gather the program had before, and the layer loop
RAGGED = ("%ragged-dot = f32[64,1024]{1,0} custom-call(bf16[64,2048] %rows), "
          'custom_call_target="tpu_custom_call"')
GATHER = ("%fusion.237 = bf16[512,128,8,128]{3,2,1,0} fusion("
          "bf16[12,544,128,8,128] %pool, s32[32,16] %table), kind=kLoop")
WHILE = ("%while.1 = (bf16[12,544,128,8,128], s32[]) while("
         "(bf16[12,544,128,8,128], s32[]) %t), condition=%c, body=%b")


def synthetic_trace(runs: int = 6, kernel: bool = True) -> Trace:
    """``runs`` decode runs of 10 ms under a loop, each with two kernel
    calls of 1 ms (or a 2 ms gather), 3 ms of another kernel, and one
    prefill run holding a call that is not decode's."""
    modules, ops = [], []
    for i in range(runs):
        t = 0.02 * i
        modules.append((DECODE, t, t + 0.010))
        ops.append((WHILE, t, t + 0.010))
        if kernel:
            ops += [(KERNEL, t + 0.001, t + 0.002),
                    (KERNEL, t + 0.004, t + 0.005)]
        else:
            ops.append((GATHER, t + 0.001, t + 0.003))
        ops.append((RAGGED, t + 0.006, t + 0.009))
    t = 0.02 * runs
    modules.append((PREFILL, t, t + 0.010))
    ops.append((KERNEL, t, t + 0.008))
    return Trace([{"modules": modules, "ops": ops, "async_ops": []}], [],
                 extent_s=t + 0.010)


KERNEL_CELLS = {"serve-doc": "serve_tokens_per_s",
                "serve-chat": "tpot_p90_ms",
                "serve-moe-gen": "serve_tokens_per_s",
                "serve-code-gen": "serve_tokens_per_s",
                "serve-instruct-gen": "serve_tokens_per_s",
                "serve-reason-gen": "serve_tokens_per_s"}


@pytest.mark.parametrize("cell", sorted(KERNEL_CELLS))
def test_the_roofline_is_the_named_kernel_inside_decode_runs(cell):
    """Two calls of 1 ms in each 10 ms run of a chunk of 8: the kernel
    takes 20% of a 1.25 ms step, and the share is the family's bytes of
    keys and values over the bandwidth over that."""
    bench, _, config, _ = harness.load_cell(cell)
    name = bench_pins.reports(bench, cell, ["paged_attn_roofline"])[
        "paged_attn_roofline"]["name"]
    read = harness.load_reader(name)
    counters = {"live_kv_tokens_mean": 20000.0,
                "occupancy_samples": [24, 24]}
    run = type("Run", (), {
        "trace": synthetic_trace(), "config": config, "counters": counters,
        "device": {"platform": "tpu", "kind": "TPU v5 lite"}})
    nbytes = systems.family(config).attention_kv_bytes(config, counters)
    want = (100.0 * nbytes / flops.peaks("TPU v5 lite")["hbm_bytes_per_s"]
            / (0.2 * 1.25e-3))
    assert nbytes > 0 and read(run) == pytest.approx(want)
    # a program without the kernel (the parent's), too few calls to tell,
    # no trace (a rehearsal), no device: nothing, and no exception
    run.trace = synthetic_trace(kernel=False)
    assert read(run) is None
    run.trace = synthetic_trace(runs=inside.MIN_SAMPLES // 2 - 1)
    assert read(run) is None
    run.trace = None
    assert read(run) is None
    run.trace = Trace([], [], 1.0)
    assert read(run) is None


@pytest.mark.parametrize("cell", sorted(KERNEL_CELLS))
def test_the_entries_name_their_cells(bench, cell):
    """Found by name with the cell under ``workloads``, wherever later
    PRs' entries stand; ``paged_attn_kernel_share`` is in no cell."""
    m = bench_pins.reports(bench, cell, ["paged_attn_roofline"],
                           moves=KERNEL_CELLS[cell])["paged_attn_roofline"]
    assert (m["layer"], m["source"], m["unit"], m["better"]) == (
        "kernels", "device_trace", "%", "higher")
    assert not any(n.startswith("paged_attn_kernel_share")
                   for n in bench_pins.reported(bench, cell))

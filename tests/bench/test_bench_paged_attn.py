"""The reader of ``paged_attn_kernel_share.*`` (PR 30) on synthetic traces:
it finds the paged decode-attention kernel by its instruction's name inside
the decode programs' runs, and reports nothing for a program without it."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_toy  # noqa: E402

from benchmark import harness, inside  # noqa: E402
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


@pytest.mark.parametrize("metric", ["paged_attn_kernel_share.doc",
                                    "paged_attn_kernel_share.chat",
                                    "paged_attn_kernel_share.moe"])
def test_kernel_share_is_the_named_kernel_inside_decode_runs(metric):
    read = harness.load_reader(metric)
    run = type("Run", (), {"trace": synthetic_trace()})
    assert read(run) == pytest.approx(20.0)
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


def test_the_entries_name_one_cell_each():
    """Found by name, wherever later PRs' entries put them."""
    with open(os.path.join(bench_toy.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    last = [m for m in bench["per_layer"]
            if m["name"].startswith("paged_attn_kernel_share.")]
    assert [(m["name"], m["workloads"], m["moves"]) for m in last] == [
        ("paged_attn_kernel_share.doc", ["serve-doc"], "serve_tokens_per_s"),
        ("paged_attn_kernel_share.chat", ["serve-chat"], "tpot_p90_ms"),
        ("paged_attn_kernel_share.moe", ["serve-moe-gen"],
         "serve_tokens_per_s")]
    for m in last:
        assert (m["layer"], m["source"], m["unit"]) == (
            "kernels", "device_trace", "%")
        # the cell reports the end-to-end metric the share moves
        moved = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
        assert m["workloads"][0] in moved["workloads"]

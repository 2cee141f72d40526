"""The reader ``grouped_expert_ffn_roofline`` (PR 49; NOT an entry of
``BENCHMARK.json`` yet: on the chip it read 75-178%, because the bytes it
counts are those of the experts an even routing touches and the seeded
routers are far from even, and the program records no expert load for a
prefill: PERF.md, section 7) on synthetic
traces and spans: it finds PR 44's grouped expert kernel by its
instruction's name inside the PREFILL programs' runs, takes each call's
rows and width from the instruction's own text, the prompt tokens' share
of the rows from the slice's dispatch spans, and what the call must do
from the family's count; and the four expert families' counts."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_pins  # noqa: E402

from benchmark import dispatch_account, families, harness, inside  # noqa: E402
from benchmark import program_spans, systems  # noqa: E402
from benchmark.trace import Trace  # noqa: E402

CELLS = ("serve-moe-gen", "serve-code-gen", "serve-note-gen",
         "serve-reason-gen")
PREFILL = "jit_paged_prefill_w8(77)"
DECODE = "jit_paged_decode_c8_w8(12)"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def kernel(rows: int, n: int, dtype: str = "bf16") -> str:
    return (f"%grouped_expert_ffn.3 = {dtype}[{rows},{n}]{{1,0}} custom-call("
            f"s32[1] %layer, s32[65] %offsets, bf16[{rows},2048] %xs), "
            'custom_call_target="tpu_custom_call"')


def widths(config: dict) -> tuple:
    return (config["hidden_size"],
            config.get("moe_intermediate_size", config["intermediate_size"]),
            config["num_experts_per_tok"])


def synthetic(config: dict, runs: int = 3, token_rows: int = 512):
    """``runs`` prefill runs of 10 ms over ``token_rows`` token-rows, each
    with an up call of 2 ms and a down call of 1 ms, another kernel beside
    them, and a decode run that holds a call that is not prefill's; the
    spans say that 400 of a dispatch's 512 rows were prompt tokens."""
    d, f, top_k = widths(config)
    modules, ops = [], []
    for i in range(runs):
        t = 0.02 * i
        modules.append((PREFILL, t, t + 0.010))
        ops += [(kernel(token_rows * top_k, f), t + 0.001, t + 0.003),
                (kernel(token_rows * top_k, d, "f32"), t + 0.004, t + 0.005),
                ('%paged_prefill_attn.1 = bf16[512,4096]{1,0} custom-call(), '
                 'custom_call_target="tpu_custom_call"', t + 0.006, t + 0.008)]
    t = 0.02 * runs
    modules.append((DECODE, t, t + 0.010))
    ops.append((kernel(64 * top_k, f), t, t + 0.008))
    spans = [{"name": "engine.dispatch_prefill", "span_id": f"p{i}",
              "duration": 0.002, "attrs": {
                  "group": 1, "bucket": token_rows, "token_rows": token_rows,
                  "new_tokens": 400, "seq": i}} for i in range(runs)]
    spans += [{"name": "engine.dispatch_prefill", "span_id": "q",
               "duration": 0.001, "attrs": {
                   "group": 2, "bucket": 64, "token_rows": 128,
                   "new_tokens": 70, "seq": 99}}]
    return Trace([{"modules": modules, "ops": ops, "async_ops": []}], [],
                 extent_s=t + 0.010), spans


@pytest.mark.parametrize("cell", CELLS)
def test_the_share_is_the_familys_count_over_the_calls_time(monkeypatch, cell):
    bench, _, config, _ = harness.load_cell(cell)
    # no cell lists it until its count of the touched experts is sound
    assert "grouped_expert_ffn_roofline" not in bench_pins.reported(bench,
                                                                    cell)
    read = harness.load_reader("grouped_expert_ffn_roofline")
    family = systems.family(config)
    d, f, top_k = widths(config)
    trace, spans = synthetic(config)
    monkeypatch.setattr(program_spans, "engine_spans", lambda: spans)
    run = type("Run", (), {"trace": trace, "config": config, "counters": {},
                           "device": {"platform": "tpu",
                                      "kind": "TPU v5 lite"}})
    pairs = 512 * top_k * 400 / 512      # the prompt tokens' choices
    least = sum(max(c["flops"] / PEAK["bf16_flops_per_s"],
                    c["bytes"] / PEAK["hbm_bytes_per_s"])
                for c in (family.grouped_expert_cost(config, f, pairs),
                          family.grouped_expert_cost(config, d, pairs)))
    got = read(run)
    assert got == pytest.approx(100.0 * 3 * least / (3 * 0.003))
    assert 0.0 < got < 100.0
    # the program's own count of the choices that fell on a held expert,
    # where the slice's decode chunks carry one, goes before the family's
    if cell != "serve-moe-gen":
        chunks = [{"name": "engine.emit", "span_id": f"e{i}",
                   "duration": 0.001, "attrs": {
                       "what": "chunk", "routed_here_share": 0.1}}
                  for i in range(inside.MIN_SAMPLES)]
        monkeypatch.setattr(program_spans, "engine_spans",
                            lambda: spans + chunks)
        assert read(run) != pytest.approx(got)
        monkeypatch.setattr(program_spans, "engine_spans", lambda: spans)
    # calls whose dispatch no span describes are left out, time and all;
    # too few calls, no kernel, no trace, no device, a family with no such
    # count: nothing, and no exception
    other, _ = synthetic(config, token_rows=1024)
    run.trace = other
    assert read(run) is None
    run.trace = synthetic(config, runs=2)[0]
    assert read(run) is None
    run.trace = Trace([{"modules": trace.devices[0]["modules"], "ops": [
        op for op in trace.devices[0]["ops"] if "grouped" not in op[0]],
        "async_ops": []}], [], extent_s=trace.extent_s)
    assert read(run) is None
    run.trace = None
    assert read(run) is None
    run.trace = Trace([], [], 1.0)
    assert read(run) is None
    run.trace, run.config = trace, dict(config, family="llama")
    assert read(run) is None


@pytest.mark.parametrize("cell", CELLS)
def test_the_familys_count_is_the_rows_operations_and_the_touched_weights(
        cell):
    _, _, config, _ = harness.load_cell(cell)
    family = systems.family(config)
    d, f, top_k = widths(config)
    held = config.get("num_experts", config.get("n_routed_experts"))
    share = family.grouped_expert_cost(config, f, 1.0, 1.0)
    ups = 1 if config.get("mlp_hidden_act") == "relu2" else 2
    # one pair: one expert's up matrices, 2 operations a multiply-add
    assert share == {"flops": 2.0 * d * f * ups,
                     "bytes": pytest.approx(2.0 * d * f * ups)}
    down = family.grouped_expert_cost(config, d, 4096.0, 1.0)
    assert down["flops"] == 2.0 * 4096 * f * d
    assert down["bytes"] == pytest.approx(2.0 * held * f * d, rel=1e-6)
    # a chip that holds a share sees that share of the choices, evenly
    total = config.get("expert_share", {}).get("num_experts_total", held)
    even = family.grouped_expert_cost(config, d, 4096.0)
    assert even["flops"] == pytest.approx(down["flops"] * held / total)
    assert family.grouped_expert_cost(config, d + 1, 8.0) is None
    assert families.grouped_matmul_cost(64, 8, 8, 1, 0.0) == {
        "flops": 0.0, "bytes": 0.0}


def test_the_fill_of_a_dispatch_is_found_by_its_rows():
    spans = synthetic({"hidden_size": 8, "intermediate_size": 8,
                       "num_experts_per_tok": 2})[1]
    assert dispatch_account.prefill_fill_by_rows(spans) == {
        512: 400 / 512, 128: 70 / 128}
    assert dispatch_account.prefill_fill_by_rows(None) == {}
    # a program older than PR 38 leaves the rows to be multiplied out
    old = [{"name": "engine.dispatch_prefill", "attrs": {
        "group": 2, "bucket": 32, "new_tokens": 48}}]
    assert dispatch_account.prefill_fill_by_rows(old) == {64: 0.75}

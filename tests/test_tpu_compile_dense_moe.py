"""Compile the paged engine's programs for the Llama-shaped cells
(``serve-doc``, ``serve-chat``: Mistral-7B-v0.3 widths cut to 12 layers)
and the OLMoE cell (``serve-moe-gen``) for a TPU that is described, not
attached (``conftest.py:v5e_2x2``), and read the compiled text."""

import pytest

import compiled_checks
import compiled_text as hlo
from engine_lowering import (D12_PAGES, MOE_LAYERS, MOE_PAGES, compiled,
                             serving_model)
from ray_tpu.ops.moe import expert_kernel_engages


# program, its dimensions (decode: chunk, window pages; prefill: prompts,
# tokens, window pages), GB of temporaries it may need
_D12_PROGRAMS = [
    ("decode", (16, 16), 0.8), ("decode", (8, 16), 0.8),
    ("prefill", (2, 2048, 16), 3.4), ("prefill", (4, 1024, 16), 3.4),
    ("prefill", (4, 1024, 8), 3.4), ("prefill", (1, 2048, 16), 3.4),
    ("prefill", (4, 2048, 16), 4.5)]


@pytest.mark.parametrize(
    "program,dims,temp_gb", _D12_PROGRAMS,
    ids=[f"{p}-{'x'.join(map(str, d))}" for p, d, _ in _D12_PROGRAMS])
def test_d12_engine_programs_keep_the_pool_in_place(v5e_2x2, program, dims,
                                                    temp_gb):
    """The paged engine's layer loop carries the stacked page pools and
    writes and gathers at [layer, page]: the compiled programs hold no
    copy, slice or update-slice the size of a layer's pool (a scan OVER
    the pools sliced each layer's K and V pool out and wrote it back,
    every layer of every step), and the prefill programs no second pool:
    their temporaries stay under one pool's 3.4 GB (with a second pool
    they are 5.1-8.1 GB), and four 2048-token prompts, which the
    compiler then refuses for HBM, fit at 4.4 GB."""
    built = compiled(v5e_2x2[0], *serving_model("d12"), program, dims,
                     num_pages=D12_PAGES)
    text = built.as_text()
    assert not hlo.pool_copy(12, D12_PAGES, 8).findall(text)
    assert built.memory_analysis().temp_size_in_bytes < temp_gb * 1e9
    # decode reads the pages where they lie (PR 30): the kernel under its
    # name, four query heads a KV head, and no window of the slots' pages
    assert bool(hlo.DECODE_KERNEL.search(text)) == (program == "decode")
    if program == "decode":
        assert not hlo.window(32, dims[1], 8).findall(text)


@pytest.mark.parametrize("model,pages,window", [
    ("d12", D12_PAGES, 16), ("olmoe-d10", MOE_PAGES, 8)])
def test_decode_programs_compile_over_int8_pages(v5e_2x2, model, pages,
                                                 window):
    """The same decode program over int8 pages and their scale pools:
    the same kernel (the pool's dtype is all that differs), dequantising
    in VMEM; no window of the pages in any type, no pool moved whole. The
    window's SCALES are gathered (1/32 of its bytes)."""
    module, cfg = serving_model(model)
    built = compiled(v5e_2x2[0], module, cfg, "decode", (16, window),
                     num_pages=pages, kv_dtype="int8")
    text = built.as_text()
    assert hlo.DECODE_KERNEL.search(text)
    assert not hlo.window(32, window, cfg.n_kv_heads).findall(text)
    assert not hlo.pool_copy(cfg.n_layers, pages,
                             cfg.n_kv_heads).findall(text)
    assert built.memory_analysis().temp_size_in_bytes < 0.8e9
    # (OLMoE's block states no fused stack, and over int8 pages its decode
    # program does evict and refetch the parked ``wv`` stack every layer:
    # no cell runs it; ROADMAP Queue 1 item 3)
    if model == "d12":
        assert not hlo.stack_moves_in_loops(
            text, cfg.n_layers, cfg.d_model, hlo.projection_widths(cfg))


_MOE_PROGRAMS = [
    ("decode", (16, 8), False), ("decode", (8, 8), False),
    ("prefill", (1, 128, 4), False), ("prefill", (2, 512, 4), True),
    ("prefill", (1, 1024, 8), True), ("prefill", (2, 1024, 8), True)]


@pytest.mark.parametrize(
    "program,dims,grouped", _MOE_PROGRAMS,
    ids=[f"{p}-{'x'.join(map(str, d))}" for p, d, _ in _MOE_PROGRAMS])
def test_olmoe_d10_engine_programs_compile_and_fit(v5e_2x2, program, dims,
                                                   grouped):
    """The same two engine programs around OLMoE's block: they compile
    for the chip beside 8.8 GB of weights and a 3.7 GB pool, keep the
    pool in place, and take the dropless op's formulation from their
    token count (``ops/moe.py:expert_kernel_engages``): every expert over
    every token up to 128 rows, which is every decode program (no grouped
    matmul in the program), the rows sorted by expert through the grouped
    kernel past it (two instructions in the layers' loop, no
    ``ragged-dot``), with no expert stack moved to feed it."""
    built = compiled(v5e_2x2[0], *serving_model("olmoe-d10"), program, dims,
                     num_pages=MOE_PAGES)
    text, mem = built.as_text(), built.memory_analysis()
    rows = 32 if program == "decode" else dims[0] * dims[1]
    assert expert_kernel_engages(rows) == grouped
    assert "ragged-dot" not in text
    assert len(hlo.EXPERT_KERNEL.findall(text)) == 2 * grouped
    assert hlo.in_loops(text, hlo.EXPERT_KERNEL) == 2 * grouped
    assert not hlo.expert_stack_moves(text, 64, 2048, 1024)
    assert not hlo.pool_copy(MOE_LAYERS, MOE_PAGES, 16).findall(text)
    # decode: the same kernel at one query head a KV head (MHA), and
    # neither the window nor a float32 copy of it
    assert bool(hlo.DECODE_KERNEL.search(text)) == (program == "decode")
    if program == "decode":
        assert not hlo.window(32, dims[1], 16).findall(text)
    pool_bytes = MOE_LAYERS * MOE_PAGES * 128 * 16 * 128 * 2
    assert mem.alias_size_in_bytes >= 2 * pool_bytes        # pools in place
    assert mem.temp_size_in_bytes < 0.8e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes) < 13.5e9              # of 15.75 GB


@pytest.mark.parametrize("model,pages,window,chunk", [
    ("d12", D12_PAGES, 16, 16), ("d12", D12_PAGES, 16, 8),
    ("olmoe-d10", MOE_PAGES, 8, 16), ("olmoe-d10", MOE_PAGES, 8, 8)])
def test_decode_loops_move_no_projection_weight_stack(v5e_2x2, model, pages,
                                                      window, chunk):
    """``compiled_checks.decode_loops_move_no_projection_stack`` of the
    cells' decode programs, both chunks."""
    compiled_checks.decode_loops_move_no_projection_stack(
        v5e_2x2[0], model, pages, (chunk, window))


# model, KV pages, prefill (prompts, tokens, window pages), the kernel's
# instructions in the program, GB of temporaries it may need
_PREFILL_RULE = [
    # serve-doc's two widest programs
    ("d12", D12_PAGES, (2, 2048, 16), 1, 0.5),
    ("d12", D12_PAGES, (1, 2048, 16), 1, 0.5),
    # the reference check's 600 tokens (128 MiB of scores) and a prefix
    # hit's suffix (32 MiB): under the rule
    ("d12", D12_PAGES, (1, 1024, 8), 0, 0.5),
    ("d12", D12_PAGES, (2, 64, 16), 0, 0.5),
    # everything serve-moe-gen warms stays plain (128 MiB at most)
    ("olmoe-d10", MOE_PAGES, (2, 512, 8), 0, 0.8),
    ("olmoe-d10", MOE_PAGES, (2, 1024, 8), 0, 0.8),
]
# what each model's check takes besides: its runs of expert layers, and
# for d12 the program under the rule whose loops' copies a program with the
# kernel must equal
_RULE_OF = {"d12": dict(plain=(2, 64, 16)), "olmoe-d10": dict(expert_runs=1)}


@pytest.mark.parametrize(
    "model,pages,dims,kernels,temp_gb", _PREFILL_RULE,
    ids=[f"{m}-{'x'.join(map(str, d))}" for m, _, d, _, _ in _PREFILL_RULE])
def test_prefill_programs_hold_the_kernel_by_the_rule(v5e_2x2, model, pages,
                                                      dims, kernels,
                                                      temp_gb):
    """``compiled_checks.prefill_holds_the_kernel_by_the_rule`` of the
    two cells' prefill programs, over the rule and under it."""
    compiled_checks.prefill_holds_the_kernel_by_the_rule(
        v5e_2x2[0], model, pages, dims, kernels, temp_gb, **_RULE_OF[model])


def test_the_plain_prefill_path_does_hold_score_arrays(v5e_2x2):
    """The fence above is not blind: the d12 program under the rule
    (two 64-token suffixes over 2048 keys) holds its float32 scores."""
    text = compiled(v5e_2x2[0], *serving_model("d12"), "prefill", (2, 64, 16),
                    num_pages=D12_PAGES).as_text()
    assert hlo.score_arrays(text, 2048) == ["2,8,4,64,2048"]


@pytest.mark.parametrize("family,pages,table", [
    ("d12", D12_PAGES, 16), ("olmoe-d10", MOE_PAGES, 8)],
    ids=["d12", "olmoe-d10"])
def test_the_other_families_decode_programs_hold_no_index_kernel(
        v5e_2x2, family, pages, table):
    """``compiled_checks.decode_holds_no_index_kernel`` of the two cells'
    decode programs."""
    compiled_checks.decode_holds_no_index_kernel(v5e_2x2[0], family, pages,
                                                 table)

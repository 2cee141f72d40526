"""Compile the paged engine's programs for SmallThinker-21BA3B-Instruct
cut to its first eight layers (``serve-brief-gen``: 32 slots, tables of
64 pages) for a TPU that is described, not attached
(``conftest.py:v5e_2x2``), and read the compiled text."""

import math
import re

import pytest

import compiled_checks
import compiled_text as hlo
from engine_lowering import BRIEF_PAGES, compiled, serving_model

# the cell's cold document: one prompt of 8,192 tokens, the 64-page table
_COLD_DOCUMENT = (1, 8192, 64)


def test_brief_d8_decode_program_reads_its_pages_in_place(v5e_2x2):
    """The decode program of the cell whose kernel walks two pages a
    step, at the published widths (28 query heads on 4 KV heads): one
    kernel instruction a run of the plan (full, three sliding, full,
    three sliding), each handed the stacked pools as dense rows ``[8,
    2304, 512, 128]`` by a bitcast of the pool ``write_kv`` scattered
    into, and no operation that copies, slices or rewrites a pool of
    either shape; the pools are donated and come back in place."""
    smallthinker, cfg = serving_model("smallthinker-d8")
    built = compiled(v5e_2x2[0], smallthinker, cfg, "decode", (16, 64),
                     num_pages=BRIEF_PAGES)
    text, mem = built.as_text(), built.memory_analysis()
    assert len(hlo.DECODE_KERNEL.findall(text)) == 4
    assert hlo.in_loops(text, hlo.DECODE_KERNEL) == 4
    assert hlo.dense_rows(text, 8, BRIEF_PAGES, 512) == ["bitcast"] * 8
    assert not hlo.pool_copy(8, BRIEF_PAGES, 4).findall(text)
    assert not hlo.window(32, 64, 4).findall(text)
    pool_bytes = 8 * BRIEF_PAGES * 128 * 4 * 128 * 2
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert mem.temp_size_in_bytes < 0.1e9


def test_brief_d8_cold_prefill_names_the_pieces_a_trace_shows(v5e_2x2):
    """The cold document of ``serve-brief-gen`` compiled for the chip,
    read as a traced engine records it at ``stop()``
    (``util/program_scopes.py:instruction_scopes``): the float32 combine
    fusion (result ``f32[rows x k, d_model]``, 8,192 rows x 6 choices)
    lies under ``moe_combine`` (the reshape behind it and its
    rematerialised copies are gone since PR 63), the grouped
    kernel's eight calls under ``moe_experts``, the prefill kernel's four
    under ``attn``, the K/V scatter (whose own name the compiler drops)
    under ``kv_write`` by the one rule for what has no ``op_name`` at all;
    and the instructions that run (not a parameter, a constant, a tuple or
    its element, or a bitcast) under no name of the vocabulary hold under
    a twentieth of the running instructions' result elements. By COUNT
    they are a sixth: 57 of them the ``pred[8192]`` masks of the write
    targets' arithmetic in the engine program, which no scope wraps; what
    weighs is the layer's normed rows ``bf16[8192, d_model]``, whose
    fusion takes the name of the reshape behind the norm (PR 57)."""
    from ray_tpu.ops import scopes
    from ray_tpu.util import program_scopes

    smallthinker, cfg = serving_model("smallthinker-d8")
    text = compiled(v5e_2x2[0], smallthinker, cfg, "prefill", _COLD_DOCUMENT,
                    num_pages=BRIEF_PAGES).as_text()
    found, inferred = program_scopes.instruction_scopes(text)

    def under(pattern, shape=None):
        return {scope for name, (was, scope) in found.items()
                if re.match(pattern, name) and shape in (None, was)}

    rows = f"f32[{8192 * cfg.top_k},{cfg.d_model}]"
    assert rows == "f32[49152,2560]"
    assert under(r"fusion", rows) == {scopes.MOE_COMBINE}
    assert not under(r"reshape.*remat")     # the [T, K, D] copies (PR 63)
    kernels = [n for n in found if n.startswith("grouped_expert_ffn")]
    assert len(kernels) == 8
    assert under(r"grouped_expert_ffn") == {scopes.MOE_EXPERTS}
    assert under(r"paged_prefill_attn") == {scopes.ATTN}
    assert under(r"fusion", "bf16[2359296,4,128]") == {scopes.KV_WRITE}
    assert {scope for _, scope in found.values()} <= set(
        scopes.VOCABULARY) | {""}
    free = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")
    runs = [m["name"] for m in map(program_scopes._INSTRUCTION.match,
                                   text.splitlines())
            if m and m["name"] in found and m["opcode"] not in free]
    unscoped = [name for name in runs if not found[name][1]]
    assert len(runs) > 500 and len(unscoped) < 0.2 * len(runs), unscoped

    def elements(names):
        return sum(math.prod(int(d) for d in re.findall(
            r"\d+", found[name][0].partition("[")[2])) for name in names)

    assert elements(unscoped) < 0.05 * elements(runs)
    assert set(inferred.values()) == {"operand", "user"}
    masks = [n for n in unscoped if found[n][0] == "pred[8192]"]
    assert len(unscoped) - len(masks) < 0.1 * len(runs)


# model, KV pages, prefill (prompts, tokens, window pages), the kernel's
# instructions in the program, GB of temporaries it may need: the cold
# document (full, three sliding, twice: four runs; the parent's program
# needed 1.22848 GB of temporaries) and a cached document's question, under
# the rule in both kinds of layer
_PREFILL_RULE = [
    ("smallthinker-d8", BRIEF_PAGES, _COLD_DOCUMENT, 4, 1.2284),
    ("smallthinker-d8", BRIEF_PAGES, (1, 128, 64), 0, 0.1),
]


@pytest.mark.parametrize(
    "model,pages,dims,kernels,temp_gb", _PREFILL_RULE,
    ids=[f"{m}-{'x'.join(map(str, d))}" for m, _, d, _, _ in _PREFILL_RULE])
def test_prefill_programs_hold_the_kernel_by_the_rule(v5e_2x2, model, pages,
                                                      dims, kernels,
                                                      temp_gb):
    """``compiled_checks.prefill_holds_the_kernel_by_the_rule`` of the
    cell's prefill programs, over the rule and under it."""
    compiled_checks.prefill_holds_the_kernel_by_the_rule(
        v5e_2x2[0], model, pages, dims, kernels, temp_gb, expert_runs=4)


@pytest.mark.parametrize("family", ["smallthinker-d8"])
def test_the_other_families_decode_programs_hold_no_index_kernel(v5e_2x2,
                                                                 family):
    """``compiled_checks.decode_holds_no_index_kernel`` of the cell's
    decode program."""
    compiled_checks.decode_holds_no_index_kernel(v5e_2x2[0], family,
                                                 BRIEF_PAGES, 64)


@pytest.mark.parametrize("cell", ["serve-brief-gen"])
def test_cold_prefills_bring_the_pairs_rows_back_without_a_relayout(v5e_2x2,
                                                                    cell):
    """``compiled_checks``' fence of the same name over the cold
    document's program (``[8192, 6, 2560]``)."""
    smallthinker, cfg = serving_model("smallthinker-d8")
    text = compiled(v5e_2x2[0], smallthinker, cfg, "prefill", _COLD_DOCUMENT,
                    num_pages=BRIEF_PAGES).as_text()
    compiled_checks.cold_prefill_brings_the_pairs_rows_back_without_a_relayout(
        text, 8192, cfg)

"""Compile the paged engine's programs for Laguna-S-2.1's leading layer
and one period (``serve-code-gen``: 64 slots, 2,304 pages) for a TPU that
is described, not attached (``conftest.py:v5e_2x2``), and read the
compiled text."""

import pytest

import compiled_checks
from engine_lowering import LAGUNA_PAGES, LAGUNA_SLOTS, serving_model


@pytest.mark.parametrize("model,pages,window,chunk", [
    ("laguna-ep4-d5", LAGUNA_PAGES, 32, 16),
    ("laguna-ep4-d5", LAGUNA_PAGES, 32, 8)])
def test_decode_loops_move_no_projection_weight_stack(v5e_2x2, model, pages,
                                                      window, chunk):
    """``compiled_checks.decode_loops_move_no_projection_stack`` of the
    cell's decode programs (64 slots, the 32-page table), both chunks.
    Laguna's blocks hold no k or v stack of their own: what is that wide
    (bf16[1, 3072, 1024]) is the last layer's shared expert, 6.3 MB
    fetched a layer ahead and read once."""
    _, cfg = serving_model(model)
    compiled_checks.decode_loops_move_no_projection_stack(
        v5e_2x2[0], model, pages, (chunk, window),
        but=(cfg.n_kv_heads * cfg.head_dim,), slots=LAGUNA_SLOTS)


# model, KV pages, prefill (prompts, tokens, window pages), the kernel's
# instructions in the program, GB of temporaries it may need: Laguna's two
# runs of full layers (48 heads) and its run of three sliding ones between
# them (72 heads under 512 keys; PR 56), an instruction each: a cold file,
# two of them, the warm-up's 4,095 tokens, and a short suffix
_PREFILL_RULE = [
    ("laguna-ep4-d5", LAGUNA_PAGES, (1, 2048, 16), 3, 1.4),
    ("laguna-ep4-d5", LAGUNA_PAGES, (2, 2048, 16), 3, 1.4),
    ("laguna-ep4-d5", LAGUNA_PAGES, (1, 4096, 32), 3, 1.4),
    ("laguna-ep4-d5", LAGUNA_PAGES, (1, 64, 32), 0, 1.4),
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
        v5e_2x2[0], model, pages, dims, kernels, temp_gb, expert_runs=2,
        held_experts=(64, 3072, 1024))


@pytest.mark.parametrize("family", ["laguna-ep4-d5"])
def test_the_other_families_decode_programs_hold_no_index_kernel(v5e_2x2,
                                                                 family):
    """``compiled_checks.decode_holds_no_index_kernel`` of the cell's
    decode program."""
    compiled_checks.decode_holds_no_index_kernel(
        v5e_2x2[0], family, LAGUNA_PAGES, 32, slots=LAGUNA_SLOTS)

"""Compile the paged engine's programs for dots3-note-prev cut to its
first five layers (``serve-note-gen``: latent rows and a learned
selection) for a TPU that is described, not attached
(``conftest.py:v5e_2x2``), and the selection itself at the shapes that
cell and ``serve-longqa-gen`` search; read the compiled text."""

import hashlib
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import compiled_text as hlo
from engine_lowering import (NOTE_PAGES, NOTE_SLOTS, NOTE_TABLE, compiled,
                             lower, serving_model)
from ray_tpu.serve.engine_programs import store_shapes

# the latent kernel's instruction, under its name: the rows' pool among
# its operands, the weighted rows [slots, heads, rank] its result
_LATENT_KERNEL = re.compile(
    r"%latent_decode_attn[.\d]* = bf16\[64,128,512\]\S* custom-call\("
    r".*tpu_custom_call.*bf16\[2,2816,128,640\]")

# the index kernel's instruction, under its name: the index keys' pool
# among its operands, the scores (a row a step of its walk: 16 pages of
# 128 keys) its result
_INDEX_KERNEL = re.compile(
    r"%index_decode_scores[.\d]* = f32\[64,4,2048\]\S* custom-call\("
    r".*tpu_custom_call.*bf16\[2,2816,128,128\]")
# the cell's cold prompt: one of 4,096 tokens, the 32-page window
_COLD_PROMPT = (1, 4096, 32)


def _note_program(made, device, program, dims):
    """One of the engine's two programs for the cell's plan, lowered or
    compiled (``made``: ``lower``, ``compiled``), over the row pools the
    plan states."""
    dots3_note, cfg = serving_model("dots3-note-d5")
    return made(device, dots3_note, cfg, program, dims, num_pages=NOTE_PAGES,
                slots=NOTE_SLOTS)


def _sorted_rows(text, width):
    """The ``sort`` instructions of a compiled program over float32 rows
    of ``width``: what ``lax.top_k`` of a row of index scores is on this
    chip."""
    return [line for line in text.splitlines()
            if re.search(rf"\(f32\[[\d,]*\b{width}\]\S*, .* sort\(", line)]


@pytest.mark.parametrize("rows,width", [
    (32, 8192), (64, 8192), (2048, 4096), (2048, 6144), (2048, 8192),
    (128, 8192)], ids=lambda n: str(n))
def test_the_selection_compiles_to_no_sort(v5e_2x2, rows, width):
    """``kept`` at the serving cells' shapes (a decode step's slots, a
    cold prefill's block of queries over each group of keys, a suffix)
    compiled for the chip: ``lax.top_k`` of such rows is one ``sort`` of
    each (the function below says so of the same shapes), the search is
    loops of fused passes and no sort, no kernel, and needs beside its
    operand no more than the scores' own keys and ties."""
    from ray_tpu.ops.index_select import kept

    scores = jax.ShapeDtypeStruct((rows, width), jnp.float32,
                                  sharding=SingleDeviceSharding(v5e_2x2[0]))
    searched = jax.jit(partial(kept, topk=2048)).lower(scores).compile()
    text = searched.as_text()
    assert not _sorted_rows(text, width)
    assert "tpu_custom_call" not in text and " while(" in text
    assert (searched.memory_analysis().temp_size_in_bytes
            <= 3 * 4 * rows * width + (1 << 20))
    sort = jax.jit(lambda x: jax.lax.top_k(x, 2048)).lower(scores).compile()
    assert len(_sorted_rows(sort.as_text(), width)) == 1


def test_note_d5_decode_program_reads_its_rows_in_place(v5e_2x2):
    """The cell's decode program (chunk 16, the 64-page table): each of
    its two runs of full layers holds the latent kernel, no operation
    gathers the slots' 2,048 chosen rows (``bf16[131072,640]``), the
    sliding layers gather their five pages as they did, and the program
    needs less beside its arguments than the gathered one did (0.67
    GB). Its 64 rows a step are under the routed experts' line: no
    grouped expert kernel. Since PR 53 each run of full layers holds the
    index kernel too: no operation copies the table's 4,096 pages of
    index keys out of the pool (``bf16[4096,128,128]``, 134 MB), and the
    temporaries fall from the parent's 0.570 GB to 0.475 GB: what the
    program of the 32-page table needed, whose copy was half as large
    (the peak is no longer the indexer's)."""
    # the pools the plan states: the full layers' latent rows and index
    # keys, the sliding layers' rows, each in whole lanes
    pools, *_ = store_shapes(
        serving_model("dots3-note-d5")[1], max_batch=NOTE_SLOTS,
        num_pages=NOTE_PAGES, page_size=128, kv_dtype="bf16")
    assert [p.shape[-1] for p in pools] == [640, 128, 1152]
    built = _note_program(compiled, v5e_2x2[0], "decode", (16, NOTE_TABLE))
    text = built.as_text()
    assert len(_LATENT_KERNEL.findall(text)) == 2
    assert len(_INDEX_KERNEL.findall(text)) == 2
    # since PR 61 the 64 slots' scores are searched, not sorted
    assert not _sorted_rows(text, 8192)
    assert "bf16[131072,640]" not in text
    assert "bf16[4096,128,128]" not in text
    assert "bf16[320,128,1152]" in text
    assert built.memory_analysis().temp_size_in_bytes < 0.48e9
    assert not hlo.EXPERT_KERNEL.search(text)


def test_note_d5_cold_prefill_runs_its_experts_in_the_grouped_kernel(
        v5e_2x2):
    """The cell's cold prompt (one of 4,096 tokens, the 32-page window):
    32,768 (token, choice) pairs of which an eighth fall on the 32 held
    experts; each of the plan's runs of sparse layers holds the grouped
    kernel twice and no ``ragged-dot``, no stack of the held experts
    (1.5 GB a layer) is moved to feed it, and the program fits beside
    its arguments."""
    from ray_tpu.ops.moe import expert_kernel_engages

    _, cfg = serving_model("dots3-note-d5")
    built = _note_program(compiled, v5e_2x2[0], "prefill", _COLD_PROMPT)
    text, mem = built.as_text(), built.memory_analysis()
    assert expert_kernel_engages(4096)
    kernels = len(hlo.EXPERT_KERNEL.findall(text))
    assert kernels and kernels % 2 == 0
    assert "ragged-dot" not in text
    assert not hlo.expert_stack_moves(text, 32, cfg.d_model, 1536)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes) < 15.75e9


def test_note_d5_cold_prefill_keeps_its_scores_on_the_core(v5e_2x2):
    """The cell's cold prompt (one of 4,096 tokens, the 32-page window):
    each of the plan's three runs of layers (the leading dense full
    layer, the full layer behind it, the three sliding ones) holds the
    latent prefill kernel under its name, the full layers' at 128 heads
    and the sliding layers' at 64, and the program holds no float32 array
    of heads x block x keys: the plain formulation wrote a full layer's
    ``f32[1,128,256,4096]`` sixteen times a layer and a sliding layer's
    ``f32[1,64,1024,1664]`` four times, and no decode kernel."""
    built = _note_program(compiled, v5e_2x2[0], "prefill", _COLD_PROMPT)
    text = built.as_text()
    kernels = hlo.LATENT_PREFILL_KERNEL.findall(text)
    assert sorted(kernels) == [("128", "4096"), ("128", "4096"),
                               ("64", "4096")]
    assert not _LATENT_KERNEL.search(text) and not _INDEX_KERNEL.search(text)
    # what is left of score shape is the indexer's, which stays in HBM in
    # float32: a block of 1,024 queries at 64 index heads over the keys
    # its group of blocks can see, where those are more than ``topk``
    scores = {dims for keys in (4096, 3072, 2048, 1024, 1664, 1537)
              for dims in hlo.score_arrays(text, keys)}
    assert scores == {"1,64,1024,3072", "1,64,1024,4096"}
    assert built.memory_analysis().temp_size_in_bytes < 2.2e9


# sha256 (first 16 hex digits) of the text the cell's prefill programs
# UNDER the latent prefill kernel's rule lower to, under ``hlo.PINNED_JAX``.
# On the commit before that kernel (5838c9b) and up to PR 60 they read
# "ebaac4e40dc1d182" and "acd16a8baa5cca46"; PR 61 MEANT to alter them
# (``kept`` searches a query's index scores for their ``topk``-th and
# sorts none: the text holds the search's loops where it held
# ``top_k``) and pinned these on its own tree.
_PARENT_NOTE_PREFILL = {(1, 64, 32): "7543ac1cf78d8465",
                        (1, 16, 32): "fb8cc2a8740101af"}


@pytest.mark.parametrize("dims", _PARENT_NOTE_PREFILL,
                         ids=lambda d: "x".join(map(str, d)))
def test_note_d5_prefill_under_the_rule_is_the_parents_text(v5e_2x2, dims):
    """A question of up to 64 tokens behind a cached transcript (a full
    layer's float32 scores and its indexer's would be 201 MB, under the
    256 MiB line): the program holds no prefill kernel and its lowered
    text is the one pinned above, byte for byte: what it was before the
    latent prefill kernel but for ``kept``'s search, and still no kernel
    and no choice by platform."""
    if jax.__version__ != hlo.PINNED_JAX:
        pytest.skip(f"digests pinned under jax {hlo.PINNED_JAX}")
    text = hlo.located_nowhere(
        _note_program(lower, v5e_2x2[0], "prefill", dims).as_text())
    assert "latent_prefill_attn" not in text
    # no kernel at all, and no ``top_k`` but the routers'
    assert "tpu_custom_call" not in text
    assert "x4096xf32>" not in "".join(
        line for line in text.splitlines() if "chlo.top_k" in line)
    assert (hashlib.sha256(text.encode()).hexdigest()[:16]
            == _PARENT_NOTE_PREFILL[dims])

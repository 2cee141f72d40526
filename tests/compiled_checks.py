"""What the compiled programs of SEVERAL families are held to, stated once:
each family's file (``test_tpu_compile_<family>.py``) parametrises these
by its own cells' programs, so a new family adds a file and edits no
other's."""

import re

import compiled_text as hlo
from engine_lowering import compiled, serving_model
from ray_tpu.ops.moe import expert_kernel_engages
from ray_tpu.ops.paged_prefill_attention import query_block


def decode_loops_move_no_projection_stack(device, model, pages, dims, *,
                                          but=(), **engine):
    """A decode step reads each layer's projection weights where they lie
    and once. With q, k and v projected from three stacks the compiler
    parked the d12 ``wk`` stack (100.7 MB of the core's 128 MiB) on the
    core as a value of the layer loop, and in every layer of every step
    wrote all twelve layers back to HBM before the attention kernel
    (``copy-done bf16[12,4096,1024]``) and fetched them again in four
    ``slice-done bf16[3,4096,1024]``: 201 MB a layer-step that no
    arithmetic needs, 2.9 ms of an 11.4-14.7 ms step on a v5e (ledger,
    PR 31). The fused stack (604 MB) cannot be parked: its matmul's
    fusion takes the whole stack and the layer index. OLMoE's block
    states no fused stack; its decode programs park ``wv`` once at the
    entry and move nothing inside a loop: a fence. Laguna's runs of one
    and of three layers hold q | k | v as one stack from the start (three
    layers' ``wk`` alone would be 18.9 MB), and its decode programs (64
    slots, the 32-page table) move none of them; the per-head gate's
    ``wg`` (0.3-0.4 MB a layer) is fetched a layer ahead, which is no
    projection stack and costs nothing to see."""
    module, cfg = serving_model(model)
    widths = [w for w in hlo.projection_widths(cfg) if w not in but]
    program = compiled(device, module, cfg, "decode", dims, num_pages=pages,
                       **engine)
    moves = hlo.stack_moves_in_loops(program.as_text(), cfg.n_layers,
                                     cfg.d_model, widths)
    assert not moves, "\n".join(moves)
    # the fused stack is built once a run, at the entry, in place of the
    # three transposed entry copies: the same bytes of temporaries
    assert program.memory_analysis().temp_size_in_bytes < 0.8e9


def prefill_holds_the_kernel_by_the_rule(device, model, pages, dims, kernels,
                                         temp_gb, *, expert_runs=0,
                                         held_experts=None, plain=None):
    """Prefill attends over the pages where they lie (PR 34): a program
    whose full layers' float32 scores would pass 256 MiB
    (``ops/paged_prefill_attention.py:kernel_engages``) holds the kernel
    under its name, one instruction a run of full layers, and no float32
    array of score shape: the d12 program of two cold 2048-token prompts
    held ``f32[2,8,4,2048,2048]``, 1 GiB, and about 2 GiB of temporaries
    with it; Laguna's went over its queries in blocks of a quarter of a
    GiB. A program under the rule holds no such instruction and is the
    text it was (digests: tests/test_fused_projections.py). Since PR 56
    a run of SLIDING layers is held to the same rule by the scores the
    plain path writes for it (every block of its queries against the
    block's and a window's keys) and holds one instruction more: Laguna's
    cold programs three (they went window by window, blocks of 512
    queries over 1,152 gathered keys, ``f32[2,8,9,512,1152]``), and the
    cold document of ``serve-brief-gen`` four, where its six sliding
    layers wrote ``f32[1,4,7,512,4736]`` sixteen times a layer. All fit,
    and the kernel's need of the core's memory moves no weight stack: the
    loops of a program with the kernel copy what they copied without it,
    ONE layer of ``wq`` / ``wk`` / ``wv`` each (the per-layer transposes,
    ROADMAP Queue 1 item 2), never a stack."""
    module, cfg = serving_model(model)
    program = compiled(device, module, cfg, "prefill", dims, num_pages=pages)
    text = program.as_text()
    assert len(hlo.PREFILL_KERNEL.findall(text)) == kernels
    assert not hlo.DECODE_KERNEL.search(text)
    # the routed experts' kernel follows its own rule, the rows alone:
    # two instructions a run of expert layers (``expert_runs`` of the
    # plan: Laguna's two runs, four; SmallThinker's four, eight), and no
    # stack of the ``held_experts`` (how many, model width, expert width)
    # moved to feed it
    assert len(hlo.EXPERT_KERNEL.findall(text)) == (
        2 * expert_runs * expert_kernel_engages(dims[0] * dims[1]))
    assert "ragged-dot" not in text
    if held_experts:
        assert not hlo.expert_stack_moves(text, *held_experts)
    if kernels:
        # neither over the table's keys nor, where a run slides, over a
        # block's and a window's (as counted, and in the whole pages the
        # plain path gathers for them)
        widths = {dims[2] * 128}
        for window in {run.window for run in module.layer_plan(cfg)} - {None}:
            seen = window + query_block(dims[0], dims[1], cfg.n_heads,
                                        dims[2] * 128, window)
            widths |= {seen, (-(-(seen - 2) // 128) + 1) * 128}
        for keys in widths:
            assert not hlo.score_arrays(text, keys), keys
    assert program.memory_analysis().temp_size_in_bytes < temp_gb * 1e9
    assert not hlo.pool_copy(cfg.n_layers, pages, cfg.n_kv_heads).findall(text)
    moved = hlo.moved_shapes(text, cfg)
    assert all(shape.startswith("bf16[1,") for shape in moved), moved
    if plain and kernels:
        # what the loops of the same cell's program UNDER the rule copy
        under = compiled(device, module, cfg, "prefill", plain,
                         num_pages=pages).as_text()
        assert moved == hlo.moved_shapes(under, cfg)


def decode_holds_no_index_kernel(device, family, pages, table, **engine):
    """No configuration but ``serve-note-gen``'s and ``serve-longqa-gen``'s
    states a layer with an indexer: the decode program of each other
    holds neither the index kernel nor the latent one."""
    module, cfg = serving_model(family)
    text = compiled(device, module, cfg, "decode", (16, table),
                    num_pages=pages, **engine).as_text()
    assert "index_decode_scores" not in text
    assert "latent_decode_attn" not in text


def cold_prefill_brings_the_pairs_rows_back_without_a_relayout(text, tokens,
                                                               cfg):
    """The two cells whose prefill spends most on the rows' way back
    (``[8192, 6, 2560]`` and ``[1024, 10, 4096]``; K = 6 pads to 8
    sublanes, K = 10 to 16): the compiled prefill gathers the grouped
    kernel's float32 rows with the choices on the major axis, so the
    split of the gathered ``[K*T, D]`` into K slabs is a bitcast and no
    float32 ``[T, K, D]`` array (a copy of every row into tiles of 8, a
    quarter to a third of the combine before PR 63) is written anywhere
    in the program, nor a ``[K, T, D]`` relayout in its place."""
    k, d = cfg.top_k, cfg.d_model
    assert not hlo.combine_relayouts(text, tokens, k, d)
    # the fence reads the right program: the gathered rows are there,
    # under the combine, and split by a bitcast
    rows = re.compile(rf"= f32\[{tokens * k},{d}\]\S* fusion\(.*moe_combine")
    slabs = re.compile(rf"= f32\[{k},{tokens},{d}\]\S* bitcast\(")
    assert rows.search(text) and slabs.search(text)

"""Compile the paged engine's programs for the plans with a recurrent
run (Falcon-H1, ``serve-instruct-gen``; Nemotron-H, ``serve-reason-gen``;
granite-4.0-h, ``serve-assist-gen``; LFM2-MoE, ``serve-extract-gen``) for
a TPU that is described, not attached (``conftest.py:v5e_2x2``), and read
the compiled text."""

import re

import pytest

import compiled_checks
import compiled_text as hlo
from engine_lowering import (ASSIST_PAGES, ASSIST_SLOTS, EXTRACT_PAGES,
                             EXTRACT_SLOTS, EXTRACT_TABLE, H1_LAYERS,
                             H1_PAGES, H1_SLOTS, NANO_PAGES, NANO_SLOTS,
                             compiled, lower, serving_model)

# Falcon-H1-34B-Instruct cut to 4 blocks: 128 slots, 1280 KV pages, and
# each slot's recurrent state beside them
_H1_PROGRAMS = [("decode", (8, 8)), ("decode", (16, 8)),
                ("prefill", (2, 1024, 8)), ("prefill", (2, 512, 4))]
_STATE_COPY = re.compile(
    r"= f32\[(?:4,|1,)?128,32,128,256\]\S* (?:copy|copy-start)\(")
# the state kernel's instruction, under its name: the stacked state among
# its operands and, in place, among its results
_STATE_KERNEL = re.compile(
    r"%ssm_state_step[.\d]* = \(.*f32\[4,128,32,128,256\]\S*\) "
    r"custom-call\(.*tpu_custom_call")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?(%[\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)$", re.M)
# what hands an array on without touching it
_HANDS_ON = {"parameter", "get-tuple-element", "tuple", "while", "bitcast",
             "call", "conditional", "opt-barrier"}


def _state_passes(text, axes="32,128,256"):
    """The instructions that pass over the recurrent state outside the
    state kernel: whatever has a float32 array of the state's axes (a
    layer's, or the stack) as its result or among its operands and does
    more than hand it on. (The text names an instruction's operands, not
    their types: an operand is one if the instruction that made it says
    so.)"""
    state = re.compile(rf"f32\[(?:\d+,)*{axes}\]")
    found = _INSTRUCTION.findall(text)
    holds = {name for name, result, _, _ in found
             if not result.startswith("(") and state.match(result)}
    return [f"{name} = {result[:60]} {op}" for name, result, op, rest in found
            if op not in _HANDS_ON and not _STATE_KERNEL.search(
                f"{name} = {result} {op}({rest}")
            and (state.search(result)
                 or holds & set(re.findall(r"%[\w.\-]+",
                                           rest.split("), ")[0])))]


@pytest.mark.parametrize(
    "program,dims", _H1_PROGRAMS,
    ids=[f"{p}-{'x'.join(map(str, d))}" for p, d in _H1_PROGRAMS])
def test_falcon_h1_d4_engine_programs_fit_beside_their_state(v5e_2x2,
                                                             program, dims):
    """The engine's programs for the recurrent plan at the published
    widths: the decode program at the cell's one table (8 pages) in both
    chunks, and the widest prefill programs (two cold 1024-token prompts,
    which the reference check's 600 tokens reach; two of 512, the
    traffic's). Arguments of 12.3 GB (8.79 GB of weights, 1.34 GB of
    pools, 2.16 GB of state) fit a v5e with the program's temporaries
    beside them; the pools AND the slots' state are donated and come back
    in place; no instruction copies a layer's state, or the stack of
    them, whole. A decode program advances the state in ONE instruction a
    layer-step, the state kernel under its name (PR 37), which takes the
    stacked array and hands it back aliased: the layer loop holds no
    other instruction that reads or writes an array of the state's axes
    (XLA lowered the plain formulation to two fusions there, one that
    read a layer's state through a fused slice and reduced it against
    ``c``, one that read it again and wrote it through a fused update:
    both are gone from the text), and the program's temporaries, under
    1 GB, hold no second state of 2.1 GB."""
    falcon_h1, cfg = serving_model("falcon-h1-d4")
    built = compiled(v5e_2x2[0], falcon_h1, cfg, program, dims,
                     num_pages=H1_PAGES, slots=H1_SLOTS)
    text, mem = built.as_text(), built.memory_analysis()
    pool_bytes = H1_LAYERS * H1_PAGES * 128 * 4 * 128 * 2
    state_bytes = H1_LAYERS * H1_SLOTS * (4 * 32 * 128 * 256
                                            + 2 * 3 * 5120)
    assert 12.2e9 < mem.argument_size_in_bytes < 12.4e9
    assert mem.alias_size_in_bytes >= 2 * pool_bytes + state_bytes
    assert mem.temp_size_in_bytes < 1.0e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes) < 13.5e9              # of 15.75 GB
    assert not hlo.pool_copy(H1_LAYERS, H1_PAGES, 4).findall(text)
    assert bool(hlo.DECODE_KERNEL.search(text)) == (program == "decode")
    assert len(_STATE_KERNEL.findall(text)) == (program == "decode")
    if program == "decode":
        assert not _STATE_COPY.findall(text)
        # the kernel's call is in the layer loop, once, and nothing else
        # there (or anywhere) passes over the state
        assert hlo.in_loops(text, _STATE_KERNEL) == 1
        assert not _state_passes(text)
        # five query heads a KV head through the decode kernel, and no
        # stack of projection weights moved in the loops: q | k | v are
        # one stack (147 MB), wo's (105 MB) is read where it lies
        assert not hlo.stack_moves_in_loops(
            text, H1_LAYERS, cfg.d_model, (3584, 9248, cfg.d_ff))
        assert not hlo.stack_moves_in_loops(text, H1_LAYERS, 2560,
                                            (cfg.d_model,))
        assert not hlo.stack_moves_in_loops(text, H1_LAYERS, cfg.d_ssm,
                                            (cfg.d_model,))


def test_the_plain_state_update_does_pass_over_the_state(v5e_2x2,
                                                         monkeypatch):
    """The fence above is not blind: the same decode program with the
    plain formulation in the kernel's place (what every platform but the
    TPU runs) holds no kernel call, and fusions in its layer loop whose
    result is a layer's states reduced against ``c`` out of the stack,
    and the stack itself written through a fused update: XLA's passes
    over the state, which the kernel's program has none of."""
    from ray_tpu.models import falcon_h1
    from ray_tpu.ops import ssm

    monkeypatch.setattr(falcon_h1, "ssm_state_step",
                        ssm.ssm_state_step_reference)
    _, cfg = serving_model("falcon-h1-d4")
    # not ``compiled``: this is another program than the cell's
    text = lower(v5e_2x2[0], falcon_h1, cfg, "decode", (8, 8),
                 num_pages=H1_PAGES, slots=H1_SLOTS).compile().as_text()
    assert not _STATE_KERNEL.search(text)
    passes = _state_passes(text)
    assert any(" fusion" in p for p in passes), passes
    stack = [p for p in passes if "= f32[4,128,32,128,256]" in p]
    assert stack, passes


@pytest.mark.parametrize("family", ["falcon-h1-d4"])
def test_the_other_families_decode_programs_hold_no_index_kernel(v5e_2x2,
                                                                 family):
    """``compiled_checks.decode_holds_no_index_kernel`` of the cell's
    decode program."""
    compiled_checks.decode_holds_no_index_kernel(
        v5e_2x2[0], family, H1_PAGES, 8, slots=H1_SLOTS)

# NVIDIA-Nemotron-3-Nano-30B-A3B cut to its first nine layers, MEMEM*EME:
# K/V pools of the ONE attention layer, state arrays of the FOUR mixers
_NANO_PROGRAMS = [("decode", (16, 16)), ("decode", (8, 16)),
                  ("prefill", (2, 512, 4)), ("prefill", (1, 512, 4))]
_NANO_STATE_KERNEL = re.compile(
    r"%ssm_state_step[.\d]* = \(.*f32\[4,128,64,64,128\]\S*\) "
    r"custom-call\(.*tpu_custom_call")
# a weight stack of the nine runs fetched into the core's memory (``S(1)``
# in the result's layout and not in the operand's), start and done
_NANO_FETCH = re.compile(
    r"= (?:\()?bf16\[1,(\d+),(\d+)\]\{[^}]*S\(1\)\}(?:, bf16\[1,\1,\2\]"
    r"\{[^}]*\)\}, u32\[\]\S*\))? (copy-start|copy-done)\(")


@pytest.mark.parametrize(
    "program,dims", _NANO_PROGRAMS,
    ids=[f"{p}-{'x'.join(map(str, d))}" for p, d in _NANO_PROGRAMS])
def test_nemotron_d9_engine_programs_hold_what_their_layers_keep(
        v5e_2x2, program, dims):
    """The engine's programs for a plan whose every layer is one thing,
    at the published widths: arguments of 7.7 GB (6.33 GB of weights, a
    0.30 GB pool of ONE layer's pages, 1.09 GB of state over FOUR layers)
    fit a v5e with the temporaries beside them (a prefill of 2 x 512 rows
    sorts its 6,144 (token, choice) pairs by held expert and holds the
    grouped kernel twice an ``E`` layer: under 0.65 GB, the pairs' rows in
    and out in float32); pools and state are donated and come back in
    place. A decode program attends
    in the decode kernel at 16 query heads a KV head and advances the
    state in the state kernel, once a mixer, over the four-layer array
    (blocks of 32 heads of [64, 128], four of the eight groups a block).

    Every run is ONE layer, so a run's weight stacks are the layer's own
    weights: the compiler prefetches some of them into the core's memory
    inside the step loop (``copy-start`` / ``copy-done`` into ``S(1)``),
    each ONCE a step and never back out, which moves the bytes the layer
    reads anyway and no others. What ``hlo.stack_moves_in_loops`` fences in
    the older programs, a stack of SEVERAL layers parked on the core and
    moved whole round a kernel to read one layer of it, cannot happen to
    a stack of one; the test holds the moves to those fetches."""
    nemotron_h, cfg = serving_model("nemotron-d9")
    plan = nemotron_h.layer_plan(cfg)
    assert [run.layers for run in plan if run.attends] == [1]
    assert sum(run.layers for run in plan if run.state is not None) == 4
    built = compiled(v5e_2x2[0], nemotron_h, cfg, program, dims,
                     num_pages=NANO_PAGES, slots=NANO_SLOTS)
    text, mem = built.as_text(), built.memory_analysis()
    pool_bytes = NANO_PAGES * 128 * 2 * 128 * 2
    state_bytes = 4 * NANO_SLOTS * (4 * 64 * 64 * 128 + 2 * 3 * 6144)
    assert 7.6e9 < mem.argument_size_in_bytes < 7.8e9
    assert mem.alias_size_in_bytes >= 2 * pool_bytes + state_bytes
    assert mem.temp_size_in_bytes < (0.65e9 if program == "prefill"
                                     else 0.5e9)
    assert not hlo.pool_copy(1, NANO_PAGES, 2).findall(text)
    assert bool(hlo.DECODE_KERNEL.search(text)) == (program == "decode")
    # the grouped expert kernel by its rule: both prefill programs are
    # over the line (512 and 1,024 rows), a decode step's 128 rows under
    # it; and the stacks are read where they lie: the up stack [64, 2688,
    # 1856] lies on the chip as [64, 1856, 2688] and is handed over so
    assert len(hlo.EXPERT_KERNEL.findall(text)) == (
        8 if program == "prefill" else 0)
    assert "ragged-dot" not in text
    assert not hlo.expert_stack_moves(text, 64, cfg.d_model, 1856)
    assert len(_NANO_STATE_KERNEL.findall(text)) == (
        4 if program == "decode" else 0)
    widths = {(cfg.d_ssm, cfg.d_model): (cfg.d_ssm, (cfg.d_model,)),
              "in": (cfg.d_model, (10304, 4608, cfg.d_shared)),
              "down": (cfg.d_shared, (cfg.d_model,))}
    moves = [m for d_in, w in widths.values()
             for m in hlo.stack_moves_in_loops(text, 1, d_in, w)]
    if program == "prefill":
        assert not moves
        return
    assert hlo.in_loops(text, _NANO_STATE_KERNEL) == 4
    # the four kernel calls, and nothing else, pass over the state
    passes = _state_passes(text, axes="64,64,128")
    assert len(passes) == 4 and all(
        p.startswith("%ssm_state_step") for p in passes), passes
    # each move is a fetch of a one-layer stack into the core's memory,
    # start and done, and no stack is fetched twice a step
    assert moves and all(_NANO_FETCH.search(m) for m in moves), moves
    starts = [m.split(" = ")[0] for m in moves if "copy-start(" in m]
    sources = [re.search(r"copy-start\((%[\w.\-]+)\)", m).group(1)
               for m in moves if "copy-start(" in m]
    assert len(starts) == len(set(sources)) == len(moves) // 2
    # no expert stack (1.28 GB a layer) is among them
    assert not any("1856" in m for m in moves)


@pytest.mark.parametrize("cell", ["serve-assist-gen"])
def test_cold_prefills_bring_the_pairs_rows_back_without_a_relayout(v5e_2x2,
                                                                    cell):
    """``compiled_checks``' fence of the same name over the cell's cold
    prompt of 1,024 tokens (``[1024, 10, 4096]``)."""
    granite, cfg = serving_model("granite-d10")
    text = compiled(v5e_2x2[0], granite, cfg, "prefill", (1, 1024, 8),
                    num_pages=ASSIST_PAGES, slots=ASSIST_SLOTS).as_text()
    compiled_checks.cold_prefill_brings_the_pairs_rows_back_without_a_relayout(
        text, 1024, cfg)


# LFM2-8B-A1B cut to its first fourteen layers: the decode program at the
# cell's full table, the cold prefill of two 4,096-token prompts and a
# suffix behind cached pages
_EXTRACT_PROGRAMS = [("decode", (16, EXTRACT_TABLE)),
                     ("prefill", (2, 4096, 32)), ("prefill", (1, 256, 32))]
# the K/V pools of the three attention layers, two 64-wide KV heads a row,
# and what the pages keep of the eleven convolutions' tails
_EXTRACT_POOL = f"bf16[3,{EXTRACT_PAGES},128,4,128]"
_EXTRACT_KEPT = f"bf16[11,{EXTRACT_PAGES},2,2048]"
_MOVES_WHOLE = r"\S* (?:copy|copy-start|dynamic-slice|dynamic-update-slice)\("


@pytest.mark.parametrize(
    "program,dims", _EXTRACT_PROGRAMS,
    ids=[f"{p}-{'x'.join(map(str, d))}" for p, d in _EXTRACT_PROGRAMS])
def test_lfm2_d14_engine_programs_hold_both_page_kernels_and_the_tails(
        v5e_2x2, program, dims):
    """``serve-extract-gen``'s programs at the published widths: the
    pools hold a token in 6,144 B (two heads of 64 a row of 128 lanes),
    the decode program attends in the decode kernel in each of its three
    attention layers and the cold prefill in the prefill kernel (the rule
    reads the pool's rows: whole lanes), with its routed experts in the
    grouped kernel; the prefill programs carry what the pages keep of the
    tails, written in place, and the decode program neither takes nor
    writes it; no program moves a pool or that store whole; each fits the
    chip beside nothing else."""
    model, cfg = serving_model("lfm2-d14")
    built = compiled(v5e_2x2[0], model, cfg, program, dims,
                     num_pages=EXTRACT_PAGES, slots=EXTRACT_SLOTS)
    text = built.as_text()
    assert _EXTRACT_POOL in text and "128,8,64]" not in text
    assert not re.search(re.escape(_EXTRACT_POOL) + _MOVES_WHOLE, text)
    assert not re.search(re.escape(_EXTRACT_KEPT) + _MOVES_WHOLE, text)
    assert (_EXTRACT_KEPT in text) == (program == "prefill")
    cold = dims == (2, 4096, 32)
    assert len(hlo.DECODE_KERNEL.findall(text)) == (
        3 if program == "decode" else 0)
    assert len(hlo.PREFILL_KERNEL.findall(text)) == (3 if cold else 0)
    assert bool(hlo.EXPERT_KERNEL.search(text)) == cold
    mem = built.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes)
    # 9.33 GB of weights, 2.42 GB of pools, 0.28 GB of tails in the pages
    assert 11.7e9 < mem.argument_size_in_bytes < 12.1e9
    assert held < 12.8e9

"""Compile the main path's device programs for a TPU that is described,
not attached: the chip's own compiler refuses here what it would refuse
on the chip (a kernel it cannot tile or partition, a step that does not
fit HBM), at no chip time. Nothing runs, so nothing here is a result or
a time.

Code that asks ``jax.default_backend()`` sees the CPU under test, so the
trainers are steered to the flash kernel from here (``attn_impl``).
"""

import dataclasses
import math
import os
import re
from functools import cache, partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else it logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import llama, olmoe
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.parallel.mesh import create_mesh
from ray_tpu.serve.paged_llm import PagedLLMEngine
from ray_tpu.train.trainer import JaxTrainer, TrainConfig


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: the next run would warn
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("heads,kv_heads,seq", [
    (32, 8, 2048), (32, 8, 16384), (12, 4, 2048)])
def test_flash_kernels_compile(v5e_2x2, heads, kv_heads, seq, grad):
    one_chip = SingleDeviceSharding(v5e_2x2[0])

    def operand(h):
        return jax.ShapeDtypeStruct((1, seq, h, 128), jnp.bfloat16,
                                    sharding=one_chip)

    def fn(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    if grad:
        fn = jax.grad(fn, argnums=(0, 1, 2))
    compiled = jax.jit(fn).lower(
        operand(heads), operand(kv_heads), operand(kv_heads)).compile()
    # forward alone is one kernel; its gradient adds dq and dk/dv
    assert compiled.as_text().count("tpu_custom_call") >= (3 if grad else 1)


def _compile_step(trainer, batch, seq):
    trainer.attn_impl = "flash"
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        trainer.abstract_state(), trainer.state_shardings())
    tokens = jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32)
    tokens = jax.ShapeDtypeStruct(
        tokens.shape, tokens.dtype,
        sharding=trainer._batch_shardings(tokens))
    return jax.jit(trainer._step, donate_argnums=(0,)).lower(
        state, tokens).compile()


def test_one_chip_1b_step_compiles(v5e_2x2):
    """A 1.0B Llama-shaped config at 4 x 2048 tokens: fits one chip's HBM (the
    compiler raises when a program does not) and keeps its three kernel
    calls (the remat policy saves the flash residuals, so the backward
    does not run the forward kernel again)."""
    cfg = llama.LlamaConfig(
        vocab_size=32768, d_model=2048, n_layers=16, n_heads=16,
        n_kv_heads=4, head_dim=128, d_ff=7168, remat="dots_attn")
    trainer = JaxTrainer(
        cfg, TrainConfig(mesh_axes={"dp": 1}, strategy="dp"),
        mesh=create_mesh({"dp": 1}, devices=v5e_2x2[:1]))
    text = _compile_step(trainer, 4, 2048).as_text()
    assert text.count("tpu_custom_call") == 3


def test_fsdp4_step_compiles_with_flash(v5e_2x2):
    """Llama-3-8B widths sharded over the four chips. The compiler cannot
    partition a Pallas kernel by itself ("Mosaic kernels cannot be
    automatically partitioned"), so this fails unless the attention
    dispatch wraps the kernel in shard_map under a multi-device mesh."""
    cfg = dataclasses.replace(llama.llama3_8b(), n_layers=2,
                              remat="dots_attn")
    trainer = JaxTrainer(
        cfg, TrainConfig(mesh_axes={"fsdp": 4}, strategy="fsdp",
                         fused_loss=True),
        mesh=create_mesh({"fsdp": 4}, devices=v5e_2x2))
    text = _compile_step(trainer, 4, 2048).as_text()
    assert text.count("tpu_custom_call") == 3
    assert "all-gather" in text       # the sharded parameters, gathered


# what the four-chip training cell's step (_D10_TRAIN, below) needed before the fused loss split the vocabulary (PR 38's
# tree, this compile): the ledger's peak_hbm_gb.train 15.108
_PARENT_TEMP_BYTES = 15_107_732_992
_COLLECTIVE = re.compile(
    r"^\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) (all-reduce|all-gather|all-to-all|"
    r"reduce-scatter|collective-permute)(?:-start)?\(", re.M)
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")


def _collectives(lines):
    """(operation, [(type, dims), ...]) of every collective among the
    lines: a result may be a tuple of arrays."""
    return [(op, [(t, tuple(int(n) for n in dims.split(",") if n))
                  for t, dims in _ARRAY.findall(result)])
            for result, op in _COLLECTIVE.findall("\n".join(lines))]


def _logit_blocks(collectives, rows=1024, vocab=32768):
    """The collectives whose result has a chunk's rows beside the
    vocabulary, whole or a device's share of it, in float32: a block of
    logits, or of their cotangent, crossing the chips."""
    return [(op, arrays) for op, arrays in collectives
            if any(t == "f32" and rows in dims
                   and {vocab, vocab // 4} & set(dims)
                   for t, dims in arrays)]


@pytest.fixture(scope="module")
def fsdp4_d10_steps(v5e_2x2):
    """The cell's step as the trainer builds it and, over the same four
    chips, with the loss left to the partitioner (the path a mesh of one
    device takes; steered from here, as ``attn_impl`` is): each one's
    trainer, compiled text and temporaries."""
    from ray_tpu.util import tracing

    steps = {}
    for form in ("split", "plain"):
        trainer = JaxTrainer(
            llama.LlamaConfig(**_D10_TRAIN),
            TrainConfig(mesh_axes={"fsdp": 4}, strategy="fsdp",
                        fused_loss=True),
            mesh=create_mesh({"fsdp": 4}, devices=v5e_2x2))
        tracing.enable_tracing()
        try:
            before = len(tracing.recorded_spans("train.compile_step"))
            trainer.compile_step(None, jax.ShapeDtypeStruct((16, 2049),
                                                            jnp.int32))
            span, = tracing.recorded_spans("train.compile_step")[before:]
        finally:
            tracing.disable_tracing()
        if form == "plain":
            trainer.loss_vocab_axes = ()
        compiled = _compile_step(trainer, 16, 2048)
        steps[form] = (trainer, span["attrs"], compiled.as_text(),
                       compiled.memory_analysis().temp_size_in_bytes)
    return steps


def test_fsdp4_loss_says_what_the_compiled_step_shows(fsdp4_d10_steps,
                                                      v5e_2x2):
    """``loss_vocab_axes`` and the ``train.compile_step`` span against
    the text: the head arrives in the loss loops as [d, vocab / 4], all of
    the model dimension and a quarter of the vocabulary; on one device
    the trainer states the plain path."""
    trainer, attrs, text, _ = fsdp4_d10_steps["split"]
    assert trainer.loss_vocab_axes == ("fsdp",)
    assert attrs == {"loss_vocab_axes": ["fsdp"], "loss_vocab_shards": 4}
    assert _in_loops(text, re.compile(r"= f32\[1024,8192\]\S* fusion\("))
    assert not _in_loops(text, re.compile(r"= f32\[1024,32768\]"))
    one = JaxTrainer(
        llama.LlamaConfig(**_D10_TRAIN),
        TrainConfig(mesh_axes={"fsdp": 1}, strategy="fsdp", fused_loss=True),
        mesh=create_mesh({"fsdp": 1}, devices=v5e_2x2[:1]))
    assert one.loss_vocab_axes == ()


def test_fsdp4_loss_moves_rows_not_logits(fsdp4_d10_steps):
    """No collective of the whole step carries the vocabulary beside a
    chunk's rows. In the loss's two loops vectors a row cross the chips in
    float32 (the maxima, the sums of exponentials with the targets'
    logits, the cotangent of the rows' losses), the rows are gathered in
    their own type, and the chunk's hidden cotangent is summed in float32:
    nothing of a chunk's rows by the model dimension is summed in
    bf16."""
    _, _, text, _ = fsdp4_d10_steps["split"]
    assert not _logit_blocks(_collectives(text.splitlines()))
    in_loops = _collectives(_loop_bodies(text))
    row_sums = [arrays for op, arrays in in_loops if op == "all-reduce"
                and all(a == ("f32", (1024,)) for a in arrays)]
    assert len(row_sums) >= 3
    gathered = [arrays for op, arrays in in_loops if op == "all-gather"
                and arrays[0][0] == "bf16" and arrays[0][1][-2:] == (1024, 4096)]
    assert len(gathered) == 2                # forward, and recomputed
    summed = [arrays[0] for op, arrays in in_loops
              if op in ("all-reduce", "reduce-scatter")
              and len(arrays[0][1]) == 2 and arrays[0][1][1] == 4096]
    # this compiler writes the reduce-scatter as an all-reduce of the padded
    # chunk that leaves each chip its rows (from-cross-replica-sharding)
    assert [t for t, _ in summed] == ["f32"]


def test_fsdp4_step_keeps_its_kernels_and_needs_less(fsdp4_d10_steps):
    _, _, text, temp = fsdp4_d10_steps["split"]
    assert text.count("tpu_custom_call") == 3
    assert temp < _PARENT_TEMP_BYTES
    assert temp < fsdp4_d10_steps["plain"][3]


def test_the_plain_loss_over_four_chips_does_all_reduce_logits(
        fsdp4_d10_steps):
    """The same walk finds what the split removes: left to the
    partitioner, the loss's forward loop and its recomputed backward each
    all-reduce a chunk's float32 logits [1024, 32768]."""
    _, _, text, _ = fsdp4_d10_steps["plain"]
    blocks = _logit_blocks(_collectives(_loop_bodies(text)))
    assert blocks == [("all-reduce", [("f32", (1024, 32768))])] * 2


# the serving cells' engine: Mistral-7B-v0.3 widths cut to 12 layers, 32
# slots x 2048 tokens, 544 KV pages of 128 tokens (142.6 MB a layer's K)
_D12 = dict(vocab_size=32768, d_model=4096, n_layers=12, n_heads=32,
            n_kv_heads=8, head_dim=128, d_ff=14336, rope_theta=1e6,
            tie_embeddings=False)
_D12_PAGES = 544
# the four-chip training cell's step: the same widths cut to 10 layers, 16 x
# 2048 tokens over {"fsdp": 4}, the fused loss in 32 chunks of 1,024 rows
# against the vocabulary's 32,768
_D10_TRAIN = dict(_D12, n_layers=10, remat="dots_attn")


def _pool_copy(layers, pages, nkv):
    """An operation that moves one layer's pool, or the stacked pool,
    whole (bf16 or int8 pages)."""
    return re.compile(
        rf"= (?:bf16|s8)\[(?:{layers},|1,)?{pages},128,{nkv},128\]\S* "
        r"(?:copy|dynamic-slice|dynamic-update-slice)\(")


# the decode kernel's instruction, under its name (PR 30), and the prefill
# kernel's (PR 34)
_DECODE_KERNEL = re.compile(
    r"%paged_decode_attn[.\d]* = \S+ custom-call\(.*tpu_custom_call")
_PREFILL_KERNEL = re.compile(
    r"%paged_prefill_attn[.\d]* = \S+ custom-call\(.*tpu_custom_call")


# the grouped expert kernel's instruction (PR 44): two a layer of routed
# experts, rows x the up stacks and x the down stack
_EXPERT_KERNEL = re.compile(
    r"%grouped_expert_ffn[.\d]* = \S+ custom-call\(.*tpu_custom_call")


def _expert_stack_moves(text, experts, d, f):
    """The instructions ANYWHERE in a compiled program (a run of one
    layer is no loop) that move the held experts' weights, whole or a
    layer of them: a copy, an asynchronous copy or slice whose result is
    ``bf16[..., experts, d, f]`` or ``[..., experts, f, d]``, or a fusion
    that writes ONE layer's stack (the slice of a scanned run of layers,
    which a kernel cannot read through: the engine hands the kernel the
    run's stacks and the layer's index instead). The grouped kernel takes
    a stack in the layout it lies in; asked for in another it would be
    copied whole, a GB a layer, under no name a trace shows."""
    stack = re.compile(rf"bf16\[(?:\d+,)?{experts},(?:{d},{f}|{f},{d})\]")
    sliced = re.compile(
        rf"^\s*(?:ROOT )?%\S+ = bf16\[(?:1,)?{experts},(?:{d},{f}|{f},{d})\]"
        r"\S* fusion\(")
    return [line.strip()[:160] for line in _outside_fusions(text)
            if sliced.match(line) or (m := _MOVE.match(line))
            and m.group(2) != "custom-call" and stack.search(m.group(1))]


def _outside_fusions(text):
    """The lines of a compiled program outside any fusion's own
    computation: inside one a value of an array's shape is no array in
    memory (every-expert's matmul reads its layer through such a one)."""
    fused = False
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            fused = line.lstrip("%").startswith("fused_computation")
        elif not fused:
            yield line


def _combine_relayouts(text, tokens, k, d):
    """The instructions of a compiled program, outside any fusion's own
    computation, that lay the (token, choice) pairs' float32 rows out
    anew on their way back to their tokens: under ``moe_combine`` a copy,
    a transpose, a reshape or a fusion whose result is ``f32[T, K, D]``
    (or its padded twin, K rounded up to a float32 tile's 8 sublanes:
    what ``[T*K, D] -> [T, K, D]`` costs with K beside D, PR 63), and a
    copy, transpose or reshape to ``[K, T, D]``, which splits the major
    axis and is a bitcast where nothing moves."""
    padded = -(-k // 8) * 8
    minor = re.compile(
        rf"^\s*(?:ROOT )?%\S+ = f32\[{tokens},(?:{k}|{padded}),{d}\]\S* "
        r"(?:copy|transpose|reshape|fusion)\(")
    major = re.compile(
        rf"^\s*(?:ROOT )?%\S+ = f32\[{k},{tokens},{d}\]\S* "
        r"(?:copy|transpose|reshape)\(")
    return [line.strip()[:160] for line in _outside_fusions(text)
            if "moe_combine" in line
            and (minor.match(line) or major.match(line))]


def _window(slots, pages, nkv):
    """An operation whose result is a page window of every slot (the
    gather decode attended over before PR 30, or a float32 copy of it)."""
    return re.compile(rf"= (?:bf16|f32|s8)\[(?:{slots * pages}|"
                      rf"{slots},{pages}),128,{nkv},128\]")


@cache        # several tests read the same program's text
def _compile_engine_program(device, model, cfg, num_pages, program, dims,
                            **engine):
    return _lower_engine_program(device, model, cfg, num_pages, program,
                                 dims, **engine).compile()


def _lower_engine_program(device, model, cfg, num_pages, program, dims,
                          slots=32, page=128, kv_dtype="bf16"):
    """One of the paged engine's two programs for ``cfg`` (whose block
    ``model`` states), lowered for ``device`` from shapes alone.
    ``dims``: decode (chunk, window pages); prefill (prompts, tokens,
    window pages). ``kv_dtype``: the engine's, bf16 or int8 pages (these
    with their scale pools)."""
    one = SingleDeviceSharding(device)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    params = jax.tree.map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(partial(model.init_params, cfg), jax.random.key(0)))
    quantized = kv_dtype == "int8"
    pool_dims = (cfg.n_layers, num_pages, page, cfg.n_kv_heads, cfg.head_dim)
    pool = shape(pool_dims, jnp.int8 if quantized else jnp.bfloat16)
    scale = shape(pool_dims[:-1] if quantized else (cfg.n_layers, 1, 1, 1),
                  jnp.float32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    # the slots' recurrent state, where the plan has a recurrent run: the
    # engine's own arrays [layers, slots, ...], after the key, donated
    recurrent = next((run.state for run in model.layer_plan(cfg)
                      if run.state is not None), None)
    state = tuple(shape((cfg.n_layers, slots, *dims_), dtype)
                  for _, dims_, dtype in (recurrent.arrays if recurrent
                                          else ()))
    if program == "decode":
        chunk, pages = dims
        fn = partial(PagedLLMEngine._paged_decode_impl, cfg, chunk=chunk,
                     page_size=page, quantized=quantized)
        args = (shape((slots, pages), jnp.int32), shape((slots,), jnp.int32),
                shape((slots,), jnp.int32), shape((slots,), jnp.bool_),
                shape((slots,), jnp.float32), key, *state)
    else:
        n, tokens, pages = dims
        fn = partial(PagedLLMEngine._paged_prefill_impl, cfg,
                     page_size=page, quantized=quantized)
        args = (shape((n, pages), jnp.int32), shape((n, tokens), jnp.int32),
                shape((n,), jnp.int32), shape((n,), jnp.int32),
                shape((n,), jnp.float32), key, *state,
                *([shape((n,), jnp.int32)] if state else []))
    donated = (1, 2, 3, 4) + tuple(range(11, 11 + len(state)))
    return jax.jit(fn, donate_argnums=donated).lower(
        params, pool, pool, scale, scale, *args)


_CALLED = re.compile(r"(?:calls|body|condition|to_apply)=(%[\w.\-]+)|"
                     r"(?:branch|called)_computations=\{([^}]*)\}")
_MOVE = re.compile(r"^\s*(?:ROOT )?%\S+ = (.*?) (copy-start|copy-done|"
                   r"slice-start|slice-done|copy|custom-call)\(")


def _loop_bodies(text):
    """The instructions of a compiled program's loops: the lines of the
    computations its ``while``s run, and of whatever those call."""
    bodies, name = {}, None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = line.split()[1 if line.startswith("ENTRY") else 0]
            bodies[name] = []
        elif name is not None:
            bodies[name].append(line)

    def called(lines):
        for hit in _CALLED.finditer("\n".join(lines)):
            yield from re.findall(r"%[\w.\-]+", hit.group(1) or hit.group(2))

    todo = list(called(line for lines in bodies.values() for line in lines
                       if " while(" in line))
    in_loops = set()
    while todo:
        comp = todo.pop()
        if comp in in_loops or comp not in bodies:
            continue
        in_loops.add(comp)
        todo.extend(called(bodies[comp]))
    assert in_loops, "the program has no loop"
    return [line for comp in in_loops for line in bodies[comp]]


def _in_loops(text, instruction) -> int:
    """How many instructions of the program's loops match."""
    return sum(bool(instruction.search(line)) for line in _loop_bodies(text))


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


def _stack_moves_in_loops(text, layers, d_in, widths):
    """The instructions of a compiled program's loops (the computations
    its ``while``s run, and whatever those call) that MOVE a stack of
    projection weights, whole or in part: a copy, an asynchronous copy
    or slice, or the ``ConcatBitcast`` that joins such slices, whose
    result is ``bf16[k, d_in, width]`` for 1 <= k <= ``layers``. What the
    compiler parks on the core (``S(1)`` in a layout) it may write back
    and fetch again round a kernel that needs the room, every trip of
    the loop: such traffic has no name of its own in a trace and shows
    only here. A fusion that READS a layer of a stack in place is no
    move."""
    stack = re.compile(
        rf"bf16\[(\d+),{d_in},(?:{'|'.join(map(str, widths))})\]")
    moves = []
    for line in _loop_bodies(text):
        m = _MOVE.match(line)
        if not m or (m.group(2) == "custom-call"
                     and "ConcatBitcast" not in line):
            continue
        if any(int(k) <= layers for k in stack.findall(m.group(1))):
            moves.append(line.strip()[:160])
    return moves


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
    compiled = _compile_engine_program(
        v5e_2x2[0], llama, llama.LlamaConfig(**_D12), _D12_PAGES, program,
        dims)
    text = compiled.as_text()
    assert not _pool_copy(12, _D12_PAGES, 8).findall(text)
    assert compiled.memory_analysis().temp_size_in_bytes < temp_gb * 1e9
    # decode reads the pages where they lie (PR 30): the kernel under its
    # name, four query heads a KV head, and no window of the slots' pages
    assert bool(_DECODE_KERNEL.search(text)) == (program == "decode")
    if program == "decode":
        assert not _window(32, dims[1], 8).findall(text)


def _serving_model(name):
    """A serve cell's block module and configuration, by its name."""
    if name == "d12":
        return llama, llama.LlamaConfig(**_D12)
    if name == "laguna-ep4-d5":
        from ray_tpu.models import laguna

        # serve-code-gen's: a leading layer and one period, 64 of each
        # sparse layer's 256 experts, a quarter of the vocabulary
        return laguna, dataclasses.replace(
            laguna.laguna_s_2_1(), layer_types=(laguna._PERIOD * 2)[:5],
            n_experts_held=64, vocab_size=25088)
    if name == "falcon-h1-d4":
        from ray_tpu.models import falcon_h1

        return falcon_h1, dataclasses.replace(
            falcon_h1.falcon_h1_34b_instruct(), n_layers=_H1_LAYERS)
    if name == "smallthinker-d8":
        from ray_tpu.models import smallthinker

        # serve-brief-gen's: full, three sliding, twice
        period = (0, 1, 1, 1)
        return smallthinker, dataclasses.replace(
            smallthinker.smallthinker_21b_a3b(),
            sliding_window_layout=period * 2, rope_layout=period * 2)
    return olmoe, dataclasses.replace(olmoe.olmoe_1b_7b(), n_layers=10)


@pytest.mark.parametrize("model,pages,window", [
    ("d12", _D12_PAGES, 16), ("olmoe-d10", 352, 8)])
def test_decode_programs_compile_over_int8_pages(v5e_2x2, model, pages,
                                                 window):
    """The same decode program over int8 pages and their scale pools:
    the same kernel (the pool's dtype is all that differs), dequantising
    in VMEM; no window of the pages in any type, no pool moved whole. The
    window's SCALES are gathered (1/32 of its bytes)."""
    module, cfg = _serving_model(model)
    compiled = _compile_engine_program(
        v5e_2x2[0], module, cfg, pages, "decode", (16, window),
        kv_dtype="int8")
    text = compiled.as_text()
    assert _DECODE_KERNEL.search(text)
    assert not _window(32, window, cfg.n_kv_heads).findall(text)
    assert not _pool_copy(cfg.n_layers, pages, cfg.n_kv_heads).findall(text)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.8e9
    # (OLMoE's block states no fused stack, and over int8 pages its decode
    # program does evict and refetch the parked ``wv`` stack every layer:
    # no cell runs it; ROADMAP Queue 1 item 3)
    if model == "d12":
        assert not _stack_moves_in_loops(text, cfg.n_layers, cfg.d_model,
                                         _projection_widths(cfg))


# the expert cell's engine (PR 28): OLMoE-1B-7B widths cut to 10 layers,
# 32 slots x 1024 tokens, 352 KV pages of 128 tokens (16 KV heads)
_MOE_PAGES, _MOE_LAYERS = 352, 10
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
    cfg = dataclasses.replace(olmoe.olmoe_1b_7b(), n_layers=_MOE_LAYERS)
    compiled = _compile_engine_program(v5e_2x2[0], olmoe, cfg, _MOE_PAGES,
                                       program, dims)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    from ray_tpu.ops.moe import expert_kernel_engages

    rows = 32 if program == "decode" else dims[0] * dims[1]
    assert expert_kernel_engages(rows) == grouped
    assert "ragged-dot" not in text
    assert len(_EXPERT_KERNEL.findall(text)) == 2 * grouped
    assert _in_loops(text, _EXPERT_KERNEL) == 2 * grouped
    assert not _expert_stack_moves(text, 64, 2048, 1024)
    assert not _pool_copy(_MOE_LAYERS, _MOE_PAGES, 16).findall(text)
    # decode: the same kernel at one query head a KV head (MHA), and
    # neither the window nor a float32 copy of it
    assert bool(_DECODE_KERNEL.search(text)) == (program == "decode")
    if program == "decode":
        assert not _window(32, dims[1], 16).findall(text)
    pool_bytes = _MOE_LAYERS * _MOE_PAGES * 128 * 16 * 128 * 2
    assert mem.alias_size_in_bytes >= 2 * pool_bytes        # pools in place
    assert mem.temp_size_in_bytes < 0.8e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes) < 13.5e9              # of 15.75 GB


def _projection_widths(cfg):
    """The output widths a stack of attention projection weights can
    have: q (and ``wo``'s input), k or v, and the three fused; at each
    number of query heads the model's layers have."""
    kv = cfg.n_kv_heads * cfg.head_dim
    heads = {cfg.n_heads, getattr(cfg, "n_heads_sliding", cfg.n_heads)}
    return sorted({w for h in heads for w in (
        h * cfg.head_dim, kv, (h * cfg.head_dim) + 2 * kv)})


@pytest.mark.parametrize("model,pages,window,chunk", [
    ("d12", _D12_PAGES, 16, 16), ("d12", _D12_PAGES, 16, 8),
    ("olmoe-d10", _MOE_PAGES, 8, 16), ("olmoe-d10", _MOE_PAGES, 8, 8),
    ("laguna-ep4-d5", 2304, 32, 16), ("laguna-ep4-d5", 2304, 32, 8)])
def test_decode_loops_move_no_projection_weight_stack(v5e_2x2, model, pages,
                                                      window, chunk):
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
    module, cfg = _serving_model(model)
    slots, widths = {}, _projection_widths(cfg)
    if model.startswith("laguna"):
        # its blocks hold no k or v stack of their own: what is that wide
        # (bf16[1, 3072, 1024]) is the last layer's shared expert, 6.3 MB
        # fetched a layer ahead and read once
        slots = {"slots": 64}
        widths.remove(cfg.n_kv_heads * cfg.head_dim)
    compiled = _compile_engine_program(v5e_2x2[0], module, cfg, pages,
                                       "decode", (chunk, window), **slots)
    moves = _stack_moves_in_loops(compiled.as_text(), cfg.n_layers,
                                  cfg.d_model, widths)
    assert not moves, "\n".join(moves)
    # the fused stack is built once a run, at the entry, in place of the
    # three transposed entry copies: the same bytes of temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < 0.8e9


def _score_arrays(text, keys):
    """The float32 arrays of attention-score shape in a program's text:
    [rows, KV heads, heads a KV head, queries, ``keys``] of 4M elements
    or more (the plain prefill attention's scores, whole or a block of
    queries of them; an expert layer's [tokens x 8, 1024] is none)."""
    found = set()
    for dims in re.findall(r"f32\[([\d,]+)\]", text):
        sizes = [int(d) for d in dims.split(",")]
        elements = 1
        for size in sizes:
            elements *= size
        if len(sizes) >= 4 and sizes[-1] == keys and elements >= 1 << 22:
            found.add(dims)
    return sorted(found)


def _moved_shapes(text, cfg):
    return {re.search(r"bf16\[[\d,]+\]", line).group()
            for line in _stack_moves_in_loops(
                text, cfg.n_layers, cfg.d_model, _projection_widths(cfg))}


# model, KV pages, prefill (prompts, tokens, window pages), the kernel's
# instructions in the program, GB of temporaries it may need
_BRIEF_PAGES = 2304
_PREFILL_RULE = [
    # serve-doc's two widest programs
    ("d12", _D12_PAGES, (2, 2048, 16), 1, 0.5),
    ("d12", _D12_PAGES, (1, 2048, 16), 1, 0.5),
    # the reference check's 600 tokens (128 MiB of scores) and a prefix
    # hit's suffix (32 MiB): under the rule
    ("d12", _D12_PAGES, (1, 1024, 8), 0, 0.5),
    ("d12", _D12_PAGES, (2, 64, 16), 0, 0.5),
    # everything serve-moe-gen warms stays plain (128 MiB at most)
    ("olmoe-d10", _MOE_PAGES, (2, 512, 8), 0, 0.8),
    ("olmoe-d10", _MOE_PAGES, (2, 1024, 8), 0, 0.8),
    # Laguna's two runs of full layers (48 heads) and its run of three
    # sliding ones between them (72 heads under 512 keys; PR 56), an
    # instruction each: a cold file, two of them, the warm-up's 4,095
    # tokens, and a short suffix
    ("laguna-ep4-d5", 2304, (1, 2048, 16), 3, 1.4),
    ("laguna-ep4-d5", 2304, (2, 2048, 16), 3, 1.4),
    ("laguna-ep4-d5", 2304, (1, 4096, 32), 3, 1.4),
    ("laguna-ep4-d5", 2304, (1, 64, 32), 0, 1.4),
    # serve-brief-gen's cold document (full, three sliding, twice: four
    # runs; the parent's program needed 1.22848 GB of temporaries) and a
    # cached document's question, under the rule in both kinds of layer
    ("smallthinker-d8", _BRIEF_PAGES, (1, 8192, 64), 4, 1.2284),
    ("smallthinker-d8", _BRIEF_PAGES, (1, 128, 64), 0, 0.1),
]


@pytest.mark.parametrize(
    "model,pages,dims,kernels,temp_gb", _PREFILL_RULE,
    ids=[f"{m}-{'x'.join(map(str, d))}" for m, _, d, _, _ in _PREFILL_RULE])
def test_prefill_programs_hold_the_kernel_by_the_rule(v5e_2x2, model, pages,
                                                      dims, kernels,
                                                      temp_gb):
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
    module, cfg = _serving_model(model)
    compiled = _compile_engine_program(v5e_2x2[0], module, cfg, pages,
                                       "prefill", dims)
    text = compiled.as_text()
    assert len(_PREFILL_KERNEL.findall(text)) == kernels
    assert not _DECODE_KERNEL.search(text)
    # the routed experts' kernel follows its own rule, the rows alone:
    # two instructions a run of expert layers (Laguna's two runs: four;
    # SmallThinker's four: eight)
    from ray_tpu.ops.moe import expert_kernel_engages
    from ray_tpu.ops.paged_prefill_attention import query_block

    expert_runs = {"d12": 0, "olmoe-d10": 1, "laguna-ep4-d5": 2,
                   "smallthinker-d8": 4}[model]
    assert len(_EXPERT_KERNEL.findall(text)) == (
        2 * expert_runs * expert_kernel_engages(dims[0] * dims[1]))
    assert "ragged-dot" not in text
    if model == "laguna-ep4-d5":
        assert not _expert_stack_moves(text, 64, 3072, 1024)
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
            assert not _score_arrays(text, keys), keys
    assert compiled.memory_analysis().temp_size_in_bytes < temp_gb * 1e9
    assert not _pool_copy(cfg.n_layers, pages, cfg.n_kv_heads).findall(text)
    moved = _moved_shapes(text, cfg)
    assert all(shape.startswith("bf16[1,") for shape in moved), moved
    if model == "d12" and kernels:
        plain = _compile_engine_program(v5e_2x2[0], module, cfg, pages,
                                        "prefill", (2, 64, 16)).as_text()
        assert moved == _moved_shapes(plain, cfg)


# the prefill kernel's launches: query heads, KV heads, rows, tokens a
# row, table pages, window
_PREFILL_KERNEL_SHAPES = {
    "mha-16x16": (16, 16, 2, 2048, 16, None),
    "gqa-48x8": (48, 8, 1, 4096, 32, None),
    "gqa-32x8": (32, 8, 1, 2048, 16, None),
    "gqa-28x4": (28, 4, 1, 8192, 64, None),
    "gqa-28x4-window-4096": (28, 4, 1, 8192, 64, 4096),
    "gqa-72x8-window-512": (72, 8, 2, 2048, 16, 512)}
# sha256 (first 16 hex digits) of a full layer's launch as the commit
# before the walk took a window (9951e21: PR 55's anchor) lowered it for a
# v5e, by this file's own helpers laid over that tree, under
# ``_PINNED_JAX``
_PARENT_PREFILL_KERNEL = {
    "mha-16x16": "15b0daf144d5fb75", "gqa-48x8": "83f850d6f27e9078",
    "gqa-32x8": "9d9ee6b13224f1ad", "gqa-28x4": "3b9a8e7799b05dc6"}


def _lower_prefill_kernel(device, heads, kv_heads, rows, tokens, pages,
                          window):
    from ray_tpu.ops.paged_prefill_attention import (
        paged_prefill_attention_kernel)

    one_chip = SingleDeviceSharding(device)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pool = shape((5, 600, 128, kv_heads, 128), jnp.bfloat16)
    scale = shape((5, 1, 1, 1), jnp.float32)
    kernel = (paged_prefill_attention_kernel if window is None else partial(
        paged_prefill_attention_kernel, window=window))
    return jax.jit(kernel).lower(
        shape((rows, tokens, heads, 128), jnp.bfloat16), pool, pool, scale,
        scale, shape((), jnp.int32), shape((rows, pages), jnp.int32),
        shape((rows,), jnp.int32), shape((rows,), jnp.int32))


@pytest.mark.parametrize("case", _PREFILL_KERNEL_SHAPES,
                         ids=list(_PREFILL_KERNEL_SHAPES))
def test_prefill_kernel_compiles_alone(v5e_2x2, case):
    """The kernel by itself at the head layouts the engine serves in full
    layers (no whole program holds it at OLMoE's 16/16: that cell's
    contexts stop at 1,024 tokens, under the rule) and at the two it
    serves under a window: SmallThinker's groups of 7 under 4,096 keys at
    the cold document's 8,192 rows, Laguna's sliding groups of 9 under
    512."""
    compiled = _lower_prefill_kernel(
        v5e_2x2[0], *_PREFILL_KERNEL_SHAPES[case]).compile()
    assert len(_PREFILL_KERNEL.findall(compiled.as_text())) == 1


@pytest.mark.parametrize("case", _PARENT_PREFILL_KERNEL,
                         ids=list(_PARENT_PREFILL_KERNEL))
def test_a_full_layers_prefill_kernel_is_the_one_it_was(v5e_2x2, case):
    """Without a window the kernel is the parent's instruction for
    instruction (the window is a Python branch on a static argument, not
    a traced select): its launch and its Mosaic body lower to the same
    text, source locations left out. The prefill programs of
    ``serve-doc`` and ``serve-chat``, Laguna's full layers and
    SmallThinker's hold this kernel. A change MEANT to alter it pins its
    new digest here, computed on its own tree."""
    import hashlib

    if jax.__version__ != _PINNED_JAX:
        pytest.skip(f"digests pinned under jax {_PINNED_JAX}")
    text = _located_nowhere(_lower_prefill_kernel(
        v5e_2x2[0], *_PREFILL_KERNEL_SHAPES[case]).as_text())
    assert ".py" not in text and "loc(" not in text
    assert (hashlib.sha256(text.encode()).hexdigest()[:16]
            == _PARENT_PREFILL_KERNEL[case])
    windowed = _located_nowhere(_lower_prefill_kernel(
        v5e_2x2[0], *_PREFILL_KERNEL_SHAPES[case][:-1], 512).as_text())
    assert windowed != text         # the fence is not blind


def test_the_plain_prefill_path_does_hold_score_arrays(v5e_2x2):
    """The fence above is not blind: the d12 program under the rule
    (two 64-token suffixes over 2048 keys) holds its float32 scores."""
    text = _compile_engine_program(
        v5e_2x2[0], llama, llama.LlamaConfig(**_D12), _D12_PAGES, "prefill",
        (2, 64, 16)).as_text()
    assert _score_arrays(text, 2048) == ["2,8,4,64,2048"]


# Falcon-H1-34B-Instruct cut to 4 blocks (``serve-instruct-gen``): 128
# slots, 1280 KV pages, and each slot's recurrent state beside them
_H1_LAYERS, _H1_SLOTS, _H1_PAGES = 4, 128, 1280
_H1_PROGRAMS = [("decode", (8, 8)), ("decode", (16, 8)),
                ("prefill", (2, 1024, 8)), ("prefill", (2, 512, 4))]
_STATE_COPY = re.compile(
    r"= f32\[(?:4,|1,)?128,32,128,256\]\S* (?:copy|copy-start)\(")
# the state kernel's instruction, under its name: the stacked state among
# its operands and, in place, among its results
_STATE_KERNEL = re.compile(
    r"%ssm_state_step[.\d]* = \(.*f32\[4,128,32,128,256\]\S*\) "
    r"custom-call\(.*tpu_custom_call")


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
    falcon_h1, cfg = _serving_model("falcon-h1-d4")
    compiled = _compile_engine_program(
        v5e_2x2[0], falcon_h1, cfg, _H1_PAGES, program, dims,
        slots=_H1_SLOTS)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    pool_bytes = _H1_LAYERS * _H1_PAGES * 128 * 4 * 128 * 2
    state_bytes = _H1_LAYERS * _H1_SLOTS * (4 * 32 * 128 * 256
                                            + 2 * 3 * 5120)
    assert 12.2e9 < mem.argument_size_in_bytes < 12.4e9
    assert mem.alias_size_in_bytes >= 2 * pool_bytes + state_bytes
    assert mem.temp_size_in_bytes < 1.0e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes) < 13.5e9              # of 15.75 GB
    assert not _pool_copy(_H1_LAYERS, _H1_PAGES, 4).findall(text)
    assert bool(_DECODE_KERNEL.search(text)) == (program == "decode")
    assert len(_STATE_KERNEL.findall(text)) == (program == "decode")
    if program == "decode":
        assert not _STATE_COPY.findall(text)
        # the kernel's call is in the layer loop, once, and nothing else
        # there (or anywhere) passes over the state
        assert _in_loops(text, _STATE_KERNEL) == 1
        assert not _state_passes(text)
        # five query heads a KV head through the decode kernel, and no
        # stack of projection weights moved in the loops: q | k | v are
        # one stack (147 MB), wo's (105 MB) is read where it lies
        assert not _stack_moves_in_loops(
            text, _H1_LAYERS, cfg.d_model, (3584, 9248, cfg.d_ff))
        assert not _stack_moves_in_loops(text, _H1_LAYERS, 2560,
                                         (cfg.d_model,))
        assert not _stack_moves_in_loops(text, _H1_LAYERS, cfg.d_ssm,
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
    cfg = dataclasses.replace(falcon_h1.falcon_h1_34b_instruct(),
                              n_layers=_H1_LAYERS)
    text = _lower_engine_program(
        v5e_2x2[0], falcon_h1, cfg, _H1_PAGES, "decode", (8, 8),
        slots=_H1_SLOTS).compile().as_text()
    assert not _STATE_KERNEL.search(text)
    passes = _state_passes(text)
    assert any(" fusion" in p for p in passes), passes
    stack = [p for p in passes if "= f32[4,128,32,128,256]" in p]
    assert stack, passes


@pytest.mark.parametrize("heads,width,size,groups", [
    (32, 128, 256, 2), (24, 64, 128, 1), (80, 64, 128, 8), (6, 8, 128, 2),
    (128, 64, 128, 1)],
    ids=["falcon-h1-34b", "24x64x128", "80x64x128", "six-tiny-heads",
         "granite-4.0-h-small"])
def test_state_kernel_compiles_alone(v5e_2x2, heads, width, size, groups):
    """The kernel by itself at the published head shapes and at others
    its rule admits (heads in one block of 24, in five of 16, six heads
    that are no whole block of 8, 128 heads of ONE group in four blocks
    of 32): the chip's compiler takes the tiles, and the stacked state
    is aliased from operand to result."""
    from ray_tpu.ops import ssm

    one_chip = SingleDeviceSharding(v5e_2x2[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    slots = 8
    states = shape((2, slots, heads, width, size), jnp.float32)
    assert ssm.state_kernel_engages(states)
    compiled = jax.jit(ssm.ssm_state_step_kernel, donate_argnums=(5,)).lower(
        shape((slots, heads, width), jnp.bfloat16),
        shape((slots, heads), jnp.float32), shape((heads,), jnp.float32),
        shape((slots, groups, size), jnp.bfloat16),
        shape((slots, groups, size), jnp.bfloat16), states,
        shape((), jnp.int32), shape((slots,), jnp.bool_)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * slots * heads * width * size * 4
    assert mem.temp_size_in_bytes < 1 << 20



def _dense_rows(text, layers, pages, rows):
    """The opcodes of the instructions whose result is a pool seen as
    dense rows ``[layers, pages, page * nkv, 128]`` (the decode kernel's
    view of it at fewer than 8 KV heads: a bitcast, or it is a copy)."""
    return re.findall(
        rf"= (?:bf16|s8)\[{layers},{pages},{rows},128\]\S* ([\w\-]+)\(", text)


# (slots, query heads, KV heads, table pages, window, pages' type): the
# three cells whose step of the walk takes more than a page
# (``serve-brief-gen``'s full and sliding layers, ``serve-instruct-gen``,
# ``serve-reason-gen``), and int8 pages at 4 KV heads (at 2 an int8 pool
# is tiled by four sublanes with two of them padding, a step is a page,
# and Mosaic refuses that kernel as it refused the parent's: no case)
_DECODE_KERNEL_SHAPES = {
    "28x4-full": (32, 28, 4, 64, None, jnp.bfloat16),
    "28x4-window-4096": (32, 28, 4, 64, 4096, jnp.bfloat16),
    "20x4": (128, 20, 4, 4, None, jnp.bfloat16),
    "32x2": (128, 32, 2, 16, None, jnp.bfloat16),
    "28x4-int8": (32, 28, 4, 64, None, jnp.int8),
    "20x4-int8-window": (128, 20, 4, 16, 512, jnp.int8),
}


def _lower_decode_kernel(device, slots, heads, kv_heads, pages, window,
                         dtype, pool_pages=600):
    from ray_tpu.ops.paged_decode_attention import (
        paged_decode_attention_kernel)

    one_chip = SingleDeviceSharding(device)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pool = shape((3, pool_pages, 128, kv_heads, 128), dtype)
    scale = shape((3, pool_pages, 128, kv_heads) if dtype == jnp.int8
                  else (3, 1, 1, 1), jnp.float32)
    return jax.jit(partial(paged_decode_attention_kernel,
                           window=window)).lower(
        shape((slots, heads, 128), jnp.bfloat16), pool, pool, scale, scale,
        shape((), jnp.int32), shape((slots, pages), jnp.int32),
        shape((slots,), jnp.int32), shape((slots,), jnp.bool_))


@pytest.mark.parametrize("case", _DECODE_KERNEL_SHAPES,
                         ids=list(_DECODE_KERNEL_SHAPES))
def test_decode_kernel_compiles_alone(v5e_2x2, case):
    """The decode kernel by itself where a step of its walk is 2 and 4
    pages (4 and 2 KV heads): the chip's compiler takes the copies into a
    step's rows, the dense operands and the step's scale row, and the
    pools reach it as they lie: the view ``[L, P, page * nkv, hd]`` is a
    bitcast, and nothing of a pool's size is made."""
    slots, heads, kv_heads, pages, window, dtype = _DECODE_KERNEL_SHAPES[case]
    compiled = _lower_decode_kernel(v5e_2x2[0], slots, heads, kv_heads,
                                    pages, window, dtype).compile()
    text = compiled.as_text()
    assert len(_DECODE_KERNEL.findall(text)) == 1
    assert text.count("tpu_custom_call") == 1
    assert _dense_rows(text, 3, 600, 128 * kv_heads) == ["bitcast"] * 2
    assert not _pool_copy(3, 600, kv_heads).findall(text)
    # nothing but the int8 window's scale rows (1/32 of its bytes)
    assert compiled.memory_analysis().temp_size_in_bytes < (
        128 << 20 if dtype == jnp.int8 else 1 << 20)


def _located_nowhere(lowered_text):
    """A lowered program's text with each Mosaic kernel's body, which is
    bytecode that carries the source's line numbers, replaced by its
    assembly without them: what a digest of the kernel can be taken of
    across an edit that moves its lines."""
    import base64
    import json

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def body(found):
        config = json.loads(re.sub(
            r"\\([0-9A-Fa-f]{2})", lambda m: chr(int(m.group(1), 16)),
            found.group(1)))
        context = mlir.make_ir_context()
        context.allow_unregistered_dialects = True
        with context:
            module = ir.Module.parse(
                base64.b64decode(config["custom_call_config"]["body"]))
            return module.operation.get_asm(enable_debug_info=False)

    return re.sub(r'backend_config = "(\{\\22custom_call_config.*?)"(?=[,}] )',
                  body, lowered_text)


# sha256 (first 16 hex digits) of the kernel's launch as the commit before
# the many-page step (b6abfad: PR 51) lowered it for a v5e, by this file's
# own helpers laid over that tree, under the jax named below: (slots,
# query heads, KV heads, table pages, window, pages' type)
_PARENT_DECODE_KERNEL = {
    "32x8": ((32, 32, 8, 16, None, jnp.bfloat16), "25b816855241afc9"),
    "16x16": ((32, 16, 16, 8, None, jnp.bfloat16), "9801f4a8da5b3c2e"),
    "48x8-window-512": ((32, 48, 8, 64, 512, jnp.bfloat16),
                        "aafa3fd0301fdb62"),
    "32x8-int8": ((32, 32, 8, 16, None, jnp.int8), "e21d62d6bab2ef5a"),
    "16x16-int8": ((32, 16, 16, 8, None, jnp.int8), "2c68b00781ed4c22"),
}
_PINNED_JAX = "0.9.0"


@pytest.mark.parametrize("case", _PARENT_DECODE_KERNEL,
                         ids=list(_PARENT_DECODE_KERNEL))
def test_decode_kernel_at_8_and_16_kv_heads_is_the_one_it_was(v5e_2x2, case):
    """Where a page holds 1,024 rows or more a step of the walk is the
    page, and the kernel is the parent's instruction for instruction: its
    launch and its Mosaic body lower to the same text (source locations
    left out), with and without a window and over int8 pages. The decode
    programs of ``serve-doc``, ``serve-chat``, ``serve-moe-gen`` and
    ``serve-code-gen`` hold this kernel and no other. A change MEANT to
    alter it pins its new digest here, computed on its own tree."""
    import hashlib

    if jax.__version__ != _PINNED_JAX:
        pytest.skip(f"digests pinned under jax {_PINNED_JAX}")
    dims, digest = _PARENT_DECODE_KERNEL[case]
    text = _located_nowhere(_lower_decode_kernel(v5e_2x2[0], *dims).as_text())
    assert ".py" not in text and "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


def test_decode_kernel_at_4_kv_heads_is_another(v5e_2x2):
    """The fence above is not blind: where a step takes two pages the
    same launch at the same shapes lowers to another text than a
    one-page step's would (dense operands, a step's buffer)."""
    text = _located_nowhere(_lower_decode_kernel(
        v5e_2x2[0], 32, 28, 4, 64, None, jnp.bfloat16).as_text())
    assert "memref<3x600x512x128xbf16" in text
    assert "memref<2x1024x128xbf16" in text
    assert "memref<3x600x128x4x128xbf16" not in text.split("custom_call")[1]


# SmallThinker-21BA3B-Instruct cut to its first eight layers
# (``serve-brief-gen``): 32 slots, ``_BRIEF_PAGES`` pages, tables of 64


def test_brief_d8_decode_program_reads_its_pages_in_place(v5e_2x2):
    """The decode program of the cell whose kernel walks two pages a
    step, at the published widths (28 query heads on 4 KV heads): one
    kernel instruction a run of the plan (full, three sliding, full,
    three sliding), each handed the stacked pools as dense rows ``[8,
    2304, 512, 128]`` by a bitcast of the pool ``write_kv`` scattered
    into, and no operation that copies, slices or rewrites a pool of
    either shape; the pools are donated and come back in place."""
    smallthinker, cfg = _serving_model("smallthinker-d8")
    compiled = _compile_engine_program(
        v5e_2x2[0], smallthinker, cfg, _BRIEF_PAGES, "decode", (16, 64))
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert len(_DECODE_KERNEL.findall(text)) == 4
    assert _in_loops(text, _DECODE_KERNEL) == 4
    assert _dense_rows(text, 8, _BRIEF_PAGES, 512) == ["bitcast"] * 8
    assert not _pool_copy(8, _BRIEF_PAGES, 4).findall(text)
    assert not _window(32, 64, 4).findall(text)
    pool_bytes = 8 * _BRIEF_PAGES * 128 * 4 * 128 * 2
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

    smallthinker, cfg = _serving_model("smallthinker-d8")
    text = _compile_engine_program(
        v5e_2x2[0], smallthinker, cfg, _BRIEF_PAGES, "prefill",
        (1, 8192, 64)).as_text()
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


# dots3-note-prev cut to its first five layers (``serve-note-gen``): 64
# slots, 2,816 pages of latent rows in whole lanes, tables of 64 pages
_NOTE_SLOTS, _NOTE_PAGES, _NOTE_TABLE = 64, 2816, 64
# the latent kernel's instruction, under its name: the rows' pool among
# its operands, the weighted rows [slots, heads, rank] its result
_LATENT_KERNEL = re.compile(
    r"%latent_decode_attn[.\d]* = bf16\[64,128,512\]\S* custom-call\("
    r".*tpu_custom_call.*bf16\[2,2816,128,640\]")


@pytest.mark.parametrize("heads,rank,lanes,table", [
    (128, 512, 640, 64), (128, 512, 640, 32), (64, 1024, 1152, 64),
    (16, 128, 256, 6)],
    ids=["full-layer", "half-table", "sliding-row", "groups-of-two"])
def test_latent_kernel_compiles_alone(v5e_2x2, heads, rank, lanes, table):
    """The kernel by itself at the full layers' shape in the cell's two
    tables, at the sliding layers' row (which no program gives it) and at
    a table it walks two pages at a time: the chip's compiler takes the
    tiles, the page buffers' slices and the flags' blocks."""
    from ray_tpu.ops.latent_attention import latent_decode_attention_kernel

    one_chip = SingleDeviceSharding(v5e_2x2[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(partial(latent_decode_attention_kernel, rank=rank,
                               scale=0.07)).lower(
        shape((_NOTE_SLOTS, heads, lanes), jnp.bfloat16),
        shape((2, 600, 128, lanes), jnp.bfloat16), shape((), jnp.int32),
        shape((_NOTE_SLOTS, table), jnp.int32),
        shape((_NOTE_SLOTS,), jnp.int32),
        shape((_NOTE_SLOTS, table * 128), jnp.bool_)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


# the index kernel's instruction, under its name: the index keys' pool
# among its operands, the scores (a row a step of its walk: 16 pages of
# 128 keys) its result
_INDEX_KERNEL = re.compile(
    r"%index_decode_scores[.\d]* = f32\[64,4,2048\]\S* custom-call\("
    r".*tpu_custom_call.*bf16\[2,2816,128,128\]")


@pytest.mark.parametrize("heads,table,pool_pages", [
    (64, 64, _NOTE_PAGES), (64, 32, _NOTE_PAGES), (4, 6, 600)],
    ids=["full-layer", "half-table", "groups-of-two"])
def test_index_kernel_compiles_alone(v5e_2x2, heads, table, pool_pages):
    """The index kernel by itself at the cell's shape (64 slots, 64 index
    heads of 128 numbers, the stacked pool of two full layers) in the
    cell's two tables, and at a table it walks two pages at a time: the
    chip's compiler takes the page buffers' slices, the weights' column
    and the scores' rows."""
    from ray_tpu.ops.index_select import index_decode_scores_kernel

    one_chip = SingleDeviceSharding(v5e_2x2[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(index_decode_scores_kernel).lower(
        shape((_NOTE_SLOTS, heads, 128), jnp.bfloat16),
        shape((_NOTE_SLOTS, heads), jnp.float32),
        shape((2, pool_pages, 128, 128), jnp.bfloat16), shape((), jnp.int32),
        shape((_NOTE_SLOTS, table), jnp.int32),
        shape((_NOTE_SLOTS,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


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
    compiled = jax.jit(partial(kept, topk=2048)).lower(scores).compile()
    text = compiled.as_text()
    assert not _sorted_rows(text, width)
    assert "tpu_custom_call" not in text and " while(" in text
    assert (compiled.memory_analysis().temp_size_in_bytes
            <= 3 * 4 * rows * width + (1 << 20))
    sort = jax.jit(lambda x: jax.lax.top_k(x, 2048)).lower(scores).compile()
    assert len(_sorted_rows(sort.as_text(), width)) == 1


def _lower_note_program(device, program, dims):
    """One of the engine's two programs for ``serve-note-gen``'s plan
    (two full layers with an indexer, three sliding ones; 32 of 256
    experts held), over the row pools the plan states. ``dims``: decode
    (chunk, table pages); prefill (prompts, tokens, table pages)."""
    from ray_tpu.models import dots3_note
    from ray_tpu.ops.paged_attention import row_pool
    from ray_tpu.serve.engine_programs import _pool_slices

    one_chip = SingleDeviceSharding(device)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    cfg = dataclasses.replace(
        dots3_note.dots3_note_prev(), vocab_size=19008, n_experts_held=32,
        layer_types=("full_attention", "full_attention")
        + ("sliding_attention",) * 3)
    plan = dots3_note.layer_plan(cfg)
    params = jax.tree.map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(partial(dots3_note.init_params, cfg),
                       jax.random.key(0)))
    pools = [
        shape(pool.shape, pool.dtype)
        for rows in _pool_slices(plan)[0] for pool in (
            jax.eval_shape(partial(
                row_pool, sum(run.layers for run in plan
                              if run.rows == rows), _NOTE_PAGES, 128, row))
            for row in rows)]
    assert [p.shape[-1] for p in pools] == [640, 128, 1152]
    key = jax.eval_shape(lambda: jax.random.key(0))
    if program == "decode":
        chunk, pages = dims
        slots = _NOTE_SLOTS
        fn = partial(PagedLLMEngine._paged_decode_impl, cfg, chunk=chunk,
                     page_size=128, quantized=False)
        args = (shape((slots, pages), jnp.int32), shape((slots,), jnp.int32),
                shape((slots,), jnp.int32), shape((slots,), jnp.bool_),
                shape((slots,), jnp.float32), key)
    else:
        n, tokens, pages = dims
        fn = partial(PagedLLMEngine._paged_prefill_impl, cfg, page_size=128,
                     quantized=False)
        args = (shape((n, pages), jnp.int32), shape((n, tokens), jnp.int32),
                shape((n,), jnp.int32), shape((n,), jnp.int32),
                shape((n,), jnp.float32), key)
    return cfg, jax.jit(fn, donate_argnums=(1, 2, 3)).lower(
        params, *pools, *args)


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
    _, lowered = _lower_note_program(v5e_2x2[0], "decode",
                                     (16, _NOTE_TABLE))
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(_LATENT_KERNEL.findall(text)) == 2
    assert len(_INDEX_KERNEL.findall(text)) == 2
    # since PR 61 the 64 slots' scores are searched, not sorted
    assert not _sorted_rows(text, 8192)
    assert "bf16[131072,640]" not in text
    assert "bf16[4096,128,128]" not in text
    assert "bf16[320,128,1152]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.48e9
    assert not _EXPERT_KERNEL.search(text)


# a serving cell's decode program: pool pages, table pages, the engine's
# slots where they are not 32 (as the tests above compile them)
_OTHER_DECODE_PROGRAMS = {
    "d12": (_D12_PAGES, 16, {}), "olmoe-d10": (_MOE_PAGES, 8, {}),
    "laguna-ep4-d5": (2304, 32, {"slots": 64}),
    "falcon-h1-d4": (_H1_PAGES, 8, {"slots": _H1_SLOTS}),
    "smallthinker-d8": (_BRIEF_PAGES, 64, {})}


@pytest.mark.parametrize("family", _OTHER_DECODE_PROGRAMS)
def test_the_other_families_decode_programs_hold_no_index_kernel(v5e_2x2,
                                                                 family):
    """No other configuration states a layer with an indexer: the decode
    program of each holds neither the index kernel nor the latent one."""
    module, cfg = _serving_model(family)
    pages, table, slots = _OTHER_DECODE_PROGRAMS[family]
    text = _compile_engine_program(
        v5e_2x2[0], module, cfg, pages, "decode", (16, table),
        **slots).as_text()
    assert "index_decode_scores" not in text
    assert "latent_decode_attn" not in text


def test_note_d5_cold_prefill_runs_its_experts_in_the_grouped_kernel(
        v5e_2x2):
    """The cell's cold prompt (one of 4,096 tokens, the 32-page window):
    32,768 (token, choice) pairs of which an eighth fall on the 32 held
    experts; each of the plan's runs of sparse layers holds the grouped
    kernel twice and no ``ragged-dot``, no stack of the held experts
    (1.5 GB a layer) is moved to feed it, and the program fits beside
    its arguments."""
    from ray_tpu.ops.moe import expert_kernel_engages

    cfg, lowered = _lower_note_program(v5e_2x2[0], "prefill", (1, 4096, 32))
    compiled = lowered.compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert expert_kernel_engages(4096)
    kernels = len(_EXPERT_KERNEL.findall(text))
    assert kernels and kernels % 2 == 0
    assert "ragged-dot" not in text
    assert not _expert_stack_moves(text, 32, cfg.d_model, 1536)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes) < 15.75e9


# the latent prefill kernel's instruction, under its name: a layer's
# heads' outputs [rows, heads, queries, value width] its result
_LATENT_PREFILL_KERNEL = re.compile(
    r"%latent_prefill_attn[.\d]* = bf16\[1,(\d+),(\d+),128\]\S* "
    r"custom-call\(.*tpu_custom_call")
# the kernel's launches: heads, key width, queries, keys, window, flags
_LATENT_PREFILL_SHAPES = {
    "full-cold": (128, 192, 4096, 4096, None, True),
    "full-suffix-128": (128, 192, 128, 4096, None, True),
    "full-under-topk": (128, 192, 2048, 2048, None, False),
    "sliding-cold": (64, 256, 4096, 4096, 513, False)}


@pytest.mark.parametrize("case", _LATENT_PREFILL_SHAPES)
def test_latent_prefill_kernel_compiles_alone(v5e_2x2, case):
    """The kernel by itself at ``serve-note-gen``'s shapes: a full
    layer's cold prompt and a suffix behind a cached transcript (keys 192
    wide, one byte of flags a pair), a full layer over a table of no more
    than ``topk`` keys (no flags), a sliding layer's cold prompt (keys
    256 wide, the walk under its window): the chip's compiler takes the
    blocks, the int8 flags and the accumulators, in a few MiB of
    temporaries beside the operands."""
    from ray_tpu.ops.latent_attention import latent_prefill_attention_kernel

    heads, dk, t, keys, window, flags = _LATENT_PREFILL_SHAPES[case]
    one_chip = SingleDeviceSharding(v5e_2x2[0])

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(partial(latent_prefill_attention_kernel, scale=0.07,
                               window=window)).lower(
        shape((1, heads, t, dk)), shape((1, heads, keys, dk)),
        shape((1, heads, keys, 128)), shape((1,), jnp.int32),
        shape((1,), jnp.int32),
        *([shape((1, t, keys), jnp.int8)] if flags else [])).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert len(_LATENT_PREFILL_KERNEL.findall(text)) == 1
    # nothing of size heads x queries x keys beside it
    assert not re.search(rf"f32\[1,{heads},{t},{keys}\]", text)


def test_note_d5_cold_prefill_keeps_its_scores_on_the_core(v5e_2x2):
    """The cell's cold prompt (one of 4,096 tokens, the 32-page window):
    each of the plan's three runs of layers (the leading dense full
    layer, the full layer behind it, the three sliding ones) holds the
    latent prefill kernel under its name, the full layers' at 128 heads
    and the sliding layers' at 64, and the program holds no float32 array
    of heads x block x keys: the plain formulation wrote a full layer's
    ``f32[1,128,256,4096]`` sixteen times a layer and a sliding layer's
    ``f32[1,64,1024,1664]`` four times, and no decode kernel."""
    _, lowered = _lower_note_program(v5e_2x2[0], "prefill", (1, 4096, 32))
    compiled = lowered.compile()
    text = compiled.as_text()
    kernels = _LATENT_PREFILL_KERNEL.findall(text)
    assert sorted(kernels) == [("128", "4096"), ("128", "4096"),
                               ("64", "4096")]
    assert not _LATENT_KERNEL.search(text) and not _INDEX_KERNEL.search(text)
    # what is left of score shape is the indexer's, which stays in HBM in
    # float32: a block of 1,024 queries at 64 index heads over the keys
    # its group of blocks can see, where those are more than ``topk``
    scores = {dims for keys in (4096, 3072, 2048, 1024, 1664, 1537)
              for dims in _score_arrays(text, keys)}
    assert scores == {"1,64,1024,3072", "1,64,1024,4096"}
    assert compiled.memory_analysis().temp_size_in_bytes < 2.2e9


# sha256 (first 16 hex digits) of the text the cell's prefill programs
# UNDER the latent prefill kernel's rule lower to, under ``_PINNED_JAX``.
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
    import hashlib

    if jax.__version__ != _PINNED_JAX:
        pytest.skip(f"digests pinned under jax {_PINNED_JAX}")
    _, lowered = _lower_note_program(v5e_2x2[0], "prefill", dims)
    text = _located_nowhere(lowered.as_text())
    assert "latent_prefill_attn" not in text
    # no kernel at all, and no ``top_k`` but the routers'
    assert "tpu_custom_call" not in text
    assert "x4096xf32>" not in "".join(
        line for line in text.splitlines() if "chlo.top_k" in line)
    assert (hashlib.sha256(text.encode()).hexdigest()[:16]
            == _PARENT_NOTE_PREFILL[dims])


_EXPERT_WIDTHS = [
    # held experts, model width, expert width, gated, (token, choice) pairs
    (64, 2688, 1856, False, 6144),       # serve-reason-gen: 2 x 512 x 6
    (64, 3072, 1024, True, 20480),       # serve-code-gen: 2,048 x 10
    (32, 5120, 1536, True, 32768),       # serve-note-gen: 4,096 x 8
    (64, 2048, 1024, True, 8192)]        # serve-moe-gen: 2 x 512 x 8


@pytest.mark.parametrize(
    "experts,d,f,gated,pairs", _EXPERT_WIDTHS,
    ids=["nano-relu2", "code-swiglu", "note-swiglu", "moe-swiglu"])
def test_expert_kernel_compiles_alone(v5e_2x2, experts, d, f, gated, pairs):
    """The grouped expert kernel at the four widths the benchmark runs:
    Mosaic takes both calls (the whole contraction a block, the widest
    column block that fits, 64 MiB of the core's memory at most) and the
    stacks of a run of three layers go in whole, as they lie, with the
    layer's index. Nemotron's up stack is the one whose last
    axis is no whole number of lanes: the compiler keeps such a parameter
    as [.., experts, f, d] (``{2,3,1,0}``), and the kernel, which contracts its
    blocks over their last axis then, is handed it without a copy."""
    from ray_tpu.ops.grouped_expert_ffn import grouped_expert_ffn_kernel

    one_chip = SingleDeviceSharding(v5e_2x2[0])

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(grouped_expert_ffn_kernel).lower(
        shape((pairs, d)), shape((experts,), jnp.int32),
        shape((3, experts, d, f)) if gated else None,
        shape((3, experts, d, f)), shape((3, experts, f, d)),
        shape((), jnp.int32)).compile()
    text = compiled.as_text()
    assert len(_EXPERT_KERNEL.findall(text)) == 2
    assert not _expert_stack_moves(text, experts, d, f)
    layout = re.search(rf"bf16\[3,{experts},{d},{f}\]\{{([\d,]+):",
                       text.split("\n", 1)[0]).group(1)
    assert layout == ("3,2,1,0" if f % 128 == 0 else "2,3,1,0")
    # the sorted rows in, the hidden activations between the calls and
    # the float32 rows out, and no more
    assert compiled.memory_analysis().temp_size_in_bytes < pairs * (
        2 * f + 64) + (1 << 20)


# NVIDIA-Nemotron-3-Nano-30B-A3B cut to its first nine layers, MEMEM*EME,
# 64 of 128 experts held (``serve-reason-gen``): 128 slots, 2,304 KV pages
# of the ONE attention layer, recurrent state of the FOUR mixers
_NANO_SLOTS, _NANO_PAGES = 128, 2304
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


def _lower_planned_program(device, model, cfg, pages, slots, program, dims):
    """One of the engine's two programs for a plan of mixers and
    attention layers, its stores sized as the engine sizes them: K/V pools
    of the attention layers alone, state arrays of the recurrent ones."""
    from ray_tpu.serve.engine_programs import _pool_layers, _state_layers

    one = SingleDeviceSharding(device)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    plan = model.layer_plan(cfg)
    params = jax.tree.map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(partial(model.init_params, cfg), jax.random.key(0)))
    pool = shape((_pool_layers(plan, None), pages, 128, cfg.n_kv_heads,
                  cfg.head_dim), jnp.bfloat16)
    scale = shape((1, 1, 1, 1), jnp.float32)
    state = tuple(shape((_state_layers(plan), slots, *dims_), dtype)
                  for _, dims_, dtype in model.recurrent_state(cfg).arrays)
    key = jax.eval_shape(lambda: jax.random.key(0))
    if program == "decode":
        chunk, pages = dims
        fn = partial(PagedLLMEngine._paged_decode_impl, cfg, chunk=chunk,
                     page_size=128, quantized=False)
        args = (shape((slots, pages), jnp.int32), shape((slots,), jnp.int32),
                shape((slots,), jnp.int32), shape((slots,), jnp.bool_),
                shape((slots,), jnp.float32), key, *state)
    else:
        n, tokens, pages = dims
        fn = partial(PagedLLMEngine._paged_prefill_impl, cfg, page_size=128,
                     quantized=False)
        args = (shape((n, pages), jnp.int32), shape((n, tokens), jnp.int32),
                shape((n,), jnp.int32), shape((n,), jnp.int32),
                shape((n,), jnp.float32), key, *state,
                shape((n,), jnp.int32))
    return jax.jit(fn, donate_argnums=(1, 2, 3, 4, 11, 12)).lower(
        params, pool, pool, scale, scale, *args)


def _lower_nano_program(device, program, dims):
    """One of the engine's two programs for the d9 plan: K/V pools of one
    layer, state arrays of four."""
    from ray_tpu.models import nemotron_h
    from ray_tpu.serve.engine_programs import _pool_layers, _state_layers

    cfg = dataclasses.replace(
        nemotron_h.nemotron_3_nano_30b_a3b(), vocab_size=65536,
        n_experts_held=64, pattern="MEMEM*EME")
    plan = nemotron_h.layer_plan(cfg)
    assert (_pool_layers(plan, None), _state_layers(plan)) == (1, 4)
    return cfg, _lower_planned_program(device, nemotron_h, cfg, _NANO_PAGES,
                                       _NANO_SLOTS, program, dims)


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
    reads anyway and no others. What ``_stack_moves_in_loops`` fences in
    the older programs, a stack of SEVERAL layers parked on the core and
    moved whole round a kernel to read one layer of it, cannot happen to
    a stack of one; the test holds the moves to those fetches."""
    cfg, lowered = _lower_nano_program(v5e_2x2[0], program, dims)
    compiled = lowered.compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    pool_bytes = _NANO_PAGES * 128 * 2 * 128 * 2
    state_bytes = 4 * _NANO_SLOTS * (4 * 64 * 64 * 128 + 2 * 3 * 6144)
    assert 7.6e9 < mem.argument_size_in_bytes < 7.8e9
    assert mem.alias_size_in_bytes >= 2 * pool_bytes + state_bytes
    assert mem.temp_size_in_bytes < (0.65e9 if program == "prefill"
                                     else 0.5e9)
    assert not _pool_copy(1, _NANO_PAGES, 2).findall(text)
    assert bool(_DECODE_KERNEL.search(text)) == (program == "decode")
    # the grouped expert kernel by its rule: both prefill programs are
    # over the line (512 and 1,024 rows), a decode step's 128 rows under
    # it; and the stacks are read where they lie: the up stack [64, 2688,
    # 1856] lies on the chip as [64, 1856, 2688] and is handed over so
    assert len(_EXPERT_KERNEL.findall(text)) == (
        8 if program == "prefill" else 0)
    assert "ragged-dot" not in text
    assert not _expert_stack_moves(text, 64, cfg.d_model, 1856)
    assert len(_NANO_STATE_KERNEL.findall(text)) == (
        4 if program == "decode" else 0)
    widths = {(cfg.d_ssm, cfg.d_model): (cfg.d_ssm, (cfg.d_model,)),
              "in": (cfg.d_model, (10304, 4608, cfg.d_shared)),
              "down": (cfg.d_shared, (cfg.d_model,))}
    moves = [m for d_in, w in widths.values()
             for m in _stack_moves_in_loops(text, 1, d_in, w)]
    if program == "prefill":
        assert not moves
        return
    assert _in_loops(text, _NANO_STATE_KERNEL) == 4
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


# serve-assist-gen's: granite-4.0-h-small's first period, 36 of each
# layer's 72 experts, half the vocabulary; 64 slots, 1,280 K/V pages
_ASSIST_SLOTS, _ASSIST_PAGES = 64, 1280


@pytest.mark.parametrize("cell", ["serve-brief-gen", "serve-assist-gen"])
def test_cold_prefills_bring_the_pairs_rows_back_without_a_relayout(v5e_2x2,
                                                                    cell):
    """The two cells whose prefill spends most on the rows' way back
    (``[8192, 6, 2560]`` and ``[1024, 10, 4096]``; K = 6 pads to 8
    sublanes, K = 10 to 16): the compiled prefill gathers the grouped
    kernel's float32 rows with the choices on the major axis, so the
    split of the gathered ``[K*T, D]`` into K slabs is a bitcast and no
    float32 ``[T, K, D]`` array (a copy of every row into tiles of 8, a
    quarter to a third of the combine before PR 63) is written anywhere
    in the program, nor a ``[K, T, D]`` relayout in its place."""
    if cell == "serve-brief-gen":
        smallthinker, cfg = _serving_model("smallthinker-d8")
        tokens, text = 8192, _compile_engine_program(
            v5e_2x2[0], smallthinker, cfg, _BRIEF_PAGES, "prefill",
            (1, 8192, 64)).as_text()
    else:
        from ray_tpu.models import granite_moe_hybrid as granite

        cfg = dataclasses.replace(
            granite.granite_4_0_h_small(), layer_types=granite._PERIOD,
            n_experts_held=36, vocab_size=50176)
        tokens, text = 1024, _lower_planned_program(
            v5e_2x2[0], granite, cfg, _ASSIST_PAGES, _ASSIST_SLOTS,
            "prefill", (1, 1024, 8)).compile().as_text()
    k, d = cfg.top_k, cfg.d_model
    assert not _combine_relayouts(text, tokens, k, d)
    # the fence reads the right program: the gathered rows are there,
    # under the combine, and split by a bitcast
    rows = re.compile(rf"= f32\[{tokens * k},{d}\]\S* fusion\(.*moe_combine")
    slabs = re.compile(rf"= f32\[{k},{tokens},{d}\]\S* bitcast\(")
    assert rows.search(text) and slabs.search(text)


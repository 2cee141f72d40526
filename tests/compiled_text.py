"""Readers of a compiled (or lowered) program's text, for the tests that
compile the device programs for a described chip: the kernels'
instructions under their names, what moves a pool or a weight stack
whole, what lies in a program's loops. Nothing here compiles anything."""

import base64
import json
import re

# the jax the digests of lowered text are pinned under
PINNED_JAX = "0.9.0"

# the decode kernel's instruction, under its name (PR 30), and the prefill
# kernel's (PR 34)
DECODE_KERNEL = re.compile(
    r"%paged_decode_attn[.\d]* = \S+ custom-call\(.*tpu_custom_call")
PREFILL_KERNEL = re.compile(
    r"%paged_prefill_attn[.\d]* = \S+ custom-call\(.*tpu_custom_call")
# the grouped expert kernel's instruction (PR 44): two a layer of routed
# experts, rows x the up stacks and x the down stack
EXPERT_KERNEL = re.compile(
    r"%grouped_expert_ffn[.\d]* = \S+ custom-call\(.*tpu_custom_call")
# the latent prefill kernel's instruction, under its name: a layer's
# heads' outputs [rows, heads, queries, value width] its result
LATENT_PREFILL_KERNEL = re.compile(
    r"%latent_prefill_attn[.\d]* = bf16\[1,(\d+),(\d+),128\]\S* "
    r"custom-call\(.*tpu_custom_call")

_CALLED = re.compile(r"(?:calls|body|condition|to_apply)=(%[\w.\-]+)|"
                     r"(?:branch|called)_computations=\{([^}]*)\}")
MOVE = re.compile(r"^\s*(?:ROOT )?%\S+ = (.*?) (copy-start|copy-done|"
                  r"slice-start|slice-done|copy|custom-call)\(")


def pool_copy(layers, pages, nkv):
    """An operation that moves one layer's pool, or the stacked pool,
    whole (bf16 or int8 pages)."""
    return re.compile(
        rf"= (?:bf16|s8)\[(?:{layers},|1,)?{pages},128,{nkv},128\]\S* "
        r"(?:copy|dynamic-slice|dynamic-update-slice)\(")


def window(slots, pages, nkv):
    """An operation whose result is a page window of every slot (the
    gather decode attended over before PR 30, or a float32 copy of it)."""
    return re.compile(rf"= (?:bf16|f32|s8)\[(?:{slots * pages}|"
                      rf"{slots},{pages}),128,{nkv},128\]")


def dense_rows(text, layers, pages, rows):
    """The opcodes of the instructions whose result is a pool seen as
    dense rows ``[layers, pages, page * nkv, 128]`` (the decode kernel's
    view of it at fewer than 8 KV heads: a bitcast, or it is a copy)."""
    return re.findall(
        rf"= (?:bf16|s8)\[{layers},{pages},{rows},128\]\S* ([\w\-]+)\(", text)


def outside_fusions(text):
    """The lines of a compiled program outside any fusion's own
    computation: inside one a value of an array's shape is no array in
    memory (every-expert's matmul reads its layer through such a one)."""
    fused = False
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            fused = line.lstrip("%").startswith("fused_computation")
        elif not fused:
            yield line


def expert_stack_moves(text, experts, d, f):
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
    return [line.strip()[:160] for line in outside_fusions(text)
            if sliced.match(line) or (m := MOVE.match(line))
            and m.group(2) != "custom-call" and stack.search(m.group(1))]


def combine_relayouts(text, tokens, k, d):
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
    return [line.strip()[:160] for line in outside_fusions(text)
            if "moe_combine" in line
            and (minor.match(line) or major.match(line))]


def loop_bodies(text):
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


def in_loops(text, instruction) -> int:
    """How many instructions of the program's loops match."""
    return sum(bool(instruction.search(line)) for line in loop_bodies(text))


def stack_moves_in_loops(text, layers, d_in, widths):
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
    for line in loop_bodies(text):
        m = MOVE.match(line)
        if not m or (m.group(2) == "custom-call"
                     and "ConcatBitcast" not in line):
            continue
        if any(int(k) <= layers for k in stack.findall(m.group(1))):
            moves.append(line.strip()[:160])
    return moves


def projection_widths(cfg):
    """The output widths a stack of attention projection weights can
    have: q (and ``wo``'s input), k or v, and the three fused; at each
    number of query heads the model's layers have."""
    kv = cfg.n_kv_heads * cfg.head_dim
    heads = {cfg.n_heads, getattr(cfg, "n_heads_sliding", cfg.n_heads)}
    return sorted({w for h in heads for w in (
        h * cfg.head_dim, kv, (h * cfg.head_dim) + 2 * kv)})


def moved_shapes(text, cfg):
    return {re.search(r"bf16\[[\d,]+\]", line).group()
            for line in stack_moves_in_loops(
                text, cfg.n_layers, cfg.d_model, projection_widths(cfg))}


def score_arrays(text, keys):
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


def located_nowhere(lowered_text):
    """A lowered program's text with each Mosaic kernel's body, which is
    bytecode that carries the source's line numbers, replaced by its
    assembly without them: what a digest of the kernel can be taken of
    across an edit that moves its lines."""
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

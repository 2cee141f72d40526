"""Compile the Pallas kernels ALONE, at the shapes the serving cells
launch them with, for a TPU that is described, not attached
(``conftest.py:v5e_2x2``): Mosaic takes the tiles or refuses them here,
at no chip time; and the kernels the older cells hold lower to the text
they lowered to (digests under ``compiled_text.PINNED_JAX``)."""

import hashlib
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import compiled_text as hlo
from engine_lowering import NOTE_PAGES, NOTE_SLOTS


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
# ``hlo.PINNED_JAX``
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
    assert len(hlo.PREFILL_KERNEL.findall(compiled.as_text())) == 1


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
    if jax.__version__ != hlo.PINNED_JAX:
        pytest.skip(f"digests pinned under jax {hlo.PINNED_JAX}")
    text = hlo.located_nowhere(_lower_prefill_kernel(
        v5e_2x2[0], *_PREFILL_KERNEL_SHAPES[case]).as_text())
    assert ".py" not in text and "loc(" not in text
    assert (hashlib.sha256(text.encode()).hexdigest()[:16]
            == _PARENT_PREFILL_KERNEL[case])
    windowed = hlo.located_nowhere(_lower_prefill_kernel(
        v5e_2x2[0], *_PREFILL_KERNEL_SHAPES[case][:-1], 512).as_text())
    assert windowed != text         # the fence is not blind


@pytest.mark.parametrize("heads,width,size,groups", [
    (32, 128, 256, 2), (24, 64, 128, 1), (80, 64, 128, 8), (6, 8, 128, 2),
    (128, 64, 128, 1), (64, 64, 128, 8)],
    ids=["falcon-h1-34b", "24x64x128", "80x64x128", "six-tiny-heads",
         "granite-4.0-h-small", "nemotron-3-nano"])
def test_state_kernel_compiles_alone(v5e_2x2, heads, width, size, groups):
    """The kernel by itself at the published head shapes and at others
    its rule admits (heads in one block of 24, in five of 16, six heads
    that are no whole block of 8, 128 heads of ONE group in four blocks
    of 32, 64 heads whose blocks of 32 hold four groups each): the chip's
    compiler takes the tiles, and the stacked state is aliased from
    operand to result."""
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
    assert len(hlo.DECODE_KERNEL.findall(text)) == 1
    assert text.count("tpu_custom_call") == 1
    assert hlo.dense_rows(text, 3, 600, 128 * kv_heads) == ["bitcast"] * 2
    assert not hlo.pool_copy(3, 600, kv_heads).findall(text)
    # nothing but the int8 window's scale rows (1/32 of its bytes)
    assert compiled.memory_analysis().temp_size_in_bytes < (
        128 << 20 if dtype == jnp.int8 else 1 << 20)


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
    if jax.__version__ != hlo.PINNED_JAX:
        pytest.skip(f"digests pinned under jax {hlo.PINNED_JAX}")
    dims, digest = _PARENT_DECODE_KERNEL[case]
    text = hlo.located_nowhere(_lower_decode_kernel(v5e_2x2[0], *dims).as_text())
    assert ".py" not in text and "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


def test_decode_kernel_at_4_kv_heads_is_another(v5e_2x2):
    """The fence above is not blind: where a step takes two pages the
    same launch at the same shapes lowers to another text than a
    one-page step's would (dense operands, a step's buffer)."""
    text = hlo.located_nowhere(_lower_decode_kernel(
        v5e_2x2[0], 32, 28, 4, 64, None, jnp.bfloat16).as_text())
    assert "memref<3x600x512x128xbf16" in text
    assert "memref<2x1024x128xbf16" in text
    assert "memref<3x600x128x4x128xbf16" not in text.split("custom_call")[1]


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
        shape((NOTE_SLOTS, heads, lanes), jnp.bfloat16),
        shape((2, 600, 128, lanes), jnp.bfloat16), shape((), jnp.int32),
        shape((NOTE_SLOTS, table), jnp.int32),
        shape((NOTE_SLOTS,), jnp.int32),
        shape((NOTE_SLOTS, table * 128), jnp.bool_)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


@pytest.mark.parametrize("heads,table,pool_pages", [
    (64, 64, NOTE_PAGES), (64, 32, NOTE_PAGES), (4, 6, 600)],
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
        shape((NOTE_SLOTS, heads, 128), jnp.bfloat16),
        shape((NOTE_SLOTS, heads), jnp.float32),
        shape((2, pool_pages, 128, 128), jnp.bfloat16), shape((), jnp.int32),
        shape((NOTE_SLOTS, table), jnp.int32),
        shape((NOTE_SLOTS,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


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
    assert len(hlo.LATENT_PREFILL_KERNEL.findall(text)) == 1
    # nothing of size heads x queries x keys beside it
    assert not re.search(rf"f32\[1,{heads},{t},{keys}\]", text)


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
    assert len(hlo.EXPERT_KERNEL.findall(text)) == 2
    assert not hlo.expert_stack_moves(text, experts, d, f)
    layout = re.search(rf"bf16\[3,{experts},{d},{f}\]\{{([\d,]+):",
                       text.split("\n", 1)[0]).group(1)
    assert layout == ("3,2,1,0" if f % 128 == 0 else "2,3,1,0")
    # the sorted rows in, the hidden activations between the calls and
    # the float32 rows out, and no more
    assert compiled.memory_analysis().temp_size_in_bytes < pairs * (
        2 * f + 64) + (1 << 20)

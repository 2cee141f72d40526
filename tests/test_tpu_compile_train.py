"""Compile the trainer's steps and the flash kernels for a TPU that is
described, not attached (``conftest.py:v5e_2x2``). Code that asks
``jax.default_backend()`` sees the CPU under test, so the trainers are
steered to the flash kernel from here (``attn_impl``)."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import compiled_text as hlo
from engine_lowering import D12
from ray_tpu.models import llama
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.parallel.mesh import create_mesh
from ray_tpu.train.trainer import JaxTrainer, TrainConfig


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


# the four-chip training cell's step: Mistral-7B-v0.3 widths cut to 10
# layers, 16 x 2048 tokens over {"fsdp": 4}, the fused loss in 32 chunks of
# 1,024 rows against the vocabulary's 32,768
_D10_TRAIN = dict(D12, n_layers=10, remat="dots_attn")
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
    assert hlo.in_loops(text, re.compile(r"= f32\[1024,8192\]\S* fusion\("))
    assert not hlo.in_loops(text, re.compile(r"= f32\[1024,32768\]"))
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
    in_loops = _collectives(hlo.loop_bodies(text))
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
    blocks = _logit_blocks(_collectives(hlo.loop_bodies(text)))
    assert blocks == [("all-reduce", [("f32", (1024, 32768))])] * 2

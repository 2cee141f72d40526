"""The Llama block's fused q | k | v projection (``models/llama.py``:
``fuse_attention_projections``, ``attention_projections``), which the
paged engine's decode program uses, and the fence round it: every
program that does not take the fused stack lowers to the text it
lowered to before the stack existed.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from engine_lowering import lower
from ray_tpu.models import llama, olmoe
from ray_tpu.ops.paged_prefill_attention import kernel_engages
from ray_tpu.ops.rope import rope_sin_cos
from ray_tpu.parallel.mesh import create_mesh
from ray_tpu.serve.paged_llm import PagedLLMEngine
from ray_tpu.train.trainer import JaxTrainer, TrainConfig


@pytest.mark.parametrize("rows,tokens", [(8, 1), (2, 48)],
                         ids=["decode-rows", "prefill-block"])
@pytest.mark.parametrize("heads,kv_heads,head_dim", [
    (4, 2, 32), (32, 8, 16), (4, 4, 32)], ids=["gqa4x2", "gqa32x8", "mha"])
def test_fused_projection_is_the_three_projections(heads, kv_heads,
                                                   head_dim, rows, tokens):
    """One matmul against the stack ``wqkv`` and a split give q, k and v
    bit for bit: a column of the product is the dot product of the same
    row and the same column of weights, accumulated in float32 and
    rounded to bf16 once, whichever matrix the column stands in. On the
    CPU the two also add in the same order, so the test holds them
    EQUAL; were a backend to block the contraction by the output's
    width, the bound would be one bf16 rounding of the float32 sum."""
    cfg = llama.LlamaConfig(vocab_size=64, d_model=128, n_layers=3,
                            n_heads=heads, n_kv_heads=kv_heads,
                            head_dim=head_dim, d_ff=256, remat="none")
    blocks = llama.init_params(cfg, jax.random.key(1))["blocks"]
    fused = llama.fuse_attention_projections(blocks)
    assert not {"wq", "wk", "wv"} & set(fused)
    assert fused["wqkv"].shape == (3, 128, (heads + 2 * kv_heads) * head_dim)
    assert set(blocks) >= {"wq", "wk", "wv"} and "wqkv" not in blocks
    x = jax.random.normal(jax.random.key(2), (rows, tokens, 128),
                          jnp.float32).astype(cfg.param_dtype)
    positions = jnp.arange(tokens, dtype=jnp.int32)[None] + 5
    sin, cos = rope_sin_cos(positions, head_dim, theta=cfg.rope_theta)
    for layer in range(cfg.n_layers):
        want = llama.attention_projections(
            cfg, jax.tree.map(lambda a: a[layer], blocks), x, sin, cos)
        got = llama.attention_projections(
            cfg, jax.tree.map(lambda a: a[layer], fused), x, sin, cos)
        for w, g in zip(want, got):
            assert w.shape == g.shape and w.dtype == g.dtype
            np.testing.assert_array_equal(np.asarray(w, np.float32),
                                          np.asarray(g, np.float32))


def test_decode_program_projects_from_one_stack_and_params_stay():
    """The Llama decode program concatenates the three stacks ONCE, at
    its entry (the only concatenate of weight shape in its text, outside
    both loops) and multiplies by the fused stack; the engine's
    ``params`` stay the caller's, in the published layout (the benchmark
    hands them to its plain reference)."""
    cfg = llama.llama_tiny()
    text = lower(jax.devices("cpu")[0], llama, cfg, "decode", (4, 4),
                 num_pages=16, slots=4, page=8).as_text()
    width = (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim
    stack = f"tensor<{cfg.n_layers}x{cfg.d_model}x{width}xbf16>"
    built = [line for line in text.splitlines()
             if "stablehlo.concatenate" in line and stack in line]
    assert len(built) == 1
    entry = text[:text.index("stablehlo.while")]
    assert built[0] in entry
    params = llama.init_params(cfg, jax.random.key(0))
    eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=2, max_len=64,
                         page_size=16)
    req = eng.submit(list(range(1, 20)), max_new_tokens=6)
    eng.start()
    assert len(list(req.tokens())) == 6
    eng.stop()
    assert eng.params is params
    assert set(params["blocks"]) >= {"wq", "wk", "wv"}
    assert "wqkv" not in params["blocks"]


def _train_step_text(strategy, fused_loss):
    cfg = llama.llama_tiny()
    trainer = JaxTrainer(
        cfg, TrainConfig(mesh_axes={strategy: 1}, strategy=strategy,
                         fused_loss=fused_loss),
        mesh=create_mesh({strategy: 1}, devices=jax.devices("cpu")[:1]))
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        trainer.abstract_state(), trainer.state_shardings())
    tokens = jax.ShapeDtypeStruct((2, 17), jnp.int32)
    tokens = jax.ShapeDtypeStruct(
        tokens.shape, tokens.dtype,
        sharding=trainer._batch_shardings(tokens))
    return jax.jit(trainer._step, donate_argnums=(0,)).lower(
        state, tokens).as_text()


def _engine_text(model, cfg, program, kv_dtype, dims=None):
    dims = dims or ((4, 4) if program == "decode" else (2, 16, 4))
    return lower(jax.devices("cpu")[0], model, cfg, program, dims,
                 num_pages=16, slots=4, page=8, kv_dtype=kv_dtype).as_text()


# sha256 (first 16 hex digits) of the text each program lowered to on the
# commit before the fused stack (69996b5: PR 31), computed by this file's
# own helpers laid over that tree, under the jax named below
_PARENT_TEXT = {
    "llama-prefill-bf16": "d40201eb20b49fc9",
    "llama-prefill-int8": "e69c2aee930c4a0e",
    "olmoe-decode-bf16": "f55d9fbbf2b5322d",
    "olmoe-decode-int8": "15255678dcc5a3be",
    "olmoe-prefill-bf16": "ca06970971f316b8",
    "olmoe-prefill-int8": "720e704b9750243f",
    "train-step-dp": "5bc6b96c516ad051",
    "train-step-fsdp-fused-loss": "0ffa58ba7ba50818",
    "llama-decode-bf16": "ddb52a82698fd267",
}
_PINNED_JAX = "0.9.0"

_UNCHANGED = {
    "olmoe-decode-bf16": lambda: _engine_text(
        olmoe, olmoe.olmoe_tiny(), "decode", "bf16"),
    "olmoe-decode-int8": lambda: _engine_text(
        olmoe, olmoe.olmoe_tiny(), "decode", "int8"),
    "olmoe-prefill-bf16": lambda: _engine_text(
        olmoe, olmoe.olmoe_tiny(), "prefill", "bf16"),
    "olmoe-prefill-int8": lambda: _engine_text(
        olmoe, olmoe.olmoe_tiny(), "prefill", "int8"),
    "llama-prefill-bf16": lambda: _engine_text(
        llama, llama.llama_tiny(), "prefill", "bf16"),
    "llama-prefill-int8": lambda: _engine_text(
        llama, llama.llama_tiny(), "prefill", "int8"),
    "train-step-dp": lambda: _train_step_text("dp", False),
    "train-step-fsdp-fused-loss": lambda: _train_step_text("fsdp", True),
}


def _digest(text):
    if jax.__version__ != _PINNED_JAX:
        pytest.skip(f"digests pinned under jax {_PINNED_JAX}: another "
                    "version words the same program differently")
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("program", sorted(_UNCHANGED))
def test_programs_without_the_fused_stack_lower_to_the_parents_text(program):
    """OLMoE's two engine programs (its module states no fused stack),
    the Llama prefill program and the train step (both hand
    ``attention_projections`` ``wq`` / ``wk`` / ``wv``) are the programs
    they were: the same lowered text, byte for byte. A change that is
    MEANT to alter one of them pins its new digest here, computed on
    its own tree."""
    assert _digest(_UNCHANGED[program]()) == _PARENT_TEXT[program]


def test_the_llama_decode_program_is_the_one_that_changed():
    """The fence above is not blind: the one program that takes the
    fused stack does lower to another text than the parent's."""
    text = _engine_text(llama, llama.llama_tiny(), "decode", "bf16")
    assert _digest(text) != _PARENT_TEXT["llama-decode-bf16"]


# The same digests of the engine's programs on the commit before the layer
# plan (9544057: PR 32), where the two programs scanned ONE stack: the
# Llama decode program with its fused stack, and for both models the
# widest prefill the serving cells warm (two cold prompts of 2048 tokens
# over 16 pages), which is where ``SCORES_MAX_BYTES`` (the plain prefill
# attention's, ``ops/paged_prefill_attention.py`` since PR 34) draws its
# line: at 32 heads it still goes whole. (PR 44 re-pinned the last: at
# 4,096 rows OLMoE's experts run over the rows sorted by expert, which
# were XLA's ``ragged_dot`` (afa0c5c62c6296a9) and are the grouped kernel
# or, lowered for the CPU as here, the plain loop over the experts; the
# narrow OLMoE programs above, 8 and 32 rows, are under the line and
# stand. PR 63 re-pinned it again, 3fbcf5a045086096 until then: the sorted
# rows come back to their tokens with the choices on the major axis.)
_BEFORE_THE_LAYER_PLAN = {
    "llama-decode-bf16": "fdd46263ed8b0999",
    "llama-decode-int8": "1cf7fdecece96f9a",
    "llama-prefill-wide": "7e741f0e9ff873fa",
    "olmoe-prefill-wide": "3c8946089040cab4",
}
_ONE_RUN = {
    "llama-decode-bf16": lambda: _engine_text(
        llama, llama.llama_tiny(), "decode", "bf16"),
    "llama-decode-int8": lambda: _engine_text(
        llama, llama.llama_tiny(), "decode", "int8"),
    "llama-prefill-wide": lambda: _engine_text(
        llama, llama.llama_tiny(), "prefill", "bf16", (2, 2048, 16)),
    "olmoe-prefill-wide": lambda: _engine_text(
        olmoe, olmoe.olmoe_tiny(), "prefill", "bf16", (2, 2048, 16)),
}


@pytest.mark.parametrize("program", sorted(_ONE_RUN))
def test_a_one_run_layer_plan_lowers_to_the_one_scan_it_was(program):
    """A model that repeats one block states a plan of one run
    (``llama.layer_plan``), and the engine's programs for it, which now
    follow the plan, ask the module for its rotary tables and hand the
    sublayer's end to ``attention_output``, are the programs they were:
    with the fence above, every engine program of ``LlamaConfig`` and
    ``OlmoeConfig``, byte for byte."""
    assert _digest(_ONE_RUN[program]()) == _BEFORE_THE_LAYER_PLAN[program]


# PR 34 put a Pallas kernel into the prefill program's full-attention
# layers, by a rule on the traced shapes, and re-pinned NO digest above:
# every prefill program pinned here is under the rule (the tiny configs'
# head size of 32 is no whole lane, their scores are 8 MiB at most, and
# the int8 programs' pools are not bf16), so each calls the plain path
# directly, before any ``platform_dependent``, and lowers to the parent's
# text although the attention moved to ``ops/paged_prefill_attention.py``
# and takes the rows' valid lengths. The decode programs and the train
# step import none of it. What a program OVER the rule lowers to is held by
# ``tests/test_engine_tracing_kernels.py`` (the kernel under its name where
# the program is lowered for the TPU, nothing of it for the CPU) and
# ``tests/test_tpu_compile_dense_moe.py`` (compiled at the cells' widths).
@pytest.mark.parametrize("model,kv_dtype,dims", [
    (llama, "bf16", (2, 16, 4)), (llama, "int8", (2, 16, 4)),
    (olmoe, "bf16", (2, 16, 4)), (olmoe, "int8", (2, 16, 4)),
    (llama, "bf16", (2, 2048, 16)), (olmoe, "bf16", (2, 2048, 16))],
    ids=["llama-prefill-bf16", "llama-prefill-int8", "olmoe-prefill-bf16",
         "olmoe-prefill-int8", "llama-prefill-wide", "olmoe-prefill-wide"])
def test_every_pinned_prefill_program_is_under_the_kernels_rule(model,
                                                                kv_dtype,
                                                                dims):
    cfg = llama.llama_tiny() if model is llama else olmoe.olmoe_tiny()
    n, tokens, pages = dims
    pool = jax.ShapeDtypeStruct(
        (cfg.n_layers, 16, 8, cfg.n_kv_heads, cfg.head_dim),
        jnp.int8 if kv_dtype == "int8" else jnp.bfloat16)
    assert not kernel_engages((n, tokens, cfg.n_heads, cfg.head_dim), pool,
                              pages, None)

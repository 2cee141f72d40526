"""The paged engine's two programs as the tests lower, compile and call
them: through ``serve/engine_programs.py``'s own statement of a plan's
stores (``store_shapes``) and of a program's body and call
(``bound_program``, ``_Order``), from shapes alone where nothing runs. A
test states the model, the program and its dimensions; what the stores
are and where an argument stands is the engine's to say.

``dims``: decode (chunk, window pages); prefill (prompts, tokens, window
pages)."""

import dataclasses
from functools import cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from ray_tpu.serve import engine_programs, paged_llm


def _input_shapes(program, dims, slots):
    """The host's inputs to a program, by the names its order states."""
    i32 = jnp.int32
    if program == "decode":
        _, pages = dims
        return {"table": ((slots, pages), i32), "tokens": ((slots,), i32),
                "lengths": ((slots,), i32), "active": ((slots,), jnp.bool_),
                "temps": ((slots,), jnp.float32)}
    n, tokens, pages = dims
    return {"table_rows": ((n, pages), i32), "tokens": ((n, tokens), i32),
            "slens": ((n,), i32), "starts": ((n,), i32),
            "temps": ((n,), jnp.float32), "slots": ((n,), i32)}


def traced(device, module, cfg, program, dims, *, num_pages, slots=32,
           page=128, kv_dtype="bf16"):
    """One of the engine's two programs for ``cfg`` (whose block
    ``module`` states), traced from shapes alone: the stores ``cfg``'s
    plan states at ``slots`` slots and ``num_pages`` pages of ``page``
    tokens, bf16 or int8 pages, on ``device`` (None: placed nowhere, for
    a caller that lowers for a platform by its name)."""
    one = None if device is None else SingleDeviceSharding(device)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def placed(tree):
        return jax.tree.map(lambda a: shape(a.shape, a.dtype), tree)

    params = placed(jax.eval_shape(partial(module.init_params, cfg),
                                   jax.random.key(0)))
    pools, state, kept = placed(engine_programs.store_shapes(
        cfg, max_batch=slots, num_pages=num_pages, page_size=page,
        kv_dtype=kv_dtype))
    body, order = engine_programs.bound_program(
        cfg, program, page_size=page, kv_dtype=kv_dtype,
        **({"chunk": dims[0]} if program == "decode" else {}))
    state = order.carried(state, kept)
    inputs = {name: shape(*said) for name, said
              in _input_shapes(program, dims, slots).items()}
    inputs["key"] = jax.eval_shape(lambda: jax.random.key(0))
    return jax.jit(body, donate_argnums=order.donated(
        len(pools), len(state))).trace(
            *order.arguments(params, pools, inputs, state))


def lower(device, module, cfg, program, dims, **engine):
    """That program lowered for ``device``."""
    return traced(device, module, cfg, program, dims, **engine).lower()


@cache        # a program is compiled once a process, whoever reads its text
def compiled(device, module, cfg, program, dims, **engine):
    return lower(device, module, cfg, program, dims, **engine).compile()


def run(cfg, program, params, pools, inputs: dict, state=(), *, page,
        kv_dtype="bf16", **static):
    """One call of a program's body as the engine binds it, ``inputs`` by
    the names its order states: (the pools, {name: result}, the state)."""
    body, order = engine_programs.bound_program(
        cfg, program, page_size=page, kv_dtype=kv_dtype, **static)
    return order.split(
        body(*order.arguments(params, pools, inputs, state)), len(pools))


def programs_logits(monkeypatch, cfg, params, prompts, new, *, page, slots,
                    n_slots=3, chunk=4):
    """The logits the engine's two programs compute for ``prompts`` (ONE
    prefill group, over a plan of K/V twins and a recurrent state) and
    ``new`` greedy tokens behind each: the prefill program (the prompts
    padded to their bucket, each row's state installed in its slot of
    ``slots``), then the decode program in chunks, the other slots
    inactive. Read where the programs hand them to ``select_tokens``.
    Returns ([row][step] logits, [row] tokens, the last decode call's
    statistics)."""
    seen = []

    def spy(logits, temps, key):
        jax.debug.callback(lambda lg: seen.append(np.asarray(lg)), logits,
                           ordered=True)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    monkeypatch.setattr(engine_programs, "select_tokens", spy)
    n, lens = len(prompts), [len(p) for p in prompts]
    max_pages = -(-(max(lens) + new + chunk) // page) + 1
    (k, v, k_scale, v_scale), state, _ = engine_programs.store_shapes(
        cfg, max_batch=n_slots, num_pages=n_slots * max_pages, page_size=page,
        kv_dtype="bf16")
    pools = [jnp.zeros(k.shape, k.dtype), jnp.zeros(v.shape, v.dtype),
             jnp.ones(k_scale.shape, k_scale.dtype),
             jnp.ones(v_scale.shape, v_scale.dtype)]
    # a predecessor's garbage in every slot: the prefill must overwrite it
    state = [jnp.full(a.shape, 7.0, a.dtype) for a in state]
    table = np.full((n_slots, max_pages), -1, np.int32)
    for slot in slots:
        table[slot] = np.arange(max_pages) + slot * max_pages
    padded = np.zeros((n, paged_llm._bucket(max(lens))), np.int32)
    for row, prompt in enumerate(prompts):
        padded[row, :len(prompt)] = prompt
    key = jax.random.key(0)
    at = jnp.array(slots, jnp.int32)
    pools, out, state = run(
        cfg, "prefill", params, pools, dict(
            table_rows=jnp.asarray(table[list(slots)]),
            tokens=jnp.asarray(padded), slens=jnp.array(lens, jnp.int32),
            starts=jnp.zeros((n,), jnp.int32),
            temps=jnp.zeros((n,), jnp.float32), key=key, slots=at),
        state, page=page)
    first = out["firsts"]
    tokens = [[int(t)] for t in first]
    last = jnp.zeros((n_slots,), jnp.int32).at[at].set(first)
    lengths = jnp.zeros((n_slots,), jnp.int32).at[at].set(
        jnp.array(lens, jnp.int32))
    active = jnp.zeros((n_slots,), bool).at[at].set(True)
    idle = [i for i in range(n_slots) if i not in slots]
    others = [np.asarray(a)[:, idle] for a in state]
    stats = {}
    while len(tokens[0]) < new:
        pools, out, state = run(
            cfg, "decode", params, pools, dict(
                table=jnp.asarray(table), tokens=last, lengths=lengths,
                active=active, temps=jnp.zeros((n_slots,), jnp.float32),
                key=key),
            state, page=page, chunk=chunk)
        lengths, last, stats = out["lengths"], out["last"], out["stats"]
        for row, slot in enumerate(slots):
            tokens[row] += [int(t) for t in np.asarray(out["toks"])[:, slot]]
    jax.effects_barrier()
    # an inactive slot's state is left as it was, bit for bit
    for before, a in zip(others, state):
        np.testing.assert_array_equal(before, np.asarray(a)[:, idle])
    rows = [np.stack([seen[0][row]] + [lg[slot] for lg in seen[1:]])[:new]
            for row, slot in enumerate(slots)]
    return rows, [t[:new] for t in tokens], stats


# -- the serving cells' engines, at the published widths ---------------------

# Mistral-7B-v0.3 widths cut to 12 layers (``serve-doc``, ``serve-chat``):
# 32 slots x 2048 tokens, 544 KV pages of 128 tokens (142.6 MB a layer's K)
D12 = dict(vocab_size=32768, d_model=4096, n_layers=12, n_heads=32,
           n_kv_heads=8, head_dim=128, d_ff=14336, rope_theta=1e6,
           tie_embeddings=False)
D12_PAGES = 544
# OLMoE-1B-7B widths cut to 10 layers (``serve-moe-gen``, PR 28): 32 slots
# x 1024 tokens, 352 KV pages of 128 tokens (16 KV heads)
MOE_PAGES, MOE_LAYERS = 352, 10
# Laguna-S-2.1's leading layer and one period (``serve-code-gen``): 64 slots
LAGUNA_PAGES, LAGUNA_SLOTS = 2304, 64
# Falcon-H1-34B-Instruct cut to 4 blocks (``serve-instruct-gen``): 128
# slots, 1280 KV pages, and each slot's recurrent state beside them
H1_LAYERS, H1_SLOTS, H1_PAGES = 4, 128, 1280
# SmallThinker-21BA3B-Instruct cut to its first eight layers
# (``serve-brief-gen``): 32 slots, tables of 64 pages
BRIEF_PAGES = 2304
# dots3-note-prev cut to its first five layers (``serve-note-gen``): 64
# slots, 2,816 pages of latent rows in whole lanes, tables of 64 pages
NOTE_SLOTS, NOTE_PAGES, NOTE_TABLE = 64, 2816, 64
# NVIDIA-Nemotron-3-Nano-30B-A3B cut to its first nine layers, MEMEM*EME,
# 64 of 128 experts held (``serve-reason-gen``): 128 slots, 2,304 KV pages
# of the ONE attention layer, recurrent state of the FOUR mixers
NANO_SLOTS, NANO_PAGES = 128, 2304
# granite-4.0-h-small's first period, 36 of each layer's 72 experts, half
# the vocabulary (``serve-assist-gen``): 64 slots, 1,280 K/V pages
ASSIST_SLOTS, ASSIST_PAGES = 64, 1280
# LFM2-8B-A1B cut to its first fourteen layers, c c | A c c c x 3
# (``serve-extract-gen``): 64 slots, 3,072 K/V pages of the THREE attention
# layers (two 64-wide KV heads a row) that each keep, beside their rows, the
# ELEVEN convolutions' tails at their end; tables of 36 pages
EXTRACT_SLOTS, EXTRACT_PAGES, EXTRACT_TABLE = 64, 3072, 36


def serving_model(name):
    """A serve cell's block module and configuration, by its name."""
    from ray_tpu.models import llama, olmoe

    if name == "d12":
        return llama, llama.LlamaConfig(**D12)
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
            falcon_h1.falcon_h1_34b_instruct(), n_layers=H1_LAYERS)
    if name == "smallthinker-d8":
        from ray_tpu.models import smallthinker

        # serve-brief-gen's: full, three sliding, twice
        period = (0, 1, 1, 1)
        return smallthinker, dataclasses.replace(
            smallthinker.smallthinker_21b_a3b(),
            sliding_window_layout=period * 2, rope_layout=period * 2)
    if name == "dots3-note-d5":
        from ray_tpu.models import dots3_note

        # serve-note-gen's: two full layers with an indexer, three sliding
        # ones; 32 of 256 experts held
        return dots3_note, dataclasses.replace(
            dots3_note.dots3_note_prev(), vocab_size=19008,
            n_experts_held=32,
            layer_types=("full_attention", "full_attention")
            + ("sliding_attention",) * 3)
    if name == "nemotron-d9":
        from ray_tpu.models import nemotron_h

        return nemotron_h, dataclasses.replace(
            nemotron_h.nemotron_3_nano_30b_a3b(), vocab_size=65536,
            n_experts_held=64, pattern="MEMEM*EME")
    if name == "granite-d10":
        from ray_tpu.models import granite_moe_hybrid as granite

        return granite, dataclasses.replace(
            granite.granite_4_0_h_small(), layer_types=granite._PERIOD,
            n_experts_held=36, vocab_size=50176)
    if name == "lfm2-d14":
        from ray_tpu.models import lfm2_moe

        return lfm2_moe, dataclasses.replace(
            lfm2_moe.lfm2_8b_a1b(),
            layer_types=lfm2_moe.lfm2_8b_a1b().layer_types[:14])
    return olmoe, dataclasses.replace(olmoe.olmoe_1b_7b(),
                                      n_layers=MOE_LAYERS)

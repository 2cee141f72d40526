"""Granite 4.0-H through the program (PR 62): a stack whose every layer is
a Mamba-2 mixer or attention with no rotary embedding AND THEN routed
SwiGLU experts beside a shared one, four fixed multipliers and a head
tied to the embedding. At a tiny size on the CPU: the program's
``forward`` against the family's plain reference on seeded weights
(logits, so ``logits_scaling`` too); a group of TWO prompts that span
several scan chunks prefilled and then decoded through the engine's pages
and slots against the reference's one forward pass, with the experts'
statistics of the runs that also keep state; the plan's three runs and
the stores they state, at the tiny size and at the cell's; the
expert-parallel share (the two chips' parts, the shared expert counted
once, add up to the uncut layer); the attention's scale, folded into q
before its one rounding; the engine end to end with its counters."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference
from benchmark.families import granite_moe_hybrid as family
from engine_lowering import programs_logits
from ray_tpu.models import granite_moe_hybrid
from ray_tpu.ops.paged_attention import page_attention_scale
from ray_tpu.serve import engine_programs
from ray_tpu.serve.paged_llm import PagedLLMEngine
from ray_tpu.util import tracing

# the published keys at a tiny size: two mixers, attention, a mixer; query
# groups of 4, one mixer group, 8 of 16 experts held, 4 a token; every
# multiplier another number than one, the attention's not head_dim ** -0.5
CONFIG = {
    "model_type": "granitemoehybrid", "vocab_size": 128, "hidden_size": 64,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_hidden_layers": 4, "num_attention_heads": 8,
    "num_key_value_heads": 2, "mamba_n_heads": 8, "mamba_d_head": 8,
    "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
    "mamba_chunk_size": 256, "mamba_expand": 2, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "intermediate_size": 24,
    "shared_intermediate_size": 48, "num_local_experts": 16,
    "num_experts_per_tok": 4,
    "expert_share": {"chips": 2, "index": 0, "num_experts_held": 8},
    "embedding_multiplier": 3.0, "attention_multiplier": 0.125,
    "residual_multiplier": 0.4, "logits_scaling": 2.0,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "hidden_act": "silu",
    "normalization_function": "rmsnorm", "position_embedding_type": "nope",
    "attention_bias": False, "tie_word_embeddings": True,
    "torch_dtype": "float32"}
# float32 against float32 over four layers: the program's sums run in
# another order than the reference's (a chunked scan, fused matmuls, the
# experts as one batched matmul)
LOGIT_TOL = 2e-4
# through the engine's programs the keys and values lie in bf16 pages
# whatever the model's dtype (the pool's format): the one attention
# layer's output carries their rounding, and ``wo`` stands at four times
# the branches' scale; a state installed at another layer's place,
# advanced for an inactive slot or padded wrongly misses by 0.3 and more
PAGED_TOL = 4e-2
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_config(**changes):
    """The program's config of ``CONFIG`` with a scan chunk of 8 (the
    chunk is the program's own: no published key sets it)."""
    return dataclasses.replace(family.model_config(CONFIG),
                               **dict({"ssm_chunk": 8}, **changes))


def make_params(cfg, seed=3):
    """Seeded weights, made in one jitted call (eagerly, op by op, the
    tiny stack's hundred small arrays take seconds)."""
    return jax.jit(granite_moe_hybrid.init_params, static_argnums=0)(
        cfg, jax.random.key(seed))


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    return cfg, make_params(cfg)


def test_the_config_and_the_layer_plan(tiny):
    cfg, params = tiny
    assert cfg == granite_moe_hybrid.granite_moe_hybrid_tiny()
    assert cfg.n_layers == 4 and cfg.conv_dim == 64 + 2 * 16
    plan = granite_moe_hybrid.layer_plan(cfg)
    assert [(run.key, run.layers, run.state is not None, run.attends,
             run.feeds) for run in plan] == [
        ("layers0-1", 2, True, False, True), ("layers2", 1, False, True, True),
        ("layers3", 1, True, False, True)]
    assert set(params["blocks"]) == {run.key for run in plan}
    feeds = {"ffn_norm", "router", "wi_gate", "wi_up", "wo_e", "ws_gate",
             "ws_up", "ws_down"}
    assert set(params["blocks"]["layers2"]) == feeds | {"norm", "wqkv", "wo"}
    assert set(params["blocks"]["layers0-1"]) == feeds | {
        "norm", "in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
        "ssm_norm", "out_proj"}
    assert params["blocks"]["layers0-1"]["wi_gate"].shape == (2, 8, 64, 24)
    assert "lm_head" not in params               # the head is the embedding
    assert granite_moe_hybrid.rotary_tables(
        cfg, jnp.zeros((1, 3), jnp.int32)) == {"full": ()}
    published = granite_moe_hybrid.granite_4_0_h_small()
    kinds = published.layer_types
    assert (kinds.count("mamba"), kinds.count("attention"),
            published.n_layers) == (36, 4, 40)
    assert published.conv_dim == 8448
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [
        5, 15, 25, 35]
    with pytest.raises(ValueError, match="not among"):
        granite_moe_hybrid.granite_moe_hybrid_tiny(first_expert=12)
    with pytest.raises(ValueError, match="mamba or attention"):
        granite_moe_hybrid.granite_moe_hybrid_tiny(
            layer_types=("mamba", "mlp"))


def test_the_cells_plan_is_three_runs_nine_states_one_pool():
    """The configuration of ``serve-assist-gen``, from its file, without a
    weight: five mixer layers, the attention layer, four mixer layers,
    every run feeding; the state arrays span NINE layers and the K/V pool
    ONE; a run's layers are counted in the store it keeps."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-small-ep2-d10.json")) as f:
        cfg = family.model_config(json.load(f))
    plan = granite_moe_hybrid.layer_plan(cfg)
    assert [(run.key, run.layers, run.attends, run.feeds) for run in plan] \
        == [("layers0-4", 5, False, True), ("layers5", 1, True, True),
            ("layers6-9", 4, False, True)]
    assert engine_programs._state_layers(plan) == 9
    assert engine_programs._pool_layers(plan, None) == 1
    assert engine_programs._places(plan) == [(None, 0), (0, None), (None, 5)]
    state, tail = granite_moe_hybrid.recurrent_state(cfg).arrays
    assert state[1:] == ((128, 64, 128), "float32")
    assert tail[1:] == ((3, 8448), "bfloat16")
    assert (cfg.n_experts, cfg.n_experts_held, cfg.first_expert,
            cfg.top_k) == (72, 36, 0, 10)
    # a decode step's state update is the kernel's at this head shape
    from ray_tpu.ops import ssm
    assert ssm.state_kernel_engages(jax.ShapeDtypeStruct(
        (9, 64, 128, 64, 128), jnp.float32))
    assert ssm._block_heads(128, 4 * 64 * 128) == 32


def test_forward_is_the_plain_reference(tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.key(1), (2, 21), 1,
                                cfg.vocab_size)
    got = granite_moe_hybrid.forward(cfg, params, tokens)
    want = family.logits(CONFIG, params, tokens)
    assert got.shape == want.shape == (2, 21, cfg.vocab_size)
    assert float(jnp.std(want)) > 0.5           # logits that are spread
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=LOGIT_TOL, atol=LOGIT_TOL)
    # ``logits_scaling`` divides them, which no greedy comparison sees:
    # held here, on the logits themselves
    halved = dataclasses.replace(cfg, logits_scaling=4.0)
    x = jax.random.normal(jax.random.key(4), (3, cfg.d_model))
    np.testing.assert_allclose(
        np.asarray(granite_moe_hybrid.head_logits(halved, params, x)),
        np.asarray(granite_moe_hybrid.head_logits(cfg, params, x)) / 2.0,
        rtol=1e-6)


# -- the share ----------------------------------------------------------------

def test_the_two_chips_parts_add_up_to_the_uncut_layer(tiny):
    """The guide's share test on one layer's second sublayer: chip 0
    holds experts 0-7, chip 1 experts 8-15, both route over all 16 and
    compute the shared expert; their routed parts and the shared expert
    ONCE, times the residual multiplier, are the uncut reference's."""
    cfg, _ = tiny
    d, f, fs = cfg.d_model, cfg.d_expert, cfg.d_shared
    ks = jax.random.split(jax.random.key(6), 7)

    def dense(key, *shape):
        return jax.random.normal(key, shape) * shape[-2] ** -0.5

    # one layer's second sublayer with ALL sixteen experts, drawn here
    p = {"ffn_norm": jnp.ones((d,)), "router": dense(ks[0], d, 16),
         "wi_gate": dense(ks[1], 16, d, f), "wi_up": dense(ks[2], 16, d, f),
         "wo_e": dense(ks[3], 16, f, d), "ws_gate": dense(ks[4], d, fs),
         "ws_up": dense(ks[5], d, fs), "ws_down": dense(ks[6], fs, d)}
    x = jax.random.normal(jax.random.key(5), (2, 11, cfg.d_model))
    kw = dict(eps=1e-5, top_k=4, gating="softmax_topk", experts="swiglu")
    m = cfg.residual_multiplier
    want = x + m * family._experts(x, p, first=0, **kw)
    assert family._share(CONFIG) == (16, 8, 0)
    assert family._share(dict(CONFIG, expert_share=None)) == (16, 16, 0)
    h = granite_moe_hybrid.rms_norm(x, p["ffn_norm"], eps=cfg.rms_eps)
    shared = m * ((jax.nn.silu(h @ p["ws_gate"]) * (h @ p["ws_up"]))
                  @ p["ws_down"])
    parts, loads = [], []
    for chip in (0, 1):
        share = dataclasses.replace(cfg, first_expert=8 * chip)
        held = dict(p, **{k: p[k][8 * chip:8 * chip + 8]
                          for k in ("wi_gate", "wi_up", "wo_e")})
        out, stats = granite_moe_hybrid.feed_forward(share, held, x)
        parts.append(out - x - shared)
        loads.append(float(stats["routed_here_share"]))
    assert float(jnp.max(jnp.abs(parts[0]))) > 0.01
    np.testing.assert_allclose(
        np.asarray(x + parts[0] + parts[1] + shared), np.asarray(want),
        rtol=LOGIT_TOL, atol=LOGIT_TOL)
    assert sum(loads) == pytest.approx(1.0)     # every choice on one chip


# -- the attention's scale ----------------------------------------------------

def test_the_scale_is_the_multiplier_and_q_is_rounded_once():
    """At the published head size in bf16: the scores the engine's
    attention computes (``page_attention_scale``, 128 ** -0.5, on the q it
    is handed) are the unfolded products times ``attention_multiplier``,
    1/128; and the q it is handed is the float32 product times the ratio
    rounded to bf16 ONCE (a q rounded, scaled and rounded again differs
    in a third of its entries)."""
    published = granite_moe_hybrid.granite_4_0_h_small()
    assert published.attention_multiplier == 1 / 128
    assert published.head_dim == 128 != 1 / published.attention_multiplier ** 2
    cfg = granite_moe_hybrid.granite_moe_hybrid_tiny(
        d_model=256, n_heads=2, n_kv_heads=1, head_dim=128,
        attention_multiplier=1 / 128, dtype="bfloat16")
    fold = cfg.attention_multiplier / page_attention_scale(cfg.head_dim)
    assert fold == pytest.approx(128 ** -0.5)
    # q and k columns at 3.36 times the fan-in scale, as ``init_params``
    # draws them: scores of unit variance under 1/128
    wqkv = (jax.random.normal(jax.random.key(1), (256, 512)) * 256 ** -0.5
            * jnp.where(jnp.arange(512) < 384, 128 ** 0.25, 1.0))
    p = {"norm": jnp.ones((256,), jnp.bfloat16),
         "wqkv": wqkv.astype(jnp.bfloat16)}
    x = jax.random.normal(jax.random.key(0), (1, 9, 256)).astype(jnp.bfloat16)
    q, k, v = granite_moe_hybrid.attention_projections(cfg, p, x)
    assert q.dtype == k.dtype == jnp.bfloat16 and q.shape == (1, 9, 2, 128)
    u = granite_moe_hybrid.rms_norm(x, p["norm"], eps=cfg.rms_eps)
    products = jnp.einsum("bsd,dk->bsk", u, p["wqkv"],
                          preferred_element_type=jnp.float32)[..., :256]
    once = (products * fold).astype(jnp.bfloat16).reshape(q.shape)
    twice = (products.astype(jnp.bfloat16).astype(jnp.float32) * fold
             ).astype(jnp.bfloat16).reshape(q.shape)
    np.testing.assert_array_equal(np.asarray(q, np.float32),
                                  np.asarray(once, np.float32))
    assert float(jnp.mean(once != twice)) > 0.1
    # the scores: q k^T / 128 of the unfolded float32 q, to bf16's rounding
    scores = jnp.einsum("bqhd,bkgd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * page_attention_scale(128)
    want = jnp.einsum("bqhd,bkgd->bhqk", products.reshape(1, 9, 2, 128),
                      k.astype(jnp.float32)) / 128
    assert float(jnp.std(want)) > 0.5
    np.testing.assert_allclose(np.asarray(scores), np.asarray(want),
                               atol=0.03)


# -- the plan's stores --------------------------------------------------------

def test_pools_and_state_have_the_layers_that_keep_them(tiny):
    cfg, params = tiny
    eng = PagedLLMEngine(cfg, params, max_batch=3, max_len=64, page_size=8,
                         num_pages=20)
    # one attention layer of four keeps pages, three mixers keep state
    assert [p.shape for p in eng._programs.pools[:2]] == [
        (1, 20, 8, 2, 8)] * 2
    assert [a.shape for a in eng._programs.state] == [(3, 3, 8, 8, 16),
                                                      (3, 3, 3, 96)]
    plan = granite_moe_hybrid.layer_plan(cfg)
    assert engine_programs._places(plan) == [(None, 0), (0, None), (None, 2)]
    runs = engine_programs._plan_runs(plan, params["blocks"])
    assert [[int(i) for i in idx] for _, idx in runs] == [[0, 1], [0], [2]]
    # every run routes: the prefill hands each its own stacks
    assert all(engine_programs._routes(run, stacks)
               for run, (stacks, _) in zip(plan, runs))
    holds = eng._programs.holds()
    assert holds["page_layers"] == "k+v=1" and holds["state_layers"] == 3
    assert holds["page_bytes"] == 2 * 8 * 2 * 8 * 2
    assert holds["state_slot_bytes"] == 3 * (4 * 8 * 8 * 16 + 4 * 3 * 96)
    assert eng.stats()["prefix_cache"]["enabled"] is False
    with pytest.raises(ValueError, match="recurrent run"):
        PagedLLMEngine(cfg, params, prefix_cache=True)


# -- the engine's two programs against the reference's one forward pass ------

@pytest.fixture(scope="module")
def programs_run(tiny):
    """Two prompts of 21 and 13 tokens, one prefill group in the 32
    bucket (four scan chunks of 8, the shorter row padded from its second
    chunk on), nine tokens each; pages of 8."""
    cfg, params = tiny
    rng = np.random.default_rng(21)
    prompts = [rng.integers(1, cfg.vocab_size, n) for n in (21, 13)]
    with pytest.MonkeyPatch.context() as patch:
        rows, tokens, stats = programs_logits(patch, cfg, params, prompts, 9,
                                              page=8, slots=(2, 0))
    return prompts, rows, tokens, stats


@pytest.mark.parametrize("row", [0, 1], ids=["len21", "len13-padded"])
def test_prefill_then_decode_is_the_references_forward_pass(
        tiny, programs_run, row):
    """The prefill program's logits and eight decode steps' are the rows
    of the reference's ONE forward pass over the prompt and the tokens
    the programs chose, for each row of the group."""
    cfg, params = tiny
    prompts, rows, tokens, _ = programs_run
    prompt, got = prompts[row], rows[row]
    seq = np.concatenate([prompt, tokens[row][:-1]])[None]
    want = np.asarray(family.logits(CONFIG, params, seq))[0, len(prompt) - 1:]
    assert got.shape == want.shape == (9, cfg.vocab_size)
    np.testing.assert_allclose(got, want, rtol=PAGED_TOL, atol=PAGED_TOL)
    gap, _ = reference.token_gap(family.logits, CONFIG, params, prompt,
                                 tokens[row])
    assert gap <= PAGED_TOL


def test_the_runs_that_keep_state_report_their_experts(programs_run):
    """The decode program's statistics are means over ALL FOUR layers:
    the three that keep state route too, and report beside the one that
    attends."""
    *_, stats = programs_run
    assert set(stats) == {"experts_touched", "expert_load_max_over_mean",
                          "routed_here_share"}
    # two live tokens a step, four choices of sixteen experts, eight held
    assert 0.0 < float(stats["routed_here_share"]) <= 1.0
    assert 0.0 < float(stats["experts_touched"]) <= 8.0


# -- the engine end to end ----------------------------------------------------

def test_the_engine_serves_it_with_its_counters_and_spans(tiny):
    """Through the engine's own loop: two requests of one bucket, greedy
    tokens the reference's own (float32 weights), the construction span
    and the counters stating what the plan's layers hold, the chunk's
    span carrying the feed-forward's means."""
    cfg, params = tiny
    was = tracing.is_enabled()
    tracing.enable_tracing()
    try:
        eng = PagedLLMEngine(cfg, params, max_batch=2, max_len=64,
                             page_size=8, decode_chunk=4)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, cfg.vocab_size, n) for n in (19, 27)]
        reqs = [eng.submit(p, max_new_tokens=9) for p in prompts]
        eng.start()
        answers = [list(r.tokens()) for r in reqs]
        eng.stop()
    finally:
        if not was:
            tracing.disable_tracing()
    assert eng.error is None
    for prompt, tokens in zip(prompts, answers):
        assert len(tokens) == 9
        gap, _ = reference.token_gap(family.logits, CONFIG, params, prompt,
                                     tokens)
        assert gap <= PAGED_TOL
    stats = eng.stats()
    assert stats["state_installs"] == 2 and stats["state_layers"] == 3
    assert stats["page_layers"] == "k+v=1"
    assert stats["decode_delivered"] == 2 * 8
    built = [s for s in tracing.recorded_spans("engine.construct")
             if s["attrs"]["state_slot_bytes"] == stats["state_slot_bytes"]]
    assert built and built[-1]["attrs"]["page_layers"] == "k+v=1"
    assert built[-1]["attrs"]["state_layers"] == 3
    # (the recorder keeps the spans of every engine this process ran: a
    # family that holds all its experts reports no ``routed_here_share``)
    chunks = [s["attrs"] for s in tracing.recorded_spans("engine.emit")
              if s["attrs"].get("what") == "chunk"
              and "routed_here_share" in s["attrs"]]
    assert chunks and all(0.0 <= c["routed_here_share"] <= 1.0
                          for c in chunks)

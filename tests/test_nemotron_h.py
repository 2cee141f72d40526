"""Nemotron-H through the program (PR 43): a stack whose every layer is ONE
thing (a Mamba-2 mixer, routed squared-ReLU experts or attention with no
rotary embedding), so that the engine's stores have the layers that keep
them and no more. At a tiny size on the CPU: the program's ``forward``
against the family's plain reference on seeded weights; prefill and then
decode through the engine's pages and slots against the reference's one
forward pass; the expert-parallel share (the two chips' parts, the shared
expert counted once, add up to the uncut layer); the experts of two
matrices in both formulations of ``moe_ffn_dropless`` against a loop over
tokens; the state kernel at the published head shape; the plan's stores,
for this plan and unchanged for the five older ones; the engine end to
end with its counters and spans."""

import dataclasses
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference
from benchmark.families import nemotron_h as family
from engine_lowering import programs_logits
from ray_tpu.models import (dots3_note, falcon_h1, laguna, llama,
                            nemotron_h, olmoe)
from ray_tpu.ops import moe, ssm
from ray_tpu.serve import engine_programs
from ray_tpu.serve.engine_programs import _model_module
from ray_tpu.serve.paged_llm import PagedLLMEngine
from ray_tpu.util import tracing

# the published keys at a tiny size: the pattern's first nine letters,
# query groups of 4, four mixer groups of two heads, 4 of 8 experts held
CONFIG = {
    "model_type": "nemotron_h", "vocab_size": 128, "hidden_size": 64,
    "hybrid_override_pattern": "MEMEM*EME", "num_hidden_layers": 9,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "ssm_state_size": 16,
    "n_groups": 4, "conv_kernel": 4, "chunk_size": 8,
    "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 48,
    "n_routed_experts": 4, "n_shared_experts": 1, "num_experts_per_tok": 3,
    "expert_share": {"chips": 2, "index": 0, "num_experts_total": 8},
    "norm_topk_prob": True, "routed_scaling_factor": 2.5, "n_group": 1,
    "topk_group": 1, "layer_norm_epsilon": 1e-5, "rope_theta": 10000,
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
    "use_conv_bias": True, "use_bias": False, "mamba_proj_bias": False,
    "mlp_bias": False, "attention_bias": False, "sliding_window": None,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "tie_word_embeddings": False, "torch_dtype": "float32"}
DEPARTURES = {
    "rotary": {"rotary": "rope"}, "scores": {"scores": "softmax"},
    "bias": {"bias": "none"}, "experts": {"experts": "swiglu"},
    "scale": {"scale": 1}, "gate_norm": {"gate_norm": "before"},
    "groups": {"groups": 1}, "conv_bias": {"conv_bias": False}}
# float32 against float32 over nine layers: the program's sums run in
# another order than the reference's (a chunked scan, fused matmuls, the
# experts as one batched matmul)
LOGIT_TOL = 2e-4
# through the engine's programs the keys and values lie in bf16 pages
# whatever the model's dtype (the pool's format): the one attention
# layer's output carries their rounding, and ``wo`` stands at four times
# the fan-in scale, so the logits read 0.01-0.02 off at worst on unit
# logits here; a state installed at another layer's place, advanced for an
# inactive slot or padded wrongly misses by 0.3 and more
PAGED_TOL = 4e-2


def make_params(cfg, seed=3):
    return nemotron_h.init_params(cfg, jax.random.key(seed))


@pytest.fixture(scope="module")
def tiny():
    cfg = family.model_config(CONFIG)
    return cfg, make_params(cfg)


def test_the_config_and_the_layer_plan(tiny):
    cfg, params = tiny
    assert cfg == nemotron_h.nemotron_h_tiny()
    assert cfg.n_layers == 9 and cfg.conv_dim == 64 + 2 * 4 * 16
    plan = nemotron_h.layer_plan(cfg)
    assert [run.key for run in plan] == [f"layers{i}" for i in range(9)]
    assert all(run.layers == 1 for run in plan)
    what = [(run.state is not None, run.attends, run.feeds) for run in plan]
    by_letter = {"M": (True, False, False), "*": (False, True, False),
                 "E": (False, False, True)}
    assert what == [by_letter[c] for c in cfg.pattern]
    assert set(params["blocks"]) == {run.key for run in plan}
    assert set(params["blocks"]["layers1"]) == {
        "norm", "router", "router_bias", "wi_up", "wo_e", "ws_up", "ws_down"}
    assert params["blocks"]["layers1"]["wi_up"].shape == (1, 4, 64, 24)
    assert set(params["blocks"]["layers5"]) == {"norm", "wqkv", "wo"}
    assert nemotron_h.rotary_tables(cfg, jnp.zeros((1, 3), jnp.int32)) == {
        "full": ()}
    # consecutive layers of one letter are one run, stacked
    double = dataclasses.replace(cfg, pattern="MMEE*")
    assert [(r.key, r.layers) for r in nemotron_h.layer_plan(double)] == [
        ("layers0-1", 2), ("layers2-3", 2), ("layers4", 1)]
    published = nemotron_h.nemotron_3_nano_30b_a3b()
    letters = published.pattern
    assert (letters.count("M"), letters.count("E"), letters.count("*"),
            published.n_layers) == (23, 23, 6, 52)
    assert published.conv_dim == 6144
    with pytest.raises(ValueError, match="not among"):
        nemotron_h.nemotron_h_tiny(first_expert=6)
    with pytest.raises(ValueError, match="M, E or"):
        nemotron_h.nemotron_h_tiny(pattern="ME-")


def test_forward_is_the_plain_reference(tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.key(1), (2, 21), 1,
                                cfg.vocab_size)
    got = nemotron_h.forward(cfg, params, tokens)
    want = family.logits(CONFIG, params, tokens)
    assert got.shape == want.shape == (2, 21, cfg.vocab_size)
    assert float(jnp.std(want)) > 0.5           # logits that are spread
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=LOGIT_TOL, atol=LOGIT_TOL)
    # two runs of two stacked layers each give the same numbers as four
    # runs of one: the stacks are the layers, in order
    double = dataclasses.replace(cfg, pattern="MMEE*")
    config = dict(CONFIG, hybrid_override_pattern="MMEE*",
                  num_hidden_layers=5)
    p2 = make_params(double)
    np.testing.assert_allclose(
        np.asarray(nemotron_h.forward(double, p2, tokens)),
        np.asarray(family.logits(config, p2, tokens)),
        rtol=LOGIT_TOL, atol=LOGIT_TOL)


@pytest.mark.parametrize("name", sorted(DEPARTURES))
def test_each_departure_moves_the_logits(tiny, name):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.key(2), (1, 40), 1,
                                cfg.vocab_size)
    want = family.logits(CONFIG, params, tokens)
    other = family.logits(CONFIG, params, tokens, **DEPARTURES[name])
    assert float(jnp.max(jnp.abs(other - want))) > 0.1


# -- the share ----------------------------------------------------------------

def test_the_two_chips_parts_add_up_to_the_uncut_layer(tiny):
    """The guide's share test on one ``E`` layer: chip 0 holds experts
    0-3, chip 1 experts 4-7, both route over all 8 and compute the shared
    expert; their routed parts and the shared expert ONCE are the uncut
    reference's layer."""
    cfg, _ = tiny
    whole = dataclasses.replace(cfg, n_experts_held=8)
    p = jax.tree.map(lambda a: a[0], make_params(whole)["blocks"]["layers1"])
    x = jax.random.normal(jax.random.key(5), (2, 11, cfg.d_model))
    uncut = dict(CONFIG, n_routed_experts=8, expert_share=None)
    want = x + family._experts(
        x, p, eps=1e-5, top_k=3, norm_topk_prob=True, scale=2.5,
        scores="sigmoid", bias="correction", experts="relu2", first=0)
    assert family._share(uncut) == (8, 0)
    h = nemotron_h.rms_norm(x, p["norm"], eps=cfg.rms_eps)
    shared = jnp.square(jax.nn.relu(h @ p["ws_up"])) @ p["ws_down"]
    parts, loads = [], []
    for chip in (0, 1):
        share = dataclasses.replace(cfg, first_expert=4 * chip)
        held = dict(p, wi_up=p["wi_up"][4 * chip:4 * chip + 4],
                    wo_e=p["wo_e"][4 * chip:4 * chip + 4])
        out, stats = nemotron_h.feed_forward(share, held, x)
        parts.append(out - x - shared)
        loads.append(float(stats["routed_here_share"]))
        # the reference given the same share computes the same part
        ref = family._experts(
            x, held, eps=1e-5, top_k=3, norm_topk_prob=True, scale=2.5,
            scores="sigmoid", bias="correction", experts="relu2",
            first=4 * chip)
        np.testing.assert_allclose(np.asarray(out - x), np.asarray(ref),
                                   rtol=LOGIT_TOL, atol=LOGIT_TOL)
    assert float(jnp.max(jnp.abs(parts[0]))) > 0.01
    np.testing.assert_allclose(
        np.asarray(x + parts[0] + parts[1] + shared), np.asarray(want),
        rtol=LOGIT_TOL, atol=LOGIT_TOL)
    assert sum(loads) == pytest.approx(1.0)     # every choice on one chip


# -- experts of two matrices --------------------------------------------------

@pytest.mark.parametrize(
    "expert_formulation", ["every-held-expert", "sorted-loop",
                           "sorted-kernel"], indirect=True)
@pytest.mark.parametrize("held,first", [(8, 0), (4, 4)],
                         ids=["whole", "share"])
def test_relu2_experts_against_a_loop_over_tokens(expert_formulation, held,
                                                  first):
    t, d, f, e, k = 13, 32, 20, 8, 3
    ks = jax.random.split(jax.random.key(7), 5)
    x = jax.random.normal(ks[0], (t, d))
    router = jax.random.normal(ks[1], (d, e))
    up = jax.random.normal(ks[2], (e, d, f)) * d ** -0.5
    down = jax.random.normal(ks[3], (e, f, d)) * f ** -0.5
    bias = 0.3 * jax.random.normal(ks[4], (e,))
    valid = jnp.arange(t) != 5
    got, load = moe.moe_ffn_dropless(
        x, router, None, up[first:first + held], down[first:first + held],
        top_k=k, norm_topk_prob=True, routed_scale=2.5, first_expert=first,
        valid=valid, scoring="sigmoid", choice_bias=bias, form="relu2")
    want, counts = np.zeros((t, d)), np.zeros(held, int)
    xs, rw, u, dn, b = (np.asarray(a, np.float64)
                        for a in (x, router, up, down, bias))
    for i in range(t):
        if i == 5:
            continue
        s = 1.0 / (1.0 + np.exp(-(xs[i] @ rw)))
        chosen = np.argsort(-(s + b), kind="stable")[:k]
        total = s[chosen].sum()
        for j in chosen:
            if first <= j < first + held:
                act = np.maximum(xs[i] @ u[j], 0.0) ** 2
                want[i] += 2.5 * s[j] / total * (act @ dn[j])
                counts[j - first] += 1
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(load), counts)


def test_the_experts_form_is_stated_not_guessed():
    x, w = jnp.ones((2, 4)), jnp.ones((3, 4, 5))
    kw = dict(top_k=1)
    with pytest.raises(ValueError, match="form must be"):
        moe.moe_ffn_dropless(x, jnp.ones((4, 3)), None, w,
                             jnp.ones((3, 5, 4)), **kw)
    with pytest.raises(ValueError, match="form must be"):
        moe.moe_ffn_dropless(x, jnp.ones((4, 3)), w, w, jnp.ones((3, 5, 4)),
                             form="relu2", **kw)
    with pytest.raises(ValueError, match="form must be"):
        moe.moe_ffn_dropless(x, jnp.ones((4, 3)), w, w, jnp.ones((3, 5, 4)),
                             form="gelu", **kw)


# -- the state kernel at the published head shape ----------------------------

def test_state_kernel_at_64_heads_of_64_in_8_groups():
    """[layers, slots, 64, 64, 128] float32: the rule engages, a grid
    step takes 32 heads, which span four of the eight groups; the kernel
    (interpret mode) is the plain formulation, an inactive slot's state
    and every other layer's bit for bit as they were."""
    heads, width, size, groups, slots = 64, 64, 128, 8, 3
    states = jax.random.normal(jax.random.key(0),
                               (2, slots, heads, width, size), jnp.float32)
    assert ssm.state_kernel_engages(states)
    assert ssm._block_heads(heads, 4 * width * size) == 32
    ks = jax.random.split(jax.random.key(1), 5)
    x = jax.random.normal(ks[0], (slots, heads, width))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (slots, heads)))
    a = -jnp.exp(jax.random.normal(ks[2], (heads,)))
    b = jax.random.normal(ks[3], (slots, groups, size))
    c = jax.random.normal(ks[4], (slots, groups, size))
    active = jnp.array([True, False, True])
    want_y, want = ssm.ssm_state_step_reference(x, dt, a, b, c, states, 1,
                                                active)
    got_y, got = ssm.ssm_state_step_kernel(x, dt, a, b, c, states, 1, active,
                                           interpret=True)
    np.testing.assert_allclose(np.asarray(got_y)[[0, 2]],
                               np.asarray(want_y)[[0, 2]],
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(states[0]))
    np.testing.assert_array_equal(np.asarray(got[1, 1]),
                                  np.asarray(states[1, 1]))
    assert not np.array_equal(np.asarray(got[1, 0]), np.asarray(states[1, 0]))
    # a head reads ITS group's rows: with b and c of another group's the
    # result differs
    swapped, _ = ssm.ssm_state_step_kernel(
        x, dt, a, b[:, ::-1], c[:, ::-1], states, 1, active, interpret=True)
    assert not np.allclose(np.asarray(swapped)[0], np.asarray(want_y)[0])


# -- the plan's stores --------------------------------------------------------

def _engine(model, cfg, **kw):
    kw = dict(dict(max_batch=3, max_len=64, page_size=8), **kw)
    return PagedLLMEngine(cfg, model.init_params(cfg, jax.random.key(0)),
                          **kw)


def test_pools_and_state_have_the_layers_that_keep_them(tiny):
    cfg, params = tiny
    eng = PagedLLMEngine(cfg, params, max_batch=3, max_len=64, page_size=8,
                         num_pages=20)
    # one attention layer of nine keeps pages, four mixers keep state
    assert [p.shape for p in eng._programs.pools[:2]] == [
        (1, 20, 8, 2, 16)] * 2
    assert [a.shape for a in eng._programs.state] == [(4, 3, 8, 8, 16),
                                             (4, 3, 3, 192)]
    plan = nemotron_h.layer_plan(cfg)
    assert engine_programs._places(plan) == [
        (None, 0), (None, None), (None, 1), (None, None), (None, 2),
        (0, None), (None, None), (None, 3), (None, None)]
    runs = engine_programs._plan_runs(plan, params["blocks"])
    assert [int(idx[0]) for _, idx in runs] == [0, 0, 1, 0, 2, 0, 0, 3, 0]
    stats = eng.stats()
    assert stats["page_layers"] == "k+v=1" and stats["state_layers"] == 4
    assert stats["page_bytes"] == 2 * 8 * 2 * 16 * 2
    assert stats["state_slot_bytes"] == 4 * (4 * 8 * 8 * 16 + 4 * 3 * 192)
    assert stats["kv_pages_bytes"] == 20 * stats["page_bytes"]
    assert stats["prefix_cache"]["enabled"] is False
    with pytest.raises(ValueError, match="recurrent run"):
        PagedLLMEngine(cfg, params, prefix_cache=True)
    # a plan of no pages at all is refused, by what the engine lacks
    bare = dataclasses.replace(cfg, pattern="MEME")
    with pytest.raises(ValueError, match="no run of the layer plan attends"):
        PagedLLMEngine(bare, make_params(bare))


@pytest.mark.parametrize("model,make", [
    (llama, llama.llama_tiny), (olmoe, olmoe.olmoe_tiny),
    (laguna, laguna.laguna_tiny), (falcon_h1, falcon_h1.falcon_h1_tiny),
    (dots3_note, dots3_note.dots3_note_tiny)],
    ids=["llama", "olmoe", "laguna", "falcon_h1", "dots3_note"])
def test_the_older_plans_stores_are_what_they_were(model, make):
    """Every layer of the five older plans attends and feeds: a pool of
    each format spans the layers of that format (all of them, for a plan
    of one format), the state arrays every layer of a recurrent plan, and
    a run's places in its pools and in the state coincide."""
    cfg = make()
    plan = model.layer_plan(cfg)
    assert all(run.attends and run.feeds for run in plan)
    eng = _engine(model, cfg)
    formats = engine_programs._pool_slices(plan)[0]
    for rows, where in formats.items():
        layers = sum(run.layers for run in plan if run.rows == rows)
        assert all(p.shape[0] == layers for p in eng._programs.pools[where])
    assert sum(engine_programs._pool_layers(plan, rows)
               for rows in formats) == cfg.n_layers
    recurrent = any(run.state is not None for run in plan)
    assert [a.shape[0] for a in eng._programs.state] == (
        [cfg.n_layers] * 2 if recurrent else [])
    for run, (pool_at, state_at) in zip(plan, engine_programs._places(plan)):
        assert pool_at is not None
        assert state_at == (pool_at if run.state is not None else None)
    assert eng.stats()["state_layers"] == (cfg.n_layers if recurrent else 0)


def test_a_module_is_asked_only_for_the_pieces_its_plan_uses(tiny):
    cfg, _ = tiny
    assert _model_module(cfg) is nemotron_h

    def module(name, pattern, leave_out):
        mod = types.ModuleType(name)
        ep = engine_programs
        for piece in (ep._PIECES + ep._ATTENTION_PIECES + ep._KV_PIECES
                      + ep._RECURRENT_PIECES + ep._FEED_PIECES):
            if piece not in leave_out:
                setattr(mod, piece, getattr(nemotron_h, piece))
        mod.Config = type("Config", (nemotron_h.NemotronHConfig,),
                          {"__module__": name})
        sys.modules[name] = mod
        return mod.Config(**dict(vars(cfg), pattern=pattern))

    try:
        # no run feeds, no run holds a mixer: neither piece is asked for
        plain = module("attention_alone", "**",
                       ("feed_forward", "recurrent_mixer", "recurrent_step"))
        assert _model_module(plain) is sys.modules["attention_alone"]
        # a run that feeds needs the feed-forward
        with pytest.raises(TypeError, match="states no feed_forward"):
            _model_module(module("no_ffn", "*E", ("feed_forward",)))
        with pytest.raises(TypeError,
                           match="recurrent_mixer, recurrent_step"):
            _model_module(module("no_mixer", "M*", ("recurrent_mixer",
                                                    "recurrent_step")))
    finally:
        for name in ("attention_alone", "no_ffn", "no_mixer"):
            sys.modules.pop(name, None)


# -- the engine's two programs against the reference's one forward pass ------

def _programs_logits(monkeypatch, cfg, params, prompt, new, *, page,
                     chunk=4):
    """The logits the engine's two programs compute for ``prompt`` and
    ``new`` greedy tokens behind it (``engine_lowering.programs_logits``:
    its state installed in slot 1 of three, the others inactive): the
    pools have the one attention layer's pages, the state arrays the four
    mixers'. With the last decode call's statistics."""
    rows, tokens, stats = programs_logits(
        monkeypatch, cfg, params, [prompt], new, page=page, slots=(1,),
        chunk=chunk)
    return rows[0], tokens[0], stats


@pytest.mark.parametrize("plen,chunk_len,page", [
    (1, 128, 128), (127, 128, 128), (129, 128, 128), (21, 8, 8),
    (40, 8, 16)],
    ids=["len1", "len127", "len129", "padded-bucket", "chunk-under-page"])
def test_prefill_then_decode_is_the_references_forward_pass(
        monkeypatch, plen, chunk_len, page):
    """Prompt lengths round the scan's chunk and the page, one token, and
    a bucket with padding over several short chunks: the prefill
    program's logits and eight decode steps' are the rows of the
    reference's ONE forward pass over the prompt and the tokens the
    programs chose. The decode program's statistics are means over the
    four layers that report them (five of nine report none)."""
    config = dict(CONFIG, chunk_size=chunk_len)
    cfg = family.model_config(config)
    params = make_params(cfg)
    prompt = np.random.default_rng(plen).integers(1, cfg.vocab_size, plen)
    new = 9
    got, tokens, stats = _programs_logits(monkeypatch, cfg, params, prompt,
                                          new, page=page)
    seq = np.concatenate([prompt, tokens[:-1]])[None]
    want = np.asarray(family.logits(config, params, seq))[0, plen - 1:]
    assert got.shape == want.shape == (new, cfg.vocab_size)
    np.testing.assert_allclose(got, want, rtol=PAGED_TOL, atol=PAGED_TOL)
    gap, _ = reference.token_gap(family.logits, config, params, prompt,
                                 tokens)
    assert gap <= PAGED_TOL
    assert set(stats) == {"experts_touched", "expert_load_max_over_mean",
                          "routed_here_share"}
    # one live token a step, three choices of eight experts, four held
    assert 0.0 <= float(stats["routed_here_share"]) <= 1.0
    assert float(stats["experts_touched"]) <= 3.0


def test_prefill_then_decode_through_the_state_kernel(monkeypatch):
    """The same check with the decode program's state update in the
    KERNEL (interpret mode; a state of 128, whole lanes, so that the rule
    holds over the engine's own four-layer arrays): a layer's state is
    read and written at ITS place among the layers that keep state."""
    config = dict(CONFIG, ssm_state_size=128)
    cfg = family.model_config(config)
    params = make_params(cfg)
    assert ssm.state_kernel_engages(jax.ShapeDtypeStruct(
        (4, 3, 8, 8, 128), jnp.float32))
    calls = []

    def kernel(x, dt, a, b, c, states, layer, active):
        calls.append(states.shape)
        return ssm.ssm_state_step_kernel(x, dt, a, b, c, states, layer,
                                         active, interpret=True)

    # the shared mixer code's own name for the update (this family's
    # mixer is that code between its two ends)
    monkeypatch.setattr(falcon_h1, "ssm_state_step", kernel)
    prompt = np.random.default_rng(2).integers(1, cfg.vocab_size, 21)
    got, tokens, _ = _programs_logits(monkeypatch, cfg, params, prompt, 5,
                                      page=8, chunk=2)
    seq = np.concatenate([prompt, tokens[:-1]])[None]
    want = np.asarray(family.logits(config, params, seq))[0, 20:]
    assert calls and set(calls) == {(4, 3, 8, 8, 128)}
    np.testing.assert_allclose(got, want, rtol=PAGED_TOL, atol=PAGED_TOL)


# -- the engine end to end ----------------------------------------------------

def test_the_engine_serves_it_with_its_counters_and_spans(tiny):
    """Through the engine's own loop: two requests that share no slot's
    state, greedy tokens the reference's own (float32 weights), the
    construction span and the counters stating what the plan's layers
    hold, the chunk's span carrying the feed-forward's means."""
    cfg, params = tiny
    was = tracing.is_enabled()
    tracing.enable_tracing()
    try:
        eng = PagedLLMEngine(cfg, params, max_batch=2, max_len=64,
                             page_size=8, decode_chunk=4)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, cfg.vocab_size, n) for n in (19, 7, 12)]
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
    assert stats["state_installs"] == 3 and stats["state_layers"] == 4
    assert stats["decode_delivered"] == 3 * 8
    built = [s for s in tracing.recorded_spans("engine.construct")
             if s["attrs"]["state_slot_bytes"] == stats["state_slot_bytes"]]
    assert built and built[-1]["attrs"]["page_layers"] == "k+v=1"
    assert built[-1]["attrs"]["state_layers"] == 4
    chunks = [s["attrs"] for s in tracing.recorded_spans("engine.emit")
              if s["attrs"].get("what") == "chunk"
              and "experts_touched" in s["attrs"]]
    assert chunks and all(0.0 <= c["routed_here_share"] <= 1.0
                          for c in chunks)
    decodes = [s["attrs"] for s in
               tracing.recorded_spans("engine.dispatch_decode")
               if "state_kernel" in s["attrs"]]
    assert decodes and decodes[-1]["state_kernel"] == 0   # not on a TPU

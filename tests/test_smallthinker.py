"""SmallThinker's block (``models/smallthinker.py``), what it forced in the
ops (a router that scores rows of its own in ``moe_ffn_dropless``, a ReGLU
form in both expert formulations, a bound on a sliding layer's blocks of
queries) and in the engine (a choice made before the attention and used
behind it: ``LayerStack.ahead``), and the model through the paged engine's
normal path, at a small size on the CPU in float32: two periods (a full
layer without rotary, then three sliding layers with it), hidden 64, 2 KV
heads of 16 with query groups of 7 as published, window 8, 8 experts of
32, 2 a token, vocabulary 128. The plain reference is the benchmark's
family file, the one statement of it (``benchmark/families/
smallthinker.py:logits``), which imports nothing from the program."""

import dataclasses
import hashlib
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference
from benchmark.families import smallthinker as family
from engine_lowering import lower
from ray_tpu.models import laguna, smallthinker
from ray_tpu.models.llama import LayerStack
from ray_tpu.ops import moe
from ray_tpu.ops import paged_prefill_attention as ppa
from ray_tpu.ops.attention import cached_attention
from ray_tpu.serve import engine_programs
from ray_tpu.serve.paged_llm import PagedLLMEngine
from ray_tpu.util import tracing

# Float32 against float32: the program and the reference differ in the
# order of their sums, in rsqrt against 1/sqrt and in where the softmax of
# the six chosen is taken (over all experts and renormalised, against over
# the chosen alone); over eight layers with logits of order 1 that is 3e-6
# (measured here). 1e-4 is thirty times that and thousands of times under
# what a wrong block shows (each departure of the reference moves the
# logits by 0.2 to 4). Through the engine the comparison is of tokens, as
# the benchmark's: the engine keeps keys and values in bf16 pages whatever
# the model's type, so a token can differ where the reference's own choice
# was that close (five seeds tried: 0.047 at most).
TOL = 1e-4
GAP_TOL = 0.1
PAGE, WINDOW = 8, 8
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = {
    "family": "smallthinker", "head_dim": 16, "hidden_size": 64,
    "max_position_embeddings": 256, "model_name": "smallthinker_tiny",
    "moe_ffn_hidden_size": 32, "moe_num_active_primary_experts": 2,
    "moe_num_primary_experts": 8, "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True, "num_attention_heads": 14,
    "num_hidden_layers": 8, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    # whole as published, longer than the depth: the first eight are run
    "rope_layout": [0, 1, 1, 1] * 3, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 3,
    "sliding_window_size": WINDOW, "tie_word_embeddings": False,
    "vocab_size": 128, "torch_dtype": "float32", "system": {}}
DEPARTURES = {"router_input": {"router_input": "post_attention"},
              "gate_act": {"gate_act": "silu"},
              "rope": {"rope": "everywhere"}, "window": {"window": None}}


def make_params(cfg, seed=0):
    """Seeded weights with norm vectors away from one, so that each norm
    is seen to be applied."""
    params = smallthinker.init_params(cfg, jax.random.key(seed))
    key = jax.random.key(seed + 1)
    for stack in params["blocks"].values():
        for name in ("attn_norm", "mlp_norm"):
            key, sub = jax.random.split(key)
            stack[name] = 1.0 + 0.3 * jax.random.normal(
                sub, stack[name].shape)
    return params


@pytest.fixture(scope="module")
def tiny():
    cfg = family.model_config(CONFIG)
    return cfg, make_params(cfg)


def test_the_config_and_the_layer_plan(tiny):
    cfg, params = tiny
    assert cfg == smallthinker.smallthinker_tiny()
    plan = smallthinker.layer_plan(cfg)
    assert [(r.key, r.kind, r.window, r.layers, r.ahead) for r in plan] == [
        ("layers0", "full", None, 1, True),
        ("layers1-3", "sliding", WINDOW, 3, True),
        ("layers4", "full", None, 1, True),
        ("layers5-7", "sliding", WINDOW, 3, True)]
    assert set(params["blocks"]) == {r.key for r in plan}
    assert params["blocks"]["layers1-3"]["wqkv"].shape == (3, 64, 18 * 16)
    assert params["blocks"]["layers4"]["wi_gate"].shape == (1, 8, 64, 32)
    assert params["blocks"]["layers4"]["router"].dtype == jnp.float32
    # a full layer takes no rotary table, a sliding layer one
    tables = smallthinker.rotary_tables(cfg, jnp.arange(5)[None])
    assert tables["full"] == () and len(tables["sliding"]) == 2
    # the published depth: thirteen periods
    keys = [r.key for r in smallthinker.layer_plan(
        smallthinker.smallthinker_21b_a3b())]
    assert len(keys) == 26 and keys[-2:] == ["layers48", "layers49-51"]
    with pytest.raises(ValueError, match="rope_layout"):
        smallthinker.smallthinker_tiny(rope_layout=(1,) * 8)
    with pytest.raises(ValueError, match="apply_softmax"):
        smallthinker.smallthinker_tiny(router_softmax=False)
    # every other model's plan states nothing of the kind
    assert LayerStack(None, "full", None, 1).ahead is False
    assert not any(r.ahead for r in laguna.layer_plan(laguna.laguna_tiny()))


def test_the_engine_asks_the_module_for_what_its_plan_uses():
    cfg = smallthinker.smallthinker_tiny()
    assert engine_programs._model_module(cfg) is smallthinker
    # a plan that states ``ahead`` over a module without the piece
    plan = laguna.layer_plan(laguna.laguna_tiny())
    ahead = tuple(r._replace(ahead=True) for r in plan)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(laguna, "layer_plan", lambda cfg: ahead)
        with pytest.raises(TypeError, match="feed_ahead"):
            engine_programs._model_module(laguna.laguna_tiny())


def test_forward_is_the_familys_reference(tiny):
    cfg, params = tiny
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 128, (2, 50)))
    got = smallthinker.forward(cfg, params, tokens)
    want = family.logits(CONFIG, params, tokens)
    assert got.shape == want.shape == (2, 50, 128)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    assert float(jnp.std(want)) > 0.5


@pytest.mark.parametrize("name", sorted(DEPARTURES))
def test_each_departure_of_the_reference_is_another_model(tiny, name):
    cfg, params = tiny
    tokens = jnp.asarray(np.random.default_rng(1).integers(1, 128, (1, 50)))
    got = smallthinker.forward(cfg, params, tokens)
    other = family.logits(CONFIG, params, tokens, **DEPARTURES[name])
    assert float(jnp.max(jnp.abs(got - other))) > 0.2     # 2,000 x TOL


def test_the_references_blocks_and_slices_change_no_value(tiny, monkeypatch):
    """The reference's two departures in form: attention over blocks of
    queries (a last block that is partial among them) and the head a
    slice of the vocabulary at a time are the same logits."""
    cfg, params = tiny
    tokens = jnp.asarray(np.random.default_rng(3).integers(1, 128, (1, 50)))
    whole = family.logits(CONFIG, params, tokens)
    monkeypatch.setattr(family, "_QUERY_BLOCK", 16)
    family._layer.clear_cache()
    try:
        blocks = family.logits(CONFIG, params, tokens)
    finally:
        monkeypatch.undo()
        family._layer.clear_cache()
    assert float(jnp.max(jnp.abs(whole - blocks))) < 1e-5


# -- the ops ------------------------------------------------------------------

def _route_then_experts(x, rows, router, gate, up, down, top_k, act):
    """By hand, float64: the router scores ``rows``, the ``top_k`` largest
    logits weigh by a softmax over themselves, the experts compute ``x``."""
    x, rows, router, gate, up, down = (
        np.asarray(a, np.float64) for a in (x, rows, router, gate, up, down))
    out = np.zeros_like(x)
    load = np.zeros(router.shape[1], np.int64)
    for t in range(x.shape[0]):
        logits = rows[t] @ router
        chosen = np.argsort(-logits)[:top_k]
        w = np.exp(logits[chosen] - logits[chosen].max())
        for e, we in zip(chosen, w / w.sum()):
            g = x[t] @ gate[e]
            g = np.maximum(g, 0.0) if act == "reglu" else g / (1 + np.exp(-g))
            out[t] += we * ((g * (x[t] @ up[e])) @ down[e])
            load[e] += 1
    return out, load


@pytest.mark.parametrize("form", ["reglu", "swiglu"])
@pytest.mark.parametrize("tokens,expert_formulation", [
    (40, "every-held-expert"), (40, "sorted-loop"), (40, "sorted-kernel"),
    (moe.DENSE_MAX_TOKENS + 8, "as-chosen")],
    indirect=["expert_formulation"])
def test_a_router_with_rows_of_its_own(tokens, expert_formulation, form):
    """``moe_route(rows)`` then ``moe_experts(x, choice)``, the path this
    model's block runs: the choice is made of ``rows``, the experts
    compute ``x``, in every formulation and both gated forms; and made of
    ``x`` itself, the two are ``moe_ffn_dropless``."""
    ks = jax.random.split(jax.random.key(4), 6)
    d, f, e, k = 32, 16, 8, 3
    x, rows = (jax.random.normal(key, (tokens, d), jnp.float32)
               for key in ks[:2])
    router = jax.random.normal(ks[2], (d, e), jnp.float32)
    gate, up = (jax.random.normal(key, (e, d, f), jnp.float32) * 0.2
                for key in ks[3:5])
    down = jax.random.normal(ks[5], (e, f, d), jnp.float32) * 0.2
    choice = moe.moe_route(rows, router, top_k=k, norm_topk_prob=True)
    got, load = moe.moe_experts(x, choice, gate, up, down, n_experts=e,
                                form=form)
    want, want_load = _route_then_experts(x, rows, router, gate, up, down,
                                          k, form)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(load), want_load)
    own, _ = moe.moe_ffn_dropless(x, router, gate, up, down, top_k=k,
                                  norm_topk_prob=True, form=form)
    same, _ = moe.moe_experts(
        x, moe.moe_route(x, router, top_k=k, norm_topk_prob=True),
        gate, up, down, n_experts=e, form=form)
    np.testing.assert_array_equal(np.asarray(own), np.asarray(same))
    assert float(jnp.max(jnp.abs(own - got))) > 0.05


def test_the_forms_are_told_apart_and_a_wrong_one_is_refused():
    assert set(moe.EXPERT_FORMS) == {"swiglu", "reglu", "relu2"}
    x = jnp.ones((4, 8))
    w = jnp.ones((2, 8, 4))
    with pytest.raises(ValueError, match="form"):
        moe.moe_ffn_dropless(x, jnp.ones((8, 2)), None, w, w.swapaxes(1, 2),
                             top_k=1, form="reglu")
    with pytest.raises(ValueError, match="form"):
        moe.moe_ffn_dropless(x, jnp.ones((8, 2)), w, w, w.swapaxes(1, 2),
                             top_k=1, form="geglu")


# sha256 (first 16 hex digits) of the text ``moe_ffn_dropless`` lowered to
# on the parent's tree (b740d11, before the op came apart), for the two
# formulations, computed by ``_moe_text`` laid over that tree under the jax
# named below; the sorted one pinned anew in PR 63, whose combine gathers
# the pairs' rows with the choices on the major axis (cbbe3e83a3e2150a
# until then), the every-expert one the same since
_PINNED_JAX = "0.9.0"
_PARENT_MOE_TEXT = {"every-held-expert": "a7476b9dfcb24d12",
                    "sorted": "f1b7bc02f6cc7d58"}


def _moe_text(tokens):
    d, f, e = 32, 16, 8
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16)  # noqa: E731
    return jax.jit(lambda x, r, g, u, w, valid: moe.moe_ffn_dropless(
        x, r, g, u, w, top_k=3, norm_topk_prob=True, routed_scale=2.5,
        first_expert=2, valid=valid)).lower(
        shape(tokens, d), jax.ShapeDtypeStruct((d, e), jnp.float32),
        shape(3, d, f), shape(3, d, f), shape(3, f, d),
        jax.ShapeDtypeStruct((tokens,), jnp.bool_)).as_text()


@pytest.mark.parametrize("tokens,formulation", [
    (40, "every-held-expert"), (moe.DENSE_MAX_TOKENS + 8, "sorted")])
def test_in_one_call_the_op_lowers_to_the_text_it_had(
        tokens, formulation):
    if jax.__version__ != _PINNED_JAX:
        pytest.skip(f"digests pinned under jax {_PINNED_JAX}")
    digest = hashlib.sha256(_moe_text(tokens).encode()).hexdigest()[:16]
    assert digest == _PARENT_MOE_TEXT[formulation]


def test_query_blocks_of_a_window_keep_the_bound():
    block = ppa.query_block
    # Laguna's shapes (window 512): window by window, as they were
    assert block(1, 4096, 72, 4096, 512) == 512
    assert block(2, 2048, 72, 2048, 512) == 512
    assert block(1, 4095, 72, 4096, 512) == 4095
    assert block(2, 64, 18, 64, 16) == 16
    assert 4 * 2 * 72 * 512 * 1024 <= ppa.SCORES_MAX_BYTES
    # this model's cold prompt: a window of queries over two windows of
    # keys would be 3.8 GB of float32 scores; blocks of 512 queries over
    # their 512 + 4,096 keys are a quarter of the bound
    assert 4 * 28 * 4096 * 8192 > 3 * ppa.SCORES_MAX_BYTES
    got = block(1, 8192, 28, 64 * 128, 4096)
    assert got == 512
    assert 4 * 28 * got * (got + 4096) <= ppa.SCORES_MAX_BYTES // 4
    # with the page that a block's first key may straddle counted too
    seen = (-(-(got + 4096 - 2) // 128) + 1) * 128
    assert 4 * 28 * got * seen < ppa.SCORES_MAX_BYTES // 3
    # a cached document's question goes whole, and so does the check's:
    # fewer queries than a window are bounded by their own scores
    assert block(1, 256, 28, 64 * 128, 4096) == 256
    assert block(1, 512, 28, 64 * 128, 4096) == 512
    assert block(1, 1024, 28, 64 * 128, 4096) == 1024
    assert 4 * 28 * 1024 * (1024 + 4096) <= ppa.SCORES_MAX_BYTES
    assert block(1, 2048, 28, 64 * 128, 4096) == 512
    # a wide group under Laguna's window: 7 rows go as they did, 8 are
    # past the bound and go in blocks of 128, but a bucket shorter than
    # the window whose own scores fit goes whole
    assert block(7, 4096, 72, 4096, 512) == 512
    assert 4 * 8 * 72 * 512 * 1024 > ppa.SCORES_MAX_BYTES
    assert block(8, 4096, 72, 4096, 512) == 128
    assert block(8, 256, 72, 4096, 512) == 256
    assert block(16, 64, 72, 4096, 512) == 64


def test_windowed_prefill_in_small_blocks_is_the_one_call(monkeypatch):
    """A sliding layer's prefill in blocks of FEWER queries than its
    window (forced by a small bound, as this model's widths force it at
    the real one) against one ``cached_attention`` over the rows' whole
    tables: two rows of 128 new tokens behind 0 and 24 cached ones, a
    window of 64 in blocks of 16."""
    rng = np.random.default_rng(6)
    pool, nkv, hd, heads, t, mp, window = 40, 2, 16, 14, 128, 20, 64
    kp, vp = (jnp.asarray(rng.standard_normal((2, pool, PAGE, nkv, hd)),
                          jnp.bfloat16) for _ in range(2))
    scale1 = jnp.ones((2, 1, 1, 1), jnp.float32)
    table = jnp.asarray(rng.permutation(pool)[:2 * mp].reshape(2, mp),
                        jnp.int32)
    q = jnp.asarray(rng.standard_normal((2, t, heads, hd)), jnp.bfloat16)
    starts = jnp.asarray([0, 24], jnp.int32)
    kg, vg = kp[1, table].reshape(2, -1, nkv, hd), vp[1, table].reshape(
        2, -1, nkv, hd)
    want = cached_attention(q, kg, vg, starts, scale=hd ** -0.5,
                            window=window)
    assert ppa.query_block(2, t, heads, mp * PAGE, window) == 64
    monkeypatch.setattr(ppa, "SCORES_MAX_BYTES",
                        4 * 2 * heads * 64 * (64 + window) - 4)
    assert ppa.query_block(2, t, heads, mp * PAGE, window) == 16
    got = ppa.paged_prefill_attention_reference(
        q, kp, vp, scale1, scale1, jnp.int32(1), table, starts,
        window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


# -- through the engine ----------------------------------------------------

def test_engine_serves_smallthinker_past_the_window_over_reused_pages(tiny):
    """Through ``submit`` -> admission -> the two programs: a prompt of 50
    tokens (six windows), then a second that shares its first 32 tokens,
    so that four of its pages are reused and lie wholly BEFORE the window
    of its first new query; 12 tokens each, decoded past the window from
    the first step. Every greedy token within the benchmark's gap of the
    reference's best, and the spans carry the new counts."""
    cfg, params = tiny
    rng = np.random.default_rng(2)
    first = rng.integers(1, 128, 50, dtype=np.int32)
    second = np.concatenate([first[:32], rng.integers(1, 128, 19,
                                                      dtype=np.int32)])
    # the ring is the process's: what another file's engine left in it
    # (another family's counts) is not this engine's
    tracing.drain_spans(1 << 20)
    tracing._rings()[1].clear()
    tracing.enable_tracing()
    try:
        eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=2,
                             max_len=128, page_size=PAGE, num_pages=40)
        assert eng._programs.window == WINDOW
        eng.start()
        served = []
        for prompt in (first, second):
            req = eng.submit(prompt, max_new_tokens=12)
            served.append((prompt, list(req.tokens())))
        hits = eng._prefix.hit_pages
        eng.stop()
        spans = tracing.recorded_spans("engine.")
    finally:
        tracing.disable_tracing()
    assert eng.error is None and hits == 4
    for prompt, tokens in served:
        assert len(tokens) == 12
        gap, _ = reference.token_gap(family.logits, CONFIG, params, prompt,
                                     tokens)
        assert gap <= GAP_TOL
    decode = [s["attrs"] for s in spans
              if s["name"] == "engine.dispatch_decode"]
    assert decode and all(
        a["slots_past_window"] == a["live"]
        and a["kv_rows_window"] == WINDOW * a["live"] < a["kv_rows_full"]
        for a in decode)
    emits = [s["attrs"] for s in spans if s["name"] == "engine.emit"
             and "experts_touched" in s["attrs"]]
    assert emits and all(1.0 <= a["experts_touched"] <= 2.0
                         and "routed_here_share" not in a for a in emits)


def test_a_plan_without_a_window_counts_none_past_it():
    from ray_tpu.models import llama

    cfg = llama.llama_tiny()
    tracing.drain_spans(1 << 20)
    tracing._rings()[1].clear()
    tracing.enable_tracing()
    try:
        eng = PagedLLMEngine(cfg=cfg, params=llama.init_params(
            cfg, jax.random.key(0)), max_batch=2, max_len=64, page_size=16)
        assert eng._programs.window is None
        eng.start()
        assert len(list(eng.submit(list(range(1, 20)),
                                   max_new_tokens=6).tokens())) == 6
        eng.stop()
        spans = tracing.recorded_spans("engine.dispatch_decode")
    finally:
        tracing.disable_tracing()
    assert spans and all(
        "kv_rows_full" in s["attrs"]
        and "slots_past_window" not in s["attrs"] for s in spans)


def _program_text(cfg, program):
    dims = (4, 4) if program == "decode" else (2, 16, 4)
    return lower(jax.devices("cpu")[0], smallthinker, cfg, program, dims,
                 num_pages=16, slots=4, page=8)


def _scoped_events(text):
    """{function: its events in order} of a program's lowered text with
    locations: ``R`` a matrix product under the ``moe_router`` scope, ``E``
    one under ``moe_experts``, ``A`` a softmax's exponential outside the
    router's, and the name of each function it calls."""
    locs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))

    def scope(line):
        found = re.search(r"loc\((#loc\d+)\)\s*$", line)
        name = locs.get(found.group(1), "") if found else ""
        for _ in range(4):      # a location may name others
            name += " ".join(locs.get(r, "")
                             for r in re.findall(r"#loc\d+", name))
        return name

    events, here = {}, None
    for line in text.splitlines():
        if "func.func" in line:
            here = events.setdefault(
                line.split("@")[1].split("(")[0], [])
        elif "stablehlo.dot_general" in line and "moe_router" in scope(line):
            here.append("R")
        elif "stablehlo.dot_general" in line and "moe_experts" in scope(line):
            here.append("E")
        elif ("stablehlo.exponential" in line
              and "moe_router" not in scope(line)):
            here.append("A")
        elif "call @" in line:
            here.append(line.split("@")[1].split("(")[0])
    return events


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_router_stands_before_the_attention_in_both_programs(program):
    """In the lowered text of each program every layer loop's body (one a
    run of the plan: four) has the router's float32 product FIRST, then
    the attention's softmax (in the body itself, or in the loop over
    blocks of queries that a sliding layer's prefill calls), then the
    experts' products: the choice is made where the layer begins and used
    where it ends."""
    cfg = smallthinker.smallthinker_tiny(dtype="bfloat16")
    events = _scoped_events(
        _program_text(cfg, program).as_text(debug_info=True))

    def attends(name, seen=()):
        return name not in seen and any(
            e == "A" or attends(e, (*seen, name))
            for e in events.get(name, ()))

    layers = [body for body in events.values() if "R" in body]
    assert len(layers) == 4
    for body in layers:
        order = "".join(
            e if e in "RAE" else "A" if attends(e) else "" for e in body)
        assert re.fullmatch(r"RA+E+", order), order


# -- the configuration at the published widths -----------------------------

def cell_config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker-21ba3b-instruct-d8.json")) as f:
        return json.load(f)


def test_the_d8_parameters_are_the_count_leaf_for_leaf():
    config = cell_config()
    cfg = family.model_config(config)
    assert cfg == dataclasses.replace(
        smallthinker.smallthinker_21b_a3b(),
        sliding_window_layout=(0, 1, 1, 1) * 2, rope_layout=(0, 1, 1, 1) * 2)
    shapes = jax.eval_shape(lambda k: family.init_params(cfg, k),
                            jax.random.key(0))
    layer = 20_971_520 + 163_840 + 5_120 + 64 * 5_898_240
    assert layer == 398_627_840 == family.layer_params(config)
    assert family.attention_params(config) == 20_971_520
    assert family.expert_params(config) == 5_898_240
    want = 8 * layer + 2 * 151_936 * 2_560 + 2_560    # the final norm
    assert want == 3_966_937_600
    assert sum(a.size for a in jax.tree.leaves(shapes)) == want \
        == family.total_params(config)
    assert shapes["blocks"]["layers1-3"]["wqkv"].shape == (3, 2560, 4608)
    assert shapes["blocks"]["layers1-3"]["wi_gate"].shape == (
        3, 64, 2560, 768)
    assert shapes["blocks"]["layers4"]["router"].shape == (1, 2560, 64)


_TENSOR = re.compile(r"tensor<((?:\d+x)+)(f32|bf16|i32|i1|i8|ui32)>")
_ITEM = {"f32": 4, "bf16": 2, "i32": 4, "i1": 1, "i8": 1, "ui32": 4}


def _largest_made(text, skip):
    """(bytes, type) of the largest tensor a lowered program's text names
    that is not one of its arguments' types ``skip``."""
    best = (0, "")
    for dims, dtype in set(_TENSOR.findall(text)):
        name = f"tensor<{dims}{dtype}>"
        if name in skip:
            continue
        size = _ITEM[dtype] * int(np.prod(
            [int(d) for d in dims[:-1].split("x")]))
        best = max(best, (size, name))
    return best


@pytest.mark.parametrize("program,dims", [
    ("prefill", (1, 8192, 64)), ("decode", (16, 64))],
    ids=["cold-prefill-1x8192", "decode-32-slots-64-pages"])
def test_the_d8_programs_make_no_array_past_the_bound(program, dims):
    """The cell's two largest programs at the published widths, lowered
    from shapes alone (no weight is made): what a sliding layer's prefill
    asks for stays under 1.5 GB: its float32 scores are [1, 4, 7, 512,
    4736], 271 MB a block of 512 queries, and the 3.8 GB block of a whole
    window of queries over two windows of keys is gone. The largest array
    either program makes is the grouped experts' float32 result over the
    (token, choice) pairs, [49152, 2560]."""
    cfg = family.model_config(cell_config())
    pages = cell_config()["system"]["num_pages"]
    text = lower(jax.devices("cpu")[0], smallthinker, cfg, program, dims,
                 num_pages=pages).as_text()
    at = text.index("func.func public @main")
    entry = text[at:text.index("\n", at)]
    skip = {m.group(0) for m in _TENSOR.finditer(entry)}
    size, name = _largest_made(text, skip)
    assert size < 1.5e9, name
    assert "x4096x8320xf32>" not in text and "x4096x8192xf32>" not in text
    if program == "prefill":
        assert "tensor<1x4x7x512x4736xf32>" in text
        assert size == 4 * 49152 * 2560, name

"""OLMoE's block (``models/olmoe.py``), the dropless routed-expert op
(``ops/moe.py:moe_ffn_dropless``) and the block through the paged engine's
normal path, at a small size on the CPU in float32 (2 layers, hidden 64,
4 heads of 16, 8 experts of 32, 3 a token, vocabulary 128). The plain
reference is the benchmark's family file, the one statement of it
(``benchmark/families/olmoe.py:logits``), which imports nothing from the
program."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference
from benchmark.families import olmoe as family
from ray_tpu.models import olmoe
from ray_tpu.ops import moe
from ray_tpu.serve.paged_llm import PagedLLMEngine
from ray_tpu.util import tracing

# Float32 against float32: the program and the reference differ only in
# the order of their sums (a fused einsum against a loop over experts, a
# masked softmax against -inf) and in rsqrt against 1/sqrt. Over two
# layers with logits of order 4 that is 2e-6 (measured here: 2.0e-6 in
# the logits); 1e-4 is fifty times that, and a thousand times under what
# a wrong block shows (QK-norm left out: 3.6 in the logits; the top-k
# renormalised: 1.5). Through the engine the comparison is of tokens: the
# gap is 0 where every served token is the reference's own greedy choice,
# and the size of the reference's margin where one is not. The engine
# keeps keys and values in bf16 pages whatever the model's type, so its
# hidden states lie about 1e-3 from the reference's, and a token can
# differ where the reference's own choice (of an expert, of the token) was
# that close: the prompts' seeds are ones where none is (three of eight
# seeds tried had such a place: gaps 0.004, 0.026, 0.65; five had none).
TOL = 1e-4
PAGE = 16
CONFIG = {
    "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 3,
    "norm_topk_prob": False, "clip_qkv": None, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "torch_dtype": "float32",
    "tie_word_embeddings": False, "system": {}}


def make_params(cfg, seed=3):
    """Seeded weights with norm vectors away from one, so that each norm
    is seen to be applied."""
    params = olmoe.init_params(cfg, jax.random.key(seed))
    keys = jax.random.split(jax.random.key(seed + 1), 4)
    for key, name in zip(keys, ("attn_norm", "mlp_norm", "q_norm", "k_norm")):
        shape = params["blocks"][name].shape
        params["blocks"][name] = 1.0 + 0.3 * jax.random.normal(key, shape)
    return params


@pytest.fixture(scope="module")
def tiny():
    cfg = family.model_config(CONFIG)
    assert cfg == olmoe.olmoe_tiny()
    return cfg, make_params(cfg)


# -- the model -------------------------------------------------------------

@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_forward_matches_the_plain_reference(tiny, norm_topk_prob):
    config = dict(CONFIG, norm_topk_prob=norm_topk_prob)
    cfg, params = family.model_config(config), tiny[1]
    tokens = jax.random.randint(jax.random.key(1), (2, 40), 1, 128)
    got = jax.jit(lambda p, t: olmoe.forward(cfg, p, t))(params, tokens)
    want = family.logits(config, params, tokens)
    assert got.dtype == jnp.float32 and got.shape == (2, 40, 128)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    # and the tolerance tells the mathematics apart
    other = family.logits(dict(config, norm_topk_prob=not norm_topk_prob),
                          params, tokens)
    assert float(jnp.max(jnp.abs(got - other))) > 100 * TOL
    no_norm = family.logits(config, params, tokens, qk_norm=False)
    assert float(jnp.max(jnp.abs(got - no_norm))) > 100 * TOL


def test_config_reads_the_published_keys():
    full = olmoe.olmoe_1b_7b()
    assert (full.n_layers, full.d_model, full.n_heads, full.n_kv_heads,
            full.head_dim, full.d_ff, full.n_experts, full.top_k) == (
        16, 2048, 16, 16, 128, 1024, 64, 8)
    assert full.norm_topk_prob is False and full.clip_qkv is None
    with pytest.raises(ValueError, match="clip_qkv"):
        olmoe.OlmoeConfig(clip_qkv=8.0)
    shapes = jax.eval_shape(lambda k: olmoe.init_params(full, k),
                            jax.random.key(0))
    blocks = shapes["blocks"]
    assert blocks["q_norm"].shape == blocks["k_norm"].shape == (16, 2048)
    assert blocks["router"].shape == (16, 2048, 64)
    assert blocks["router"].dtype == jnp.float32
    assert blocks["wi_gate"].shape == blocks["wi_up"].shape == (
        16, 64, 2048, 1024)
    assert blocks["wo_e"].shape == (16, 64, 1024, 2048)
    assert sum(a.size for a in jax.tree.leaves(shapes)) == \
        family.total_params(dict(CONFIG, **PUBLISHED))
    axes = olmoe.param_logical_axes(full)
    assert jax.tree.structure(axes, is_leaf=lambda a: isinstance(a, tuple)) \
        == jax.tree.structure(shapes)
    assert axes["blocks"]["wi_gate"][1] == "expert"


PUBLISHED = {
    "vocab_size": 50304, "hidden_size": 2048, "num_hidden_layers": 16,
    "num_attention_heads": 16, "num_key_value_heads": 16, "head_dim": 128,
    "intermediate_size": 1024, "num_experts": 64, "num_experts_per_tok": 8}


# -- the dropless op ---------------------------------------------------------

def op_inputs(t, seed=0, d=64, f=32, e=8):
    ks = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(ks[0], (t, d)),
            jax.random.normal(ks[1], (d, e)) * d ** -0.5,
            jax.random.normal(ks[2], (e, d, f)) * d ** -0.5,
            jax.random.normal(ks[3], (e, d, f)) * d ** -0.5,
            jax.random.normal(ks[4], (e, f, d)) * f ** -0.5)


def per_token_loop(x, router, gate, up, down, top_k, norm, valid=None):
    """The op's definition, a token and a choice at a time, in float64:
    each token's chosen experts gathered and applied to it alone."""
    x, router, gate, up, down = (np.asarray(a, np.float64)
                                 for a in (x, router, gate, up, down))
    out = np.zeros_like(x)
    load = np.zeros(router.shape[1], np.int64)
    for i, row in enumerate(x):
        if valid is not None and not valid[i]:
            continue
        logits = row @ router
        p = np.exp(logits - logits.max())
        p /= p.sum()
        order = np.argsort(-p)
        # no tie at the boundary of the choice, by construction
        assert logits[order[top_k - 1]] - logits[order[top_k]] > 1e-4
        chosen = order[:top_k]
        w = p[chosen] / (p[chosen].sum() if norm else 1.0)
        for e, we in zip(chosen, w):
            h = row @ gate[e]
            out[i] += we * ((h / (1.0 + np.exp(-h))) * (row @ up[e])) @ down[e]
            load[e] += 1
    return out, load


@pytest.mark.parametrize("norm", [False, True])
@pytest.mark.parametrize("t,expert_formulation", [
    (1, "as-chosen"), (7, "as-chosen"), (100, "as-chosen"),
    (300, "as-chosen"),                   # on both sides of the line
    (300, "every-held-expert"),           # forced past the line
    (7, "sorted-loop"), (300, "sorted-loop"),
    (7, "sorted-kernel"), (300, "sorted-kernel")], indirect=[
        "expert_formulation"])
def test_dropless_op_against_a_per_token_loop(t, expert_formulation, norm):
    """Every formulation the op can choose, at token counts on both sides
    of its line, and each of them forced: every held expert past the
    line, and the sorted rows (the plain loop, and the kernel interpreted)
    at small counts too, where groups are empty."""
    assert moe.expert_kernel_engages(t) == (
        t == 300 and expert_formulation == "as-chosen"
        or expert_formulation.startswith("sorted"))
    args = op_inputs(t, seed=t)
    out, load = jax.jit(lambda *a: moe.moe_ffn_dropless(
        *a, top_k=3, norm_topk_prob=norm))(*args)
    want, want_load = per_token_loop(*args, 3, norm)
    assert float(np.max(np.abs(np.asarray(out) - want))) < 1e-5
    assert np.array_equal(np.asarray(load), want_load)
    assert int(load.sum()) == 3 * t                     # nothing dropped


_FORCED = pytest.mark.parametrize(
    "expert_formulation", ["as-chosen", "sorted-loop", "sorted-kernel"],
    indirect=True)


@_FORCED
def test_padding_rows_go_to_no_expert(expert_formulation):
    args = op_inputs(20, seed=5)
    valid = np.arange(20) % 3 != 1
    out, load = jax.jit(lambda *a: moe.moe_ffn_dropless(
        *a, top_k=3, valid=jnp.asarray(valid)))(*args)
    want, want_load = per_token_loop(*args, 3, False, valid)
    assert float(np.max(np.abs(np.asarray(out) - want))) < 1e-5
    assert not np.asarray(out)[~valid].any()
    assert np.array_equal(np.asarray(load), want_load)
    assert int(load.sum()) == 3 * int(valid.sum())


@_FORCED
def test_nothing_is_dropped_where_capacity_routing_drops(expert_formulation):
    """Every token's first choice is expert 0: capacity routing keeps
    ``1.25 * T * k / E`` of them and drops the rest; the dropless op
    serves all (sorted, expert 0's group is several tiles of rows and
    most experts' are empty)."""
    t, k, e = 32, 2, 8
    x, router, gate, up, down = op_inputs(t, seed=9)
    x = jnp.abs(x)
    router = router.at[:, 0].set(0.1)          # x >= 0: expert 0 wins
    capacity = int(1.25 * t * k / e)
    dispatch, _, _ = moe.router_topk(x @ router, top_k=k, capacity=capacity)
    assert float(dispatch[:, 0].sum()) == capacity < t   # the drop
    out, load = moe.moe_ffn_dropless(x, router, gate, up, down, top_k=k)
    want, want_load = per_token_loop(x, router, gate, up, down, k, False)
    assert int(load[0]) == t and int(load.sum()) == t * k
    assert float(np.max(np.abs(np.asarray(out) - want))) < 1e-5
    dropped, _ = moe.moe_ffn(x, router, gate, up, down, top_k=k)
    assert float(np.max(np.abs(np.asarray(dropped) - want))) > 1e-2


def test_the_op_builds_no_capacity_tensor():
    """No [T, E, C] dispatch or combine tensor: nothing in the grouped
    formulation grows with T x E, and the dense one holds [T, E, F] only
    up to its threshold."""
    t = 4096
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (t, 64), (64, 8), (8, 64, 32), (8, 64, 32), (8, 32, 64))]
    jaxpr = jax.make_jaxpr(lambda *a: moe.moe_ffn_dropless(*a, top_k=3))(
        *shapes)
    biggest = max(int(np.prod(v.aval.shape)) for eqn in jaxpr.eqns
                  for v in eqn.outvars)
    assert biggest <= t * 3 * 64          # the sorted pairs' rows, no more


# -- through the paged engine's normal path ----------------------------------

def served(eng, prompt, new):
    tokens = list(eng.submit(prompt, max_new_tokens=new).tokens())
    assert len(tokens) == new
    return tokens


def gap(prompt, tokens, params, **departures):
    def logits(config, params, seq):
        return family.logits(config, params, seq, **departures)
    return reference.token_gap(logits, CONFIG, params, prompt, tokens)


def test_prefill_then_decode_through_the_pages_is_the_reference(tiny):
    """``PagedLLMEngine(OlmoeConfig, ...)`` through ``submit``: prefill,
    then decode through the pages, gives the reference's own greedy token
    at every step (its teacher-forced gap under the tolerance); again for
    a second prompt that reuses the first's pages, in the slot the first
    left (one slot: a refill); and the same comparison fails against the
    reference with QK-norm left out and with the top-k renormalised."""
    cfg, params = tiny
    eng = PagedLLMEngine(cfg, params, max_batch=1, max_len=128,
                         page_size=PAGE, num_pages=16, prefix_cache=True)
    eng.start()
    try:
        rng = np.random.default_rng(5)
        first = rng.integers(1, 128, 45)
        second = np.concatenate([first[:2 * PAGE], rng.integers(1, 128, 9)])
        answers = [(p, served(eng, p, 12)) for p in (first, second)]
        hits = eng.stats()["prefix_cache"]["hit_pages"]
        # a slot refill with no reuse: a third prompt of its own
        third = rng.integers(1, 128, 23)
        answers.append((third, served(eng, third, 12)))
    finally:
        eng.stop()
    assert hits == 2                       # the second reused two pages
    for prompt, tokens in answers:
        short, not_own = gap(prompt, tokens, params)
        assert short < TOL and not_own == 0
        assert gap(prompt, tokens, params, qk_norm=False)[0] > 100 * TOL
        assert gap(prompt, tokens, params,
                   norm_topk_prob=True)[0] > 100 * TOL


def test_batched_slots_and_padding_do_not_touch_each_other(tiny):
    """Three requests of different lengths at once in four slots (one
    slot idle, prompts padded to their buckets): each answer is the
    reference's."""
    cfg, params = tiny
    eng = PagedLLMEngine(cfg, params, max_batch=4, max_len=128,
                         page_size=PAGE, num_pages=40)
    eng.start()
    try:
        rng = np.random.default_rng(1)
        prompts = [rng.integers(1, 128, n) for n in (5, 33, 70)]
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        answers = [list(r.tokens()) for r in reqs]
    finally:
        eng.stop()
    for prompt, tokens in zip(prompts, answers):
        short, not_own = gap(prompt, tokens, params)
        assert len(tokens) == 10 and short < TOL and not_own == 0


def test_decode_chunks_carry_the_routing_counts(tiny, tmp_path):
    """While spans are recorded, each decode chunk's ``engine.emit`` span
    carries what the program counted of its routing: experts touched a
    layer-step (between k and E) and the busiest expert's load over the
    mean (1 = even; E / k = every token on the same experts)."""
    cfg, params = tiny
    eng = PagedLLMEngine(cfg, params, max_batch=4, max_len=128,
                         page_size=PAGE, num_pages=40)
    eng.start()
    rng = np.random.default_rng(2)
    served(eng, rng.integers(1, 128, 20), 20)              # compiles
    jax.profiler.start_trace(str(tmp_path))
    try:
        reqs = [eng.submit(rng.integers(1, 128, 20), max_new_tokens=40)
                for _ in range(3)]
        for r in reqs:
            assert len(list(r.tokens())) == 40
    finally:
        jax.profiler.stop_trace()
        eng.stop()
    chunks = [s["attrs"] for s in tracing.recorded_spans("engine.emit")
              if s["attrs"].get("what") == "chunk"
              and "experts_touched" in s["attrs"]]
    assert len(chunks) >= 2
    for attrs in chunks:
        assert 0.0 <= attrs["experts_touched"] <= 8.0
        assert 0.0 <= attrs["expert_load_max_over_mean"] <= 8.0 / 3 + 1e-6
    # three live slots x 3 choices reach more than 3 of 8 experts
    assert max(a["experts_touched"] for a in chunks) > 3.0
    assert not eng._chunk_stats          # every dispatched chunk was read


def test_the_engine_resolves_the_block_from_the_configs_class(tiny):
    from ray_tpu.models import llama, mixtral
    from ray_tpu.serve import engine_programs

    assert engine_programs._model_module(tiny[0]) is olmoe
    assert engine_programs._model_module(llama.llama_tiny()) is llama
    with pytest.raises(TypeError, match="MixtralConfig"):
        engine_programs._model_module(mixtral.mixtral_tiny())
    # the programs keep their names, whatever the block
    eng = PagedLLMEngine(tiny[0], tiny[1], max_batch=1, max_len=64,
                         page_size=PAGE, num_pages=8)
    assert eng._programs._decode_paged(4, 2).__name__ == "paged_decode_c4_w2"
    assert eng._programs._prefill_paged(2).__name__ == "paged_prefill_w2"

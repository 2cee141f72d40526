"""LFM2-MoE through the program (PR 67): gated short convolutions three to
one with grouped-query attention over 64-wide heads, two dense layers and
then sigmoid-routed experts, a head tied to the embedding; the first
recurrent plan whose prefix is reused, by the state a page keeps at its
end. At a tiny size with the published STRUCTURE (heads of 64 in pairs a
row, 3 taps, two dense layers, a period ``A c c c``, 8 experts 2 a token
with a bias that changes the choice), on the CPU: the program's
``forward`` against the family's plain reference on seeded weights, and
each named departure of the reference past the tolerance; prefill then
decode through the engine's pages, slots and page-kept tails, logits not
tokens; a prefix hit of ``k`` pages against the same request cold, bit
for bit; the tail as stated (float32 here) and an inactive slot's kept;
the 64-wide heads two a row of the pools against the plain formulation,
both kernels in interpret mode; the engine end to end with its counters,
through an eviction."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference
from benchmark.families import lfm2_moe as family
from engine_lowering import run
from ray_tpu.models import lfm2_moe
from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops import paged_decode_attention as pda
from ray_tpu.ops import paged_prefill_attention as ppa
from ray_tpu.serve import engine_programs
from ray_tpu.serve.paged_llm import PagedLLMEngine
from ray_tpu.util import tracing

# the published keys at a tiny size: c c | A c c c, 4 query heads of 64 on
# 2 KV heads (one row of the pools), 8 experts of 48, 2 a token, a dense
# width of four experts
CONFIG = {
    "model_type": "lfm2_moe", "vocab_size": 128, "hidden_size": 256,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv"],
    "num_hidden_layers": 6, "num_attention_heads": 4,
    "num_key_value_heads": 2, "conv_L_cache": 3, "conv_bias": False,
    "intermediate_size": 192, "moe_intermediate_size": 48,
    "num_dense_layers": 2, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "use_expert_bias": True, "norm_eps": 1e-5, "rope_theta": 10000,
    "max_position_embeddings": 128000, "torch_dtype": "float32",
    "tie_word_embeddings": True, "system": {"page_size": 8}}
PAGE = 8
# float32 against float32 over six layers: the program's sums run in
# another order than the reference's (fused matmuls, the experts as one
# batched matmul, the convolution as shifted multiply-adds)
LOGIT_TOL = 2e-4
# through the engine's programs the keys and values lie in bf16 pages
# whatever the model's dtype (the pool's format), and q is rounded once
# more where two heads share a row: the one attention layer's output
# carries both, and ``wo`` stands at four times its branch's scale. A tail
# installed at another layer's place, a page's state read one page off or
# a hit begun from zeros misses by 0.2 and more; the least of the
# reference's departures (``weights``) by 0.15
PAGED_TOL = 4e-2
DEPARTURES = [
    {"order": "x_first"}, {"conv_act": "silu"}, {"qk_norm": "none"},
    {"norm_place": "after_rope"}, {"scores": "softmax"}, {"bias": "none"},
    {"weights": "with_bias"}, {"experts": "geglu"}, {"dense_layers": 0}]
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_params(cfg, seed=3):
    """Seeded weights, made in one jitted call."""
    return jax.jit(lfm2_moe.init_params, static_argnums=0)(
        cfg, jax.random.key(seed))


@pytest.fixture(scope="module")
def tiny():
    cfg = family.model_config(CONFIG)
    return cfg, make_params(cfg)


def test_the_config_and_the_layer_plan(tiny):
    cfg, params = tiny
    assert cfg == lfm2_moe.lfm2_moe_tiny()
    plan = lfm2_moe.layer_plan(cfg)
    assert [(run.key, run.layers, run.state is not None, run.attends,
             run.feeds) for run in plan] == [
        ("layers0-1", 2, True, False, True), ("layers2", 1, False, True, True),
        ("layers3-5", 3, True, False, True)]
    # ONE state array, which the pages keep too; no array the state
    # kernel takes
    state = lfm2_moe.recurrent_state(cfg)
    assert state.arrays == (("conv_tail", (2, 256), "float32"),)
    assert state.pages_keep and state.chunk == PAGE
    assert set(params["blocks"]) == {run.key for run in plan}
    conv = {"norm", "ffn_norm", "in_proj", "conv_w", "out_proj"}
    routed = {"router", "router_bias", "wi_gate", "wi_up", "wo_e"}
    assert set(params["blocks"]["layers0-1"]) == conv | {
        "w_gate", "w_up", "w_down"}
    assert set(params["blocks"]["layers3-5"]) == conv | routed
    assert set(params["blocks"]["layers2"]) == routed | {
        "norm", "ffn_norm", "wqkv", "q_norm", "k_norm", "wo"}
    assert params["blocks"]["layers3-5"]["in_proj"].shape == (3, 256, 768)
    assert params["blocks"]["layers3-5"]["conv_w"].shape == (3, 256, 3)
    assert params["blocks"]["layers2"]["q_norm"].shape == (1, 64)
    assert "lm_head" not in params               # the head is the embedding
    published = lfm2_moe.lfm2_8b_a1b()
    kinds = published.layer_types
    assert (kinds.count("conv"), kinds.count("full_attention"),
            published.n_layers) == (18, 6, 24)
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == [
        2, 6, 10, 14, 18, 21]
    with pytest.raises(ValueError, match="conv or full_attention"):
        lfm2_moe.lfm2_moe_tiny(layer_types=("conv", "mamba"))


def test_the_cells_plan_is_seven_runs_eleven_tails_three_pools():
    """The configuration of ``serve-extract-gen``, from its file, without
    a weight: c c dense, then A, c c c three times, routed; the tails
    span ELEVEN layers in the slots and in the pages alike, the K/V pools
    THREE, two 64-wide KV heads a row: 6,144 B a token."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-8b-a1b-d14.json")) as f:
        config = json.load(f)
    cfg = family.model_config(config)
    plan = lfm2_moe.layer_plan(cfg)
    assert [(run.key, run.layers, run.attends) for run in plan] == [
        ("layers0-1", 2, False), ("layers2", 1, True),
        ("layers3-5", 3, False), ("layers6", 1, True),
        ("layers7-9", 3, False), ("layers10", 1, True),
        ("layers11-13", 3, False)]
    assert engine_programs._state_layers(plan) == 11
    assert engine_programs._pool_layers(plan, None) == 3
    system = config["system"]
    pools, state, kept = engine_programs.store_shapes(
        cfg, max_batch=system["max_batch"], num_pages=system["num_pages"],
        page_size=system["page_size"], kv_dtype="bf16")
    pages = system["num_pages"]
    assert [(p.shape, p.dtype) for p in pools[:2]] == [
        ((3, pages, 128, 4, 128), jnp.bfloat16)] * 2
    assert [(a.shape, a.dtype) for a in state] == [
        ((11, 64, 2, 2048), jnp.bfloat16)]
    assert [(a.shape, a.dtype) for a in kept] == [
        ((11, pages, 2, 2048), jnp.bfloat16)]
    token = sum(p.size * p.dtype.itemsize for p in pools[:2]) // (pages * 128)
    assert token == 6144 == 3 * family.kv_bytes_per_token_layer(config)
    assert kept[0].size * 2 // pages == 90112 == (
        11 * family.state_bytes_per_slot_layer(config))
    # both page kernels engage at these shapes: a cold 4,096 prompt, and
    # a step of the decode kernel's walk over two pages
    assert ppa.kernel_engages((1, 4096, 32, 128), pools[0], 32, None)
    assert pda.step_pages(pools[0]) == 2


# -- the module against the plain reference ----------------------------------

@pytest.fixture(scope="module")
def forward_pass(tiny):
    cfg, params = tiny
    tokens = np.random.default_rng(5).integers(1, cfg.vocab_size, (2, 37))
    return tokens, np.asarray(lfm2_moe.forward(cfg, params,
                                               jnp.asarray(tokens)))


def test_forward_is_the_plain_reference(tiny, forward_pass):
    cfg, params = tiny
    tokens, got = forward_pass
    want = np.asarray(family.logits(CONFIG, params, tokens))
    assert got.shape == want.shape == (2, 37, cfg.vocab_size)
    assert 0.25 < want.std() < 0.45     # the tied head's logits, as drawn
    np.testing.assert_allclose(got, want, rtol=LOGIT_TOL, atol=LOGIT_TOL)


@pytest.mark.parametrize("departure", DEPARTURES,
                         ids=[next(iter(d)) for d in DEPARTURES])
def test_each_departure_of_the_reference_is_another_model(tiny, forward_pass,
                                                          departure):
    """Each thing the configuration assumes, read the other way, moves
    the logits far past both tolerances: the program states the reading
    taken."""
    _, params = tiny
    tokens, got = forward_pass
    other = np.asarray(family.logits(CONFIG, params, tokens, **departure))
    assert np.abs(other - got).max() > 3 * PAGED_TOL
    with pytest.raises(ValueError, match="is one of"):
        family.logits(CONFIG, params, tokens, order="no-such")


def test_the_bias_changes_the_choice_and_no_weight(tiny):
    """``expert_bias`` moves which experts a token takes (on these
    weights, for most tokens) and the chosen weights are the scores
    without it, over their sum plus the published epsilon."""
    cfg, params = tiny
    from ray_tpu.ops.moe import moe_route

    p = jax.tree.map(lambda a: a[0], params["blocks"]["layers3-5"])
    rows = jax.random.normal(jax.random.key(1), (64, cfg.d_model))
    kw = dict(top_k=cfg.top_k, norm_topk_prob=True, scoring="sigmoid")
    weights, chosen = moe_route(rows, p["router"], **kw,
                                choice_bias=p["router_bias"],
                                norm_eps=lfm2_moe.ROUTE_EPS)
    _, unbiased = moe_route(rows, p["router"], **kw)
    moved = np.mean(np.sort(chosen, -1) != np.sort(unbiased, -1))
    assert moved > 0.2
    scores = jax.nn.sigmoid(rows @ p["router"])
    taken = np.take_along_axis(np.asarray(scores), np.asarray(chosen), -1)
    np.testing.assert_allclose(
        weights, taken / (taken.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    # told no epsilon, a sigmoid router's sum takes the older families'
    older, _ = moe_route(rows, p["router"], **kw,
                         choice_bias=p["router_bias"])
    assert np.abs(np.asarray(older).sum(-1) - 1.0).max() < 1e-6
    assert np.asarray(weights).sum(-1).max() < 1.0


# -- the engine's two programs: cold, and behind a prefix hit -----------------

def _stores(cfg, slots, pages):
    """Empty pools (scales one), a predecessor's garbage in every slot's
    tail and in every page's."""
    pools, state, kept = engine_programs.store_shapes(
        cfg, max_batch=slots, num_pages=pages, page_size=PAGE,
        kv_dtype="bf16")
    pools = [(jnp.ones if i >= 2 else jnp.zeros)(a.shape, a.dtype)
             for i, a in enumerate(pools)]
    return (pools, [jnp.full(a.shape, 7.0, a.dtype) for a in state],
            [jnp.full(a.shape, -3.0, a.dtype) for a in kept])


class _Programs:
    """The two programs' bodies called as the engine binds them, their
    logits read where they are handed to ``select_tokens``."""

    def __init__(self, patch, cfg, params, slots=3, pages=40):
        self.cfg, self.params, self.slots = cfg, params, slots
        self.pools, self.state, self.kept = _stores(cfg, slots, pages)
        self.seen = []

        def spy(logits, temps, key):
            jax.debug.callback(lambda lg: self.seen.append(np.asarray(lg)),
                               logits, ordered=True)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        patch.setattr(engine_programs, "select_tokens", spy)

    def prefill(self, rows, tokens, starts, slots, bucket):
        """``tokens``: each row's whole prompt; the program gets the
        suffix past ``starts``, padded to ``bucket``. Returns the first
        tokens' logits [n, vocab]."""
        n = len(tokens)
        padded = np.zeros((n, bucket), np.int32)
        for i, (toks, start) in enumerate(zip(tokens, starts)):
            padded[i, :len(toks) - start] = toks[start:]
        self.pools, out, state = run(
            self.cfg, "prefill", self.params, self.pools, dict(
                table_rows=jnp.asarray(rows), tokens=jnp.asarray(padded),
                slens=jnp.array([len(t) - s for t, s in zip(tokens, starts)],
                                jnp.int32),
                starts=jnp.array(starts, jnp.int32),
                temps=jnp.zeros((n,), jnp.float32), key=jax.random.key(0),
                slots=jnp.array(slots, jnp.int32)),
            (*self.state, *self.kept), page=PAGE)
        self.state, self.kept = list(state[:1]), list(state[1:])
        jax.effects_barrier()
        return self.seen.pop(), np.asarray(out["firsts"])

    def decode(self, table, last, lengths, active, chunk=4):
        self.pools, out, state = run(
            self.cfg, "decode", self.params, self.pools, dict(
                table=jnp.asarray(table), tokens=jnp.asarray(last),
                lengths=jnp.asarray(lengths), active=jnp.asarray(active),
                temps=jnp.zeros((self.slots,), jnp.float32),
                key=jax.random.key(0)),
            self.state, page=PAGE, chunk=chunk)
        self.state = list(state)
        jax.effects_barrier()
        logits, self.seen = np.stack(self.seen), []
        return logits, out


@pytest.fixture(scope="module")
def served(tiny):
    """One prompt of 37 tokens (four whole pages and five tokens) served
    COLD into slot 1 and, from the pages that run filled, behind a hit of
    one page, of three, and of all four (all but the last, partial one)
    into slot 2, each followed by eight decoded tokens; slot 0 stays
    idle."""
    cfg, params = tiny
    rng = np.random.default_rng(21)
    prompt = rng.integers(1, cfg.vocab_size, 37)
    with pytest.MonkeyPatch.context() as patch:
        progs = _Programs(patch, cfg, params)
        table = np.full((3, 8), -1, np.int32)
        table[1] = np.arange(8)                 # the cold run's pages
        cold_first, tok = progs.prefill(table[[1]], [prompt], [0], [1], 64)
        kept_cold = [np.asarray(a) for a in progs.kept]
        tail_cold = np.asarray(progs.state[0])[:, 1]
        idle = np.asarray(progs.state[0])[:, 0]

        def decoded(slot, first):
            last = np.zeros((3,), np.int32)
            last[slot] = first
            lengths = np.zeros((3,), np.int32)
            lengths[slot] = len(prompt)
            active = np.arange(3) == slot
            out, toks = [], []
            for _ in range(2):
                lg, res = progs.decode(table, last, lengths, active)
                out.append(lg[:, slot])
                toks += [int(t) for t in np.asarray(res["toks"])[:, slot]]
                last, lengths = res["last"], res["lengths"]
            return np.concatenate(out), toks, res["stats"]

        cold_steps, cold_toks, stats = decoded(1, tok[0])
        hits = {}
        for k in (1, 3, 4):
            # the hit's table: the cold run's first k pages, then fresh
            table[2] = -1
            table[2, :k] = table[1, :k]
            table[2, k:8] = 8 * k + np.arange(8 - k)
            # (in the cold run's bucket, so the same program: one cut
            # to the suffix's own bucket sums its matmuls in another
            # order, a float32 rounding apart, which bf16 K and V rows
            # then round either way)
            first, tok2 = progs.prefill(table[[2]], [prompt], [k * PAGE],
                                        [2], 64)
            tail = np.asarray(progs.state[0])[:, 2]
            pages = [np.asarray(a) for a in progs.kept]
            steps, toks, _ = decoded(2, tok2[0])
            hits[k] = (first, tail, pages, steps, toks, table[2].copy())
        idle_after = np.asarray(progs.state[0])[:, 0]
    return dict(prompt=prompt, cold_first=cold_first, cold_steps=cold_steps,
                cold_toks=[int(tok[0])] + cold_toks, kept_cold=kept_cold,
                tail_cold=tail_cold, hits=hits, idle=(idle, idle_after),
                stats=stats, cold_table=table[1].copy())


def test_prefill_then_decode_is_the_references_forward_pass(tiny, served):
    """The cold prefill's logits and eight decode steps' are the rows of
    the reference's ONE forward pass over the prompt and the tokens the
    programs chose."""
    cfg, params = tiny
    prompt, toks = served["prompt"], served["cold_toks"]
    got = np.concatenate([served["cold_first"], served["cold_steps"]])
    seq = np.concatenate([prompt, toks[:-1]])[None]
    want = np.asarray(family.logits(CONFIG, params, seq))[0, len(prompt) - 1:]
    assert got.shape == want.shape == (9, cfg.vocab_size)
    np.testing.assert_allclose(got, want, rtol=PAGED_TOL, atol=PAGED_TOL)
    gap, _ = reference.token_gap(family.logits, CONFIG, params, prompt, toks)
    assert gap <= PAGED_TOL
    assert set(served["stats"]) == {"experts_touched",
                                    "expert_load_max_over_mean"}


@pytest.mark.parametrize("k", [1, 3, 4], ids=["one-page", "three-pages",
                                              "all-but-the-last"])
def test_a_hit_of_k_pages_is_the_cold_run_bit_for_bit(served, k):
    """A row that begins behind ``k`` reused pages, from the tail page
    ``k - 1`` keeps: the logits of its first token and of eight decode
    steps, the tail installed in its slot and the tails it wrote at the
    ends of the pages it completed are those of the same prompt served
    cold, bit for bit."""
    first, tail, pages, steps, toks, table = served["hits"][k]
    np.testing.assert_array_equal(first, served["cold_first"])
    np.testing.assert_array_equal(steps, served["cold_steps"])
    assert [int(np.argmax(first[0]))] + toks == served["cold_toks"]
    np.testing.assert_array_equal(tail, served["tail_cold"])
    cold = served["cold_table"]
    for (new,), (old,) in zip(zip(pages), zip(served["kept_cold"])):
        # the pages the suffix completed: 4 whole pages in the prompt
        for j in range(k, 4):
            np.testing.assert_array_equal(new[:, table[j]], old[:, cold[j]])
        # the reused pages' own are as the cold run wrote them, and a
        # page no prompt filled keeps the garbage it had
        np.testing.assert_array_equal(new[:, cold[:4]], old[:, cold[:4]])
        assert (new[:, table[4]] == -3.0).all()
        assert (new[:, 39] == -3.0).all()


def test_the_tail_is_kept_as_stated_and_an_idle_slots_is_left(tiny, served):
    """The tail a prefill installs and a page keeps is float32 where the
    model says float32: the last two rows of ``g = B * u`` of layer 0,
    computed here from the weights, to float32's accuracy (a bf16 tail
    would miss by 4e-3). The idle slot's tail is the garbage it had, bit
    for bit, after three prefills and eight decode chunks."""
    cfg, params = tiny
    p = jax.tree.map(lambda a: np.asarray(a[0], np.float64),
                     params["blocks"]["layers0-1"])
    x = np.asarray(params["embedding"], np.float64)[served["prompt"]]
    h = x / np.sqrt((x * x).mean(-1, keepdims=True) + cfg.rms_eps) * p["norm"]
    b, _, u = np.split(h @ p["in_proj"], 3, axis=-1)
    g = b * u
    kept = served["kept_cold"][0]
    assert kept.dtype == np.float32
    for j in range(4):              # page j ends behind token 8 j + 7
        np.testing.assert_allclose(
            kept[0, served["cold_table"][j]], g[8 * j + 6:8 * j + 8],
            rtol=2e-5, atol=2e-6)
    before, after = served["idle"]
    np.testing.assert_array_equal(before, after)
    assert (after == 7.0).all()


# -- 64-wide heads, two a row of the pools ------------------------------------

def _paged(seed, nkv, heads, slots, table_pages, page, lengths):
    """Random q, and K/V pools of ``nkv`` heads of 64 filled through the
    table up to each slot's length, as the module states them."""
    rng = np.random.default_rng(seed)
    pool_pages = slots * table_pages
    k = rng.standard_normal((2, pool_pages, page, nkv, 64), np.float32)
    v = rng.standard_normal((2, pool_pages, page, nkv, 64), np.float32)
    table = rng.permutation(pool_pages).reshape(slots, table_pages)
    return (jnp.asarray(k, jnp.bfloat16), jnp.asarray(v, jnp.bfloat16),
            jnp.asarray(table, jnp.int32), jnp.asarray(lengths, jnp.int32))


def test_heads_of_64_lie_two_a_row_and_attend_as_the_plain_formulation():
    """The decode kernel (interpret mode) and the gather formulation over
    the pool of two heads a row, queries laid out by ``rows_of_heads``
    and their own parts taken back, against the gather formulation over
    the pool as the model states it, [.., 4, 64]: the same attention (the
    packed one rounds q once more, by ``sqrt(2)``)."""
    nkv, heads, page = 4, 8, 16
    assert pa.heads_per_row(nkv, 64) == 2 and pa.pool_heads(nkv, 64) == (2, 128)
    assert pa.heads_per_row(8, 128) == 1 and pa.pool_heads(8, 128) == (8, 128)
    assert pa.heads_per_row(3, 64) == 1     # an odd count stays as it is
    k, v, table, lengths = _paged(0, nkv, heads, 3, 4, page, [37, 0, 64])
    q = jnp.asarray(np.random.default_rng(1).standard_normal(
        (3, heads, 64), np.float32), jnp.bfloat16)
    active = lengths > 0
    pos = jnp.maximum(lengths - 1, 0)
    ones = jnp.ones((2, 1, 1, 1), jnp.float32)
    layer = jnp.int32(1)
    want = pda.paged_decode_attention_reference(
        q, k, v, ones, ones, layer, table, pos, active)
    q2, k2, v2 = pa.rows_of_heads(q, k, v)
    assert q2.shape == (3, heads, 128) and k2.shape == (2, 12, page, 2, 128)
    # a row of the packed pool IS the two heads side by side
    np.testing.assert_array_equal(
        np.asarray(k2).reshape(k.shape), np.asarray(k))
    for attend in (pda.paged_decode_attention_reference,
                   jax.jit(lambda *a: pda.paged_decode_attention_kernel(
                       *a, interpret=True))):
        got = pa.own_parts(attend(q2, k2, v2, ones, ones, layer, table, pos,
                                  active), nkv, 64)
        assert got.shape == want.shape
        live = np.asarray(active)
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[live],
            np.asarray(want, np.float32)[live], rtol=2e-2, atol=2e-2)
    # a head that fills its row passes through untouched (nothing traced)
    wide = jnp.zeros((3, 8, 128))
    assert pa.rows_of_heads(wide, wide, wide) == (wide, wide, wide)
    assert pa.own_parts(wide, 8, 128) is wide


def test_the_prefill_kernel_reads_the_rows_of_two_heads(monkeypatch):
    """The prefill kernel (interpret mode) over the packed pool against
    the gather formulation over the pool as the model states it: two
    rows, one cold and one behind a cached page."""
    nkv, heads, page, t = 4, 8, 16, 32
    k, v, table, _ = _paged(2, nkv, heads, 2, 4, page, [0, 0])
    q = jnp.asarray(np.random.default_rng(3).standard_normal(
        (2, t, heads, 64), np.float32), jnp.bfloat16)
    starts = jnp.array([0, page], jnp.int32)
    slens = jnp.array([t, 19], jnp.int32)
    ones = jnp.ones((2, 1, 1, 1), jnp.float32)
    layer = jnp.int32(0)
    want = ppa.paged_prefill_attention_reference(
        q, k, v, ones, ones, layer, table, starts)
    q2, k2, v2 = pa.rows_of_heads(q, k, v)
    got = pa.own_parts(jax.jit(
        lambda *a: ppa.paged_prefill_attention_kernel(*a, interpret=True))(
            q2, k2, v2, ones, ones, layer, table, starts, slens), nkv, 64)
    for row, n in enumerate([t, 19]):
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[row, :n],
            np.asarray(want, np.float32)[row, :n], rtol=2e-2, atol=2e-2)


# -- the engine end to end ----------------------------------------------------

@pytest.fixture(scope="module")
def engine_run(tiny):
    """Through the engine's own loop, with a pool of twelve pages: a
    context of 29 tokens asked three times (a cold prompt, then two hits
    of three pages), then two other contexts that evict the first's idle
    pages and recycle them, then the first asking again: cold once more,
    over recycled pages."""
    cfg, params = tiny
    rng = np.random.default_rng(0)
    doc = rng.integers(1, cfg.vocab_size, 29)
    asks = [np.concatenate([doc, rng.integers(1, cfg.vocab_size, n)])
            for n in (4, 9, 6)]
    others = [rng.integers(1, cfg.vocab_size, n) for n in (41, 43)]
    was = tracing.is_enabled()
    tracing.enable_tracing()
    try:
        eng = PagedLLMEngine(cfg, params, max_batch=2, max_len=64,
                             page_size=PAGE, num_pages=12, decode_chunk=4)
        eng.start()
        answers = [list(eng.submit(p, max_new_tokens=7).tokens())
                   for p in asks]
        warm = dict(eng.stats())
        for p in others:
            list(eng.submit(p, max_new_tokens=7).tokens())
        again = list(eng.submit(asks[1], max_new_tokens=7).tokens())
        stats = eng.stats()
        eng.stop()
        cold = PagedLLMEngine(cfg, params, max_batch=2, max_len=64,
                              page_size=PAGE, num_pages=12, decode_chunk=4,
                              prefix_cache=False)
        cold.start()
        plain = [list(cold.submit(p, max_new_tokens=7).tokens())
                 for p in asks]
        cold.stop()
    finally:
        if not was:
            tracing.disable_tracing()
    assert eng.error is None and cold.error is None
    return dict(asks=asks, answers=answers, again=again, plain=plain,
                warm=warm, stats=stats, cold_stats=cold.stats())


def test_the_engine_reuses_a_prefix_over_the_recurrent_plan(tiny,
                                                            engine_run):
    cfg, params = tiny
    r = engine_run
    # the cache is on by the flag: the plan says its pages keep the state
    assert r["stats"]["prefix_cache"]["enabled"] is True
    assert r["cold_stats"]["prefix_cache"]["enabled"] is False
    # a hit yields the cold engine's tokens, which are the reference's
    assert r["answers"] == r["plain"]
    for prompt, tokens in zip(r["asks"], r["answers"]):
        gap, _ = reference.token_gap(family.logits, CONFIG, params, prompt,
                                     tokens)
        assert gap <= PAGED_TOL
    warm = r["warm"]
    assert warm["prefix_cache"]["hit_pages"] == 6       # two hits of three
    assert warm["state_restores"] == 2 and warm["state_installs"] == 3
    # the cold prompt completed four pages (33 tokens); the hits' suffixes
    # of 14 and 11 tokens one each
    assert warm["state_snapshot_pages"] == 4 + 1 + 1
    assert r["cold_stats"]["state_restores"] == 0
    assert r["cold_stats"]["state_snapshot_pages"] == 3 * 4
    # what the plan's layers hold: a page's bytes include its tails
    assert warm["page_layers"] == "k+v=1" and warm["state_layers"] == 5
    assert warm["state_slot_bytes"] == 5 * 2 * 256 * 4
    assert warm["state_page_bytes"] == 5 * 2 * 256 * 4
    assert warm["page_bytes"] == 2 * PAGE * 2 * 64 * 2 + 5 * 2 * 256 * 4


def test_a_page_evicted_and_recycled_is_rewritten_before_any_lookup(
        engine_run):
    """Two other contexts took the first's idle pages (twelve pages, six
    a request): its asking again misses, prefills cold over recycled
    pages whose tails it rewrites, and yields the same tokens."""
    r = engine_run
    hits = r["stats"]["prefix_cache"]["hit_pages"]
    assert hits == r["warm"]["prefix_cache"]["hit_pages"]   # no hit since
    assert r["again"] == r["answers"][1] == r["plain"][1]
    assert r["stats"]["state_restores"] == 2


def test_the_spans_say_what_was_restored_and_written(engine_run):
    spans = [s["attrs"] for s in tracing.recorded_spans(
        "engine.dispatch_prefill") if "state_restores" in s["attrs"]]
    assert spans and {"state_snapshot_pages", "group"} <= set(spans[0])
    assert sum(a["state_restores"] for a in spans) >= 2
    built = [s["attrs"] for s in tracing.recorded_spans("engine.construct")
             if s["attrs"].get("state_page_bytes")]
    assert built and built[-1]["state_page_bytes"] == 5 * 2 * 256 * 4


def test_a_plan_whose_pages_keep_no_state_still_refuses_the_cache(tiny):
    """The rule stands for a state the pages do not keep: the same model
    with the statement off is refused, and follows the flag to off."""
    cfg, params = tiny
    state = lfm2_moe.recurrent_state(cfg)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(lfm2_moe, "recurrent_state",
                      lambda cfg: state._replace(pages_keep=False))
        with pytest.raises(ValueError, match="pages do not keep"):
            PagedLLMEngine(cfg, params, prefix_cache=True)
        eng = PagedLLMEngine(cfg, params, max_batch=2, max_len=64,
                             page_size=PAGE)
        assert eng.stats()["prefix_cache"]["enabled"] is False
        assert eng._programs.kept == ()

"""dots3-note's block (``models/dots3_note.py``), what it forced in the ops
(latent rows in the page pools and the two forms of attention over them,
the indexer's selection, ``ops/latent_attention.py``; a sigmoid scoring
and a choice bias in ``moe_ffn_dropless``) and the model through the paged
engine's normal path, at a small size on the CPU in float32: the leading
dense full layer and one period (full, sliding x 3), hidden 64, latents of
16 and 32 under 4 and 2 heads, a selection of 12 keys, a window of 9,
8 experts of 32 of which 2 are held, 3 a token, vocabulary 128. The plain
reference is the benchmark's family file, the one statement of it
(``benchmark/families/dots3_note.py:logits``), which imports nothing from
the program; it is itself held to ``transformers``' ``DeepseekV3`` (latent
attention, the sigmoid router with its correction bias in one group) and
``LongcatFlashMLA`` (the rescale) on copied weights."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference
from benchmark.families import dots3_note as family
from ray_tpu.models import dots3_note
from ray_tpu.ops import index_select
from ray_tpu.ops import latent_attention as la
from ray_tpu.ops import moe
from ray_tpu.ops.paged_attention import PageRow, row_pool
from ray_tpu.serve.paged_llm import PagedLLMEngine
from ray_tpu.util import tracing

# Float32 against float32: the program and the reference differ in the
# order of their sums and in rsqrt against 1/sqrt; over five layers with
# logits of order 1 that is 4e-6 (measured here). 1e-4 is twenty-five
# times that, and thousands of times under what a wrong block shows (each
# departure of the reference moves the logits by 0.5 to 4). Through the
# engine the comparison is of tokens, as the benchmark's.
TOL = 1e-4
GAP_TOL = 0.1
PAGE, WINDOW, TOPK = 8, 9, 12
KINDS = ["full_attention", "full_attention"] + ["sliding_attention"] * 3
CONFIG = {
    "vocab_size": 128, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 5, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 8e7, "index_n_heads": 2, "index_head_dim": 16,
    "index_topk": TOPK, "swa_num_attention_heads": 2,
    "swa_num_key_value_heads": 2, "swa_q_lora_rank": 32,
    "swa_kv_lora_rank": 32, "swa_qk_nope_head_dim": 24,
    "swa_qk_rope_head_dim": 8, "swa_v_head_dim": 16,
    "swa_rope_theta": 50000, "sliding_window_size": WINDOW,
    "layer_types": KINDS, "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "moe_intermediate_size": 32, "n_routed_experts": 2,
    "n_shared_experts": 1, "num_experts_per_tok": 3, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "apply_mla_qkv_lora_rescale": True,
    "attention_gate_type": "headwise", "swa_attention_gate_type": "headwise",
    "torch_dtype": "float32",
    "expert_share": {"chips": 4, "index": 0, "num_experts_total": 8}}
DEPARTURES = [{"rescale": False}, {"gate": "none"}, {"window": None},
              {"indexer": "none"}, {"topk": TOPK // 2},
              {"scores": "softmax"}, {"bias": False}]
IDS = ["rescale", "gate", "window", "indexer", "topk", "scores", "bias"]


def clear_ring():
    tracing.drain_spans(1 << 20)
    _, flight = tracing._rings()
    flight.clear()


def make_params(cfg, seed=0):
    return dots3_note.init_params(cfg, jax.random.key(seed))


@pytest.fixture(scope="module")
def tiny():
    cfg = family.model_config(CONFIG)
    assert cfg == dots3_note.dots3_note_tiny()
    return cfg, make_params(cfg)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.key(1), (2, 40), 0, 128)


# -- the block against the reference ------------------------------------------

def test_forward_is_the_references_logits_past_the_selection_and_the_window(
        tiny, tokens):
    """40 positions: the full layers drop keys from position 12 on, the
    sliding ones from position 9."""
    cfg, params = tiny
    want = family.logits(CONFIG, params, tokens)
    got = dots3_note.forward(cfg, params, tokens)
    assert got.shape == (2, 40, 128) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=TOL)


def test_the_absorbed_form_gives_the_expanded_forms_numbers(tiny, tokens):
    """One query at a time against the rows as they lie (what decode
    runs) and every query against the expanded keys and values (what
    prefill and the reference run), selection, window and all."""
    cfg, params = tiny
    np.testing.assert_allclose(
        dots3_note.forward(cfg, params, tokens, absorbed=True),
        dots3_note.forward(cfg, params, tokens), atol=TOL)


@pytest.mark.parametrize("departure", DEPARTURES, ids=IDS)
def test_each_assumed_reading_shows_in_the_logits(tiny, tokens, departure):
    """What ``config.json`` names and does not define: the other reading
    of each moves the logits far outside the tolerance the program is
    held to, so the comparison sees the mechanism."""
    _, params = tiny
    want = family.logits(CONFIG, params, tokens)
    other = family.logits(CONFIG, params, tokens, **departure)
    assert float(jnp.max(jnp.abs(other - want))) > 1000 * TOL


def test_the_plan_states_each_runs_page_rows():
    plan = dots3_note.layer_plan(dots3_note.dots3_note_prev())
    assert [run.layers for run in plan][:4] == [1, 1, 3, 1]
    assert sum(run.layers for run in plan) == 46
    full, sliding = plan[1], plan[2]
    assert (full.kind, full.window, full.rows) == ("full", None, (
        PageRow("latent", 576, "bfloat16"),
        PageRow("index_key", 128, "bfloat16")))
    assert (sliding.kind, sliding.window, sliding.rows) == (
        "sliding", 513, (PageRow("latent", 1088, "bfloat16"),))
    kinds = dots3_note.dots3_note_prev().layer_types
    assert kinds.count("full_attention") == 13 and len(kinds) == 46


# -- the shares ---------------------------------------------------------------

def test_the_shares_parts_add_up_to_the_uncut_layer():
    """One sparse layer's feed-forward with all 8 experts held, against
    the four shares of 2: each share computes its experts' part plus the
    shared expert; summed with the shared expert counted once they are
    the uncut layer."""
    whole = dots3_note.dots3_note_tiny(n_experts_held=8)
    params = make_params(whole)
    p = jax.tree.map(lambda a: a[0], params["blocks"]["layers1"])
    x = jax.random.normal(jax.random.key(3), (2, 12, 64), jnp.float32)
    want, _ = dots3_note.feed_forward(whole, p, x)
    h = dots3_note.rms_norm(x, p["mlp_norm"], eps=whole.rms_eps)
    shared = (jax.nn.silu(h @ p["ws_gate"]) * (h @ p["ws_up"])) @ p["ws_down"]
    total = x + shared
    for share in range(4):
        cut = dataclasses.replace(whole, n_experts_held=2,
                                  first_expert=2 * share)
        held = {k: v[2 * share:2 * share + 2] if k in (
            "wi_gate", "wi_up", "wo_e") else v for k, v in p.items()}
        part, stats = dots3_note.feed_forward(cut, held, x)
        total = total + (part - x - shared)
        assert 0.0 <= float(stats["routed_here_share"]) <= 1.0
    np.testing.assert_allclose(total, want, atol=1e-5)


@pytest.mark.parametrize(
    "expert_formulation", ["every-held-expert", "sorted-loop",
                           "sorted-kernel"], indirect=True)
def test_sigmoid_scoring_and_the_choice_bias_in_the_dropless_op(
        expert_formulation):
    """The op's new arguments against the arithmetic written out, in
    every formulation: the choice by score + bias, the weights the scores
    alone, renormalised over the chosen with 1e-20 under the line."""
    k = jax.random.split(jax.random.key(4), 6)
    t, d, e, f, top = 24, 16, 8, 12, 3
    x = jax.random.normal(k[0], (t, d))
    router = jax.random.normal(k[1], (d, e))
    bias = 0.5 * jax.random.normal(k[2], (e,))
    wg, wu = (jax.random.normal(kk, (e, d, f)) / 4 for kk in k[3:5])
    wo = jax.random.normal(k[5], (e, f, d)) / 4
    out, load = moe.moe_ffn_dropless(
        x, router, wg, wu, wo, top_k=top, norm_topk_prob=True,
        scoring="sigmoid", choice_bias=bias)
    s = jax.nn.sigmoid(x @ router)
    chosen = jnp.argsort(-(s + bias), axis=-1)[:, :top]
    assert bool(jnp.any(chosen != jnp.argsort(-s, axis=-1)[:, :top]))
    want = jnp.zeros((t, d))
    for j in range(top):
        idx = chosen[:, j]
        w = (jnp.take_along_axis(s, idx[:, None], 1)[:, 0]
             / (jnp.take_along_axis(s, chosen, 1).sum(-1) + 1e-20))
        y = jnp.einsum("tf,tfd->td", jax.nn.silu(jnp.einsum(
            "td,tdf->tf", x, wg[idx])) * jnp.einsum("td,tdf->tf", x, wu[idx]),
            wo[idx])
        want = want + w[:, None] * y
    np.testing.assert_allclose(out, want, atol=1e-5)
    assert int(load.sum()) == t * top
    with pytest.raises(ValueError):
        moe.moe_ffn_dropless(x, router, wg, wu, wo, top_k=top,
                             scoring="tanh")


# -- the ops over paged rows ----------------------------------------------------

def _inputs(rng, b, s, *, heads=4, dn=16, dr=8, r=16, dv=16, hi=2, di=16,
            topk=None):
    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    index = None if topk is None else la.IndexInputs(
        normal(b, s, hi, di), normal(b, s, hi), normal(b, s, di), topk)
    return la.LatentInputs(normal(b, s, heads, dn + dr), normal(b, s, r + dr),
                           normal(r, heads, dn + dv) / 4, 0.2, index)


def _plain(inputs, window=None):
    """The expanded form over each sequence's own rows, written out."""
    q, row, wkv = inputs.q, inputs.row, inputs.wkv_b
    r, dn = wkv.shape[0], wkv.shape[2] - 16
    kv = jnp.einsum("bsr,rhe->bshe", row[..., :r], wkv)
    att = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :dn], kv[..., :dn])
           + jnp.einsum("bqhd,bkd->bhqk", q[..., dn:], row[..., r:])
           ) * inputs.scale
    s = q.shape[1]
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = jnp.broadcast_to(j <= i, (q.shape[0], s, s))
    if window is not None:
        seen = seen & (j > i - window)
    if inputs.index is not None:
        scores = la.index_scores(inputs.index.q, inputs.index.weights,
                                 inputs.index.key)
        seen = family._selected(scores, seen, inputs.index.topk)
    att = jax.nn.softmax(jnp.where(seen[:, None], att, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", att, kv[..., dn:])


@pytest.mark.parametrize("window,topk", [(None, None), (9, None), (None, 12)],
                         ids=["full", "window", "selected"])
def test_prefill_then_decode_then_a_reused_prefix_over_scattered_pages(
        window, topk, monkeypatch):
    """Two sequences of 40 tokens whose pages lie scattered in a pool of
    30: a prefill of the first 24 (in blocks of queries), then the next
    token by token in the absorbed form, then the last 12 as a SUFFIX
    prefill behind the 28 rows already there: every output is the plain
    attention's over the sequence's own rows."""
    rng = np.random.default_rng(5)
    b, s, pool_pages = 2, 40, 30
    inputs = _inputs(rng, b, s, topk=topk)
    want = _plain(inputs, window)
    rows = (PageRow("latent", 24, "float32"),) + (
        (PageRow("index_key", 16, "float32"),) if topk else ())
    pools = tuple(row_pool(2, pool_pages, PAGE, row) for row in rows)
    table = jnp.asarray(rng.permutation(pool_pages)[:b * 5].reshape(b, 5),
                        jnp.int32)
    layer = jnp.int32(1)

    def part(lo, hi):
        def cut(a):
            return a[:, lo:hi]
        index = inputs.index and inputs.index._replace(
            q=cut(inputs.index.q), weights=cut(inputs.index.weights),
            key=cut(inputs.index.key))
        return inputs._replace(q=cut(inputs.q), row=cut(inputs.row),
                               index=index)

    def write(pools, lo, hi):
        pos = jnp.broadcast_to(jnp.arange(lo, hi), (b, hi - lo))
        return la.write_latent(
            part(lo, hi), pools, layer,
            jnp.take_along_axis(table, pos // PAGE, axis=1), pos % PAGE)

    monkeypatch.setattr(index_select, "SCORES_MAX_BYTES", 4 * b * 4 * 8 * 40)
    assert la.query_block(b, 24, 4, 40, window) == 12      # two blocks
    pools = write(pools, 0, 24)
    got = la.latent_prefill_attention(
        part(0, 24), pools, layer, table, jnp.zeros((b,), jnp.int32),
        jnp.full((b,), 24, jnp.int32), window=window)
    np.testing.assert_allclose(got, want[:, :24], atol=1e-5)
    for t in range(24, 28):
        pos = jnp.full((b,), t, jnp.int32)
        pools = la.write_latent(
            part(t, t + 1), pools, layer,
            jnp.take_along_axis(table, pos[:, None] // PAGE, 1)[:, 0],
            pos % PAGE)
        got = la.latent_decode_attention(part(t, t + 1), pools, layer, table,
                                         pos, window=window)
        np.testing.assert_allclose(got, want[:, t], atol=1e-5)
    pools = write(pools, 28, 40)
    got = la.latent_prefill_attention(
        part(28, 40), pools, layer, table, jnp.full((b,), 28, jnp.int32),
        jnp.full((b,), 12, jnp.int32), window=window)
    np.testing.assert_allclose(got, want[:, 28:], atol=1e-5)
    # a suffix whose PADDING runs past its table (24 padded queries from
    # position 28 over 40 keys): no block may lose a key it can see
    padded = part(28, 40)
    padded = jax.tree.map(
        lambda a: jnp.pad(a, [(0, 0), (0, 12)] + [(0, 0)] * (a.ndim - 2))
        if hasattr(a, "ndim") and a.ndim >= 3 and a.shape[1] == 12 else a,
        padded)
    got = la.latent_prefill_attention(
        padded, pools, layer, table, jnp.full((b,), 28, jnp.int32),
        jnp.full((b,), 12, jnp.int32), window=window)
    np.testing.assert_allclose(got[:, :12], want[:, 28:], atol=1e-5)
    # nothing was written to the other layer of the pools
    assert all(float(jnp.abs(pool[0]).max()) == 0.0 for pool in pools)


def test_ties_in_the_indexers_scores_go_to_the_lower_position():
    """Two index heads give exact zeros (both products negative) often
    enough that the twelfth and the thirteenth key tie: the prefill's
    mask, the decode's top-k and the reference's sort agree on which."""
    rng = np.random.default_rng(7)
    inputs = _inputs(rng, 1, 32, topk=12)
    scores = la.index_scores(inputs.index.q, inputs.index.weights,
                             inputs.index.key)
    seen = jnp.tril(jnp.ones((32, 32), bool))[None]
    kth = jnp.sort(jnp.where(seen, scores, -jnp.inf), -1)[..., -12]
    assert int(jnp.sum((scores == kth[..., None]) & seen & (
        kth[..., None] == 0.0))) > 0      # the case is met
    pools = tuple(row_pool(1, 1, 32, row) for row in (
        PageRow("latent", 24, "float32"), PageRow("index_key", 16,
                                                  "float32")))
    table = jnp.zeros((1, 1), jnp.int32)
    pools = la.write_latent(inputs, pools, 0, jnp.zeros((1, 32), jnp.int32),
                            jnp.arange(32)[None])
    np.testing.assert_allclose(
        la.latent_prefill_attention(inputs, pools, 0, table,
                                    jnp.zeros((1,), jnp.int32),
                                    jnp.full((1,), 32, jnp.int32)),
        _plain(inputs), atol=1e-5)


def test_query_blocks_keep_the_scores_under_the_limit():
    assert la.query_block(1, 4096, 128 + 64, 4096, None) == 256
    assert 4 * 192 * 256 * 4096 <= index_select.SCORES_MAX_BYTES
    assert la.query_block(1, 4096, 64, 4096, 513) == 1024
    assert la.query_block(2, 32, 6, 32, None) == 32


# -- through the engine ---------------------------------------------------------

def test_engine_serves_past_the_selection_and_the_window_and_reuses_pages(
        tiny):
    """Through ``submit`` -> admission -> the two programs: a prompt of 50
    tokens (past the selection's 12 keys and the window's 9), then a
    second that shares its first 32, so that four of its pages are reused;
    12 tokens each, decoded in the absorbed form over the pools. Every
    greedy token within the benchmark's gap of the reference's best, no
    K/V twin exists, and the spans carry the new counters, equal to
    ``stats()``'s."""
    cfg, params = tiny
    rng = np.random.default_rng(2)
    first = rng.integers(1, 128, 50, dtype=np.int32)
    second = np.concatenate([first[:32], rng.integers(1, 128, 19,
                                                      dtype=np.int32)])
    clear_ring()
    tracing.enable_tracing()
    try:
        eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=2,
                             max_len=128, page_size=PAGE, num_pages=40)
        # a full run's rows and index keys over 2 layers, a sliding run's
        # rows over 3, each in whole lanes; no V twin
        assert [a.shape for a in eng._programs.pools] == [
            (2, 40, PAGE, 128), (2, 40, PAGE, 128), (3, 40, PAGE, 128)]
        eng.start()
        served = []
        for prompt in (first, second):
            req = eng.submit(prompt, max_new_tokens=12)
            served.append((prompt, list(req.tokens())))
        stats = eng.stats()
        eng.stop()
        spans = tracing.recorded_spans("engine.")
    finally:
        tracing.disable_tracing()
        clear_ring()     # the ring is the process's: leave no span behind
    assert eng.error is None and stats["prefix_cache"]["hit_pages"] == 4
    for prompt, toks in served:
        assert len(toks) == 12
        gap, _ = reference.token_gap(family.logits, CONFIG, params, prompt,
                                     toks)
        assert gap <= GAP_TOL
    # float32 rows in whole lanes: 5 pools' layers x 128 lanes x 4 bytes
    assert stats["cache_bytes_per_token"] == 7 * 128 * 4
    assert stats["kv_pages_bytes"] == 7 * 128 * 4 * 40 * PAGE
    assert stats["kv_dense_equiv_bytes"] == 2 * 128 * 7 * 128 * 2
    decode = [s["attrs"] for s in spans
              if s["name"] == "engine.dispatch_decode"]
    assert decode and all(
        a["kv_rows_selected"] == TOPK * a["live"] < a["index_rows"]
        == a["kv_rows_full"] and a["kv_rows_window"] == WINDOW * a["live"]
        for a in decode)
    prefill = [s["attrs"] for s in spans
               if s["name"] == "engine.dispatch_prefill"]
    assert prefill and all(
        a["page_rows"] == "latent:24,index_key:16;latent:40"
        and a["attn_kernel"] == 0 for a in prefill)
    emits = [s["attrs"] for s in spans if s["name"] == "engine.emit"
             and "routed_here_share" in s["attrs"]]
    assert emits and all(0.0 <= a["routed_here_share"] <= 1.0
                         and a["experts_touched"] <= 2.0 for a in emits)


def test_a_slots_new_tenant_never_reads_its_predecessors_rows(tiny):
    """One slot, one prompt served, then an unrelated prompt through the
    same slot and (the pool being two requests wide) recycled pages: the
    second's tokens are what an engine that never saw the first serves."""
    cfg, params = tiny
    rng = np.random.default_rng(8)
    old = rng.integers(1, 128, 60, dtype=np.int32)
    new = rng.integers(1, 128, 45, dtype=np.int32)

    def serve(prompts):
        eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=1,
                             max_len=128, page_size=PAGE, num_pages=12,
                             prefix_cache=False)
        eng.start()
        out = [list(eng.submit(p, max_new_tokens=10).tokens())
               for p in prompts]
        eng.stop()
        assert eng.error is None
        return out

    assert serve([old, new])[1] == serve([new])[0]


def test_a_handed_over_slots_rows_are_its_new_tenants(tiny):
    """One slot, two prompts waiting before the loop starts: the first's
    end is foreseen, and the second's prefill is dispatched behind the
    first's last chunk, into the slot and (the pool being two requests
    wide) pages the first has just given up, before the first's last
    tokens are read. Both streams are what an engine gives that serves
    the two in a slot each: latent rows, index keys and the selection
    over them are each tenant's own."""
    cfg, params = tiny
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 128, n, dtype=np.int32) for n in (60, 45)]
    budgets = [7, 6]

    def serve(slots):
        eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=slots,
                             max_len=128, page_size=PAGE, num_pages=12,
                             prefix_cache=False, decode_chunk=4)
        reqs = [eng.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, budgets)]
        eng.start()
        out = [list(r.tokens()) for r in reqs]
        stats = eng.stats()
        eng.stop()
        assert eng.error is None
        return out, stats["slots_handed_over"]

    one_slot, handed = serve(1)
    assert handed == 1 and [len(o) for o in one_slot] == budgets
    # a slot each: nothing is handed over, nothing shared
    assert (one_slot, 0) == serve(2)


def test_int8_pages_are_refused_over_a_plan_of_rows(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="int8"):
        PagedLLMEngine(cfg=cfg, params=params, max_batch=1, max_len=64,
                       page_size=PAGE, num_pages=8, kv_dtype="int8")


# -- the reference against transformers ---------------------------------------

def _hf_rope(dim, base, s):
    import torch

    inv = 1.0 / base ** (torch.arange(0, dim, 2, dtype=torch.float32) / dim)
    freqs = torch.arange(s, dtype=torch.float32)[:, None] * inv[None]
    emb = torch.cat([freqs, freqs], -1)[None]
    return emb.cos(), emb.sin()


def _hf_mla(cls, hf_config, p, shape, interleaved):
    """``transformers``' latent attention with this layer's weights copied
    in (a layer of the tiny stack, ``w_in``'s columns split as the family
    file says); with ``interleaved`` the rotary columns are laid out as
    that module rotates them (pairs (2i, 2i + 1), where the repo rotates
    (i, i + r/2): a permutation of columns)."""
    import torch

    heads, rq, r, dn, dr, dv, _ = shape
    t = lambda a: torch.tensor(np.asarray(a, np.float32).T)   # noqa: E731
    perm = (np.arange(dr).reshape(2, dr // 2).T.reshape(-1) if interleaved
            else np.arange(dr))
    w_in = np.asarray(p["w_in"], np.float32)
    wq_b = np.asarray(p["wq_b"], np.float32).reshape(rq, heads, dn + dr)
    wq_b = np.concatenate([wq_b[..., :dn], wq_b[..., dn:][..., perm]], -1)
    kv_a = np.concatenate([w_in[:, rq:rq + r],
                           w_in[:, rq + r:rq + r + dr][:, perm]], -1)
    attn = cls(hf_config, layer_idx=0)
    with torch.no_grad():
        attn.q_a_proj.weight.copy_(t(w_in[:, :rq]))
        attn.q_a_layernorm.weight.copy_(t(p["q_norm"]))
        attn.q_b_proj.weight.copy_(t(wq_b.reshape(rq, -1)))
        attn.kv_a_proj_with_mqa.weight.copy_(t(kv_a))
        attn.kv_a_layernorm.weight.copy_(t(p["kv_norm"]))
        attn.kv_b_proj.weight.copy_(t(p["wkv_b"]))
        attn.o_proj.weight.copy_(t(p["wo"]))
    return attn


@pytest.mark.parametrize("which", ["deepseek_v3", "longcat_flash"])
def test_the_references_latent_attention_is_transformers(tiny, which):
    """The reference's attention sublayer with the gate, the window and
    the selection off, a sliding layer's shape (24 + 8 a head for scores,
    16 for values, latents of 32): against ``DeepseekV3Attention`` with
    ``rescale=False`` (rotate-half rotary) and against ``LongcatFlashMLA``
    with the rescale (its rotary interleaved: the columns permuted)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    _, params = tiny
    p = jax.tree.map(lambda a: a[1], params["blocks"]["layers2-4"])
    shape = family._shape(CONFIG, True)
    heads, rq, r, dn, dr, dv, base = shape
    kw = dict(hidden_size=64, num_attention_heads=heads,
              num_key_value_heads=heads, q_lora_rank=rq, kv_lora_rank=r,
              qk_nope_head_dim=dn, qk_rope_head_dim=dr, v_head_dim=dv,
              rope_theta=base, rms_norm_eps=1e-5, attention_bias=False,
              attention_dropout=0.0, rope_scaling=None)
    if which == "deepseek_v3":
        from transformers.models.deepseek_v3 import modeling_deepseek_v3 as m
        hf_config = transformers.DeepseekV3Config(rope_interleave=False,
                                                  **kw)
        cls, rescale, interleaved = m.DeepseekV3Attention, False, False
    else:
        from transformers.models.longcat_flash import (
            modeling_longcat_flash as m)
        hf_config = transformers.LongcatFlashConfig(**kw)
        cls, rescale, interleaved = m.LongcatFlashMLA, True, True
    hf_config._attn_implementation = "eager"
    attn = _hf_mla(cls, hf_config, p, shape, interleaved)
    s = 24
    u = np.asarray(jax.random.normal(jax.random.key(9), (1, s, 64)),
                   np.float32)
    causal = torch.full((s, s), float("-inf")).triu(1)[None, None]
    with torch.no_grad():
        want = attn(torch.tensor(u), _hf_rope(dr, base, s), causal)[0]
    got = family._attention(
        jnp.asarray(u[0]), p, jnp.arange(s), shape=shape, eps=1e-5,
        rescale=rescale, gate="none", window=None, index=None
    ) @ p["wo"].astype(jnp.float32)
    # outputs of order 1 through a softmax whose scores spread over +-15:
    # torch and XLA sum in another order, 5e-5 at the worst found; a
    # wrong rotation, scale or split is off by order 1
    np.testing.assert_allclose(got, want[0].numpy(), atol=3e-4)


def test_the_references_router_is_transformers_in_one_group(tiny):
    """Sigmoid scores, the choice by score + ``e_score_correction_bias``,
    the chosen scores over their sum, the experts and the shared expert:
    ``DeepseekV3MoE`` with ``n_group`` 1 on copied weights, all 8 experts
    held."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from transformers.models.deepseek_v3 import modeling_deepseek_v3 as m

    whole = dots3_note.dots3_note_tiny(n_experts_held=8)
    p = jax.tree.map(lambda a: a[0],
                     make_params(whole, 5)["blocks"]["layers1"])
    hf_config = transformers.DeepseekV3Config(
        hidden_size=64, moe_intermediate_size=32, n_routed_experts=8,
        n_shared_experts=1, num_experts_per_tok=3, n_group=1, topk_group=1,
        norm_topk_prob=True, routed_scaling_factor=1.0, hidden_act="silu")
    layer = m.DeepseekV3MoE(hf_config)
    t = lambda a: torch.tensor(np.asarray(a, np.float32).T)   # noqa: E731
    with torch.no_grad():
        layer.gate.weight.copy_(t(p["router"]))
        layer.gate.e_score_correction_bias.copy_(
            torch.tensor(np.asarray(p["router_bias"])))
        for i, expert in enumerate(layer.experts):
            expert.gate_proj.weight.copy_(t(p["wi_gate"][i]))
            expert.up_proj.weight.copy_(t(p["wi_up"][i]))
            expert.down_proj.weight.copy_(t(p["wo_e"][i]))
        layer.shared_experts.gate_proj.weight.copy_(t(p["ws_gate"]))
        layer.shared_experts.up_proj.weight.copy_(t(p["ws_up"]))
        layer.shared_experts.down_proj.weight.copy_(t(p["ws_down"]))
    h = np.asarray(jax.random.normal(jax.random.key(6), (1, 20, 64)),
                   np.float32)
    with torch.no_grad():
        want = layer(torch.tensor(h))[0].numpy()
    f32 = lambda name: p[name].astype(jnp.float32)   # noqa: E731
    got = family._routed_ffn(
        jnp.asarray(h[0]), p, top_k=3, norm_topk_prob=True,
        routing_scale=1.0, scores="sigmoid", bias=True, first=0
    ) + family._swiglu(jnp.asarray(h[0]), f32("ws_gate"), f32("ws_up"),
                       f32("ws_down"))
    np.testing.assert_allclose(got, want, atol=2e-5)
    # and the program's op on the same layer
    prog, _ = dots3_note.feed_forward(whole, {**p, "mlp_norm": jnp.ones(
        (64,))}, jnp.asarray(h))
    normed = h / np.sqrt((h ** 2).mean(-1, keepdims=True) + whole.rms_eps)
    with torch.no_grad():
        want_normed = layer(torch.tensor(normed))[0].numpy()
    np.testing.assert_allclose(prog[0] - h[0], want_normed, atol=5e-5)

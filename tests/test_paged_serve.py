"""The serving engine (serve/paged_llm.py) and its paged KV pool.

Reference: ABSENT from the reference (it serves via user code in
replicas, SURVEY.md P15); this is the vLLM-style paged KV design
TPU-first. Tests run the tiny llama config on CPU.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import decoding, llama
from ray_tpu.serve.paged_llm import PagedLLMEngine


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.llama_tiny()
    params = llama.init_params(cfg, jax.random.key(0))
    # sharpen the head: random-weight logits sit near ties, and the
    # engine and the plain decode it is held to compile DIFFERENT
    # programs whose float rounding can flip a near-tie greedy argmax —
    # a 4x margin makes exact token equality robust to program-level
    # rounding
    params["lm_head"] = params["lm_head"] * 4.0
    return cfg, params


def _run(engine, prompts, max_new=16):
    # submit BEFORE start: admission happens in ONE deterministic wave
    # (thread timing otherwise splits waves, changing which prefill
    # program — and therefore which rounding — each request sees).
    # max_new: one budget for all, or one a prompt
    budgets = ([max_new] * len(prompts) if isinstance(max_new, int)
               else max_new)
    reqs = [engine.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, budgets)]
    engine.start()
    outs = [list(r.tokens()) for r in reqs]
    return reqs, outs


def _plain_greedy(cfg, params, prompts, max_new=16):
    """The tokens the engine must produce: the library's plain decode
    over contiguous KV rows (``decoding.generate``), one prompt a call."""
    budgets = ([max_new] * len(prompts) if isinstance(max_new, int)
               else max_new)
    return [np.asarray(decoding.generate(
        cfg, params, jnp.asarray(p, jnp.int32)[None],
        sampling=decoding.SamplingParams(temperature=0.0,
                                         max_new_tokens=n)))[0].tolist()
            for p, n in zip(prompts, budgets)]


def test_paged_matches_dense_greedy(tiny):
    """Greedy decode through the paged engine must produce EXACTLY the
    plain decode's tokens — paging changes layout, not math."""
    cfg, params = tiny
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, int(n))
               for n in (24, 48, 13, 70)]
    out_d = _plain_greedy(cfg, params, prompts)
    paged = PagedLLMEngine(cfg=cfg, params=params, max_batch=4,
                           max_len=256, page_size=32)
    _, out_p = _run(paged, prompts)
    st = paged.stats()
    paged.stop()
    assert out_p == out_d
    # the pool is half of max_batch full-length rows by default
    assert st["kv_pages_bytes"] * 2 == st["kv_dense_equiv_bytes"]


def test_paged_matches_dense_across_admission_waves(tiny):
    """Requests admitted SEQUENTIALLY (multiple admission waves) must
    still match the plain decode — regression for the stale device
    active-mask/table after the first wave."""
    cfg, params = tiny
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, int(n))
               for n in (20, 33, 27)]

    paged = PagedLLMEngine(cfg=cfg, params=params, max_batch=2,
                           max_len=128, page_size=32)
    paged.start()
    out_p = []
    for p in prompts:   # one at a time: each is its own wave
        req = paged.submit(p, max_new_tokens=12)
        out_p.append(list(req.tokens()))
    paged.stop()
    assert out_p == _plain_greedy(cfg, params, prompts, 12)


def test_pages_released_on_completion(tiny):
    cfg, params = tiny
    eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=2,
                         max_len=128, page_size=32)
    total = eng.num_pages
    rng = np.random.default_rng(1)
    _run(eng, [rng.integers(1, cfg.vocab_size, 20) for _ in range(4)],
         max_new=8)
    # deferred frees drain within a couple of chunk syncs; poke the
    # engine with one more request to age them out
    last = eng.submit(rng.integers(1, cfg.vocab_size, 8),
                      max_new_tokens=4)
    list(last.tokens())
    eng.stop()
    # every page except possibly the final request's deferred ones is back
    assert len(eng._alloc.free) >= total - 2


def test_pool_exhaustion_applies_backpressure(tiny):
    """More concurrent requests than the pool can hold: later requests
    WAIT for pages (no crash, no corruption) and still complete."""
    cfg, params = tiny
    # pool: 4 pages of 32 = 128 tokens; each request reserves
    # ceil((20+24)/32)+1 = 3 pages -> only one fits at a time
    eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=4,
                         max_len=128, page_size=32, num_pages=4)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg.vocab_size, 20) for _ in range(3)]
    _, outs = _run(eng, prompts, max_new=24)
    eng.stop()
    assert all(len(o) == 24 for o in outs)


def test_reservation_larger_than_pool_rejected(tiny):
    """A request whose page reservation exceeds the whole pool must be
    REJECTED (requeueing it forever would hang it and head-of-line
    block the queue behind it)."""
    cfg, params = tiny
    eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=2,
                         max_len=256, page_size=32, num_pages=2)
    eng.start()
    big = eng.submit(np.ones(100, np.int32), max_new_tokens=64)
    small = eng.submit(np.ones(8, np.int32), max_new_tokens=8)
    with pytest.raises(MemoryError):
        list(big.tokens())
    # the queue behind the infeasible request still drains
    assert len(list(small.tokens())) == 8
    eng.stop()


def test_prompt_too_long_rejected(tiny):
    cfg, params = tiny
    eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=2,
                         max_len=64, page_size=32)
    eng.start()
    req = eng.submit(np.ones(64, np.int32), max_new_tokens=4)
    with pytest.raises(ValueError):
        list(req.tokens())
    eng.stop()


def test_temperature_sampling_runs(tiny):
    cfg, params = tiny
    eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=2,
                         max_len=128, page_size=32)
    eng.start()
    req = eng.submit(np.arange(1, 9, dtype=np.int32),
                     max_new_tokens=12, temperature=0.8)
    toks = list(req.tokens())
    eng.stop()
    assert len(toks) == 12
    assert all(0 <= t < cfg.vocab_size for t in toks)


def test_prefix_cache_reuse_and_correctness(tiny):
    """Requests sharing a full-page prompt prefix reuse its cached KV
    pages (suffix-only prefill) and produce EXACTLY the tokens a
    prefix-cache-disabled engine produces."""
    cfg, params = tiny
    rng = np.random.default_rng(3)
    base = rng.integers(1, cfg.vocab_size, 96)     # 3 full pages @ ps=32
    prompts = [base,
               np.concatenate([base, rng.integers(1, cfg.vocab_size, 20)]),
               np.concatenate([base, rng.integers(1, cfg.vocab_size, 7)])]

    ref = PagedLLMEngine(cfg=cfg, params=params, max_batch=2, max_len=256,
                         page_size=32, prefix_cache=False)
    _, out_ref = _run(ref, prompts)
    st_ref = ref.stats()
    ref.stop()
    assert st_ref["prefix_cache"]["hit_pages"] == 0

    eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=2, max_len=256,
                         page_size=32)
    _, out = _run(eng, prompts)
    st = eng.stats()
    eng.stop()
    assert out == out_ref
    # at least the second wave's tailed prompt hit the base's 3 pages
    assert st["prefix_cache"]["hit_pages"] >= 3


def test_prefix_cache_eviction_under_pressure(tiny):
    """Idle cached prefix pages are LRU-evicted when admission needs
    their space; the engine keeps serving distinct prompts forever on a
    small pool."""
    cfg, params = tiny
    rng = np.random.default_rng(4)
    eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=2, max_len=128,
                         page_size=32, num_pages=8)
    eng.start()
    for _ in range(5):
        r = eng.submit(rng.integers(1, cfg.vocab_size, 64),
                       max_new_tokens=8)
        assert len(list(r.tokens())) == 8
    st = eng.stats()
    eng.stop()
    pc = st["prefix_cache"]
    assert pc["cached_idle_pages"] + len(eng._alloc.free) <= eng.num_pages


def test_prefix_cache_exact_prompt_repeat(tiny):
    """Repeating an identical prompt reuses every full page except the
    sampling tail (at least one suffix token always prefills so the
    first output token has logits)."""
    cfg, params = tiny
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, cfg.vocab_size, 64)   # exactly 2 full pages
    eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=1, max_len=128,
                         page_size=32, num_pages=8)
    eng.start()
    r1 = eng.submit(prompt, max_new_tokens=6)
    out1 = list(r1.tokens())
    r2 = eng.submit(prompt, max_new_tokens=6)
    out2 = list(r2.tokens())
    st = eng.stats()
    eng.stop()
    assert out1 == out2                     # greedy + same prompt
    # max reuse for plen 64 is (64-1)//32 = 1 page (suffix stays nonempty)
    assert st["prefix_cache"]["hit_pages"] >= 1


def test_kv_quantization_roundtrip_error():
    from ray_tpu.ops.paged_attention import dequantize_kv, quantize_kv
    x = jax.random.normal(jax.random.key(0), (4, 16, 2, 64),
                          jnp.bfloat16) * 3.0
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == x.shape[:-1]
    y = dequantize_kv(q, s)
    rel = (np.abs(np.asarray(y, np.float32) - np.asarray(x, np.float32))
           / (np.abs(np.asarray(x, np.float32)).max()))
    assert rel.max() < 0.01
    # zero rows stay exactly zero (scale guard, no 0/0)
    q0, s0 = quantize_kv(jnp.zeros((2, 3, 1, 8), jnp.bfloat16))
    assert np.all(np.asarray(q0) == 0)
    assert np.all(np.asarray(dequantize_kv(q0, s0)) == 0)


def test_int8_kv_engine_serves(tiny):
    """kv_dtype="int8" halves page bytes and still serves correct-shape,
    deterministic streams (greedy outputs may differ from bf16 by
    quantization rounding — determinism and plausibility are the
    contract)."""
    cfg, params = tiny
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, int(n)) for n in (24, 40)]

    eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=2, max_len=128,
                         page_size=32, kv_dtype="int8")
    st = eng.stats()
    _, outs = _run(eng, prompts, max_new=12)
    eng.stop()
    assert all(len(o) == 12 for o in outs)
    assert all(0 <= t < cfg.vocab_size for o in outs for t in o)
    assert st["kv_dtype"] == "int8"

    bf16 = PagedLLMEngine(cfg=cfg, params=params, max_batch=2, max_len=128,
                          page_size=32)
    assert st["kv_pages_bytes"] < bf16.stats()["kv_pages_bytes"]
    bf16.stop()

    # deterministic: a fresh int8 engine reproduces the same tokens
    eng2 = PagedLLMEngine(cfg=cfg, params=params, max_batch=2,
                          max_len=128, page_size=32, kv_dtype="int8")
    _, outs2 = _run(eng2, prompts, max_new=12)
    eng2.stop()
    assert outs == outs2


def test_int8_kv_with_prefix_cache(tiny):
    """Prefix caching composes with int8 KV: reused pages carry the
    SAME quantized content the original prompt wrote, so a repeat
    prompt decodes identically with and without the cached prefix."""
    cfg, params = tiny
    rng = np.random.default_rng(8)
    prompt = rng.integers(1, cfg.vocab_size, 80)   # 2 full pages @32
    cold = PagedLLMEngine(cfg=cfg, params=params, max_batch=1,
                          max_len=128, page_size=32, num_pages=8,
                          kv_dtype="int8", prefix_cache=False)
    cold.start()
    want = list(cold.submit(prompt, max_new_tokens=8).tokens())
    cold.stop()

    eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=1,
                         max_len=128, page_size=32, num_pages=8,
                         kv_dtype="int8")
    eng.start()
    a = list(eng.submit(prompt, max_new_tokens=8).tokens())
    b = list(eng.submit(prompt, max_new_tokens=8).tokens())
    st = eng.stats()
    eng.stop()
    assert a == want and b == want
    assert st["prefix_cache"]["hit_pages"] >= 2


@pytest.mark.parametrize("prefix_cache", [True, False],
                         ids=["reuse", "no_reuse"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_matches_dense_through_slot_refill(tiny, monkeypatch,
                                                 kv_dtype, prefix_cache):
    """Five requests over two slots with unequal budgets: slots retire
    at different chunks and are refilled from the queue while the other
    still decodes, three prompts share a two-page prefix (reused or
    prefilled again), and the pool is small enough that freed pages are
    handed out again. The stacked pools are written and gathered at
    [layer, page] inside the layer loop: every layer's rows must land in
    that layer's pages, in bf16 and through the int8 scale pools, or the
    greedy tokens leave the plain decode's. For int8 the plain decode
    rounds each new K and V row as the int8 pages do (plain int8 against
    bf16 flips near-tie tokens in most draws of the prompts)."""
    from ray_tpu.ops.paged_attention import dequantize_kv, quantize_kv

    cfg, params = tiny
    if kv_dtype == "int8":
        write = decoding._write_cache
        monkeypatch.setattr(
            decoding, "_write_cache", lambda cache, new, start: write(
                cache, dequantize_kv(*quantize_kv(new), cache.dtype), start))
    rng = np.random.default_rng(11)
    base = rng.integers(1, cfg.vocab_size, 64)     # 2 full pages @ ps=32
    prompts = [np.concatenate([base, rng.integers(1, cfg.vocab_size, n)])
               for n in (9, 30)]
    prompts += [rng.integers(1, cfg.vocab_size, n) for n in (21, 50)]
    prompts.append(np.concatenate([base, rng.integers(1, cfg.vocab_size, 3)]))
    budgets = [5, 20, 9, 14, 7]

    want = _plain_greedy(cfg, params, prompts, budgets)
    paged = PagedLLMEngine(
        cfg=cfg, params=params, max_batch=2, max_len=128, page_size=32,
        num_pages=10, kv_dtype=kv_dtype, prefix_cache=prefix_cache)
    _, got = _run(paged, prompts, budgets)
    st = paged.stats()
    paged.stop()
    assert [len(o) for o in got] == budgets
    assert got == want
    assert (st["prefix_cache"]["hit_pages"] >= 2) == prefix_cache


def _pages_all_back(eng, timeout=30.0) -> bool:
    """Once the loop idles every page is free or idle in the prefix cache
    (``_wait_idle`` drains the deferred frees)."""
    import time
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if (len(eng._alloc.free) + eng._prefix.evictable()
                == eng.num_pages):
            return True
        time.sleep(0.01)
    return False


def test_a_successor_the_pool_cannot_place_waits_and_pages_come_back(tiny):
    """Two slots and a pool of five pages: A (2 pages) and B (3) fill it.
    A's end is foreseen and its slot released with A's last chunk in
    flight, but C needs 3 pages and A's 2 are all there are: C is put
    back (no deadlock, nothing corrupted), placed once B's pages return,
    and every stream is its budget of the plain decode's tokens."""
    cfg, params = tiny
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, n) for n in (20, 20, 40)]
    budgets = [8, 40, 20]      # ceil((p + n) / 32) + 1 = 2, 3, 3 pages
    eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=2, max_len=128,
                         page_size=32, num_pages=5, decode_chunk=4)
    refused, reserve = [], eng._reserve_pages
    eng._reserve_pages = lambda req, slot: (
        reserve(req, slot) or bool(refused.append(req.request_id)))
    reqs, got = _run(eng, prompts, budgets)
    assert _pages_all_back(eng)
    st = eng.stats()
    eng.stop()
    assert refused and set(refused) == {reqs[2].request_id}
    assert [len(o) for o in got] == budgets
    assert got == _plain_greedy(cfg, params, prompts, budgets)
    assert st["retirements_foreseen"] == 3


def test_a_handed_over_slots_successor_shares_its_predecessors_prefix(tiny):
    """One slot: A's end is foreseen, its shared prefix pages are
    released with its last chunk still to run, and B, whose prompt
    starts with the same two pages, takes slot and pages at the next
    pass: B's suffix prefill lies behind A's last chunk on the device
    stream, both read the shared pages, and both streams are the plain
    decode's."""
    cfg, params = tiny
    rng = np.random.default_rng(13)
    base = rng.integers(1, cfg.vocab_size, 64)     # 2 full pages @ ps=32
    prompts = [np.concatenate([base, rng.integers(1, cfg.vocab_size, n)])
               for n in (9, 30)]
    budgets = [7, 5]
    eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=1, max_len=128,
                         page_size=32, num_pages=8, decode_chunk=4)
    _, got = _run(eng, prompts, budgets)
    assert _pages_all_back(eng)
    st = eng.stats()
    eng.stop()
    assert got == _plain_greedy(cfg, params, prompts, budgets)
    assert st["prefix_cache"]["hit_pages"] == 2
    assert (st["retirements_foreseen"], st["slots_handed_over"]) == (2, 1)
    assert st["decode_overrun_ahead"] == 0

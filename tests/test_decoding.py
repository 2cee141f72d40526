"""KV-cache decoding + continuous-batching engine tests.

Correctness anchor: prefill+decode through the cache, the plain one of
``models/decoding.py`` and the serving engine's paged one, must reproduce
the full (uncached) forward pass exactly under greedy sampling.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decoding, llama
from ray_tpu.models.decoding import SamplingParams


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.llama_tiny()
    params = llama.init_params(cfg, jax.random.key(0))
    return cfg, params


def greedy_teacher_forced(cfg, params, prompt, n_new):
    """Reference decode: rerun the full forward each step."""
    seq = list(prompt)
    out = []
    for _ in range(n_new):
        tokens = jnp.asarray(seq, jnp.int32)[None, :]
        logits = llama.forward(cfg, params, tokens, attn_impl="reference")
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
        seq.append(tok)
    return out


def test_cached_forward_matches_forward(tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.key(1), (2, 24), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    full = llama.forward(cfg, params, tokens, attn_impl="reference")
    cache = decoding.init_cache(cfg, 2, 48)
    cached, _ = decoding.cached_forward(
        cfg, params, tokens, cache,
        start=jnp.zeros((2,), jnp.int32), logits_mode="all")
    np.testing.assert_allclose(np.asarray(full), np.asarray(cached),
                               rtol=2e-2, atol=2e-2)


def test_incremental_decode_matches_prefill(tiny):
    """Feeding tokens one at a time through the cache == one-shot prefill."""
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.key(2), (1, 16), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    cache = decoding.init_cache(cfg, 1, 32)
    oneshot, _ = decoding.cached_forward(
        cfg, params, tokens, cache,
        start=jnp.zeros((1,), jnp.int32), logits_mode="last")

    cache = decoding.init_cache(cfg, 1, 32)
    for t in range(16):
        step_logits, cache = decoding.cached_forward(
            cfg, params, tokens[:, t:t + 1], cache,
            start=jnp.full((1,), t, jnp.int32), logits_mode="last")
    np.testing.assert_allclose(np.asarray(oneshot), np.asarray(step_logits),
                               rtol=2e-2, atol=2e-2)


def test_generate_greedy_matches_teacher_forced(tiny):
    cfg, params = tiny
    prompt = [3, 17, 99, 254, 7]
    n_new = 8
    want = greedy_teacher_forced(cfg, params, prompt, n_new)
    prompts = jnp.asarray([prompt], jnp.int32)
    got = decoding.generate(
        cfg, params, prompts,
        sampling=SamplingParams(temperature=0.0, max_new_tokens=n_new))
    assert np.asarray(got)[0].tolist() == want


def test_generate_batch_right_padded(tiny):
    """Rows with different prompt lengths decode independently and each
    matches its single-row run (padding must not leak)."""
    cfg, params = tiny
    p1, p2 = [5, 9, 13], [21, 34, 55, 89, 144, 233]
    n_new = 6
    pad = max(len(p1), len(p2))
    batch = np.zeros((2, pad), np.int32)
    batch[0, :len(p1)] = p1
    batch[1, :len(p2)] = p2
    sp = SamplingParams(temperature=0.0, max_new_tokens=n_new)
    got = np.asarray(decoding.generate(cfg, params, jnp.asarray(batch),
                                       sampling=sp))
    want1 = greedy_teacher_forced(cfg, params, p1, n_new)
    want2 = greedy_teacher_forced(cfg, params, p2, n_new)
    assert got[0].tolist() == want1
    assert got[1].tolist() == want2


def test_generate_eos_stops(tiny):
    cfg, params = tiny
    prompt = [3, 17, 99]
    want = greedy_teacher_forced(cfg, params, prompt, 8)
    eos = want[1]
    stop = want.index(eos)  # first occurrence is where generation must stop
    got = np.asarray(decoding.generate(
        cfg, params, jnp.asarray([prompt], jnp.int32),
        sampling=SamplingParams(temperature=0.0, max_new_tokens=8),
        eos_id=eos))[0]
    assert got[stop] == eos
    assert got[:stop].tolist() == want[:stop]
    assert all(t == 0 for t in got[stop + 1:])  # pad after eos


def test_sample_top_k_top_p():
    logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0]])
    key = jax.random.key(0)
    # top_k=1 == greedy regardless of key
    sp = SamplingParams(temperature=1.0, top_k=1)
    for i in range(5):
        tok = decoding.sample(logits, jax.random.fold_in(key, i), sp)
        assert int(tok[0]) == 3
    # top_p tiny -> only the argmax survives
    sp = SamplingParams(temperature=1.0, top_p=0.1)
    for i in range(5):
        tok = decoding.sample(logits, jax.random.fold_in(key, i), sp)
        assert int(tok[0]) == 3


# ---------------------------------------------------------------------------
# Continuous batching engine
# ---------------------------------------------------------------------------

def test_llm_engine_streams_and_matches_offline(tiny):
    from ray_tpu.serve.paged_llm import PagedLLMEngine

    cfg, params = tiny
    eng = PagedLLMEngine(cfg, params, max_batch=4, max_len=128)
    eng.start()
    try:
        prompts = [[3, 17, 99, 254, 7], [5, 9, 13], [21, 34, 55, 89]]
        n_new = 6
        reqs = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
        outs = [list(r.tokens()) for r in reqs]
        for p, got in zip(prompts, outs):
            want = greedy_teacher_forced(cfg, params, p, n_new)
            assert got == want, f"prompt {p}: {got} != {want}"
        stats = eng.stats()
        assert stats["total_finished"] == 3
        assert stats["mean_ttft_s"] is not None
        for r in reqs:
            assert r.ttft is not None and r.ttft >= 0
    finally:
        eng.stop()


def _tiny_builder():
    cfg = llama.llama_tiny()
    return cfg, llama.init_params(cfg, jax.random.key(0))


def test_llm_deployment_via_serve(ray_tpu_start):
    """End-to-end: the engine hosted in a Serve replica actor."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMDeployment

    try:
        dep = serve.deployment(LLMDeployment).bind(
            _tiny_builder, max_batch=2, max_len=64)
        handle = serve.run(dep, name="llm")
        prompt = [3, 17, 99]
        got = handle.call(prompt, max_new_tokens=4)
        cfg, params = _tiny_builder()
        assert got == greedy_teacher_forced(cfg, params, prompt, 4)
    finally:
        serve.shutdown()


def test_llm_engine_more_requests_than_slots(tiny):
    """Requests beyond max_batch queue up and still complete correctly."""
    from ray_tpu.serve.paged_llm import PagedLLMEngine

    cfg, params = tiny
    eng = PagedLLMEngine(cfg, params, max_batch=2, max_len=64)
    eng.start()
    try:
        prompts = [[i + 1, i + 2, i + 3] for i in range(5)]
        reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        outs = [list(r.tokens()) for r in reqs]
        for p, got in zip(prompts, outs):
            assert got == greedy_teacher_forced(cfg, params, p, 4)
        assert eng.stats()["total_finished"] == 5
    finally:
        eng.stop()

"""The paged decode-attention kernel (ops/paged_decode_attention.py), in
interpret mode on the CPU: against a plain float32 paged attention at the
serving cells' head layouts, and through the paged engine against the
gather formulation. (Its Mosaic compile at the real shapes:
tests/test_tpu_compile.py.)"""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import llama, olmoe
from ray_tpu.ops import paged_decode_attention as pda
from ray_tpu.ops.paged_attention import quantize_kv
from ray_tpu.serve import engine_programs
from ray_tpu.serve.paged_llm import PagedLLMEngine

PAGE, BUCKET, HEAD_DIM, LAYERS, POOL = 16, 4, 32, 3, 24
LENGTHS = {"inactive": 0, "one-token": 1, "one-page": PAGE,
           "one-page-plus-1": PAGE + 1, "full-bucket": PAGE * BUCKET}


def plain_attention(q, k, v, table, pos, scale):
    """Float32, one slot at a time: the slot's pages in table order, its
    keys up to ``pos``, a softmax a query head over its KV head's keys."""
    out = np.zeros(q.shape, np.float32)
    group = q.shape[1] // k.shape[2]
    for b in range(q.shape[0]):
        n = pos[b] + 1
        keys = np.concatenate([k[p] for p in table[b] if p >= 0])[:n]
        vals = np.concatenate([v[p] for p in table[b] if p >= 0])[:n]
        for h in range(q.shape[1]):
            s = keys[:, h // group] @ q[b, h] * scale
            w = np.exp(s - s.max())
            out[b, h] = (w / w.sum()) @ vals[:, h // group]
    return out


@pytest.mark.parametrize("length", LENGTHS, ids=list(LENGTHS))
@pytest.mark.parametrize("pages", ["bf16", "int8"])
@pytest.mark.parametrize("nkv,group", [(8, 4), (16, 1)],
                         ids=["gqa-8x4", "mha-16x1"])
def test_kernel_is_plain_paged_attention(nkv, group, pages, length):
    """Slot 1 has the case's length, slot 0 is never live and slot 2
    always is (so a live slot follows a dead or a short one: the next
    slot's first page is fetched while the last one computes). Pages in
    shuffled order, holes past each slot's reserved pages, layer 2 of a
    stacked pool whose other layers hold other numbers."""
    rng = np.random.default_rng(nkv + LENGTHS[length])
    shape = (LAYERS, POOL, PAGE, nkv, HEAD_DIM)
    k, v = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for _ in range(2))
    q = jnp.asarray(rng.standard_normal((3, nkv * group, HEAD_DIM)),
                    jnp.bfloat16)
    if pages == "int8":
        (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
        plain_k = np.asarray(k, np.float32) * np.asarray(ks)[..., None]
        plain_v = np.asarray(v, np.float32) * np.asarray(vs)[..., None]
    else:
        k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
        ks = vs = jnp.ones((LAYERS, 1, 1, 1), jnp.float32)
        plain_k, plain_v = np.asarray(k, np.float32), np.asarray(v, np.float32)
    lengths = np.array([0, LENGTHS[length], PAGE + 3])
    table = rng.permutation(POOL)[:3 * BUCKET].reshape(3, BUCKET)
    for slot, n in enumerate(lengths):        # reserved: its pages + 1
        table[slot, -(-n // PAGE) + 1:] = -1
    pos = np.maximum(lengths - 1, 0)
    layer, scale = 2, HEAD_DIM ** -0.5
    got = pda.paged_decode_attention_kernel(
        q, k, v, ks, vs, jnp.int32(layer), jnp.asarray(table, jnp.int32),
        jnp.asarray(pos, jnp.int32), jnp.asarray(lengths > 0),
        interpret=True)
    assert got.shape == q.shape and got.dtype == q.dtype
    got = np.asarray(got, np.float32)
    want = plain_attention(np.asarray(q, np.float32), plain_k[layer],
                           plain_v[layer], table, pos, scale)
    live = lengths > 0
    # bf16 probabilities and a bf16 result: 2**-8 of values of order 1
    assert np.abs(got - want)[live].max() < 2e-2
    assert np.isfinite(got).all()
    # and the formulation every other platform runs is the same function
    ref = pda.paged_decode_attention_reference(
        q, k, v, ks, vs, jnp.int32(layer), jnp.asarray(table, jnp.int32),
        jnp.asarray(pos, jnp.int32), jnp.asarray(live))
    assert np.abs(np.asarray(ref, np.float32) - want)[live].max() < 2e-2


def _tiny(model):
    cfg = llama.llama_tiny() if model is llama else olmoe.olmoe_tiny()
    return cfg, model.init_params(cfg, jax.random.key(0))


# The kernel and the gather formulation round differently (unnormalised
# against normalised probabilities in bf16: one unit in the last place of
# an attention output), and a random-weight model's greedy choice sits on
# near ties that this flips (measured here over 24 runs: every divergence
# at a place where the model's own top two logits are 0.002-0.073 apart,
# of logits near 10). So the comparison is the benchmark's ``token_gap``
# kind: the same tokens up to the first divergence, and there a tie, by
# the model's own teacher-forced logits. A wrong attention is off by
# whole logits, not by 0.1.
NEAR_TIE = 0.1


def _same_greedy_choice(model, cfg, params, prompt, got, want):
    if got == want:
        return True
    at = next(i for i, (a, b) in enumerate(zip(got, want)) if a != b)
    seq = jnp.asarray(list(prompt) + got[:at], jnp.int32)[None]
    logits = np.asarray(model.forward(cfg, params, seq)[0, -1], np.float32)
    return abs(logits[got[at]] - logits[want[at]]) < NEAR_TIE


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("model", [llama, olmoe], ids=["llama", "olmoe"])
def test_engine_decodes_the_same_tokens_through_the_kernel(monkeypatch, model,
                                                          kv_dtype):
    """The paged engine's greedy tokens with the kernel in its decode
    program (interpret mode) are those of the gather formulation: three
    prompts of different lengths in four slots (one idle), answers that
    cross a page boundary."""
    cfg, params = _tiny(model)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, n) for n in (5, 30, 41)]

    def served(attention):
        monkeypatch.setattr(engine_programs, "paged_decode_attention",
                            attention)
        eng = PagedLLMEngine(cfg, params, max_batch=4, max_len=128,
                             page_size=PAGE, num_pages=40,
                             kv_dtype=kv_dtype)
        reqs = [eng.submit(p, max_new_tokens=20) for p in prompts]
        eng.start()
        try:
            return [list(r.tokens()) for r in reqs]
        finally:
            eng.stop()

    kernel = served(partial(pda.paged_decode_attention_kernel,
                            interpret=True))
    gather = served(pda.paged_decode_attention_reference)
    assert [len(t) for t in kernel] == [20, 20, 20]
    for prompt, got, want in zip(prompts, kernel, gather):
        assert _same_greedy_choice(model, cfg, params, prompt, got, want)
    # and not by diverging everywhere: most answers are the same tokens
    same = sum(g == w for g, w in zip(kernel, gather))
    assert same >= 1

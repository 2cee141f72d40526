"""The paged decode-attention kernel (ops/paged_decode_attention.py), in
interpret mode on the CPU: against a plain float32 paged attention at the
serving cells' head layouts, and through the paged engine against the
gather formulation. (Its Mosaic compile at the real shapes:
tests/test_tpu_compile_kernels.py.)"""

from functools import cache, partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import llama, olmoe
from ray_tpu.ops import paged_decode_attention as pda
from ray_tpu.ops.paged_attention import quantize_kv
from ray_tpu.serve import engine_programs
from ray_tpu.serve.paged_llm import PagedLLMEngine
from toy_engine import same_greedy_choice

PAGE, BUCKET, HEAD_DIM, LAYERS, POOL = 16, 4, 32, 3, 24
# (KV heads, query heads a KV head, page, pages a table row): the two
# layouts the kernel had at the page the engine tests use, and the three
# whose step of the walk is 2 and 4 pages at a page of 128 tokens (the
# rule reads ``page x nkv``: 512 and 256 rows a page, 1,024 a step)
LAYOUTS = {"gqa-8x4": (8, 4, PAGE, BUCKET), "mha-16x1": (16, 1, PAGE, BUCKET),
           "gqa-4x7": (4, 7, 128, 6), "gqa-4x5": (4, 5, 128, 6),
           "gqa-2x16": (2, 16, 128, 6)}
STEP_PAGES = {"gqa-8x4": 8, "mha-16x1": 4, "gqa-4x7": 2, "gqa-4x5": 2,
              "gqa-2x16": 4}
# a slot's keys, from the page and the pages a step takes: the last three
# end a walk mid-step (an odd number of pages; a count one past a step's
# edge, held to the table's row; one page short of the row)
LENGTHS = {"inactive": lambda page, step, bucket: 0,
           "one-token": lambda page, step, bucket: 1,
           "one-page": lambda page, step, bucket: page,
           "one-page-plus-1": lambda page, step, bucket: page + 1,
           "full-bucket": lambda page, step, bucket: page * bucket,
           "three-pages": lambda page, step, bucket: 3 * page - 2,
           "one-past-a-step": lambda page, step, bucket:
               min(step, bucket - 1) * page + 1,
           "a-page-short": lambda page, step, bucket: (bucket - 1) * page}


def test_the_layouts_step_as_the_rule_says():
    def pool(page, nkv, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((LAYERS, POOL, page, nkv, 128), dtype)

    assert {name: pda.step_pages(pool(page, nkv))
            for name, (nkv, _, page, _) in LAYOUTS.items()} == STEP_PAGES
    # and at the serving cells' page: 2 and 4 KV heads alone take more
    assert [pda.step_pages(pool(128, nkv)) for nkv in (2, 4, 8, 16)] == \
        [4, 2, 1, 1]
    # a page's rows lie dense in the pool where its KV heads fill a
    # tile's sublanes (a power of two of them; two bf16 or four int8 rows
    # a 32-bit sublane at least): elsewhere the view of a page as rows
    # would be a copy of the pool, and a step is a page
    assert [pda.step_pages(pool(128, nkv, jnp.int8))
            for nkv in (2, 4, 8)] == [1, 2, 1]
    assert [pda.step_pages(pool(128, nkv)) for nkv in (1, 3, 6)] == [1, 1, 1]


@cache
def _kernel(window=None):
    """The kernel in interpret mode, compiled once a shape: the cases of
    a layout share theirs (run eagerly it is lowered anew every call)."""
    return jax.jit(partial(pda.paged_decode_attention_kernel, window=window,
                           interpret=True))


@cache
def _reference(window=None):
    return jax.jit(partial(pda.paged_decode_attention_reference,
                           window=window))


def plain_attention(q, k, v, table, pos, scale, window=None):
    """Float32, one slot at a time: the slot's pages in table order, its
    keys up to ``pos`` (the ``window`` newest of them), a softmax a query
    head over its KV head's keys."""
    out = np.zeros(q.shape, np.float32)
    group = q.shape[1] // k.shape[2]
    for b in range(q.shape[0]):
        n = pos[b] + 1
        old = 0 if window is None else max(n - window, 0)
        keys = np.concatenate([k[p] for p in table[b] if p >= 0])[old:n]
        vals = np.concatenate([v[p] for p in table[b] if p >= 0])[old:n]
        for h in range(q.shape[1]):
            s = keys[:, h // group] @ q[b, h] * scale
            w = np.exp(s - s.max())
            out[b, h] = (w / w.sum()) @ vals[:, h // group]
    return out


def _pools(rng, nkv, page, pages):
    """K and V pools [LAYERS, POOL, page, nkv, HEAD_DIM] as the engine
    holds them (bf16, or int8 with their scales) and as plain float32."""
    shape = (LAYERS, POOL, page, nkv, HEAD_DIM)
    k, v = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for _ in range(2))
    if pages == "int8":
        (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
        plain_k = np.asarray(k, np.float32) * np.asarray(ks)[..., None]
        plain_v = np.asarray(v, np.float32) * np.asarray(vs)[..., None]
    else:
        k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
        ks = vs = jnp.ones((LAYERS, 1, 1, 1), jnp.float32)
        plain_k, plain_v = np.asarray(k, np.float32), np.asarray(v, np.float32)
    return (k, v, ks, vs), (plain_k, plain_v)


def _table(rng, lengths, page, bucket):
    """Pages in shuffled order, a slot's own and one reserved beyond
    them, holes past those."""
    slots = len(lengths)
    table = rng.permutation(POOL)[:slots * bucket].reshape(slots, bucket)
    for slot, n in enumerate(lengths):
        table[slot, -(-n // page) + 1:] = -1
    return table


@pytest.mark.parametrize("length", LENGTHS, ids=list(LENGTHS))
@pytest.mark.parametrize("pages", ["bf16", "int8"])
@pytest.mark.parametrize("layout", LAYOUTS, ids=list(LAYOUTS))
def test_kernel_is_plain_paged_attention(layout, pages, length):
    """Slot 1 has the case's length, slot 0 is never live and slot 2
    always is (so a live slot follows a dead or a short one: the next
    slot's first page is fetched while the last one computes). Pages in
    shuffled order, holes past each slot's reserved pages, layer 2 of a
    stacked pool whose other layers hold other numbers."""
    nkv, group, page, bucket = LAYOUTS[layout]
    n = LENGTHS[length](page, STEP_PAGES[layout], bucket)
    rng = np.random.default_rng(nkv + n)
    (k, v, ks, vs), (plain_k, plain_v) = _pools(rng, nkv, page, pages)
    q = jnp.asarray(rng.standard_normal((3, nkv * group, HEAD_DIM)),
                    jnp.bfloat16)
    lengths = np.array([0, n, page + 3])
    table = _table(rng, lengths, page, bucket)
    pos = np.maximum(lengths - 1, 0)
    layer, scale = 2, HEAD_DIM ** -0.5
    got = _kernel()(
        q, k, v, ks, vs, jnp.int32(layer), jnp.asarray(table, jnp.int32),
        jnp.asarray(pos, jnp.int32), jnp.asarray(lengths > 0))
    assert got.shape == q.shape and got.dtype == q.dtype
    got = np.asarray(got, np.float32)
    want = plain_attention(np.asarray(q, np.float32), plain_k[layer],
                           plain_v[layer], table, pos, scale)
    live = lengths > 0
    # bf16 probabilities and a bf16 result: 2**-8 of values of order 1
    assert np.abs(got - want)[live].max() < 2e-2
    assert np.isfinite(got).all()
    # and the formulation every other platform runs is the same function
    ref = _reference()(
        q, k, v, ks, vs, jnp.int32(layer), jnp.asarray(table, jnp.int32),
        jnp.asarray(pos, jnp.int32), jnp.asarray(live))
    assert np.abs(np.asarray(ref, np.float32) - want)[live].max() < 2e-2


# a sliding layer's walk begins at the page of key ``count - window``,
# which need be no step's first: (the oldest key the window holds, the
# keys past it) in pages and keys, from the page and a step's pages
WINDOWS = {
    # the window opens in the second page of the table's first step, and
    # the walk ends mid-step
    "opens-in-a-second-page": lambda page, step: (page + 5, 4 * page + 4),
    # it opens on a step's edge, at a page's first key
    "opens-on-a-steps-edge": lambda page, step: (step * page, 3 * page),
    # one key past a step's edge: the walk's first page holds one key less
    "opens-one-key-on": lambda page, step: (step * page + 1, 2 * page - 1),
    # the context is shorter than the window: the walk is the whole of it
    "context-is-shorter": lambda page, step: (None, 2 * page + 3),
}


@pytest.mark.parametrize("case", WINDOWS, ids=list(WINDOWS))
@pytest.mark.parametrize("pages", ["bf16", "int8"])
@pytest.mark.parametrize("layout", ["gqa-4x7", "gqa-4x5", "gqa-2x16"])
def test_kernel_is_the_window_of_plain_paged_attention(layout, pages, case):
    """A sliding layer at the layouts whose step is 2 and 4 pages: slot 1
    has the case's context under the case's window, slot 2 a context
    shorter than any of them, slot 0 none. Against the gather formulation
    and against plain float32 attention over the window's keys."""
    nkv, group, page, _ = LAYOUTS[layout]
    bucket = 8
    old, more = WINDOWS[case](page, STEP_PAGES[layout])
    n = more if old is None else old + more
    window = 4 * page if old is None else more
    rng = np.random.default_rng(nkv + n)
    (k, v, ks, vs), (plain_k, plain_v) = _pools(rng, nkv, page, pages)
    q = jnp.asarray(rng.standard_normal((3, nkv * group, HEAD_DIM)),
                    jnp.bfloat16)
    lengths = np.array([0, n, page // 2])
    table = _table(rng, lengths, page, bucket)
    pos = np.maximum(lengths - 1, 0)
    layer, scale = 1, HEAD_DIM ** -0.5
    args = (q, k, v, ks, vs, jnp.int32(layer), jnp.asarray(table, jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(lengths > 0))
    got = np.asarray(_kernel(window)(*args), np.float32)
    ref = np.asarray(_reference(window)(*args), np.float32)
    want = plain_attention(np.asarray(q, np.float32), plain_k[layer],
                           plain_v[layer], table, pos, scale, window=window)
    live = lengths > 0
    assert np.isfinite(got).all()
    assert np.abs(got - ref)[live].max() < 2e-2
    assert np.abs(got - want)[live].max() < 2e-2


@pytest.mark.parametrize("window", [None, 300], ids=["full", "sliding"])
@pytest.mark.parametrize("length", ["one-token", "three-pages",
                                    "one-past-a-step", "a-page-short"])
@pytest.mark.parametrize("layout", ["gqa-4x7", "gqa-2x16"])
def test_a_row_never_fetched_reaches_no_result(layout, length, window):
    """Every page of the pool that no live slot holds keys in is NaN, the
    reserved page past each slot's last among them: a walk's last step
    takes fewer pages than its buffer has room for, and what the rest of
    the buffer holds (a page of another slot, of an earlier step, or
    nothing yet) is masked and must not be multiplied in."""
    nkv, group, page, bucket = LAYOUTS[layout]
    n = LENGTHS[length](page, STEP_PAGES[layout], bucket)
    rng = np.random.default_rng(nkv + n)
    (k, v, ks, vs), (plain_k, plain_v) = _pools(rng, nkv, page, "bf16")
    q = jnp.asarray(rng.standard_normal((3, nkv * group, HEAD_DIM)),
                    jnp.bfloat16)
    lengths = np.array([page + 3, 0, n])
    table = _table(rng, lengths, page, bucket)
    used = np.concatenate([table[slot, :-(-count // page)]
                           for slot, count in enumerate(lengths)])
    unused = np.setdiff1d(np.arange(POOL), used)
    k, v = k.at[:, unused].set(jnp.nan), v.at[:, unused].set(jnp.nan)
    pos = np.maximum(lengths - 1, 0)
    layer, scale = 0, HEAD_DIM ** -0.5
    got = np.asarray(_kernel(window)(
        q, k, v, ks, vs, jnp.int32(layer), jnp.asarray(table, jnp.int32),
        jnp.asarray(pos, jnp.int32), jnp.asarray(lengths > 0)), np.float32)
    want = plain_attention(np.asarray(q, np.float32), plain_k[layer],
                           plain_v[layer], table, pos, scale, window=window)
    assert np.isfinite(got).all()
    assert np.abs(got - want)[lengths > 0].max() < 2e-2


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
# whole logits, not by 0.1 (``toy_engine.same_greedy_choice``).
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
        assert same_greedy_choice(model, cfg, params, prompt, got, want)
    # and not by diverging everywhere: most answers are the same tokens
    same = sum(g == w for g, w in zip(kernel, gather))
    assert same >= 1

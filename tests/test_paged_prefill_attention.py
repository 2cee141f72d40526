"""The paged prefill-attention kernel (ops/paged_prefill_attention.py), in
interpret mode on the CPU: against the gather formulation it replaces on
the TPU, at the serving cells' head layouts; the rule by which it
engages; and through the paged engine. (Its Mosaic compile at the real
shapes: tests/test_tpu_compile_kernels.py, and inside the engine's prefill
programs in each family's tests/test_tpu_compile_<family>.py.)"""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import llama, smallthinker
from ray_tpu.ops import paged_prefill_attention as ppa
from ray_tpu.ops.paged_attention import quantize_kv
from ray_tpu.serve import engine_programs
from ray_tpu.serve.paged_llm import PagedLLMEngine
from toy_engine import same_greedy_choice

PAGE, WP, T, HEAD_DIM, LAYERS, POOL = 16, 8, 64, 32, 3, 40

# starts, valid queries a row, the rows' tables cut to this many pages
# (-1 after them; None: every entry a page)
CASES = {
    "cold-full": ([0], [T], None),
    "start-page-aligned": ([2 * PAGE], [T], None),
    "start-mid-page": ([21], [T], None),
    "two-rows-of-different-context": ([0, 21], [T, 37], None),
    "padded-queries": ([5], [19], None),
    "context-ends-mid-page": ([32], [T - 7], None),
    "holes-past-the-reservation": ([0, 16], [40, 30], [4, 3]),
    "one-query-over-a-table-of-holes": ([48], [1], [0]),     # the warm-up's
}
# KV heads, query heads a KV head, rows a query block (so that every
# layout walks several blocks of 16 positions): the last two are
# SmallThinker's (a group of 7) and Laguna's sliding layers' (of 9)
LAYOUTS = {"gqa-32x8": (8, 4, 64), "mha-16x16": (16, 1, 16),
           "gqa-48x8": (8, 6, 96), "gqa-28x4": (4, 7, 112),
           "gqa-72x8": (8, 9, 144)}
# a full layer, and a sliding layer's keys under chunks of 32: a window
# inside one chunk, and one that straddles two
WINDOWS = {"full": None, "window-24": 24, "window-40": 40}


def _inputs(nkv, group, starts, slens, reserved, seed=0):
    rng = np.random.default_rng(seed)
    n = len(starts)
    shape = (LAYERS, POOL, PAGE, nkv, HEAD_DIM)
    k, v = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
            for _ in range(2))
    q = jnp.asarray(rng.standard_normal((n, T, nkv * group, HEAD_DIM)),
                    jnp.bfloat16)
    table = rng.permutation(POOL)[:n * WP].reshape(n, WP)
    for row, pages in enumerate(reserved or []):
        table[row, pages:] = -1
    one = jnp.ones((LAYERS, 1, 1, 1), jnp.float32)
    return (q, k, v, one, one, jnp.int32(1), jnp.asarray(table, jnp.int32),
            jnp.asarray(starts, jnp.int32), jnp.asarray(slens, jnp.int32))


@pytest.mark.parametrize("window", WINDOWS, ids=list(WINDOWS))
@pytest.mark.parametrize("case", CASES, ids=list(CASES))
@pytest.mark.parametrize("layout", LAYOUTS, ids=list(LAYOUTS))
def test_kernel_is_the_gather_formulation(monkeypatch, layout, case, window):
    """Layer 1 of a stacked pool whose other layers hold other numbers,
    pages in shuffled order, blocks of 16 queries and chunks of two pages
    (so a block's walk has whole chunks, a masked one and chunks it never
    fetches; a block of padding follows a live one and a live row a
    padded one). Under a window the walk has a first chunk too: the
    blocks from position 48 on start past chunk 0 (``start-mid-page``:
    its second block already), each next block's first copies are its
    own first chunk's, the last rows of a block see nothing of the first
    chunk it fetches (the first rows nothing of the last), and a row of
    padding may see nothing of what was walked at all. The valid rows
    within a bf16 rounding of the gather formulation's under the same
    window, the rows of padding finite."""
    nkv, group, rows = LAYOUTS[layout]
    starts, slens, reserved = CASES[case]
    window = WINDOWS[window]
    monkeypatch.setattr(ppa, "_BLOCK_ROWS", rows)
    monkeypatch.setattr(ppa, "_CHUNK_KEYS", 2 * PAGE)
    args = _inputs(nkv, group, starts, slens, reserved,
                   seed=nkv + len(case))
    got = ppa.paged_prefill_attention_kernel(*args, window=window,
                                             interpret=True)
    want = ppa.paged_prefill_attention_reference(*args, window=window)
    assert got.shape == args[0].shape and got.dtype == args[0].dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    for row, valid in enumerate(slens):
        # bf16 probabilities and a bf16 result: 2**-8 of values of order 1
        # (what tests/test_paged_decode_attention.py holds decode's to)
        assert np.abs(got[row, :valid] - want[row, :valid]).max() < 2e-2


def _pool(dtype, nkv=8, hd=128, pages=544):
    return jax.ShapeDtypeStruct((12, pages, 128, nkv, hd), dtype)


@pytest.mark.parametrize("q,pool,pages,window,engages", [
    # serve-doc's and serve-chat's cold 2048-token programs, and 1024
    # tokens behind a cached prefix
    ((2, 2048, 32, 128), _pool(jnp.bfloat16), 16, None, True),
    ((1, 2048, 32, 128), _pool(jnp.bfloat16), 16, None, True),
    ((2, 1024, 32, 128), _pool(jnp.bfloat16), 16, None, True),
    # 256 MiB of scores and under: not over the line
    ((1, 1024, 32, 128), _pool(jnp.bfloat16), 16, None, False),
    ((2, 1024, 32, 128), _pool(jnp.bfloat16), 8, None, False),
    ((1, 1024, 32, 128), _pool(jnp.bfloat16), 8, None, False),
    # a prefix hit's suffix
    ((1, 128, 32, 128), _pool(jnp.bfloat16), 16, None, False),
    ((2, 64, 32, 128), _pool(jnp.bfloat16), 16, None, False),
    # everything serve-moe-gen warms (128 MiB at most)
    ((2, 512, 16, 128), _pool(jnp.bfloat16, nkv=16), 8, None, False),
    ((2, 1024, 16, 128), _pool(jnp.bfloat16, nkv=16), 8, None, False),
    # Laguna: a full layer's 48 heads (two 512-token suffixes over 2048
    # keys are 384 MiB); a sliding layer's 72 by what the plain path
    # writes for it, every block of 512 queries against 1,024 keys (the
    # warm-up's 4,095 tokens 1.2 GB, a cold file 604 MB, two suffixes of
    # 256 rows 113 MB)
    ((2, 512, 48, 128), _pool(jnp.bfloat16), 16, None, True),
    ((1, 512, 48, 128), _pool(jnp.bfloat16), 16, None, False),
    ((1, 4096, 72, 128), _pool(jnp.bfloat16), 32, 512, True),
    ((1, 2048, 72, 128), _pool(jnp.bfloat16), 16, 512, True),
    ((2, 256, 72, 128), _pool(jnp.bfloat16), 16, 512, False),
    # serve-brief-gen's sliding layers, 28 heads on 4 under 4,096 keys:
    # the cold document in blocks of 512 queries over 4,608 keys (4.2
    # GB), a cached document's question over its window (60 MB)
    ((1, 8192, 28, 128), _pool(jnp.bfloat16, nkv=4), 64, 4096, True),
    ((1, 128, 28, 128), _pool(jnp.bfloat16, nkv=4), 64, 4096, False),
    # a window wider than the table is no window: the table's keys
    ((1, 2048, 28, 128), _pool(jnp.bfloat16, nkv=4), 16, 4096, True),
    ((1, 1024, 28, 128), _pool(jnp.bfloat16, nkv=4), 16, 4096, False),
    # int8 pages and odd shapes keep the plain path under a window too
    ((1, 8192, 28, 128), _pool(jnp.int8, nkv=4), 64, 4096, False),
    # int8 pages keep the plain path; so do shapes the kernel cannot read
    ((2, 2048, 32, 128), _pool(jnp.int8), 16, None, False),
    ((2, 2048, 32, 128), _pool(jnp.bfloat16, nkv=1), 16, None, False),
    ((2, 2048, 64, 64), _pool(jnp.bfloat16, hd=64), 16, None, False),
], ids=lambda v: None)
def test_the_rule_reads_shapes_layer_kind_and_pool_dtype(q, pool, pages,
                                                         window, engages):
    assert ppa.kernel_engages(q, pool, pages, window) is engages


@pytest.mark.parametrize("pages", ["bf16", "int8"])
def test_the_entry_off_the_tpu_is_the_plain_path(monkeypatch, pages):
    """With every shape over the rule (the constant at 0), a program
    lowered for the CPU still holds no kernel and computes what the
    gather formulation does, bit for bit; int8 pools never reach the
    choice and are dequantised as they were."""
    monkeypatch.setattr(ppa, "KERNEL_SCORES_BYTES", 0)
    rng = np.random.default_rng(4)
    shape = (2, 12, PAGE, 2, 128)
    k, v = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for _ in range(2))
    if pages == "int8":
        (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
    else:
        k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
        ks = vs = jnp.ones((2, 1, 1, 1), jnp.float32)
    q = jnp.asarray(rng.standard_normal((2, 32, 4, 128)), jnp.bfloat16)
    table = jnp.asarray(rng.permutation(12)[:8].reshape(2, 4), jnp.int32)
    args = (q, k, v, ks, vs, jnp.int32(1), table,
            jnp.asarray([0, 9], jnp.int32), jnp.asarray([32, 20], jnp.int32))
    assert ppa.kernel_engages(q.shape, k, 4, None) == (pages == "bf16")
    entry = jax.jit(ppa.paged_prefill_attention)
    assert "custom_call" not in entry.lower(*args).as_text()
    np.testing.assert_array_equal(
        np.asarray(entry(*args), np.float32),
        np.asarray(ppa.paged_prefill_attention_reference(*args), np.float32))


@pytest.mark.parametrize("model,tiny", [
    (llama, llama.llama_tiny), (smallthinker, smallthinker.smallthinker_tiny)],
    ids=["llama", "smallthinker-window-8"])
def test_engine_prefills_the_same_tokens_through_the_kernel(monkeypatch,
                                                            model, tiny):
    """The paged engine's greedy tokens with the kernel in its prefill
    program (interpret mode, every layer that attends over K/V twins) are
    those of the gather formulation: a cold prompt of three pages and a
    part, a second that reuses its first two pages (a suffix behind
    ``starts`` 32), a short one; the comparison is
    tests/test_paged_decode_attention.py's (the same tokens, or a near
    tie by the model's own logits where the two roundings part). The
    second plan has two runs of three sliding layers under a window of 8
    keys, which every prompt is past: the kernel is handed the run's
    window and walks from the window's first chunk."""
    cfg = tiny()
    params = model.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(5)
    first = rng.integers(1, cfg.vocab_size, 53)
    prompts = [first, np.concatenate([first[:40],
                                      rng.integers(1, cfg.vocab_size, 9)]),
               rng.integers(1, cfg.vocab_size, 7)]
    kernel_calls = []

    def through_kernel(*args, window=None, flags=None):
        assert flags is None        # neither family picks its keys
        kernel_calls.append((args[0].shape, window))
        return ppa.paged_prefill_attention_kernel(*args, window=window,
                                                 interpret=True)

    def served(attention):
        monkeypatch.setattr(engine_programs, "paged_prefill_attention",
                            attention)
        eng = PagedLLMEngine(cfg, params, max_batch=4, max_len=128,
                             page_size=PAGE, num_pages=40)
        eng.start()
        try:
            out = []
            for p in prompts:       # in turn, so the second finds the pages
                out.append(list(eng.submit(p, max_new_tokens=12).tokens()))
            return out, eng.stats()
        finally:
            eng.stop()

    kernel, stats = served(through_kernel)
    gather, _ = served(ppa.paged_prefill_attention)
    assert kernel_calls and stats["prefix_cache"]["hit_pages"] == 2
    assert {w for _, w in kernel_calls} == {
        run.window for run in model.layer_plan(cfg)}
    assert [len(t) for t in kernel] == [12, 12, 12]
    for prompt, got, want in zip(prompts, kernel, gather):
        assert same_greedy_choice(model, cfg, params, prompt, got, want)
    assert sum(g == w for g, w in zip(kernel, gather)) >= 1

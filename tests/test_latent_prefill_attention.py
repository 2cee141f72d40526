"""The latent prefill-attention kernel (ops/latent_attention.py), in
interpret mode on the CPU: against ``latent_prefill_attention``'s plain
formulation over the same scattered pages, for a full layer that hands
its selection as flags (ties at the selection's edge among them: the set
is ``kept``'s, key for key), a full layer that selects nothing, a
sliding layer across its window's edge, a suffix behind a cached prefix,
two rows of unlike lengths and a block of padding alone; in the rows'
bf16 too; the rule's two sides; and the tiny model through the kernel.
(Its Mosaic compile at the serving cell's shapes:
tests/test_tpu_compile_kernels.py, test_tpu_compile_note.py.)"""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import dots3_note
from ray_tpu.ops import index_select
from ray_tpu.ops import latent_attention as la
from ray_tpu.ops.paged_attention import PageRow, row_pool

PAGE, TABLE, POOL, TOPK = 8, 8, 40, 12
HEADS, DN, DR, RANK, DV, INDEX_HEADS, INDEX_DIM = 4, 16, 8, 16, 16, 2, 16
# starts, valid queries (of the T a row), T, the layer's window, whether
# it has an indexer
CASES = {
    # 32 queries over 64 keys' pages: from the thirteenth on a query
    # drops keys, and two index heads give exact zeros often enough that
    # the twelfth and the thirteenth score tie
    "full-flags": ((0, 0), (32, 32), 32, None, True),
    # no indexer: every key up to the query's own
    "full-no-flags": ((0, 0), (32, 32), 32, None, False),
    # a window of 9 under blocks of 16 queries and chunks of 16 keys: a
    # block's walk starts a chunk before its own and ends with it
    "sliding-window-edge": ((0, 0), (32, 32), 32, 9, False),
    "sliding-suffix": ((21, 30), (16, 14), 16, 9, False),
    # behind cached prefixes of 24 and 17 rows, the second mid-page
    "suffix": ((24, 17), (16, 16), 16, None, True),
    "unlike-lengths": ((0, 5), (32, 11), 32, None, True),
    # row 0's second block of 16 queries is padding alone
    "padding-block": ((0, 3), (9, 32), 32, None, True),
}


@pytest.fixture
def through_the_kernel(monkeypatch):
    """Every prefill attention through the kernel in interpret mode,
    whatever the rule says of its shapes: both branches of the entry's
    choice are the kernel's formulation, in blocks of 16 queries, chunks
    of 16 keys and two heads a step."""
    monkeypatch.setattr(
        la, "latent_prefill_attention_kernel",
        partial(la.latent_prefill_attention_kernel, interpret=True))
    formulations = la._prefill_formulations.__wrapped__
    monkeypatch.setattr(
        la, "_prefill_formulations",
        lambda *statics: (formulations(*statics)[0],) * 3)
    monkeypatch.setattr(la, "latent_prefill_kernel_engages",
                        lambda *a, **k: True)
    monkeypatch.setattr(la, "_PREFILL_BLOCK_Q", 16)
    monkeypatch.setattr(la, "_PREFILL_CHUNK", 16)
    monkeypatch.setattr(la, "_PREFILL_HEADS", 2)


def _case(name, dtype=jnp.float32, seed=7):
    """Two sequences' rows (and index keys) written into scattered pages
    of layer 1 of stacked pools, up to each row's last valid query; the
    suffix's ``LatentInputs`` [2, T, ...], padded with the numbers the
    padding's tokens would have."""
    starts, slens, t, window, indexed = CASES[name]
    rng = np.random.default_rng(seed)
    total = max(starts) + t

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    index = la.IndexInputs(
        normal(2, total, INDEX_HEADS, INDEX_DIM),
        jnp.asarray(rng.standard_normal((2, total, INDEX_HEADS)),
                    jnp.float32),
        normal(2, total, INDEX_DIM), TOPK) if indexed else None
    whole = la.LatentInputs(
        normal(2, total, HEADS, DN + DR), normal(2, total, RANK + DR),
        normal(RANK, HEADS, DN + DV, scale=0.25), 0.2, index)
    rows = (PageRow("latent", RANK + DR, dtype),) + (
        (PageRow("index_key", INDEX_DIM, dtype),) if indexed else ())
    pools = tuple(row_pool(2, POOL, PAGE, row) for row in rows)
    table = jnp.asarray(
        rng.permutation(POOL)[:2 * TABLE].reshape(2, TABLE), jnp.int32)
    layer = jnp.int32(1)

    def part(lo, n):
        """Positions lo[i] to lo[i] + n of row i."""
        at = np.asarray(lo)[:, None] + np.arange(n)

        def cut(a):
            return jnp.take_along_axis(
                a, jnp.asarray(at).reshape(2, n, *[1] * (a.ndim - 2)), 1)
        return whole._replace(
            q=cut(whole.q), row=cut(whole.row),
            index=index and index._replace(
                q=cut(index.q), weights=cut(index.weights),
                key=cut(index.key)))

    # every position up to a row's last valid query holds its row; the
    # padding's positions hold nothing (zeros), as a fresh page does
    filled = [s + n for s, n in zip(starts, slens)]
    for i in range(2):
        pos = jnp.arange(filled[i])[None]
        one = jax.tree.map(lambda a: a[i:i + 1, :filled[i]]
                           if getattr(a, "ndim", 0) >= 3 else a, whole)
        pools = la.write_latent(one, pools, layer, table[i][pos // PAGE],
                                pos % PAGE)
    return dict(inputs=part(starts, t), pools=pools, layer=layer,
                table=table, starts=jnp.asarray(starts, jnp.int32),
                slens=jnp.asarray(slens, jnp.int32), window=window)


def _attend(case):
    return la.latent_prefill_attention(
        case["inputs"], case["pools"], case["layer"], case["table"],
        case["starts"], case["slens"], window=case["window"])


def _valid(case, out):
    t = out.shape[1]
    return np.asarray(out, np.float32)[
        np.arange(t)[None] < np.asarray(case["slens"])[:, None]]


@pytest.mark.parametrize("name", CASES)
def test_kernel_is_the_plain_formulation(name, request):
    """Every valid query's output is the plain formulation's over the
    same pages (float32 rows: the two differ in the order of their sums
    alone); a block of padding alone comes back as zeros."""
    case = _case(name)
    want = _attend(case)
    request.getfixturevalue("through_the_kernel")
    got = _attend(case)
    assert got.shape == want.shape == (2, CASES[name][2], HEADS, DV)
    np.testing.assert_allclose(_valid(case, got), _valid(case, want),
                               atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()
    if name == "padding-block":
        assert not np.asarray(got)[0, 16:].any()
        assert np.asarray(got)[1, 16:].any()


@pytest.mark.parametrize("name", ["full-flags", "sliding-window-edge",
                                  "suffix"])
def test_kernel_in_the_rows_own_type(name, request):
    """bf16 rows and expansions, float32 scores and softmax state, bf16
    probabilities before the weighted sum: as far from the plain
    formulation as a bf16 output's last places."""
    case = _case(name, jnp.bfloat16)
    want = _attend(case)
    request.getfixturevalue("through_the_kernel")
    got = _attend(case)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(_valid(case, got), _valid(case, want),
                               atol=0.03)


@pytest.mark.parametrize("name,blocks", [
    ("full-flags", 1), ("full-flags", 2), ("suffix", 1), ("suffix", 2),
    ("unlike-lengths", 4)])
def test_the_flags_are_kepts_set_key_for_key(name, blocks, monkeypatch):
    """What the kernel is handed: for every query, ``kept``'s set among
    the keys up to its own, the same whether the index scores go in one
    block of queries or several (in the plain formulation's groups of
    keys), ties at the selection's edge to the lower position."""
    case = _case(name)
    index, starts = case["inputs"].index, case["starts"]
    index_keys = la.gather_rows(case["pools"][1], case["layer"],
                                case["table"])
    t, keys = index.q.shape[1], index_keys.shape[1]
    monkeypatch.setattr(index_select, "SCORES_MAX_BYTES",
                        4 * 2 * INDEX_HEADS * (t // blocks) * keys)
    assert la.query_block(2, t, INDEX_HEADS, keys, None) == max(
        t // blocks, 16)
    flags = la.selection_flags((index.q, index.weights), index_keys,
                                starts, topk=TOPK)
    assert flags.shape == (2, t, keys) and flags.dtype == jnp.int8
    qpos = np.asarray(starts)[:, None] + np.arange(t)
    seen = np.arange(keys)[None, None] <= qpos[..., None]
    scores = np.asarray(la.index_scores(index.q, index.weights, index_keys))
    if name == "full-flags":
        # the case is met: a query whose twelfth and thirteenth scores tie
        ranked = np.sort(np.where(seen, scores, -np.inf), -1)
        assert (ranked[..., -TOPK] == ranked[..., -TOPK - 1]).any()
    valid = np.arange(t)[None] < np.asarray(case["slens"])[:, None]
    for b, i in zip(*np.nonzero(valid)):
        # a stable sort by falling score: ties to the lower position
        order = np.argsort(-np.where(seen[b, i], scores[b, i], -np.inf),
                           kind="stable")
        want = set(order[:min(TOPK, qpos[b, i] + 1)].tolist())
        assert set(np.nonzero(np.asarray(flags[b, i]))[0].tolist()) == want


def test_the_model_prefills_through_the_kernel(through_the_kernel):
    """The tiny model (float32; the leading dense full layer, a full
    layer, three sliding ones; a selection of 12 keys, a window of 9)
    with every layer's prefill attention in the kernel, against one query
    at a time in the absorbed form over the same rows: 48 positions in
    one page, three blocks of queries over three chunks of keys."""
    cfg = dots3_note.dots3_note_tiny()
    params = dots3_note.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 48), 0, 128)
    np.testing.assert_allclose(
        dots3_note.forward(cfg, params, tokens),
        dots3_note.forward(cfg, params, tokens, absorbed=True), atol=1e-4)


def _pool(lanes, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct((2, 2816, 128, lanes), dtype)


@pytest.mark.parametrize("q,lanes,pages,window,index_heads,engages", [
    # serve-note-gen's cold prompt: a full layer's float32 scores and its
    # indexer's would be 12.9 GB, a sliding layer's 1.6 GB
    ((1, 4096, 128, 192), 640, 32, None, 64, True),
    ((1, 4096, 64, 256), 1152, 32, 513, 0, True),
    # the check's second prompt, behind 2,048 cached tokens
    ((1, 2048, 128, 192), 640, 32, None, 64, True),
    ((1, 2048, 64, 256), 1152, 32, 513, 0, True),
    # its suffix programs (a question behind a cached transcript)
    ((1, 256, 128, 192), 640, 32, None, 64, True),
    ((1, 128, 128, 192), 640, 32, None, 64, True),
    ((1, 64, 128, 192), 640, 32, None, 64, False),
    ((1, 256, 64, 256), 1152, 32, 513, 0, False),
    ((1, 64, 64, 256), 1152, 32, 513, 0, False),
    # a table of no more than topk keys: no indexer beside the layer
    ((1, 256, 128, 192), 640, 16, None, 0, False),
], ids=["cold-full", "cold-sliding", "check-full", "check-sliding",
        "suffix-256-full", "suffix-128-full", "suffix-64-full",
        "suffix-256-sliding", "suffix-64-sliding", "under-topk"])
def test_the_rule_at_the_serving_cells_shapes(q, lanes, pages, window,
                                              index_heads, engages):
    assert la.latent_prefill_kernel_engages(
        q, _pool(lanes), pages, window, index_heads) is engages


def test_the_rule_keeps_other_rows_and_ragged_shapes_plain():
    cold = ((1, 4096, 128, 192), _pool(640), 32, None, 64)
    assert la.latent_prefill_kernel_engages(*cold)
    # float32 rows (the tiny model's), whatever their scores' bytes
    assert not la.latent_prefill_kernel_engages(
        cold[0], _pool(640, jnp.float32), *cold[2:])
    # queries no block of whole sublanes divides, keys no chunk of whole
    # lanes does, heads not by fours
    assert not la.latent_prefill_kernel_engages(
        (1, 4040, 128, 192), *cold[1:])
    assert not la.latent_prefill_kernel_engages(
        cold[0], jax.ShapeDtypeStruct((2, 64, 4000, 640), jnp.bfloat16), 1,
        None, 64)
    assert not la.latent_prefill_kernel_engages(
        (1, 4096, 126, 192), *cold[1:])
    # the tiny model's prompts
    assert not la.latent_prefill_kernel_engages(
        (2, 48, 4, 24), jax.ShapeDtypeStruct((1, 2, 48, 24), jnp.float32),
        1, None, 2)


def test_under_the_rule_the_entry_is_the_plain_formulation():
    """A program under the rule holds no choice by platform and no
    kernel: the entry calls the plain formulation directly."""
    case = _case("full-flags")
    text = jax.jit(lambda: _attend(case)).lower().as_text()
    assert la.PREFILL_KERNEL_NAME not in text
    assert "platform_index" not in text

"""The latent decode-attention kernel (ops/latent_attention.py), in
interpret mode on the CPU: against a plain float32 attention over the
slots' chosen rows and against the gathered formulation, at both row
shapes of the serving cell (640 and 1,152 lanes; few heads, small pages)
and at tables that walk in groups of one, two, four and eight pages; the
selection's set against ``lax.top_k``'s, key for key; the shape rule and
what the lowered text holds on each side of it; and the tiny model
through the kernel. The index kernel (``index_decode_scores``) the same
way: against ``gather_rows`` + ``index_scores`` + the count's mask over
ragged slots and tables of 32 and 64 pages, the selection made from
either, the entry through either, its own shape rule, and the tiny model
through both kernels. (Their Mosaic compiles at the real shapes:
tests/test_tpu_compile_kernels.py, test_tpu_compile_note.py.)"""

import math
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import dots3_note
from ray_tpu.ops import index_select
from ray_tpu.ops import latent_attention as la

PAGE, TABLE, POOL, LAYERS, TOPK = 16, 6, 64, 3, 24
# heads, latent rank, rotary numbers, lanes: a full layer's row and a
# sliding layer's, as the cell's pools hold them
SHAPES = {"full-640": (4, 512, 64, 640), "sliding-1152": (2, 1024, 64, 1152)}
# a dead slot; fewer keys than the selection keeps; a count that ends
# mid-page; one that ends with a page; the last live slot, whose index
# scores tie at the selection's edge
COUNTS = np.array([0, 19, 53, 64, 77], np.int32)
SCALE = 0.11


def _case(shape, table_pages=TABLE, seed=0):
    heads, rank, rope, lanes = SHAPES[shape]
    rng = np.random.default_rng(seed)
    pool = rng.standard_normal((LAYERS, POOL, PAGE, lanes)) * 0.4
    pool[..., rank + rope:] = 0
    q = rng.standard_normal((len(COUNTS), heads, lanes)) * 0.4
    q[..., rank + rope:] = 0
    # scattered pages, holes past each slot's reserved pages
    table = rng.permutation(POOL)[:len(COUNTS) * table_pages].reshape(
        -1, table_pages)
    for slot, n in enumerate(COUNTS):
        table[slot, -(-n // PAGE) + 1:] = -1
    scores = rng.standard_normal((len(COUNTS), table_pages * PAGE))
    scores = scores.astype(np.float32)
    # slot 4: forty keys share the score at the selection's 24th place
    scores[4, 5:45] = np.sort(scores[4, :77])[-20]
    seen = np.arange(table_pages * PAGE)[None] < COUNTS[:, None]
    return dict(
        q=jnp.asarray(q, jnp.bfloat16), pool=jnp.asarray(pool, jnp.bfloat16),
        layer=jnp.int32(2), table=jnp.asarray(table, jnp.int32),
        count=jnp.asarray(COUNTS),
        chosen=jnp.where(seen, scores, la.MASKED), rank=rank)


def _chosen_keys(chosen, topk):
    """What ``lax.top_k`` picks of each slot's seen keys, as sets."""
    values, positions = jax.lax.top_k(chosen, topk)
    return [set(np.asarray(p)[np.asarray(v) > la.MASKED].tolist())
            for v, p in zip(values, positions)]


def plain_attention(case, keys_of_slot):
    """Float32, one slot at a time: softmax over the slot's ``keys`` of
    the rows its pages hold in table order, the probabilities over the
    rows' first ``rank`` numbers."""
    q = np.asarray(case["q"], np.float32)
    pool = np.asarray(case["pool"], np.float32)[int(case["layer"])]
    table = np.asarray(case["table"])
    out = np.zeros((*q.shape[:2], case["rank"]), np.float32)
    for b, keys in enumerate(keys_of_slot):
        if not keys:
            continue
        rows = np.concatenate([pool[max(p, 0)] for p in table[b]])
        rows = rows[sorted(keys)]
        s = q[b] @ rows.T * SCALE
        w = np.exp(s - s.max(-1, keepdims=True))
        out[b] = (w / w.sum(-1, keepdims=True)) @ rows[:, :case["rank"]]
    return out


@pytest.mark.parametrize("table_pages", [5, 6, 8, 12])
@pytest.mark.parametrize("shape", SHAPES)
def test_kernel_is_plain_latent_attention(shape, table_pages):
    """Slot 0 is dead and the others are live after it (the next live
    slot's first pages are fetched while the last one's compute); layer 2
    of a stacked pool whose other layers hold other numbers; tables of 5,
    6, 8 and 12 pages, walked a page, two, eight and four at a time, so that a
    slot's last group holds pages it has and pages it has not."""
    case = _case(shape, table_pages)
    assert math.gcd(table_pages, la._GROUP) == {5: 1, 6: 2, 8: 8, 12: 4}[table_pages]
    args = (case["q"], case["pool"], case["layer"], case["table"],
            case["count"])
    flags = (case["chosen"] > la.MASKED) & la.kept(case["chosen"], TOPK)
    got = la.latent_decode_attention_kernel(
        *args, flags, rank=case["rank"], scale=SCALE, interpret=True)
    assert got.shape == (*case["q"].shape[:2], case["rank"])
    assert got.dtype == case["q"].dtype
    got = np.asarray(got, np.float32)
    want = plain_attention(case, _chosen_keys(case["chosen"], TOPK))
    live = COUNTS > 0
    # bf16 probabilities and a bf16 result: 2**-8 of values of order 1
    assert np.abs(got - want)[live].max() < 2e-2
    assert np.isfinite(got).all() and not got[~live].any()
    # and the formulation every other platform runs is the same function
    plain = np.asarray(la._gathered(
        *args, case["chosen"], rank=case["rank"], scale=SCALE, topk=TOPK,
        window=None), np.float32)
    assert np.abs(plain - want)[live].max() < 2e-2
    assert np.abs(plain - got)[live].max() < 2e-2


TIES = {
    "none": lambda s: s,
    "at-the-edge": lambda s: np.where(
        (np.arange(s.size) % 3 == 0), np.sort(s)[-TOPK], s),
    "all-equal": lambda s: np.zeros_like(s),
    "two-values": lambda s: np.where(s > 0, 1.0, 0.0).astype(np.float32),
}


@pytest.mark.parametrize("count", [7, TOPK, TOPK + 1, 90, TABLE * PAGE])
@pytest.mark.parametrize("ties", TIES)
def test_the_mask_is_top_ks_set_key_for_key(ties, count):
    """``kept`` over a slot's seen keys is the set ``select_keys``'s
    ``lax.top_k`` returns by position (ties to the lower position), with
    scores that tie across the ``topk``-th place; a slot that sees no
    more than ``topk`` keys keeps all of them and no unseen one."""
    rng = np.random.default_rng(count)
    scores = TIES[ties](rng.standard_normal(TABLE * PAGE).astype(np.float32))
    seen = np.arange(TABLE * PAGE) < count
    chosen = jnp.where(seen, scores, la.MASKED)[None]
    flags = np.asarray((chosen > la.MASKED) & la.kept(chosen, TOPK))[0]
    assert set(np.nonzero(flags)[0].tolist()) == _chosen_keys(chosen, TOPK)[0]
    assert flags.sum() == min(count, TOPK) and not flags[count:].any()


# The serving cells' widths: a full table of 64 pages of 128 keys and a
# prefill's third key group, which is no power of two; 2,048 keys kept.
WIDE_TOPK = 2048


def _wide(kind, width, rng):
    """Six rows of index scores [2, 3, width], as a prefill hands them,
    that put ``kind`` across the ``WIDE_TOPK``-th place."""
    x = rng.standard_normal((6, width)).astype(np.float32)
    at = np.arange(width)[None]
    if kind == "negative":
        # a few hundred values, none above zero: ties at every place
        x = -np.abs(np.round(x * 64) / 64) - 0.25
    elif kind == "quantised":
        x = np.round(x * 8) / 8
    elif kind == "zeros-of-both-signs":
        # 1,000-1,500 scores above zero and as many below, zeros of both
        # signs between them in the rows' own mixes (the first row's
        # count runs out among the ``+0.0``, the last's among the ``-0.0``)
        plus = np.linspace(0.9, 0.1, 6)[:, None]
        zero = np.where(rng.random((6, width)) < plus, 0.0, -0.0)
        x = np.where(np.abs(x) > 1.1, x, zero).astype(np.float32)
    elif kind == "masked-alone":
        x[:] = la.MASKED
    elif kind == "topk-seen":
        # exactly ``topk`` seen keys, one fewer, one more, half, none, all
        seen = np.array([WIDE_TOPK, WIDE_TOPK - 1, WIDE_TOPK + 1,
                         WIDE_TOPK // 2, 0, width])[:, None]
        x = np.where(at < seen, x, la.MASKED)
    else:
        assert kind == "distinct"
    return jnp.asarray(x.astype(np.float32).reshape(2, 3, width))


def _top_ks_mask(chosen, topk):
    """The positions ``lax.top_k`` returns, as a mask."""
    positions = np.asarray(jax.lax.top_k(chosen, topk)[1])
    mask = np.zeros(chosen.shape, bool)
    np.put_along_axis(mask, positions, True, axis=-1)
    return mask


@pytest.mark.parametrize("width", [8192, 6144])
@pytest.mark.parametrize("kind", [
    "distinct", "negative", "quantised", "zeros-of-both-signs",
    "masked-alone", "topk-seen"])
def test_the_search_finds_top_ks_set_at_the_cells_widths(kind, width):
    """``kept`` over rows [n, T, S] at the serving cells' widths IS the
    set ``lax.top_k`` returns, position for position (the keys it takes
    of a row with fewer seen keys than ``topk`` too: the ``MASKED`` ones
    of lowest position), whatever lies across the ``topk``-th place."""
    chosen = _wide(kind, width, np.random.default_rng(width + len(kind)))
    got = np.asarray(la.kept(chosen, WIDE_TOPK))
    assert got.shape == chosen.shape and got.dtype == bool
    assert np.array_equal(got, _top_ks_mask(chosen, WIDE_TOPK))
    assert (got.sum(-1) == WIDE_TOPK).all()


@pytest.mark.parametrize("shape,topk", [
    ((72, 8192), 2048), ((5, 384), 100), ((72, 128), 1), ((16, 256), 255),
    ((8, 256), 256), ((3, 7, 50), 13), ((1, 33), 32)],
    ids=lambda x: "x".join(map(str, x)) if isinstance(x, tuple) else str(x))
def test_the_search_is_top_ks_set_whatever_the_shape(shape, topk):
    """Rows that are no whole lanes wide, one key kept, all but one, all;
    scores with ties and zeros of both signs."""
    rng = np.random.default_rng(sum(shape))
    x = np.round(rng.standard_normal(shape) * 4) / 4
    x = jnp.asarray(np.where(rng.random(shape) < 0.2, -0.0, x), jnp.float32)
    got = np.asarray(la.kept(x, topk))
    assert np.array_equal(got, _top_ks_mask(x, topk))
    assert (got.sum(-1) == topk).all()


def test_no_row_is_sorted_for_the_selection():
    """``kept``'s lowered text holds no ``top_k`` and no ``sort``, for
    the TPU or the CPU: the passes of a search alone."""
    scores = jax.ShapeDtypeStruct((32, 8192), jnp.float32)
    for platform in ("tpu", "cpu"):
        text = jax.jit(partial(la.kept, topk=2048)).trace(scores).lower(
            lowering_platforms=(platform,)).as_text()
        assert "top_k" not in text and "sort" not in text
        assert "stablehlo.while" in text and "custom_call" not in text


def _lowered(table_pages, platform, page=128, topk=256):
    """The text of one decode step's attention of a layer with an
    indexer over a table of ``table_pages`` pages, lowered for
    ``platform``."""
    heads, rank, rope, lanes = 4, 128, 64, 256
    slots, pages = 2, 8
    inputs = la.LatentInputs(
        jnp.zeros((slots, 1, heads, 32 + rope), jnp.bfloat16),
        jnp.zeros((slots, 1, rank + rope), jnp.bfloat16),
        jnp.zeros((rank, heads, 32 + 16), jnp.bfloat16), 0.1,
        la.IndexInputs(jnp.zeros((slots, 1, 2, 128), jnp.bfloat16),
                       jnp.zeros((slots, 1, 2), jnp.float32),
                       jnp.zeros((slots, 1, 128), jnp.bfloat16), topk))
    pools = (jnp.zeros((1, pages, page, lanes), jnp.bfloat16),
             jnp.zeros((1, pages, page, 128), jnp.bfloat16))
    fn = jax.jit(partial(la.latent_decode_attention, inputs))
    return fn.trace(pools, jnp.int32(0),
                    jnp.zeros((slots, table_pages), jnp.int32),
                    jnp.zeros((slots,), jnp.int32)).lower(
        lowering_platforms=(platform,)).as_text()


@pytest.mark.parametrize("table_pages,engages", [
    (2, False),      # 256 keys: no more than topk, nothing is selected
    (4, True),       # 2 x topk
    (16, True),      # 8 x topk: the last table that reads in place
    (32, False),     # 16 x topk: the gather by position
], ids=["1x", "2x", "8x", "16x"])
def test_the_shape_rule_says_which_formulation_a_tpu_program_holds(
        table_pages, engages):
    """Lowered for a TPU, a layer with an indexer holds the kernel where
    its table holds more than ``topk`` keys and no more than
    ``GATHER_PAST`` times as many, and the gather of the chosen rows (and
    no kernel) on the other side; lowered for the CPU it holds the gather
    on both."""
    assert la.latent_kernel_engages(128, table_pages, 256) is engages
    assert la.GATHER_PAST == 8
    text = _lowered(table_pages, "tpu")
    # (past 8 x topk the index kernel still scores the keys in place)
    assert ("tpu_custom_call" in text) is (table_pages * 128 > 256)
    assert (la.KERNEL_NAME in text) is engages
    # the rows copied out of the pool: the 256 chosen ones (or, at 1x,
    # the table's 256)
    gathers = "stablehlo.gather" in text and "tensor<2x256x256xbf16>" in text
    assert gathers is (not engages)
    assert "tpu_custom_call" not in _lowered(table_pages, "cpu")


def test_small_pages_and_layers_without_an_indexer_stay_gathered():
    assert not la.latent_kernel_engages(16, 64, 256)     # flags of 16 lanes
    assert not la.latent_kernel_engages(128, 8, None)    # no selection


@pytest.fixture
def through_the_kernel(monkeypatch):
    """Every decode-form attention of a layer that selects through the
    kernel in interpret mode, whatever the rule says of its page size:
    both branches of the entry's choice are the in-place formulation."""
    monkeypatch.setattr(
        la, "latent_decode_attention_kernel",
        partial(la.latent_decode_attention_kernel, interpret=True))
    formulations = la._formulations.__wrapped__

    def in_place_where_it_selects(rank, scale, topk, window):
        in_place, gathered = formulations(rank, scale, topk, window)
        return in_place, gathered if topk is None else in_place

    monkeypatch.setattr(la, "_formulations", in_place_where_it_selects)
    monkeypatch.setattr(la, "latent_kernel_engages",
                        lambda page, table_pages, topk: topk is not None)


def test_the_model_decodes_through_the_kernel(through_the_kernel):
    """The tiny model (float32; the leading dense full layer, a full
    layer, three sliding ones; a selection of 12 keys, a window of 9) one
    query at a time with the full layers' attention in the kernel,
    against the expanded form over the same rows: 40 positions, so the
    selection drops keys from the thirteenth on, in one page of the whole
    prompt."""
    cfg = dots3_note.dots3_note_tiny()
    params = dots3_note.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 40), 0, 128)
    np.testing.assert_allclose(
        dots3_note.forward(cfg, params, tokens, absorbed=True),
        dots3_note.forward(cfg, params, tokens), atol=1e-4)


# ---------------------------------------------------------------------------
# The index kernel
# ---------------------------------------------------------------------------

INDEX_HEADS, INDEX_DIM = 4, 128
# slots, by what their count is: a dead slot; fewer keys than a page;
# a count that ends mid-page; one on a page's boundary; one mid-table;
# the last, set by the table: its every page
INDEX_SLOTS = ("dead", "one-page", "mid-page", "boundary", "ragged", "full")


def _index_case(table_pages, seed=0):
    """Six slots over scattered pages of a stacked index pool: slot i's
    pages end before the table does (holes, -1, from one page past its
    last) but for the last slot, which fills the table; slot 2 has a
    hole INSIDE its count too (page 0 is read there, as ``gather_rows``
    reads it)."""
    rng = np.random.default_rng(seed)
    keys = table_pages * PAGE
    counts = np.array([0, 9, 2 * PAGE + 5, 4 * PAGE, keys // 2 + 3, keys],
                      np.int32)
    pool_pages = len(counts) * table_pages + 3
    pool = rng.standard_normal((LAYERS, pool_pages, PAGE, INDEX_DIM))
    q = rng.standard_normal((len(counts), 1, INDEX_HEADS, INDEX_DIM))
    weights = rng.standard_normal((len(counts), 1, INDEX_HEADS))
    table = rng.permutation(pool_pages)[:counts.size * table_pages].reshape(
        -1, table_pages)
    for slot, n in enumerate(counts):
        table[slot, -(-n // PAGE) + 1:] = -1
    table[2, 1] = -1
    return dict(
        q=jnp.asarray(q, jnp.bfloat16),
        weights=jnp.asarray(weights, jnp.float32),
        pool=jnp.asarray(pool, jnp.bfloat16), layer=jnp.int32(1),
        table=jnp.asarray(table, jnp.int32), count=jnp.asarray(counts))


def _both_scores(case):
    """(the index kernel's, the plain formulation's) [B, PB x page]."""
    args = (case["pool"], case["layer"], case["table"], case["count"])
    got = index_select.index_decode_scores_kernel(
        case["q"][:, 0], case["weights"][:, 0], *args, interpret=True)
    want = index_select._scored_gathered(case["q"], case["weights"], *args)
    return np.asarray(got), np.asarray(want)


@pytest.mark.parametrize("slot", range(len(INDEX_SLOTS)), ids=INDEX_SLOTS)
@pytest.mark.parametrize("table_pages", [6, 12, 32, 64])
def test_index_kernel_is_plain_index_scores(table_pages, slot):
    """The kernel's scores of one slot against ``gather_rows`` +
    ``index_scores`` + the count's mask: equal within the order of a
    float32 sum at every position under the slot's count (a hole's too)
    and the mask's own value, exactly, from the count on, in the pages
    the walk fetched and in those it never reached; layer 1 of a stacked
    pool; tables walked 2, 4 and 16 pages at a step (one group a slot at
    6 x 16 keys... several at 64)."""
    case = _index_case(table_pages)
    assert math.gcd(table_pages, index_select._INDEX_GROUP) == {
        6: 2, 12: 4, 32: 16, 64: 16}[table_pages]
    got, want = _both_scores(case)
    assert got.shape == want.shape == (len(INDEX_SLOTS), table_pages * PAGE)
    assert got.dtype == np.float32
    count = int(case["count"][slot])
    np.testing.assert_allclose(got[slot, :count], want[slot, :count],
                               rtol=1e-5, atol=1e-5)
    assert (got[slot, count:] == la.MASKED).all()
    assert (want[slot, count:] == la.MASKED).all()
    assert (got[slot, :count] > la.MASKED).all()


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("table_pages", [32, 64])
def test_the_selection_is_the_same_from_either_scores(table_pages, seed):
    """``kept`` over the kernel's scores and over the plain ones is one
    mask, key for key, on seeded inputs (a selection of 24 of up to 1,024
    keys a slot)."""
    got, want = _both_scores(_index_case(table_pages, seed))
    np.testing.assert_array_equal(
        np.asarray(la.kept(jnp.asarray(got), TOPK)),
        np.asarray(la.kept(jnp.asarray(want), TOPK)))


def _score_in_place(patch):
    """Every decode step's index scores of a layer that selects through
    the index kernel in interpret mode, whatever the rule says of its
    shapes: both branches of the entry's choice are the kernel's
    formulation."""
    patch.setattr(
        index_select, "index_decode_scores_kernel",
        partial(index_select.index_decode_scores_kernel, interpret=True))
    patch.setattr(index_select, "_scored_gathered",
                  index_select._scored_in_place)
    patch.setattr(index_select, "index_kernel_engages",
                  lambda page, table_pages, topk, width: True)


@pytest.fixture
def scored_in_place(monkeypatch):
    _score_in_place(monkeypatch)


def _entry(case, heads=4, rank=128, rope=64, nope=32, v=16):
    """``latent_decode_attention`` of ``_index_case``'s slots, their
    latent rows in a pool beside the index keys'."""
    rng = np.random.default_rng(7)
    slots = case["count"].shape[0]

    def bf16(*dims):
        return jnp.asarray(rng.standard_normal(dims) * 0.4, jnp.bfloat16)

    inputs = la.LatentInputs(
        bf16(slots, 1, heads, nope + rope), bf16(slots, 1, rank + rope),
        bf16(rank, heads, nope + v), SCALE,
        la.IndexInputs(case["q"], case["weights"],
                       bf16(slots, 1, INDEX_DIM), TOPK))
    pools = (bf16(*case["pool"].shape[:3], 256), case["pool"])
    return jax.jit(partial(la.latent_decode_attention, inputs))(
        pools, case["layer"], case["table"], case["count"] - 1,
        active=case["count"] > 0)


@pytest.mark.parametrize("table_pages", [6, 32, 64])
def test_the_entry_gives_the_same_through_either_formulation(
        table_pages, monkeypatch):
    """``latent_decode_attention`` [B, H, dv] with its index scores from
    the kernel against the same call with them gathered: the selection
    is the same set, so the live slots' results are the same numbers."""
    case = _index_case(table_pages)
    plain = np.asarray(_entry(case), np.float32)
    with monkeypatch.context() as patch:
        _score_in_place(patch)
        got = np.asarray(_entry(case), np.float32)
    assert got.shape == (len(INDEX_SLOTS), 4, 16)
    live = np.asarray(case["count"]) > 0
    np.testing.assert_array_equal(got[live], plain[live])


@pytest.mark.parametrize("page,table_pages,topk,width,engages", [
    (128, 2, 256, 128, False),      # no more than topk keys: none dropped
    (128, 4, 256, 128, True),
    (128, 32, 256, 128, True),      # past GATHER_PAST too: it still scores
    (128, 64, 2048, 128, True),     # the serving cell's
    (16, 64, 256, 128, False),      # a page that is not whole lanes
    (128, 64, 256, 64, False),      # a key that is not
    (128, 64, 256, 256, True),
    (128, 64, None, 128, False),    # no indexer
], ids=["1x", "2x", "16x", "cell", "page-16", "width-64", "width-256",
        "no-indexer"])
def test_the_index_kernels_shape_rule(page, table_pages, topk, width,
                                      engages):
    assert index_select.index_kernel_engages(page, table_pages, topk, width) is engages


@pytest.mark.parametrize("table_pages", [2, 4, 16, 32])
def test_a_tpu_program_holds_the_index_kernel_where_its_layer_selects(
        table_pages):
    """Lowered for a TPU, a layer with an indexer scores its keys in the
    index kernel wherever its table holds more than ``topk`` keys, on
    both sides of ``GATHER_PAST``, and copies no table's worth of index
    keys out of the pool; lowered for the CPU it holds the gather and no
    kernel, as it did."""
    engages = table_pages * 128 > 256
    assert index_select.index_kernel_engages(128, table_pages, 256, 128) is engages
    text = _lowered(table_pages, "tpu")
    assert (index_select.INDEX_KERNEL_NAME in text) is engages
    gathered_keys = f"tensor<2x{table_pages}x128x128xbf16>"
    cpu = _lowered(table_pages, "cpu")
    assert index_select.INDEX_KERNEL_NAME not in cpu and "tpu_custom_call" not in cpu
    if engages:
        assert gathered_keys not in text and gathered_keys in cpu


def test_the_model_decodes_through_both_kernels(through_the_kernel,
                                                scored_in_place):
    """The tiny model one query at a time with the full layers' index
    scores from the index kernel and their attention in the latent
    kernel, against the expanded form over the same rows."""
    cfg = dots3_note.dots3_note_tiny()
    params = dots3_note.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 40), 0, 128)
    np.testing.assert_allclose(
        dots3_note.forward(cfg, params, tokens, absorbed=True),
        dots3_note.forward(cfg, params, tokens), atol=1e-4)
